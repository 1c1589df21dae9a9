"""The system under test, built from a configuration through its own
front door: the configuration's rows go in through the program's
dataset registry, and ``repro.api.build`` makes the Session that the
cell drives."""
from __future__ import annotations

import jax

from bench import data


def dataset_name(config: dict) -> str:
    return f"bench.{config['name']}"


def register_data(config: dict, seed: int):
    """Make the configuration's rows from the seed and register them
    with the program as a dataset; returns the four arrays."""
    from repro.data.registry import register_dataset
    arrays = data.make(config["dataset"], seed)
    model = config["model"]

    def make(n=None, seed=None, test_frac=0.2):
        return arrays
    register_dataset(dataset_name(config), make=make,
                     n_classes=model["n_classes"], arch=model["arch"],
                     partition=config["federation"]["partition"],
                     overwrite=True)
    return arrays


def session(config: dict, seed: int, *, rounds: int, eval_every: int):
    """A Session of the configuration's federation with seed ``seed``
    (data already registered)."""
    from repro.api import ExperimentSpec, build
    fed, trn = config["federation"], config["training"]
    spec = ExperimentSpec(
        dataset=dataset_name(config), mode=fed["mode"],
        n_clients=fed["n_clients"], seeds=(seed,), rounds=rounds,
        epochs=trn["epochs"], batch_size=trn["batch_size"], lr=trn["lr"],
        exchange_at=fed["exchange_at"], fedavg=fed["fedavg"],
        schedule=fed["schedule"], first_layer=fed["first_layer"],
        n_samples=config["dataset"]["rows"], eval_every=eval_every)
    sess = build(spec)
    check_model(config, sess.federation)
    return sess


def check_model(config: dict, fed):
    """Refuse a federation whose model or first-layer lane is not what
    the configuration states."""
    m = config["model"]
    got = {"in_features": fed.model.in_features,
           "hidden": fed.model.hidden, "n_hidden": fed.model.n_hidden,
           "n_classes": fed.model.n_classes}
    want = {k: m[k] for k in got}
    if got != want:
        raise SystemExit(f"model {m['arch']!r} is {got}, the configuration "
                         f"states {want}")
    lane = fed.pcfg.first_layer         # "auto" is resolved by the spec
    want_lane = config["federation"]["first_layer_lane"]
    if want_lane is not None and lane != want_lane:
        raise SystemExit(f"first layer runs {lane!r} on "
                         f"{jax.default_backend()}, the configuration "
                         f"states {want_lane!r}")
