"""The system under test, built from a configuration through its own
front door: ``repro.api.build`` makes the Session that the cell drives
from the dataset the configuration's model family registered
(``bench/families/<family>.py``, ``session``)."""
from __future__ import annotations

import jax


def dataset_name(config: dict) -> str:
    return f"bench.{config['name']}"


def build(config: dict, seed: int, *, rounds: int, eval_every: int):
    """A Session of the configuration's federation with seed ``seed``,
    on its registered dataset; refuses a first-layer lane that is not
    the configuration's."""
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
    lane = sess.federation.pcfg.first_layer  # "auto" is resolved by the spec
    want_lane = fed["first_layer_lane"]
    if want_lane is not None and lane != want_lane:
        raise SystemExit(f"first layer runs {lane!r} on "
                         f"{jax.default_backend()}, the configuration "
                         f"states {want_lane!r}")
    return sess
