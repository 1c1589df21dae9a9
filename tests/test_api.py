"""The repro.api front door: spec validation against the registries,
process-stable hashing, jit-cache reuse, and the parity bar -- spec
-> Session runs reproduce every legacy entry point bit-for-bit
(DeVertiFL.train in all mode x first_layer x padding lanes, run_cell,
run_grid, SplitNN), plus checkpoint/resume and the train_federation
deprecation shim."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (ExperimentSpec, RunResult, build, dataset_names,
                       first_layer_names, mode_names, register_dataset,
                       register_mode, run_grid, spec_grid)
from repro.core.baselines import SplitNN, SplitNNConfig
from repro.core.protocol import (DeVertiFL, ProtocolConfig,
                                 init_padded_params, train_federation)
from repro.core.sweep import SweepConfig, run_cell
from repro.core.sweep import run_grid as sweep_run_grid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(dataset="titanic", n_clients=3, rounds=2, epochs=1)


# ---------------------------------------------------------------------------
# eager validation with actionable errors
# ---------------------------------------------------------------------------
@pytest.mark.fast
def test_unknown_names_raise_with_registered_options():
    with pytest.raises(ValueError) as e:
        ExperimentSpec(dataset="cifar")
    for name in dataset_names():
        assert name in str(e.value)
    with pytest.raises(ValueError) as e:
        ExperimentSpec(mode="fedsgd")
    for name in mode_names():
        assert name in str(e.value)
    with pytest.raises(ValueError) as e:
        ExperimentSpec(first_layer="dense")
    for name in first_layer_names():
        assert name in str(e.value)


@pytest.mark.fast
def test_spec_validation_is_eager_and_actionable():
    for kw, frag in [
        (dict(engine="jit"), "engine"),
        (dict(n_clients=0), "n_clients"),
        (dict(max_clients=2, n_clients=5), "max_clients"),
        (dict(exchange_at=7), "exchange_at"),
        (dict(checkpoint_every=2), "checkpoint_dir"),
        (dict(seeds=(0, 1), engine="python"), "scan"),
    ]:
        with pytest.raises(ValueError, match=frag):
            ExperimentSpec(dataset="titanic", **kw)
    # run(key=) is refused when a checkpoint would record the wrong
    # key stream for resume()
    with pytest.raises(ValueError, match="key="):
        build(ExperimentSpec(dataset="titanic", checkpoint_dir="/tmp/c",
                             checkpoint_every=1)).run(
            key=jax.random.PRNGKey(9))
    for kw, frag in [
        (dict(seeds=(0, 1), max_clients=8), "max_clients"),
        (dict(seeds=()), "seeds"),
        (dict(shard=True), "shard"),
        (dict(eval_every=-1), "eval_every"),
    ]:
        with pytest.raises(ValueError, match=frag):
            ExperimentSpec(dataset="titanic", **kw)


@pytest.mark.fast
def test_spec_normalization_and_replace():
    # ints and lists coerce to seed tuples (hashability + UX)
    assert ExperimentSpec(seeds=4).seeds == (4,)
    assert ExperimentSpec(seeds=[0, 1]).seeds == (0, 1)
    spec = ExperimentSpec(dataset="titanic")
    assert spec.replace(n_clients=5).n_clients == 5
    with pytest.raises(ValueError):        # replace re-validates
        spec.replace(n_clients=-1)
    # frozen + hashable
    assert hash(spec) == hash(ExperimentSpec(dataset="titanic"))
    with pytest.raises(Exception):
        spec.rounds = 3


# ---------------------------------------------------------------------------
# hashing: process-stable, observation-knob-blind, jit-cache-aligned
# ---------------------------------------------------------------------------
@pytest.mark.fast
def test_spec_hash_stable_across_processes():
    spec = ExperimentSpec(dataset="titanic", n_clients=4, rounds=7,
                          seeds=(0, 1), first_layer="slice")
    code = ("from repro.api import ExperimentSpec;"
            "print(ExperimentSpec(dataset='titanic', n_clients=4,"
            " rounds=7, seeds=(0, 1), first_layer='slice').spec_hash)")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               PYTHONHASHSEED="12345")   # prove hash() salting is moot
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=240)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == spec.spec_hash


@pytest.mark.fast
def test_auto_first_layer_canonicalizes_at_construction():
    """'auto' resolves per backend at spec construction, so the spec
    (and spec_hash) records the lane that actually runs -- two
    backends' auto lanes are allclose, not bitwise, and must not
    share one hash."""
    from repro.core.protocol import auto_first_layer
    spec = ExperimentSpec(dataset="titanic", first_layer="auto")
    assert spec.first_layer == auto_first_layer() != "auto"
    assert spec.spec_hash == ExperimentSpec(
        dataset="titanic", first_layer=auto_first_layer()).spec_hash


@pytest.mark.fast
def test_mode_aliases_canonicalize():
    """Aliases name the same experiment, so they must not fork the
    spec (or its hash): backward_exchange IS verticomb."""
    a = ExperimentSpec(dataset="titanic", mode="backward_exchange")
    b = ExperimentSpec(dataset="titanic", mode="verticomb")
    assert a.mode == "verticomb"
    assert a == b and a.spec_hash == b.spec_hash


@pytest.mark.fast
def test_spec_hash_ignores_observation_knobs():
    spec = ExperimentSpec(dataset="titanic")
    assert spec.spec_hash == spec.replace(
        eval_every=0, checkpoint_dir="/tmp/x", checkpoint_every=0,
        shard=False).spec_hash
    # every result-determining field forks the hash
    assert spec.spec_hash != spec.replace(first_layer="masked").spec_hash
    assert spec.spec_hash != spec.replace(seeds=(1,)).spec_hash


@pytest.mark.fast
def test_equal_specs_share_the_jit_cache():
    """ExperimentSpec is a leafless pytree whose treedef carries the
    spec: equal specs hit the trace cache, different specs retrace."""
    traces = []

    @jax.jit
    def f(spec, x):
        traces.append(1)
        return x * spec.n_clients

    x = jnp.arange(3.0)
    f(ExperimentSpec(dataset="titanic", n_clients=3), x)
    f(ExperimentSpec(dataset="titanic", n_clients=3), x)
    assert len(traces) == 1
    f(ExperimentSpec(dataset="titanic", n_clients=5), x)
    assert len(traces) == 2


# ---------------------------------------------------------------------------
# registries are extensible
# ---------------------------------------------------------------------------
def test_register_custom_dataset_runs_everywhere():
    def loader(n=600, seed=0):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, 9)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        return x, y

    if "toy9" not in dataset_names():
        register_dataset("toy9", loader, n_classes=2,
                         arch="paper-mlp-titanic", partition="random")
    assert "toy9" in dataset_names()
    rr = build(ExperimentSpec(**{**TINY, "dataset": "toy9"})).run()
    assert 0.0 <= rr.metrics["f1"] <= 1.0
    # and through the sweep engine (multi-seed cell)
    rr2 = build(ExperimentSpec(dataset="toy9", n_clients=2, rounds=1,
                               epochs=1, seeds=(0, 1))).run()
    assert len(rr2.metrics["f1_per_seed"]) == 2
    # the registered name now appears in unknown-name errors
    with pytest.raises(ValueError, match="toy9"):
        ExperimentSpec(dataset="nope")


@pytest.mark.fast
def test_register_custom_mode():
    class EchoRunner:
        def __init__(self, spec):
            self.spec = spec

        def run(self):
            return ({"f1": 1.0, "acc": 1.0}, [], None, {"wall_s": 0.0})

    if "echo" not in mode_names():
        register_mode("echo", lambda spec: EchoRunner(spec))
    rr = build(ExperimentSpec(dataset="titanic", mode="echo")).run()
    assert rr.metrics == {"f1": 1.0, "acc": 1.0}
    assert rr.schema_version == 5


# ---------------------------------------------------------------------------
# parity: spec-driven == legacy, bit for bit
# ---------------------------------------------------------------------------
def _legacy_traj(pcfg):
    r = DeVertiFL(pcfg).train()
    return (np.concatenate([h["round_losses"] for h in r["history"]]),
            np.array([h["f1"] for h in r["history"]]), r["final"])


@pytest.mark.parametrize("mode", ["devertifl", "non_federated",
                                  "verticomb"])
@pytest.mark.parametrize("fl", ["masked", "slice", "pallas"])
@pytest.mark.parametrize("padded", [False, True])
def test_session_reproduces_legacy_bitwise(mode, fl, padded):
    """build(spec).run() == DeVertiFL(ProtocolConfig(...)).train() for
    every mode x first_layer x {padded, unpadded} lane: loss
    trajectories, per-round F1, and final metrics all exactly equal."""
    max_clients = 6 if padded else None
    pcfg = ProtocolConfig(mode=mode, seed=0, first_layer=fl,
                          max_clients=max_clients, **TINY)
    losses, f1s, final = _legacy_traj(pcfg)
    rr = build(ExperimentSpec(mode=mode, seeds=(0,), first_layer=fl,
                              max_clients=max_clients, **TINY)).run()
    np.testing.assert_array_equal(
        np.concatenate([h["round_losses"] for h in rr.history]), losses)
    np.testing.assert_array_equal(
        np.array([h["f1"] for h in rr.history]), f1s)
    assert rr.metrics == final


@pytest.mark.parametrize("obs", ["none", "basic"])
def test_repeated_session_runs_share_one_set_up_program(obs):
    """Every Session.run sets up through the one compiled program: it
    traces once, each call's set-up sits in its own ``init`` span, and
    the round consuming its outputs by donation leaves later calls
    bitwise equal to the first."""
    sess = build(ExperimentSpec(seeds=(0,), obs=obs, **TINY))
    runs = [sess.run() for _ in range(3)]
    assert sess.federation.init_traces == 1
    if sess.tracer.active:
        assert [r["name"] for r in sess.tracer.records].count("init") == 3
    for rr in runs[1:]:
        for a, b in zip(jax.tree.leaves(runs[0].params),
                        jax.tree.leaves(rr.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.concatenate([h["round_losses"] for h in rr.history]),
            np.concatenate([h["round_losses"]
                            for h in runs[0].history]))


@pytest.mark.parametrize("max_clients", [None, 6])
def test_init_params_equals_set_up_program(max_clients):
    """fed.init_params(init_key) is the set-up program's params bit for
    bit, and its loop key is train_keys' (resume and the call share
    one initialisation)."""
    from repro.core.protocol import train_keys
    fed = DeVertiFL(ProtocolConfig(seed=0, max_clients=max_clients,
                                   **TINY))
    key = jax.random.PRNGKey(3)
    loop_key, params, opt_state, step_idx = fed._init(key)
    init_key, want_loop = train_keys(key)
    np.testing.assert_array_equal(np.asarray(loop_key),
                                  np.asarray(want_loop))
    for a, b in zip(jax.tree.leaves(fed.init_params(init_key)),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for m in jax.tree.leaves(opt_state):
        assert not np.asarray(m).any()
    assert int(step_idx) == 0


def test_session_python_engine_matches_legacy():
    pcfg = ProtocolConfig(engine="python", seed=1, **TINY)
    _, _, final = _legacy_traj(pcfg)
    rr = build(ExperimentSpec(engine="python", seeds=(1,), **TINY)).run()
    assert rr.metrics == final


def test_multi_seed_session_matches_run_cell():
    seeds = (0, 1)
    rr = build(ExperimentSpec(seeds=seeds, **TINY)).run()
    cell = run_cell("titanic", "devertifl", TINY["n_clients"],
                    SweepConfig(seeds=seeds, rounds=TINY["rounds"],
                                epochs=TINY["epochs"]))
    assert rr.metrics["f1"] == cell["f1_mean"]
    assert rr.metrics["f1_per_seed"] == cell["f1_per_seed"]
    assert rr.metrics["acc_per_seed"] == cell["acc_per_seed"]
    assert rr.metrics["final_loss_mean"] == cell["final_loss_mean"]


def test_run_padded_cells_accepts_alias_mode_argument():
    """Spec grids canonicalize mode aliases; the mode *argument* must
    resolve through the registry too, so the alias doesn't falsely
    mismatch its own canonical name."""
    from repro.core.sweep import run_padded_cells
    specs = spec_grid(datasets=("titanic",),
                      modes=("backward_exchange",), client_counts=(2,),
                      seeds=(0,), rounds=1, epochs=1)
    out = run_padded_cells("titanic", "backward_exchange", specs)
    assert set(out["cells"]) == {2}


def test_spec_grid_matches_legacy_run_grid():
    """api.run_grid over a spec grid == sweep.run_grid over the
    equivalent SweepConfig (PR 3's padded engine), cell for cell."""
    kw = dict(datasets=("titanic",),
              modes=("devertifl", "non_federated"),
              client_counts=(2, 3), seeds=(0,))
    specs = spec_grid(rounds=1, epochs=1, **kw)
    assert len(specs) == 4
    g_api = run_grid(specs)
    g_old = sweep_run_grid(SweepConfig(rounds=1, epochs=1, **kw))
    assert set(g_api["cells"]) == set(g_old["cells"])
    for k, old in g_old["cells"].items():
        new = dict(g_api["cells"][k])
        assert new.pop("spec_hash")
        for kk, v in old.items():
            if kk in ("wall_s", "steps_per_sec"):
                continue            # timings are not deterministic
            assert new[kk] == v, (k, kk)
    assert g_api["compare"] == g_old["compare"]


def test_splitnn_session_matches_baseline():
    spec = ExperimentSpec(dataset="bank", mode="splitnn", n_clients=2,
                          rounds=1, epochs=2, n_samples=1500)
    rr = build(spec).run()
    legacy = SplitNN(SplitNNConfig(dataset="bank", n_clients=2,
                                   rounds=1, epochs=2,
                                   n_samples=1500)).train()
    assert rr.metrics == legacy
    # params are kept so predict() works
    assert rr.params is not None


# ---------------------------------------------------------------------------
# the train_federation deprecation shim
# ---------------------------------------------------------------------------
def test_train_federation_shim_warns_and_matches_legacy():
    kw = dict(seed=2, **TINY)
    with pytest.warns(DeprecationWarning, match="ExperimentSpec"):
        out = train_federation(**kw)
    legacy = DeVertiFL(ProtocolConfig(**kw)).train()
    assert out["final"] == legacy["final"]
    np.testing.assert_array_equal(
        np.concatenate([h["round_losses"] for h in out["history"]]),
        np.concatenate([h["round_losses"] for h in legacy["history"]]))
    for leaf_a, leaf_b in zip(jax.tree.leaves(out["params"]),
                              jax.tree.leaves(legacy["params"])):
        np.testing.assert_array_equal(np.asarray(leaf_a),
                                      np.asarray(leaf_b))


# ---------------------------------------------------------------------------
# checkpointing: Session wiring + padded round-trips
# ---------------------------------------------------------------------------
def test_session_checkpoint_resume_bitwise(tmp_path):
    """resume() from the latest checkpoint continues bit-for-bit where
    the uninterrupted run would be: identical round losses and final
    metrics (round r consumes only carried state + fold_in(key, r))."""
    d = str(tmp_path / "ckpt")
    full = build(ExperimentSpec(dataset="titanic", rounds=4, epochs=1,
                                seeds=(0,))).run()
    build(ExperimentSpec(dataset="titanic", rounds=2, epochs=1,
                         seeds=(0,), checkpoint_dir=d,
                         checkpoint_every=1)).run()
    res = build(ExperimentSpec(dataset="titanic", rounds=4, epochs=1,
                               seeds=(0,), checkpoint_dir=d,
                               checkpoint_every=1)).resume()
    assert res.resumed_from == 2
    assert res.metrics == full.metrics
    for i, r in enumerate((2, 3)):
        assert res.history[i]["round"] == r
        np.testing.assert_array_equal(res.history[i]["round_losses"],
                                      full.history[r]["round_losses"])
    # resume with no checkpoints is a fresh run
    fresh = build(ExperimentSpec(dataset="titanic", rounds=2, epochs=1,
                                 seeds=(0,),
                                 checkpoint_dir=str(tmp_path / "empty"),
                                 checkpoint_every=1)).resume()
    assert fresh.resumed_from is None
    # a checkpoint BEYOND spec.rounds must not masquerade as this
    # spec's run (the spec_hash joinability contract)
    with pytest.raises(ValueError, match="beyond spec.rounds"):
        build(ExperimentSpec(dataset="titanic", rounds=1, epochs=1,
                             seeds=(0,), checkpoint_dir=d,
                             checkpoint_every=1)).resume()
    # ...and neither may another experiment's checkpoint in a reused
    # dir (resume_hash is rounds-blind but forks on lr/seed/etc)
    with pytest.raises(ValueError, match="resume_hash"):
        build(ExperimentSpec(dataset="titanic", rounds=6, epochs=1,
                             seeds=(0,), lr=1e-2, checkpoint_dir=d,
                             checkpoint_every=1)).resume()


def test_padded_session_predict_trims_dead_slots():
    sess = build(ExperimentSpec(dataset="titanic", rounds=1, epochs=1,
                                seeds=(0,), n_clients=3, max_clients=5))
    sess.run()
    preds = sess.predict(np.zeros((4, 9), np.float32))
    assert np.asarray(preds).shape == (3, 4)   # live clients only


@pytest.mark.fast
def test_checkpoint_roundtrips_padded_trees(tmp_path):
    """Padded per-client param/opt trees (dead client slots, empty
    arrays) and NamedTuple nodes (LayoutArrays) round-trip through
    save/load unchanged -- values, dtypes, and structure."""
    from repro.checkpoint import (latest_step, load_checkpoint,
                                  save_checkpoint)
    from repro.configs import get_config
    from repro.core import partition as PT
    from repro.models.mlp_model import PaperMLP
    from repro.optim import adam

    model = PaperMLP(get_config("paper-mlp-titanic"))
    params = init_padded_params(model, jax.random.PRNGKey(0), 3, 8)
    opt_state = jax.vmap(adam(1e-3).init)(params)
    lay = PT.make_layout("titanic", 9, 3, seed=0, max_clients=8).arrays()
    tree = {"params": params, "opt_state": opt_state, "lay": lay,
            "step_idx": jnp.zeros((), jnp.int32),
            "empty": jnp.zeros((0, 5))}
    save_checkpoint(str(tmp_path), 3, tree)
    assert latest_step(str(tmp_path)) == 3
    restored = load_checkpoint(str(tmp_path), 3, tree)
    assert jax.tree.structure(restored) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # loading into a differently-padded like_tree fails actionably
    bad_like = dict(tree,
                    params=init_padded_params(model,
                                              jax.random.PRNGKey(0), 3, 6))
    with pytest.raises(ValueError, match="padded"):
        load_checkpoint(str(tmp_path), 3, bad_like)


# ---------------------------------------------------------------------------
# RunResult record
# ---------------------------------------------------------------------------
def test_run_result_schema_and_serialization():
    rr = build(ExperimentSpec(dataset="titanic", rounds=1, epochs=1,
                              seeds=(0,))).run()
    assert isinstance(rr, RunResult) and rr.schema_version == 5
    assert rr.spec_hash == rr.spec.spec_hash and len(rr.spec_hash) == 16
    d = json.loads(json.dumps(rr.to_dict()))
    assert d["schema_version"] == 5
    assert d["spec"]["dataset"] == "titanic"
    assert {"metrics", "history", "timings", "git_sha",
            "spec_hash"} <= set(d)
    assert "params" not in d
    # predict() rides the last run's params
    sess = build(ExperimentSpec(dataset="titanic", rounds=1, epochs=1,
                                seeds=(0,)))
    out = sess.run()
    preds = sess.predict(np.zeros((4, 9), np.float32))
    assert np.asarray(preds).shape == (3, 4)
    assert out.params is not None
