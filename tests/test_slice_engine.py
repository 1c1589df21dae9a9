"""The slice-aware protocol engine: canonical column layout,
masked / slice / pallas first-layer equivalence, sweep integration,
the perm-plan tail-drop contract, and the bench smoke lane.

masked is the paper-literal zero-padding reference; slice and pallas
compute the identical first layer over only the client's contiguous
feature slice, so trajectories agree to allclose (float reduction
order differs) rather than bitwise.
"""
import os
import sys

import jax
import numpy as np
import pytest

from repro.core import partition as PT
from repro.core.protocol import (DeVertiFL, ProtocolConfig, make_perm_fn,
                                 resolve_first_layer)
from repro.core.sweep import SweepConfig, run_cell

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# canonical layout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("ds,nf", [("mnist", 784), ("titanic", 9),
                                   ("bank", 51)])
@pytest.mark.parametrize("n", [2, 3, 5])
def test_layout_canonicalization(ds, nf, n):
    lay = PT.make_layout(ds, nf, n, seed=1)
    # perm is a permutation of all features
    assert np.array_equal(np.sort(lay.perm), np.arange(nf))
    assert np.array_equal(lay.perm[lay.inv_perm], np.arange(nf))
    # contiguous disjoint complete slices in partition order
    assert lay.offsets[0] == 0
    assert np.array_equal(np.asarray(lay.offsets),
                          np.concatenate([[0], np.cumsum(lay.sizes)[:-1]]))
    assert sum(lay.sizes) == nf
    for i, (off, sz) in enumerate(zip(lay.offsets, lay.sizes)):
        # canonical slice i holds exactly client i's original features
        np.testing.assert_array_equal(lay.perm[off:off + sz],
                                      lay.partition[i])
    # masks are contiguous slabs implementing the same zeropad
    m = lay.masks()
    assert m.sum() == nf
    for i, (off, sz) in enumerate(zip(lay.offsets, lay.sizes)):
        assert m[i, off:off + sz].all() and m[i].sum() == sz


def test_layout_apply_matches_client_view():
    """Canonical slice i of permuted data == the client's raw features;
    slab-masked canonical data == permuted zeropad view."""
    lay = PT.make_layout("titanic", 9, 3, seed=5)
    x = np.random.default_rng(0).normal(size=(7, 9)).astype(np.float32)
    xc = lay.apply(x)
    old_masks = PT.masks_for(lay.partition, 9)
    for i, (off, sz) in enumerate(zip(lay.offsets, lay.sizes)):
        np.testing.assert_array_equal(xc[:, off:off + sz],
                                      x[:, lay.partition[i]])
        np.testing.assert_array_equal(xc * lay.masks()[i],
                                      (x * old_masks[i])[:, lay.perm])


@pytest.mark.fast
def test_resolve_first_layer():
    assert resolve_first_layer(ProtocolConfig(first_layer="masked")) == \
        "masked"
    auto = resolve_first_layer(ProtocolConfig(first_layer="auto"))
    assert auto == ("pallas" if jax.default_backend() == "tpu" else "slice")
    # exchanging the raw input (exchange_at=0) forces the masked path
    assert resolve_first_layer(ProtocolConfig(first_layer="slice",
                                              exchange_at=0)) == "masked"
    with pytest.raises(ValueError):
        resolve_first_layer(ProtocolConfig(first_layer="bogus"))


# ---------------------------------------------------------------------------
# engine equivalence: masked vs slice vs pallas
# ---------------------------------------------------------------------------
def _trajectories(pcfg):
    r = DeVertiFL(pcfg).train()
    losses = np.concatenate([h["round_losses"] for h in r["history"]])
    f1s = np.array([h["f1"] for h in r["history"]])
    return losses, f1s, r["final"]["f1"]


@pytest.mark.parametrize("mode", ["devertifl", "non_federated",
                                  "verticomb"])
def test_first_layer_paths_allclose_titanic(mode):
    """Same seed => masked, slice, and pallas(interpret) loss/F1
    trajectories agree (allclose: only float reduction order differs)."""
    base = ProtocolConfig(dataset="titanic", n_clients=3, rounds=2,
                          epochs=2, mode=mode, seed=0)
    ref_l, ref_f1, ref_final = _trajectories(base.replace(
        first_layer="masked"))
    for fl in ("slice", "pallas"):
        l, f1, final = _trajectories(base.replace(first_layer=fl))
        np.testing.assert_allclose(l, ref_l, rtol=1e-4, atol=1e-5,
                                   err_msg=f"{fl} loss vs masked")
        np.testing.assert_allclose(f1, ref_f1, atol=0.02,
                                   err_msg=f"{fl} F1 vs masked")
        assert abs(final - ref_final) <= 0.02


def test_first_layer_paths_allclose_mnist():
    """The bench config's shape: mnist has non-trivial offsets (whole
    28-column image rows), exercising the kernel's in-VMEM W slice."""
    base = ProtocolConfig(dataset="mnist", n_clients=3, rounds=1,
                          epochs=2, n_samples=1200, seed=0)
    ref_l, ref_f1, _ = _trajectories(base.replace(first_layer="masked"))
    for fl in ("slice", "pallas"):
        l, f1, _ = _trajectories(base.replace(first_layer=fl))
        np.testing.assert_allclose(l, ref_l, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(f1, ref_f1, atol=0.02)


def test_scan_matches_python_loop_slice():
    """The slice path keeps the scan == python-loop bitwise invariant
    (both engines share the same jitted step)."""
    pcfg = ProtocolConfig(dataset="titanic", n_clients=3, rounds=2,
                          epochs=2, seed=0, first_layer="slice")
    scan = DeVertiFL(pcfg).train(engine="scan")
    loop = DeVertiFL(pcfg).train(engine="python")
    np.testing.assert_array_equal(
        np.concatenate([h["round_losses"] for h in scan["history"]]),
        np.concatenate([h["round_losses"] for h in loop["history"]]))
    assert scan["final"]["f1"] == loop["final"]["f1"]


def test_sweep_slice_lane_matches_standalone():
    """Seed lane s of a slice-layout sweep == DeVertiFL(seed=s,
    first_layer='slice').train() -- per-seed column permutations
    (titanic's random partitions differ by seed) ride the vmapped
    LayoutArrays correctly."""
    seeds = (0, 1)
    cell = run_cell("titanic", "devertifl", 3,
                    SweepConfig(seeds=seeds, rounds=2, epochs=2,
                                first_layer="slice"))
    for i, s in enumerate(seeds):
        solo = DeVertiFL(ProtocolConfig(
            dataset="titanic", n_clients=3, rounds=2, epochs=2,
            seed=s, first_layer="slice")).train(eval_every_round=False)
        assert cell["f1_per_seed"][i] == solo["final"]["f1"]


# ---------------------------------------------------------------------------
# perm plan: the tail-drop contract
# ---------------------------------------------------------------------------
@pytest.mark.fast
def test_perm_plan_tail_drop():
    """Regression-pin the epoch-shuffle semantics: n_batches =
    n_train // bs, and the trailing n_train % bs indices of every
    epoch's permutation are dropped (a different random subset each
    epoch)."""
    pcfg = ProtocolConfig(epochs=3, batch_size=64)
    plan = make_perm_fn(pcfg, 150)
    assert (plan.n_batches, plan.batch_size, plan.n_dropped) == (2, 64, 22)
    idx = np.asarray(plan.perms(jax.random.PRNGKey(0)))
    assert idx.shape == (pcfg.epochs * 2, 64)
    assert idx.min() >= 0 and idx.max() < 150
    per_epoch = idx.reshape(pcfg.epochs, -1)
    for e in range(pcfg.epochs):
        # within an epoch indices are distinct (a permutation prefix)
        assert np.unique(per_epoch[e]).size == per_epoch[e].size
    # epochs drop different tails (independent permutations)
    assert not np.array_equal(np.sort(per_epoch[0]), np.sort(per_epoch[1]))


@pytest.mark.fast
def test_perm_plan_small_dataset():
    """n_train < batch_size clamps bs to n_train: nothing is dropped."""
    plan = make_perm_fn(ProtocolConfig(epochs=2, batch_size=64), 10)
    assert (plan.n_batches, plan.batch_size, plan.n_dropped) == (1, 10, 0)


# ---------------------------------------------------------------------------
# bench smoke lane: append-only trajectory file
# ---------------------------------------------------------------------------
@pytest.mark.fast
def test_protocol_bench_smoke_appends(tmp_path):
    """The smoke bench runs all engine lanes at toy sizes and appends
    (never clobbers) the trajectory file, migrating the pre-slice
    single-dict format into the list."""
    import json
    sys.path.insert(0, REPO_ROOT)
    try:
        from benchmarks import protocol_bench
    finally:
        sys.path.remove(REPO_ROOT)
    path = tmp_path / "BENCH_protocol.json"
    legacy = {"config": {}, "loop_steps_per_sec": 1.0,
              "scan_steps_per_sec": 2.0}
    path.write_text(json.dumps(legacy))
    rows = protocol_bench.run(smoke=True, results_path=str(path))
    lanes = {name.split("/")[1] for name, _, _ in rows}
    assert {"masked", "slice", "pallas", "loop"} <= lanes
    data = json.loads(path.read_text())
    assert isinstance(data, list) and len(data) == 2
    assert data[0] == legacy                      # old entry preserved
    entry = data[1]
    assert {"date", "git_sha", "config", "engines"} <= set(entry)
    assert {"masked", "slice", "pallas", "loop"} <= set(entry["engines"])
    assert entry["config"]["smoke"] is True
