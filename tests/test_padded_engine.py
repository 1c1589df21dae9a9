"""The padded client axis and the multi-count sweep engine.

Contracts pinned here (see docs/ARCHITECTURE.md):

  * Layout.pad appends dead slots that own nothing: all-zero mask
    rows, size-0 slices, client_mask 0.
  * A padded federation (n_clients=3, max_clients=8) trains its LIVE
    clients bit-for-bit identically to the unpadded run in ALL THREE
    first-layer lanes -- the exchange sum, FedAvg weighting, and loss
    means see exact-zero dead terms only.
  * A dataset x mode sweep over >= 3 client counts compiles its round
    function ONCE (round_traces == 1), and its masked lanes reproduce
    the standalone runs bit-for-bit.
  * Sharding the lane axis over the device mesh (shard_map) changes
    nothing: sharded results == single-device results.
  * vfl_matmul's gate: 1.0 is a bitwise no-op, 0.0 zeroes the output
    and BOTH cotangents (the masked dW scatter).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partition as PT
from repro.core.exchange import fedavg, hidden_output_exchange
from repro.core.protocol import (DeVertiFL, ProtocolConfig,
                                 init_padded_params)
from repro.core.sweep import (SweepConfig, run_grid, run_padded_cells)
from repro.kernels.vfl_matmul import vfl_matmul

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Layout.pad / LayoutArrays.client_mask
# ---------------------------------------------------------------------------
@pytest.mark.fast
def test_layout_pad_structure():
    lay = PT.make_layout("titanic", 9, 3, seed=1)
    pad = lay.pad(7)
    assert (pad.n_real, pad.n_clients) == (3, 7)
    assert pad.sizes == lay.sizes + (0,) * 4
    assert pad.offsets == lay.offsets + (0,) * 4
    # live rows identical, dead rows all-zero
    np.testing.assert_array_equal(pad.masks()[:3], lay.masks())
    assert pad.masks()[3:].sum() == 0
    np.testing.assert_array_equal(pad.client_mask(),
                                  [1, 1, 1, 0, 0, 0, 0])
    arrs = pad.arrays()
    assert arrs.client_mask.shape == (7,)
    assert arrs.sizes.shape == (7,) and arrs.offsets.shape == (7,)
    # pad is idempotent at the same width and refuses to shrink
    assert pad.pad(7) is pad
    with pytest.raises(ValueError):
        lay.pad(2)
    # make_layout(max_clients=...) is the same padding
    pad2 = PT.make_layout("titanic", 9, 3, seed=1, max_clients=7)
    assert pad2.sizes == pad.sizes and pad2.n_real == 3


@pytest.mark.fast
@pytest.mark.parametrize("path", ["eager", "program"])
def test_init_padded_params_live_prefix_matches_unpadded(path):
    """Live clients' init must be the unpadded derivation exactly
    (split(key, n)[:k] != split(key, k), so this is a real contract),
    called eagerly and through a federation's compiled set-up."""
    key = jax.random.PRNGKey(0)
    if path == "eager":
        from repro.configs import get_config
        from repro.models.mlp_model import PaperMLP
        model = PaperMLP(get_config("paper-mlp-titanic"))
        plain = init_padded_params(model, key, 3)
        padded = init_padded_params(model, key, 3, 8)
    else:
        base = ProtocolConfig(dataset="titanic", n_clients=3, seed=0)
        plain = DeVertiFL(base)._init(key)[1]
        padded = DeVertiFL(base.replace(max_clients=8))._init(key)[1]
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(padded)):
        assert b.shape[0] == 8
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b[:3]))


# ---------------------------------------------------------------------------
# masked cross-client reductions
# ---------------------------------------------------------------------------
@pytest.mark.fast
def test_exchange_client_mask_drops_dead_contributions():
    h = jnp.asarray(np.random.default_rng(0).normal(
        size=(5, 4, 6)).astype(np.float32))
    cm = jnp.asarray([1, 1, 1, 0, 0], jnp.float32)
    out = hidden_output_exchange(h, client_mask=cm)
    ref = hidden_output_exchange(h[:3])
    # live rows see only live peers' sums
    np.testing.assert_array_equal(np.asarray(out[:3]), np.asarray(ref))


@pytest.mark.fast
def test_fedavg_client_mask_weighted():
    leaf = jnp.asarray(np.random.default_rng(1).normal(
        size=(5, 2, 3)).astype(np.float32))
    cm = jnp.asarray([1, 1, 1, 0, 0], jnp.float32)
    out = fedavg({"w": leaf}, client_mask=cm)["w"]
    ref = fedavg({"w": leaf[:3]})["w"]
    # dead params never dilute the mean; every slot (dead included)
    # ends synced to the live mean
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(
        np.asarray(out), np.broadcast_to(np.asarray(out[:1]), out.shape))


# ---------------------------------------------------------------------------
# vfl_matmul gate (masked dW scatter)
# ---------------------------------------------------------------------------
@pytest.mark.fast
def test_vfl_matmul_gate():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(12, 8)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))

    def loss(x, w, gate):
        return (vfl_matmul(x, w, 4, gate=gate) * g).sum()

    y_plain = vfl_matmul(x, w, 4)
    # gate=1.0 is a bitwise no-op on y and both grads
    np.testing.assert_array_equal(
        np.asarray(vfl_matmul(x, w, 4, gate=jnp.float32(1.0))),
        np.asarray(y_plain))
    dx1, dw1 = jax.grad(loss, argnums=(0, 1))(x, w, jnp.float32(1.0))
    dx0, dw0 = jax.grad(loss, argnums=(0, 1))(
        x, w, jnp.float32(0.0))
    dxp, dwp = jax.grad(lambda x, w: (vfl_matmul(x, w, 4)
                                      * g).sum(), argnums=(0, 1))(x, w)
    np.testing.assert_array_equal(np.asarray(dx1), np.asarray(dxp))
    np.testing.assert_array_equal(np.asarray(dw1), np.asarray(dwp))
    # gate=0.0: y, dx, and the dW scatter rows are all exact zeros
    assert float(np.abs(np.asarray(
        vfl_matmul(x, w, 4, gate=jnp.float32(0.0)))).max()) == 0.0
    assert float(np.abs(np.asarray(dx0)).max()) == 0.0
    assert float(np.abs(np.asarray(dw0)).max()) == 0.0
    # ungated dW only ever touches the client's row block
    assert float(np.abs(np.asarray(dwp[:4])).max()) == 0.0
    assert float(np.abs(np.asarray(dwp[4:8])).max()) > 0.0


# ---------------------------------------------------------------------------
# padded federation == unpadded federation, bit for bit, all lanes
# ---------------------------------------------------------------------------
def _traj(pcfg):
    r = DeVertiFL(pcfg).train()
    return (np.concatenate([h["round_losses"] for h in r["history"]]),
            np.array([h["f1"] for h in r["history"]]),
            r["final"]["f1"])


@pytest.mark.parametrize("fl", ["masked", "slice", "pallas"])
def test_padded_federation_bitwise(fl):
    """n_clients=3 padded to max_clients=8 trains the live clients
    bit-for-bit identically to the unpadded run in every first-layer
    lane: loss trajectory, per-round F1, final F1 all exactly equal."""
    base = ProtocolConfig(dataset="titanic", n_clients=3, rounds=2,
                          epochs=2, seed=0, first_layer=fl)
    l0, f0, fin0 = _traj(base)
    l1, f1, fin1 = _traj(base.replace(max_clients=8))
    np.testing.assert_array_equal(l0, l1)
    np.testing.assert_array_equal(f0, f1)
    assert fin0 == fin1


@pytest.mark.fast
def test_padded_rejects_mask_blind_custom_fedavg():
    """A custom aggregator that cannot see client_mask would average
    dead slots' random params into live clients -- refused at build
    time, not silently mis-averaged."""
    import jax as _jax
    pcfg = ProtocolConfig(dataset="titanic", n_clients=3, max_clients=8,
                          rounds=1, epochs=1)
    with pytest.raises(ValueError, match="client_mask"):
        DeVertiFL(pcfg, fedavg_fn=lambda p: _jax.tree.map(
            lambda l: l, p))
    # mask-aware custom aggregators are fine
    DeVertiFL(pcfg, fedavg_fn=lambda p, client_mask=None: fedavg(
        p, client_mask=client_mask))
    # and mask-blind ones remain fine without padding
    DeVertiFL(ProtocolConfig(dataset="titanic", n_clients=3, rounds=1,
                             epochs=1),
              fedavg_fn=lambda p: _jax.tree.map(lambda l: l, p))


@pytest.mark.parametrize("mode", ["non_federated", "verticomb"])
def test_padded_federation_bitwise_other_modes(mode):
    base = ProtocolConfig(dataset="titanic", n_clients=3, rounds=2,
                          epochs=1, seed=0, mode=mode)
    l0, _, fin0 = _traj(base)
    l1, _, fin1 = _traj(base.replace(max_clients=6))
    np.testing.assert_array_equal(l0, l1)
    assert fin0 == fin1


# ---------------------------------------------------------------------------
# multi-count padded sweep: one compile, bitwise masked lanes
# ---------------------------------------------------------------------------
def test_padded_sweep_compiles_once_and_matches_standalone():
    """A sweep over THREE client counts compiles the round function
    exactly once (the compile-once acceptance criterion), and every
    masked lane reproduces the corresponding standalone unpadded
    DeVertiFL run bit-for-bit."""
    seeds = (0, 1)
    counts = (2, 3, 4)
    out = run_padded_cells(
        "titanic", "devertifl",
        SweepConfig(client_counts=counts, seeds=seeds, rounds=2,
                    epochs=2, first_layer="masked"))
    assert out["round_traces"] == 1, out
    assert out["lanes"] == len(counts) * len(seeds)
    for nc in counts:
        cell = out["cells"][nc]
        for i, s in enumerate(seeds):
            solo = DeVertiFL(ProtocolConfig(
                dataset="titanic", n_clients=nc, rounds=2, epochs=2,
                seed=s, first_layer="masked")).train(
                    eval_every_round=False)
            assert cell["f1_per_seed"][i] == solo["final"]["f1"], \
                (nc, s)


def test_padded_sweep_gather_slice_lane_allclose():
    """The shape-uniform gather-slice first layer (slice/pallas/auto
    under the lane vmap) pads the contraction, so it is allclose --
    not bitwise -- to the standalone dynamic_slice run."""
    out = run_padded_cells(
        "titanic", "devertifl",
        SweepConfig(client_counts=(2, 3), seeds=(0,), rounds=2,
                    epochs=2, first_layer="slice"))
    assert out["round_traces"] == 1
    for nc in (2, 3):
        solo = DeVertiFL(ProtocolConfig(
            dataset="titanic", n_clients=nc, rounds=2, epochs=2,
            seed=0, first_layer="slice")).train(eval_every_round=False)
        assert abs(out["cells"][nc]["f1_per_seed"][0]
                   - solo["final"]["f1"]) <= 0.02


def test_run_grid_schema_unchanged():
    """run_grid still emits {"cells": {"ds/mode/n": ...}, "compare"}
    with per-count cell dicts, now driven by the padded engine."""
    grid = run_grid(SweepConfig(
        datasets=("titanic",), modes=("devertifl", "non_federated"),
        client_counts=(2, 3), seeds=(0,), rounds=1, epochs=1))
    assert set(grid["cells"]) == {"titanic/devertifl/2",
                                  "titanic/devertifl/3",
                                  "titanic/non_federated/2",
                                  "titanic/non_federated/3"}
    cell = grid["cells"]["titanic/devertifl/2"]
    assert {"f1_mean", "f1_std", "acc_mean", "steps_per_sec"} <= set(cell)
    assert set(grid["compare"]["titanic/2"]) == {"devertifl",
                                                 "non_federated"}


# ---------------------------------------------------------------------------
# sharded lanes == single device (8 fake CPU devices, subprocess so the
# main process keeps its single real device -- same pattern as
# tests/test_sharding_mesh.py)
# ---------------------------------------------------------------------------
def test_sharded_sweep_matches_single_device():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        assert jax.device_count() == 8, jax.devices()
        from repro.core.sweep import SweepConfig, run_padded_cells

        scfg = SweepConfig(client_counts=(2, 3, 4, 5), seeds=(0, 1),
                           rounds=2, epochs=1, first_layer="masked")
        single = run_padded_cells("titanic", "devertifl", scfg,
                                  shard=False)
        shard = run_padded_cells("titanic", "devertifl", scfg,
                                 shard="auto")
        assert single["devices"] == 1 and shard["devices"] == 8, \\
            (single["devices"], shard["devices"])
        for nc in (2, 3, 4, 5):
            a, b = single["cells"][nc], shard["cells"][nc]
            assert a["f1_per_seed"] == b["f1_per_seed"], nc
            assert a["final_loss_mean"] == b["final_loss_mean"], nc
        print("sharded == single-device over", shard["devices"],
              "devices")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
