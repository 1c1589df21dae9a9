#!/usr/bin/env python3
"""Smoke run of the De-VertiFL engine on a TPU, through the entry
points a user calls.

  python3 chip_smoke.py                # one chip
  python3 chip_smoke.py --four-chips   # the lane-sharded grid, 4 chips

One chip, in order:
  1. train the paper's full MNIST federation -- 5 clients, 70,000 rows
     (56,000 train / 14,000 test) x 784 features, 3x10 MLP towers --
     with ``build(spec).run()`` and ``first_layer="auto"``, which must
     resolve to the compiled Pallas kernel, and check the kernel
     against an f32 reference at every client slice;
  2. train the same spec on the Pallas lane and on the paper-literal
     ``masked`` lane (pure jax.numpy, the reference), both with f32
     matmuls, and compare the loss and F1 trajectories;
  3. serve 32 requests of test rows through ``Session.serve`` -- 26
     fresh rows, then 6 repeated entities answered from the exchange
     cache -- with the phase-1 federation, and compare the predictions
     with ``Session.predict``.

``--four-chips`` runs only ``run_padded_cells`` for mnist/devertifl
over client counts (2, 3, 5) x 4 seeds -- 12 lanes split over 4 chips
-- against the same grid on one device (``shard=False``).

Exits non-zero, printing no result line, unless JAX's first device is
a TPU.  Any failed phase or check raises.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The numbers printed before it are host-clock smoke readings, not
benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SPEC = dict(dataset="mnist", mode="devertifl", n_clients=5,
            n_samples=70000, rounds=3, epochs=1)
N_FRESH, REPEATS = 26, (0, 3, 7, 11, 19, 25)    # 26 + 6 = 32 requests
# pallas vs masked on one chip.  At the default TPU matmul precision
# both lanes round the first layer's inputs to bf16, in different
# programs, and 2,625 Adam steps with a FedAvg each round amplify that
# into different trajectories: on a v5e the round-mean losses drifted
# 21% apart and F1 0.12.  Phase 2 therefore runs both lanes with f32
# matmuls (jax.default_matmul_precision("highest"), which the kernel's
# dot follows too); what is left is f32 summation order, measured on a
# v5e at 1.8e-4 relative on round-mean loss and 0.003 on F1.  The
# bounds leave about ten times that:
LOSS_RTOL = 2e-3
F1_ATOL = 0.02
# vfl_matmul at the default precision against an f32
# (Precision.HIGHEST) reference, relative to the reference's largest
# magnitude.  bf16 inputs carry 2^-9 relative rounding each; measured
# 3.7e-3 on a v5e:
KERNEL_RTOL = 1e-2
# sharded vs one-device grid lanes, when they are not bitwise equal
GRID_LOSS_RTOL = 1e-5
GRID_F1_ATOL = 1e-3


def log(msg):
    print(msg, flush=True)


def tpu_device():
    """The device record of the last line; exits when no TPU is found."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (jax.devices()[0] is "
                 f"{devs[0].platform!r}); this check has no CPU fallback")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def train(spec):
    """build(spec).run(); returns the session, its RunResult and the
    wall time of the run."""
    from repro.api import build
    sess = build(spec)
    t0 = time.perf_counter()
    res = sess.run()
    return sess, res, time.perf_counter() - t0


def lowered_has_kernel(sess, res):
    """Whether the session's jitted round and predict lower to the
    compiled Pallas kernel (a Mosaic ``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp
    fed = sess.federation
    opt_state = jax.eval_shape(jax.vmap(fed.opt.init), res.params)
    rnd = fed._round.lower(res.params, opt_state, jnp.zeros((), jnp.int32),
                           fed.init_sched_state(), jax.random.PRNGKey(0),
                           fed._xtr, fed._ytr, fed._lay).as_text()
    pred = fed._predict.lower(res.params, fed._xte, fed._lay).as_text()
    return "tpu_custom_call" in rnd and "tpu_custom_call" in pred


def kernel_parity(layout, n_features, hidden, rows=64):
    """vfl_matmul and its VJP against the zero-padded matmul at f32
    precision, at every client slice of ``layout``: the largest error
    relative to the reference's largest magnitude."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.vfl_matmul import vfl_matmul
    hi = jax.lax.Precision.HIGHEST
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (rows, n_features), jnp.float32)
    w = jax.random.normal(k[1], (n_features, hidden), jnp.float32)
    g = jax.random.normal(k[2], (rows, hidden), jnp.float32)
    worst = 0.0
    for off, size in zip(layout.offsets, layout.sizes):
        x_i = x[:, off:off + size]
        y, vjp = jax.vjp(lambda a, b: vfl_matmul(a, b, off), x_i, w)
        ref, vjp_ref = jax.vjp(
            lambda a, b: jnp.dot(jnp.pad(a, ((0, 0), (off, n_features
                                                      - off - size))),
                                 b, precision=hi), x_i, w)
        for got, want in zip((y, *vjp(g)), (ref, *vjp_ref(g))):
            got, want = np.asarray(got), np.asarray(want)
            worst = max(worst, float(np.abs(got - want).max()
                                     / np.abs(want).max()))
    return worst


def trajectory(res):
    losses = np.stack([h["round_losses"] for h in res.history])  # [R, S]
    return losses, np.asarray([h["f1"] for h in res.history])


def check_learning(name, res):
    losses, _ = trajectory(res)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name} run has non-finite losses")
    means = losses.mean(axis=1)
    if not means[-1] < means[0]:
        raise AssertionError(f"{name} run's mean loss did not fall over "
                             f"the rounds: {means.tolist()}")


def compare_lanes(res_p, res_m):
    lp, fp = trajectory(res_p)
    lm, fm = trajectory(res_m)
    mean_p, mean_m = lp.mean(axis=1), lm.mean(axis=1)
    rel = np.abs(mean_p - mean_m) / np.abs(mean_m)
    f1_gap = np.abs(fp - fm)
    final_gap = abs(res_p.metrics["f1"] - res_m.metrics["f1"])
    log(f"pallas round mean losses {mean_p.tolist()}")
    log(f"masked round mean losses {mean_m.tolist()}")
    log(f"pallas round F1 {fp.tolist()}")
    log(f"masked round F1 {fm.tolist()}")
    log(f"pallas-vs-masked: max |step loss diff| "
        f"{float(np.abs(lp - lm).max())}, max rel round-mean loss diff "
        f"{float(rel.max())} (bound {LOSS_RTOL}), max |round F1 diff| "
        f"{float(f1_gap.max())}, |final F1 diff| {final_gap} "
        f"(bound {F1_ATOL})")
    if rel.max() > LOSS_RTOL or f1_gap.max() > F1_ATOL \
            or final_gap > F1_ATOL:
        raise AssertionError("pallas and masked trajectories disagree "
                             "beyond the stated tolerance")


def serve(sess, res):
    """Serve 26 fresh test rows, then 6 repeated entities from the
    exchange cache; check predictions (and the cached per-client
    logits) against the predict path."""
    import jax
    import jax.numpy as jnp
    from repro.api import ExchangeCache, ServeRequest, split_features
    from repro.core.protocol import make_h_all_fn
    fed = sess.federation
    x = np.asarray(fed.xte)[:N_FRESH]
    cache = ExchangeCache(64)
    fresh = [ServeRequest(uid=i, entity_id=f"row-{i}",
                          slices=split_features(fed.layout, x[i]))
             for i in range(N_FRESH)]
    repeat = [ServeRequest(uid=N_FRESH + j, entity_id=f"row-{r}")
              for j, r in enumerate(REPEATS)]
    t0 = time.perf_counter()
    rep1 = sess.serve(fresh, cache=cache, max_slots=8)
    rep2 = sess.serve(repeat, cache=cache, max_slots=8)
    wall = time.perf_counter() - t0
    done = rep1.counters["completed"] + rep2.counters["completed"]
    if done != N_FRESH + len(REPEATS):
        raise AssertionError(f"served {done} of "
                             f"{N_FRESH + len(REPEATS)} requests")
    if rep2.cache["hits"] != len(REPEATS):
        raise AssertionError(f"expected {len(REPEATS)} exchange-cache "
                             f"hits, got {rep2.cache}")
    ref = np.asarray(sess.predict(x))                  # [n_clients, 26]
    served = {**rep1.results, **rep2.results}
    rows = list(range(N_FRESH)) + list(REPEATS)
    bad = [u for u, r in enumerate(rows)
           if not np.array_equal(served[u], ref[:, r])]
    if bad:
        raise AssertionError(f"served predictions differ from "
                             f"Session.predict() for requests {bad}")
    # per-client logits (the exchange stacks the cache holds) against
    # the same stacks computed on the predict path's batch
    h_fn = jax.jit(make_h_all_fn(fed.model, fed.pcfg, layout=fed.layout))
    h_ref = np.asarray(h_fn(res.params, jnp.asarray(fed.layout.apply(x)),
                            fed._lay))
    logits_equal = all(
        np.array_equal(cache.lookup((sess.spec.spec_hash, f"row-{i}")),
                       h_ref[:, i]) for i in range(N_FRESH))
    log(f"serve: {done} requests, {rep2.cache['hits']} cache hits, "
        f"{rep1.counters['steps'] + rep2.counters['steps']} steps, "
        f"{wall:.3f} s wall (compile included); predictions equal "
        f"Session.predict(): True; per-client logits bitwise equal to "
        f"the predict path: {logits_equal}")
    return logits_equal


def train_log(name, spec):
    sess, res, wall = train(spec)
    log(f"train {name}: {wall:.3f} s wall, "
        f"{res.timings['steps_per_sec']:.1f} steps/s (host clock, first "
        f"round's compile included), round mean losses "
        f"{trajectory(res)[0].mean(axis=1).tolist()}, final F1 "
        f"{res.metrics['f1']}")
    check_learning(name, res)
    return sess, res


def one_chip(spec_kw=SPEC):
    """Phases 1-3.  ``spec_kw`` lets a CPU rehearsal shrink the run."""
    import jax
    from repro.api import ExperimentSpec
    from repro.kernels import interpret_default
    spec = ExperimentSpec(**spec_kw, first_layer="auto")
    lane = spec.first_layer
    log(f"first_layer='auto' resolved to {lane!r}; interpret mode "
        f"{interpret_default()}")
    if lane != "pallas":
        raise AssertionError(f"auto resolved to {lane!r}, not 'pallas'")
    sess, res = train_log("auto (pallas)", spec)
    fed = sess.federation
    err = kernel_parity(fed.layout, fed.model.in_features, fed.model.hidden)
    log(f"vfl_matmul forward+VJP vs f32 zero-padded matmul, all "
        f"{len(fed.layout.sizes)} client slices: max relative error {err} "
        f"(bound {KERNEL_RTOL})")
    if err > KERNEL_RTOL:
        raise AssertionError("vfl_matmul disagrees with its reference")
    if not interpret_default() and not lowered_has_kernel(sess, res):
        raise AssertionError("the pallas lane did not lower to the "
                             "compiled kernel")
    with jax.default_matmul_precision("highest"):
        _, res_p = train_log("pallas, f32 matmuls", spec)
        _, res_m = train_log("masked, f32 matmuls",
                             spec.replace(first_layer="masked"))
    compare_lanes(res_p, res_m)
    serve(sess, res)


def four_chips(n_samples=70000, rounds=2):
    """The lane-sharded grid on every device vs the same grid on one."""
    import jax
    from repro.core.sweep import SweepConfig, run_padded_cells
    scfg = SweepConfig(client_counts=(2, 3, 5), seeds=(0, 1, 2, 3),
                       rounds=rounds, epochs=1, n_samples=n_samples)
    t0 = time.perf_counter()
    sharded = run_padded_cells("mnist", "devertifl", scfg, shard="auto")
    t1 = time.perf_counter()
    single = run_padded_cells("mnist", "devertifl", scfg, shard=False)
    t2 = time.perf_counter()
    log(f"grid: {sharded['lanes']} lanes on {sharded['devices']} devices "
        f"in {t1 - t0:.3f} s, on {single['devices']} device in "
        f"{t2 - t1:.3f} s (host clock, compiles included)")
    if sharded["devices"] != jax.device_count():
        raise AssertionError(f"lanes were split over "
                             f"{sharded['devices']} devices, not "
                             f"{jax.device_count()}")
    f1_a, f1_b, l_a, l_b = [], [], [], []
    for nc in scfg.client_counts:
        a, b = sharded["cells"][nc], single["cells"][nc]
        f1_a += a["f1_per_seed"]
        f1_b += b["f1_per_seed"]
        l_a += a["final_loss_per_seed"]
        l_b += b["final_loss_per_seed"]
        log(f"  {nc} clients: F1 {a['f1_per_seed']} vs {b['f1_per_seed']}")
    f1_a, f1_b, l_a, l_b = map(np.asarray, (f1_a, f1_b, l_a, l_b))
    if not np.all(np.isfinite(l_a)):
        raise AssertionError("non-finite lane losses")
    bitwise = np.array_equal(f1_a, f1_b) and np.array_equal(l_a, l_b)
    rel = float(np.max(np.abs(l_a - l_b) / np.abs(l_b)))
    gap = float(np.max(np.abs(f1_a - f1_b)))
    log(f"sharded vs one device: bitwise {bitwise}; max rel final-loss "
        f"diff {rel}, max |F1 diff| {gap}")
    if not bitwise and (rel > GRID_LOSS_RTOL or gap > GRID_F1_ATOL):
        raise AssertionError("sharded and one-device lanes disagree "
                             "beyond the stated tolerance")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded grid over 4 chips "
                         "and its one-device comparison")
    args = ap.parse_args(argv)
    device = tpu_device()
    log(f"device: {device['kind']} x {device['count']}")
    from repro.compile_cache import setup_compile_cache
    log(f"compile cache: {setup_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        if device["count"] != 4:
            raise SystemExit(f"--four-chips needs 4 devices, found "
                             f"{device['count']}")
        four_chips()
    else:
        one_chip()
    log(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
