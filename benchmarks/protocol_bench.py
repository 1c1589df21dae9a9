"""Protocol engine throughput across first-layer strategies: the
paper-literal masked (zero-padded) scan, the slice-aware dynamic_slice
scan, the vfl_matmul Pallas scan, and the per-batch Python-loop
reference -- plus the sweep lane comparing three executions of the
same multi-client-count grid slice:

  looped   one run_cell per client count (one compile EACH)
  padded   run_padded_cells: all counts on one padded lane axis,
           ONE compile, single device
  sharded  run_padded_cells with the lane axis shard_map'ed over
           the device mesh (== padded when only one device exists;
           the recorded "devices" field disambiguates)

Appends one dated, git-SHA-keyed entry per run to
benchmarks/results/BENCH_protocol.json (a list), so the perf
trajectory accumulates across PRs instead of being overwritten:

  [{"date": ..., "git_sha": ..., "spec_hash": ...,
    "spec_hashes": {lane: ...}, "config": {...},
    "engines": {"loop": sps, "masked": sps, "slice": sps,
                "pallas": sps},
    "slice_speedup_vs_masked": ..., "scan_speedup_vs_loop": ...,
    "schedules": {sched: {"steps_per_sec": ..., "f1": ...,
                          "spec_hash": ...}},
    "sweep": {"client_counts": [...], "spec_hashes": {n: ...},
              "n_seeds": ...,
              "looped_cells_per_sec": ..., "padded_cells_per_sec": ...,
              "sharded_cells_per_sec": ..., "devices": ...,
              "round_traces": ...}}, ...]

(docs/ARCHITECTURE.md documents the append-only schema contract.)
Pre-slice-engine entries (a single dict with loop/scan keys) are
migrated into the list on first append.

Run:  PYTHONPATH=src python -m benchmarks.protocol_bench
Smoke (toy sizes, no file write): python -m benchmarks.run --smoke
"""
from __future__ import annotations

import datetime
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.api import (ExperimentSpec, build, git_sha as _git_sha,
                       spec_grid, sweep_config_for_specs)
from repro.core.protocol import train_keys
from repro.core.sweep import run_cell, run_padded_cells

RESULTS = os.path.join(os.path.dirname(__file__), "results")

# the paper's MNIST configuration, sized so one round is ~100 steps
BENCH_CFG = dict(dataset="mnist", n_clients=3, epochs=2, n_samples=4000)
SMOKE_CFG = dict(dataset="mnist", n_clients=3, epochs=1, n_samples=640)


def _append_entry(entry, path):
    """Append-only trajectory: never clobber previous runs.  An
    unreadable file is moved aside (.corrupt) rather than overwritten."""
    data = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            data = old if isinstance(old, list) else [old]
        except (json.JSONDecodeError, OSError):
            backup = path + ".corrupt"
            os.replace(path, backup)
            print(f"warning: unreadable {path} moved to {backup}")
    data.append(entry)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1)
    os.replace(tmp, path)       # atomic: a crash never truncates history
    return data


def _bench_engine(fed, run_round, n_steps, iters=3):
    """run_round(params, opt_state, sched_state) must return
    (params, opt_state, sched_state, losses)."""
    def fresh():
        _, p, o, _ = fed._init(jax.random.PRNGKey(0))
        return p, o, fed.init_sched_state()

    p, o, st = fresh()
    p, o, st, losses = run_round(p, o, st)      # warm-up / compile
    jax.block_until_ready(losses)
    t0 = time.perf_counter()
    for _ in range(iters):
        p, o, st, losses = run_round(p, o, st)
    jax.block_until_ready(losses)
    return iters * n_steps / (time.perf_counter() - t0)


def _scan_round(fed, rkey, si):
    """Adapter: the jitted scan round as a (p, o, st) -> ... callable."""
    def run_round(p, o, st):
        p, o, _, st, losses = fed._round(p, o, si, st, rkey, fed._xtr,
                                         fed._ytr, fed._lay)
        return p, o, st, losses
    return run_round


def run(smoke=False, results_path=None, iters=None):
    """Bench all engine lanes.  smoke=True shrinks to toy sizes and
    (unless results_path is given) skips the trajectory file write, so
    it is safe inside tier-1 time budgets."""
    cfg = SMOKE_CFG if smoke else BENCH_CFG
    iters = iters if iters is not None else (1 if smoke else 3)
    _, lk = train_keys(jax.random.PRNGKey(0))
    rkey = jax.random.fold_in(lk, 0)
    si = jnp.zeros((), jnp.int32)

    base_spec = ExperimentSpec(rounds=1, seeds=(0,), eval_every=0, **cfg)
    engines, spec_hashes = {}, {}
    n_steps = None
    for fl in ("masked", "slice", "pallas"):
        lane_spec = base_spec.replace(first_layer=fl)
        spec_hashes[fl] = lane_spec.spec_hash
        fed = build(lane_spec).federation
        n_steps = fed.pcfg.epochs * fed.n_batches
        engines[fl] = _bench_engine(fed, _scan_round(fed, rkey, si),
                                    n_steps, iters=iters)
        if fl == "masked":
            spec_hashes["loop"] = lane_spec.replace(
                engine="python").spec_hash

            def loop_round(p, o, st, fed=fed):
                p, o, _, st, losses = fed._python_round(p, o, si, st,
                                                        rkey)
                return p, o, st, losses
            engines["loop"] = _bench_engine(fed, loop_round, n_steps,
                                            iters=iters)

    # exchange-schedule lane: scan-round throughput + final F1 per
    # schedule, each stamped with the exact spec it timed.  "sync" is
    # the reference row (same engine as the slice lane above), so the
    # schedule overhead -- ring pushes, double-buffer swaps, the extra
    # data-copy forward -- is measured against it like-for-like.
    sched_rounds = 1 if smoke else 2
    schedules = {}
    for sname in ("sync", "stale_k:1", "double_buffer", "partial:0.8"):
        sspec = base_spec.replace(schedule=sname, rounds=sched_rounds)
        sess = build(sspec)
        sfed = sess.federation
        sps = _bench_engine(sfed, _scan_round(sfed, rkey, si),
                            sfed.pcfg.epochs * sfed.n_batches,
                            iters=iters)
        f1 = sess.run().metrics["f1"]
        schedules[sname] = {"steps_per_sec": sps, "f1": f1,
                            "spec_hash": sspec.spec_hash}

    # the sweep lane's config is DERIVED from its spec grid, so the
    # spec_hashes stamped below can never diverge from what is timed
    sweep_specs = spec_grid(
        datasets=("mnist",), modes=("devertifl",),
        **(dict(client_counts=(2, 3), seeds=(0, 1), rounds=2, epochs=1,
                n_samples=512)
           if smoke else
           dict(client_counts=(2, 3, 5), seeds=(0, 1, 2, 3), rounds=2,
                epochs=2, n_samples=2000)))
    _, _, sweep_scfg = sweep_config_for_specs(sweep_specs)
    counts = tuple(sweep_scfg.client_counts)
    # all three lanes are timed END-TO-END (data stacking + compiles +
    # training + eval): compile amortization is the padded engine's
    # win, so the walls must include it on every side
    t0 = time.perf_counter()
    looped_cells = [run_cell("mnist", "devertifl", nc, sweep_scfg)
                    for nc in counts]
    looped_wall = time.perf_counter() - t0
    # padded: every count on one lane axis, ONE round compile
    t0 = time.perf_counter()
    padded = run_padded_cells("mnist", "devertifl", sweep_scfg,
                              shard=False)
    padded_wall = time.perf_counter() - t0
    # sharded: same batch, lanes split over the device mesh.  With a
    # single device the shard_map is a no-op and the run would be
    # bitwise the padded one -- reuse it instead of paying a second
    # compile + train just to record noise.
    if jax.device_count() > 1:
        t0 = time.perf_counter()
        sharded = run_padded_cells("mnist", "devertifl", sweep_scfg,
                                   shard="auto")
        sharded_wall = time.perf_counter() - t0
    else:
        sharded, sharded_wall = padded, padded_wall
    sweep_entry = {
        "client_counts": list(counts),
        # spec ids of the per-count experiments this sweep covers,
        # keyed by n_clients (the very specs sweep_scfg was derived
        # from).  NOTE these identify the experiment CONFIGURATION:
        # the padded multi-count engine executes the gather-slice
        # first-layer lane, which is allclose -- not bitwise -- to
        # these specs' standalone runs (see repro.core.sweep docs)
        "spec_hashes": {str(s.n_clients): s.spec_hash
                        for s in sweep_specs},
        "n_seeds": len(sweep_scfg.seeds),
        "looped_cells_per_sec": len(counts) / max(looped_wall, 1e-9),
        "padded_cells_per_sec": len(counts) / max(padded_wall, 1e-9),
        "sharded_cells_per_sec": len(counts) / max(sharded_wall, 1e-9),
        # steady-state (post-compile) throughput of the padded batch
        "padded_steady_cells_per_sec": padded["cells_per_sec"],
        "devices": sharded["devices"],
        "round_traces": padded["round_traces"],
        # the SAME n_clients=3 run_cell measurement older trajectory
        # entries recorded, so the steps_per_sec series stays
        # comparable across PRs (if 3 ever leaves the count list, fall
        # back to the first count rather than aborting a finished run)
        "steps_per_sec": looped_cells[
            counts.index(3) if 3 in counts else 0]["steps_per_sec"],
    }

    entry = {
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "git_sha": _git_sha(),
        # joinability: spec_hash identifies the base bench experiment
        # (repro.api.ExperimentSpec.spec_hash); spec_hashes maps each
        # engine lane to the exact spec it timed
        "spec_hash": base_spec.spec_hash,
        "spec_hashes": spec_hashes,
        # on non-TPU backends the pallas lane times the interpreter,
        # not the compiled kernel -- record the backend so trajectory
        # entries from different machines stay comparable
        "backend": jax.default_backend(),
        "config": dict(cfg, smoke=smoke, iters=iters),
        "steps_per_round": n_steps,
        "engines": engines,
        "slice_speedup_vs_masked": engines["slice"] / engines["masked"],
        # same first layer on both sides: comparable with PR 1's
        # scan_speedup trajectory entry
        "scan_speedup_vs_loop": engines["masked"] / engines["loop"],
        # per-schedule scan throughput + final F1 (spec-hash-stamped):
        # the exchange-schedule lane added in PR 5
        "schedules": schedules,
        "sweep": sweep_entry,
    }
    # statically-verified compile-once contract (repro.analysis): the
    # retrace pass proves the benched round's carried avals close and
    # no captured scalar can drift -- 1 iff no unwaived hazard.  The
    # runtime sweep counter above measures one grid; this stamps the
    # structural claim the counter relies on.
    from repro.analysis.audit import audit as _static_audit
    entry["static_round_traces"] = _static_audit(
        base_spec, passes=("retrace",),
        lane_check=False).static_round_traces
    if results_path is None and not smoke:
        os.makedirs(RESULTS, exist_ok=True)
        results_path = os.path.join(RESULTS, "BENCH_protocol.json")
    if results_path is not None:
        _append_entry(entry, results_path)

    rows = [(f"protocol/{name}", 1e6 / sps, f"steps_per_sec={sps:.1f}")
            for name, sps in engines.items()]
    rows += [(f"protocol/sched_{name}", 1e6 / d["steps_per_sec"],
              f"steps_per_sec={d['steps_per_sec']:.1f} f1={d['f1']:.3f}")
             for name, d in schedules.items()]
    rows += [
        ("protocol/slice_vs_masked", 0.0,
         f"x{entry['slice_speedup_vs_masked']:.2f}"),
        ("protocol/sweep_looped", looped_wall * 1e6,
         f"cells_per_sec={sweep_entry['looped_cells_per_sec']:.2f}"),
        ("protocol/sweep_padded", padded_wall * 1e6,
         f"cells_per_sec={sweep_entry['padded_cells_per_sec']:.2f}"),
        ("protocol/sweep_sharded", sharded_wall * 1e6,
         f"cells_per_sec={sweep_entry['sharded_cells_per_sec']:.2f}"
         f" devices={sweep_entry['devices']}"),
    ]
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
