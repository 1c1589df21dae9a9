#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

  python3 bench/readings.py --workload <cell> --seeds 12 [--first 1000]

For each seed, in one process, at the cell's own size: the program's
compared numbers (training: the set-up call; serving: a short window at
the cell's load) and the control's (the reference in the program's
place at the next lower precision, ``reference.matmul_high``).  For
training the readings are the loss gaps (largest and mean) over
several stretches of the first round and the change gaps of the whole
call (worst and median leaf); ``--kinds`` adds, on the first
``--faults`` seeds, a second witness (the reference computed on the host CPU), and three faults
planted in the program: half of every batch left out of the loss, a
step that returns its state unchanged, and FedAvg skipped.  One JSON
line per seed and kind goes to standard output.
"""
import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HORIZONS = (1, 3, 8, 16, 32, 64, 128, 256, None)  # None: the whole round
KINDS = ("program", "control", "one_pass", "witness_cpu", "half_batch",
         "state_unchanged", "fedavg_skipped")
FAULTS = ("half_batch", "state_unchanged", "fedavg_skipped")


def train_readings(losses, final, ref):
    """Loss gaps (largest and mean relative gap) over each horizon of
    the first round, and the call's change gaps (worst and median
    leaf), of one side against the reference."""
    from bench import check
    out = {}
    r = np.asarray(ref["losses"], np.float64)
    p = np.asarray(losses, np.float64)
    for h in HORIZONS:
        n = len(r) if h is None else h
        tag = "round" if h is None else h
        out[f"loss_gap_{tag}"] = check.loss_gap(p, r, n)
        rel = np.abs(p[:n] - r[:n]) / np.abs(r[:n]) \
            if p.shape == r.shape else np.inf
        out[f"loss_mean_gap_{tag}"] = float(np.mean(rel))
    out["change_gap"], left_out = check.change_gap(
        final, ref["start"], ref["final"], ref["first_grads"])
    out["change_gap_median_leaf"], _ = check.change_gap(
        final, ref["start"], ref["final"], ref["first_grads"],
        worst=False)
    out["left_out"] = left_out
    return out


def half_batch():
    """Plant the fault: the program's loss takes the mean over the first
    half of each batch only."""
    from repro.core import protocol
    ce = protocol._ce
    protocol._ce = lambda logits, labels: ce(logits[:len(labels) // 2],
                                             labels[:len(labels) // 2])
    return lambda: setattr(protocol, "_ce", ce)


def state_unchanged():
    """Plant the fault: every optimizer step hands back the parameters
    it was given."""
    from repro.core import protocol
    from repro.optim import Optimizer
    adam = protocol.adam

    def frozen(*a, **kw):
        opt = adam(*a, **kw)
        return Optimizer(opt.init, lambda g, s, p, t: (p, s, {}))
    protocol.adam = frozen
    return lambda: setattr(protocol, "adam", adam)


def fedavg_skipped():
    """Plant the fault: the round's FedAvg hands back every client's
    parameters unaveraged."""
    from repro.core import protocol
    avg = protocol.fedavg
    protocol.fedavg = lambda params, client_mask=None: params
    return lambda: setattr(protocol, "fedavg", avg)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=1000)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds that also run the planted faults and the "
                    "witness")
    ap.add_argument("--kinds", default="program,control,half_batch,"
                    "state_unchanged,fedavg_skipped",
                    help=f"training readings to take, of {','.join(KINDS)}")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import catalog, reference
    from bench.serve import ServeCell
    from bench.train import TrainCell
    from repro.compile_cache import setup_compile_cache
    cell = catalog.cell(args.workload)
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision",
                      cell["config"]["precision"]["matmul"])
    kind = cell["traffic"]["kind"]

    def emit(seed, what, numbers):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "kind": what, **numbers}), flush=True)

    kinds = args.kinds.split(",")

    def planted(seed, name):
        """Set up the cell afresh with the fault planted and read its
        set-up call."""
        undo = globals()[name]()
        try:
            f = TrainCell(cell, seed)
            f.setup()
            emit(seed, name, train_readings(f.first_losses,
                                            f.first_params, ref))
        finally:
            undo()

    for k in range(args.seeds):
        seed = args.first + k
        if kind == "train":
            c = TrainCell(cell, seed)
            c.setup()
            ref = c.follow(c.rounds)
            emit(seed, "program", train_readings(
                c.first_losses, c.first_params, ref))
            others = {"control": reference.matmul_high,
                      "one_pass": reference.matmul_bf16}
            for name, mm in others.items():
                if name in kinds:
                    o = c.follow(c.rounds, mm=mm)
                    emit(seed, name, train_readings(o["losses"],
                                                    o["final"], ref))
            c.release()
            if k >= args.faults:
                continue
            if "witness_cpu" in kinds:
                w = c.follow(c.rounds, device=jax.devices("cpu")[0])
                emit(seed, "witness_cpu", train_readings(
                    w["losses"], w["final"], ref))
            for name in FAULTS:
                if name in kinds:
                    planted(seed, name)
        else:
            c = ServeCell(cell, seed)
            c.setup()
            c.window(args.seconds)
            emit(seed, "program", c.numbers())
            emit(seed, "control", c.numbers(mm=reference.matmul_high))


if __name__ == "__main__":
    main()
