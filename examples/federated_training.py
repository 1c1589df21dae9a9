"""The paper in one script: De-VertiFL vs non-federated training on the
synthetic MNIST stand-in with vertically partitioned features, driven
by the declarative repro.api front door.

  PYTHONPATH=src python examples/federated_training.py --clients 5

Each comparison side is one ExperimentSpec; ``build(spec).run()``
picks the engine -- a standalone scan-fused federation for one seed,
the seed-vmapped sweep cell (one compile per mode) for ``--seeds k``
> 1 -- and returns a RunResult with mean +/- std F1.

  --smoke runs the reduced CI configuration (titanic, 2 rounds) --
  the examples-smoke lane in scripts/ci.sh.
"""
import argparse

from repro.api import ExperimentSpec, build
from repro.compile_cache import setup_compile_cache


def report(name, rr):
    m = rr.metrics
    if "f1_std" in m:
        print(f"  {name:14s} F1={m['f1']:.3f} +/- {m['f1_std']:.3f}  "
              f"({rr.timings['steps_per_sec']:.0f} steps/s across "
              f"{len(rr.spec.seeds)} federations)")
    else:
        for h in rr.history[:: max(1, rr.spec.rounds // 5)]:
            print(f"  round {h['round']:3d}  F1={h['f1']:.3f}  "
                  f"loss={h['loss']:.3f}")
        print(f"  {name:14s} final F1={m['f1']:.3f}  acc={m['acc']:.3f}")
    return m["f1"]


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--dataset", default="mnist",
                    choices=["mnist", "fmnist", "titanic", "bank"])
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--engine", default="scan",
                    choices=["scan", "python"],
                    help="scan = fused lax.scan rounds (default); "
                         "python = per-batch reference loop")
    ap.add_argument("--first-layer", default="auto",
                    choices=["auto", "pallas", "slice", "masked"],
                    help="first-layer strategy: slice/pallas read only "
                         "each client's contiguous feature slice; masked "
                         "is the paper-literal zero-padding reference; "
                         "auto = pallas on TPU, slice elsewhere")
    ap.add_argument("--seeds", type=int, default=1,
                    help=">1 runs the vmapped multi-seed sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI config: titanic, 3 clients, "
                         "2 rounds x 1 epoch, 2 seeds (~seconds)")
    args = ap.parse_args()
    if args.smoke:
        args.dataset, args.clients = "titanic", 3
        args.rounds, args.epochs, args.seeds = 2, 1, 2

    n = 6000 if args.dataset in ("mnist", "fmnist") else None
    try:
        spec = ExperimentSpec(
            dataset=args.dataset, mode="devertifl",
            n_clients=args.clients, rounds=args.rounds,
            epochs=args.epochs, n_samples=n, engine=args.engine,
            first_layer=args.first_layer,
            seeds=tuple(range(args.seeds)))
    except ValueError as e:
        ap.error(str(e))    # e.g. --seeds >1 with --engine python

    print(f"De-VertiFL: {args.clients} clients, {args.dataset}, "
          f"{args.rounds} rounds x {args.epochs} epochs "
          f"[engine={spec.engine}, seeds={spec.seeds}, "
          f"spec={spec.spec_hash}]")
    fed_f1 = report("devertifl", build(spec).run())

    print("non-federated baseline (no exchange, no FedAvg):")
    non_f1 = report("non-federated", build(spec.replace(
        mode="non_federated", fedavg=False)).run())

    gain = fed_f1 - non_f1
    print(f"collaboration gain: {gain:+.3f} F1 "
          f"({'matches' if gain > 0 else 'CONTRADICTS'} the paper's claim)")


if __name__ == "__main__":
    main()
