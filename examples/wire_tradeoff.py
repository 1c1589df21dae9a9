"""Wire-transform tradeoff in ~20 lines: one spec_grid over exchange
transforms (raw vs int8 vs topk+int8+dp), one run_grid call, one
compiled round shared by every transform lane (repro.wire), bytes on
the wire read straight from the per-cell telemetry.

Run:   PYTHONPATH=src python examples/wire_tradeoff.py
Smoke: PYTHONPATH=src python examples/wire_tradeoff.py --smoke
"""
import argparse

from repro.api import run_grid, spec_grid
from repro.compile_cache import setup_compile_cache

TRANSFORMS = ("none", "int8", "topk:0.5+int8+dp:0.1")


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes (1 round, 1 seed)")
    args = ap.parse_args()
    specs = spec_grid(datasets=("titanic",), modes=("devertifl",),
                      client_counts=(3,), transforms=TRANSFORMS,
                      seeds=(0,) if args.smoke else (0, 1),
                      rounds=1 if args.smoke else 3, epochs=2)
    grid = run_grid(specs)
    for t in TRANSFORMS:
        cell = grid["cells"][f"titanic/devertifl/{t}/none/sync/3"]
        w = cell["wire"]
        print(f"{t:24s} f1={cell['f1_mean']:.3f} bytes="
              f"{w['encoded_bytes']}/{w['raw_bytes']} "
              f"(spec {cell['spec_hash']})")


if __name__ == "__main__":
    main()
