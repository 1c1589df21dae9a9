"""Exchange-schedule sweep in ~20 lines: one spec_grid over schedules
(sync vs stale vs stale+partial), one run_grid call, one compiled
round shared by every schedule lane (repro.schedule).

Run: PYTHONPATH=src python examples/staleness_sweep.py
"""
from repro.api import run_grid, spec_grid
from repro.compile_cache import setup_compile_cache

SCHEDULES = ("sync", "stale_k:2", "stale_k:4+partial:0.8")


def main():
    setup_compile_cache()
    specs = spec_grid(datasets=("titanic",), modes=("devertifl",),
                      client_counts=(3,), seeds=(0, 1),
                      schedules=SCHEDULES, rounds=2, epochs=2)
    grid = run_grid(specs)
    for sched in SCHEDULES:
        cell = grid["cells"][f"titanic/devertifl/{sched}/3"]
        print(f"{sched:24s} f1={cell['f1_mean']:.3f} "
              f"(spec {cell['spec_hash']})")


if __name__ == "__main__":
    main()
