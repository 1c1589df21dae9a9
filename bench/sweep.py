#!/usr/bin/env python3
"""The offered-load sweep that a serving cell's fixed rate is set from
(not run by the benchmark).

  python3 bench/sweep.py --workload mnist5.serve --rates 500,1000,2000

One server, set up as the cell sets it up, serves a window of
``--seconds`` at each rate in turn.  One JSON line per rate: the
latency quantiles from the due time, how late the generator ran, and
how long the queue took to drain after the last request was due (a
backlog that grows through the window shows as a drain that grows
with the window).  The last line names the knee: the highest rate,
below the first that fails, whose p95 stays under ``--limit-ms`` and
whose queue drains within ``--drain-s``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--limit-ms", type=float, default=15.0)
    ap.add_argument("--drain-s", type=float, default=0.05)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np
    from bench import catalog
    from bench.serve import ServeCell
    from repro.compile_cache import setup_compile_cache
    cell = catalog.cell(args.workload)
    setup_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      cell["config"]["precision"]["matmul"])
    c = ServeCell(cell, args.seed)
    c.setup()
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        n = int(round(rate * args.seconds))
        lat, late, _, steps, wall = c._serve(c._stream(n, args.seconds))
        done = lat[~np.isnan(lat)] * 1e3
        row = {
            "rate_per_s": rate, "requests": n, "answered": int(done.size),
            "steps": steps, "p50_ms": float(np.percentile(done, 50)),
            "p95_ms": float(np.percentile(done, 95)),
            "p99_ms": float(np.percentile(done, 99)),
            "late_p95_ms": float(np.percentile(late, 95) * 1e3),
            "drain_s": wall - args.seconds}
        print(json.dumps(row), flush=True)
        if row["answered"] < n or row["p95_ms"] > args.limit_ms \
                or row["drain_s"] > args.drain_s:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee, "limit_ms": args.limit_ms,
                      "drain_s": args.drain_s}), flush=True)


if __name__ == "__main__":
    main()
