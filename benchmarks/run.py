# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV covering: Fig 3-7 (F1 curves), Table II (literature comparison),
# kernel micro-benchmarks, and the roofline table from the dry-run.
# ``--report`` instead aggregates every benchmarks/results/BENCH_*.json
# trajectory into one chronological, git-SHA-keyed perf table.
from __future__ import annotations

import argparse
import sys
import time


def _fmt_num(v):
    if isinstance(v, int):
        return str(v)
    return f"{v:.4g}"


def _headline(entry, max_items=6):
    """A few representative numeric scalars from one trajectory entry
    (top level, plus one dict level down), in insertion order."""
    skip = {"date", "git_sha", "backend", "smoke", "config",
            "spec_hash", "spec_hashes", "lanes", "devices"}
    out = []
    for k, v in entry.items():
        if len(out) >= max_items:
            break
        if k in skip or isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out.append(f"{k}={_fmt_num(v)}")
        elif isinstance(v, dict):
            for k2, v2 in v.items():
                if len(out) >= max_items:
                    break
                if isinstance(v2, bool) or k2 in skip:
                    continue
                if isinstance(v2, (int, float)):
                    out.append(f"{k}.{k2}={_fmt_num(v2)}")
                elif isinstance(v2, dict) and \
                        isinstance(v2.get("steps_per_sec"),
                                   (int, float)):
                    out.append(
                        f"{k}.{k2}="
                        f"{_fmt_num(v2['steps_per_sec'])}/s")
    return out


def trajectory_report(results_dir=None) -> int:
    """Print the accumulated perf trajectories: one section per
    BENCH_*.json, one dated git-SHA-keyed line per appended entry
    (append order IS chronological -- the files are append-only)."""
    import glob
    import json
    import os
    d = results_dir or os.path.join(os.path.dirname(__file__),
                                    "results")
    paths = sorted(glob.glob(os.path.join(d, "BENCH_*.json")))
    if not paths:
        print(f"no BENCH_*.json trajectories under {d}; run the "
              "benches first (python -m benchmarks.run)")
        return 1
    for path in paths:
        try:
            with open(path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError) as e:
            print(f"\n== {os.path.basename(path)}: unreadable ({e})")
            continue
        data = data if isinstance(data, list) else [data]
        print(f"\n== {os.path.basename(path)} ({len(data)} entries)")
        for e in data:
            if not isinstance(e, dict):
                continue
            flag = " smoke" if e.get("smoke") else ""
            print(f"  {str(e.get('date', '?'))[:19]:<20}"
                  f"{str(e.get('git_sha', '?')):<18}"
                  + " ".join(_headline(e)) + flag)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(
        description="One function per paper table/figure; prints "
                    "name,us_per_call,derived CSV rows.")
    ap.add_argument("--paper", action="store_true",
                    help="full client range 2..10, 3 seeds (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast perf-regression canary (~1 min): runs ONLY "
                         "the protocol lane (engine + schedule + sweep "
                         "throughput), the staleness schedule sweep, the "
                         "fault-tolerance sweep, the wire-transform "
                         "sweep, the serving offered-load sweep, and "
                         "the obs tap-overhead lane at toy sizes and "
                         "skips the figures, table2, kernels, roofline, "
                         "and ablations lanes; nothing is written to "
                         "benchmarks/results/. Paired with the 'fast' "
                         "pytest marker in scripts/ci.sh.")
    ap.add_argument("--only", default=None,
                    help="comma list of lanes to run: figures,table2,"
                         "kernels,roofline,ablations,protocol,staleness,"
                         "faults,wire,serving,obs (default: all; "
                         "incompatible with --smoke)")
    ap.add_argument("--report", action="store_true",
                    help="print the accumulated BENCH_*.json perf "
                         "trajectories (dated, git-SHA-keyed) instead "
                         "of running anything")
    args = ap.parse_args()
    if args.report:
        if args.smoke or args.only:
            ap.error("--report only reads benchmarks/results/; drop "
                     "--smoke/--only")
        sys.exit(trajectory_report())
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    which = set((args.only or
                 "figures,table2,kernels,roofline,ablations,protocol,"
                 "staleness,faults,wire,serving,obs,analysis")
                .split(","))
    if args.smoke:
        if args.only:
            ap.error("--smoke runs only the protocol + staleness + "
                     "faults + wire + serving + obs + analysis lanes; "
                     "drop --only")
        which = {"protocol", "staleness", "faults", "wire", "serving",
                 "obs", "analysis"}

    rows = []
    t0 = time.time()
    if "analysis" in which:
        # static-audit smoke: taint/deadness/retrace over the sync x
        # slice subset (the full grid is the CI `analysis` lane).  A
        # violation here is a correctness regression, not a perf one,
        # so it aborts the bench rather than printing a row quietly.
        from repro.analysis.audit import audit_combos
        ta = time.time()
        report = audit_combos(schedules=("sync",),
                              first_layers=("slice",),
                              lane_check=False)
        if not report.ok:
            print(report.summary(), file=sys.stderr)
            sys.exit(1)
        rows.append(("analysis/audit_smoke",
                     f"{(time.time()-ta)*1e6:.0f}",
                     f"combos={len(report.combos)}_traces="
                     f"{report.static_round_traces}"))
    if "protocol" in which:
        from benchmarks import protocol_bench
        rows += protocol_bench.run(smoke=args.smoke)
    if "staleness" in which:
        from benchmarks import staleness
        rows += staleness.run(smoke=args.smoke)
    if "faults" in which:
        from benchmarks import faults
        rows += faults.run(smoke=args.smoke)
    if "wire" in which:
        import os
        import tempfile

        from benchmarks import wire
        # the wire bench appends even under --smoke (its entry is the
        # deliverable); keep the smoke entry out of benchmarks/results/
        rows += wire.run(
            smoke=args.smoke,
            results_path=os.path.join(tempfile.mkdtemp(),
                                      "BENCH_wire.json")
            if args.smoke else None)
    if "serving" in which:
        from benchmarks import serving
        rows += serving.run(smoke=args.smoke)
    if "obs" in which:
        import os
        import tempfile

        from benchmarks import obs
        # like the wire lane: the obs bench appends even under --smoke
        # (its entry is the deliverable); keep smoke entries out of
        # benchmarks/results/
        rows += obs.run(
            smoke=args.smoke,
            results_path=os.path.join(tempfile.mkdtemp(),
                                      "BENCH_obs.json")
            if args.smoke else None)
    if "kernels" in which:
        from benchmarks import kernels_bench
        rows += kernels_bench.run()
    if "roofline" in which:
        from benchmarks import roofline_table
        rows += roofline_table.run()
    if "table2" in which:
        from benchmarks import table2
        rows += table2.run()
    if "figures" in which:
        from benchmarks import figures
        rows += figures.main(paper=args.paper)
    if "ablations" in which:
        from benchmarks import ablations
        rows += ablations.run()

    print("name,us_per_call,derived")
    for r in rows:
        print(",".join(str(x) for x in r))
    print(f"# total wall time: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == '__main__':
    main()
