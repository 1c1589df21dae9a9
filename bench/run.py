#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; ``bench/catalog.py`` finds their files.  The run makes
its inputs from the seed, builds the system under test, warms up every
program the window runs (set-up, reported as ``setup_s``), measures for
``--seconds``, and then compares what the timed path produced with the
plain reference (``bench/check.py``), which decides ``correct``.  With
``--trace 1`` it also traces a short window of the same work under the
profiler and prints the cell's per-layer metrics instead of its
end-to-end ones.  The last line of standard output is one JSON object.

It exits non-zero, printing no result, when JAX's first device is not
a TPU or there are fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def device_record(chips):
    """The platform, kind and count as JAX reports them; exits when
    they are not the TPU chips the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: JAX's first device is {devs[0].platform!r}, not a "
            "TPU; this benchmark has no CPU fallback")
        sys.exit(3)
    if len(devs) < chips:
        log(f"bench: the cell asks for {chips} chips, JAX finds "
            f"{len(devs)}")
        sys.exit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileCounter:
    """Counts XLA backend compilations and jaxpr traces while ``on``."""

    def __init__(self):
        import jax
        self.on, self.compiles, self.traces = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *args, **kw):
        if self.on:
            self.compiles += "backend_compile" in name
            self.traces += "jaxpr_trace" in name


def run_context(job, cell, peak):
    """What the metric readers read about this run: what the model
    family says of its model (``context``), and this run's numbers."""
    cfg = cell["config"]
    run = dict(job.family.context(cfg))
    run["test_rows"] = int(cfg["dataset"]["rows"]
                           * cfg["dataset"]["test_frac"])
    run["batch"] = cfg["training"]["batch_size"]
    run["train_samples_per_s"] = getattr(job, "samples_per_s", None)
    run["counters"] = getattr(job, "counters", None)
    return {"run": run, "config": cfg, "traffic": cell["traffic"],
            "peak": peak, "chips": cell["workload"]["chips"]}


def finite(x):
    """A JSON number, or None for inf and nan."""
    return x if x == x and abs(x) != float("inf") else None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import catalog, check, peaks
    cell = catalog.cell(args.workload)
    cfg, trf = cell["config"], cell["traffic"]
    device = device_record(cell["workload"]["chips"])
    peak = peaks.peak(device["kind"])
    t_devices = time.perf_counter()

    import jax
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    # every program goes into the cache, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision",
                      cfg["precision"]["matmul"])
    compiles = CompileCounter()

    from bench.serve import ServeCell
    from bench.train import TrainCell
    job = {"train": TrainCell, "serve": ServeCell}[trf["kind"]](
        cell, args.seed)
    job.setup()
    setup_s = time.perf_counter() - T_START

    compiles.on = True
    e2e = job.window(args.seconds)
    compiles.on = False
    e2e["setup_s"] = setup_s
    device["memory_peak_bytes"] = memory_peak(cell["workload"]["chips"])

    metrics, breakdown = {}, None
    if args.trace:
        from bench import trace
        ctx = run_context(job, cell, peak)
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                traced = job.traced()
            jax.profiler.stop_trace()
            tr = trace.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx["trace"] = tr
        ctx["run"]["traced_steps"] = traced["steps"]
        for m in cell["per_layer"]:
            value = catalog.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = trace.busy_s(tr) or 0.0
        device["window_s"] = tr.window_s
        breakdown = trace.breakdown(tr)
    else:
        for m in cell["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}

    job.release()
    numbers = job.numbers()
    limits = {k: v for k, v in cfg["limits"].items() if k in numbers}
    correct, rows = check.verdict(numbers, limits)

    marks = [("devices", t_devices)] + job.marks
    log(f"bench: {args.workload} seed {args.seed}: set-up "
        f"{setup_s:.3f} s (" + ", ".join(
            f"{name} at {t - T_START:.3f}" for name, t in marks)
        + f"); inside the window {compiles.compiles} compiles, "
        f"{compiles.traces} traces")
    for line in job.notes():
        log(f"bench: {line}")
    for name, value, limit in rows:
        log(f"check {name} {value!r} limit {limit!r}")

    out = {"correct": correct, "attempted": int(job.attempted),
           "failed": int(job.failed), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": finite(value), "limit": limit}
                     for name, value, limit in rows}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
