"""Small versions of the benchmark's cells for the CPU tests: the
configurations and traffic of ``BENCHMARK.json`` with fewer rows, a
lower offered rate and a short warm-up."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import catalog  # noqa: E402

CELL = catalog.cell      # the benchmark's own, kept from patches
SMALL = {"mnist5": dict(rows=1500), "bank2": dict(rows=1500, epochs=2)}


def small_cell(name):
    c = CELL(name)
    cfg = c["config"]
    size = SMALL[cfg["name"]]
    cfg["dataset"]["rows"] = size["rows"]
    if "epochs" in size:
        cfg["training"]["epochs"] = size["epochs"]
    cfg["federation"]["first_layer_lane"] = None   # the CPU runs its own
    if c["traffic"]["kind"] == "serve":
        c["traffic"].update(rate_per_s=300.0, warmup_requests=64,
                            trace_seconds=0.3)
    return c
