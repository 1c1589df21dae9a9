"""Share of the traced serving window in which no operation ran on the
device, averaged over the chips (profiler trace)."""
from bench import trace


def read(ctx):
    tr = ctx.get("trace")
    idle = None if tr is None else trace.idle_share(tr)
    return None if idle is None else 100.0 * idle
