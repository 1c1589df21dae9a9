"""Host wall time of one serving slot step, ``FederatedServer.step``:
admission, the uploads, the jitted step's dispatch, the blocking
fetch of its results and the completions; the mean of the program's
``devertifl.step`` spans in the traced window (profiler trace:
bench.scopes)."""
from bench import scopes


def read(ctx):
    tr = ctx.get("trace")
    steps = [] if tr is None else scopes.spans(tr, "step")
    if not steps:
        return None
    return sum(e.dur for e in steps) * 1e-3 / len(steps)
