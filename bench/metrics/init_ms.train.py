"""Host wall time of each call's set-up: one dispatch of the compiled
set-up program (loop key, parameters, optimizer state, step index) and
the schedule state, the program's ``devertifl.init`` spans in the
traced window, per ``Session.run`` call (``devertifl.run`` spans
there) (profiler trace: bench.scopes)."""
from bench import scopes, trace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    calls = scopes.spans(tr, "run")
    inits = scopes.spans(tr, "init")
    if not calls or not inits:
        return None
    return trace.busy_ns(inits) * 1e-6 / len(calls)
