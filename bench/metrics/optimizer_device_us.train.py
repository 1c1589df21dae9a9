"""Device time of the optimizer update (Adam, every party's tower):
the operations under the named scope ``optimizer`` inside the round
program, per training step the traced window ran, averaged over the
chips (profiler trace, scopes from the compiled round program:
bench.scopes)."""
from bench import scopes


def read(ctx):
    steps = ctx["run"].get("traced_steps")
    ns = scopes.round_scope(ctx, "optimizer") if steps else None
    return None if ns is None else ns * 1e-3 / steps
