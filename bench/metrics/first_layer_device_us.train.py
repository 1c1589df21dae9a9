"""Device time of the first layer (the ``vfl_matmul`` kernel, the
relayouts of its inputs, and its backward pass): the operations under
the named scope ``first_layer`` inside the round program, per training
step the traced window ran, averaged over the chips (profiler trace,
scopes from the compiled round program: bench.scopes)."""
from bench import scopes


def read(ctx):
    steps = ctx["run"].get("traced_steps")
    ns = scopes.round_scope(ctx, "first_layer") if steps else None
    return None if ns is None else ns * 1e-3 / steps
