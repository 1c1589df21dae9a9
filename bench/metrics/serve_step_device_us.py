"""Device time of the serving slot step (``make_serve_step_fn`` under
the server's ``jax.jit``) per step the traced window ran, averaged
over the chips (profiler trace)."""
from bench import trace

STEP = r"counted"


def read(ctx):
    tr, run = ctx.get("trace"), ctx["run"]
    if tr is None or not run.get("traced_steps"):
        return None
    per_chip = [sum(e.dur for e in trace.named(tr.modules.get(c, []),
                                                STEP))
                for c in tr.chips]
    per_chip = [t for t in per_chip if t > 0]
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) * 1e-3 / run["traced_steps"]
