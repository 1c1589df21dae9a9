"""Device time of the jitted round program (``make_round_fn`` under
``jax.jit``, one ``lax.scan`` per round) per training step it ran,
averaged over the chips (profiler trace)."""
from bench import trace

ROUND = r"round_fn"


def read(ctx):
    tr, run = ctx.get("trace"), ctx["run"]
    if tr is None or not run.get("traced_steps"):
        return None
    per_chip = [sum(e.dur for e in trace.named(tr.modules.get(c, []),
                                                ROUND))
                for c in tr.chips]
    per_chip = [t for t in per_chip if t > 0]
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) * 1e-3 / run["traced_steps"]
