"""Host wall time of evaluation (the test-set predict and its fetch,
then the host F1 and accuracy): the program's ``devertifl.eval`` spans
in the traced window, less the part in which the device still ran the
round program dispatched before them (the fetch waits for it), per
``Session.run`` call (``devertifl.run`` spans there), averaged over
the chips (profiler trace: bench.scopes)."""
from bench import scopes, trace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    calls = scopes.spans(tr, "run")
    evals = scopes.spans(tr, "eval")
    if not calls or not evals:
        return None
    waited = sum(scopes.overlap_ns(evals, trace.named(
        tr.modules.get(c, []), scopes.ROUND)) for c in tr.chips)
    own = trace.busy_ns(evals) - waited / len(tr.chips)
    return own * 1e-6 / len(calls)
