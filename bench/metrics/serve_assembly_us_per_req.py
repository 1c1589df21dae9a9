"""Host wall time of request assembly per request, read from the
program: the union of its ``devertifl.submit`` and ``devertifl.offer``
spans in the traced window, over the ``submit`` spans there (profiler
trace: bench.scopes).  The twin of ``serve_host_us_per_req``, which
the harness times around its own calls."""
from bench import scopes, trace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    submits = scopes.spans(tr, "submit")
    if not submits:
        return None
    spans = submits + scopes.spans(tr, "offer")
    return trace.busy_ns(spans) * 1e-3 / len(submits)
