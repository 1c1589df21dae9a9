"""Host time of request assembly per request: the benchmark's own
clock around its ``submit`` and ``offer`` calls into the server, over
the window's requests."""


def read(ctx):
    c = ctx["run"].get("counters")
    if not c or not c.get("requests"):
        return None
    return c["assembly_s"] * 1e6 / c["requests"]
