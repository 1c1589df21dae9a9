"""Share of the window's requests that the hot exchange cache answered
(the server's ``ExchangeCache`` hit and miss counters)."""


def read(ctx):
    c = ctx["run"].get("counters")
    if not c:
        return None
    probes = c["cache_hits"] + c["cache_misses"]
    return 100.0 * c["cache_hits"] / probes if probes else None
