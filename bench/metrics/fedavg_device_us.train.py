"""Device time of FedAvg, the parties' parameter average that ends each
round: the operations under the named scope ``fedavg`` inside the
round program, per round (round programs in the traced window),
averaged over the chips (profiler trace, scopes from the compiled
round program: bench.scopes)."""
from bench import scopes


def read(ctx):
    ns = scopes.round_scope(ctx, "fedavg")
    rounds = scopes.rounds(ctx["trace"]) if ns is not None else 0
    return ns * 1e-3 / rounds if rounds else None
