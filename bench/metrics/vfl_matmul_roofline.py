"""Share of its roofline that the first-layer kernel ``vfl_matmul``
reaches: the least time the chip could take for the first-layer
forwards the trace shows (each live client's slice only, bench.work)
over the summed device time of the kernel's events.  Kernel calls
inside the round program multiply ``batch`` rows, those inside the
predict program the test rows; the ``bound`` (memory or compute) is
the one that sets the least time."""
from bench import trace, work

KERNEL = r"^vfl_matmul"
PROGRAMS = {"round": r"round_fn", "predict": r"predict"}


def read(ctx):
    tr, run = ctx.get("trace"), ctx["run"]
    if tr is None:
        return None
    widths, hidden, peak = run["widths"], run["hidden"], ctx["peak"]
    rows = {"round": run["batch"], "predict": run["test_rows"]}
    least = spent = 0.0
    for c in tr.chips:
        kernels = trace.named(tr.ops[c], KERNEL)
        for prog, pattern in PROGRAMS.items():
            inside = trace.within(kernels,
                                  trace.named(tr.modules.get(c, []),
                                              pattern))
            calls = len(inside) / len(widths)
            per_call = sum(work.least_seconds(
                *work.first_layer(rows[prog], w, hidden), peak)[0]
                for w in widths)
            least += calls * per_call
            spent += sum(e.dur for e in inside) * 1e-9
    if spent <= 0:
        return None
    return 100.0 * least / spent
