"""Model FLOP/s utilisation of training: the De-VertiFL step's matmul
FLOPs per sample (forward and backward, nothing recomputed,
bench.work) times this run's ``train_samples_per_s``, over the chips'
bf16 peak."""
from bench import work


def read(ctx):
    run = ctx["run"]
    rate = run.get("train_samples_per_s")
    if not rate:
        return None
    flops = work.step_flops_per_sample(run["widths"], run["hidden"],
                                       run["n_hidden"], run["n_classes"])
    return 100.0 * flops * rate / (ctx["chips"]
                                   * ctx["peak"]["bf16_flops_per_s"])
