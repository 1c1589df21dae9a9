"""Model FLOP/s utilisation of training: the step's matmul FLOPs per
sample (forward and backward, nothing recomputed), as the
configuration's model family counts them (its ``context``; for the MLP
family ``bench.work.step_flops_per_sample``), times this run's
``train_samples_per_s``, over the chips' bf16 peak."""


def read(ctx):
    run = ctx["run"]
    rate, flops = (run.get("train_samples_per_s"),
                   run.get("step_flops_per_sample"))
    if not rate or not flops:
        return None
    return 100.0 * flops * rate / (ctx["chips"]
                                   * ctx["peak"]["bf16_flops_per_s"])
