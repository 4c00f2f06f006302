"""Model FLOPs of the training step (forward and backward, counted from
the configuration by its family's reference, ``train_step_flops``) times
the steps per second of the traced run's untraced window, over the bf16
peak of the chips."""


def read(ctx):
    rate = ctx.get("steps_per_s")
    if not rate:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["step_flops"] * rate / peak
