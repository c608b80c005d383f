"""Model FLOPs of the traced iterations (counts.train_flops: forward,
activation gradients and LoRA gradients, nothing recomputed) over the
traced window times the chips' bf16 peak, in %."""


def read(ctx):
    return 100.0 * ctx["flops"] / (ctx["window_s"] * ctx["chips"]
                                   * ctx["peak"]["bf16_flops"])
