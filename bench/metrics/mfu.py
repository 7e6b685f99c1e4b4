"""Model FLOP/s utilisation of the whole step: model FLOPs per example
(the configuration's formula: forward and backward of non-private
training, no recompute, no side channel) times the examples per second of
the measured (untraced) window, over chips times the bf16 peak."""


def read(ctx):
    if ctx.peak is None or ctx.window_s <= 0:
        return None
    rate = ctx.steps * ctx.examples_per_step / ctx.window_s
    return 100.0 * ctx.flops_per_example * rate / (
        ctx.chips * ctx.peak["bf16_flops"])
