"""Per cent of the chip's bf16 peak that the whole round reaches: the
model FLOPs of the window's rounds (one execution of the round core a
round, counted by the configuration through ``harness.flops.round_core``)
over the window's seconds times the peak."""
from harness import flops


def read(ctx):
    if ctx.peaks is None or not ctx.rounds:
        return None
    f, _ = flops.round_core(ctx.ref, ctx.cfg, ctx.traffic)
    return 100.0 * f * ctx.rounds / (ctx.window_s * ctx.peaks["bf16_flops"])
