"""Per cent of the chip's bf16 peak that the whole round reaches: the
model FLOPs of the window's rounds (tau SGD steps on b images per device
and the Eq. 10 forward over each device's first batch, counted from the
model's shapes in ``harness.flops``) over the window's seconds times the
peak."""
from harness import flops


def read(ctx):
    if ctx.peaks is None or not ctx.rounds:
        return None
    t = ctx.traffic
    f = flops.round_core_flops(ctx.ref.layers(ctx.cfg), t["num_cells"],
                               t["num_devices"], t["tau"], t["batch_size"])
    return 100.0 * f * ctx.rounds / (ctx.window_s * ctx.peaks["bf16_flops"])
