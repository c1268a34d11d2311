"""Per cent of its roofline that the round core reaches: the least time
one execution could take on the chip (the larger of its FLOPs over the
bf16 peak and its unavoidable bytes over the HBM bandwidth, both counted
by the configuration through ``harness.flops.round_core``) over the
device time of the round core's executions in the trace.  The round core
is the program ``jit_one_cell`` (``make_round_core`` in
``repro/fl/client.py``)."""
from harness import flops, trace

PROGRAM = r"^jit_one_cell\b"


def read(ctx):
    if not ctx.trace or ctx.peaks is None:
        return None
    total_ns, n = trace.program_time(ctx.trace["device_programs"], PROGRAM)
    if not n or not total_ns:
        return None
    f, b = flops.round_core(ctx.ref, ctx.cfg, ctx.traffic)
    least = max(f / ctx.peaks["bf16_flops"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / (total_ns * 1e-9)
