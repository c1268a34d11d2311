"""Milliseconds per round the host waits in the scheduler's pulls
(``span.schedule.pull``): GS's one pull and each FSCD phase's pull of
its outputs, each blocking until that f64 program has run on the chip.
Nothing to read where the program has no such span."""


def read(ctx):
    return ctx.span_ms("schedule.pull")
