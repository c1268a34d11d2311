"""Milliseconds per round in the program's ``finalize`` phase: the summed
``span.finalize`` histogram over the window's rounds, divided by them."""


def read(ctx):
    return ctx.span_ms("finalize")
