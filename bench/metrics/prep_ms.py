"""Milliseconds per round in the program's ``prep`` phase: the summed
``span.prep`` histogram over the window's rounds, divided by them."""


def read(ctx):
    return ctx.span_ms("prep")
