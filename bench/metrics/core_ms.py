"""Milliseconds per round in the program's ``core`` phase: the summed
``span.core`` histogram over the window's rounds, divided by them."""


def read(ctx):
    return ctx.span_ms("core")
