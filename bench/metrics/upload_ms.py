"""Milliseconds per round in the program's ``upload`` phase: the summed
``span.upload`` histogram over the window's rounds, divided by them."""


def read(ctx):
    return ctx.span_ms("upload")
