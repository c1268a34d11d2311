"""Milliseconds per round in the program's ``schedule`` phase: the summed
``span.schedule`` histogram over the window's rounds, divided by them."""


def read(ctx):
    return ctx.span_ms("schedule")
