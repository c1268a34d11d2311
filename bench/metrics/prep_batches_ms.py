"""Milliseconds per round in the program's ``prep.batches`` span, inside
``prep``: each device's batch gather, its host-to-device transfer and
the round's key split (``_prep_from_channel``).  The summed
``span.prep.batches`` histogram over the window's rounds, divided by
them; nothing to read where the program has no such span."""


def read(ctx):
    return ctx.span_ms("prep.batches")
