"""Milliseconds per round the host waits in the round core's pull
(``span.core.pull``, inside ``core``): the one device-to-host copy of
the core's losses, sigmas, norms and flags, which blocks until the
round core has run on the chip.  Nothing to read where the program has
no such span."""


def read(ctx):
    return ctx.span_ms("core.pull")
