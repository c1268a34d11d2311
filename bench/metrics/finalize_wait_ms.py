"""Milliseconds per round the host waits in finalize's pull
(``span.finalize.pull``, inside ``finalize``): the device-to-host copy
of the Eq. 12 norms, which blocks until the finalize program has run on
the chip.  Nothing to read where the program has no such span."""


def read(ctx):
    return ctx.span_ms("finalize.pull")
