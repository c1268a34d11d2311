"""Device-to-host pulls a round that the scheduler makes: the count of
``span.schedule.pull`` in the window over the window's rounds (one for
GS, one a phase for FSCD).  Nothing to read where the program has no
such span."""


def read(ctx):
    s = ctx.spans.get("schedule.pull")
    if not s or not s[1] or not ctx.rounds:
        return None
    return s[1] / ctx.rounds
