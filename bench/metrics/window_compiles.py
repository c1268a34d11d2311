"""Programs JAX built inside the measured window, compiled or loaded
from the persistent cache (JAX's backend-compile monitoring event)."""


def read(ctx):
    return ctx.window_compiles
