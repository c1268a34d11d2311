import os
import sys

import pytest

# Tests run on the single real CPU device (the dry-run subprocesses set
# their own XLA_FLAGS). Keep compilation fast + deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root, for the benchmarks.* helpers (mini_fl_world etc.)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def sched_dispatches(monkeypatch):
    """The names of the scheduler programs dispatched while the test
    runs, one entry per call of a ``scheduling_jax._jitted`` program."""
    from repro.core import scheduling_jax as SJ
    dispatches = []
    real = SJ._jitted

    def counting(name, fn, static_argnums=()):
        jitted = real(name, fn, static_argnums)

        def call(*args):
            dispatches.append(name)
            return jitted(*args)
        return call

    monkeypatch.setattr(SJ, "_jitted", counting)
    return dispatches
