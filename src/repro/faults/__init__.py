"""Wireless fault injection + server-side defenses for the round loop.

See ``repro.faults.config.FaultConfig`` for the knobs,
``repro.faults.injector.FaultInjector`` for the per-round realisation,
and ``repro.faults.sanitize`` for the delta screening applied before
Eq. 2 aggregation.
"""
from repro.faults.config import (CORRUPT_MODES, LOSSY_UPLINK,  # noqa: F401
                                 FaultConfig)
from repro.faults.injector import (FAILURE_CAUSES, FaultInjector,  # noqa: F401
                                   RoundFaults)
from repro.faults.sanitize import (SanitizeResult, finite_per_device,  # noqa: F401
                                   sanitize_updates, tree_is_finite)
