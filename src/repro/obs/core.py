"""The ``Obs`` facade: one tracer + one registry + the sink fan-out.

Trainers hold an ``Obs`` built by ``from_config(FLConfig.obs)``.  With
observability off (the default) that is the shared ``DISABLED``
singleton: ``span()`` returns the no-op null span, ``pull()`` is plain
``jax.device_get``, and every emit helper is guarded by
``if obs.enabled`` at the call site — the fault-free round is
bitwise-identical to the uninstrumented trainer and pays no measurable
per-round cost.

XLA compiles are counted by one process-wide listener on JAX's
backend-compile event, registered when the first facade is enabled: it
feeds ``xla.compiles_total`` and ``xla.compile_seconds_total`` of every
enabled facade alive, so programs built anywhere in the process (round
core, finalize, the scheduler's f64 programs, eager ops) are counted.

``DEFAULT`` is the process-wide facade used by library code that has no
trainer handle (e.g. ``core.scheduling.solve_many`` when called without
``obs=``).  It starts disabled; ``enable_default()`` arms it.
"""
from __future__ import annotations

import weakref
from typing import Dict, List, Optional

import jax

from repro.obs.config import ObsConfig
from repro.obs.metrics import Registry
from repro.obs.sinks import ConsoleSink, JSONLSink, MemorySink
from repro.obs.tracing import Tracer

# JAX's event around every backend compile or persistent-cache load
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compile_watchers: "weakref.WeakSet[Obs]" = weakref.WeakSet()
_compile_listener_registered = False


def _on_duration_event(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    for obs in list(_compile_watchers):
        if obs.enabled:
            obs.metrics.counter("xla.compiles_total").inc()
            obs.metrics.counter("xla.compile_seconds_total").inc(duration)


def _watch_compiles(obs: "Obs") -> None:
    """Count the process's XLA compiles into ``obs`` (the listener is
    registered with JAX once, on the first enabled facade)."""
    global _compile_listener_registered
    if not _compile_listener_registered:
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _compile_listener_registered = True
    _compile_watchers.add(obs)


class Obs:
    def __init__(self, enabled: bool = True,
                 registry: Optional[Registry] = None, sinks=()):
        self.enabled = enabled
        self.metrics = registry if registry is not None else Registry()
        self.tracer = Tracer(self.metrics, enabled=enabled)
        self.sinks = list(sinks)
        if enabled:
            _watch_compiles(self)

    # ------------------------------------------------------------------
    # tracing
    def span(self, name: str, **tags):
        return self.tracer.span(name, **tags)

    def trace(self, name: str):
        return self.tracer.trace(name)

    def pull(self, tree, name: str):
        """``jax.device_get(tree)`` under the span ``name``: the host's
        wait for the device work behind ``tree`` plus the copy.  Every
        device->host pull of a round goes through here (``core.pull``,
        ``finalize.pull``, ``schedule.pull``)."""
        with self.tracer.span(name):
            return jax.device_get(tree)

    # ------------------------------------------------------------------
    # sinks
    def emit(self, record: Dict) -> None:
        for sink in self.sinks:
            sink.emit(record)

    def round_record(self, record: Dict) -> Dict:
        """Attach the drained span breakdown to ``record`` and emit it.

        ``phases`` maps each depth-1 span name to its summed seconds;
        ``round_s`` is the enclosing depth-0 ``round`` span's duration,
        so consumers can check that the phases cover the round;
        ``sched_pulls`` counts the scheduler's device->host pulls (its
        ``schedule.pull`` spans), which the trainers' host-sync count
        leaves out."""
        phases: Dict[str, float] = {}
        round_s = None
        sched_pulls = 0
        for s in self.tracer.drain():
            if s.depth == 0 and s.name == "round":
                round_s = s.seconds
            elif s.depth == 1:
                phases[s.name] = phases.get(s.name, 0.0) + s.seconds
            if s.name == "schedule.pull":
                sched_pulls += 1
        out = dict(record)
        out.setdefault("kind", "round")
        out["sched_pulls"] = sched_pulls
        if phases:
            out["phases"] = phases
        if round_s is not None:
            out["round_s"] = round_s
        self.emit(out)
        return out

    def records(self) -> List[Dict]:
        """Records held by the first memory sink ([] if none)."""
        for sink in self.sinks:
            if isinstance(sink, MemorySink):
                return sink.records()
        return []

    def close(self) -> None:
        for sink in self.sinks:
            sink.close()


# Shared no-op facade for every obs-disabled trainer.  Never written to
# (all writers guard on ``enabled``), so sharing is safe.
DISABLED = Obs(enabled=False)

# Process-wide facade for code without a trainer handle.  Disabled
# until ``enable_default()``.
DEFAULT = Obs(enabled=False)


def enable_default(sinks=()) -> Obs:
    """Arm the process-wide ``DEFAULT`` facade (idempotent)."""
    DEFAULT.enabled = True
    DEFAULT.tracer.enabled = True
    _watch_compiles(DEFAULT)
    for s in sinks:
        DEFAULT.sinks.append(s)
    return DEFAULT


def from_config(cfg: Optional[ObsConfig]) -> Obs:
    """Build a facade from ``FLConfig.obs`` (the shared ``DISABLED``
    singleton when off — no per-trainer state at all)."""
    if cfg is None or not cfg.enabled:
        return DISABLED
    sinks = []
    if cfg.ring_size:
        sinks.append(MemorySink(cfg.ring_size))
    if cfg.jsonl_path is not None:
        sinks.append(JSONLSink(cfg.jsonl_path))
    if cfg.console:
        sinks.append(ConsoleSink())
    return Obs(enabled=True, sinks=sinks)
