"""repro.obs — round-phase tracing, metrics registry, sinks, profiling.

The observability substrate for the FL engine:

  * ``Obs`` / ``from_config`` — the facade trainers hold (span tracer +
    metrics registry + sink fan-out); ``DISABLED`` is the shared no-op
    used whenever ``FLConfig.obs.enabled`` is False.
  * ``Registry`` / ``Counter`` / ``Gauge`` / ``Histogram`` — host-side
    metrics with fixed-bucket percentiles; never a device sync.
  * ``MemorySink`` / ``JSONLSink`` / ``ConsoleSink`` — per-round record
    sinks; ``read_jsonl`` / ``format_summary`` for consumers.
  * ``profile_rounds`` — ``jax.profiler`` trace of N steady rounds.

Span names emitted by the trainers: ``round`` (whole round), and its
phases ``prep`` / ``core`` / ``schedule`` / ``upload`` / ``finalize``;
inside them ``prep.batches`` (batch gather, transfer, key split),
``solve_many.<backend>``, and the device->host pulls made through
``Obs.pull``: ``core.pull``, ``schedule.pull``, ``finalize.pull``.
Each span is also a profiler annotation ``fl.<name>``.  Metric names
are documented in ROADMAP.md's Observability section.
"""
from repro.obs.config import ObsConfig  # noqa: F401
from repro.obs.core import (DEFAULT, DISABLED, Obs,  # noqa: F401
                            enable_default, from_config)
from repro.obs.metrics import (COUNT_BUCKETS, TIME_BUCKETS,  # noqa: F401
                               Counter, Gauge, Histogram, Registry)
from repro.obs.profile import profile_rounds  # noqa: F401
from repro.obs.sinks import (ConsoleSink, JSONLSink,  # noqa: F401
                             MemorySink, dumps_record, format_summary,
                             read_jsonl)
from repro.obs.tracing import NULL_SPAN, SpanRecord, Tracer  # noqa: F401
