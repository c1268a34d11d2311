"""repro.obs: span tracing, metrics registry, sinks, and the trainer
instrumentation.

Key contracts:
  * obs disabled (the default) is free: ``from_config`` returns the
    shared ``DISABLED`` singleton, spans are the no-op ``NULL_SPAN``,
    ``pull`` is plain ``jax.device_get``, and a fault-free run is
    bitwise-identical (history AND params) to an obs-enabled run on
    both scheduler backends;
  * every round emits one record whose ``phases`` (prep/core/schedule/
    upload/finalize) cover ``round_s`` — the spans wrap the whole body;
  * the host-sync contract (<= 3 fault-free, constant in C) is
    asserted through the ``fl.round.host_syncs`` registry gauge;
  * round records carry ``g_refresh_errors_round`` plus the deprecated
    ``g_refresh_errors`` alias (same per-round value; the trainer
    attribute stays cumulative);
  * the compile listener counts every program the process builds (the
    scheduler's too) — steady rounds at a fixed shape add none;
  * enabled spans are profiler annotations ``fl.<name>`` on the host's
    timeline, each ``*.pull`` inside its phase; obs off writes none;
  * the scheduler's device->host pulls go through ``Obs.pull``: one for
    GS, one per FSCD phase, with unchanged masks.
"""
import dataclasses
import math

import jax
import numpy as np
import pytest

import repro.core.estimation as E
from repro.configs.paper_cnn import CNNConfig
from repro.data import (sort_and_partition, synthetic_image_dataset,
                        train_test_split)
from repro.faults import FaultConfig
from repro.fl import FederatedTrainer, FLConfig, MultiCellTrainer
from repro.models import build_model
from repro.obs import (COUNT_BUCKETS, DISABLED, NULL_SPAN, Counter,
                       Gauge, Histogram, JSONLSink, MemorySink, Obs,
                       ObsConfig, Registry, Tracer, dumps_record,
                       format_summary, from_config, profile_rounds,
                       read_jsonl)

LOSSY = FaultConfig(outage_prob=0.3, dropout_prob=0.2,
                    corrupt_prob=0.3, reshadow_std_db=4.0,
                    clip_delta_norm=10.0, backfill=True)

PHASES = ("prep", "core", "schedule", "upload", "finalize")

RECORD_KEYS = ("round", "kind", "phases", "round_s", "host_syncs",
               "sched_pulls",
               "upload_bytes", "sched_iterations", "num_uploaded",
               "num_failed", "failure_causes", "num_sanitized",
               "num_clipped", "num_backfilled",
               "g_refresh_errors_round", "g_refresh_errors")


@pytest.fixture(scope="module")
def micro_world():
    ds = synthetic_image_dataset(num_classes=2, num_per_class=40,
                                 image_size=8, seed=0)
    train, test = train_test_split(ds, seed=0)
    parts = sort_and_partition(train.labels, 8, 1,
                               np.random.default_rng(0))
    model = build_model(CNNConfig(name="micro-cnn", kind="paper_cnn",
                                  num_classes=2, image_size=8,
                                  dropout=False, width=0.25))
    return model, train, test, parts


def micro_cfg(backend="jax", avail=1.0, cells=1, **kw):
    kw.setdefault("scheduler", "fedcgd-fscd")
    return FLConfig(num_devices=8, available_prob=avail, batch_size=2,
                    tau=1, scheduler_backend=backend, eval_every=0,
                    seed=0, num_cells=cells, **kw)


def make_trainer(micro_world, **cfg_kw):
    model, train, test, parts = micro_world
    return FederatedTrainer(model, train, test, parts,
                            micro_cfg(**cfg_kw))


def params_equal(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# metrics unit level


def test_counter_monotone():
    c = Counter("x")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_last_write_wins():
    g = Gauge("x")
    assert math.isnan(g.value)
    g.set(3)
    g.set(7)
    assert g.value == 7.0


def test_histogram_percentiles():
    h = Histogram("t", buckets=(1, 2, 5, 10, 100))
    for v in (1.5, 1.5, 1.5, 1.5, 1.5, 1.5, 1.5, 1.5, 1.5, 80):
        h.observe(v)
    assert h.count == 10
    # p50 rank lands in the (1, 2] bucket -> upper edge 2
    assert h.percentile(0.5) == 2
    # p99 rank is the outlier's bucket; clamped to the observed max
    assert h.percentile(0.99) == 80
    assert h.percentile(0.0) == 1.5
    assert h.percentile(1.0) == 80
    assert h.mean == pytest.approx((9 * 1.5 + 80) / 10)
    with pytest.raises(ValueError):
        h.percentile(50)
    assert math.isnan(Histogram("empty").percentile(0.5))


def test_registry_reset_preserves_identity():
    r = Registry()
    c = r.counter("a")
    g = r.gauge("b")
    h = r.histogram("c", COUNT_BUCKETS)
    c.inc(5)
    g.set(2)
    h.observe(3)
    r.reset()
    assert r.counter("a") is c and c.value == 0
    assert r.gauge("b") is g and math.isnan(g.value)
    assert r.histogram("c") is h and h.count == 0


def test_registry_snapshot_json():
    import json
    r = Registry()
    r.counter("a").inc()
    r.histogram("h").observe(0.5)
    snap = r.snapshot()
    assert snap["counters"]["a"] == 1
    assert snap["histograms"]["h"]["count"] == 1
    json.dumps(snap)        # plain data, serializable


# ---------------------------------------------------------------------------
# tracer unit level


def test_tracer_nesting_and_drain():
    reg = Registry()
    tr = Tracer(reg)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    recs = tr.records
    # children complete (and are recorded) before their parent
    assert [(r.name, r.depth) for r in recs] == [
        ("inner", 1), ("inner", 1), ("outer", 0)]
    assert reg.histogram("span.inner").count == 2
    assert reg.histogram("span.outer").count == 1
    drained = tr.drain()
    assert len(drained) == 3 and tr.records == []


def test_trace_decorator_respects_enabled_flag():
    reg = Registry()
    tr = Tracer(reg, enabled=False)

    @tr.trace("f")
    def f():
        return 42

    assert f() == 42
    assert reg.histogram("span.f").count == 0
    tr.enabled = True
    assert f() == 42
    assert reg.histogram("span.f").count == 1


def test_disabled_facade_is_shared_and_null():
    assert from_config(ObsConfig()) is DISABLED
    assert from_config(None) is DISABLED
    assert DISABLED.span("anything") is NULL_SPAN
    assert DISABLED.pull(jax.numpy.arange(3), "core.pull").tolist() == \
        [0, 1, 2]
    assert not DISABLED.metrics.histograms


def test_obs_config_validation():
    with pytest.raises(ValueError):
        ObsConfig(ring_size=-1)
    with pytest.raises(ValueError):
        ObsConfig(jsonl_path="")


# ---------------------------------------------------------------------------
# sinks


def test_memory_sink_ring():
    s = MemorySink(capacity=3)
    for i in range(5):
        s.emit({"i": i})
    assert [r["i"] for r in s.records()] == [2, 3, 4]
    with pytest.raises(ValueError):
        MemorySink(capacity=0)


def test_jsonl_roundtrip_numpy_types(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = JSONLSink(path)
    rec = {"i": np.int64(3), "f": np.float32(0.5), "b": np.bool_(True),
           "a": np.arange(3), "s": "x"}
    sink.emit(rec)
    sink.close()
    (back,) = read_jsonl(path)
    assert back == {"i": 3, "f": 0.5, "b": True, "a": [0, 1, 2], "s": "x"}
    assert dumps_record(rec) == dumps_record(rec)


def test_round_record_attaches_phase_breakdown():
    obs = Obs(enabled=True, sinks=[MemorySink(8)])
    with obs.span("round"):
        with obs.span("prep"):
            pass
        with obs.span("core"):
            pass
    out = obs.round_record({"round": 0})
    assert out["kind"] == "round"
    assert set(out["phases"]) == {"prep", "core"}
    assert out["round_s"] >= sum(out["phases"].values())
    assert obs.records() == [out]


# ---------------------------------------------------------------------------
# trainer integration


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_enabled_vs_disabled_bitwise(micro_world, backend):
    """Acceptance: observability off/on never changes the training
    trajectory — histories equal, params bitwise, same host syncs."""
    t0 = make_trainer(micro_world, backend=backend)
    h0 = t0.run(3)
    t1 = make_trainer(micro_world, backend=backend,
                      obs=ObsConfig(enabled=True))
    h1 = t1.run(3)
    assert h0 == h1
    assert params_equal(t0.params, t1.params)
    assert t0.last_round_host_syncs == t1.last_round_host_syncs


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("faults", [FaultConfig(), LOSSY],
                         ids=["fault_free", "lossy"])
def test_round_record_schema(micro_world, backend, faults):
    tr = make_trainer(micro_world, backend=backend, faults=faults,
                      obs=ObsConfig(enabled=True))
    tr.run(2)
    recs = tr.obs.records()
    assert len(recs) == 2
    for rec in recs:
        for key in RECORD_KEYS:
            assert key in rec, key
        assert set(rec["phases"]) == set(PHASES)
        assert rec["g_refresh_errors"] == rec["g_refresh_errors_round"]
        # trainer history records never grow obs-only keys
        assert "phases" not in tr.history[rec["round"]]


def test_phases_cover_round_time(micro_world):
    """Acceptance: per-round phase timings sum to within 10% of the
    round wall-clock (everything run_round does is inside a phase)."""
    tr = make_trainer(micro_world, obs=ObsConfig(enabled=True))
    tr.run(3)
    for rec in tr.obs.records():
        assert sum(rec["phases"].values()) >= 0.9 * rec["round_s"]


def test_host_sync_contract_through_registry(micro_world):
    """Acceptance: the <=3-and-constant-in-C host-sync contract is
    asserted through the metrics registry, not an ad-hoc attribute."""
    model, train, test, parts = micro_world
    syncs = {}
    for C in (2, 4):
        mc = MultiCellTrainer(model, train, test, parts,
                              micro_cfg(cells=C,
                                        obs=ObsConfig(enabled=True)))
        for j in range(2):
            mc.run_round(j)
        g = mc.obs.metrics.gauge("fl.round.host_syncs")
        assert g.value <= 3
        assert g.value == mc.last_round_host_syncs
        syncs[C] = g.value
    assert syncs[2] == syncs[4]


def test_multicell_cells_stay_bitwise_with_obs(micro_world):
    """Engine-level observability must not disturb the cells: an
    obs-enabled C=2 run matches the obs-disabled one bitwise."""
    model, train, test, parts = micro_world
    mc0 = MultiCellTrainer(model, train, test, parts, micro_cfg(cells=2))
    mc1 = MultiCellTrainer(model, train, test, parts,
                           micro_cfg(cells=2,
                                     obs=ObsConfig(enabled=True)))
    h0 = [mc0.run_round(j) for j in range(2)]
    h1 = [mc1.run_round(j) for j in range(2)]
    assert h0 == h1
    assert params_equal(jax.device_get(mc0._params_c),
                        jax.device_get(mc1._params_c))
    kinds = [r["kind"] for r in mc1.obs.records()]
    assert kinds.count("multicell_round") == 2
    assert kinds.count("round") == 4          # one per cell per round


def test_g_refresh_errors_round_and_alias(micro_world, monkeypatch):
    """Satellite: the per-round key is ``g_refresh_errors_round`` (the
    deprecated alias carries the same value), the attribute stays
    cumulative, and the registry total matches."""
    def boom(*a, **k):
        raise ValueError("synthetic Eq. 12 failure")
    monkeypatch.setattr(E, "g_hat", boom)
    tr = make_trainer(micro_world, obs=ObsConfig(enabled=True))
    hist = tr.run(2)
    assert all(h["g_refresh_errors_round"] == 1 for h in hist)
    assert all(h["g_refresh_errors"] == 1 for h in hist)
    assert tr.g_refresh_errors == 2
    assert tr.obs.metrics.counter(
        "fl.g_refresh_errors_total").value == 2


def test_compile_metrics_steady_state(micro_world):
    """The compile listener counts programs as they are built: round 0
    pays them, later rounds at the same shape add calls, not compiles."""
    tr = make_trainer(micro_world, obs=ObsConfig(enabled=True))
    tr.run_round(0)
    m = tr.obs.metrics
    compiles0 = m.counter("xla.compiles_total").value
    seconds0 = m.counter("xla.compile_seconds_total").value
    assert compiles0 >= 2            # round core + finalize core
    assert seconds0 > 0
    for j in range(1, 3):
        tr.run_round(j)
    assert m.counter("xla.compiles_total").value == compiles0
    assert m.counter("xla.compile_seconds_total").value == seconds0
    assert m.histogram("span.core.pull").count == 3


def test_solve_many_scheduler_metrics(micro_world):
    tr = make_trainer(micro_world, backend="jax",
                      obs=ObsConfig(enabled=True))
    tr.run(2)
    m = tr.obs.metrics
    assert m.counter("sched.solve_many_calls.jax").value == 2
    assert m.counter("sched.problems_total").value == 2
    assert m.counter("sched.iterations_total").value >= 2
    assert m.histogram("span.solve_many.jax").count == 2


def test_fault_and_failure_metrics(micro_world):
    tr = make_trainer(micro_world, faults=LOSSY,
                      obs=ObsConfig(enabled=True))
    hist = tr.run(3)
    m = tr.obs.metrics
    assert m.counter("faults.rounds_drawn").value == 3
    injected = sum(c.value for name, c in m.counters.items()
                   if name.startswith("faults.injected."))
    assert injected > 0
    causes = {}
    for h in hist:
        for c, n in h["failure_causes"].items():
            causes[c] = causes.get(c, 0) + n
    for cause, n in causes.items():
        assert m.counter(f"fl.failures.{cause}").value == n
    assert m.counter("fl.uploads_total").value == \
        sum(h["num_uploaded"] for h in hist)


def test_jsonl_end_to_end(micro_world, tmp_path):
    """Acceptance: a lossy run with a JSONL sink produces valid JSONL
    with per-round phase timings."""
    path = str(tmp_path / "metrics.jsonl")
    tr = make_trainer(micro_world, faults=LOSSY,
                      obs=ObsConfig(enabled=True, jsonl_path=path))
    tr.run(3)
    tr.obs.close()
    rows = read_jsonl(path)
    assert len(rows) == 3
    for row in rows:
        assert row["kind"] == "round"
        assert set(row["phases"]) == set(PHASES)
        assert sum(row["phases"].values()) >= 0.9 * row["round_s"]
    summary = format_summary(tr.obs.metrics)
    assert "span timings" in summary and "fl.rounds_total" in summary


def test_profile_rounds_smoke(micro_world, tmp_path):
    tr = make_trainer(micro_world)
    try:
        out = profile_rounds(tr, 1, tmp_path / "trace", warmup=1)
    except Exception as exc:        # pragma: no cover - env dependent
        pytest.skip(f"jax.profiler unavailable: {exc}")
    assert len(tr.history) == 2     # warmup + traced round both ran
    import os
    assert os.path.isdir(out)
    with pytest.raises(ValueError):
        profile_rounds(tr, 0, tmp_path / "t2")


def test_compile_listener_counts_scheduler_programs(monkeypatch):
    """The listener sees programs no trainer wraps: a fresh GS program
    counts into every enabled facade, never into ``DISABLED``."""
    from repro.core import scheduling as S
    from repro.core import scheduling_jax as SJ
    monkeypatch.setattr(SJ, "_JIT_CACHE", {})     # force a fresh build
    obs = Obs(enabled=True)
    S.solve_many(_sched_problems(np.random.default_rng(5), 2, 12), "gs",
                 backend="jax", obs=obs)
    m = obs.metrics
    assert m.counter("xla.compiles_total").value >= 1
    assert m.counter("xla.compile_seconds_total").value > 0
    assert not DISABLED.metrics.counters


# ---------------------------------------------------------------------------
# the scheduler's device->host pulls


def _sched_problems(rng, n, V, C=6):
    from repro.core import scheduling as S
    return [S.Problem(p_dev=rng.dirichlet(np.full(C, 0.4), size=V),
                      global_dist=rng.dirichlet(np.full(C, 3.0)),
                      class_weights=rng.uniform(0.5, 1.5, C),
                      sigma=float(rng.uniform(2.0, 6.0)), batch_size=32,
                      min_bw=rng.uniform(0.4, 1.6, V), total_bw=V * 0.5)
            for _ in range(n)]


@pytest.mark.parametrize("algorithm", ["gs", "fscd"])
def test_scheduler_pulls_one_per_dispatch(monkeypatch, algorithm):
    """GS pulls once per ``solve_many`` call, FSCD once per phase, each
    under ``schedule.pull``; masks equal numpy's with obs on and off."""
    from repro.core import scheduling as S
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
    probs = _sched_problems(np.random.default_rng(11), 6, 16)
    want = S.solve_many(probs, algorithm, backend="numpy")
    obs = Obs(enabled=True, sinks=[MemorySink(4)])
    with obs.span("round"):
        got = S.solve_many(probs, algorithm, backend="jax", obs=obs)
    n_dispatch = len(dispatches)
    off = S.solve_many(probs, algorithm, backend="jax", obs=DISABLED)
    for w, g, o in zip(want, got, off):
        assert np.array_equal(w.mask, g.mask)
        assert np.array_equal(w.mask, o.mask)
        assert w.iterations == g.iterations == o.iterations
    pulls = obs.metrics.histogram("span.schedule.pull").count
    if algorithm == "gs":
        assert pulls == n_dispatch == 1
    else:
        assert pulls == n_dispatch > 1
    assert obs.round_record({"round": 0})["sched_pulls"] == pulls


def test_round_records_count_scheduler_pulls(micro_world):
    model, train, test, parts = micro_world
    mc = MultiCellTrainer(model, train, test, parts,
                          micro_cfg(obs=ObsConfig(enabled=True)))
    for j in range(2):
        mc.run_round(j)
    recs = [r for r in mc.obs.records() if r["kind"] == "multicell_round"]
    pulls = mc.obs.metrics.histogram("span.schedule.pull").count
    assert sum(r["sched_pulls"] for r in recs) == pulls >= 2
    assert mc.obs.metrics.histogram("span.core.pull").count == 2
    assert mc.obs.metrics.histogram("span.finalize.pull").count == 2
    assert mc.obs.metrics.histogram("span.prep.batches").count == 2


# ---------------------------------------------------------------------------
# spans on the profiler's timeline

# each inner span and the phase span it lies in
INNER_SPANS = {"fl.prep.batches": "fl.prep", "fl.core.pull": "fl.core",
               "fl.schedule.pull": "fl.schedule",
               "fl.finalize.pull": "fl.finalize"}


def _host_events(trainer, outdir):
    """[(name, start_ns, end_ns)] of the host's events in a profiler
    trace of rounds 1 and 2, taken with the benchmark's trace options
    (no Python tracer, host tracer at its lowest level)."""
    import glob
    import os
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_COMPUTE"}
    trainer.run_round(0)            # builds the programs outside the trace
    jax.profiler.start_trace(str(outdir), profiler_options=opts)
    try:
        for j in (1, 2):
            trainer.run_round(j)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(outdir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_spans_on_the_profiler_host_timeline(micro_world, tmp_path):
    model, train, test, parts = micro_world
    mc = MultiCellTrainer(model, train, test, parts,
                          micro_cfg(obs=ObsConfig(enabled=True)))
    events = _host_events(mc, tmp_path)
    names = [n for n, _, _ in events]
    for name in ("fl.prep", "fl.prep.batches", "fl.core", "fl.core.pull",
                 "fl.schedule", "fl.schedule.pull", "fl.finalize",
                 "fl.finalize.pull"):
        assert name in names, name
    assert names.count("fl.round") == 2
    for inner, outer in INNER_SPANS.items():
        spans = [(a, b) for n, a, b in events if n == outer]
        for n, a, b in events:
            if n == inner:
                assert any(lo <= a and b <= hi for lo, hi in spans), n
    # a profiler step annotation named "round" stays the caller's own
    assert "round" not in names


def test_no_annotations_with_obs_off(micro_world, tmp_path):
    model, train, test, parts = micro_world
    mc = MultiCellTrainer(model, train, test, parts, micro_cfg())
    events = _host_events(mc, tmp_path)
    assert events                       # the trace itself was taken
    assert not [n for n, _, _ in events if n.startswith("fl.")]
