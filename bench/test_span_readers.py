"""The readers of the program's wait and pull spans (``prep_batches_ms``,
``core_wait_ms``, ``schedule_wait_ms``, ``schedule_pulls``,
``finalize_wait_ms``): on a small context, and in a traced run at a
small size (CPU only).

  python3 -m pytest bench/test_span_readers.py
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cell, spec  # noqa: E402
from test_bench import _no_persistent_cache, _run, small_root  # noqa: E402,F401,E501

# each time reader, the span it reads and the phase metric it lies in
WAITS = {"prep_batches_ms": ("prep.batches", "prep_ms"),
         "core_wait_ms": ("core.pull", "core_ms"),
         "schedule_wait_ms": ("schedule.pull", "schedule_ms"),
         "finalize_wait_ms": ("finalize.pull", "finalize_ms")}


def _read(name, ctx):
    return spec.load_module(BENCH / "metrics" / f"{name}.py",
                            f"bench_metric_{name}").read(ctx)


def _ctx(spans, rounds=4):
    return cell.Context(device={"platform": "cpu"}, spans=spans,
                        rounds=rounds, window_s=1.0)


@pytest.mark.parametrize("name", sorted(WAITS))
def test_wait_reader_is_its_span_per_round(name):
    span = WAITS[name][0]
    assert _read(name, _ctx({span: (0.02, 8), "core": (1.0, 4)})) == \
        pytest.approx(5.0)
    # a program without the span reads nothing, and raises nothing
    assert _read(name, _ctx({"core": (1.0, 4)})) is None
    assert _read(name, _ctx({span: (0.02, 8)}, rounds=0)) is None


def test_schedule_pulls_is_the_span_count_per_round():
    read = lambda spans, rounds=4: _read("schedule_pulls",  # noqa: E731
                                         _ctx(spans, rounds))
    assert read({"schedule.pull": (0.01, 22)}) == 5.5
    assert read({}) is None
    assert read({"schedule.pull": (0.0, 0)}) is None
    assert read({"schedule.pull": (0.01, 3)}, rounds=0) is None


def test_traced_run_reports_each_wait_inside_its_phase(small_root):
    r = _run(small_root, traced=True)
    assert r["correct"], r["check"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name, (_, phase) in WAITS.items():
        assert 0 <= m[name] <= m[phase], name
    assert m["schedule_pulls"] >= 1
    assert r["metrics"]["schedule_pulls"]["unit"] == "pulls"
