"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Under the benchmark's directory (``bench/``):

  configs/<config>.json   the model configuration as it is run
  configs/<config>.py     its plain reference: weights, loss, sigma, counts
  traffic/<traffic>.json  the deployment: cells, devices, channel, schedule
  limits/<workload>.json  the limits of the numbers that decide ``correct``
  metrics/<metric>.py     one per-layer metric: ``read(ctx) -> float | None``

A new cell is a new entry in ``BENCHMARK.json`` plus new files here; no
existing file changes.

``configs/<config>.json`` always holds ``num_classes`` (P1's class axis:
the labels of the rows) and ``reference_chunk`` (devices the reference
runs at once), and then one of two kinds of input:

  images (no ``inputs`` key)   ``train_images``, ``test_images``,
                               ``image_size``, ``channels``, and
                               ``parameters`` for the default byte count
  ``"inputs": "tokens"``       ``train_sequences``, ``test_sequences``,
                               ``seq_len``, ``vocab_size`` and ``zipf``
                               (``harness.data.tokens``); the label is
                               the row's topic, ``num_classes`` topics

``configs/<config>.py`` defines ``init(key, cfg)`` (the weights, as the
program's own parameter tree, made in one jitted call) and
``program_model(cfg)`` (the program's ``repro.models.Model``), and four
hooks, each optional:

  loss(p, cfg, x, y, precision, key) -> scalar   one device's loss on
      its rows x [b, ...] with labels y [b]; key draws any dropout masks
  sigma(p, cfg, x, y, precision) -> scalar       its Eq. 10 sigma
  round_core_flops(cfg, traffic) -> int          one execution of the
      round core: its FLOPs
  round_core_bytes(cfg, traffic) -> int          and the bytes it cannot
      avoid moving through HBM

A module without ``loss`` and ``sigma`` is a classifier: it defines
``features_logits(p, cfg, x, precision, key=None) -> (features [b, d],
logits [b, classes])``, and the reference takes the mean cross-entropy
and the last-layer sigma (``harness.reference``).  One without the
counts defines ``layers(cfg)``, its layer table (``harness.flops``), and
is counted on f32 images.  A token configuration defines all four hooks.
The reference's functions see the configuration with lists as tuples and
groups as hashable dicts; ``init`` and ``program_model`` see it as read.

``traffic/<traffic>.json`` holds the deployment's ``num_cells``,
``num_devices``, ``available_prob``, ``batch_size``, ``tau``, ``eta``,
``deadline_s``, ``scheduler``, ``scheduler_backend`` and
``channel_seed`` (the program's ``FLConfig``), ``shards_per_device``,
``total_bandwidth_hz``, ``warm_fix_sums`` ([lo, hi], the largest P1
fix-sums warmed before the window) and ``trace_rounds``.
``limits/<workload>.json`` holds ``{"limits": {number: {"limit": x}}}``
over ``harness.check.NUMBERS``, ``mask_mismatches`` always among them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """The benchmark's files do not describe the requested cell."""


def _read_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload named {name!r} in BENCHMARK.json")


def load_config(name: str, bench_dir: Path = BENCH_DIR):
    """(configuration dict, its reference module)."""
    d = Path(bench_dir) / "configs"
    cfg = _read_json(d / f"{name}.json")
    ref = load_module(d / f"{name}.py", f"bench_config_{name}")
    return cfg, ref


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _read_json(Path(bench_dir) / "traffic" / f"{name}.json")


def load_limits(workload: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _read_json(Path(bench_dir) / "limits" / f"{workload}.json")


def metric_readers(bench: dict, workload: str,
                   bench_dir: Path = BENCH_DIR) -> dict:
    """name -> read(ctx) for every per-layer metric of this cell."""
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and workload not in cells:
            continue
        mod = load_module(Path(bench_dir) / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name']}")
        out[m["name"]] = (m, mod.read)
    return out


def end_to_end(bench: dict, workload: str) -> list:
    return [m for m in bench["end_to_end"]
            if m.get("workloads") is None or workload in m["workloads"]]
