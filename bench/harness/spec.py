"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Under the benchmark's directory (``bench/``):

  configs/<config>.json   the model configuration as it is run
  configs/<config>.py     its plain reference: weights, forward pass, layers
  traffic/<traffic>.json  the deployment: cells, devices, channel, schedule
  limits/<workload>.json  the limits of the numbers that decide ``correct``
  metrics/<metric>.py     one per-layer metric: ``read(ctx) -> float | None``

A new cell is a new entry in ``BENCHMARK.json`` plus new files here; no
existing file changes."""
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
