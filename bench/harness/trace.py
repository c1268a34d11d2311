"""Reduction of a profiler trace to device busy time, idle gaps and
per-program time.

``load`` turns the profiler's ``.xplane.pb`` into a plain dict (also the
form of the recorded test trace):

  device_ops      [[name, start_ns, dur_ns]]  events of the "XLA Ops" line
                                              of the first TPU plane, named
                                              "<program>: <HLO op>"
  device_programs [[name, start_ns, dur_ns]]  events of its "XLA Modules" line
  steps           [[step, start_ns, end_ns]]  the benchmark's per-round
                                              ``StepTraceAnnotation``

The trace is taken without the Python tracer and with the host tracer
at its lowest level: their cost would slow the host and inflate the
idle share.  An idle gap is labelled with its round
and the device programs on either side of it, which tells the host phase
it falls in (the program has no trace annotations of its own yet).

Everything after ``load`` is plain arithmetic on those lists."""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

STEP_NAME = "round"


def options():
    """Profiler options: the device's compute events and the host's
    critical events (the step annotations), without the Python tracer,
    the runtime's per-transfer host events or the TPU's sync events,
    whose cost would slow the traced rounds."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_COMPUTE"}
    return opts


def load(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device_ops": [], "device_programs": [], "steps": []}
    device_seen = False
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and not device_seen:
            for line in plane.lines:
                key = {"XLA Ops": "device_ops",
                       "XLA Modules": "device_programs"}.get(line.name)
                if key:
                    out[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
            device_seen = bool(out["device_ops"])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == STEP_NAME:
                        out["steps"].append(
                            [len(out["steps"]), e.start_ns,
                             e.start_ns + e.duration_ns])
    out["steps"].sort(key=lambda s: s[1])
    for i, s in enumerate(out["steps"]):
        s[0] = i
    out["device_programs"].sort(key=lambda e: e[1])
    out["device_ops"] = [[f"{_program_at(out['device_programs'], st)}: "
                          f"{_op_name(n)}", st, d]
                         for n, st, d in out["device_ops"]]
    return out


def _op_name(hlo: str) -> str:
    """'%fusion.16 = (u32[1]...) fusion(...)' -> 'fusion.16'."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _program(name: str) -> str:
    """'jit_one_cell(8236501613946886101)' -> 'jit_one_cell'."""
    return name.split("(", 1)[0]


def _program_at(programs: Sequence, t: float) -> str:
    """The program whose execution covers time ``t`` (programs sorted)."""
    i = bisect.bisect_right([p[1] for p in programs], t) - 1
    if i >= 0 and programs[i][1] + programs[i][2] >= t:
        return _program(programs[i][0])
    return "?"


def window(tr: Dict) -> Tuple[float, float]:
    """(start_ns, end_ns): first round's start to last round's end."""
    return tr["steps"][0][1], tr["steps"][-1][2]


def busy_intervals(events: Sequence, lo: float, hi: float) -> List[list]:
    """Union of the events' intervals, clipped to [lo, hi], sorted."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s + d > lo and s < hi)
    merged: List[list] = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events: Sequence, lo: float, hi: float) -> float:
    return sum(b - a for a, b in busy_intervals(events, lo, hi))


def idle_share(events: Sequence, lo: float, hi: float) -> float:
    return 1.0 - busy_ns(events, lo, hi) / (hi - lo)


def gaps(events: Sequence, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of the device within [lo, hi]."""
    out, t = [], lo
    for a, b in busy_intervals(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def program_time(programs: Sequence, pattern: str) -> Tuple[float, int]:
    """(total ns, executions) of the programs whose name matches."""
    rx = re.compile(pattern)
    hits = [d for name, _, d in programs if rx.search(name)]
    return float(sum(hits)), len(hits)


def top_ops(events: Sequence, n: int = 10) -> List[list]:
    """The n op names with the most device time: [[name, seconds]]."""
    tot: Dict[str, float] = {}
    for name, _, d in events:
        tot[name] = tot.get(name, 0.0) + d
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _label(a: float, b: float, tr: Dict) -> str:
    mid = (a + b) / 2
    step = next((s for s, lo, hi in tr["steps"] if lo <= mid <= hi), None)
    progs = tr["device_programs"]
    inside = [p for p in progs if p[1] <= a and p[1] + p[2] >= b]
    if inside:
        where = f"inside {_program(inside[0][0])}"
        return (f"round {step}: {where}" if step is not None
                else f"between rounds: {where}")
    before = [p for p in progs if p[1] + p[2] <= a + 1]
    after = [p for p in progs if p[1] >= b - 1]
    where = (f"{_program(before[-1][0]) if before else 'start'} -> "
             f"{_program(after[0][0]) if after else 'end'}")
    return (f"round {step}: {where}" if step is not None
            else f"between rounds: {where}")


def top_gaps(tr: Dict, n: int = 10) -> List[list]:
    """The n longest idle gaps, each labelled with its round and the
    device programs that end before it and start after it (or the one
    whose execution it lies inside)."""
    lo, hi = window(tr)
    g = sorted(gaps(tr["device_ops"], lo, hi), key=lambda ab: ab[0] - ab[1])
    return [[_label(a, b, tr), (b - a) * 1e-9] for a, b in g[:n]]
