"""One run of one cell: set-up, the measured window, the traced
sub-window, and the comparison that decides ``correct``."""
from __future__ import annotations

import gc
import resource
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, Optional

import jax
import numpy as np

from harness import check, reference, spec, trace, world
from harness.peaks import peaks

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

CHECK_ROUNDS = 3             # rounds the reference follows, run in set-up
WARM_ROUNDS = 5              # rounds run before the window, checked ones too
CHECK_WINDOW_INSTANCES = 24  # window P1 instances re-solved, drawn by seed


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def device_info(chips: int, require_chip: bool) -> Dict:
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCount:
    """Programs JAX builds, compiled or loaded from the persistent cache
    (JAX's own monitoring event, one per program)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        from jax import monitoring

        def listen(event, duration, **_):
            if event == COMPILE_EVENT:
                self.count += 1
                self.seconds += duration

        monitoring.register_event_duration_secs_listener(listen)


def enable_cache(root: Path = spec.ROOT) -> str:
    """JAX's persistent compile cache at a fixed path inside the
    checkout, every program kept however short its compile."""
    where = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def _block(trainer):
    jax.block_until_ready([c.params for c in trainer.cells])


def _span_totals(trainer) -> Dict[str, tuple]:
    """(summed seconds, count) of every ``span.*`` histogram."""
    return {name[5:]: (h.sum, h.count)
            for name, h in trainer.obs.metrics.histograms.items()
            if name.startswith("span.")}


def _program_round(trainer, recs) -> list:
    """Per cell {"loss", "dev_losses", "sigma_hat", "params"} after one
    round.  After the first round a cell's ``cum_loss`` (its per-device
    loss statistic, zero before) holds each available device's loss."""
    return [{"loss": r["mean_local_loss"], "sigma_hat": r["sigma_hat"],
             "dev_losses": c.cum_loss[c.cum_loss != 0].copy(),
             "params": jax.device_get(c.params)}
            for r, c in zip(recs, trainer.cells)]


def run(workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, require_chip: bool = True,
        fault: Optional[str] = None, root: Path = spec.ROOT,
        bench_dir: Path = spec.BENCH_DIR, log=None) -> Dict:
    """Runs the cell once and returns the result line's object."""
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    bench = spec.load_benchmark(root)
    w = spec.find_workload(bench, workload)
    cfg, ref = spec.load_config(w["config"], bench_dir)
    traffic = spec.load_traffic(w["traffic"], bench_dir)
    limits = spec.load_limits(workload, bench_dir)
    readers = spec.metric_readers(bench, workload, bench_dir) if traced \
        else {}
    device = device_info(w["chips"], require_chip)
    # set-up is timed from here: the wait for the runtime to hold the
    # chip is the same for every program and carries most of the spread
    t_ready = time.perf_counter()
    log(f"device: {device}; compile cache: {enable_cache(root)}")
    clock = CompileCount()

    def phase(name):
        log(f"set-up: {name} at {time.perf_counter() - t_start:.3f} s, "
            f"{clock.count} programs built ({clock.seconds:.3f} s)")

    from repro.core import scheduling as S
    phase("jax ready")
    wd = world.build(cfg, ref, traffic, seed, obs=traced, fault=fault)
    tr = wd.trainer
    C = traffic["num_cells"]
    phase("data, weights and trainer built")
    with check.P1Recorder(S, flip=(fault == "flip_mask")) as p1:
        checked, prog_rounds = checked_rounds(wd, p1)
        n_checked = len(p1.solved)
        phase("checked rounds run")
        world.warm_fix_sums(p1.real, traffic, cfg["num_classes"])
        phase("scheduler fix-sums warmed")
        for j in range(CHECK_ROUNDS, WARM_ROUNDS):
            tr.run_round(j)
        _block(tr)
        j = WARM_ROUNDS
        phase("warm-up rounds run")

        # the measured window
        setup_s = time.perf_counter() - t_ready
        spans0, compiles0 = _span_totals(tr), clock.count
        times, slowest = [], RoundHost()
        t0 = time.perf_counter()
        while True:
            ts, host = time.perf_counter(), RoundHost.now(p1)
            tr.run_round(j)
            tb = time.perf_counter()
            _block(tr)
            te = time.perf_counter()
            times.append(te - ts)
            if te - ts > slowest.wall:
                slowest = RoundHost.since(host, p1, j, te - ts, te - tb)
            j += 1
            if te - t0 >= seconds:
                break
        window_s = te - t0
        window_compiles = clock.count - compiles0
        spans1 = _span_totals(tr)
        n_window = len(times)
        log(f"window: {n_window} rounds in {window_s:.3f} s, "
            f"{window_compiles} programs built")

        tr_data = None
        if traced:
            tdir = str(root / ".bench_trace")
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(tdir, profiler_options=trace.options())
            for k in range(traffic["trace_rounds"]):
                with jax.profiler.StepTraceAnnotation(trace.STEP_NAME,
                                                      step_num=k):
                    tr.run_round(j)
                    _block(tr)
                j += 1
            jax.profiler.stop_trace()
            tr_data = trace.load(tdir)
            shutil.rmtree(tdir, ignore_errors=True)

    # the TPU runtime keeps the programs' scratch memory as reserved
    # bytes, outside the bytes in use by arrays
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0)
                                      + stats.get("peak_bytes_reserved", 0))

    # the program's state is freed before the reference runs
    window_solved = p1.solved[n_checked:]
    rng = np.random.default_rng(seed)
    k = min(CHECK_WINDOW_INSTANCES, len(window_solved))
    sample = [window_solved[i] for i in
              sorted(rng.choice(len(window_solved), k, replace=False))]
    solved_checked = p1.solved[:n_checked]
    w0 = jax.device_get(wd.weights)
    inputs, labels = wd.inputs, wd.labels
    del wd, tr, p1
    gc.collect()

    t_ref = time.perf_counter()
    refs = reference_rounds(ref, cfg, traffic, w0, inputs, labels, checked,
                            solved_checked)
    numbers = gaps(w0, prog_rounds, refs)
    numbers["mask_mismatches"] = float(check.mask_mismatches(
        solved_checked + sample))
    correct = check.judge(numbers, limits["limits"])
    log(f"check: reference and solver took "
        f"{time.perf_counter() - t_ref:.3f} s")

    result = {"correct": bool(correct), "attempted": n_window * C,
              "failed": 0, "device": device}
    if traced:
        ctx = Context(cfg=cfg, ref=ref, traffic=traffic, device=device,
                      rounds=n_window, window_s=window_s,
                      spans=_span_delta(spans0, spans1),
                      window_compiles=window_compiles, trace=tr_data)
        result["metrics"] = {}
        for name, (m, read) in readers.items():
            v = read(ctx)
            if v is not None:
                result["metrics"][name] = {"value": v, "unit": m["unit"]}
        lo, hi = trace.window(tr_data)
        device["busy_s"] = trace.busy_ns(tr_data["device_ops"], lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {
            "device_ops": trace.top_ops(tr_data["device_ops"]),
            "idle_gaps": trace.top_gaps(tr_data)}
    else:
        e2e = {"rounds_per_s": n_window / window_s,
               "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec.end_to_end(bench, workload)}
    log(f"round wall ms: median {1e3 * statistics.median(times):.3f}; "
        f"slowest {slowest}; xla programs built in all {clock.count} "
        f"({clock.seconds:.3f} s); set-up from process start "
        f"{setup_s + t_ready - t_start:.3f} s")
    result["check"] = {n: {"value": numbers[n],
                           "limit": limits["limits"][n]["limit"]}
                       for n in limits["limits"]}
    return result


class RoundHost:
    """Where the host's time in one window round went: its wall time,
    the process's CPU time (every thread), the time in ``solve_many``,
    the wait for the device after ``run_round`` returned, and the page
    faults and context switches the OS counted (a diagnostic of slow
    rounds, logged for the slowest)."""

    def __init__(self, **kw):
        self.wall = 0.0
        self.__dict__.update(kw)

    @staticmethod
    def now(p1):
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (time.process_time(), p1.seconds, ru.ru_majflt, ru.ru_minflt,
                ru.ru_nvcsw, ru.ru_nivcsw)

    @classmethod
    def since(cls, start, p1, j, wall, wait):
        d = [b - a for a, b in zip(start, cls.now(p1))]
        return cls(round=j, wall=wall, cpu=d[0], solve=d[1], wait=wait,
                   faults=(d[2], d[3]), switches=(d[4], d[5]))

    def __str__(self):
        if not self.wall:
            return "none"
        return (f"round {self.round}: {1e3 * self.wall:.3f} ms wall, "
                f"{1e3 * self.cpu:.3f} ms process CPU, {1e3 * self.solve:.3f} "
                f"ms in solve_many, {1e3 * self.wait:.3f} ms waiting for the "
                f"device; page faults major/minor {self.faults[0]}/"
                f"{self.faults[1]}, context switches voluntary/involuntary "
                f"{self.switches[0]}/{self.switches[1]}")


def checked_rounds(wd, p1):
    """The first rounds, through the window's own call, with the rows
    every device gathers recorded for the reference.  Returns
    ([(takes, records, instances solved so far)], per round the
    program's per-cell {"loss", "sigma_hat", "params"})."""
    tr = wd.trainer
    checked, prog_rounds = [], []
    for j in range(CHECK_ROUNDS):
        wd.recorder.log = []
        recs = tr.run_round(j)
        _block(tr)
        takes, wd.recorder.log = wd.recorder.log, None
        checked.append((takes, recs, len(p1.solved)))
        prog_rounds.append(_program_round(tr, recs))
    return checked, prog_rounds


def reference_rounds(ref, cfg, traffic, w0, inputs, labels, checked,
                     solved, dtype=None, precision=None) -> list:
    """Per cell, the reference followed over the checked rounds, with
    the uploads the reference solver picks on the program's P1
    instances.  Cell c's seed is the deployment's ``channel_seed`` + c,
    and its round keys are split over the most devices any cell has
    available that round, as the program pads its cells."""
    import jax.numpy as jnp
    C = traffic["num_cells"]
    out = []
    for c in range(C):
        keys = reference.device_keys(
            traffic["channel_seed"] + c,
            [max(r["num_available"] for r in recs) for _, recs, _ in checked])
        rounds = []
        for (takes, recs, n_solved), k in zip(checked, keys):
            # gathers come cell by cell, one per available device
            n = recs[c]["num_available"]
            start = sum(r["num_available"] for r in recs[:c])
            dev_takes = takes[start:start + n]
            alg, inst, _ = solved[n_solved - C + c]
            rounds.append({"takes": np.stack(dev_takes), "keys": k[:n],
                           "upload": check.sched_ref.SOLVERS[alg](inst)})
        out.append(reference.follow(
            ref, cfg, w0, inputs, labels, rounds, traffic["eta"],
            dtype=dtype or jnp.float32,
            precision=precision or jax.lax.Precision.HIGHEST,
            chunk=cfg["reference_chunk"]))
    return out


def gaps(w0, prog_rounds, refs) -> Dict:
    """The worst cell's gaps; ``prog_rounds`` is per round, per cell."""
    worst: Dict[str, float] = {}
    for c, ref_rounds in enumerate(refs):
        g = check.compare(w0, [p[c] for p in prog_rounds], ref_rounds)
        for k, v in g.items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.peaks = peaks(self.device["kind"]) \
            if self.device["platform"] == "tpu" else None

    def span_ms(self, name: str) -> Optional[float]:
        s = self.spans.get(name)
        if not s or not s[1] or not self.rounds:
            return None
        return 1e3 * s[0] / self.rounds


def _span_delta(a: Dict, b: Dict) -> Dict:
    return {k: (v[0] - a.get(k, (0.0, 0))[0], v[1] - a.get(k, (0.0, 0))[1])
            for k, v in b.items()}
