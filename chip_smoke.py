"""Smoke run of the FedCGD round engine on one TPU chip, at paper width.

Runs in one process, on one chip, through the entry points a user calls.
Its phases run in this order, and any failure exits non-zero with no
result line:

  (a) device check: JAX's first device must be a TPU; there is no CPU
      fallback and no interpreted kernel;
  (b) kernels: the WEMD Pallas kernels (``wemd_swap``/``wemd_add``),
      compiled, at the P1 shapes of (c), against ``repro.kernels.ref``;
  (c) training: the paper CNN (``PAPER_CNN_CIFAR10``: 32x32x3 inputs,
      10 classes, 558,226 parameters) on synthetic CIFAR-10-shaped
      images made from ``--seed``, with the ``FLConfig`` defaults
      (V=64, p_a=0.3, b=32, tau=1) and the jax scheduler
      (``solve_many`` with the Pallas kernels): rounds of
      ``FederatedTrainer``, then rounds of a C=4 ``MultiCellTrainer``
      under the lossy-uplink fault mix;
  (d) checks: finite losses and params, scheduled devices, at most 3
      device->host syncs on fault-free rounds, and for every P1 instance
      solved in (c) the jax-backend masks equal the numpy solvers'
      (FSCD as solved, and GS solved again on the chip);
  (e) report: peak device memory, XLA compile seconds and round times,
      labelled with the device kind; the last line of stdout is
      ``{"ok": true, "device": {"platform", "kind", "count"}}``.

  python3 chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# P1 shapes (problems, devices, classes) of the phase (c) world: one
# cell's instance, the C=4 batch, and FSCD lane batches
KERNEL_SHAPES = [(1, 24, 10), (4, 24, 10), (32, 24, 10), (64, 64, 10)]
IMAGES_PER_CLASS = 1000      # 8,000 train / 2,000 test images
ROUNDS = 4                   # rounds of each trainer
MAX_FAULT_FREE_SYNCS = 3


def log(msg: str) -> None:
    print(msg, flush=True)


def check_device(jax) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (first device is "
                 f"{devs[0].platform!r}); nothing was run")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.wemd_swap import wemd_add_pallas, wemd_swap_pallas

    rng = np.random.default_rng(seed)
    for B, V, C in KERNEL_SHAPES:
        p_dev = rng.dirichlet(np.full(C, 0.4), size=(B, V))
        size = 5
        host = (p_dev[:, :size].sum(axis=1), p_dev, p_dev.mean(axis=1),
                rng.uniform(0.5, 1.5, (B, C)), np.full(B, float(size)))
        args32 = [jnp.asarray(a, jnp.float32) for a in host]
        for kernel, oracle in ((wemd_swap_pallas, ref.wemd_swap_ref),
                               (wemd_add_pallas, ref.wemd_add_ref)):
            want = np.asarray(oracle(*args32))
            got = np.asarray(kernel(*args32, interpret=False))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            # f64 inputs under the x64 scope solve_many calls them from
            with jax.enable_x64(True):
                got64 = np.asarray(kernel(*[jnp.asarray(a) for a in host],
                                          interpret=False))
            np.testing.assert_allclose(got64, want, rtol=1e-5, atol=1e-5)
        log(f"kernels ok at (B, V, C) = {(B, V, C)}")


class P1Recorder:
    """Keeps a copy of every P1 instance the trainers hand to
    ``repro.core.scheduling.solve_many`` and of the masks it returns."""

    def __init__(self, S):
        self._S = S
        self.solved = []          # (algorithm, Problem, mask)

    def __enter__(self):
        real = self._real = self._S.solve_many

        def solve_many(problems, algorithm, **kw):
            out = real(problems, algorithm, **kw)
            # the multi-cell pad cache rewrites its buffers in place
            self.solved += [(algorithm, copy.deepcopy(p), s.mask.copy())
                            for p, s in zip(problems, out)]
            return out

        self._S.solve_many = solve_many
        return self

    def __exit__(self, *exc):
        self._S.solve_many = self._real


class CompileClock:
    """Sums XLA backend compile seconds (JAX's own monitoring events)."""

    def __init__(self, jax):
        from jax import monitoring
        self.seconds = 0.0
        self.count = 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        monitoring.register_event_duration_secs_listener(listen)


def timed_rounds(jax, clock, run_round, params_of, rounds):
    """Runs ``rounds`` rounds; returns per-round (wall s, compiles)."""
    out = []
    for j in range(rounds):
        n0 = clock.count
        t0 = time.perf_counter()
        recs = run_round(j)
        jax.block_until_ready(params_of())
        out.append((time.perf_counter() - t0, clock.count - n0, recs))
    return out


def all_finite(jax, params) -> bool:
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(params))


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def train(jax, clock, model_cfg, seed: int, rounds: int):
    """Phase (c): FederatedTrainer rounds, then C=4 lossy
    MultiCellTrainer rounds, with every P1 instance recorded."""
    from repro.core import scheduling as S
    from repro.data import (sort_and_partition, synthetic_image_dataset,
                            train_test_split)
    from repro.faults import LOSSY_UPLINK
    from repro.fl import FederatedTrainer, FLConfig, MultiCellTrainer
    from repro.models import build_model

    cfg = FLConfig(scheduler_backend="jax", seed=seed)
    ds = synthetic_image_dataset(num_classes=model_cfg.num_classes,
                                 num_per_class=IMAGES_PER_CLASS,
                                 image_size=model_cfg.image_size, seed=seed)
    train_ds, test_ds = train_test_split(ds, seed=seed)
    parts = sort_and_partition(train_ds.labels, cfg.num_devices, 1,
                               np.random.default_rng(seed))
    model = build_model(model_cfg)

    with P1Recorder(S) as p1:
        tr = FederatedTrainer(model, train_ds, test_ds, parts, cfg)
        syncs = []

        def fl_round(j):
            rec = tr.run_round(j)
            syncs.append(tr.last_round_host_syncs)
            return [rec]

        fl = timed_rounds(jax, clock, fl_round, lambda: tr.params, rounds)
        for (wall, nc, (rec,)), hs in zip(fl, syncs):
            log(f"FederatedTrainer round {rec['round']}: "
                f"{rec['num_scheduled']}/{rec['num_available']} scheduled, "
                f"loss {rec['mean_local_loss']:.4f}, host syncs {hs}, "
                f"{wall:.3f} s, {nc} compiles")

        mc_cfg = dataclasses.replace(cfg, num_cells=4, faults=LOSSY_UPLINK)
        mc = MultiCellTrainer(model, train_ds, test_ds, parts, mc_cfg)
        mcr = timed_rounds(jax, clock, mc.run_round, lambda: mc._params_c,
                           rounds)
        for wall, nc, recs in mcr:
            log(f"MultiCellTrainer C=4 lossy round {recs[0]['round']}: "
                f"scheduled {[r['num_scheduled'] for r in recs]}, "
                f"uploaded {[r['num_uploaded'] for r in recs]}, "
                f"failed {[r['num_failed'] for r in recs]}, "
                f"backfilled {[r['num_backfilled'] for r in recs]}, "
                f"sanitized {[r['num_sanitized'] for r in recs]}, "
                f"{wall:.3f} s, {nc} compiles")
    return tr, mc, fl, mcr, syncs, p1.solved


def check_training(jax, tr, mc, fl, mcr, syncs, solved) -> None:
    """Phase (d)."""
    from repro.core import scheduling as S

    fl_recs = [recs[0] for _, _, recs in fl]
    mc_recs = [r for _, _, recs in mcr for r in recs]
    require(all(np.isfinite(r["mean_local_loss"])
                for r in fl_recs + mc_recs), "local losses are finite")
    require(all_finite(jax, tr.params), "FederatedTrainer params finite")
    for c, cell in enumerate(mc.cells):
        require(all_finite(jax, cell.params), f"cell {c} params finite")
    require(all(r["num_scheduled"] > 0 for r in fl_recs),
            "every FederatedTrainer round schedules devices")
    require(sum(r["num_scheduled"] for r in mc_recs) > 0,
            "the multi-cell rounds schedule devices")
    require(max(syncs) <= MAX_FAULT_FREE_SYNCS,
            f"fault-free rounds make <= {MAX_FAULT_FREE_SYNCS} host syncs "
            f"(got {syncs})")
    require(sum(r["num_failed"] for r in mc_recs) > 0,
            "the lossy fault mix fired")

    bad = [i for i, (alg, p, m) in enumerate(solved)
           if not np.array_equal(m, (S.fscd(p) if alg == "fscd"
                                     else S.greedy_scheduling(p)).mask)]
    require(not bad, f"jax masks equal numpy's (mismatch on {bad})")
    by_shape = {}
    for _, p, _ in solved:
        by_shape.setdefault(p.p_dev.shape, []).append(p)
    for probs in by_shape.values():
        for p, s in zip(probs, S.solve_many(probs, "gs", backend="jax")):
            require(np.array_equal(s.mask, S.greedy_scheduling(p).mask),
                    "jax GS masks equal numpy's")
    log(f"mask parity: {len(solved)} instances as solved and "
        f"{len(solved)} GS re-solves equal numpy")
    require("repro.launch.dryrun" not in sys.modules,
            "the main path does not import repro.launch.dryrun")


def report(jax, device, clock, fl, mcr) -> None:
    """Phase (e): device numbers, labelled with the device kind."""
    kind = device["kind"]
    stats = jax.devices()[0].memory_stats() or {}
    log(f"measured on {kind}: peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"measured on {kind}: xla_backend_compile_s={clock.seconds:.3f} "
        f"over {clock.count} compiles")
    for name, rows in (("FederatedTrainer", fl),
                       ("MultiCellTrainer C=4 lossy", mcr)):
        steady = [wall for wall, nc, _ in rows if nc == 0]
        log(f"measured on {kind}: {name} first round {rows[0][0]:.3f} s; "
            + (f"steady round (no compile) min {min(steady):.4f} s over "
               f"{len(steady)} rounds" if steady else
               "steady round not measured (every round compiled)"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    device = check_device(jax)                                   # (a)
    log(f"device: {device}")

    from repro.compile_cache import enable_compile_cache
    from repro.configs.paper_cnn import PAPER_CNN_CIFAR10

    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)
    check_kernels(args.seed)                                     # (b)
    tr, mc, fl, mcr, syncs, solved = train(                      # (c)
        jax, clock, PAPER_CNN_CIFAR10, args.seed, ROUNDS)
    n_params = sum(x.size for x in jax.tree.leaves(tr.params))
    require(n_params == 558_226, f"paper CNN has 558,226 parameters "
            f"(got {n_params})")
    check_training(jax, tr, mc, fl, mcr, syncs, solved)          # (d)
    report(jax, device, clock, fl, mcr)                          # (e)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
