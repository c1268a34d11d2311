"""End-to-end driver (paper Section VI): wireless FL with FedCGD or any
baseline scheduler, TR 38.901 UMi channel, Table I parameters.

  PYTHONPATH=src python examples/wireless_fl.py --scheduler fedcgd-fscd \
      --rounds 40 --devices 32 --classes 10 --imbalance 3

This is the paper's experiment at container scale: CIFAR-10 is replaced
by a synthetic class-structured image set (DESIGN.md §3) — everything
else (channel, Eq. 9 bandwidth, Algorithm 1/2/3, estimators) is the
paper's pipeline.

``--lossy`` turns on the wireless fault model (upload-time shadow
re-draws, outages, dropouts, corrupted deltas) plus the server defenses
(sanitization, norm clipping, one-shot backfill) and prints the failure
telemetry; individual knobs can be overridden, e.g.:

  PYTHONPATH=src python examples/wireless_fl.py --lossy \
      --outage-prob 0.5 --rounds 10 --devices 16

``--cells C`` simulates C independent cells per aggregation step through
the batched multi-cell engine (one fused local-update program + one
``solve_many`` scheduling dispatch per round; FedCGD schedulers only):

  PYTHONPATH=src python examples/wireless_fl.py --cells 4 --rounds 20

``--metrics-out PATH.jsonl`` turns on the observability layer
(``repro.obs``): per-round phase-timing records stream to the JSONL
file and an end-of-run console summary reports p50/p95 phase times and
failure-cause totals:

  PYTHONPATH=src python examples/wireless_fl.py --lossy \
      --metrics-out metrics.jsonl --rounds 10
"""
import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.paper_cnn import PAPER_CNN_CIFAR10
from repro.data import (apply_imbalance, dirichlet_partition,
                        sort_and_partition, synthetic_image_dataset,
                        train_test_split)
from repro.fl import FederatedTrainer, FLConfig, MultiCellTrainer
from repro.faults import LOSSY_UPLINK, FaultConfig
from repro.models import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scheduler", default="fedcgd-fscd",
                    choices=["fedcgd-fscd", "fedcgd-gs", "fedcgd-fscd-gc",
                             "bc", "bn", "poc", "fcbs", "random"])
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--devices", type=int, default=32)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--partition", default="sort",
                    choices=["sort", "dirichlet"])
    ap.add_argument("--shards", type=int, default=1, help="l")
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--imbalance", type=float, default=1.0, help="r")
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--available-prob", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cells", type=int, default=1,
                    help="independent cells per aggregation step "
                         "(multi-cell engine; FedCGD schedulers only)")
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax"],
                    help="P1 scheduling backend (jax = the batched "
                         "solve_many engine)")
    ap.add_argument("--lossy", action="store_true",
                    help="enable the wireless fault model + defenses")
    ap.add_argument("--outage-prob", type=float, default=None)
    ap.add_argument("--dropout-prob", type=float, default=None)
    ap.add_argument("--corrupt-prob", type=float, default=None)
    ap.add_argument("--reshadow-std-db", type=float, default=None)
    ap.add_argument("--clip-delta-norm", type=float, default=None)
    ap.add_argument("--metrics-out", default=None, metavar="PATH.jsonl",
                    help="enable repro.obs and stream per-round metric "
                         "records to this JSONL file")
    args = ap.parse_args()
    enable_compile_cache()

    ds = synthetic_image_dataset(num_classes=args.classes, num_per_class=120,
                                 image_size=16, seed=args.seed)
    train, test = train_test_split(ds, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    labels = train.labels
    if args.imbalance != 1.0:
        import dataclasses
        idx = apply_imbalance(labels, args.imbalance, rng)
        train = dataclasses.replace(train, inputs=train.inputs[idx],
                                    labels=labels[idx])
    if args.partition == "sort":
        parts = sort_and_partition(train.labels, args.devices, args.shards,
                                   rng)
    else:
        parts = dirichlet_partition(train.labels, args.devices, args.alpha,
                                    rng)

    import dataclasses as dc
    cfg = dc.replace(PAPER_CNN_CIFAR10.reduced(), num_classes=args.classes)
    model = build_model(cfg)
    faults = LOSSY_UPLINK if args.lossy else FaultConfig()
    overrides = {k: getattr(args, k) for k in
                 ("outage_prob", "dropout_prob", "corrupt_prob",
                  "reshadow_std_db", "clip_delta_norm")
                 if getattr(args, k) is not None}
    if overrides:
        import dataclasses
        faults = dataclasses.replace(faults, **overrides)

    from repro.obs import ObsConfig
    obs_cfg = ObsConfig(enabled=args.metrics_out is not None,
                        jsonl_path=args.metrics_out)
    fl = FLConfig(num_devices=args.devices,
                  available_prob=args.available_prob, batch_size=16,
                  tau=args.tau, scheduler=args.scheduler,
                  scheduler_backend=args.backend, eval_every=5,
                  seed=args.seed, num_cells=args.cells, faults=faults,
                  obs=obs_cfg)
    if args.cells > 1:
        mc = MultiCellTrainer(model, train, test, parts, fl)
        mc.run(args.rounds, verbose=True)
        engine = mc
        trainer = mc.cells[0]           # report cell 0 below
        hist = trainer.history
        print(f"\n(multi-cell: {args.cells} cells, "
              f"{mc.solve_many_calls} scheduling dispatches over "
              f"{args.rounds} rounds; reporting cell 0)")
    else:
        trainer = FederatedTrainer(model, train, test, parts, fl)
        engine = trainer
        hist = trainer.run(args.rounds, verbose=True)

    accs = [h["test_accuracy"] for h in hist if "test_accuracy" in h]
    scheds = [h["num_scheduled"] for h in hist]
    wemds = [h["wemd"] for h in hist]
    print(f"\n== {args.scheduler} ==")
    print(f"max accuracy      : {max(accs):.3f}")
    print(f"avg scheduled num : {np.mean(scheds):.2f}")
    print(f"avg WEMD          : {np.mean(wemds):.3f}")
    print(f"final sigma-hat   : {trainer.sigma_hat:.3f}  "
          f"G-hat: {trainer.g_hat:.3f}  "
          f"(G/sigma = {trainer.g_hat / max(trainer.sigma_hat, 1e-9):.3f})")

    if faults.injection_enabled:
        causes = {}
        for h in hist:
            for c, n in h["failure_causes"].items():
                causes[c] = causes.get(c, 0) + n
        uploaded = sum(h["num_uploaded"] for h in hist)
        print("\n-- failure telemetry --")
        print(f"uploads landed    : {uploaded} "
              f"({sum(h['num_failed'] for h in hist)} failed)")
        print(f"causes            : " + ", ".join(
            f"{c}={n}" for c, n in sorted(causes.items())))
        print(f"backfilled        : {sum(h['num_backfilled'] for h in hist)}")
        print(f"sanitized deltas  : {sum(h['num_sanitized'] for h in hist)} "
              f"(clipped {sum(h['num_clipped'] for h in hist)})")
        print(f"zero-upload rounds: "
              f"{sum(1 for h in hist if h['num_uploaded'] == 0)}")

    if engine.obs.enabled:
        from repro.obs import format_summary
        engine.obs.close()
        print("\n== observability summary ==")
        print(format_summary(engine.obs.metrics))
        print(f"metrics written to {args.metrics_out}")


if __name__ == "__main__":
    main()
