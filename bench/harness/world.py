"""Builds the system under test for one cell: data and weights from the
seed, the program's model and ``MultiCellTrainer`` with the deployment's
settings, and the warm-up of the shapes the cell's rounds use."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import numpy as np

from harness import data

# faults a test or a calibration plants underneath the timed path
FAULTS = (None, "frozen", "half_batch", "flip_mask")


@dataclasses.dataclass
class World:
    trainer: object
    weights: object            # the benchmark's weights, on the device
    inputs: np.ndarray         # train rows (host), as the reference reads them
    labels: np.ndarray
    recorder: data.RecordingArray


def _planted(model, fault: Optional[str]):
    """The program's model with a fault planted in its loss."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    loss = model.loss_fn
    if fault == "frozen":       # gradients vanish: the state never moves
        def planted(params, batch, rng=None, ctx=None):
            return loss(jax.lax.stop_gradient(params), batch, rng)
    elif fault == "half_batch":  # the mean over the first half alone
        def planted(params, batch, rng=None, ctx=None):
            half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
            return loss(params, half, rng)
    else:
        return model
    return dataclasses.replace(model, loss_fn=planted)


def _inputs(cfg: Dict, seed: int):
    """(rows, labels, number of training rows) as the configuration's
    ``inputs`` says: token sequences labelled by topic, or (the default)
    images labelled by class."""
    kind = cfg.get("inputs", "images")
    if kind == "tokens":
        n_train = cfg["train_sequences"]
        x, y = data.tokens(seed, n_train + cfg["test_sequences"],
                           cfg["num_classes"], cfg["seq_len"],
                           cfg["vocab_size"], cfg["zipf"])
        return x, y, n_train
    if kind != "images":
        raise ValueError(f"unknown inputs {kind!r}")
    n_train = cfg["train_images"]
    x, y = data.images(seed, n_train + cfg["test_images"], cfg["num_classes"],
                       cfg["image_size"], cfg["channels"])
    return x, y, n_train


def build(cfg: Dict, ref, traffic: Dict, seed: int, obs: bool = False,
          fault: Optional[str] = None) -> World:
    from repro.data.datasets import ArrayDataset
    from repro.fl import FLConfig, MultiCellTrainer
    from repro.obs import ObsConfig

    x, y, n_train = _inputs(cfg, seed)
    rec = x[:n_train].view(data.RecordingArray)
    train = ArrayDataset(rec, y[:n_train], cfg["num_classes"])
    test = ArrayDataset(x[n_train:], y[n_train:], cfg["num_classes"])
    parts = data.shards(y[:n_train], traffic["num_devices"],
                        traffic["shards_per_device"], seed)

    weights = jax.jit(lambda k: ref.init(k, cfg))(data.seed_key(seed))
    model = _planted(ref.program_model(cfg), fault)
    model = dataclasses.replace(model, init=lambda key: weights)
    fl = FLConfig(
        num_devices=traffic["num_devices"],
        available_prob=traffic["available_prob"],
        batch_size=traffic["batch_size"], tau=traffic["tau"],
        eta=traffic["eta"], deadline_s=traffic["deadline_s"],
        scheduler=traffic["scheduler"],
        scheduler_backend=traffic["scheduler_backend"],
        num_cells=traffic["num_cells"], seed=traffic["channel_seed"],
        eval_every=0,
        obs=ObsConfig(enabled=obs, ring_size=64 if obs else 0))
    trainer = MultiCellTrainer(model, train, test, parts, fl)
    return World(trainer, weights, x[:n_train], y[:n_train], rec)


def warm_fix_sums(solve_many, traffic: Dict, num_classes: int) -> None:
    """Solve one P1 instance for every largest fix-sum S in the
    deployment's ``warm_fix_sums`` range, so that the scheduler has built
    the programs of each before the window (the channel draws decide
    which S a round needs).  An instance whose devices each need 1/S of
    the band (less a hair) has exactly S as its largest fix-sum."""
    from repro.core.scheduling import Problem
    lo, hi = traffic["warm_fix_sums"]
    V = traffic["num_devices"]
    total = traffic["total_bandwidth_hz"]
    rng = np.random.default_rng(0)
    for s in range(lo, hi + 1):
        p_dev = rng.dirichlet(np.ones(num_classes), size=V)
        prob = Problem(p_dev=p_dev, global_dist=p_dev.mean(axis=0),
                       class_weights=np.ones(num_classes), sigma=1.0,
                       batch_size=traffic["batch_size"],
                       min_bw=np.full(V, total / s * (1 - 1e-6)),
                       total_bw=total)
        solve_many([prob] * traffic["num_cells"],
                   "gs" if traffic["scheduler"] == "fedcgd-gs" else "fscd",
                   backend=traffic["scheduler_backend"])
