"""The numbers that decide ``correct``, and the recorders that collect
what the timed path produced.

Over the first rounds (run in set-up through the window's own
``run_round``), the reference follows the program from the same weights
on the same rows, and these numbers compare the two:

  loss0_gap   first round's |program mean local loss - reference| / reference
              (both from the same weights, so nothing has compounded yet)
  loss0_dev_gap  the worst device's first-round |program loss - reference|
              / reference
  loss_gap    the same as loss0_gap, worst over all checked rounds
  sigma0_gap  first round's |program Eq. 11 sigma - reference| / reference
  sigma_gap   the same, worst over all checked rounds
  update_gap  first round's update (w1 - w0), by the worst leaf
  change_gap  the change after all checked rounds (wN - w0), by the worst leaf

A leaf's gap is | ||program leaf|| - ||reference leaf|| | over the
larger of the reference leaf's norm and the median leaf's.  Leaves
whose reference update is under a thousandth of the median leaf's move
by round-off alone and are left out.

  mask_mismatches  P1 instances (those of the checked rounds and a
                   sample of the window's, drawn from the seed) whose
                   program mask differs from the reference solver's."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax
import numpy as np

from harness import sched_ref

NUMBERS = ("loss0_gap", "loss0_dev_gap", "loss_gap", "sigma0_gap", "sigma_gap",
           "update_gap", "change_gap", "mask_mismatches")
EXCLUDE_BELOW = 1e-3


def _leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64).ravel())) for k, v in flat}


def _sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def leaf_gap(prog_delta, ref_delta) -> float:
    """Worst leaf's gap between the norms of two updates."""
    pn, rn = _leaf_norms(prog_delta), _leaf_norms(ref_delta)
    med = float(np.median(list(rn.values())))
    if med == 0:         # the reference did not move: neither may the program
        return 0.0 if not any(pn.values()) else float("inf")
    gaps = [abs(pn[k] - rn[k]) / max(rn[k], med) for k in rn
            if rn[k] >= EXCLUDE_BELOW * med]
    return max(gaps)


def dev_gap(prog, ref) -> float:
    """Worst device's relative gap (devices in the same order)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else float("inf")


def compare(w0, prog_rounds: List[Dict], ref_rounds: List[Dict]) -> Dict:
    """The gaps of one cell over the checked rounds.  Each program round
    is {"loss", "sigma_hat", "params"} like the reference's."""
    if not prog_rounds or len(prog_rounds) != len(ref_rounds):
        return {n: float("inf") for n in NUMBERS[:-1]}
    p0, r0 = prog_rounds[0], ref_rounds[0]
    return {
        "loss0_gap": rel_gap(p0["loss"], r0["loss"]),
        "loss0_dev_gap": dev_gap(p0["dev_losses"], r0["dev_losses"]),
        "sigma0_gap": rel_gap(p0["sigma_hat"], r0["sigma_hat"]),
        "loss_gap": max(rel_gap(p["loss"], r["loss"])
                        for p, r in zip(prog_rounds, ref_rounds)),
        "sigma_gap": max(rel_gap(p["sigma_hat"], r["sigma_hat"])
                         for p, r in zip(prog_rounds, ref_rounds)),
        "update_gap": leaf_gap(_sub(prog_rounds[0]["params"], w0),
                               _sub(ref_rounds[0]["params"], w0)),
        "change_gap": leaf_gap(_sub(prog_rounds[-1]["params"], w0),
                               _sub(ref_rounds[-1]["params"], w0)),
    }


def as_instance(problem) -> Dict:
    """A program ``Problem`` as a plain dict of host arrays."""
    return {
        "p_dev": np.array(problem.p_dev, np.float64),
        "global_dist": np.array(problem.global_dist, np.float64),
        "class_weights": np.array(problem.class_weights, np.float64),
        "sigma": float(problem.sigma),
        "batch_size": int(problem.batch_size),
        "min_bw": np.array(problem.min_bw, np.float64),
        "total_bw": float(problem.total_bw),
    }


class P1Recorder:
    """Wraps ``repro.core.scheduling.solve_many``: keeps every P1 instance
    the program solves and the mask it returned, and the seconds spent
    solving.  ``flip`` plants a fault: the first feasible device of every
    instance has its mask entry flipped on its way back to the program."""

    def __init__(self, module, flip: bool = False):
        self._module = module
        self.flip = flip
        self.solved: List[tuple] = []   # (algorithm, instance, mask)
        self.seconds = 0.0
        self.real = module.solve_many

    def __enter__(self):
        real = self.real

        def solve_many(problems, algorithm="fscd", **kw):
            t = time.perf_counter()
            out = real(problems, algorithm, **kw)
            self.seconds += time.perf_counter() - t
            if self.flip:
                out = [self._flipped(p, s) for p, s in zip(problems, out)]
            self.solved += [(algorithm, as_instance(p), s.mask.copy())
                            for p, s in zip(problems, out)]
            return out

        self._module.solve_many = solve_many
        return self

    def __exit__(self, *exc):
        self._module.solve_many = self.real

    @staticmethod
    def _flipped(problem, sched):
        feas = np.flatnonzero(np.asarray(problem.min_bw) >= 0)
        if not len(feas):
            return sched
        mask = sched.mask.copy()
        mask[feas[0]] = ~mask[feas[0]]
        return dataclasses.replace(sched, mask=mask)


def mask_mismatches(solved: List[tuple]) -> int:
    return sum(not np.array_equal(mask, sched_ref.SOLVERS[alg](inst))
               for alg, inst, mask in solved)


def judge(numbers: Dict, limits: Dict) -> bool:
    return all(np.isfinite(numbers[n]) and numbers[n] <= limits[n]["limit"]
               for n in limits)
