"""Plain reference of the P1 schedulers (FedCGD Algorithms 1 and 2).

A frozen copy of the program's numpy solvers (``repro.core.scheduling``
``greedy_scheduling`` / ``fscd`` and the WEMD helpers of
``repro.core.wemd``), kept with the benchmark so that the masks the
timed path produces are judged against a solver no later change to the
program can move.  A P1 instance is a dict with the fields of the
program's ``Problem``: p_dev [V, C], global_dist [C], class_weights [C],
sigma, batch_size, min_bw [V] (-1 = infeasible), total_bw.

Candidates whose WEMD lies within TIE_TOL * (1 + |min|) of the minimum
tie, and the lowest device index among them wins: a tie in exact
arithmetic is otherwise decided by rounding."""
from __future__ import annotations

import numpy as np

TIE_TOL = 1e-12


def _argmin_tied(w: np.ndarray) -> int:
    w = np.ravel(w)
    m = w.min()
    return int(np.flatnonzero(w <= m + TIE_TOL * (abs(m) + 1.0))[0])


def _wemd_of_set(p_dev, mask, gd, cw) -> float:
    mask = np.asarray(mask, dtype=np.float64)
    s = mask.sum()
    if s == 0:
        return float(gd @ cw)
    return float(np.abs(mask @ p_dev / s - gd) @ cw)


def _feasible(p) -> np.ndarray:
    return (p["min_bw"] >= 0) & (p["min_bw"] <= p["total_bw"])


def greedy(p) -> np.ndarray:
    """Algorithm 1 (GS): the scheduled mask."""
    p_dev, gd, cw = p["p_dev"], p["global_dist"], p["class_weights"]
    V = p_dev.shape[0]
    feas = _feasible(p)
    mask = np.zeros(V, bool)
    p_sum = np.zeros(p_dev.shape[1])
    used = 0.0
    sigma_b = p["sigma"] / np.sqrt(p["batch_size"])
    w_cur = _wemd_of_set(p_dev, mask, gd, cw)
    while True:
        cand = feas & ~mask & (p["min_bw"] <= p["total_bw"] - used + 1e-9)
        if not cand.any():
            break
        size = int(mask.sum())
        w_new = np.abs((p_sum[None, :] + p_dev) / (size + 1)
                       - gd[None, :]) @ cw
        w_new = np.where(cand, w_new, np.inf)
        k = _argmin_tied(w_new)
        sv_gain = sigma_b * ((1.0 / np.sqrt(size) if size else np.inf)
                             - 1.0 / np.sqrt(size + 1))
        if (w_cur - w_new[k]) + sv_gain < 0:
            break
        mask[k] = True
        p_sum += p_dev[k]
        used += p["min_bw"][k]
        w_cur = w_new[k]
    return mask


def fscd(p, max_inner: int = 200) -> np.ndarray:
    """Algorithm 2 (fix-sum coordinate descent): the scheduled mask."""
    p_dev, gd, cw = p["p_dev"], p["global_dist"], p["class_weights"]
    V = p_dev.shape[0]
    feas = _feasible(p)
    bw = np.where(feas, p["min_bw"], np.inf)
    order = np.argsort(bw, kind="stable")
    sigma_b = p["sigma"] / np.sqrt(p["batch_size"])
    best_mask, best_obj = np.zeros(V, bool), np.inf
    s_max = int((np.cumsum(bw[order]) <= p["total_bw"] + 1e-9).sum())
    for S in range(s_max, 0, -1):
        mask = np.zeros(V, bool)
        mask[order[:S]] = True
        p_sum = p_dev[mask].sum(axis=0)
        used = float(bw[order[:S]].sum())
        w_cur = _wemd_of_set(p_dev, mask, gd, cw)
        for _ in range(max_inner):
            in_idx = np.flatnonzero(mask)
            out_idx = np.flatnonzero(~mask & feas)
            if len(out_idx) == 0:
                break
            base = (p_sum[None, None, :] - p_dev[in_idx][:, None, :]
                    + p_dev[out_idx][None, :, :])
            w_swap = np.abs(base / S - gd[None, None, :]) @ cw
            bw_new = used - bw[in_idx][:, None] + bw[out_idx][None, :]
            w_swap = np.where(bw_new <= p["total_bw"] + 1e-9, w_swap, np.inf)
            i, j = np.unravel_index(_argmin_tied(w_swap), w_swap.shape)
            if w_swap[i, j] >= w_cur - 1e-12:
                break
            vi, vj = in_idx[i], out_idx[j]
            mask[vi], mask[vj] = False, True
            p_sum += p_dev[vj] - p_dev[vi]
            used = float(bw_new[i, j])
            w_cur = float(w_swap[i, j])
        obj = w_cur + sigma_b / np.sqrt(S)
        if obj < best_obj:
            best_obj, best_mask = obj, mask.copy()
        if S > 1 and obj <= sigma_b / np.sqrt(S - 1):
            break
    return best_mask


SOLVERS = {"fscd": fscd, "gs": greedy}
