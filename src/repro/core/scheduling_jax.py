"""Batched JAX backend for the P1 schedulers (GS / FSCD).

``solve_many_jax`` replicates the numpy solvers' arithmetic op-for-op in
float64 (inside a local ``jax.enable_x64(True)`` scope) so its
masks coincide with the per-problem numpy path, while amortizing the
solver over a problem axis:

  * GS (Algorithm 1) runs all problems through one jitted while-loop —
    each iteration adds at most one device per problem.
  * FSCD (Algorithm 2) is vectorized over problems *and* over the
    fix-sum axis: every (problem, S) pair of the outer loop is an
    independent lane of a coordinate-descent while-loop, run in short
    *phases* — after each phase the still-unconverged lanes are
    compacted on the host so the batch shrinks as lanes converge.  The
    swap matrix is scanned on member-compacted rows in float32 and the
    top-K candidates re-evaluated with numpy's float64 op order (the
    whole matrix when more than K candidates may tie the minimum), with
    ties broken by device index exactly like ``scheduling.argmin_tied``.
    The ``best``/early-exit bookkeeping of the numpy outer loop is
    replayed on the host from per-lane results.

The float64 decisions make the masks equal numpy's.  The float32 Pallas
kernels in ``repro.kernels`` (``wemd_swap`` / ``wemd_add``) can be
routed in for the candidate *scan* (``pallas=True``, or automatically
on a TPU backend): the kernels produce the f32 swap/add matrices and the
float64 re-evaluation still makes every accept/swap decision.  On a single
CPU core the batched FSCD path roughly matches the numpy loop (the
lanes are data-parallel, so the win scales with cores/accelerator);
batched GS is ~8x even single-core.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduling as SCH

_LANE_BUCKET = 32          # min lane-batch granule (see _bucket)


def _use_pallas(pallas) -> bool:
    """Kernel routing: explicit override wins, else auto-on-TPU.  The
    Pallas kernels compute the f32 candidate matrices; every decision
    still goes through the f64 re-evaluation, so the selected masks equal
    the numpy solvers' (verified in tests with the interpret-mode kernels
    on CPU, and by ``chip_smoke.py`` on the chip)."""
    if pallas is not None:
        return bool(pallas)
    return jax.default_backend() == "tpu"


def _pick_tied(w64, valid, index):
    """``scheduling.argmin_tied`` over candidate subsets, batched: per
    row, the lowest ``index`` among valid candidates within ``tie_tol``
    of the minimum, and its value.  Rows without a valid candidate give
    (+inf, an index past every candidate).  w64 / valid / index [B, N]."""
    w = jnp.where(valid, w64, jnp.inf)
    wmin = w.min(axis=1)
    tied = valid & (w <= (wmin + SCH.tie_tol(wmin))[:, None])
    sel = jnp.where(tied, index, jnp.iinfo(jnp.int32).max).min(axis=1)
    wsel = jnp.where(tied & (index == sel[:, None]), w, jnp.inf).min(axis=1)
    return wsel, sel


def _topk_covers(w32, valid, cw, K):
    """True when, in every row, the K smallest f32 candidates include all
    that may tie the f64 minimum.  The f32 scans err by under ~1e-6 of
    (value + sum_c cw_c); candidates within 1e-5 of that above the f32
    minimum are the ones that may tie."""
    w = jnp.where(valid, w32, jnp.inf)
    m = w.min(axis=1)
    margin = 1e-5 * (jnp.abs(m) + cw.sum(axis=1).astype(w32.dtype))
    n = (valid & (w <= (m + margin)[:, None])).sum(axis=1)
    return (n <= K).all()


# ---------------------------------------------------------------------------
# Algorithm 1 (GS), batched over problems


def _gs_batch_impl(p_dev, gd, cw, sigma, batch_size, min_bw, total_bw,
                   use_pallas=False):
    B, V, C = p_dev.shape
    feas = (min_bw >= 0) & (min_bw <= total_bw[:, None])
    sigma_b = sigma / jnp.sqrt(batch_size)
    K = min(16, V)
    dev_idx = jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32), (B, V))

    def cond(carry):
        return carry[4].any()

    def body(carry):
        mask, p_sum, used, w_cur, active, iters = carry
        cand = feas & ~mask & (min_bw <= (total_bw - used)[:, None] + 1e-9)
        act = active & cand.any(axis=1)
        iters = iters + act.astype(jnp.int32)
        size = jnp.sum(mask, axis=1).astype(p_dev.dtype)

        def add_full():
            # wemd_add_candidates, batched, all-f64
            new = (p_sum[:, None, :] + p_dev) / (size[:, None, None] + 1.0)
            w_new = jnp.einsum("bvc,bc->bv",
                               jnp.abs(new - gd[:, None, :]), cw)
            return _pick_tied(w_new, cand, dev_idx)

        if use_pallas:
            # f32 Pallas add-candidate row, then the f64 (numpy op order)
            # re-evaluation of the top K, or of the whole row when the
            # top K cannot hold every candidate that may tie the minimum
            from repro.kernels import ops
            w32 = ops.wemd_add(p_sum, p_dev, gd, cw, size)
            _, topk = jax.lax.top_k(-jnp.where(cand, w32, jnp.inf), K)

            def add_topk():
                p_k = jnp.take_along_axis(p_dev, topk[:, :, None], 1)
                new_k = (p_sum[:, None, :] + p_k) \
                    / (size[:, None, None] + 1.0)
                w64k = jnp.einsum("bkc,bc->bk",
                                  jnp.abs(new_k - gd[:, None, :]), cw)
                return _pick_tied(w64k, jnp.take_along_axis(cand, topk, 1),
                                  topk)

            wk, k = jax.lax.cond(_topk_covers(w32, cand, cw, K), add_topk,
                                 add_full)
        else:
            wk, k = add_full()
        k = jnp.minimum(k, V - 1)
        inv_sqrt = jnp.where(size > 0,
                             1.0 / jnp.sqrt(jnp.where(size > 0, size, 1.0)),
                             jnp.inf)
        sv_gain = sigma_b * (inv_sqrt - 1.0 / jnp.sqrt(size + 1.0))
        accept = (w_cur - wk) + sv_gain >= 0
        upd = act & accept
        sel = jnp.arange(V)[None, :] == k[:, None]
        mask = mask | (upd[:, None] & sel)
        pk = jnp.take_along_axis(p_dev, k[:, None, None], 1)[:, 0]
        p_sum = jnp.where(upd[:, None], p_sum + pk, p_sum)
        bwk = jnp.take_along_axis(min_bw, k[:, None], 1)[:, 0]
        used = jnp.where(upd, used + bwk, used)
        w_cur = jnp.where(upd, wk, w_cur)
        return mask, p_sum, used, w_cur, upd, iters

    init = (jnp.zeros((B, V), bool),
            jnp.zeros((B, C), p_dev.dtype),
            jnp.zeros((B,), p_dev.dtype),
            jnp.einsum("bc,bc->b", gd, cw),
            jnp.ones((B,), bool),
            jnp.zeros((B,), jnp.int32))
    mask, _, _, _, _, iters = jax.lax.while_loop(cond, body, init)
    return mask, iters


# ---------------------------------------------------------------------------
# Algorithm 2 (FSCD), one lane per (problem, S) pair
#
# The swap-candidate matrix is evaluated on member-compacted rows
# [L, S_cap, V] (S_cap = max fix-sum across lanes) instead of the full
# [L, V, V] grid, with the factored form
#     W[l,r,j] = sum_c cw_c * | A[l,r,c] + B[l,j,c] |,
#     A = (p_sum - p_member)/S - gd,   B = p_dev/S,
# and ties broken by the *device-order* flat index (min vi*V+vj among
# the tied minima) — precisely numpy's ``argmin_tied`` over [in, out],
# so the member-row permutation cannot change the selected swap.


def _fscd_phase_impl(p_dev, gd, cw, bw, feas, total_bw, s_lane,
                     members, mask, p_sum, used, w_cur, act, iters,
                     max_inner, phase_steps, use_pallas=False):
    L, V, C = p_dev.shape
    R = members.shape[1]
    sf = s_lane.astype(p_dev.dtype)
    sf_safe = jnp.maximum(sf, 1.0)
    valid_r = jnp.arange(R)[None, :] < s_lane[:, None]

    def cond(carry):
        live = carry[5] & (carry[6] < max_inner)
        return live.any() & (carry[7] < phase_steps)

    K = min(16, R * V)
    f32 = jnp.float32

    def body(carry):
        members, mask, p_sum, used, w_cur, act, iters, step = carry
        live = act & (iters < max_inner)
        iters = iters + live.astype(jnp.int32)
        p_mem = jnp.take_along_axis(p_dev, members[:, :, None], 1)  # [L,R,C]
        bw_mem = jnp.take_along_axis(bw, members, 1)
        bw_new = (used[:, None, None] - bw_mem[:, :, None]) + bw[:, None, :]
        ok = (valid_r[:, :, None] & (~mask & feas)[:, None, :]
              & (bw_new <= total_bw[:, None, None] + 1e-9)).reshape(L, R * V)
        # float32 scan of the full [R, V] swap matrix, then the float64
        # re-evaluation (numpy's op order) of the K best candidates, or of
        # the whole matrix when the top K cannot hold every candidate
        # that may tie the minimum
        if use_pallas:
            # dense [L,V,V] f32 swap matrix from the Pallas kernel,
            # gathered down to the member rows
            from repro.kernels import ops
            w_dense = ops.wemd_swap(p_sum, p_dev, gd, cw, sf_safe)
            w32 = jnp.take_along_axis(w_dense, members[:, :, None], 1)
        else:
            a = ((p_sum[:, None, :] - p_mem) / sf_safe[:, None, None]
                 - gd[:, None, :]).astype(f32)
            b = (p_dev / sf_safe[:, None, None]).astype(f32)
            w32 = jnp.sum(jnp.abs(a[:, :, None, :] + b[:, None, :, :])
                          * cw[:, None, None, :].astype(f32),
                          axis=-1)                              # [L,R,V]
        w32 = w32.reshape(L, R * V)
        # numpy's tie-break: (vi, vj) in device order
        flat_dev = (members[:, :, None] * jnp.int32(V)
                    + jnp.arange(V, dtype=jnp.int32)).reshape(L, R * V)

        def swap_w64(p_i, p_j):
            base = (p_sum[:, None, :] - p_i) + p_j
            return jnp.sum(jnp.abs(base / sf_safe[:, None, None]
                                   - gd[:, None, :]) * cw[:, None, :],
                           axis=-1)

        def swap_full():
            p_i = jnp.repeat(p_mem, V, axis=1)                  # [L,R*V,C]
            p_j = jnp.tile(p_dev, (1, R, 1))
            return _pick_tied(swap_w64(p_i, p_j), ok, flat_dev)

        _, flat_rv = jax.lax.top_k(-jnp.where(ok, w32, jnp.inf), K)

        def swap_topk():
            vi_k = jnp.take_along_axis(members, flat_rv // V, 1)
            p_i_k = jnp.take_along_axis(p_dev, vi_k[:, :, None], 1)
            p_j_k = jnp.take_along_axis(p_dev, (flat_rv % V)[:, :, None], 1)
            return _pick_tied(swap_w64(p_i_k, p_j_k),
                              jnp.take_along_axis(ok, flat_rv, 1),
                              jnp.take_along_axis(flat_dev, flat_rv, 1))

        wmin, flatmin = jax.lax.cond(_topk_covers(w32, ok, cw, K),
                                     swap_topk, swap_full)
        vi = jnp.minimum(flatmin // V, V - 1)
        vj = flatmin % V
        rsel = (members == vi[:, None]) & valid_r
        rpos = jnp.argmax(rsel, axis=1)
        improve = wmin < w_cur - 1e-12
        upd = live & improve
        members = jnp.where(
            upd[:, None] & (jnp.arange(R)[None, :] == rpos[:, None]),
            vj[:, None], members)
        sel_i = jnp.arange(V)[None, :] == vi[:, None]
        sel_j = jnp.arange(V)[None, :] == vj[:, None]
        mask = jnp.where(upd[:, None], (mask & ~sel_i) | sel_j, mask)
        p_i = jnp.take_along_axis(p_dev, vi[:, None, None], 1)[:, 0]
        p_j = jnp.take_along_axis(p_dev, vj[:, None, None], 1)[:, 0]
        p_sum = jnp.where(upd[:, None], p_sum + (p_j - p_i), p_sum)
        bw_i = jnp.take_along_axis(bw, vi[:, None], 1)[:, 0]
        bw_j = jnp.take_along_axis(bw, vj[:, None], 1)[:, 0]
        used = jnp.where(upd, (used - bw_i) + bw_j, used)
        w_cur = jnp.where(upd, wmin, w_cur)
        return members, mask, p_sum, used, w_cur, upd, iters, step + 1

    init = (members, mask, p_sum, used, w_cur, act, iters,
            jnp.asarray(0, jnp.int32))
    out = jax.lax.while_loop(cond, body, init)
    return out[:7]


_JIT_CACHE: dict = {}


def _jitted(name, fn, static_argnums=()):
    if name not in _JIT_CACHE:
        _JIT_CACHE[name] = jax.jit(fn, static_argnums=static_argnums)
    return _JIT_CACHE[name]


# ---------------------------------------------------------------------------
# host-side drivers


def _stack(problems: Sequence[SCH.Problem]):
    V = problems[0].num_devices
    C = problems[0].p_dev.shape[1]
    for p in problems:
        if p.p_dev.shape != (V, C):
            raise ValueError("solve_many requires same-shaped problems, got "
                             f"{p.p_dev.shape} vs {(V, C)}")
    return {
        "p_dev": np.stack([np.asarray(p.p_dev, np.float64)
                           for p in problems]),
        "gd": np.stack([np.asarray(p.global_dist, np.float64)
                        for p in problems]),
        "cw": np.stack([np.asarray(p.class_weights, np.float64)
                        for p in problems]),
        "sigma": np.array([p.sigma for p in problems], np.float64),
        "batch_size": np.array([p.batch_size for p in problems], np.float64),
        "min_bw": np.stack([np.asarray(p.min_bw, np.float64)
                            for p in problems]),
        "total_bw": np.array([p.total_bw for p in problems], np.float64),
    }


def solve_many_gs(problems: Sequence[SCH.Problem],
                  pallas: bool | None = None) -> List[SCH.Schedule]:
    st = _stack(problems)
    up = _use_pallas(pallas)
    with jax.enable_x64(True):
        fn = _jitted(f"gs_p{int(up)}", _gs_batch_impl,
                     static_argnums=(7,))
        masks, iters = fn(st["p_dev"], st["gd"], st["cw"], st["sigma"],
                          st["batch_size"], st["min_bw"], st["total_bw"],
                          up)
        masks, iters = np.asarray(masks), np.asarray(iters)
    return [SCH._make_schedule(p, masks[b], int(iters[b]), "GS")
            for b, p in enumerate(problems)]


def _bucket(n: int) -> int:
    # round up to a coarse-enough granule that recompilation stays rare
    # while padding waste stays ~<12%
    g = _LANE_BUCKET
    while g * 8 < n:
        g *= 2
    return -(-n // g) * g


def solve_many_fscd(problems: Sequence[SCH.Problem],
                    max_inner: int = 200,
                    phase_steps: int = 4,
                    pallas: bool | None = None) -> List[SCH.Schedule]:
    from repro.core import wemd as WE

    st = _stack(problems)
    B, V, C = st["p_dev"].shape

    # lane layout: per problem, one lane per S in range(S_max, 0, -1),
    # initialized with the numpy solver's exact host arithmetic
    feas_p = (st["min_bw"] >= 0) & (st["min_bw"] <= st["total_bw"][:, None])
    bw_p = np.where(feas_p, st["min_bw"], np.inf)
    order_p = np.argsort(bw_p, axis=1, kind="stable")
    s_max = np.zeros(B, int)
    for b in range(B):
        cum = np.cumsum(bw_p[b][order_p[b]])
        s_max[b] = int((cum <= st["total_bw"][b] + 1e-9).sum())
    lane_b = np.concatenate([np.full(s_max[b], b, int) for b in range(B)]
                            or [np.zeros(0, int)])
    s_lane = np.concatenate([np.arange(s_max[b], 0, -1) for b in range(B)]
                            or [np.zeros(0, int)])
    L = len(lane_b)

    masks = np.zeros((L, V), bool)
    w_cur = np.zeros(L)
    iters = np.zeros(L, np.int32)
    if L:
        S_cap = int(s_lane.max())
        members = np.zeros((L, S_cap), np.int32)
        p_sum = np.zeros((L, C))
        used = np.zeros(L)
        act = np.ones(L, bool)
        for l in range(L):
            b, S = lane_b[l], int(s_lane[l])
            members[l, :S] = order_p[b][:S]
            masks[l, order_p[b][:S]] = True
            p_sum[l] = st["p_dev"][b][masks[l]].sum(axis=0)
            used[l] = float(bw_p[b][order_p[b][:S]].sum())
            w_cur[l] = WE.wemd_of_set(st["p_dev"][b], masks[l], st["gd"][b],
                                      st["cw"][b])
        # lane-indexed constants
        p_dev_l, gd_l, cw_l = (st["p_dev"][lane_b], st["gd"][lane_b],
                               st["cw"][lane_b])
        bw_l, feas_l = bw_p[lane_b], feas_p[lane_b]
        tot_l = st["total_bw"][lane_b]

        # phase-chunked descent: run every live lane a few steps, pull
        # the still-live set to the host, compact, repeat — so the batch
        # shrinks as lanes converge instead of spinning until the
        # slowest lane is done
        alive = np.arange(L)
        up = _use_pallas(pallas)
        with jax.enable_x64(True):
            fn = _jitted(f"fscd_phase_p{int(up)}", _fscd_phase_impl,
                         static_argnums=(14, 15, 16))
            while alive.size:
                n = alive.size
                sel = np.concatenate(
                    [alive, np.full(_bucket(n) - n, alive[0])])
                act_in = act[sel]
                act_in[n:] = False
                out = fn(p_dev_l[sel], gd_l[sel], cw_l[sel], bw_l[sel],
                         feas_l[sel], tot_l[sel], s_lane[sel],
                         members[sel], masks[sel], p_sum[sel], used[sel],
                         w_cur[sel], act_in, iters[sel],
                         int(max_inner), int(phase_steps), up)
                o = [np.asarray(x)[:n] for x in out]
                members[alive], masks[alive], p_sum[alive] = o[0], o[1], o[2]
                used[alive], w_cur[alive] = o[3], o[4]
                act[alive], iters[alive] = o[5], o[6]
                alive = alive[o[5] & (o[6] < max_inner)]

    # replay the numpy outer loop (best tracking + early exit) exactly
    out: List[SCH.Schedule] = []
    lane0 = np.concatenate([[0], np.cumsum(s_max)])[:-1]
    for b, prob in enumerate(problems):
        sigma_b = prob.sigma / np.sqrt(prob.batch_size)
        best_mask, best_obj = np.zeros(V, bool), np.inf
        total_iters = 0
        for t, S in enumerate(range(s_max[b], 0, -1)):
            l = lane0[b] + t
            total_iters += int(iters[l])
            obj = w_cur[l] + sigma_b / np.sqrt(S)
            if obj < best_obj:
                best_obj, best_mask = obj, masks[l]
            if S > 1 and w_cur[l] + sigma_b / np.sqrt(S) \
                    <= sigma_b / np.sqrt(S - 1):
                break
        out.append(SCH._make_schedule(prob, best_mask, total_iters, "FSCD"))
    return out
