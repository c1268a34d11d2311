"""Edge-server aggregation (paper Eq. 2) + the fused finalize core.

``aggregate`` is the reference eager implementation; the trainers'
round hot path goes through ``make_finalize_core``, which fuses Eq. 2
and the Eq. 12 centered-gradient norms into ONE jitted dispatch batched
over a leading cell axis (same op order as ``aggregate``, so the two
agree bitwise for a single cell)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.estimation import tree_norm


def aggregate(device_params, mask: np.ndarray, weights: np.ndarray = None):
    """Weighted FedAvg over scheduled devices.

    device_params: pytree with leading [V] device dim (vmapped local
    update output); mask [V] bool; weights default alpha_v = 1/|Pi|
    (equal dataset sizes, paper Sec. V-A)."""
    mask = np.asarray(mask, dtype=np.float64)
    s = mask.sum()
    if s == 0:
        raise ValueError(
            "aggregate() called with an all-False schedule mask — "
            "averaging zero uploads would silently zero the model; the "
            "caller must keep the previous round's params instead "
            "(see FederatedTrainer.run_round's zero-upload path)")
    if weights is None:
        weights = mask / max(s, 1.0)
    else:
        weights = np.asarray(weights) * mask
        weights = weights / max(weights.sum(), 1e-12)
    w = jnp.asarray(weights, dtype=jnp.float32)

    def agg(leaf):
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(jnp.float32)
        return (leaf.astype(jnp.float32) * wb).sum(0).astype(leaf.dtype)

    return jax.tree.map(agg, device_params)


def select_device(device_params, v: int):
    return jax.tree.map(lambda x: x[v], device_params)


def make_finalize_core(tau: int, eta: float, cell_axis: str = "auto"):
    """Fused server-side finalize, batched over cells.

    Returns ``finalize_cells(params, dev_params, deltas, w, active)``
    (the program ``jit_finalize_cells``) where every
    argument carries a leading [C] cell axis: ``params`` [C, ...] the
    pre-round models, ``dev_params`` / ``deltas`` [C, V, ...] the round
    core's outputs (with any sanitizer replacements already scattered
    in), ``w`` [C, V] f32 the Eq. 2 upload weights (upload_v / |uploads|,
    all-zero rows for zero-upload cells and padded device rows) and
    ``active`` [C] bool (True = this cell aggregates).  One XLA program
    computes, per cell:

      new_params [C, ...]  Eq. 2 weighted sum where the cell had uploads,
                           else the previous params (an in-graph select,
                           so zero-upload cells cost no extra dispatch)
      norms      [C, V]    || grad_v - sum_u w_u grad_u || with
                           grad_v = -delta_v / (tau * eta) — the Eq. 12
                           numerators; rows with w_v = 0 are garbage and
                           must be masked by the caller

    ``cell_axis`` follows ``make_round_core``: ``"scan"`` rolls the cell
    axis with ``lax.map`` (the compiled body is the single-cell program,
    so a C-cell finalize is bitwise-identical to C standalone ones —
    the CPU default), ``"vmap"`` batches it for accelerators.

    Nothing is donated: no output has the [C, V, ...] shape of
    ``dev_params`` / ``deltas``, so XLA could not reuse their buffers."""

    def one_cell(args):
        params, dev_params, deltas, w, active = args

        def agg_leaf(leaf):
            wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
            return (leaf.astype(jnp.float32) * wb).sum(0).astype(leaf.dtype)

        agg = jax.tree.map(agg_leaf, dev_params)
        new_params = jax.tree.map(lambda a, p: jnp.where(active, a, p),
                                  agg, params)

        # Eq. 12 numerators: ||grad_v - mean|| with grad = -delta/(tau*eta).
        # Centering commutes with the scale, so the deltas are centered
        # RAW and the norms divided afterwards: with exact {0, 1/|U|}
        # weights a single-upload cell's centered row is then exactly
        # zero (d_r - d_r), so its norm is exactly 0 and the host-side
        # `g > 0` refresh guard skips it.  Folding the division into the
        # graph lets XLA reassociate it through the weighted mean,
        # leaving ulp-level residue that turns the zero into ~1e-7 and
        # silently collapses g_hat.
        def center(x):
            a = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
            return x - (x * a).sum(0)[None]

        norms = jax.vmap(tree_norm)(jax.tree.map(center, deltas)) \
            / (tau * eta)
        return new_params, norms

    if cell_axis == "auto":
        cell_axis = "scan" if jax.default_backend() == "cpu" else "vmap"
    if cell_axis not in ("scan", "vmap"):
        raise ValueError(f"cell_axis must be auto|vmap|scan, "
                         f"got {cell_axis!r}")
    if cell_axis == "vmap":
        def finalize_cells(params, dev_params, deltas, w, active):
            return one_cell((params, dev_params, deltas, w, active))

        return jax.jit(jax.vmap(finalize_cells))

    @jax.jit
    def finalize_cells(params_c, dev_params_c, deltas_c, w_c, active_c):
        return jax.lax.map(one_cell, (params_c, dev_params_c, deltas_c,
                                      w_c, active_c))

    return finalize_cells
