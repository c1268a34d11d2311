"""Batched WEMD swap/add candidate kernels (FSCD / GS hot loops).

The FSCD inner loop (Algorithm 2) evaluates, per problem, the dense
swap-candidate matrix

    W[i, j] = sum_c cw_c * | (p_sum - p_dev_i + p_dev_j) / S  -  gd_c |

over every (i in set, j out of set) pair, and GS (Algorithm 1) its rank-1
analogue W[v] over add candidates.  Batched across a [B] problem axis
these are the scheduler's compute hot-spots (O(B V^2 C) per FSCD step).

``wemd_swap_pallas`` tiles the i-rows and the class axis: each grid step
loads one [block_i, block_c] slab of member rows plus the full [V,
block_c] candidate slab, forms the |.| term in VMEM and accumulates the
class-partial sums into the [block_i, V] output block — the [V, V, C]
intermediate never exists in HBM.  ``wemd_add_pallas`` does the same for
the [B, V] add matrix.

Both kernels are float32 (TPU-native); the float64 parity path used for
mask-exact scheduling on CPU lives in ``core/scheduling_jax.py``.
Validity masking (membership, bandwidth) is the caller's job — the
kernels compute the dense matrices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK_I = 8
DEFAULT_BLOCK_C = 128
# block index maps return int32: a bare 0 becomes int64 when the caller
# traces under ``jax.enable_x64(True)`` (as the f64 schedulers do), and
# Mosaic rejects 64-bit block indices
_0 = np.int32(0)


def _swap_kernel(psum_ref, pdi_ref, pdj_ref, gd_ref, cw_ref, sz_ref,
                 out_ref):
    ct = pl.program_id(2)

    @pl.when(ct == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = sz_ref[0]                                  # [1, 1]
    ps = psum_ref[0]                               # [1, bc]
    gd = gd_ref[0]
    cw = cw_ref[0]
    pi = pdi_ref[0]                                # [bi, bc]
    pj = pdj_ref[0]                                # [V,  bc]
    base = (ps[None] - pi[:, None, :]) + pj[None, :, :]
    out_ref[0] += jnp.sum(jnp.abs(base / s[None] - gd[None])
                          * cw[None], axis=-1)


def _add_kernel(psum_ref, pd_ref, gd_ref, cw_ref, sz_ref, out_ref):
    ct = pl.program_id(1)

    @pl.when(ct == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = sz_ref[0]                                  # [1, 1]
    new = (psum_ref[0] + pd_ref[0]) / (s + 1.0)    # [V, bc]
    out_ref[0] += jnp.sum(jnp.abs(new - gd_ref[0]) * cw_ref[0], axis=-1,
                          keepdims=True)


def _row(x, pad_c):
    """[B, C] per-problem vector -> f32 [B, 1, C + pad_c]: the problem
    axis leads the block, so the last two block dims (1, block_c) equal
    the array's or tile at 128 (the TPU (8, 128) block rule)."""
    return jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad_c)))[:, None, :]


@functools.partial(jax.jit, static_argnames=("block_i", "block_c",
                                             "interpret"))
def wemd_swap_pallas(p_sum, p_dev, global_dist, class_weights, sizes, *,
                     block_i: int = DEFAULT_BLOCK_I,
                     block_c: int = DEFAULT_BLOCK_C,
                     interpret: bool = False):
    """p_sum [B,C], p_dev [B,V,C], global_dist/class_weights [B,C],
    sizes [B] (set sizes, >= 1) -> dense swap matrix [B, V, V]."""
    B, V, C = p_dev.shape
    block_c = min(block_c, C)
    pad_c = (-C) % block_c
    pad_v = (-V) % block_i
    f32 = jnp.float32
    # padded classes get zero weight -> contribute nothing to the sum
    p_sum = _row(p_sum, pad_c)
    gd = _row(global_dist, pad_c)
    cw = _row(class_weights, pad_c)
    pd = jnp.pad(p_dev.astype(f32), ((0, 0), (0, pad_v), (0, pad_c)))
    Vp, Cp = V + pad_v, C + pad_c
    sz = jnp.reshape(sizes.astype(f32), (B, 1, 1))

    row = pl.BlockSpec((1, 1, block_c), lambda b, it, ct: (b, _0, ct))
    grid = (B, Vp // block_i, Cp // block_c)
    out = pl.pallas_call(
        _swap_kernel,
        grid=grid,
        in_specs=[
            row,
            pl.BlockSpec((1, block_i, block_c),
                         lambda b, it, ct: (b, it, ct)),
            pl.BlockSpec((1, Vp, block_c), lambda b, it, ct: (b, _0, ct)),
            row,
            row,
            pl.BlockSpec((1, 1, 1), lambda b, it, ct: (b, _0, _0)),
        ],
        out_specs=pl.BlockSpec((1, block_i, Vp),
                               lambda b, it, ct: (b, it, _0)),
        out_shape=jax.ShapeDtypeStruct((B, Vp, Vp), f32),
        interpret=interpret,
    )(p_sum, pd, pd, gd, cw, sz)
    return out[:, :V, :V]


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def wemd_add_pallas(p_sum, p_dev, global_dist, class_weights, sizes, *,
                    block_c: int = DEFAULT_BLOCK_C,
                    interpret: bool = False):
    """p_sum [B,C], p_dev [B,V,C], global_dist/class_weights [B,C],
    sizes [B] (current set sizes, >= 0) -> add matrix [B, V]."""
    B, V, C = p_dev.shape
    block_c = min(block_c, C)
    pad_c = (-C) % block_c
    f32 = jnp.float32
    p_sum = _row(p_sum, pad_c)
    gd = _row(global_dist, pad_c)
    cw = _row(class_weights, pad_c)
    pd = jnp.pad(p_dev.astype(f32), ((0, 0), (0, 0), (0, pad_c)))
    Cp = C + pad_c
    sz = jnp.reshape(sizes.astype(f32), (B, 1, 1))

    row = pl.BlockSpec((1, 1, block_c), lambda b, ct: (b, _0, ct))
    grid = (B, Cp // block_c)
    out = pl.pallas_call(
        _add_kernel,
        grid=grid,
        in_specs=[
            row,
            pl.BlockSpec((1, V, block_c), lambda b, ct: (b, _0, ct)),
            row,
            row,
            pl.BlockSpec((1, 1, 1), lambda b, ct: (b, _0, _0)),
        ],
        # [B, V, 1]: the class-sum lands on the sublane axis, and the
        # last two block dims equal the array's
        out_specs=pl.BlockSpec((1, V, 1), lambda b, ct: (b, _0, _0)),
        out_shape=jax.ShapeDtypeStruct((B, V, 1), f32),
        interpret=interpret,
    )(p_sum, pd, gd, cw, sz)
    return out[:, :, 0]
