"""Jit'd public wrappers over the Pallas kernels.

On the CPU backend (tests) the kernels run in ``interpret=True`` mode —
the kernel body executes as Python/jnp, validating the exact TPU code
path.  On any other backend ``interpret=False`` compiles to Mosaic, so a
run meant for the chip fails loudly instead of interpreting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.persample_gradnorm import persample_gradnorm_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.rwkv_scan import wkv_pallas
from repro.kernels.wemd_swap import wemd_add_pallas, wemd_swap_pallas


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q [B,H,S,hd] layout (kernel layout; models use [B,S,H,hd])."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=_interpret())


def wkv(r, k, v, w, u):
    return wkv_pallas(r, k, v, w, u, interpret=_interpret())


def rglru(a, b, h0):
    return rglru_pallas(a, b, h0, interpret=_interpret())


def persample_gradnorm_sigma(features, logits, labels):
    sigma, _ = persample_gradnorm_pallas(features, logits, labels,
                                         interpret=_interpret())
    return sigma


def wemd_swap(p_sum, p_dev, global_dist, class_weights, sizes):
    """Dense [B,V,V] swap-candidate WEMD matrix (FSCD inner loop)."""
    return wemd_swap_pallas(p_sum, p_dev, global_dist, class_weights,
                            sizes, interpret=_interpret())


def wemd_add(p_sum, p_dev, global_dist, class_weights, sizes):
    """[B,V] add-candidate WEMD row (GS inner loop)."""
    return wemd_add_pallas(p_sum, p_dev, global_dist, class_weights,
                           sizes, interpret=_interpret())


__all__ = ["attention", "wkv", "rglru", "persample_gradnorm_sigma",
           "wemd_swap", "wemd_add",
           "flash_attention", "wkv_pallas", "rglru_pallas",
           "persample_gradnorm_pallas", "wemd_swap_pallas",
           "wemd_add_pallas", "ref"]
