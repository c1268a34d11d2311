"""Roofline analysis from compiled dry-run artifacts (brief §Roofline).

Per-chip peaks come from ``CHIP_PEAKS``, keyed by the device kind JAX
reports (``jax.Device.device_kind``); a kind missing from the table is
an error, never a default.

  compute term    = HLO_FLOPs / (chips * peak)
  memory term     = HLO_bytes / (chips * hbm_bw)
  collective term = collective_bytes / (chips * link_bw)

``compiled.cost_analysis()`` reports the per-device (post-SPMD) program,
so chips-totals are per-device values * chips; the formulas above then
cancel back to per-device time — we report exactly the brief's three
terms.  Collective bytes are parsed from the optimized HLO text: the sum
of result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (result bytes ~= bytes crossing links per
device, the standard proxy).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float               # bf16 FLOP/s per chip
    hbm_bw: float              # HBM bytes/s per chip
    link_bw: float             # bytes/s per ICI link
    source: str


CHIP_PEAKS: Dict[str, ChipPeaks] = {
    # 1,600 Gbit/s of ICI per chip over 4 links = 50 GB/s a link
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Published peaks of one chip of ``device_kind``."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to CHIP_PEAKS with their source") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Per-device bytes moved by each collective kind (result-shape sum)."""
    out = {k: 0.0 for k in COLLECTIVE_OPS}
    counts = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for op in COLLECTIVE_OPS:
            # match " = <shape> all-gather(" and async "-start(" forms
            if f" {op}(" not in stripped and f" {op}-start(" not in stripped:
                continue
            eq = stripped.split(" = ", 1)
            if len(eq) != 2:
                continue
            rhs = eq[1]
            total = 0
            # result may be a tuple shape: sum every element shape before
            # the op name
            opidx = rhs.find(op)
            for m in _SHAPE_RE.finditer(rhs[:opidx]):
                if m.group(1) in _DTYPE_BYTES:
                    total += _shape_bytes(m.group(1), m.group(2))
            out[op] += total
            counts[op] += 1
            break
    out["counts"] = counts
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float = 0.0
    flops_ratio: float = 0.0     # MODEL_FLOPS / (HLO_FLOPs * chips)

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   collective_bytes_per_device: float, chips: int,
                   device_kind: str, model_flops: float = 0.0) -> Roofline:
    peaks = chip_peaks(device_kind)
    compute = flops_per_device / peaks.flops
    memory = bytes_per_device / peaks.hbm_bw
    collective = collective_bytes_per_device / peaks.link_bw
    terms = {"compute": compute, "memory": memory, "collective": collective}
    bottleneck = max(terms, key=terms.get)
    total_hlo_flops = flops_per_device * chips
    return Roofline(
        flops_per_device=flops_per_device,
        bytes_per_device=bytes_per_device,
        collective_bytes_per_device=collective_bytes_per_device,
        chips=chips,
        compute_s=compute,
        memory_s=memory,
        collective_s=collective,
        bottleneck=bottleneck,
        model_flops=model_flops,
        flops_ratio=(model_flops / total_hlo_flops
                     if total_hlo_flops else 0.0),
    )


# ---------------------------------------------------------------------------
# MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE) for training;
# 2 N D for inference forward


def count_params(cfg) -> tuple:
    """(total_params, active_params) from the config (analytic)."""
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    H, KV, hd, dff = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_ff
    from repro.configs.base import (CROSS_ATTN, GLOBAL_ATTN, LOCAL_ATTN,
                                    RECURRENT, RWKV)
    total = V * d   # embedding
    active = V * d
    if not cfg.tie_embeddings:
        total += d * V
        active += d * V
    for i in range(L):
        kind = cfg.layer_kind(i)
        if kind == RWKV:
            tm = 4 * d * H * hd + H * hd * d + d * (5 * 32) + 5 * 32 * d \
                + d * 64 + 64 * d
            cm = d * dff + dff * d + d * d
            total += tm + cm
            active += tm + cm
            continue
        if kind == RECURRENT:
            lru = cfg.lru_width or d
            rec = 2 * d * lru + 2 * lru * lru + lru * d
            mlp = 3 * d * dff
            total += rec + mlp
            active += rec + mlp
            continue
        attn = d * (H * hd) + 2 * d * (KV * hd) + (H * hd) * d
        total += attn
        active += attn
        if kind == CROSS_ATTN or not cfg.num_experts:
            mlp = 3 * d * dff
            total += mlp
            active += mlp
        else:
            E, k = cfg.num_experts, cfg.experts_per_token
            total += E * 3 * d * dff + d * E
            active += k * 3 * d * dff + d * E
            if cfg.num_shared_experts:
                sh = 3 * d * (cfg.num_shared_experts * dff)
                total += sh
                active += sh
            if cfg.moe_dense_ff:
                dr = 3 * d * cfg.moe_dense_ff
                total += dr
                active += dr
    return total, active


def model_flops(cfg, shape, kind: str) -> float:
    """6 N_active D for training, 2 N_active D for one forward/decode."""
    _, active = count_params(cfg)
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    tokens = shape.global_batch          # one new token each
    return 2.0 * active * tokens
