"""Plain reference of a toy dense decoder LM, for the benchmark's CPU
tests of the token path.

``num_layers`` x [RMSNorm, causal GQA attention with RoPE, residual,
RMSNorm, SwiGLU MLP, residual], a final RMSNorm and an untied head, as
``repro.models.transformer`` computes them: the embedding scaled by
sqrt(d_model), RMSNorm x * rsqrt(mean(x^2) + 1e-6) * (1 + scale), RoPE on
the two halves of each head, scores over sqrt(head_dim).  Float32
throughout.

The loss is the program's next-token cross-entropy: the target of
position s is token s + 1, the last position is masked out, and the mean
is taken over every unmasked position of the batch.  The sigma is the
program's LM proxy of Eq. 10: the last-layer sigma with features of ones
on the final position's logits, whose target is the sequence's last
token.  The topic label takes no part in either."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6


def _n_params(cfg):
    d, hd, V = cfg["d_model"], cfg["head_dim"], cfg["vocab_size"]
    H, KV, ff = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    layer = 2 * d + d * (H + 2 * KV) * hd + H * hd * d + 3 * d * ff
    return cfg["num_layers"] * layer + 2 * V * d + d


def init(key, cfg):
    """The program's parameter tree: one period of one global-attention
    layer, stacked over the layers under ``blocks``."""
    d, hd, V = cfg["d_model"], cfg["head_dim"], cfg["vocab_size"]
    H, KV, ff, L = (cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"],
                    cfg["num_layers"])
    k = jax.random.split(key, 6)
    normal = lambda kk, shape, scale: jax.random.normal(kk, shape) * scale
    lk = jax.random.split(k[2], 7)
    layer = {
        "norm1": {"scale": jnp.zeros((L, d))},
        "norm2": {"scale": jnp.zeros((L, d))},
        "attn": {"wq": normal(lk[0], (L, d, H, hd), 1 / math.sqrt(d)),
                 "wk": normal(lk[1], (L, d, KV, hd), 1 / math.sqrt(d)),
                 "wv": normal(lk[2], (L, d, KV, hd), 1 / math.sqrt(d)),
                 "wo": normal(lk[3], (L, H, hd, d), 1 / math.sqrt(H * hd))},
        "ffn": {"w_gate": normal(lk[4], (L, d, ff), 1 / math.sqrt(d)),
                "w_up": normal(lk[5], (L, d, ff), 1 / math.sqrt(d)),
                "w_down": normal(lk[6], (L, ff, d), 1 / math.sqrt(ff))},
    }
    return {"embed": normal(k[0], (V, d), 0.02),
            "blocks": {"layer0": layer},
            "final_norm": {"scale": jnp.zeros((d,))},
            "lm_head": normal(k[3], (d, V), 1 / math.sqrt(d))}


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * (1.0 + scale)


def _rope(x, theta):
    """x [b, S, heads, hd] at positions 0..S-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freq
    sin, cos = jnp.sin(ang).astype(x.dtype), jnp.cos(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, cfg, x, precision):
    H, KV, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    a = p["attn"]
    h = _rms(x, p["norm1"]["scale"])
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wq"], precision=precision),
              cfg["rope_theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wk"], precision=precision),
              cfg["rope_theta"])
    v = jnp.einsum("bsd,dhk->bshk", h, a["wv"], precision=precision)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bshk,bthk->bhst", q, k, precision=precision) \
        / math.sqrt(hd)
    S = x.shape[1]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthk->bshk", w, v, precision=precision)
    x = x + jnp.einsum("bshk,hkd->bsd", o, a["wo"], precision=precision)
    f = p["ffn"]
    h = _rms(x, p["norm2"]["scale"])
    g = jax.nn.silu(jnp.dot(h, f["w_gate"], precision=precision)) \
        * jnp.dot(h, f["w_up"], precision=precision)
    return x + jnp.dot(g, f["w_down"], precision=precision)


def logits(p, cfg, tokens, precision):
    """Logits [b, S, vocab] of int tokens [b, S]."""
    x = p["embed"][tokens] * jnp.sqrt(jnp.asarray(cfg["d_model"],
                                                  p["embed"].dtype))
    for i in range(cfg["num_layers"]):
        x = _layer(jax.tree.map(lambda a: a[i], p["blocks"]["layer0"]), cfg,
                   x, precision)
    x = _rms(x, p["final_norm"]["scale"])
    return jnp.dot(x, p["lm_head"], precision=precision)


def loss(p, cfg, x, y, precision, key):
    z = logits(p, cfg, x, precision)
    targets = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
    mask = jnp.ones(x.shape, z.dtype).at[:, -1].set(0)
    true = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    ce = jax.nn.logsumexp(z, axis=-1) - true
    return jnp.sum(ce * mask) / jnp.sum(mask)


def sigma(p, cfg, x, y, precision):
    z = logits(p, cfg, x, precision)[:, -1]
    e = jax.nn.softmax(z, axis=-1) - jax.nn.one_hot(x[:, -1], z.shape[-1],
                                                    dtype=z.dtype)
    # features of ones: each sample's head gradient is its e
    return jnp.sqrt(jnp.mean(jnp.sum(jnp.square(e - e.mean(0)), axis=-1)))


def _forward_flops(cfg):
    """FLOPs of one sequence's forward pass: the projections, the MLP
    and the head at every position, and the full S x S scores and their
    weighted sum in every layer (the program masks, it does not skip)."""
    d, hd, V, S = (cfg["d_model"], cfg["head_dim"], cfg["vocab_size"],
                   cfg["seq_len"])
    H, KV, ff = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    per_token = cfg["num_layers"] * (d * (H + 2 * KV) * hd + H * hd * d
                                     + 3 * d * ff) + d * V
    return 2 * S * per_token + cfg["num_layers"] * 2 * 2 * S * S * H * hd


def round_core_flops(cfg, traffic):
    """tau SGD steps (forward, and a backward of twice its FLOPs: the
    embedding's gradient needs every layer's input gradient) on b
    sequences per device, and the sigma's forward over the first batch."""
    t = traffic
    seqs = t["num_cells"] * t["num_devices"] * t["batch_size"]
    return seqs * (t["tau"] * 3 + 1) * _forward_flops(cfg)


def round_core_bytes(cfg, traffic):
    """The broadcast model read once per cell, every device's int32
    tokens read, and every device's f32 update written."""
    t = traffic
    n = _n_params(cfg)
    return (t["num_cells"] * n * 4
            + t["num_cells"] * t["num_devices"] * t["batch_size"]
            * cfg["seq_len"] * 4
            + t["num_cells"] * t["num_devices"] * n * 4)


def program_model(cfg):
    """The system under test's model for this configuration."""
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    return build_model(ModelConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=cfg["num_layers"], d_model=cfg["d_model"],
        num_heads=cfg["num_heads"], num_kv_heads=cfg["num_kv_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["d_ff"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        dtype="float32", param_dtype="float32", remat=False))
