"""The paper's vision models (Section VI-A), pure JAX.

- paper_cnn: 2x[conv3x3-32]+pool+drop(0.2), 2x[conv3x3-64]+pool+drop(0.3),
  FC-120-ReLU, FC-num_classes.  Input NHWC 32x32x3 (paper: 20x3x32x32
  batches).
- resnet18_gn: ResNet-18 with every BatchNorm replaced by GroupNorm [50]
  (CIFAR stem: 3x3 stride-1 conv, no max-pool).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.paper_cnn import CNNConfig


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout)) * math.sqrt(2 / fan_in)


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _gn(x, scale, bias, groups=8, eps=1e-5):
    n, h, wd, c = x.shape
    g = x.reshape(n, h, wd, groups, c // groups).astype(jnp.float32)
    mean = g.mean((1, 2, 4), keepdims=True)
    var = g.var((1, 2, 4), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + eps)
    return (g.reshape(x.shape) * scale + bias).astype(x.dtype)


def _maxpool2(x):
    """2x2 max-pool, stride 2, "VALID": an odd H or W drops its last row
    or column.  A reshape and a max, which XLA compiles to plain
    reductions; a ``reduce_window`` would bring a ``select_and_scatter``
    into the gradient, the slowest op of the round core on a TPU.  Where
    a window's maximum is tied the gradient is split equally."""
    n, h, w, c = x.shape
    x = x[:, :h - h % 2, :w - w % 2]
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def _dropout(x, rate, rng):
    if rng is None or rate == 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


# ---------------------------------------------------------------------------
# paper CNN


def init_paper_cnn(key, cfg: CNNConfig):
    ks = jax.random.split(key, 8)
    # width may be fractional (benchmark-scale micro models): channel
    # counts round to >= 1
    c32 = max(1, int(round(32 * cfg.width)))
    c64 = max(1, int(round(64 * cfg.width)))
    flat = (cfg.image_size // 4) ** 2 * c64
    return {
        "c1": _conv_init(ks[0], 3, 3, cfg.channels, c32),
        "c2": _conv_init(ks[1], 3, 3, c32, c32),
        "c3": _conv_init(ks[2], 3, 3, c32, c64),
        "c4": _conv_init(ks[3], 3, 3, c64, c64),
        "fc1": jax.random.normal(ks[4], (flat, 120)) * math.sqrt(2 / flat),
        "b1": jnp.zeros((120,)),
        "fc2": jax.random.normal(ks[5], (120, cfg.num_classes)) * 0.1,
        "b2": jnp.zeros((cfg.num_classes,)),
    }


def paper_cnn_forward(params, cfg: CNNConfig, images, rng=None):
    """images [B,H,W,C] f32 -> logits [B,num_classes]."""
    r1 = r2 = None
    if rng is not None and cfg.dropout:
        r1, r2 = jax.random.split(rng)
    x = jax.nn.relu(_conv(images, params["c1"]))
    x = jax.nn.relu(_conv(x, params["c2"]))
    x = _maxpool2(x)
    x = _dropout(x, 0.2, r1)
    x = jax.nn.relu(_conv(x, params["c3"]))
    x = jax.nn.relu(_conv(x, params["c4"]))
    x = _maxpool2(x)
    x = _dropout(x, 0.3, r2)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"] + params["b1"])
    return x @ params["fc2"] + params["b2"]


# ---------------------------------------------------------------------------
# ResNet18-GN


def _block_init(key, cin, cout, stride):
    ks = jax.random.split(key, 3)
    p = {
        "conv1": _conv_init(ks[0], 3, 3, cin, cout),
        "gn1_s": jnp.ones((cout,)), "gn1_b": jnp.zeros((cout,)),
        "conv2": _conv_init(ks[1], 3, 3, cout, cout),
        "gn2_s": jnp.ones((cout,)), "gn2_b": jnp.zeros((cout,)),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[2], 1, 1, cin, cout)
        p["gnp_s"] = jnp.ones((cout,))
        p["gnp_b"] = jnp.zeros((cout,))
    return p


def _block_fwd(p, x, stride, groups):
    h = jax.nn.relu(_gn(_conv(x, p["conv1"], stride), p["gn1_s"], p["gn1_b"],
                        groups))
    h = _gn(_conv(h, p["conv2"]), p["gn2_s"], p["gn2_b"], groups)
    if "proj" in p:
        x = _gn(_conv(x, p["proj"], stride), p["gnp_s"], p["gnp_b"], groups)
    return jax.nn.relu(x + h)


STAGES = [(64, 1), (128, 2), (256, 2), (512, 2)]


def init_resnet18_gn(key, cfg: CNNConfig):
    ks = jax.random.split(key, 11)
    params = {
        "stem": _conv_init(ks[0], 3, 3, cfg.channels, 64),
        "gn_s": jnp.ones((64,)), "gn_b": jnp.zeros((64,)),
        "fc": jax.random.normal(ks[1], (512, cfg.num_classes)) * 0.05,
        "fc_b": jnp.zeros((cfg.num_classes,)),
    }
    cin = 64
    i = 2
    for si, (cout, stride) in enumerate(STAGES):
        for bi in range(2):
            params[f"s{si}b{bi}"] = _block_init(
                ks[i], cin, cout, stride if bi == 0 else 1)
            cin = cout
            i += 1
    return params


def resnet18_gn_forward(params, cfg: CNNConfig, images, rng=None):
    g = cfg.gn_groups
    x = jax.nn.relu(_gn(_conv(images, params["stem"]), params["gn_s"],
                        params["gn_b"], g))
    for si, (cout, stride) in enumerate(STAGES):
        for bi in range(2):
            x = _block_fwd(params[f"s{si}b{bi}"], x,
                           stride if bi == 0 else 1, g)
    x = x.mean(axis=(1, 2))
    return x @ params["fc"] + params["fc_b"]


def init_cnn(key, cfg: CNNConfig):
    if cfg.kind == "paper_cnn":
        return init_paper_cnn(key, cfg)
    return init_resnet18_gn(key, cfg)


def cnn_forward(params, cfg: CNNConfig, images, rng=None):
    if cfg.kind == "paper_cnn":
        return paper_cnn_forward(params, cfg, images, rng)
    return resnet18_gn_forward(params, cfg, images, rng)
