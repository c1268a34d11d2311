"""Plain reference of the FedCGD paper's CIFAR-10 CNN (paper §VI-A).

2 x [conv3x3-32, ReLU], 2x2 max-pool, dropout 0.2, 2 x [conv3x3-64,
ReLU], 2x2 max-pool, dropout 0.3, FC-120 with ReLU, FC-10; NHWC input,
SAME padding, no conv bias.  Dropout keeps each activation with
probability 1 - rate and scales the kept ones by 1 / (1 - rate); its two
masks are Bernoulli draws from the two halves of the step's key."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _widths(cfg):
    w = cfg["width"]
    return max(1, int(round(32 * w))), max(1, int(round(64 * w)))


def layers(cfg):
    c32, c64 = _widths(cfg)
    s, ch = cfg["image_size"], cfg["channels"]
    conv = lambda hw, cin, cout: {"kind": "conv", "in_hw": hw, "cin": cin,
                                  "cout": cout, "k": 3, "stride": 1}
    return [conv(s, ch, c32), conv(s, c32, c32),
            conv(s // 2, c32, c64), conv(s // 2, c64, c64),
            {"kind": "dense", "din": (s // 4) ** 2 * c64, "dout": 120},
            {"kind": "dense", "din": 120, "dout": cfg["num_classes"]}]


def init(key, cfg):
    """He-normal convolutions and hidden layer, N(0, 0.1) head, zero
    biases, float32."""
    c32, c64 = _widths(cfg)
    flat = (cfg["image_size"] // 4) ** 2 * c64
    k = jax.random.split(key, 6)
    he = lambda kk, shape, fan: (jax.random.normal(kk, shape)
                                 * math.sqrt(2.0 / fan))
    ch = cfg["channels"]
    return {
        "c1": he(k[0], (3, 3, ch, c32), 9 * ch),
        "c2": he(k[1], (3, 3, c32, c32), 9 * c32),
        "c3": he(k[2], (3, 3, c32, c64), 9 * c32),
        "c4": he(k[3], (3, 3, c64, c64), 9 * c64),
        "fc1": he(k[4], (flat, 120), flat),
        "b1": jnp.zeros((120,)),
        "fc2": jax.random.normal(k[5], (120, cfg["num_classes"])) * 0.1,
        "b2": jnp.zeros((cfg["num_classes"],)),
    }


def _conv(x, w, precision):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _pool(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def _drop(x, rate, key):
    if key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / jnp.asarray(1.0 - rate, x.dtype), 0.0)


def features_logits(p, cfg, x, precision, key=None):
    """(penultimate features [B, 120], logits [B, classes]); with a
    ``key`` and ``dropout`` on, the training pass with its masks."""
    relu = jax.nn.relu
    k1 = k2 = None
    if key is not None and cfg["dropout"]:
        k1, k2 = jax.random.split(key)
    x = relu(_conv(x, p["c1"], precision))
    x = _drop(_pool(relu(_conv(x, p["c2"], precision))), 0.2, k1)
    x = relu(_conv(x, p["c3"], precision))
    x = _drop(_pool(relu(_conv(x, p["c4"], precision))), 0.3, k2)
    x = x.reshape(x.shape[0], -1)
    h = relu(jnp.dot(x, p["fc1"], precision=precision) + p["b1"])
    return h, jnp.dot(h, p["fc2"], precision=precision) + p["b2"]


def program_model(cfg):
    """The system under test's model for this configuration."""
    from repro.configs.paper_cnn import CNNConfig
    from repro.models import build_model
    return build_model(CNNConfig(
        name=cfg["name"], kind="paper_cnn", num_classes=cfg["num_classes"],
        image_size=cfg["image_size"], channels=cfg["channels"],
        dropout=cfg["dropout"], width=cfg["width"]))
