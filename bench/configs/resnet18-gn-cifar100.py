"""Plain reference of ResNet-18 with GroupNorm for CIFAR-100 (FedCGD
paper §VI-A; He et al. 2016, basic blocks; Wu & He 2018, GroupNorm).

CIFAR stem (3x3 conv-64, stride 1, no max-pool), four stages of two
basic blocks (64, 128, 256, 512 channels; the first block of stages 2-4
strides by 2 and projects its shortcut with a 1x1 conv), GroupNorm with
``gn_groups`` groups after every conv (eps 1e-5), global average pool,
FC-100.  Convolutions have no bias and SAME padding (a 3x3 stride-2
conv on an even side pads 0 before and 1 after)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STAGES = ((64, 1), (128, 2), (256, 2), (512, 2))


def layers(cfg):
    s, ch = cfg["image_size"], cfg["channels"]
    conv = lambda hw, cin, cout, k=3, stride=1: {
        "kind": "conv", "in_hw": hw, "cin": cin, "cout": cout, "k": k,
        "stride": stride}
    out = [conv(s, ch, 64)]
    hw, cin = s, 64
    for cout, stride in STAGES:
        for b in range(2):
            st = stride if b == 0 else 1
            out.append(conv(hw, cin, cout, 3, st))
            if st != 1 or cin != cout:
                out.append(conv(hw, cin, cout, 1, st))
            hw = -(-hw // st)
            out.append(conv(hw, cout, cout))
            cin = cout
    out.append({"kind": "dense", "din": 512, "dout": cfg["num_classes"]})
    return out


def init(key, cfg):
    """He-normal convolutions, N(0, 0.05) head, GroupNorm scale 1 and
    bias 0, zero head bias, float32."""
    keys = iter(jax.random.split(key, 64))
    conv = lambda k, cin, cout: (jax.random.normal(
        next(keys), (k, k, cin, cout)) * math.sqrt(2.0 / (k * k * cin)))
    gn = lambda c: (jnp.ones((c,)), jnp.zeros((c,)))
    p = {"stem": conv(3, cfg["channels"], 64)}
    p["gn_s"], p["gn_b"] = gn(64)
    cin = 64
    for si, (cout, stride) in enumerate(STAGES):
        for b in range(2):
            st = stride if b == 0 else 1
            blk = {"conv1": conv(3, cin, cout), "conv2": conv(3, cout, cout)}
            blk["gn1_s"], blk["gn1_b"] = gn(cout)
            blk["gn2_s"], blk["gn2_b"] = gn(cout)
            if st != 1 or cin != cout:
                blk["proj"] = conv(1, cin, cout)
                blk["gnp_s"], blk["gnp_b"] = gn(cout)
            p[f"s{si}b{b}"] = blk
            cin = cout
    p["fc"] = jax.random.normal(next(keys), (512, cfg["num_classes"])) * 0.05
    p["fc_b"] = jnp.zeros((cfg["num_classes"],))
    return p


def _conv(x, w, precision, stride=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _gn(x, scale, bias, groups, eps=1e-5):
    n, h, w, c = x.shape
    g = x.reshape(n, h, w, groups, c // groups)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.square(g - mean).mean(axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + eps)
    return g.reshape(x.shape) * scale.astype(x.dtype) + bias.astype(x.dtype)


def features_logits(p, cfg, x, precision, key=None):
    """(pooled features [B, 512], logits [B, classes]); the model has no
    dropout, so ``key`` is unused."""
    G = cfg["gn_groups"]
    relu = jax.nn.relu
    x = relu(_gn(_conv(x, p["stem"], precision), p["gn_s"], p["gn_b"], G))
    for si, (_, stride) in enumerate(STAGES):
        for b in range(2):
            st = stride if b == 0 else 1
            q = p[f"s{si}b{b}"]
            h = relu(_gn(_conv(x, q["conv1"], precision, st),
                         q["gn1_s"], q["gn1_b"], G))
            h = _gn(_conv(h, q["conv2"], precision), q["gn2_s"],
                    q["gn2_b"], G)
            if "proj" in q:
                x = _gn(_conv(x, q["proj"], precision, st), q["gnp_s"],
                        q["gnp_b"], G)
            x = relu(x + h)
    feats = x.mean(axis=(1, 2))
    return feats, jnp.dot(feats, p["fc"], precision=precision) + p["fc_b"]


def program_model(cfg):
    """The system under test's model for this configuration."""
    from repro.configs.paper_cnn import CNNConfig
    from repro.models import build_model
    return build_model(CNNConfig(
        name=cfg["name"], kind="resnet18_gn", num_classes=cfg["num_classes"],
        image_size=cfg["image_size"], channels=cfg["channels"],
        gn_groups=cfg["gn_groups"]))
