"""Operations and bytes of a round's core, counted by the configuration.

``round_core`` is the one entry point.  A configuration's reference
module may count its own round with ``round_core_flops(cfg, traffic)``
and ``round_core_bytes(cfg, traffic)``; a module without them is an
image classifier counted here from its layer table (``layers(cfg)``) and
its image size.

A layer is a dict with ``kind`` ("conv" or "dense") and its shapes:

  conv:  in_hw, cin, cout, k, stride   (SAME padding: out_hw = in_hw / stride)
  dense: din, dout

Counts are multiply-adds times two.  Elementwise work (activations,
normalisation, pooling, the softmax) is left out: it is under 1% of the
convolutions of either model here."""
from __future__ import annotations

from typing import Dict, List, Tuple


def layer_macs(layer: Dict) -> int:
    if layer["kind"] == "conv":
        out_hw = -(-layer["in_hw"] // layer["stride"])
        return (out_hw * out_hw * layer["cout"]
                * layer["k"] * layer["k"] * layer["cin"])
    if layer["kind"] == "dense":
        return layer["din"] * layer["dout"]
    raise ValueError(f"unknown layer kind {layer['kind']!r}")


def forward_flops(layers: List[Dict]) -> int:
    """FLOPs of one image's forward pass."""
    return 2 * sum(layer_macs(l) for l in layers)


def train_flops(layers: List[Dict]) -> int:
    """FLOPs of one image's forward and backward pass: the forward, the
    weight gradient of every layer, and the input gradient of every
    layer but the first (the image needs none)."""
    fwd = forward_flops(layers)
    dgrad = fwd - 2 * layer_macs(layers[0])
    return fwd + fwd + dgrad


def round_core_flops(layers: List[Dict], cells: int, devices: int,
                     tau: int, batch: int) -> int:
    """FLOPs a round needs on the device: tau SGD steps of ``batch``
    images on every device of every cell, plus the Eq. 10 forward pass
    over each device's first batch."""
    images = cells * devices * batch
    return images * (tau * train_flops(layers) + forward_flops(layers))


def round_core_bytes(n_params: int, cells: int, devices: int, batch: int,
                     image_bytes: int, param_bytes: int = 4) -> int:
    """Bytes the round core cannot avoid moving through HBM: the
    broadcast model read once per cell, every device's images read, and
    every device's update (the upload payload) written."""
    return (cells * n_params * param_bytes
            + cells * devices * batch * image_bytes
            + cells * devices * n_params * param_bytes)


def round_core(ref, cfg: Dict, traffic: Dict) -> Tuple[int, int]:
    """(FLOPs, bytes) of one execution of the round core: the module's
    own counts where it defines them, else the layer table's FLOPs and
    the bytes of f32 images and weights."""
    t = traffic
    if hasattr(ref, "round_core_flops"):
        f = ref.round_core_flops(cfg, t)
    else:
        f = round_core_flops(ref.layers(cfg), t["num_cells"],
                             t["num_devices"], t["tau"], t["batch_size"])
    if hasattr(ref, "round_core_bytes"):
        b = ref.round_core_bytes(cfg, t)
    else:
        b = round_core_bytes(cfg["parameters"], t["num_cells"],
                             t["num_devices"], t["batch_size"],
                             cfg["image_size"] ** 2 * cfg["channels"] * 4)
    return f, b
