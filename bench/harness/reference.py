"""The plain reference of a FedCGD round, followed over the checked rounds.

Per round, for every device: one SGD step (Eq. 1, tau = 1) of the
configuration's loss on the rows that device trained on, its loss, and
its Eq. 10 sigma; then Eq. 11 over the devices and Eq. 2, the mean of
the device models that uploaded.

The loss and the sigma are the configuration's reference module's
``loss(p, cfg, x, y, precision, key) -> scalar`` and
``sigma(p, cfg, x, y, precision) -> scalar`` where it defines them, and
otherwise the classifier's: the mean softmax cross-entropy of
``features_logits``'s logits against the rows' labels, and the
root-mean-square deviation of the per-sample gradients of the classifier
head from their mean, formed sample by sample, on the round's weights
without dropout (``default_loss``, ``default_sigma``).  Floating inputs
take the reference's dtype; integer inputs (token ids) stay integers.

Where the configuration drops activations, the masks come from the
device's key as the program derives it (``device_keys``): a cell's key
from its seed, split once a round, the round's key split over the
devices, each device's key split once for its local step; the reference
module draws its masks from that step key.

Devices run in chunks of ``chunk`` (vmapped) so that the reference fits
beside nothing: it runs after the program's state is freed."""
from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def device_keys(cell_seed: int, num_devices: List[int]) -> list:
    """Per round i, the key data [num_devices[i], 2] of the devices'
    keys."""
    k = jax.random.key(cell_seed)
    out = []
    for n in num_devices:
        k, sub = jax.random.split(k)
        out.append(np.asarray(jax.random.key_data(jax.random.split(sub, n))))
    return out


def default_loss(ref, p, cfg, x, y, precision, key):
    """Mean softmax cross-entropy of ``features_logits``'s logits."""
    _, logits = ref.features_logits(p, cfg, x, precision, key)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - true)


def default_sigma(ref, p, cfg, x, y, precision):
    """Eq. 10 over the classifier head: per-sample gradients h_i e_i^T."""
    h, z = ref.features_logits(p, cfg, x, precision)
    e = jax.nn.softmax(z, axis=-1) - jax.nn.one_hot(y, z.shape[-1],
                                                    dtype=z.dtype)
    gi = h[:, :, None] * e[:, None, :]                 # [b, d, C]
    dev_sq = jnp.sum(jnp.square(gi - gi.mean(0)), axis=(1, 2))
    return jnp.sqrt(jnp.mean(dev_sq))


def _device_step(ref, cfg, eta, dtype, precision, params, x, y, key):
    _, step_key = jax.random.split(jax.random.wrap_key_data(key))
    loss = getattr(ref, "loss", None) or partial(default_loss, ref)
    sigma = getattr(ref, "sigma", None) or partial(default_sigma, ref)
    l, g = jax.value_and_grad(
        lambda p: loss(p, cfg, x, y, precision, step_key))(params)
    dev = jax.tree.map(lambda a, b: (a - jnp.asarray(eta, dtype) * b),
                       params, g)
    return l, sigma(params, cfg, x, y, precision), dev


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _chunk(ref, cfg, eta, dtype, precision, params, xs, ys, keys, w):
    step = partial(_device_step, ref, cfg, eta, dtype, precision)
    l, s, dev = jax.vmap(step, in_axes=(None, 0, 0, 0))(params, xs, ys,
                                                       keys)
    contrib = jax.tree.map(
        lambda d: jnp.sum(d * w.astype(d.dtype).reshape(
            (-1,) + (1,) * (d.ndim - 1)), axis=0), dev)
    return l, s, contrib


class Frozen(dict):
    """A configuration as the jitted chunk's static argument: a dict that
    hashes.  ``freeze`` makes its lists tuples and its groups ``Frozen``;
    nothing may change it after."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def freeze(v):
    """The configuration value ``v`` with every list a tuple and every
    dict ``Frozen``, recursively: the whole configuration reaches the
    reference module."""
    if isinstance(v, dict):
        return Frozen((k, freeze(x)) for k, x in v.items())
    if isinstance(v, (list, tuple)):
        return tuple(freeze(x) for x in v)
    return v


def follow(ref, cfg: Dict, w0, inputs: np.ndarray, labels: np.ndarray,
           rounds: List[Dict], eta: float, dtype=jnp.float32,
           precision=jax.lax.Precision.HIGHEST, chunk: int = 8) -> List[Dict]:
    """Follow the rounds from the weights ``w0``.

    Each round is {"takes": [V, b] row indices, "keys": [V, 2] key data
    (``device_keys``), "upload": [V] bool}.
    Returns per round {"loss", "dev_losses", "sigma_hat", "params"}
    (params after the round, as host numpy arrays)."""
    frozen = freeze(cfg)
    p = jax.tree.map(lambda a: jnp.asarray(a, dtype), w0)
    out = []
    for r in rounds:
        takes = np.asarray(r["takes"])
        keys = np.asarray(r["keys"])
        up = np.asarray(r["upload"], bool)
        V = len(takes)
        w = up / max(int(up.sum()), 1)
        pad = (-V) % chunk
        if pad:      # pad with zero-weight copies of device 0
            takes = np.concatenate([takes, np.repeat(takes[:1], pad, 0)])
            keys = np.concatenate([keys, np.repeat(keys[:1], pad, 0)])
            w = np.concatenate([w, np.zeros(pad)])
        losses, sigmas, acc = [], [], None
        for s in range(0, len(takes), chunk):
            x = inputs[takes[s:s + chunk]]
            l, sg, contrib = _chunk(
                ref, frozen, float(eta), dtype, precision, p,
                jnp.asarray(x, dtype if np.issubdtype(x.dtype, np.floating)
                            else None),
                jnp.asarray(labels[takes[s:s + chunk]]),
                jnp.asarray(keys[s:s + chunk]),
                jnp.asarray(w[s:s + chunk], dtype))
            losses.append(np.asarray(l, np.float64))
            sigmas.append(np.asarray(sg, np.float64))
            acc = contrib if acc is None else jax.tree.map(jnp.add, acc,
                                                           contrib)
        losses = np.concatenate(losses)[:V]
        sigmas = np.concatenate(sigmas)[:V]
        if up.any():
            p = acc
        out.append({"loss": float(losses.mean()), "dev_losses": losses,
                    "sigma_hat": float(np.sqrt(np.mean(sigmas ** 2))),
                    "params": jax.device_get(p)})
    return out
