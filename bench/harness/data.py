"""Inputs made from the seed: CIFAR-shaped images and the device shards.

Images are class templates (smoothed Gaussian noise), each sample the
template of its class rolled by a random shift of up to 3 pixels plus
Gaussian noise of standard deviation 0.6, with balanced classes in a
random order.  One jitted call makes the whole set on the device; the
host gets it back in one transfer."""
from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NOISE = 0.6
MAX_SHIFT = 3


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _images(key, n: int, num_classes: int, size: int, channels: int):
    kt, kl, ks, kn = jax.random.split(key, 4)
    t = jax.random.normal(kt, (num_classes, size, size, channels))
    t = (t + jnp.roll(t, 1, 1) + jnp.roll(t, 1, 2)) / 3.0
    # every class template under every shift, one flat row each (a
    # trailing axis of 3 channels would be padded to 128 lanes)
    span = range(-MAX_SHIFT, MAX_SHIFT + 1)
    shifted = jnp.stack([jnp.roll(t, (dy, dx), axis=(1, 2))
                         for dy in span for dx in span], axis=1)
    rows = shifted.reshape(num_classes * len(span) ** 2, -1)
    labels = jax.random.permutation(kl, jnp.arange(n) % num_classes)
    shift = jax.random.randint(ks, (n,), 0, len(span) ** 2)
    x = rows[labels * len(span) ** 2 + shift]
    x = x + NOISE * jax.random.normal(kn, x.shape)
    return x.astype(jnp.float32), labels.astype(jnp.int32)


def seed_key(seed: int):
    """A JAX key from a seed of any size (the low and high 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def images(seed: int, n: int, num_classes: int, size: int = 32,
           channels: int = 3) -> Tuple[np.ndarray, np.ndarray]:
    """(images [n, size, size, channels] f32, labels [n] i32)."""
    x, y = jax.device_get(_images(seed_key(seed), n, num_classes, size,
                                  channels))
    return np.asarray(x).reshape(n, size, size, channels), np.asarray(y)


def shards(labels: np.ndarray, num_devices: int, shards_per_device: int,
           seed: int) -> List[np.ndarray]:
    """Label-sorted shards, ``shards_per_device`` to a device, dealt in
    an order drawn from the seed (the paper's sort-and-partition)."""
    order = np.argsort(labels, kind="stable")
    pieces = np.array_split(order, num_devices * shards_per_device)
    deal = np.random.default_rng(seed).permutation(len(pieces))
    k = shards_per_device
    return [np.concatenate([pieces[i] for i in deal[v * k:(v + 1) * k]])
            for v in range(num_devices)]


class RecordingArray(np.ndarray):
    """The image array handed to the program.  While its ``log`` is a
    list, every gather by an index array appends the indices to it, so
    the reference knows which rows each device trained on."""

    def __array_finalize__(self, obj):
        self.log = None

    def __getitem__(self, idx):
        out = np.ndarray.__getitem__(self, idx)
        if self.log is not None and isinstance(idx, np.ndarray) \
                and idx.dtype.kind in "iu":
            self.log.append(np.array(idx))
        return out.view(np.ndarray) if isinstance(out, np.ndarray) else out
