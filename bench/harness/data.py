"""Inputs made from the seed: CIFAR-shaped images or token sequences, and
the device shards.

Images are class templates (smoothed Gaussian noise), each sample the
template of its class rolled by a random shift of up to 3 pixels plus
Gaussian noise of standard deviation 0.6, with balanced classes in a
random order.

Token sequences each belong to a topic, the row's label, with balanced
topics in a random order.  Every topic ranks the vocabulary by a
permutation of its own and draws each token of its sequences
independently from a Zipf law over that ranking: P(rank r) is
proportional to (r + 1) ** -zipf.

One jitted call makes the whole set on the device; the host gets it back
in one transfer."""
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


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _tokens(key, n: int, num_classes: int, seq_len: int, vocab_size: int,
            zipf: float):
    kp, kl, ku = jax.random.split(key, 3)
    ranked = jax.vmap(lambda k: jax.random.permutation(k, vocab_size))(
        jax.random.split(kp, num_classes))           # [topics, vocab]
    law = jnp.arange(1, vocab_size + 1, dtype=jnp.float32) ** -zipf
    cdf = jnp.cumsum(law) / jnp.sum(law)
    topics = jax.random.permutation(kl, jnp.arange(n) % num_classes)
    u = jax.random.uniform(ku, (n, seq_len))
    rank = jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                       vocab_size - 1)
    return (ranked[topics[:, None], rank].astype(jnp.int32),
            topics.astype(jnp.int32))


def seed_key(seed: int):
    """A JAX key from a seed of any size (the low and high 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def images(seed: int, n: int, num_classes: int, size: int = 32,
           channels: int = 3) -> Tuple[np.ndarray, np.ndarray]:
    """(images [n, size, size, channels] f32, labels [n] i32)."""
    x, y = jax.device_get(_images(seed_key(seed), n, num_classes, size,
                                  channels))
    return np.asarray(x).reshape(n, size, size, channels), np.asarray(y)


def tokens(seed: int, n: int, num_classes: int, seq_len: int,
           vocab_size: int, zipf: float) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens [n, seq_len] i32 in [0, vocab_size), topics [n] i32)."""
    x, y = jax.device_get(_tokens(seed_key(seed), n, num_classes, seq_len,
                                  vocab_size, float(zipf)))
    return np.asarray(x), np.asarray(y)


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
    """The input array handed to the program.  While its ``log`` is a
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
