"""The paper CNN's 2x2/2 max-pool (``repro.models.cnn._maxpool2``).

The pool is a reshape and a max.  These tests hold it to a
``lax.reduce_window`` max pool, kept here as the oracle: the forward
bitwise (NaN included), the gradient wherever a window's maximum is
unique or the tie sits at 0 behind a ReLU.  The round core's StableHLO
must hold no ``reduce_window`` and no ``select_and_scatter``: on a TPU
they cost the paper CNN's round core more than its convolutions' largest
fusions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import CNNConfig
from repro.data import sort_and_partition, synthetic_image_dataset
from repro.fl import FederatedTrainer, FLConfig
from repro.fl.client import make_round_core
from repro.models import build_model
from repro.models.cnn import _maxpool2

SHAPES = [(2, 32, 32, 32), (3, 7, 9, 5)]


def _oracle(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _distinct(shape, seed):
    """Floats all different from each other, centred on 0."""
    size = int(np.prod(shape))
    vals = np.random.default_rng(seed).permutation(size) - size / 2
    return jnp.asarray(vals.reshape(shape) / size, jnp.float32)


def _grad(pool, x, pre):
    out = jax.eval_shape(pool, x)
    w = jax.random.normal(jax.random.key(1), out.shape)
    return jax.grad(lambda x: (pool(pre(x)) * w).sum())(x)


@pytest.mark.parametrize("case", ["forward", "gradient"])
@pytest.mark.parametrize("shape", SHAPES)
def test_maxpool2_matches_reduce_window(shape, case):
    if case == "forward":
        x = np.array(jax.random.normal(jax.random.key(0), shape))
        x.reshape(-1)[::7] = np.nan
        x.reshape(-1)[3::11] = -np.inf
        x = jnp.asarray(x)
        got, want = np.asarray(_maxpool2(x)), np.asarray(_oracle(x))
        assert got.shape == want.shape
        assert np.isnan(want).any()
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))
        return
    # tie-free: the gradient goes to each window's one maximum
    x = _distinct(shape, 0)
    ident = lambda v: v
    np.testing.assert_array_equal(_grad(_maxpool2, x, ident),
                                  _grad(_oracle, x, ident))
    # half the values negative, so the ReLU ties many windows at 0,
    # where its derivative is 0 and neither form passes any gradient
    x = x - 0.25
    relu_out = np.asarray(jax.nn.relu(x))
    h, w = shape[1] // 2 * 2, shape[2] // 2 * 2
    win = relu_out[:, :h, :w].reshape(shape[0], h // 2, 2, w // 2, 2, -1)
    assert ((win == 0).sum(axis=(2, 4)) >= 2).any()
    g = _grad(_maxpool2, x, jax.nn.relu)
    np.testing.assert_array_equal(g, _grad(_oracle, x, jax.nn.relu))
    assert np.asarray(g).any()


def test_round_core_has_no_reduce_window():
    """The paper CNN's round core (training step and the Eq. 10 sigma
    forward), lowered as the accelerator runs it, keeps no windowed
    reduction."""
    cfg = CNNConfig(name="micro-cnn", kind="paper_cnn", num_classes=2,
                    image_size=8, width=0.25)
    ds = synthetic_image_dataset(num_classes=2, num_per_class=8,
                                 image_size=8, seed=0)
    fl = FLConfig(num_devices=4, batch_size=2, tau=1, eval_every=0)
    parts = sort_and_partition(ds.labels, fl.num_devices, 1,
                               np.random.default_rng(0))
    tr = FederatedTrainer(build_model(cfg), ds, ds, parts, fl)
    core = make_round_core(tr._loss, tr._sigma_one, fl.eta, fl.tau,
                           cell_axis="vmap")
    V, b = fl.num_devices, fl.batch_size
    params = jax.tree.map(lambda x: x[None], tr.params)
    batches = {"images": jnp.zeros((1, V, fl.tau, b, 8, 8, 3)),
               "labels": jnp.zeros((1, V, fl.tau, b), jnp.int32)}
    hlo = core.lower(params, batches,
                     jax.random.split(jax.random.key(0), 1)).as_text()
    assert "convolution" in hlo
    assert "reduce_window" not in hlo
    assert "select_and_scatter" not in hlo
