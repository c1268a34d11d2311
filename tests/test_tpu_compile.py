"""Compiles the main path's device programs for a described TPU v5e.

No chip is attached: the TPU compiler shipped with jaxlib compiles for a
topology that is only described, so these tests catch what interpret
mode cannot (Mosaic block-shape rules, 64-bit index maps, programs that
do not fit the chip's memory) without running anything.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# P1 instances of the paper deployment (V=64 at p_a=0.3 leaves ~19-24
# available devices a cell) and the FSCD lane batches built from them
P1_SHAPES = [(8, 24, 10), (8, 64, 10), (64, 64, 10)]
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Route ``repro.kernels.ops`` to the compiled (not interpreted)
    kernels although the process runs on the CPU backend."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["swap", "add"])
@pytest.mark.parametrize("B,V,C", P1_SHAPES)
def test_wemd_kernel_compiles(one_chip, kernel, B, V, C):
    from repro.kernels.wemd_swap import wemd_add_pallas, wemd_swap_pallas
    fn = wemd_swap_pallas if kernel == "swap" else wemd_add_pallas
    f64 = jnp.float64
    with jax.enable_x64(True):      # the scope solve_many calls them from
        args = (_spec(one_chip, (B, C), f64),
                _spec(one_chip, (B, V, C), f64),
                _spec(one_chip, (B, C), f64),
                _spec(one_chip, (B, C), f64),
                _spec(one_chip, (B,), f64))
        compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gs_program_compiles(one_chip, compiled_kernels):
    from repro.core.scheduling_jax import _gs_batch_impl
    B, V, C = 8, 24, 10
    f64 = jnp.float64
    with jax.enable_x64(True):
        args = (_spec(one_chip, (B, V, C), f64),
                _spec(one_chip, (B, C), f64),
                _spec(one_chip, (B, C), f64),
                _spec(one_chip, (B,), f64),
                _spec(one_chip, (B,), f64),
                _spec(one_chip, (B, V), f64),
                _spec(one_chip, (B,), f64))
        compiled = jax.jit(_gs_batch_impl, static_argnums=(7,)).lower(
            *args, True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fscd_phase_program_compiles(one_chip, compiled_kernels):
    from repro.core.scheduling_jax import _fscd_descent_impl
    B, L, V, C, R = 2, 32, 24, 10, 16
    f64, i32 = jnp.float64, jnp.int32
    with jax.enable_x64(True):
        args = (_spec(one_chip, (B, V, C), f64),      # p_dev
                _spec(one_chip, (B, C), f64),         # gd
                _spec(one_chip, (B, C), f64),         # cw
                _spec(one_chip, (B, V), f64),         # bw
                _spec(one_chip, (B, V), bool),        # feas
                _spec(one_chip, (B,), f64),           # total_bw
                _spec(one_chip, (L,), i32),           # lane_b
                _spec(one_chip, (L,), jnp.int64),     # s_lane
                _spec(one_chip, (L, R), i32),         # members
                _spec(one_chip, (L, V), bool),        # mask
                _spec(one_chip, (L, C), f64),         # p_sum
                _spec(one_chip, (L,), f64),           # used
                _spec(one_chip, (L,), f64))           # w_cur
        compiled = jax.jit(_fscd_descent_impl,
                           static_argnums=(13, 14)).lower(
            *args, 200, True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_round_core_compiles_at_paper_width(one_chip):
    """C=1 vmapped round core of the paper CNN (32x32x3, 10 classes,
    558,226 parameters) with 24 available devices, b=32, tau=1."""
    from repro.configs.paper_cnn import PAPER_CNN_CIFAR10
    from repro.data import sort_and_partition, synthetic_image_dataset
    from repro.fl import FederatedTrainer, FLConfig
    from repro.fl.client import make_round_core
    from repro.models import build_model

    cfg = FLConfig()
    ds = synthetic_image_dataset(num_classes=10, num_per_class=8, seed=0)
    parts = sort_and_partition(ds.labels, cfg.num_devices, 1,
                               np.random.default_rng(0))
    tr = FederatedTrainer(build_model(PAPER_CNN_CIFAR10), ds, ds, parts,
                          cfg)
    assert sum(x.size for x in jax.tree.leaves(tr.params)) == 558_226
    core = make_round_core(tr._loss, tr._sigma_one, cfg.eta, cfg.tau,
                           cell_axis="vmap")
    Cc, V, b = 1, 24, cfg.batch_size
    params = (jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                           tr.params),)
    batches = {"images": _spec(one_chip, (Cc, V, cfg.tau, b, 32, 32, 3),
                               jnp.float32),
               "labels": _spec(one_chip, (Cc, V, cfg.tau, b), jnp.int32)}
    keys = _spec(one_chip, (Cc,), jax.random.key(0).dtype)
    compiled = core.lower(params, batches, keys).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES


def test_dropless_moe_layer_compiles_at_moonlight_width(one_chip):
    """One of Moonlight-16B-A3B's MoE layers at its published widths
    (hidden 2,048, 64 routed experts of width 1,408, top-6, 8 held, 2
    shared), one sequence of 2,048 tokens, forward and gradient as the
    round core runs them: the held experts' grouped matmuls are the
    TPU's ``ragged-dot`` kernels, unbatched."""
    from repro.configs.base import ModelConfig
    from repro.models import moe as M

    cfg = ModelConfig(name="moonlight-moe", family="moe", source="test",
                      num_layers=2, d_model=2048, num_heads=16,
                      d_ff=1408, vocab_size=8, num_experts=64,
                      experts_per_token=6, num_shared_experts=2,
                      experts_held=8, capacity_factor=0.0,
                      router_aux_loss=0.0, moe_scoring="sigmoid",
                      routed_scaling_factor=2.446, dtype="float32",
                      param_dtype="float32")
    params = jax.eval_shape(
        lambda k: M.moe_params_init(k, cfg, jnp.float32), jax.random.key(0))
    params = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                          params)
    x = _spec(one_chip, (1, 2048, 2048), jnp.float32)

    def loss(p, x):
        y, _, counts = M.moe_layer(p, cfg, x)
        return jnp.sum(y * y), counts

    compiled = jax.jit(jax.grad(loss, has_aux=True)).lower(
        params, x).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < V5E_HBM_BYTES
