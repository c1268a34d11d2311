"""Distributed semantics on a small host-device mesh (subprocess: the
device count must be set before jax initializes)."""
import os
import subprocess
import sys

import pytest

# 8 forced host devices rendezvous through one real core: minutes of
# wall-clock per subprocess on CI-sized boxes -> opt-in profile only
pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(script: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    # the children want forced host devices; on a machine with a chip the
    # parent holds it, and a child reaching for it would contend or hang
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_federated_step_weighted_aggregation():
    """fed_train_step with schedule weights == manual weighted FedAvg of
    per-silo gradients (paper Eq. 2, tau=1), on a 2x2x2 pod mesh."""
    run_sub('''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.launch import shardings as SH
from repro.launch.shapes import InputShape
from repro.fl.distributed import make_train_step, silo_weights
from repro.models import transformer as T
from repro.sharding import ShardingCtx

cfg = get_config("qwen2-7b").reduced()
mesh = make_debug_mesh(2, 2, multi_pod=True)
ctx = ShardingCtx(mesh=mesh, batch_axes=("pod", "data"), model_axis="model",
                  fsdp_axes=("data",), tp=False)
params = T.init(jax.random.key(0), cfg)
B, S = 8, 16
tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
targets = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
mask_silos = np.array([1.0, 0.0])           # only silo 0 scheduled
w = np.repeat(mask_silos / mask_silos.sum() * 2, B // 2)
batch = {"tokens": tokens, "targets": targets,
         "schedule_weights": jnp.asarray(w, jnp.float32)}

step = make_train_step(cfg, ctx, eta=0.1, federated=True)
with mesh:
    new_params, metrics = jax.jit(step)(params, batch)

# manual: gradient on silo-0 half of the batch only
def loss0(p):
    return T.loss_fn(p, cfg, {"tokens": tokens[:4],
                              "targets": targets[:4]})[0]
g0 = jax.grad(loss0)(params)
expect = jax.tree.map(lambda p, g: p - 0.1 * g, params, g0)
err = max(float(jnp.abs(a - b).max())
          for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(expect)))
assert err < 3e-2, err   # bf16-free f32 reduced cfg: tight-ish
print("fed step OK", err)
''')


def test_moe_shard_map_matches_local():
    """Expert-parallel shard_map MoE == single-device MoE."""
    run_sub('''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.models import moe as M
from repro.sharding import ShardingCtx

cfg = get_config("qwen3-moe-235b-a22b").reduced()
cfg = dataclasses.replace(cfg, capacity_factor=8.0)
params = M.moe_params_init(jax.random.key(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))

y_local, aux_local = M.moe_ffn(params, cfg, x, ctx=None)

mesh = make_debug_mesh(4, 2)    # data=4, model=2; E=4 experts /2 shards
ctx = ShardingCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                  fsdp_axes=(), tp=True)
with mesh:
    y_dist, aux_dist = jax.jit(
        lambda p, xx: M.moe_ffn(p, cfg, xx, ctx=ctx))(params, x)
err = float(jnp.abs(y_dist - y_local).max())
assert err < 1e-4, err
assert abs(float(aux_dist) - float(aux_local)) < 1e-5
print("moe shard_map OK", err)
''')


def test_debug_mesh_dryrun_lowers():
    """A miniature dry-run on an 8-device mesh: every family lowers and
    compiles with the production sharding rules."""
    run_sub('''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.launch import shardings as SH
from repro.launch.shapes import InputShape
from repro.fl.distributed import make_train_step

for arch in ["qwen2-7b", "gemma3-27b", "rwkv6-3b", "recurrentgemma-2b",
             "qwen3-moe-235b-a22b"]:
    cfg = get_config(arch).reduced()
    # reduced configs have tiny dims; use a debug shape
    shape = InputShape("debug", 64, 8, "train")
    mesh = make_debug_mesh(4, 2)
    ctx = SH.make_ctx(cfg, mesh, shape)
    ps = SH.param_specs(cfg)
    psh = SH.param_shardings(ps, cfg, ctx)
    bs = SH.input_specs(cfg, shape)
    bsh = SH.batch_shardings(bs, ctx)
    with mesh:
        c = jax.jit(make_train_step(cfg, ctx), in_shardings=(psh, bsh),
                    out_shardings=(psh, None)).lower(ps, bs).compile()
    assert c.cost_analysis().get("flops", 0) > 0
    print(arch, "lowered OK")
''')
