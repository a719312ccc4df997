"""Distribution substrate tests. These force an 8-device CPU topology in a
subprocess-free way: the module is SKIPPED unless the flag is already set
(pytest main process must keep 1 device), and a dedicated launcher test runs
them under the forced flag. Sharding-rule tests that only build PartitionSpecs
run everywhere."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import load_smoke
from repro.dist import partitioning as part
from repro.models import model as M

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")


def test_param_specs_shard_the_right_dims():
    cfg = load_smoke("qwen3_4b")
    abs_p = M.abstract_params(cfg)
    specs = part.param_specs(abs_p)
    # embed: vocab-sharded on model
    assert specs["embed"] == P("model", None)
    blk = specs["blocks"]["p0"]
    assert blk["attn"]["wq"] == P(None, None, "model")   # stacked + col
    assert blk["attn"]["wo"] == P(None, "model", None)   # stacked + row
    assert blk["ln1"] == P(None, None)                   # replicated norm


def test_param_specs_moe_expert_sharding():
    cfg = load_smoke("moonshot_v1_16b_a3b")
    specs = part.param_specs(M.abstract_params(cfg))
    moe = specs["blocks"]["p0"]["moe"]
    assert moe["w_in"] == P(None, "model", None, None)   # stacked + E-sharded
    assert moe["router"] == P(None, None, None)


def test_fsdp_adds_data_axis():
    cfg = load_smoke("yi_34b")
    abs_p = M.abstract_params(cfg)
    specs = part.param_specs(abs_p, fsdp=2)
    wq = specs["blocks"]["p0"]["attn"]["wq"]
    assert "data" in jax.tree.leaves(tuple(wq))  # some dim picked up fsdp


def test_spec_shapes_divide(example_mesh_shape=(4, 2)):
    """Every sharded dim must divide by its mesh axis (smoke extents)."""
    cfg = load_smoke("qwen3_4b")
    abs_p = M.abstract_params(cfg)
    specs = part.param_specs(abs_p)

    def check(leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax == "model":
                assert dim % 2 == 0
    jax.tree.map(check, abs_p, specs,
                 is_leaf=lambda x: isinstance(x, P))


DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs.base import load_smoke, ShapeConfig
from repro.data.pipeline import batch_for
from repro.dist import partitioning as part
from repro.dist import collective_matmul as cm
from repro.models import model as M
from repro.optim import adamw
from repro.train.train_step import make_train_step

# 1. sharded end-to-end train step == single-device train step
cfg = load_smoke("qwen3_4b")
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
shape = ShapeConfig("t", 32, 4, "train")
batch = batch_for(cfg, shape, 0)
params = M.init_params(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params)
step = make_train_step(cfg, adamw.AdamWConfig(warmup_steps=0))
p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)

p_sh = part.param_shardings(mesh, jax.eval_shape(lambda: params))
o_sh = adamw.OptState(NamedSharding(mesh, P()), p_sh, p_sh)
b_sh = {k: NamedSharding(mesh, part.batch_spec(mesh)) for k in batch}
with mesh:
    params_s = jax.tree.map(lambda a, s: jax.device_put(a, s), params, p_sh)
    opt_s = jax.tree.map(lambda a, s: jax.device_put(a, s), opt, o_sh)
    batch_s = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
    p_out, o_out, m_out = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))(
        params_s, opt_s, batch_s)
np.testing.assert_allclose(float(m_out["loss"]), float(m_ref["loss"]),
                           rtol=1e-4)
d = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)))),
    p_out, p_ref)))
assert d < 5e-2, d
print("SHARDED_TRAIN_OK", d)

# 2. collective matmul matches oracle under shard_map
rng = np.random.default_rng(0)
mesh1 = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
x = rng.normal(size=(16, 64)).astype(np.float32)
w = rng.normal(size=(64, 32)).astype(np.float32)
fn = jax.shard_map(lambda a, b: cm.allgather_matmul(a, b, "model"),
    mesh=mesh1, in_specs=(P(None, "model"), P()), out_specs=P(),
    check_vma=False)
np.testing.assert_allclose(np.asarray(fn(x, w.reshape(8, 8, 32))), x @ w,
                           rtol=1e-5, atol=1e-4)
fn2 = jax.shard_map(lambda a, b: cm.matmul_reducescatter(a, b, "model"),
    mesh=mesh1, in_specs=(P(None, "model"), P("model", None)),
    out_specs=P(None, "model"), check_vma=False)
np.testing.assert_allclose(np.asarray(fn2(x, w)), x @ w, rtol=1e-5,
                           atol=1e-4)
print("COLLECTIVE_MATMUL_OK")

# 3. hierarchical compressed psum ~= exact mean
from repro.dist.compression import hierarchical_psum
mesh2 = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
g = np.arange(8, dtype=np.float32).reshape(8, 1) * np.ones((8, 16),
                                                           np.float32)
def hp(gl):
    r, _ = hierarchical_psum(gl, pod_axis="pod", data_axis="data")
    return r
fn3 = jax.shard_map(hp, mesh=mesh2, in_specs=P(("pod", "data"), None),
                    out_specs=P(("pod", "data"), None), check_vma=False)
out = np.asarray(fn3(g))
assert abs(out[0, 0] - g.mean(0)[0]) < 1e-3
print("HIER_PSUM_OK")
"""


def test_distributed_semantics_under_8_devices():
    """Run the sharded-equivalence suite in a subprocess with 8 host
    devices (the main pytest process keeps the 1-device default)."""
    env = dict(os.environ,
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", DIST_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED_TRAIN_OK" in r.stdout
    assert "COLLECTIVE_MATMUL_OK" in r.stdout
    assert "HIER_PSUM_OK" in r.stdout
