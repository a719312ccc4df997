"""Tests for the §Perf optimizations: flash (online-softmax chunked)
attention, grouped-GQA einsums, head-aligned sharding rules, SP constraint
plumbing, and the head-sharded decode cache specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.base import load_smoke
from repro.dist import partitioning as part
from repro.dist.act_sharding import act_sharding, constrain_residual, sp_spec
from repro.models import layers as L
from repro.models import model as M


def _qkv(rng, B, S, H, KV, dh):
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, KV, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, dh)).astype(np.float32))
    return q, k, v


def _repeat_reference(q, k, v, mask, n_rep):
    kk, vv = jnp.repeat(k, n_rep, 2), jnp.repeat(v, n_rep, 2)
    B, S, H, dh = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / dh ** 0.5
    s = jnp.where(mask, s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      vv).reshape(B, S, H * dh)


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (12, 2), (6, 1)])
def test_grouped_sdpa_matches_repeat(rng, H, KV):
    q, k, v = _qkv(rng, 2, 23, H, KV, 16)
    mask = L.causal_mask(23, 23)
    got = L._sdpa(q, k, v, mask, H // KV)
    ref = _repeat_reference(q, k, v, mask, H // KV)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("S,chunk", [(16, 4), (37, 8), (64, 64), (100, 32)])
@pytest.mark.parametrize("window", [None, 11])
@pytest.mark.parametrize("unroll", [False, True])
def test_flash_matches_dense(rng, S, chunk, window, unroll):
    q, k, v = _qkv(rng, 2, S, 8, 2, 16)
    mask = L.causal_mask(S, S, window)
    ref = L._sdpa(q, k, v, mask, 4)
    got = L._flash_sdpa(q, k, v, 4, window=window, kv_chunk=chunk,
                        unroll=unroll)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_flash_model_forward_and_grad(rng):
    cfg = load_smoke("qwen3_4b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 1, cfg.vocab,
                              dtype=jnp.int32)
    dense, _ = M.forward(params, toks, cfg)
    flash, _ = M.forward(params, toks, cfg, flash_chunk=16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-3, atol=2e-3)
    g = jax.grad(lambda p: M.forward(p, toks, cfg, flash_chunk=16)[0].sum())(
        params)
    assert float(jnp.abs(g["embed"]).sum()) > 0


# --------------------------------------------------------------------------
# head-aligned sharding rules (factored mesh)
# --------------------------------------------------------------------------
class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_make_rules_baseline():
    r = part.make_rules(_FakeMesh({"data": 16, "model": 16}), 56, 8)
    assert r.tp == ("model",) and r.q_axes == ("model",)


def test_make_rules_factored_gqa():
    mesh = _FakeMesh({"data": 16, "model1": 8, "model2": 2})
    r = part.make_rules(mesh, 56, 8)       # yi-34b: 56 q heads, 8 kv heads
    assert r.tp == ("model1", "model2")    # FFN/vocab keep full 16-way TP
    assert r.q_axes == ("model1",)         # 56 % 16 != 0, 56 % 8 == 0
    assert r.kv_axes == ("model1",)        # 8 % 8 == 0
    r2 = part.make_rules(mesh, 32, 8)      # qwen3: q divides 16
    assert r2.q_axes == ("model1", "model2")
    assert r2.kv_axes == ("model1",)
    r3 = part.make_rules(mesh, 8, 1)       # paligemma MQA: kv unshardable
    assert r3.q_axes == ("model1",) and r3.kv_axes == ()


def test_leaf_spec_head_alignment():
    mesh = _FakeMesh({"data": 16, "model1": 8, "model2": 2})
    r = part.make_rules(mesh, 56, 8)
    assert part.leaf_spec(("blocks", "attn", "wq"), (1, 64, 128),
                          rules=r) == P(None, None, "model1")
    assert part.leaf_spec(("blocks", "attn", "wk"), (1, 64, 32),
                          rules=r) == P(None, None, "model1")
    assert part.leaf_spec(("blocks", "ffn", "w_in"), (1, 64, 256),
                          rules=r) == P(None, None, ("model1", "model2"))
    assert part.leaf_spec(("embed",), (512, 64),
                          rules=r) == P(("model1", "model2"), None)


def test_cache_spec_head_sharded():
    mesh = _FakeMesh({"data": 16, "model1": 8, "model2": 2})
    r = part.make_rules(mesh, 32, 8)
    spec = part.cache_spec(mesh, 128, "k", 5, rules=r)
    assert spec == P(None, ("data",), None, ("model1",), None)
    # baseline: sequence-sharded (the measured all-gather-per-token mode)
    base = part.cache_spec(_FakeMesh({"data": 16, "model": 16}), 128, "k", 5)
    assert base == P(None, ("data",), "model", None, None)


# --------------------------------------------------------------------------
# SP constraint plumbing
# --------------------------------------------------------------------------
def test_constrain_residual_noop_without_context():
    x = jnp.ones((2, 8, 4))
    assert constrain_residual(x) is x


def test_constrain_residual_applies_under_mesh():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    spec = sp_spec(mesh)
    assert spec == P(("data",), ("model",), None)
    with act_sharding(mesh, spec):
        out = jax.jit(lambda x: constrain_residual(x))(jnp.ones((2, 8, 4)))
        np.testing.assert_array_equal(np.asarray(out), np.ones((2, 8, 4)))
        # S=1 (decode) and non-3D tensors pass through unharmed
        assert constrain_residual(jnp.ones((2,))).shape == (2,)


def test_sp_forward_numerics_unchanged():
    """The SP constraint must not change model outputs (1-device mesh)."""
    cfg = load_smoke("qwen3_4b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1, cfg.vocab,
                              dtype=jnp.int32)
    ref, _ = M.forward(params, toks, cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with mesh, act_sharding(mesh, sp_spec(mesh)):
        got, _ = jax.jit(lambda p, t: M.forward(p, t, cfg))(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
