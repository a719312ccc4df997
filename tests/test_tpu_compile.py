"""TPU v5e compile rehearsals of the main path's kernels at real widths.

Each test compiles for a described, unattached v5e (``jax.experimental.
topologies``). The TPU compiler then refuses what the chip would refuse:
blocks not aligned to the (8, 128) tiling, scalar-prefetch tables larger
than SMEM, layouts the kernel compiler cannot lower. Nothing runs, so these
tests say nothing about results or speed. The backend seen by the program
stays the CPU, so each test pins the compiled Pallas path itself
(``executor="pallas"``, ``interpret=False``, ``im2col="patches"``).

The topology is described inside the ``topo`` fixture, never at import:
every pytest-xdist worker then collects the same tests, and only the worker
given this file loads the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.configs.base import load_config
from repro.kernels.bitmask_spmm import bitmask_spmm
from repro.kernels.fused_ffn import fused_ffn_spmm
from repro.kernels.sparse_conv import sparse_conv_spmm
from repro.kernels.worklist_core import build_worklist, worklist_spmm

# VGG16 conv1_2 at 224 px, batch 1: M = 224 * 224 patch rows, K = 64 * 9
# padded to 5 chunks of 128, one 128-wide output block
CONV1_2 = dict(M=224 * 224, K=640, nb=1, nz=5, mb_per_img=392)
MOSAIC = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs on disk
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        jax.config.update("jax_enable_compilation_cache", was)
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def vgg16():
    from repro.vision import build_vision_model
    return build_vision_model("VGGNet", seed=0)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count(MOSAIC)


def _ffn_tables(nb: int, kb: int, nz: int, seed: int):
    rng = np.random.default_rng(seed)
    return np.sort(np.stack([rng.choice(kb, nz, replace=False)
                             for _ in range(nb)]), 1).astype(np.int32)


@pytest.mark.parametrize("emit_occupancy", [False, True],
                         ids=["plain", "emit-occupancy"])
def test_walker_compiles_at_vgg16_conv1_2(one_chip, emit_occupancy):
    g = CONV1_2
    idx = np.tile(np.arange(g["nz"], dtype=np.int32), (g["nb"], 1))
    wl = build_worklist(idx, g["M"] // 128, mb_per_img=g["mb_per_img"])
    fn = jax.jit(lambda p, v: worklist_spmm(
        p, v, wl, bm_rows=128, sub_m=8, mb_per_img=g["mb_per_img"],
        ncolors=2, act="relu", emit_occupancy=emit_occupancy,
        executor="pallas", interpret=False))
    compiled = fn.lower(_sds((g["M"], g["K"]), one_chip),
                        _sds((g["nb"], g["nz"], 128, 128), one_chip)
                        ).compile()
    assert _mosaic_calls(compiled) == 1


def test_gated_walker_compiles_at_qwen3_4b_ffn_width(one_chip):
    cfg = load_config("qwen3_4b")
    M, K, F = 128, cfg.d_model, cfg.d_ff        # a 128-row decode batch
    nb, kb, nz = F // 128, K // 128, 7          # ~0.35 chunk density
    wl = build_worklist(_ffn_tables(nb, kb, nz, 0), M // 8,
                        gate_indices=_ffn_tables(nb, kb, nz, 1))
    fn = jax.jit(lambda x, v, v2: worklist_spmm(
        x, v, wl, vals2=v2, bm_rows=8, sub_m=8, act=cfg.act,
        executor="pallas", interpret=False))
    w = _sds((nb, nz, 128, 128), one_chip)
    compiled = fn.lower(_sds((M, K), one_chip), w, w).compile()
    assert _mosaic_calls(compiled) == 1


def test_predicated_conv_compiles_with_counters_and_emission(one_chip):
    g = CONV1_2
    fn = jax.jit(lambda p, i, v: sparse_conv_spmm(
        p, i, v, bm_rows=128, sub_m=8, mb_per_img=g["mb_per_img"],
        emit_occupancy=True, count_macs=True, interpret=False))
    compiled = fn.lower(_sds((g["M"], g["K"]), one_chip),
                        _sds((g["nb"], g["nz"]), one_chip, jnp.int32),
                        _sds((g["nb"], g["nz"], 128, 128), one_chip)
                        ).compile()
    assert _mosaic_calls(compiled) == 1


@pytest.mark.parametrize("kernel", ["bitmask_spmm", "fused_ffn_spmm"])
def test_predicated_ffn_kernels_compile_at_qwen3_4b_width(one_chip, kernel):
    cfg = load_config("qwen3_4b")
    M, K, F, nz = 128, cfg.d_model, cfg.d_ff, 7
    nb = F // 128
    x = _sds((M, K), one_chip)
    idx = _sds((nb, nz), one_chip, jnp.int32)
    w = _sds((nb, nz, 128, 128), one_chip)
    if kernel == "bitmask_spmm":
        fn = jax.jit(lambda x, i, v: bitmask_spmm(
            x, i, v, sub_m=8, two_sided=True, count_macs=True,
            interpret=False))
        compiled = fn.lower(x, idx, w).compile()
    else:
        fn = jax.jit(lambda x, i, v, g, gv: fused_ffn_spmm(
            x, i, v, g, gv, act=cfg.act, sub_m=8, interpret=False))
        compiled = fn.lower(x, idx, w, idx, w).compile()
    assert _mosaic_calls(compiled) == 1


@pytest.mark.parametrize("devices", [1, 4], ids=["one-chip", "data-mesh-4"])
def test_vgg16_forward_compiles_at_224(topo, vgg16, devices):
    """The whole-net jit at batch 8: one Mosaic walker per conv layer,
    alone or data-sharded two images per device."""
    from repro.vision import compile_forward
    if devices == 1:
        mesh = None
        sharding = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:devices]), ("data",))
        sharding = NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    fwd = compile_forward(vgg16, executor="pallas", im2col="patches",
                          interpret=False, mesh=mesh)
    compiled = fwd.lower(_sds((8, 224, 224, 3), sharding)).compile()
    assert _mosaic_calls(compiled) == vgg16.num_layers == 13


def test_vgg16_forward_keeps_kernel_names_under_layer_scopes(one_chip,
                                                             vgg16):
    """The per-layer name scopes are metadata only: the compiled v5e
    forward is still the module ``jit_vision_forward``, its Mosaic calls
    are still the instructions ``_worklist_spmm_pallas.N``, and each
    carries its layer's ``walker`` scope."""
    import re
    from repro.vision import compile_forward
    fwd = compile_forward(vgg16, executor="pallas", im2col="patches",
                          interpret=False)
    text = fwd.lower(_sds((8, 224, 224, 3), one_chip)).compile().as_text()
    assert text.startswith("HloModule jit_vision_forward")
    calls = [l for l in text.splitlines() if MOSAIC in l]
    assert len(calls) == vgg16.num_layers
    layers = []
    for line in calls:
        assert re.match(r"\s*(ROOT )?%_worklist_spmm_pallas\.\d+ = ", line)
        m = re.search(r'op_name="jit\(vision_forward\)/(layer\d\d)/walker/',
                      line)
        assert m, line[:200]
        layers.append(m.group(1))
    assert sorted(layers) == [f"layer{i:02d}"
                              for i in range(vgg16.num_layers)]
