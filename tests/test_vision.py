"""Vision subsystem: implicit-GEMM sparse conv kernel vs
``jax.lax.conv_general_dilated``, telescoped work-list compaction vs the
dense grid, output-buffer coloring, whole-network forward (eager and
compiled), engine admission, and the conv2d_im2col / tile-density
satellites."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_stubs import given, settings, st

from repro.core import simulator as S
from repro.core.sparse import (activation_tile_density, conv2d_im2col,
                               prune_by_magnitude)
from repro.kernels import ops
from repro.kernels.bitmask_spmm import build_worklist
from repro.kernels.sparse_conv import (extract_patches, sparse_conv2d_nhwc,
                                       sparse_conv_spmm)
from repro.sparsity.conv import build_sparse_chain, pack_conv_filters
from repro.vision import (ImageRequest, VisionEngine, build_vision_model,
                          compile_forward, dense_forward, forward,
                          measured_densities)


def _conv_operands(rng, B=2, H=9, W=11, cin=8, cout=20, k=3, density=0.4,
                   map_density=0.6):
    w = rng.normal(size=(k, k, cin, cout)).astype(np.float32)
    if density < 1.0:
        w *= prune_by_magnitude(w, density, axis_out=-1)
    x = np.abs(rng.normal(size=(B, H, W, cin))).astype(np.float32)
    x[rng.random(x.shape) >= map_density] = 0.0
    return x, w


def _lax_ref(x, w, stride, padding, relu=True):
    st = (stride, stride) if isinstance(stride, int) else stride
    out = jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w), st, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jnp.maximum(out, 0.0) if relu else out


# ---------------------------------------------------------------------------
# kernel == lax.conv_general_dilated across the satellite's sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize("density", [1.0, 0.4])
def test_sparse_conv_matches_lax(rng, stride, padding, density):
    x, w = _conv_operands(rng, H=9, W=11, density=density)  # odd spatial
    ws = pack_conv_filters(w)
    out, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                stride=stride, padding=padding,
                                fuse_relu=True)
    exp = _lax_ref(x, w, stride, padding)
    assert out.shape == exp.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


def test_sparse_conv_per_axis_stride_and_explicit_padding(rng):
    x, w = _conv_operands(rng, H=13, W=9)
    ws = pack_conv_filters(w)
    stride, padding = (1, 2), ((2, 0), (1, 1))
    out, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                stride=stride, padding=padding,
                                fuse_relu=True)
    exp = _lax_ref(x, w, stride, padding)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


def test_relu_epilogue_off(rng):
    """fuse_relu=False must reproduce the raw (signed) conv output."""
    x, w = _conv_operands(rng)
    ws = pack_conv_filters(w)
    out, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                fuse_relu=False)
    exp = _lax_ref(x, w, 1, "SAME", relu=False)
    assert float(jnp.min(out)) < 0  # signed outputs actually exercised
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


def test_emitted_occupancy_matches_host(rng):
    """The in-kernel tile bitmask must equal a host recompute on the
    kernel's own output."""
    sub_m = 8
    x, w = _conv_operands(rng, B=2, H=12, W=12, map_density=0.3)
    ws = pack_conv_filters(w)
    out, aux = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                  sub_m=sub_m, fuse_relu=True,
                                  emit_occupancy=True)
    occ = np.asarray(aux["occupancy"])       # [B, ceil(M/sub_m), n_blocks]
    b, oh, ow, cout = out.shape
    m_img = oh * ow
    flat = np.zeros((b, -(-m_img // sub_m) * sub_m, ws.n_blocks * ws.bn),
                    np.float32)
    flat[:, :m_img, :cout] = np.asarray(out).reshape(b, m_img, cout)
    host = (flat.reshape(b, -1, sub_m, ws.n_blocks, ws.bn) != 0
            ).any(axis=(2, 4)).astype(np.int32)
    np.testing.assert_array_equal(occ, host)


def test_two_sided_equals_one_sided_numerics(rng):
    """Activation-side skips only elide exact zeros."""
    x, w = _conv_operands(rng, B=2, H=16, W=16, map_density=0.2)
    x[0, :8] = 0.0                            # whole zero region
    ws = pack_conv_filters(w)
    outs = []
    for two_sided in (False, True):
        out, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                    two_sided=two_sided)
        outs.append(np.asarray(out))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_skip_counters_fire_on_zero_rows(rng):
    """A zero image in the batch must cost no MACs in two-sided mode."""
    x, w = _conv_operands(rng, B=2, H=12, W=12, map_density=0.9)
    x[1] = 0.0
    ws = pack_conv_filters(w)
    _, aux2 = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                 two_sided=True, count_macs=True)
    two = np.asarray(aux2["mac_counts"])      # [nb, mb] sub-block MACs
    mb = two.shape[1]
    assert np.all(two[:, mb // 2:] == 0)      # second image fully skipped
    assert two[:, : mb // 2].sum() > 0        # first image did real work


# ---------------------------------------------------------------------------
# output-buffer coloring (paper §3.3)
# ---------------------------------------------------------------------------
def test_coloring_interleaved_equals_sequential(rng):
    """A batch of consecutive images through the colored double-buffered
    kernel must be BITWISE identical to each image run alone."""
    x, w = _conv_operands(rng, B=4, H=10, W=10, map_density=0.5)
    ws = pack_conv_filters(w)
    batched, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1])
    for i in range(x.shape[0]):
        solo, _ = sparse_conv2d_nhwc(jnp.asarray(x[i:i + 1]), ws, 3, 3,
                                     w.shape[-1])
        np.testing.assert_array_equal(np.asarray(batched[i]),
                                      np.asarray(solo[0]))


def test_coloring_multi_block_images(rng):
    """Images spanning several row blocks keep per-image parity (all blocks
    of one image share a color; the flush order cannot mix images)."""
    x, w = _conv_operands(rng, B=3, H=16, W=16, cin=4, cout=8)  # 256 rows/img
    ws = pack_conv_filters(w)
    batched, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1])
    solo = [np.asarray(sparse_conv2d_nhwc(jnp.asarray(x[i:i + 1]), ws, 3, 3,
                                          w.shape[-1])[0][0])
            for i in range(3)]
    np.testing.assert_array_equal(np.asarray(batched), np.stack(solo))


# ---------------------------------------------------------------------------
# telescoped work-list compaction (the grid is the schedule)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("executor", ["pallas", "xla"])
def test_compacted_schedule_bitwise_equals_dense_grid(rng, executor):
    """Moving the skip from in-lane predication into the schedule must not
    change a single bit, for both work-list walkers."""
    x, w = _conv_operands(rng, B=2, H=12, W=12, map_density=0.4)
    ws = pack_conv_filters(w)
    dense, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                  schedule="dense")
    compact, aux = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                      schedule="compact", executor=executor)
    np.testing.assert_array_equal(np.asarray(compact), np.asarray(dense))
    sched = aux["schedule"]
    assert sched["scheduled_steps"] <= sched["dense_grid_steps"]


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([0.05, 0.3, 0.7, 1.0]),
       st.sampled_from([0.1, 0.5, 0.9]), st.integers(1, 3),
       st.sampled_from([(8, 9, 11), (4, 16, 16), (12, 10, 7)]))
@settings(max_examples=12, deadline=None)
def test_compaction_property_random_densities(seed, density, map_density,
                                              batch, geom):
    """Property (satellite): compacted-grid output == dense-grid output for
    random densities/shapes, on both executors, including the dynamic
    activation-side intersection."""
    rng = np.random.default_rng(seed)
    cin, H, W = geom
    x, w = _conv_operands(rng, B=batch, H=H, W=W, cin=cin, cout=12,
                          density=density, map_density=map_density)
    ws = pack_conv_filters(w)
    dense, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3, w.shape[-1],
                                  schedule="dense")
    for kwargs in ({"executor": "pallas"}, {"executor": "xla"},
                   {"executor": "xla", "compact_activations": True}):
        compact, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3,
                                        w.shape[-1], schedule="compact",
                                        **kwargs)
        np.testing.assert_array_equal(np.asarray(compact), np.asarray(dense))


def test_scheduled_steps_match_skip_model(rng):
    """Exactness (satellite): the compacted schedule's MAC step count must
    equal the pure-jnp skip model's predicted live-chunk count — no dead
    steps scheduled, none missing."""
    x, w = _conv_operands(rng, B=2, H=16, W=16, cin=8, cout=20,
                          density=0.3, map_density=0.15)
    # zero image rows 0..8: every 3x3 patch of the first 8 output rows
    # (the first 128-row patch block) is all-zero -> a dead block
    x[0, :9] = 0.0
    ws = pack_conv_filters(w)
    patches, _ = extract_patches(jnp.asarray(x), 3, 3, 1, "SAME")
    m_img = patches.shape[1]
    pad_rows = (-m_img) % 128
    pad_k = ws.shape[0] - patches.shape[-1]
    flat = jnp.pad(patches, ((0, 0), (0, pad_rows), (0, pad_k))
                   ).reshape(-1, ws.shape[0])
    model = ops.conv_schedule_stats(flat, ws.indices, bk=ws.bk)
    occ_blk = np.asarray((np.asarray(flat).reshape(
        flat.shape[0] // 128, 128, -1, ws.bk) != 0).any(axis=(1, 3)))
    wl = build_worklist(ws.host_indices(), flat.shape[0] // 128,
                        occ_blk=occ_blk)
    assert wl.mac_steps == int(model["live_chunk_steps"])
    assert wl.num_steps == int(model["scheduled_steps"])
    assert wl.flush_only_steps == int(model["dead_pairs"])
    assert wl.dense_grid_steps == int(model["dense_grid_steps"])
    # compaction actually fired on this input
    assert wl.mac_steps < wl.dense_grid_steps
    # and every scheduled MAC step is genuinely live: stored chunk + block
    live = wl.k >= 0
    assert occ_blk[wl.m[live], wl.k[live]].all()
    host_idx = ws.host_indices()
    assert all(wl.k[t] in host_idx[wl.n[t]] for t in np.nonzero(live)[0])


def test_worklist_ragged_and_flat_forms_agree(rng):
    """The ragged-padded [nb, mb, max_live] tensor and the flat schedule
    are two serializations of the same intersection."""
    x, w = _conv_operands(rng, B=1, H=16, W=16, cin=8, cout=20,
                          density=0.3, map_density=0.2)
    ws = pack_conv_filters(w)
    patches, _ = extract_patches(jnp.asarray(x), 3, 3, 1, "SAME")
    m_img = patches.shape[1]
    flat = jnp.pad(patches, ((0, 0), (0, (-m_img) % 128),
                             (0, ws.shape[0] - patches.shape[-1]))
                   ).reshape(-1, ws.shape[0])
    occ_blk = np.asarray((np.asarray(flat).reshape(
        flat.shape[0] // 128, 128, -1, ws.bk) != 0).any(axis=(1, 3)))
    wl = build_worklist(ws.host_indices(), flat.shape[0] // 128,
                        occ_blk=occ_blk)
    assert (wl.steps_per_pair == (wl.ragged_idx >= 0).sum(-1)).all()
    assert wl.mac_steps == int(wl.steps_per_pair.sum())
    for t in range(wl.num_steps):
        n, m, j = int(wl.n[t]), int(wl.m[t]), int(wl.j[t])
        if j >= 0:
            assert j in wl.ragged_idx[n, m]


def test_coloring_worklist_kernel_batched_equals_sequential(rng):
    """§3.3 coloring regression (satellite): after collapsing to a single
    color-indexed accumulator, batched output must stay bitwise-equal to
    per-image sequential — on the dense grid and on both work-list
    walkers."""
    x, w = _conv_operands(rng, B=4, H=10, W=10, map_density=0.5)
    ws = pack_conv_filters(w)
    for kwargs in ({"schedule": "dense"},
                   {"schedule": "compact", "executor": "pallas"},
                   {"schedule": "compact", "executor": "xla"}):
        batched, _ = sparse_conv2d_nhwc(jnp.asarray(x), ws, 3, 3,
                                        w.shape[-1], **kwargs)
        for i in range(x.shape[0]):
            solo, _ = sparse_conv2d_nhwc(jnp.asarray(x[i:i + 1]), ws, 3, 3,
                                         w.shape[-1], **kwargs)
            np.testing.assert_array_equal(np.asarray(batched[i]),
                                          np.asarray(solo[0]))


def test_im2col_strategies_bitwise_equal(rng):
    """Both in-jit patch extraction strategies produce the identical patch
    matrix (channel-major feature order)."""
    x = rng.normal(size=(2, 11, 9, 5)).astype(np.float32)
    for stride, padding in ((1, "SAME"), ((2, 1), "VALID"),
                            ((1, 2), ((1, 0), (2, 1)))):
        a, (oh, ow) = extract_patches(jnp.asarray(x), 3, 3, stride, padding,
                                      strategy="patches")
        b, (oh2, ow2) = extract_patches(jnp.asarray(x), 3, 3, stride,
                                        padding, strategy="slices")
        assert (oh, ow) == (oh2, ow2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("stride, padding",
                         [(1, "SAME"), ((2, 1), "VALID"),
                          ((1, 2), ((1, 0), (2, 1)))])
def test_taps_are_patches_in_tap_major_order(rng, stride, padding):
    """``taps`` holds the values of ``patches``, bit for bit, with the
    columns moved from channel-major (c * 9 + t) to tap-major (t * cin +
    c) order: the K-row permutation between the two packings."""
    cin = 5
    x = rng.normal(size=(2, 11, 9, cin)).astype(np.float32)
    a, hw = extract_patches(jnp.asarray(x), 3, 3, stride, padding,
                            strategy="patches")
    b, hw2 = extract_patches(jnp.asarray(x), 3, 3, stride, padding,
                             strategy="taps")
    assert hw == hw2
    to_channel = (np.arange(cin)[None, :] * 9
                  + np.arange(9)[:, None]).reshape(-1)
    np.testing.assert_array_equal(np.asarray(b),
                                  np.asarray(a)[..., to_channel])


@pytest.mark.parametrize("hw, k, cin, m_pad", [(12, 3, 64, None),
                                                (27, 5, 96, 768)],
                         ids=["cin64-3x3", "cin96-5x5-rows-padded"])
def test_taps_fold_the_k_pad(rng, hw, k, cin, m_pad):
    """Where ``cin`` is not whole lane tiles, ``taps`` with the K pad
    folded in is ``patches`` with its columns moved to tap-major order,
    its rows padded to ``m_pad`` and zero columns appended up to the next
    chunk, bit for bit."""
    x = rng.normal(size=(2, hw, hw, cin)).astype(np.float32)
    taps = k * k
    k_pad = (-taps * cin) % 128
    a, hw_a = extract_patches(jnp.asarray(x), k, k, 1, "SAME",
                              strategy="patches")
    b, hw_b = extract_patches(jnp.asarray(x), k, k, 1, "SAME",
                              strategy="taps", m_pad=m_pad, k_pad=k_pad)
    assert hw_a == hw_b
    to_channel = (np.arange(cin)[None, :] * taps
                  + np.arange(taps)[:, None]).reshape(-1)
    want = np.asarray(a)[..., to_channel]
    rows = (m_pad or want.shape[1]) - want.shape[1]
    want = np.pad(want, ((0, 0), (0, rows), (0, k_pad)))
    assert b.shape == want.shape and b.shape[-1] % 128 == 0
    np.testing.assert_array_equal(np.asarray(b).view(np.uint32),
                                  want.view(np.uint32))


# VGG16's conv2_1 -> conv3_1 widths: 64 input channels (half a chunk, so
# tap-major with chunks that straddle taps), then 128 and 128 (whole
# chunks, so each chunk inside one tap)
VGG_WIDTHS = [(3, 3, 64, 128), (3, 3, 128, 128), (3, 3, 128, 256)]


@pytest.fixture(scope="module")
def vgg_width_chain():
    rng = np.random.default_rng(7)
    ws = [(rng.normal(size=s) * np.sqrt(2.0 / (9 * s[2])))
          .astype(np.float32) for s in VGG_WIDTHS]
    return build_sparse_chain(ws, density=0.334, strict=True)


def test_unstructured_chain_is_tap_major_where_cin_fills_chunks(
        vgg_width_chain):
    """Tap-major wherever ``cin`` fills at least half a chunk (64: chunks
    straddle taps; 128: whole chunks); the packed matrix is the
    channel-major one with its K rows permuted and the same zero K pad,
    and the verifier (``strict=True``, and ``deep`` here) accepts the
    chain."""
    from repro.analysis import has_errors, verify_chain
    from repro.sparsity.conv import matrixize_filters
    assert [c.layout for c in vgg_width_chain] == ["tap", "tap", "tap"]
    assert {c.pattern for c in vgg_width_chain} == {"unstructured"}
    assert not has_errors(verify_chain(vgg_width_chain, deep=True))
    for c in vgg_width_chain:
        tap = matrixize_filters(c.w_dense, layout="tap")
        chan = matrixize_filters(c.w_dense, layout="channel")
        k = 9 * c.cin
        to_channel = (np.arange(c.cin)[None, :] * 9
                      + np.arange(9)[:, None]).reshape(-1)
        assert tap.shape == chan.shape and tap.shape[0] % 128 == 0
        np.testing.assert_array_equal(tap[:k], chan[to_channel])
        assert not tap[k:].any() and not chan[k:].any()


@pytest.mark.parametrize("size", [16, 32])
def test_tap_packing_keeps_the_schedule(vgg_width_chain, size):
    """Per layer, the tap-major work list schedules exactly the steps the
    channel-major packing of the same filters would: at density 0.334
    every 128x128 chunk stays live in either order."""
    from repro.kernels.worklist_core import schedule_counters
    mb_per_img = -(-size * size // 128)
    for c in vgg_width_chain:
        chan = pack_conv_filters(c.w_dense, layout="channel")
        got, want = (schedule_counters(build_worklist(
            w.host_indices(), 2 * mb_per_img, mb_per_img=mb_per_img))
            ["scheduled_steps"] for w in (c.packed, chan))
        assert got == want > 0


@pytest.mark.parametrize("executor", ["xla", "pallas"])
def test_tap_major_chain_forward_matches_dense(vgg_width_chain, executor):
    from repro.vision.model import VisionLayer, VisionModel
    layers = [VisionLayer(c, (1, 1), "SAME", (2, 2) if i == 1 else None)
              for i, c in enumerate(vgg_width_chain)]
    model = VisionModel("VGGNet", layers, 16, 0.334)
    rng = np.random.default_rng(3)
    x = np.abs(rng.normal(size=(2, 16, 16, 64))).astype(np.float32)
    x[rng.random(x.shape) >= 0.45] = 0.0
    out = compile_forward(model, executor=executor)(jnp.asarray(x))
    ref = dense_forward(model, jnp.asarray(x))
    assert out.shape == ref.shape == (2, 8, 8, 256)
    rel = float(jnp.abs(out - ref).max()) / (float(jnp.abs(ref).max()) + 1e-9)
    assert rel < 1e-4


@pytest.mark.parametrize("layer", [0, 1], ids=["straddling", "whole"])
def test_lazy_im2col_where_chunks_straddle_taps_is_taps(vgg_width_chain,
                                                        layer):
    """``im2col="lazy"`` gives the ``taps`` answer, bit for bit: on a
    layer whose chunks straddle taps (cin 64) it is demoted to ``taps``,
    since a slab is one tap's slice; on whole-chunk taps it runs."""
    c = vgg_width_chain[layer]
    assert c.layout == "tap" and (c.cin % c.packed.bk != 0) == (layer == 0)
    rng = np.random.default_rng(5)
    x = np.abs(rng.normal(size=(2, 12, 12, c.cin))).astype(np.float32)
    x[rng.random(x.shape) >= 0.5] = 0.0
    lazy, taps = (np.asarray(sparse_conv2d_nhwc(
        jnp.asarray(x), c.packed, 3, 3, c.cout, executor="xla",
        layout="tap", im2col=s)[0]) for s in ("lazy", "taps"))
    np.testing.assert_array_equal(lazy, taps)


@pytest.mark.parametrize("plant", ["chunk-pattern", "tuned-lazy"])
def test_straddling_tap_chunks_refused_where_a_chunk_needs_one_tap(
        vgg_width_chain, plant):
    """An unstructured tap layer may straddle taps; under the chunk
    pattern or a tuned ``lazy`` strategy the same layer reads
    ``PC-LAYOUT``, and the tuner offers ``lazy`` only where each chunk
    lies inside one tap."""
    import dataclasses
    from repro.analysis import Severity, verify_packed_conv
    from repro.kernels.autotune import (ConvTileConfig, TuneRecord,
                                        candidate_configs)

    def rules(pc):
        return {d.rule for d in verify_packed_conv(pc)
                if d.severity >= Severity.ERROR}

    c, whole = vgg_width_chain[0], vgg_width_chain[1]
    assert c.cin % c.packed.bk and "PC-LAYOUT" not in rules(c)
    assert {g.im2col for g in candidate_configs(c, 144)} == {"taps"}
    assert {g.im2col for g in candidate_configs(whole, 144)} == \
        {"taps", "lazy"}
    if plant == "chunk-pattern":
        bad = dataclasses.replace(c, pattern="chunk")
    else:
        rec = TuneRecord(config=ConvTileConfig(bm_rows=128, sub_m=8,
                                               im2col="lazy"),
                         cost=1.0, counts={}, table=[], m_img=1, batch=1,
                         measured=False)
        bad = dataclasses.replace(c, tuned=rec)
    assert "PC-LAYOUT" in rules(bad)


def test_compact_activations_rejected_under_jit(rng):
    """The dynamic intersection needs concrete activations; under a trace
    it must raise, not silently fall back."""
    x, w = _conv_operands(rng)
    ws = pack_conv_filters(w)

    @jax.jit
    def f(v):
        return sparse_conv2d_nhwc(v, ws, 3, 3, w.shape[-1],
                                  schedule="compact", executor="xla",
                                  compact_activations=True)[0]

    with pytest.raises(ValueError, match="compact_activations"):
        f(jnp.asarray(x))


# ---------------------------------------------------------------------------
# compiled whole-net pipeline
# ---------------------------------------------------------------------------
def test_compiled_forward_bitwise_equals_eager(rng):
    model = build_vision_model("VGGNet", num_layers=2, seed=0)
    x = np.abs(rng.normal(size=(2, 24, 24, 3))).astype(np.float32)
    x[rng.random(x.shape) >= 0.45] = 0.0
    eager, stats = forward(model, jnp.asarray(x), collect_stats=True)
    fn = compile_forward(model)
    np.testing.assert_array_equal(np.asarray(fn(jnp.asarray(x))),
                                  np.asarray(eager))
    # the jit is cached per config on the model
    assert compile_forward(model) is fn
    # stats carry the schedule compaction numbers
    assert all(s["scheduled_steps"] <= s["dense_grid_steps"] for s in stats)
    assert all(s["live_chunk_steps"] <= s["scheduled_steps"] for s in stats)
    assert all(s["combine_factor"] >= 1.0 for s in stats)
def test_vgg16_full_network_matches_dense(rng):
    model = build_vision_model("VGGNet", seed=0)   # Table-1 density 0.334
    assert model.num_layers == 13
    x = np.abs(rng.normal(size=(1, 24, 24, 3))).astype(np.float32)
    x[rng.random(x.shape) >= 0.45] = 0.0
    out, stats = forward(model, jnp.asarray(x), collect_stats=True)
    ref = dense_forward(model, jnp.asarray(x))
    rel = float(jnp.abs(out - ref).max()) / (float(jnp.abs(ref).max()) + 1e-9)
    assert rel < 1e-4
    fd, md = measured_densities(stats)
    assert abs(fd - 0.334) < 0.01              # pruning hit Table-1 density
    assert 0.0 < md <= 1.0
    assert all(s["skipped_tile_frac"] >= 0.0 for s in stats)


@pytest.mark.parametrize("arch", ["AlexNet", "ResNet18"])
def test_other_archs_short_chain(rng, arch):
    model = build_vision_model(arch, num_layers=3, seed=1)
    size = 35 if arch == "AlexNet" else 16
    x = np.abs(rng.normal(size=(1, size, size, 3))).astype(np.float32)
    out, _ = forward(model, jnp.asarray(x))
    ref = dense_forward(model, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_unsupported_arch_raises():
    with pytest.raises(ValueError):
        build_vision_model("Inception-v4")
    with pytest.raises(ValueError):
        build_vision_model("VGGNet", num_layers=0)


def test_one_sided_skip_frac_unit(rng):
    """Regression: one-sided counters are whole-tile units; a dense input
    must report ~0 skipped, not the 15/16 a sub-block denominator gives."""
    model = build_vision_model("VGGNet", num_layers=1, seed=0)
    x = jnp.asarray(np.abs(rng.normal(size=(1, 16, 16, 3))
                           ).astype(np.float32))
    _, stats = forward(model, x, two_sided=False, collect_stats=True)
    assert stats[0]["skipped_tile_frac"] == 0.0


def test_chain_balance_fold_roundtrip(rng):
    """Greedy-balancing + folding must leave the chain's function intact."""
    ws = [rng.normal(size=(3, 3, 4, 24)).astype(np.float32),
          rng.normal(size=(3, 3, 24, 16)).astype(np.float32)]
    x = np.abs(rng.normal(size=(1, 8, 8, 4))).astype(np.float32)

    def run_chain(chain):
        h = jnp.asarray(x)
        for c in chain:
            h = _lax_ref(h, c.w_dense, 1, "SAME")
        return np.asarray(h)

    plain = build_sparse_chain(ws, density=0.5, balance_filters=False)
    balanced = build_sparse_chain(ws, density=0.5, balance_filters=True)
    np.testing.assert_allclose(run_chain(plain), run_chain(balanced),
                               rtol=1e-5, atol=1e-5)
    assert not np.array_equal(balanced[0].perm,
                              np.arange(balanced[0].perm.shape[0]))


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
def _requests(rng, n, size=10, stagger=0):
    return [ImageRequest(rid=i, image=np.abs(
        rng.normal(size=(size, size, 3))).astype(np.float32),
        arrival=i * stagger) for i in range(n)]


def test_engine_matches_solo_forward(rng):
    model = build_vision_model("VGGNet", num_layers=2, seed=0)
    eng = VisionEngine(model, num_slots=2)
    reqs = _requests(rng, 5, stagger=1)
    produced = eng.run(reqs)
    assert sorted(produced) == [0, 1, 2, 3, 4]
    for r in reqs:
        solo, _ = forward(model, jnp.asarray(r.image[None]))
        np.testing.assert_allclose(produced[r.rid], np.asarray(solo)[0],
                                   rtol=1e-5, atol=1e-5)


def test_engine_batch_composition_invariance(rng):
    """Outputs must not depend on which lane or batch an image rode in."""
    model = build_vision_model("VGGNet", num_layers=2, seed=0)
    reqs = _requests(rng, 4)
    together = VisionEngine(model, num_slots=4).run(
        [ImageRequest(r.rid, r.image, 0) for r in reqs])
    staggered = VisionEngine(model, num_slots=2).run(
        [ImageRequest(r.rid, r.image, r.rid) for r in reqs])
    for r in reqs:
        np.testing.assert_allclose(together[r.rid], staggered[r.rid],
                                   rtol=1e-5, atol=1e-5)


def test_engine_round_robin_spreads_lanes(rng):
    """Consecutive single admissions must rotate across lanes, not pin
    lane 0 (BARISTA round-robin admission)."""
    model = build_vision_model("VGGNet", num_layers=1, seed=0)
    eng = VisionEngine(model, num_slots=3)
    lanes = []
    for i, r in enumerate(_requests(rng, 3, size=8)):
        eng.submit(r)
        eng._admit_ready()
        lanes.append(int(np.nonzero(eng.slot_req == r.rid)[0][0]))
        eng.step()
    assert len(set(lanes)) > 1, f"admissions pinned lane {lanes}"


def test_engine_rejects_mixed_image_shapes(rng):
    model = build_vision_model("VGGNet", num_layers=1, seed=0)
    eng = VisionEngine(model, num_slots=2)
    eng.submit(ImageRequest(0, np.ones((8, 8, 3), np.float32)))
    with pytest.raises(ValueError):
        eng.submit(ImageRequest(1, np.ones((10, 10, 3), np.float32)))


def test_engine_utilization_and_counts(rng):
    model = build_vision_model("VGGNet", num_layers=1, seed=0)
    eng = VisionEngine(model, num_slots=2)
    eng.run(_requests(rng, 4, size=8))
    assert eng.stats.images == 4
    assert eng.stats.engine_steps == 2          # 2 full batches
    assert eng.stats.slot_utilization == 1.0


# ---------------------------------------------------------------------------
# satellites: conv2d_im2col generalization + tile-density padding fix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stride,padding", [
    (1, "SAME"), ((2, 1), "VALID"), ((1, 2), ((1, 0), (2, 1))), (3, "SAME")])
def test_conv2d_im2col_generalized(rng, stride, padding):
    x = rng.normal(size=(2, 11, 9, 5)).astype(np.float32)
    w = rng.normal(size=(3, 3, 5, 7)).astype(np.float32)
    out = conv2d_im2col(jnp.asarray(x), jnp.asarray(w), stride, padding)
    exp = _lax_ref(x, w, stride, padding, relu=False)
    assert out.shape == exp.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_im2col_legacy_signature(rng):
    """int stride + string padding must keep working unchanged."""
    x = rng.normal(size=(1, 8, 8, 4)).astype(np.float32)
    w = rng.normal(size=(3, 3, 4, 6)).astype(np.float32)
    out = conv2d_im2col(jnp.asarray(x), jnp.asarray(w), 2, "VALID")
    exp = _lax_ref(x, w, 2, "VALID", relu=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_activation_tile_density_odd_shape(rng):
    """Regression: odd (non-block-multiple) shapes must not be diluted by
    padding tiles. An all-ones [130, 70] tensor is 100% dense."""
    x = jnp.ones((130, 70), jnp.float32)
    assert float(activation_tile_density(x, block=128)) == 1.0


def test_activation_tile_density_prepadded(rng):
    """Kernel-side operands arrive pre-padded to the block grid; the padded
    tiles must be excluded from the mean via valid_rows/valid_cols."""
    x = jnp.ones((130, 128), jnp.float32)
    padded = jnp.pad(x, ((0, 126), (0, 128)))   # the kernel's [256, 256]
    naive = float(activation_tile_density(padded, block=128))
    fixed = float(activation_tile_density(padded, block=128,
                                          valid_rows=130, valid_cols=128))
    assert naive == 0.5                         # understated by padding
    assert fixed == 1.0
    assert float(activation_tile_density(x, block=128)) == 1.0


def test_spmm_rejects_ragged_rows(rng):
    """The raw grid entry point asserts block-aligned rows (the NHWC wrapper
    owns the padding)."""
    w = rng.normal(size=(128, 128)).astype(np.float32)
    from repro.core import bitmask as bm
    ws = bm.block_sparsify(w)
    with pytest.raises(AssertionError):
        sparse_conv_spmm(jnp.ones((100, 128), jnp.float32), ws.indices,
                         ws.vals)


# ---------------------------------------------------------------------------
# chunk-aligned pattern at the committed bench settings
# ---------------------------------------------------------------------------
def _bench_blob(batch=1, size=24, live_frac=0.12, seed=0):
    """The committed BENCH_vision.json input (see benchmarks/vision_bench):
    blob images sparse enough that whole activation row blocks go dead."""
    from repro.launch.vision import blob_images
    return jnp.asarray(blob_images(np.random.default_rng(seed), batch, size,
                                   live_frac))


def test_chunk_pattern_bench_settings_compaction(rng):
    """Satellite: the 2-layer VGG head at the committed bench settings
    under pattern="chunk" must show real schedule compaction — flush-only
    steps exist, grid_compaction > 0 — while staying on the oracle
    (rel err <= 1e-5) and on the target scalar density (within 2% of the
    unstructured pruner at the same target)."""
    from repro.vision import oracle_check, schedule_summary
    x = _bench_blob()
    chunkm = build_vision_model("VGGNet", density=0.334, num_layers=2,
                                pattern="chunk", seed=0)
    out, stats, rel = oracle_check(chunkm, x)
    assert rel <= 1e-5
    tot = schedule_summary(stats)
    assert tot["flush_only_steps"] > 0
    assert tot["grid_compaction"] > 0
    assert tot["scheduled_steps"] < tot["dense_grid_steps"]
    # real dead chunks on the tap layer, reported through the stats path
    assert stats[1]["layout"] == "tap" and stats[1]["pattern"] == "chunk"
    assert stats[1]["dead_chunk_fraction"] == pytest.approx(2 / 3, abs=0.05)
    # scalar-density parity with the unstructured pruner at equal target
    unstr = build_vision_model("VGGNet", density=0.334, num_layers=2,
                               pattern="unstructured", seed=0)
    for cc, cu in zip((l.conv for l in chunkm.layers),
                      (l.conv for l in unstr.layers)):
        assert abs(cc.scalar_density() - cu.scalar_density()) <= 0.02


def test_chunk_pattern_compiled_pipeline_and_engine(rng):
    """The compiled whole-net jit and the serving engine both run the
    mixed-layout (channel stem + tap body) chunk network and agree with
    the eager kernel path bitwise."""
    model = build_vision_model("VGGNet", density=0.334, num_layers=2,
                               pattern="chunk", seed=0)
    x = _bench_blob()
    eager, _ = forward(model, x, compiled=False)
    fn = compile_forward(model)
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(eager))
    eng = VisionEngine(model, num_slots=2)
    produced = eng.run([ImageRequest(rid=0, image=np.asarray(x)[0])])
    np.testing.assert_allclose(produced[0], np.asarray(eager)[0],
                               rtol=1e-5, atol=1e-5)


def test_chunk_pattern_engine_with_tuned_schedules(rng):
    """Engine with use_tuned bakes the autotuned per-layer configs and
    still matches the untuned engine bitwise."""
    from repro.vision import autotune_model
    model = build_vision_model("VGGNet", density=0.334, num_layers=2,
                               pattern="chunk", seed=0)
    x = _bench_blob()
    base = np.asarray(compile_forward(model)(x))
    autotune_model(model, 24)
    eng = VisionEngine(model, num_slots=1, use_tuned=True)
    produced = eng.run([ImageRequest(rid=0, image=np.asarray(x)[0])])
    np.testing.assert_array_equal(produced[0], base[0])
