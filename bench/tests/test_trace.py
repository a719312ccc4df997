"""The trace reduction: on a synthetic trace with known answers, on a small
trace recorded on a v5e, and the reading of an ``.xplane.pb``."""
import gzip
import json
import os
import types

import pytest

import tracered

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_vgg16_closed8.json.gz")


def _synthetic():
    # window [100, 200); device 0: ops overlap at 120-140, a gap 150-170,
    # one op straddles the window's end; device 1: idle after 150
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 100.0, 100.0],
            ["bench.step", 100.0, 60.0],
            ["bench.collect", 160.0, 5.0],
            ["bench.step", 165.0, 40.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_f(1)", 90.0, 200.0]]},
            {"name": "XLA Ops", "events": [
                ["_worklist_spmm_pallas.3", 90.0, 40.0],   # 90-130
                ["fusion.1", 120.0, 20.0],                 # 120-140
                ["_worklist_spmm_pallas.4", 140.0, 10.0],  # 140-150
                ["_worklist_spmm_pallas.3", 170.0, 50.0],  # 170-220
            ]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 100.0, 50.0]]}]},
        {"name": "/device:TPU:2", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 100.0, 99.0]]}]},
    ]}


def test_reduce_synthetic():
    r = tracered.reduce(_synthetic(), [0, 1])
    assert r.window_ns == 100.0
    d0, d1 = r.devices[0], r.devices[1]
    assert d0.busy_ns == 50.0 + 30.0      # 100-150 and 170-200
    assert d0.idle == [(150.0, 170.0)]
    assert d1.busy_ns == 50.0 and d1.idle == [(150.0, 200.0)]
    assert r.busy_s == pytest.approx((80.0 + 50.0) / 2 / 1e9)
    walker = lambda n: n.startswith("_worklist_spmm_pallas")  # noqa: E731
    assert r.op_seconds(walker) == pytest.approx((30 + 10 + 30) / 1e9)
    # started in the window: the op at 90 is out, the one at 170 is in whole
    assert r.started(walker) == (2, pytest.approx((10 + 50) / 1e9))
    top = r.top_ops(2)
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx((20 + 50) / 2 / 1e9)
    gaps = r.longest_gaps()
    assert gaps[0] == ["host: bench.step", pytest.approx(50 / 1e9)]
    assert gaps[1] == ["host: bench.collect", pytest.approx(20 / 1e9)]


def test_reduce_needs_the_window_and_the_devices():
    t = _synthetic()
    with pytest.raises(ValueError):
        tracered.reduce(t, [0, 5])
    t["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        tracered.reduce(t, [0])


def test_metric_readers_on_the_synthetic_trace():
    import spec
    import work
    r = tracered.reduce(_synthetic(), [0])
    layer = work.LayerWork(in_hw=8, out_hw=8, cin=4, cout=4, kernel=3,
                           nonzero=10)
    w = types.SimpleNamespace(
        trace=r, peaks={"bf16_flops": 1e3, "hbm_bytes_s": 1e12},
        work=[layer], slots=2, chips=1, seconds=1e-7, images_in_window=2)
    assert spec.reader("device_idle_pct")(w) == pytest.approx(
        100 * (1 - 80 / 100))
    # two walker calls started in the window, 60 ns whole; one layer
    expect = 2 * layer.flops(2) / 1e3 / 60e-9
    assert spec.reader("walker_roofline")(w) == pytest.approx(100 * expect)
    assert spec.reader("non_walker_ms_per_img")(w) == pytest.approx(
        1e3 * (80 - 70) / 1e9 / 2)
    assert spec.reader("step_mfu_pct")(w) == pytest.approx(
        100 * 2 * layer.flops(1) / (1e-7 * 1e3))
    # the 4-chip cells' names read the same quantities
    for name in ("device_idle_pct", "step_mfu_pct", "walker_roofline",
                 "img_per_s"):
        assert spec.reader(name + ".mesh4")(w) == spec.reader(name)(w)


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_v5e_trace(recorded):
    """A trace of the VGG16@224 closed loop at 8 slots on one TPU v5e,
    cut to three forward calls."""
    meta = recorded["meta"]
    r = tracered.reduce(recorded["trace"], [0])
    assert r.window_ns == pytest.approx(meta["window_ns"])
    walker = lambda n: n.startswith("_worklist_spmm_pallas")  # noqa: E731
    calls, seconds = r.started(walker)
    assert calls == meta["walker_calls"] == 13 * meta["forward_calls"]
    assert seconds == pytest.approx(meta["walker_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(meta["busy_s"], rel=1e-9)
    assert 0 < r.busy_s <= r.window_s
    assert r.op_seconds(walker) <= r.busy_s
    assert [n for n, _ in r.top_ops(3)] == meta["top_ops"]
    assert r.top_ops(1)[0][0] == "_worklist_spmm_pallas.14 f32[401408,128]"


def test_op_name_keeps_instruction_and_shape():
    text = ('%_worklist_spmm_pallas.14 = f32[401408,128]{1,0:T(8,128)} '
            'custom-call(s32[15680]{0:T(1024)S(1)} %broadcast.7)')
    assert tracered.op_name(text) == \
        "_worklist_spmm_pallas.14 f32[401408,128]"
    assert tracered.op_name("fusion.3") == "fusion.3"


def test_harness_spans_mark_the_window():
    import harness
    log = harness.SpanLog()
    with log.span(tracered.WINDOW_SPAN):
        with log.span("bench.step"):
            pass
        with log.span("bench.step"):
            pass
    origin = min(t0 for _, t0, _ in log.spans) - 1000
    t = {"planes": [log.plane(origin)]}
    s, e = tracered.window(t)
    assert s == 1000.0 and e > s
    d = log.diag()
    assert d["steps"] == 2 and d["max_gap_ms"] >= 0


def test_compact_reads_an_xplane(tmp_path):
    import glob
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracered.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tracered.find_xplane(str(tmp_path))
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    t = tracered.compact(path)
    s, e = tracered.window(t)
    assert e > s
    assert t["profile_start_ns"] > 0
    assert tracered.device_ops(t, [0]) == {}     # the CPU has no TPU plane
