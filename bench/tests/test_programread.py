"""The readers of the program's spans and of the forward's layer scopes
(``programread.py``) and the metric readers built on them: on synthetic
snapshots and HLO text with known answers, on a trace recorded on a v5e,
and in the harness's traced run on the CPU."""
import gzip
import json
import os
import time
import types

import pytest

import harness
import programread as PT
import spec
import tracered

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "v5e_vgg16_closed8.json.gz")
PROGRAM = os.path.join(DATA, "v5e_vgg16_closed8_program.json.gz")


def _span(seq, name, start, end, parent=-1, **attrs):
    return {"name": name, "start_ns": start, "end_ns": end,
            "parent": parent, "attrs": attrs, "seq": seq}


def _snapshot():
    # set-up before 1000: packing, then a warm-up holding a compile whose
    # cache load took 30 of its 100; the window [1000, 2000) holds two
    # steps and a third starts after it
    spans = [_span(0, "setup.pack", 0, 200),
             _span(1, "serve.verify", 200, 210),
             _span(2, "serve.warmup", 300, 900, bucket=224),
             _span(4, "jax.cache_load", 420, 450, 3),
             _span(3, "jax.compile", 400, 500, 2, fun_name="vision_forward"),
             _span(5, "jax.trace", 310, 390, 2, fun_name="vision_forward")]
    seq = 6
    for t0, wait in ((1000, 60), (1100, 80), (2050, 10)):
        spans.append(_span(seq, "serve.step", t0, t0 + 100, images=8))
        t, parent = t0, seq
        seq += 1
        for name, d in (("serve.admit", 5), ("serve.h2d", 10),
                        ("serve.dispatch", 2), ("serve.wait", wait),
                        ("serve.d2h", 8), ("serve.record", 1)):
            spans.append(_span(seq, name, t, t + d, parent))
            t, seq = t + d, seq + 1
    spans.sort(key=lambda s: (s["start_ns"], s["seq"]))
    return {"spans": spans}


def test_step_readers_on_a_synthetic_snapshot():
    snap, win = _snapshot(), (1000, 2000)
    assert PT.host_ms_per_step(snap, win) == pytest.approx(
        ((100 - 60) + (100 - 80)) / 2 / 1e6)
    ph = PT.step_phases(snap, win)
    assert ph["serve.step"] == {"mean_ms": 100 / 1e6, "max_ms": 100 / 1e6,
                                "n": 2}
    assert ph["serve.wait"]["mean_ms"] == pytest.approx(70 / 1e6)
    assert ph["serve.wait"]["max_ms"] == pytest.approx(80 / 1e6)
    assert set(ph) == {"serve.step", *PT.STEP_PHASES}
    assert PT.host_ms_per_step(snap, (3000, 4000)) is None
    assert PT.images_in_window(snap, win) == 16
    assert PT.images_in_window(snap, (1000, 1150)) == 8   # one step ends
    assert PT.images_in_window(None, win) == 0


def test_setup_readers_on_a_synthetic_snapshot():
    snap = _snapshot()
    assert PT.pack_s(snap, 1000) == pytest.approx(200 / 1e9)
    assert PT.warmup_s(snap, 1000) == pytest.approx(600 / 1e9)
    assert PT.compile_s(snap, 1000) == pytest.approx(70 / 1e9)
    ph = PT.setup_phases(snap, 1000)
    assert ph["jax.compile"] == pytest.approx(100 / 1e9)
    assert ph["jax.cache_load"] == pytest.approx(30 / 1e9)
    assert ph["compile_proper"] == pytest.approx(70 / 1e9)
    assert PT.warmup_s(snap, 800) is None        # not over by then
    assert PT.compile_s(snap, 450) is None       # the compile neither
    # a snapshot whose set-up spans were lost reads no compile time
    late = {"spans": [s for s in snap["spans"] if s["start_ns"] >= 1000]}
    assert PT.compile_s(late, 1000) is None


@pytest.mark.parametrize("reader", ["host_ms_per_step", "step_phases"])
def test_step_readers_read_nothing_without_a_snapshot(reader):
    assert getattr(PT, reader)(None, (0, 1)) is None


@pytest.mark.parametrize("reader", ["pack_s", "warmup_s", "compile_s",
                                    "setup_phases"])
def test_setup_readers_read_nothing_without_a_snapshot(reader):
    assert getattr(PT, reader)(None, 0) is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(vision_forward)/layer03/im2col/jit(_pad)/pad", "layer03/im2col"),
    ("jit(vision_forward)/layer11/walker/jit(_worklist_spmm_pallas)/"
     "pallas_call", "layer11/walker"),
    ("jit(vision_forward)/layer01/pool/reduce_window_max", "layer01/pool"),
    ("jit(vision_forward)/layer00/reshape", "layer00"),
    # any name scope under the layer with a primitive after it is a part
    ("jit(vision_forward)/layer07/add/add", "layer07/add"),
    ("jit(vision_forward)/layer12/proj/walker/jit(_worklist_spmm_pallas)/"
     "pallas_call", "layer12/proj"),
    # a transform's component is no part
    ("jit(vision_forward)/layer03/jit(_pad)/pad", "layer03"),
    ("jit(vision_forward)/transpose", None),
    (None, None)])
def test_scope_of(op_name, scope):
    assert PT.scope_of(op_name) == scope


HLO = """HloModule jit_vision_forward, is_scheduled=true

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %n = f32[8]{0} negate(%p), metadata={op_name="jit(vision_forward)/layer01/pool/max"}
}

%fused_computation.3 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %c = f32[8]{0} convolution(%p.1, %p.1), window={size=1}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %copy.21 = f32[8]{1,0} copy(%x.1)
  %fusion.2 = f32[8]{0} fusion(%copy.21), kind=kOutput, calls=%fused_computation.3
  %pad.6 = f32[8]{0} pad(%fusion.2, %copy.21), padding=0_0, metadata={op_name="jit(vision_forward)/layer00/im2col/jit(_pad)/pad"}
  %constant.4 = s32[4]{0} constant({0, 1, 2, 3})
  %copy-start.1 = (s32[4]{0}, s32[4]{0}, u32[]) copy-start(%constant.4)
  %copy-done.1 = s32[4]{0} copy-done(%copy-start.1)
  %_worklist_spmm_pallas.13 = f32[8]{0} custom-call(%copy-done.1, %pad.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(vision_forward)/layer00/walker/jit(_worklist_spmm_pallas)/pallas_call"}
  %fusion.1 = f32[8]{0} fusion(%_worklist_spmm_pallas.13), kind=kLoop, calls=%fused_computation.2
  %copy.30 = f32[8]{0:T(8,128)} copy(%fusion.1)
  %fusion.7 = ((f32[8]{0:T(8,128)}, u32[]{:S(2)}), s32[]) fusion(%copy.30), kind=kLoop, calls=%fused_computation.2
  %get-tuple-element.3 = (f32[8]{0}, u32[]) get-tuple-element(%fusion.7), index=0
  ROOT %copy.9 = f32[8]{0} copy(%fusion.1)
}
"""


def test_op_scopes_follow_metadata_calls_and_consumers():
    scopes = PT.op_scopes(HLO)
    assert scopes["pad.6"] == ("layer00/im2col", "own")
    assert scopes["_worklist_spmm_pallas.13"] == ("layer00/walker", "own")
    # its computation's metadata
    assert scopes["fusion.1"] == scopes["fusion.7"] == ("layer01/pool",
                                                        "called")
    # metadata dropped: the first scoped consumer's scope
    assert scopes["fusion.2"] == scopes["copy.21"] == ("layer00/im2col",
                                                       "consumer")
    assert scopes["copy-start.1"] == scopes["copy-done.1"] \
        == ("layer00/walker", "consumer")
    # a consumer whose result is a nested tuple still links its operand
    assert scopes["copy.30"] == ("layer01/pool", "consumer")
    assert scopes["copy.9"] == scopes["get-tuple-element.3"] \
        == (None, None)                            # nothing consumes them
    assert "n" not in scopes                      # entry computation only
    assert PT.op_scopes("not HLO") == {}


@pytest.mark.parametrize("rest,operands", [
    ("f32[8]{1,0:T(8,128)} copy(%x.1), metadata={op_name=\"a(b)\"}",
     ["x.1"]),
    ("((f32[8]{0}, u32[]), s32[]) fusion(%a, %b.2), calls=%f",
     ["a", "b.2"]),
    ("(s32[4]{0:T(128)S(1)}, s32[4]{0}, u32[]{:S(2)}) copy-start(%c.4)",
     ["c.4"]),
    ("f32[8]{0} custom-call(f32[8]{0:T(8,128)} %p, s32[] %q), "
     "custom_call_target=\"tpu_custom_call\"", ["p", "q"]),
    ("s32[] constant(3)", [])])
def test_operands_follow_the_opcode(rest, operands):
    assert PT._operands(rest) == operands


def _reduction():
    dev = types.SimpleNamespace(op_ns={
        "_worklist_spmm_pallas.13 f32[8]": 4e6,
        "pad.6 f32[8]": 2e6, "fusion.2 f32[8]": 1e6,
        "fusion.1 f32[8]": 0.5e6, "copy.9 f32[8]": 0.5e6})
    return types.SimpleNamespace(devices={0: dev, 1: dev})


def _origin_pct(reduction, scopes):
    """Shares of the device time whose scope came from each origin."""
    secs, total = {}, 0.0
    for d in reduction.devices.values():
        for name, ns in d.op_ns.items():
            origin = scopes.get(name.split(" ", 1)[0], (None, None))[1]
            secs[origin] = secs.get(origin, 0.0) + ns
            total += ns
    return {o: 100.0 * secs.get(o, 0.0) / total
            for o in ("own", "called", "consumer")}


def test_layer_readers_on_a_synthetic_reduction():
    scopes, red = PT.op_scopes(HLO), _reduction()
    secs = PT.scope_seconds(red, scopes)
    assert secs == pytest.approx({"layer00/walker": 8e-3,
                                  "layer00/im2col": 6e-3,
                                  "layer01/pool": 1e-3, None: 1e-3})
    # two devices, 4 images: im2col (pad + fusion.2) 3 ms per device
    assert PT.im2col_ms_per_img(secs, 4) == pytest.approx(2 * 3.0 / 4)
    lay = PT.layers(secs, 4)
    assert lay["per_layer_ms_per_img"] == {
        "layer00": {"walker": 2.0, "im2col": 1.5, "pool": 0.0,
                    "other": 0.0},
        "layer01": {"walker": 0.0, "im2col": 0.0, "pool": 0.25,
                    "other": 0.0}}
    assert lay["unscoped_pct"] == pytest.approx(100 * 0.5 / 8)
    # pad.6 and the walker scoped by their own metadata, fusion.1 by its
    # computation's, fusion.2 by its consumer's
    assert _origin_pct(red, scopes) == pytest.approx(
        {"own": 100 * 6 / 8, "called": 100 * 0.5 / 8,
         "consumer": 100 * 1 / 8})
    assert PT.im2col_ms_per_img(None, 4) is None
    assert PT.im2col_ms_per_img({"layer01/pool": 1.0}, 4) is None
    assert PT.layers(PT.scope_seconds(red, {}), 4) == {
        "per_layer_ms_per_img": {}, "unscoped_pct": 100.0}
    assert PT.layers(None, 4) is None


# a residual block as a graph forward could scope it: a projection
# walked under its own part, layer 01's walker output plus it under
# ``add``, then the ReLU
ADD_HLO = """HloModule jit_vision_forward, is_scheduled=true

ENTRY %main.4 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %_worklist_spmm_pallas.1 = f32[8]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(vision_forward)/layer00/proj/walker/jit(_worklist_spmm_pallas)/pallas_call"}
  %_worklist_spmm_pallas.2 = f32[8]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(vision_forward)/layer01/walker/jit(_worklist_spmm_pallas)/pallas_call"}
  %add.3 = f32[8]{0} add(%_worklist_spmm_pallas.2, %_worklist_spmm_pallas.1), metadata={op_name="jit(vision_forward)/layer01/add/add"}
  ROOT %maximum.4 = f32[8]{0} maximum(%add.3, %add.3), metadata={op_name="jit(vision_forward)/layer01/max"}
}
"""

ADD_READER = '''"""add_ms_per_img: device time under the layers' ``add`` parts per
image, with the mean ``serve.admit`` beside it, as a later
configuration's reader could take them from the window."""
import programread


def read(w):
    secs = [s for k, s in (w.scope_s or {}).items()
            if k and k.endswith("/add")]
    if not secs or w.program is None:
        return None
    admit = [s["end_ns"] - s["start_ns"] for s in w.program["spans"]
             if s["name"] == "serve.admit"]
    return {"add_ms_per_img": 1e3 * sum(secs) / w.images_in_window,
            "admit_ns": sum(admit) / len(admit),
            "host_ms_per_step": programread.host_ms_per_step(
                w.program, w.window_ns)}
'''


def test_a_new_reader_reads_any_part_and_any_span(tmp_path):
    """A reader file of its own finds an ``add`` part in ``Window.scope_s``
    and a program span in ``Window.program``, names that the harness
    does not know."""
    scopes = PT.op_scopes(ADD_HLO)
    assert scopes["add.3"] == ("layer01/add", "own")
    assert scopes["_worklist_spmm_pallas.1"] == ("layer00/proj", "own")
    assert scopes["maximum.4"] == ("layer01", "own")
    dev = types.SimpleNamespace(op_ns={
        "_worklist_spmm_pallas.1 f32[8]": 1e6,
        "_worklist_spmm_pallas.2 f32[8]": 3e6, "add.3 f32[8]": 0.5e6,
        "maximum.4 f32[8]": 0.25e6})
    win = harness.Window(seconds=1.0, setup_s=1.0, slots=8, chips=1,
                         images_in_window=8, program=_snapshot(),
                         window_ns=(1000, 2000))
    win.scope_s = PT.scope_seconds(types.SimpleNamespace(devices={0: dev}),
                                   scopes)
    path = tmp_path / "add_ms_per_img.py"
    path.write_text(ADD_READER)
    got = spec._module(str(path), "add_ms_per_img").read(win)
    assert got["add_ms_per_img"] == pytest.approx(0.5 / 8)
    assert got["admit_ns"] == 5
    assert got["host_ms_per_step"] == pytest.approx(30 / 1e6)
    lay = PT.layers(win.scope_s, 8)["per_layer_ms_per_img"]
    assert lay == {
        "layer00": {"add": 0.0, "proj": 1 / 8, "walker": 0.0,
                    "other": 0.0},
        "layer01": {"add": pytest.approx(0.5 / 8), "proj": 0.0,
                    "walker": 3 / 8, "other": pytest.approx(0.25 / 8)}}


def _load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _program_plane(trace):
    """A ``/host:program`` plane whose steps nest in the harness's
    ``bench.step`` spans, as the recorder would write it."""
    events = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name == "bench.step":
                    events += [["serve.step", s + 10, d - 20],
                               ["serve.h2d", s + 20, 1e6],
                               ["serve.wait", s + 1e6 + 30, d - 2e6]]
    return {"name": "/host:program", "lines": [{"name": "program",
                                                "events": events}]}


def _readings(trace, work):
    r = tracered.reduce(trace, [0])
    w = types.SimpleNamespace(
        trace=r, peaks=spec.peaks("TPU v5 lite"), work=work, slots=8,
        chips=1, seconds=r.window_s, images_in_window=24)
    return {m: spec.reader(m)(w) for m in (
        "device_idle_pct", "walker_roofline", "non_walker_ms_per_img",
        "step_mfu_pct")}


def test_a_program_plane_leaves_the_old_readings_as_they_were():
    import work
    rec = _load(OLD)
    config = spec.load_cell("vgg16_224_closed8").config
    layers = work.table_work(config, 224, [9 * 64 * 64] * 13)
    before = _readings(rec["trace"], layers)
    rec["trace"]["planes"].append(_program_plane(rec["trace"]))
    after = _readings(rec["trace"], layers)
    assert after == before
    assert all(v is not None for v in after.values())
    # and the gaps are named by the program's phases now
    names = {n for n, _ in tracered.reduce(rec["trace"], [0])
             .longest_gaps()}
    assert "host: bench.step" not in names


@pytest.fixture(scope="module")
def program():
    """A ``program_trace.py`` run of VGG16@224 at 8 slots on one TPU v5e
    with the recorder on, cut to three forward calls: the device trace,
    the harness's and the program's host planes, the recorder's snapshot
    (set-up and the three steps) and the compiled forward's text."""
    rec = _load(PROGRAM)
    rec["op_scopes"] = PT.op_scopes(rec["hlo"])
    return rec


def _window(rec):
    return tracered.window(rec["trace"])


# what the trace reader's own functions (``bench/program_trace.py``, before
# they moved to ``programread``) read on the recorded run
PARENT = {
    "host_ms_per_step": 2.7213596666666664,
    "im2col_ms_per_img": 5.216666541666667,
    "pack_s": 0.274062152, "warmup_s": 23.944375396,
    "compile_s": 22.370639975,
    "setup_phases": {"setup.pack": 0.274062152, "serve.verify": 0.02353978,
                     "serve.warmup": 23.944375396, "jax.trace": 0.715747328,
                     "jax.compile": 22.964561408,
                     "jax.cache_load": 0.593921433,
                     "compile_proper": 22.370639975},
    "step_mean_ms": {"serve.step": 80.65244833333333,
                     "serve.admit": 0.76605,
                     "serve.h2d": 0.40890366666666667,
                     "serve.dispatch": 0.21482,
                     "serve.wait": 77.93108866666667,
                     "serve.d2h": 1.230273, "serve.record": 0.05488},
    "layer01": {"walker": 0.8385479166666667, "im2col": 1.4565079583333331,
                "pool": 0.034024500000000006,
                "other": 0.07814091666666667},
    "layer12": {"walker": 0.09269775000000001, "im2col": 0.145108875,
                "pool": 0.0, "other": 0.0010208749999999999},
    "scope_origin_pct": {"own": 82.21311658907955, "called": 0.0,
                         "consumer": 17.786883410920414},
}


def test_recorded_program_readings(program):
    meta, snap = program["meta"], program["program"]
    win = _window(program)
    red = tracered.reduce(program["trace"], [0])
    assert red.window_ns == pytest.approx(meta["window_ns"])
    secs = PT.scope_seconds(red, program["op_scopes"])
    images = meta["images"]
    assert PT.images_in_window(snap, win) == images == 24
    assert PT.host_ms_per_step(snap, win) == pytest.approx(
        PARENT["host_ms_per_step"], rel=1e-12)
    assert PT.im2col_ms_per_img(secs, images) == pytest.approx(
        PARENT["im2col_ms_per_img"], rel=1e-12)
    for reader in ("pack_s", "warmup_s", "compile_s"):
        assert getattr(PT, reader)(snap, win[0]) == pytest.approx(
            PARENT[reader], rel=1e-12)
    assert PT.setup_phases(snap, win[0]) == pytest.approx(
        PARENT["setup_phases"], rel=1e-12)
    phases = PT.step_phases(snap, win)
    assert {k: v["mean_ms"] for k, v in phases.items()} == pytest.approx(
        PARENT["step_mean_ms"], rel=1e-12)
    assert phases["serve.step"]["n"] == meta["forward_calls"]
    per = PT.layers(secs, images)["per_layer_ms_per_img"]
    for layer in ("layer01", "layer12"):
        assert per[layer] == pytest.approx(PARENT[layer], rel=1e-12)


def test_recorded_readings_through_the_metric_readers(program):
    """The benchmark's reader files give what ``programread`` gives, on
    a window built as the harness builds it."""
    red = tracered.reduce(program["trace"], [0])
    w = harness.Window(seconds=red.window_s, setup_s=1.0, slots=8, chips=1,
                       images_in_window=24, trace=red,
                       program=program["program"],
                       window_ns=_window(program),
                       scope_s=PT.scope_seconds(red, program["op_scopes"]))
    for name in ("host_ms_per_step", "host_ms_per_step.mesh4",
                 "im2col_ms_per_img", "pack_s", "warmup_s", "compile_s"):
        assert spec.reader(name)(w) == pytest.approx(
            PARENT[name.split(".")[0]], rel=1e-12), name


def test_recorded_layers_cover_the_busy_time(program):
    red = tracered.reduce(program["trace"], [0])
    lay = PT.layers(PT.scope_seconds(red, program["op_scopes"]),
                    program["meta"]["images"])
    per = lay["per_layer_ms_per_img"]
    assert sorted(per) == [f"layer{i:02d}" for i in range(13)]
    assert lay["unscoped_pct"] < 5.0
    origins = _origin_pct(red, program["op_scopes"])
    assert lay["unscoped_pct"] + sum(origins.values()) == pytest.approx(
        100.0)
    assert origins == pytest.approx(PARENT["scope_origin_pct"], rel=1e-12)
    assert all(rec["walker"] > 0 and rec["im2col"] > 0
               for rec in per.values())
    total = sum(sum(rec.values()) for rec in per.values())
    ops_s = sum(ns for d in red.devices.values() for ns in d.op_ns.values())
    assert total * program["meta"]["images"] / 1e3 == pytest.approx(
        ops_s / 1e9 * (1 - lay["unscoped_pct"] / 100), rel=1e-9)
    # the walker kept its instruction names, one per layer, each scoped
    # by its own metadata
    walker = {n: s for n, s in program["op_scopes"].items()
              if n.startswith("_worklist_spmm_pallas.")}
    assert sorted(walker.values()) == [(f"layer{i:02d}/walker", "own")
                                       for i in range(13)]


def _vgg16_geometry():
    """(input px, input channels) of each VGG16 conv layer."""
    config = spec.load_cell("vgg16_224_closed8").config
    out, size = [], config["input_size"]
    for layer in config["layers"]:
        out.append((size, layer["in_channels"]))
        if layer["pool_after"]:
            size //= layer["pool_after"][1]
    return out


def test_recorded_consumer_rule_matches_the_layer_geometry(program):
    """The ops that take their scope from a consumer, checked against
    what their shapes say: conv1_2's patch fusion, its layout copy and
    its pad are ``layer01/im2col``; a patch tensor ``[8, H, W, C, 9]`` or
    ``[8, H*W, 9C]`` and a one-hot patch filter ``[3, 3, 1, C, 9]`` lie in
    a layer whose input is H px and C channels. Together these hold
    nearly all the inherited device time."""
    import re
    scopes = program["op_scopes"]
    for name in ("fusion.6", "copy.24", "pad.2"):
        assert scopes[name][0] == "layer01/im2col", name
    geo = _vgg16_geometry()
    red = tracered.reduce(program["trace"], [0])
    checked = inherited = 0.0
    for d in red.devices.values():
        for key, ns in d.op_ns.items():
            name, shape = key.split(" ", 1)
            scope, origin = scopes[name]
            if origin != "consumer":
                continue
            inherited += ns
            dims = [int(x) for x in re.findall(
                r"\d+", shape.split("[", 1)[1].split("]", 1)[0])]
            px, cin = geo[int(scope[5:7])]
            if dims[:3] == [3, 3, 1] and dims[-1] == 9:
                assert dims[3] == cin, (key, scope)
            elif len(dims) == 5 and dims[0] == 8 and dims[-1] == 9:
                assert dims[1:4] == [px, px, cin], (key, scope)
            elif len(dims) == 3 and dims[0] == 8 and dims[2] % 9 == 0:
                assert dims[1:] == [px * px, 9 * cin], (key, scope)
            else:
                continue
            checked += ns
    assert inherited > 0 and checked / inherited > 0.95


def test_recorded_steps_nest_in_the_harness_steps(program):
    planes = {p["name"]: p for p in program["trace"]["planes"]}
    bench = [(s, s + d) for n, s, d in
             planes["/host:bench"]["lines"][0]["events"]
             if n == "bench.step"]
    steps = [(s, s + d) for n, s, d in
             planes["/host:program"]["lines"][0]["events"]
             if n == "serve.step"]
    assert steps and len(steps) == len(bench)
    for s, e in steps:
        assert any(bs <= s and e <= be for bs, be in bench)


def test_recorded_gaps_are_named_by_the_program(program):
    red = tracered.reduce(program["trace"], [0])
    names = {n for n, _ in red.longest_gaps()}
    assert names and all(n.startswith("host: serve.")
                         or n == "host: bench.collect" for n in names)
    # without the program's plane the same gaps were the harness's step
    planes = [p for p in program["trace"]["planes"]
              if p["name"] != "/host:program"]
    old = tracered.reduce(dict(program["trace"], planes=planes), [0])
    assert "host: bench.step" in {n for n, _ in old.longest_gaps()}


ALL = ["vgg16_224_closed8", "alexnet_227_closed8",
       "vgg16_224_mesh4_closed32"]
NEW = {"host_ms_per_step": ALL[:2],
       "host_ms_per_step.mesh4": ALL[2:],
       "im2col_ms_per_img": ALL[:2],
       "pack_s": ALL, "warmup_s": ALL, "compile_s": ALL}


@pytest.mark.parametrize("name", sorted(NEW))
def test_program_readers_read_nothing_untraced(name):
    w = harness.Window(seconds=20.0, setup_s=30.0, slots=8, chips=1,
                       images_in_window=100)
    assert spec.reader(name)(w) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_program_metrics_load_for_the_cells_they_list(name):
    assert [c for c in ALL if name in {
        m.name for m in spec.load_cell(c).per_layer}] == NEW[name]


def test_an_untraced_run_leaves_the_recorder_off():
    from conftest import run_small, small_cell
    from repro import obs
    obs.enable()
    obs.disable()                # an empty store, recording off
    out = run_small(small_cell("alexnet_227_closed8", layers=2, size=67,
                               limit=5e-6))
    assert out["correct"] and out["attempted"] > 0
    assert obs.snapshot() == {"spans": []}
    assert obs.span("a") is obs.span("b")        # the shared no-op


def _fake_device_trace(monkeypatch):
    """The CPU's profile has no device plane: give the harness one whose
    operations are the served forward's instructions, run once inside
    each ``serve.step`` that the recorder saw. Returns the forward texts
    that the harness read its op scopes from."""
    from repro import obs
    texts = []
    real = PT.op_scopes

    def op_scopes(text):
        texts.append(text)
        return real(text)

    def compact(_path):
        names = list(real(texts[-1]))
        events = []
        for s in obs.snapshot()["spans"]:
            if s["name"] == PT.STEP:
                d = (s["end_ns"] - s["start_ns"]) / (len(names) + 1)
                events += [[n, float(s["start_ns"] + i * d), d]
                           for i, n in enumerate(names)]
        return {"profile_start_ns": 0, "planes": [{
            "name": "/device:TPU:0",
            "lines": [{"name": "XLA Ops", "events": events}]}]}

    monkeypatch.setattr(PT, "op_scopes", op_scopes)
    monkeypatch.setattr(tracered, "compact", compact)
    monkeypatch.setattr(tracered, "find_xplane", lambda log_dir: log_dir)
    return texts


@pytest.mark.parametrize("cell_name", ["vgg16_224_closed8",
                                       "alexnet_227_closed8"])
def test_recorder_run_and_forward_scopes_on_the_cpu(monkeypatch,
                                                     cell_name):
    """The harness's traced run at a small cut on the CPU, with only the
    profiler's device plane made up (the CPU has none): the recorder is on
    from set-up to the window's end and off after it, the readers see
    set-up and every step, and the op scopes come from the served
    forward's own compiled text and cover every layer of the cut."""
    from conftest import small_cell
    from repro import obs
    texts = _fake_device_trace(monkeypatch)
    cell = small_cell(cell_name, layers=3, slots=4, size=67 if
                      cell_name.startswith("alexnet") else 32,
                      limit=spec.load_cell(cell_name).config["limits"][
                          "max_rel_err"])
    seen = []
    out = harness.run(cell, 2 ** 33 + 11, 1.0, True,
                      t_start=time.monotonic(), require_chip=False,
                      on_window=seen.append)
    assert out["correct"]
    assert obs.span("a") is obs.span("b")        # the recorder is off
    w = seen[0]
    assert w.window_ns[0] < w.window_ns[1]
    names = {s["name"] for s in w.program["spans"]}
    assert {"setup.pack", "serve.verify", "serve.warmup", "jax.compile",
            PT.STEP, *PT.STEP_PHASES} <= names
    # the harness's count ends at ``seconds``, the span's with the last
    # step of the window: they differ by one step's images at most
    assert abs(PT.images_in_window(w.program, w.window_ns)
               - w.images_in_window) <= w.slots < w.images_in_window
    ph = PT.step_phases(w.program, w.window_ns)
    assert set(ph) == {PT.STEP, *PT.STEP_PHASES}
    assert ph[PT.STEP]["n"] == out["window_diag"]["steps"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("host_ms_per_step", "pack_s", "warmup_s",
                 "im2col_ms_per_img"):
        assert got[name] > 0, name
    assert got["compile_s"] >= 0
    assert got["host_ms_per_step"] == PT.host_ms_per_step(w.program,
                                                          w.window_ns)
    # one forward text, the server's; its scopes cover every layer
    assert len(texts) == 1
    found = {s for s in w.scope_s if s}
    for layer in ("layer00", "layer01", "layer02"):
        assert {f"{layer}/walker", f"{layer}/im2col"} <= found, layer
    # the idle gaps are named by the program's spans
    assert out["breakdown"]["idle_gaps"]
    assert {n for n, _ in out["breakdown"]["idle_gaps"]} <= {
        f"host: {n}" for n in (PT.STEP, *PT.STEP_PHASES, "bench.step",
                               "bench.collect", "no span")}
