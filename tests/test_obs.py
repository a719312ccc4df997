"""The program's span recorder (``repro.obs``): off by default, nesting,
its bounded store, JAX's compile events, the serving step's phases, and
the forward's per-layer name scopes."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.serve.vision import VirtualClock, VisionServer
from repro.vision import ImageRequest, build_vision_model, compile_forward

SERVE_PHASES = ["serve.admit", "serve.h2d", "serve.dispatch", "serve.wait",
                "serve.d2h", "serve.record"]


@pytest.fixture
def recording():
    obs.enable()
    try:
        yield
    finally:
        obs.disable()


def _by_seq(snap):
    return {s["seq"]: s for s in snap["spans"]}


def test_disabled_records_nothing_and_shares_one_noop():
    obs.disable()
    before = obs.snapshot()
    a, b = obs.span("a"), obs.span("b", bucket=3)
    assert a is b
    with a:
        with b:
            pass
    assert obs.snapshot() == before


def test_nested_spans_keep_parents_and_start_order(recording):
    with obs.span("outer", k=1):
        with obs.span("first"):
            pass
        with obs.span("second"):
            with obs.span("inner"):
                pass
    with obs.span("after"):
        pass
    snap = obs.snapshot()
    names = [s["name"] for s in snap["spans"]]
    assert names == ["outer", "first", "second", "inner", "after"]
    by = {s["name"]: s for s in snap["spans"]}
    assert by["outer"]["parent"] == by["after"]["parent"] == -1
    assert by["first"]["parent"] == by["second"]["parent"] \
        == by["outer"]["seq"]
    assert by["inner"]["parent"] == by["second"]["seq"]
    assert by["outer"]["attrs"] == {"k": 1}
    for s in snap["spans"]:
        assert s["start_ns"] <= s["end_ns"]
        if s["parent"] >= 0:
            p = _by_seq(snap)[s["parent"]]
            assert p["start_ns"] <= s["start_ns"] <= s["end_ns"] \
                <= p["end_ns"]


def test_store_is_capped(monkeypatch):
    monkeypatch.setattr(obs, "MAX_SPANS", 8)
    obs.enable()
    try:
        for i in range(20):
            with obs.span(f"s{i}"):
                pass
    finally:
        obs.disable()
    names = [s["name"] for s in obs.snapshot()["spans"]]
    assert names == [f"s{i}" for i in range(12, 20)]
    obs.enable()             # a fresh recorder starts empty
    obs.disable()
    assert obs.snapshot()["spans"] == []


def test_plane_has_the_harness_form(recording):
    with obs.span("a"):
        with obs.span("b"):
            pass
    snap = obs.snapshot()
    origin = snap["spans"][0]["start_ns"] - 100
    plane = obs.plane(origin)
    assert plane["name"] == "/host:program"
    events = plane["lines"][0]["events"]
    assert [e[0] for e in events] == ["a", "b"]
    assert events[0][1] == 100.0
    assert all(len(e) == 3 and e[2] >= 0 for e in events)


def test_compile_events_are_spans(recording):
    f = jax.jit(lambda x: jnp.cos(x) * 3.0 + x.sum())
    with obs.span("outer"):
        f(jnp.arange(7.0)).block_until_ready()
    snap = obs.snapshot()
    outer = next(s for s in snap["spans"] if s["name"] == "outer")
    jx = [s for s in snap["spans"] if s["name"] in ("jax.trace",
                                                      "jax.compile")]
    assert jx and all(s["parent"] == outer["seq"] for s in jx)
    assert any(s["attrs"]["fun_name"] == "<lambda>" for s in jx)


def test_cache_load_is_a_child_of_its_compile(recording):
    mon = jax.monitoring
    t0 = 1_000.0            # seconds on the wall clock, as JAX passes them
    mon.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    load = next(s for s in obs.snapshot()["spans"]
                if s["name"] == "jax.cache_load")
    start = load["start_ns"] / 1e9 - 0.1
    mon.record_event_time_span(
        "/jax/core/compile/backend_compile_duration", start, start + 1.0,
        fun_name="f")
    mon.record_event_time_span(
        "/jax/core/compile/backend_compile_duration", t0, t0 + 1.0,
        fun_name="g")
    snap = obs.snapshot()
    by = {(s["name"], s["attrs"].get("fun_name")): s for s in snap["spans"]}
    assert by[("jax.cache_load", None)]["parent"] \
        == by[("jax.compile", "f")]["seq"]
    assert by[("jax.compile", "g")]["parent"] == -1


def _serve(model, recorder):
    rng = np.random.default_rng(3)
    if recorder:
        obs.enable()
    try:
        srv = VisionServer(model, num_slots=2, buckets=(8, 16),
                           clock=VirtualClock(), step_cost_s=0.1,
                           executor="pallas", interpret=True)
        srv.warmup()
        for rid, size in enumerate([8, 16, 8, 16, 8]):
            img = np.abs(rng.normal(size=(size, size, 3))).astype(
                np.float32)
            srv.submit(ImageRequest(rid=rid, image=img,
                                    arrival_s=0.0 if rid < 3 else 5.0))
        while srv.step():
            pass
    finally:
        obs.disable()
    return srv.produced, obs.snapshot()


def test_server_step_phases_in_order_and_answers_unchanged():
    model = build_vision_model("VGGNet", num_layers=2, seed=0)
    off, _ = _serve(model, recorder=False)
    on, snap = _serve(model, recorder=True)
    assert off.keys() == on.keys()
    for rid in off:
        np.testing.assert_array_equal(on[rid], off[rid])
    kids = {}
    for s in snap["spans"]:
        kids.setdefault(s["parent"], []).append(s)
    steps = [s for s in snap["spans"] if s["name"] == "serve.step"]
    assert len(steps) == 4       # two batches at t=0, two after the idle
    assert sum(st["attrs"]["images"] for st in steps) == 5
    for st in steps:
        phases = kids[st["seq"]]
        assert [c["name"] for c in phases] == SERVE_PHASES
        assert st["start_ns"] == phases[0]["start_ns"]
        for a, b in zip(phases, phases[1:]):
            assert a["end_ns"] <= b["start_ns"]
        assert phases[-1]["end_ns"] <= st["end_ns"]
    warm = [s for s in snap["spans"] if s["name"] == "serve.warmup"]
    assert sorted(s["attrs"]["bucket"] for s in warm) == [8, 16]
    assert [s["name"] for s in snap["spans"]].count("serve.verify") == 1


def test_packing_is_a_span(recording):
    build_vision_model("VGGNet", num_layers=2, seed=0)
    names = [s["name"] for s in obs.snapshot()["spans"]]
    assert names.count("setup.pack") == 1


def test_forward_carries_layer_scopes_in_its_metadata():
    model = build_vision_model("VGGNet", num_layers=3, seed=0)
    assert model.layers[1].pool_after is not None
    fwd = compile_forward(model, executor="pallas", im2col="patches",
                          interpret=True)
    text = fwd.lower(jnp.zeros((2, 16, 16, 3), jnp.float32)).compile() \
        .as_text()
    assert text.startswith("HloModule jit_vision_forward")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for layer in ("layer00", "layer01", "layer02"):
        for part in ("im2col", "walker"):
            assert any(n.startswith(f"jit(vision_forward)/{layer}/{part}/")
                       for n in names), (layer, part)
    assert any(n.startswith("jit(vision_forward)/layer01/pool/")
               for n in names)
