"""Layer tables that form a graph: the yardstick and the reference follow
named inputs, residual adds and padded pools; chain tables read exactly
as the chain-only readers read them; the harness takes a new graph cell
from data files alone, serves it through the program's graph builder
where there is one, and the chain builder refuses what it cannot
serve."""
import functools
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spec
import weights as W
import work
from conftest import BENCH, ROOT

TOY = os.path.join(os.path.dirname(__file__), "data", "toy_resnet.json")
HIGHEST = jax.lax.Precision.HIGHEST


def _toy():
    return spec.load_json(TOY)


def _config(name):
    return spec.load_json(os.path.join(BENCH, "configs", name + ".json"))


# -- the chain-only readers that the table readers replaced, kept as the
# -- reference for what a chain table must still read as
def _chain_work(config, size, nonzero):
    out, h = [], size
    for layer, nz in zip(config["layers"], nonzero):
        k, s, pad = layer["kernel"], layer["stride"], layer["padding"]
        oh = -(-h // s) if pad == "SAME" else (h - k) // s + 1
        out.append((h, oh, layer["in_channels"], layer["out_channels"], k,
                    int(nz)))
        h = oh
        if layer["pool_after"]:
            win, s = layer["pool_after"]
            h = (h - win) // s + 1
    return out


@functools.partial(jax.jit, static_argnames=("geo",))
def _chain_forward_highest(weights, x, *, geo):
    for w, (stride, padding, pool) in zip(weights, geo):
        x = jnp.maximum(jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST),
            0.0)
        if pool is not None:
            win, s = pool
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, win, win, 1), (1, s, s, 1), "VALID")
    return x


# -- the toy residual net -----------------------------------------------------
def test_layer_graph_resolves_the_toy_net():
    g = spec.layer_graph(_toy())
    assert [l.input for l in g] == [spec.IMAGE, 0, 0, 2, 3, 4, 5, 6]
    assert [l.add for l in g] == [None] * 4 + [1, None, None, 4]
    assert [l.relu for l in g] == [True, False] + [True] * 6
    assert g[0].padding == ((3, 3), (3, 3))
    assert g[0].pool == (3, 2, ((1, 1), (1, 1)))
    assert g[3].pool is None and g[5].padding == "VALID"


def test_table_work_follows_the_graph():
    config = _toy()
    nz = [10 * (i + 1) for i in range(len(config["layers"]))]
    lw = work.table_work(config, 32, nz)
    # stem 32 -> 16, its padded 3x3/2 pool -> 8; the projection and the
    # block's first 1x1 read the pooled stem; the strided 3x3 halves it
    assert [(l.in_hw, l.out_hw) for l in lw] == [
        (32, 16), (8, 4), (8, 8), (8, 4), (4, 4), (4, 4), (4, 4), (4, 4)]
    assert [l.add for l in lw] == [False] * 4 + [True, False, False, True]
    block1 = lw[4]
    assert block1.bytes(3) == 4 * (3 * (4 * 4 * 16 + 2 * 4 * 4 * 32) + 50)
    assert block1.flops(3) == 2 * 3 * 16 * 50      # no FLOPs for the add
    plain = work.LayerWork(4, 4, 16, 32, 1, 50)
    assert block1.bytes(3) - plain.bytes(3) == 4 * 3 * 16 * 32


def _hand_written_toy(ws, x):
    """The toy net as a ResNet writes it, with no layer table."""
    def conv(x, w, s, p):
        return jax.lax.conv_general_dilated(
            x, w, (s, s), p, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST)
    relu = jax.nn.relu
    stem = relu(conv(x, ws[0], 2, ((3, 3), (3, 3))))
    stem = jax.lax.reduce_window(stem, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 ((0, 0), (1, 1), (1, 1), (0, 0)))
    shortcut = conv(stem, ws[1], 2, "VALID")
    y = relu(conv(stem, ws[2], 1, "VALID"))
    y = relu(conv(y, ws[3], 2, ((1, 1), (1, 1))))
    block1 = relu(conv(y, ws[4], 1, "VALID") + shortcut)
    y = relu(conv(block1, ws[5], 1, "VALID"))
    y = relu(conv(y, ws[6], 1, ((1, 1), (1, 1))))
    return relu(conv(y, ws[7], 1, "VALID") + block1)


def test_reference_matches_a_hand_written_residual_net():
    config = _toy()
    ref = spec.reference(config)
    ws = [jnp.asarray(w) for w in W.make_weights(config, 2 ** 33 + 3)]
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(2, 32, 32, 3)).astype(np.float32))
    got = np.asarray(ref.forward(ws, x, geo=ref.geometry(config)))
    want = np.asarray(_hand_written_toy(ws, x))
    assert got.shape == (2, 4, 4, 32)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 *
                               np.abs(want).max())
    assert np.abs(want).max() > 0


@pytest.mark.parametrize("table, message", [
    ([{"input": "later"}], "input 'later' names no earlier entry"),
    ([{}, {"add": "image"}], "add 'image' names no earlier entry"),
    ([{"padding": [[1, 1], [0, 0]]}], "same non-negative pair"),
    ([{"pool_after": [3, 2, "SAME"]}], "padding 'SAME' is not one of"),
    ([{"inputs": "image"}], "unknown keys ['inputs']"),
    ([{"name": "a"}, {"name": "a", "in_channels": 8}], "name 'a' is taken"),
    ([{}, {"in_channels": 3}], "in_channels 3 but its input has 8"),
])
def test_layer_graph_refuses_a_malformed_table(table, message):
    base = {"kernel": 3, "in_channels": 3, "out_channels": 8, "stride": 1,
            "padding": "SAME", "pool_after": None}
    base8 = dict(base, in_channels=8)
    layers = [dict(base if i == 0 else base8, **e) for i, e in
              enumerate(table)]
    with pytest.raises(ValueError, match=message.replace("[", r"\[")
                       .replace("]", r"\]").replace("(", r"\(")):
        spec.layer_graph({"in_channels": 3, "layers": layers})


def test_an_add_of_another_size_is_refused():
    config = _toy()
    config["layers"][1]["stride"] = 1          # an 8 px projection
    with pytest.raises(ValueError, match="add reads a 8 px map onto 4 px"):
        work.table_work(config, 32, [1] * 8)


# -- chain tables read as before --------------------------------------------
@pytest.mark.parametrize("name, size, small", [("vgg16_224", 224, 32),
                                               ("alexnet_227", 227, 67)])
def test_chain_tables_read_as_before(name, size, small):
    config = _config(name)
    filters = W.make_weights(config, 2 ** 31 + 11)
    nz = work.nonzero_counts(filters)
    new = work.table_work(config, size, nz)
    assert [(l.in_hw, l.out_hw, l.cin, l.cout, l.kernel, l.nonzero)
            for l in new] == _chain_work(config, size, nz)
    assert not any(l.add for l in new)
    # the reference gives bitwise the chain-only reference's answers
    ref = spec.reference(config)
    geo = tuple((l["stride"], l["padding"],
                 tuple(l["pool_after"]) if l["pool_after"] else None)
                for l in config["layers"])
    ws = [jnp.asarray(w) for w in filters]
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, small, small, 3)).astype(np.float32))
    got = np.asarray(ref.forward(ws, x, geo=ref.geometry(config)))
    want = np.asarray(_chain_forward_highest(ws, x, geo=geo))
    assert got.shape == want.shape and np.array_equal(got, want)


@pytest.mark.parametrize("name", ["vgg16_224", "alexnet_227"])
def test_chain_model_is_the_chain_construction(name):
    """The harness's builder gives the packed model that the chain-only
    construction gave: the same strides, paddings, pools, layouts and
    packed arrays."""
    import harness
    from repro.sparsity.conv import build_sparse_chain
    config = _config(name)
    filters = W.make_weights(config, 2 ** 31 + 13)
    got = harness.build_model(config, filters, 1)
    chain = build_sparse_chain(filters, density=1.0,
                               pattern=config["pattern"], mesh_devices=None)
    assert got.name == config["program_arch"]
    assert got.input_size == config["input_size"]
    assert len(got.layers) == len(chain) == len(config["layers"])
    for layer, conv, entry in zip(got.layers, chain, config["layers"]):
        assert layer.stride == (entry["stride"], entry["stride"])
        assert layer.padding == entry["padding"]
        assert layer.pool_after == (tuple(entry["pool_after"])
                                    if entry["pool_after"] else None)
        assert layer.conv.layout == conv.layout
        assert np.array_equal(layer.conv.w_dense, conv.w_dense)
        assert np.array_equal(layer.conv.perm, conv.perm)
        a, b = layer.conv.packed, conv.packed
        assert (a.shape, a.bk, a.bn) == (b.shape, b.bk, b.bn)
        assert np.array_equal(a.indices, b.indices)
        assert np.array_equal(a.vals, b.vals)


# -- the chain builder and the program's builder -----------------------------
@pytest.mark.parametrize("key, value, entry", [
    ("input", "stem", 2),
    ("add", "stem", 2),
    ("relu", False, 0),
    ("pool_after", [3, 2, [[1, 1], [1, 1]]], 0),
])
def test_chain_model_refuses_what_it_cannot_serve(key, value, entry):
    import harness
    base = {"kernel": 3, "in_channels": 16, "out_channels": 16, "stride": 1,
            "padding": "SAME", "pool_after": None}
    layers = [dict(base, in_channels=3, name="stem"), dict(base),
              dict(base)]
    layers[entry][key] = value
    config = {"in_channels": 3, "layers": layers, "pattern": "unstructured",
              "program_arch": "ResNet50", "input_size": 16,
              "filter_density": 1.0}
    with pytest.raises(ValueError, match=rf"entry {entry}\b.*'{key}'"):
        harness.chain_model(config, [], mesh_devices=None)


def test_build_model_takes_the_programs_table_builder(monkeypatch):
    import harness
    from repro.vision import model as program_model
    seen = []
    monkeypatch.setattr(program_model, "model_from_table",
                        lambda table, filters, *, mesh_devices=None:
                        seen.append((table, filters, mesh_devices)) or "m",
                        raising=False)
    assert harness.build_model({"t": 1}, ["f"], 4) == "m"
    assert harness.build_model({"t": 1}, ["f"], 1) == "m"
    assert seen == [({"t": 1}, ["f"], 4), ({"t": 1}, ["f"], None)]


# -- a new graph cell from data files alone ----------------------------------
def _toy_benchmark(tmp_path):
    """BENCHMARK.json with the toy configuration and a one-chip closed-loop
    cell of it appended, as a configuration PR would add them."""
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "toy_resnet", "source": "https://arxiv.org/abs/1512.03385",
        "file": "bench/configs/toy_resnet.json", "reduced": [],
        "why": "a residual graph small enough for the CPU"})
    bench["workloads"].append({
        "name": "toy_resnet_closed8", "config": "toy_resnet",
        "traffic": "closed8", "chips": 1, "why": "the toy graph"})
    one_chip = [m for m in bench["end_to_end"] + bench["per_layer"]
                if "vgg16_224_closed8" in m.get("workloads", [])]
    for m in one_chip:
        m["workloads"].append("toy_resnet_closed8")
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    shutil.copy(TOY, tmp_path / "bench" / "configs" / "toy_resnet.json")
    shutil.copytree(os.path.join(BENCH, "traffic"),
                    tmp_path / "bench" / "traffic")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return {m["name"] for m in one_chip}


def test_a_graph_cell_loads_from_data_files(tmp_path):
    names = _toy_benchmark(tmp_path)
    cell = spec.load_cell("toy_resnet_closed8", root=str(tmp_path))
    assert cell.chips == 1 and cell.config["name"] == "toy_resnet"
    assert cell.traffic["slots"] == 8
    got = {m.name for m in cell.end_to_end + cell.per_layer}
    assert got == names | {"setup_s"}
    assert {m.name for m in cell.end_to_end} == {"img_per_s", "setup_s"}


def test_the_harness_refuses_a_graph_the_program_cannot_serve(tmp_path,
                                                            monkeypatch):
    """Where the program has no graph builder, a graph cell stops at
    set-up with the chain builder's ValueError, before any answer is
    served."""
    from conftest import run_small
    from repro.vision import model as program_model
    monkeypatch.delattr(program_model, "model_from_table", raising=False)
    _toy_benchmark(tmp_path)
    cell = spec.load_cell("toy_resnet_closed8", root=str(tmp_path))
    with pytest.raises(ValueError, match=r"entry 0 \('stem'\).*'pool_after'"):
        run_small(cell)


def test_the_programs_graph_builder_serves_the_graph_cell(tmp_path):
    """Where the program builds graphs, the toy graph cell runs through
    its builder and the check holds it to the reference."""
    from conftest import run_small
    from repro.vision import model as program_model
    if not hasattr(program_model, "model_from_table"):
        pytest.skip("the program has no model_from_table")
    _toy_benchmark(tmp_path)
    cell = spec.load_cell("toy_resnet_closed8", root=str(tmp_path))
    out = run_small(cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["answers_checked"]["value"] == 8


def test_a_chain_with_explicit_pads_is_served_correctly():
    """Explicit conv pads and a stride-2 3x3 are chain keys: the program's
    chain serves them, and the check holds it to the reference."""
    from conftest import run_small
    full = spec.load_cell("vgg16_224_closed8")
    config = _toy()
    stem = dict(config["layers"][0], pool_after=[3, 2])
    del stem["name"]
    config["layers"] = [stem, dict(config["layers"][3], in_channels=16,
                                   out_channels=32)]
    cell = spec.Cell("toy_chain", 1, config,
                     {"loop": "closed", "clients": 4, "slots": 4,
                      "image_pool": 4}, full.end_to_end, full.per_layer)
    out = run_small(cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["answers_checked"]["value"] == 8
