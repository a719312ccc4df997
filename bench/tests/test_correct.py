"""The check that decides ``correct``: a sound run passes, its control and
each fault that a serving cell can have fail.

Every run here skips the harness's look for a chip and drives the rest of
a run (weights, packing, ``VisionServer``, the window, the sample, the
reference) at a small cut of the configuration on the CPU. The faults are
planted in the compiled forward that the server's window drives.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import check
import spec
from conftest import ROOT, run_small, small_cell

CELLS = ["vgg16_224_closed8", "alexnet_227_closed8"]


def _limit(cell_name):
    return spec.load_cell(cell_name).config["limits"]["max_rel_err"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name):
    size = 67 if cell_name.startswith("alexnet") else 32
    cell = small_cell(cell_name, layers=3, size=size,
                      limit=_limit(cell_name))
    out = run_small(cell, control=True)
    c = out["checks"]
    assert out["correct"], c
    assert c["max_rel_err"]["value"] <= c["max_rel_err"]["limit"]
    assert c["answers_checked"]["value"] == 8
    assert out["failed"] == 0 and out["attempted"] > 8
    # the control: the reference in three bf16 passes in the program's
    # place reads above the same limit, and the predicate that decides
    # ``correct`` refuses it
    assert out["control_max_rel_err"] > c["max_rel_err"]["limit"]
    assert out["control_correct"] is False
    assert list(out)[-1] == "checks"
    for m in cell.end_to_end:
        assert m.name in out["metrics"]


def _broken_forward(monkeypatch, fault):
    """Plant ``fault`` under the server: wrap what compile_forward returns."""
    import jax.numpy as jnp
    from repro.vision import model as VM
    real = VM.compile_forward

    def compile_forward(*a, **kw):
        fwd = real(*a, **kw)
        prev = []

        def broken(x):
            y = fwd(x)
            if fault == "answer_altered":      # one value of every answer
                y = y.at[:, 0, 0, 0].add(1e-3 * jnp.abs(y).max() + 1e-3)
            elif fault == "stale_answers":     # the last batch's answers
                out = prev[0] if prev else y
                prev[:] = [y]
                y = out
            elif fault == "half_batch_dropped":
                y = y.at[: y.shape[0] // 2].set(0.0)
            return y
        return broken
    monkeypatch.setattr(VM, "compile_forward", compile_forward)


@pytest.mark.parametrize("fault", ["answer_altered", "stale_answers",
                                   "half_batch_dropped"])
def test_faults_are_not_correct(monkeypatch, fault):
    cell = small_cell("vgg16_224_closed8", layers=3,
                      limit=_limit("vgg16_224_closed8"))
    _broken_forward(monkeypatch, fault)
    out = run_small(cell)
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_rel_err"]["value"] > \
        out["checks"]["max_rel_err"]["limit"]


MESH_RUN = r"""
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import jax.numpy as jnp
from conftest import run_small, small_cell
from repro.vision import model as VM
fault = sys.argv[1]
real = VM.compile_forward
def compile_forward(*a, **kw):
    fwd = real(*a, **kw)
    def broken(x):
        y = fwd(x)
        if fault == "shard_dropped":        # device 3's images never return
            y = y.at[3 * y.shape[0] // 4:].set(0.0)
        return y
    return broken
VM.compile_forward = compile_forward
cell = small_cell("vgg16_224_mesh4_closed32", layers=3, slots=8,
                  limit={limit!r})
print(json.dumps(run_small(cell)))
"""


@pytest.mark.parametrize("fault", ["none", "shard_dropped"])
def test_mesh_cell_catches_a_lost_shard(fault):
    """The 4-chip cell on 4 virtual CPU devices (a fresh process, since the
    device count is fixed when JAX starts)."""
    code = MESH_RUN.format(bench=spec.BENCH, src=os.path.join(ROOT, "src"),
                           tests=os.path.dirname(__file__),
                           limit=_limit("vgg16_224_mesh4_closed32"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code, fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] == (fault == "none"), out["checks"]
    assert set(out["metrics"]) == {"img_per_s.mesh4", "setup_s"}


def test_max_rel_err_reads_each_image_against_its_own_scale():
    ref = [np.array([[1.0, 2.0]]), np.array([[100.0, 0.0]])]
    assert check.max_rel_err(ref, ref) == 0.0
    got = [ref[0] + 0.02, ref[1]]
    assert check.max_rel_err(got, ref) == pytest.approx(0.01)
    assert check.max_rel_err([ref[0] * np.nan, ref[1]], ref) == float("inf")
    assert check.max_rel_err([ref[0][:, :1], ref[1]], ref) == float("inf")


def test_reservoir_is_uniform_and_seeded():
    counts = np.zeros(100)
    for s in range(400):
        r = check.Reservoir(10, np.random.default_rng(s))
        for i in range(100):
            j = r.slot()
            if j is not None:
                r.items[j] = i
        counts[r.items] += 1
    assert counts.sum() == 4000
    assert counts[:50].sum() == pytest.approx(2000, rel=0.1)


def test_no_chip_no_result():
    """On this CPU the entry point refuses, exits 2 and prints nothing."""
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         "vgg16_224_closed8", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout == "", p.stderr[-2000:]


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and bench/ (no program) exits
    non-zero and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16_224_closed8",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_judge_is_the_one_predicate():
    import harness
    assert harness.judge(1e-6, 6e-6, 32, 32, 500, 0)
    assert not harness.judge(1.6e-5, 6e-6, 32, 32, 500, 0)   # the control
    assert not harness.judge(1e-6, None, 32, 32, 500, 0)     # no limit
    assert not harness.judge(1e-6, 6e-6, 31, 32, 500, 0)     # short sample
    assert not harness.judge(1e-6, 6e-6, 0, 32, 0, 0)        # nothing served
    assert not harness.judge(1e-6, 6e-6, 32, 32, 500, 1)     # one unanswered
