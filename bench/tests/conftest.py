"""The benchmark's own tests: ``python -m pytest bench/tests -q``, on CPU.

They sit outside the repository's ``testpaths``. Each run of the harness
here skips the look for a chip and uses a small cut of a configuration.
"""
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_cell(cell_name: str, *, layers: int = 4, size: int = 32,
               slots: int = 4, limit: float = 1e-5):
    """A cell of BENCHMARK.json with its configuration cut to ``layers``
    layers at ``size`` px and a small traffic mix."""
    import spec
    full = spec.load_cell(cell_name)
    cfg = dict(full.config)
    cfg["layers"] = cfg["layers"][:layers]
    cfg["input_size"] = size
    cfg["check_answers"] = 8
    cfg["limits"] = {"max_rel_err": limit}
    traffic = {"loop": "closed", "clients": slots, "slots": slots,
               "image_pool": 4}
    return spec.Cell(cell_name, full.chips, cfg, traffic, full.end_to_end,
                     full.per_layer)


def run_small(cell, seed: int = 2 ** 33 + 7, seconds: float = 1.0, **kw):
    import harness
    return harness.run(cell, seed, seconds, False, t_start=time.monotonic(),
                       require_chip=False, **kw)
