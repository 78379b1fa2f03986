"""The benchmark's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They run the cells at tiny sizes through ``run.run_cell`` with the chip
check left out (``tiny_cell``)."""
from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

TINY_GRAPH = {"assumed": {"seq": 256, "trace_batch": 1, "microbatches": 1}}
TINY_STAGE2 = {"scope": "unit", "hierarchy": None, "batch": 16,
               "chunk_size": 8, "grad_chunk_size": 4, "trace_dispatches": 2}
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


@pytest.fixture(scope="session")
def run_mod():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import run
    return run


@pytest.fixture
def tiny_cell(run_mod, monkeypatch):
    """run_cell(cell, seed, trace) at tiny sizes on the CPU devices."""
    import jax
    orig = run_mod.load_json

    def load_json(path):
        d = orig(path)
        p = pathlib.Path(path)
        if p.parent.name == "configs":
            d = {**d, **TINY_GRAPH}
        elif p.parent.name == "traffic":
            d = {**d, **TINY_STAGE2}
        return d

    monkeypatch.setattr(run_mod, "load_json", load_json)

    def go(cell, seed=2**31 + 5, trace=False, seconds=1.0):
        dev = {"platform": "cpu", "kind": "cpu",
               "count": len(jax.devices())}
        return run_mod.run_cell(cell, seed, seconds, trace, dev, PEAKS)

    return go
