"""The main path compiles for a TPU v5e chip that is described, not attached.

The chip's compiler is installed with jax, so the Pallas kernels at real
widths and the whole fused Stage-II step are compiled here for one chip
of a described ``v5e:2x2`` topology.  Each compile must hold a
``tpu_custom_call``: the kernels reached Mosaic instead of the Pallas
interpreter.  Nothing runs, so this says nothing about results or times.

The topology is described inside the fixture, never at import: only one
process may load the TPU library, and the test runner's workers all
import this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compilation
    cache off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        with pytest.MonkeyPatch.context() as mp:
            # or the TPU library writes its logs under /tmp
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR",
                                                    "disabled"))
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_wc_step_compiles(one_chip):
    from repro.kernels.wc_oracle.ops import wc_step
    B, nd, K = 1024, 8, 32
    R = nd + nd * nd
    hlo = _compiled_text(
        lambda run, rows, ridx: wc_step(run, rows, ridx, interpret=False),
        _spec((B, R, 6), jnp.float32, one_chip),
        _spec((B, K, 6), jnp.float32, one_chip),
        _spec((B, K), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def test_segment_sum_mp_compiles(one_chip):
    from repro.kernels.gnn_mp.ops import segment_sum_mp
    m, n, d = 6000, 4096, 64
    hlo = _compiled_text(
        lambda msg, dst: segment_sum_mp(msg, dst, n=n, interpret=False),
        _spec((m, d), jnp.float32, one_chip),
        _spec((m,), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def test_fused_stage2_step_compiles(one_chip, monkeypatch):
    """model:olmo_1b, batch 256, both Pallas backends: the step the
    trainer dispatches on the chip, interpret mode resolved as on a TPU."""
    from repro.core.devices import get_device_model
    from repro.core.sim_jax import SimGraph
    from repro.core.train_fused import (FusedStage2Config, RewardStats,
                                        build_fused_stage2)
    from repro.core.training import DopplerTrainer
    from repro.graphs.workloads import get_workload

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    g, dev = get_workload("model:olmo_1b"), get_device_model("tpu_v5e_2x2")
    tr = DopplerTrainer(g, dev, encoder_backend="pallas",
                        oracle_backend="pallas")
    cfg = FusedStage2Config(batch_size=256, updates=1,
                            encoder_backend="pallas",
                            oracle_backend="pallas")
    step = build_fused_stage2(cfg, tr.gd, SimGraph.build(g, dev),
                              tr.lr_sched, tr.eps_sched)
    args = jax.tree_util.tree_map(
        lambda x: _spec(x.shape, x.dtype, one_chip),
        (tr.params, tr.opt_state, RewardStats.make(), tr.key,
         jnp.int32(0)))
    hlo = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _tiled_arrays(hlo: str):
    """Each array type in compiled TPU text, as ``(text, dims, tiled
    size / logical size)``: the layout's first tile pads the most-minor
    physical dims up to its sizes."""
    for m in re.finditer(r"\b[a-z]+\d*\[([\d,]*)\]\{([^}]*)\}", hlo):
        dims = [int(d) for d in m.group(1).split(",") if d]
        order, _, tiling = m.group(2).partition(":")
        phys = [dims[int(i)] for i in reversed(order.split(",")) if i]
        tile = re.match(r"T\(([\d,]+)\)", tiling)
        tile = [int(t) for t in tile.group(1).split(",")] if tile else []
        phys = [1] * (len(tile) - len(phys)) + phys
        for k, t in enumerate(tile, start=len(phys) - len(tile)):
            phys[k] = -(-phys[k] // t) * t
        yield m.group(0), dims, math.prod(phys) / max(1, math.prod(dims))


def test_oracle_queue_table_dense_on_chip(one_chip):
    """model:olmo_1b, batch 128, Pallas oracle: the trip loop's per-task
    queue table (N tasks, or B*N flattened) is stored one array per
    column, so no array over it is tiled to more than twice its bytes (a
    3-wide column axis in the lanes pads to 128, about 43x)."""
    from repro.core.devices import get_device_model
    from repro.core.sim_jax import SimGraph, _makespan_fifo_batch_pallas
    from repro.graphs.workloads import get_workload

    g, dev = get_workload("model:olmo_1b"), get_device_model("tpu_v5e_2x2")
    sg = SimGraph.build(g, dev)
    B, N = 128, sg.n + sg.esrc.shape[0]
    hlo = _compiled_text(
        lambda a: _makespan_fifo_batch_pallas(sg, a, interpret=False),
        _spec((B, g.n), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo
    over_n = [(t, r) for t, dims, r in _tiled_arrays(hlo)
              if N in dims or B * N in dims]
    assert over_n
    assert not [(t, r) for t, r in over_n if r > 2], over_n


def test_oracle_scatters_flat_on_chip(one_chip):
    """model:olmo_1b, batch 128, Pallas oracle: the trip loop writes its
    queues and indegrees as 1-D scatters over the flat episode-by-slot
    index, so no compiled scatter writes a rank-2 (128, X) array (a
    vmapped 2-D scatter costs about twelve times as much per update)."""
    from repro.core.devices import get_device_model
    from repro.core.sim_jax import SimGraph, _makespan_fifo_batch_pallas
    from repro.graphs.workloads import get_workload

    g, dev = get_workload("model:olmo_1b"), get_device_model("tpu_v5e_2x2")
    sg = SimGraph.build(g, dev)
    B = 128
    hlo = _compiled_text(
        lambda a: _makespan_fifo_batch_pallas(sg, a, interpret=False),
        _spec((B, g.n), jnp.int32, one_chip))
    scattered = [[int(d) for d in dims.split(",") if d]
                 for line in hlo.splitlines() if " scatter(" in line
                 for dims in re.findall(r"\[([\d,]*)\]",
                                        line.split(" scatter(")[0])]
    assert [B * g.n] in scattered
    assert not [d for d in scattered if len(d) == 2 and d[0] == B], scattered
