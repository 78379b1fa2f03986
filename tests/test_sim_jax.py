"""JAX WC oracle (sim_jax.py): equivalence contract with the serial engine.

The contract under test: for the noise-free 'fifo' strategy the
device-resident oracle makes the same scheduling decisions as
``WCSimulator.run`` — same task system, same FIFO queue order, same
work-conserving start passes, same completion order — evaluating costs in
float32, so makespans match the float64 serial engine to float tolerance
(not bit-for-bit; docs/SIMULATOR.md).  Coverage spans the synthetic
suite, the real-model zoo, and the heterogeneous fleets.
"""
import numpy as np
import pytest

from conftest import make_chain, make_diamond, random_dag
from repro.core.devices import (HETERO_FLEETS, get_device_model, p100_box,
                                tpu_v5e_slice, uniform_box, v100_two_groups)
from repro.core.sim_jax import JaxWCEngine, SimGraph, makespan_fifo_batch
from repro.core.simulator import WCSimulator
from repro.graphs.workloads import (chainmm, ffnn, llama_layer,
                                    synthetic_layered)

RTOL = 2e-4
DEVICE_MODELS = [uniform_box(1), uniform_box(4), p100_box(),
                 v100_two_groups(), tpu_v5e_slice(2, 2)]


def assert_parity(graph, dev, n_assign=4, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.integers(0, dev.n, (n_assign, graph.n))
    sim = WCSimulator(graph, dev, choose="fifo", noise_sigma=0.0)
    ref = np.array([sim.run(a).makespan for a in A])
    got = JaxWCEngine(graph, dev).run_batch(A)
    np.testing.assert_allclose(got, ref, rtol=RTOL)


# ----------------------------------------------------------- structured
def test_structured_graphs_all_fleets():
    for i, dev in enumerate(DEVICE_MODELS):
        assert_parity(make_diamond(), dev, seed=i)
        assert_parity(make_chain(12), dev, seed=i)


def test_random_dags():
    rng = np.random.default_rng(42)
    for k in range(8):
        g = random_dag(rng, int(rng.integers(8, 48)))
        dev = DEVICE_MODELS[int(rng.integers(len(DEVICE_MODELS)))]
        assert_parity(g, dev, seed=100 + k)


# ------------------------------------------------------ paper workloads
def test_synthetic_suite():
    dev = p100_box()
    assert_parity(chainmm(), dev)
    assert_parity(ffnn(), dev)
    assert_parity(llama_layer(), dev, n_assign=3)
    assert_parity(synthetic_layered(16, 8), dev)


@pytest.mark.parametrize("fleet", HETERO_FLEETS)
def test_zoo_graphs_on_hetero_fleets(fleet):
    """Real-model layer graphs x heterogeneous fleets (per-device rates,
    asymmetric links) keep makespan parity."""
    from repro.graphs.workloads import get_workload
    dev = get_device_model(fleet)
    for arch in ("gemma_2b", "granite_moe_3b_a800m"):
        g = get_workload(f"model:{arch}", seq=64)
        assert_parity(g, dev, n_assign=3, seed=3)


# -------------------------------------------------------------- details
def test_exec_time_scalar_matches_run(diamond, dev4):
    eng = JaxWCEngine(diamond, dev4)
    sim = WCSimulator(diamond, dev4)
    a = np.arange(diamond.n) % 4
    assert eng.exec_time(a) == pytest.approx(sim.run(a).makespan,
                                             rel=RTOL)


def test_batch_is_one_dispatch_consistent(diamond, dev4):
    """vmapped batch == per-assignment calls."""
    rng = np.random.default_rng(1)
    A = rng.integers(0, 4, (5, diamond.n))
    eng = JaxWCEngine(diamond, dev4)
    batch = eng.run_batch(A)
    single = np.array([eng.exec_time(a) for a in A])
    np.testing.assert_allclose(batch, single, rtol=1e-6)


def test_deadlock_flag():
    """Corrupted indegrees must surface as ok=False -> RuntimeError, not
    hang (the scan is fixed-trip)."""
    import jax.numpy as jnp
    g = make_chain(4)
    dev = uniform_box(2)
    eng = JaxWCEngine(g, dev)
    sg = eng.sim_graph
    bad = SimGraph(
        is_input=sg.is_input,
        need0=sg.need0.at[1].set(99),      # vertex 1 waits forever
        esrc=sg.esrc, edst=sg.edst, edge_pos=sg.edge_pos,
        edge_valid=sg.edge_valid, out_row=sg.out_row,
        exec_cost=sg.exec_cost, link_lat=sg.link_lat,
        link_bw=sg.link_bw, out_bytes=sg.out_bytes,
        n=sg.n, nd=sg.nd, m=sg.m, C=sg.C, n_compute=sg.n_compute,
        n_trips=sg.n_trips, seqw=sg.seqw, koff=sg.koff)
    ms, ok = makespan_fifo_batch(bad, jnp.zeros((1, g.n), jnp.int32))
    assert not bool(np.asarray(ok)[0])


def test_simgraph_key_capacity_guard():
    """Graphs whose queue keys would lose f32 exactness must refuse."""
    class FakeGraph:
        pass
    # build() raises before any jax work when 2*koff >= 2^24; emulate by
    # checking the documented bound on a real small graph
    sg = SimGraph.build(make_chain(6), uniform_box(2))
    assert 2 * sg.koff < 2 ** 24


# --------------------------------------------------------------- layout
def _subjaxprs(eqn):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for p in eqn.params.values():
        for sub in p if isinstance(p, (tuple, list)) else (p,):
            if isinstance(sub, ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, Jaxpr):
                yield sub


def _trip_loops(jaxpr):
    """The outermost ``while`` / ``scan`` equations."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "scan"):
            found.append(eqn)
        else:
            for sub in _subjaxprs(eqn):
                found += _trip_loops(sub)
    return found


def _trip_loop_carries(jaxpr):
    """Avals carried by the outermost ``while`` / ``scan`` loops."""
    return [[v.aval for v in (eqn.outvars if eqn.primitive.name == "while"
                              else eqn.outvars[:eqn.params["num_carry"]])]
            for eqn in _trip_loops(jaxpr)]


def _scatter_operands(jaxpr):
    """Operand shapes of every scatter in ``jaxpr``, outside the Pallas
    kernel's wrapper (which pads the running table to its own layout)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.append(eqn.invars[0].aval.shape)
        if eqn.params.get("name") != "_wc_step":
            for sub in _subjaxprs(eqn):
                found += _scatter_operands(sub)
    return found


def _oracle_jaxpr(path, sg, n, B=5):
    import jax
    import jax.numpy as jnp

    from repro.core.sim_jax import (_makespan_fifo_batch_pallas,
                                    _makespan_fifo_batch_xla, makespan_fifo)
    A = jnp.zeros((B, n), jnp.int32)
    fn, arg = {
        "single": (lambda a: makespan_fifo(sg, a), A[0]),
        "xla": (lambda a: _makespan_fifo_batch_xla(sg, a), A),
        "pallas": (lambda a: _makespan_fifo_batch_pallas(sg, a, True), A),
    }[path]
    return jax.make_jaxpr(fn)(arg).jaxpr


@pytest.mark.parametrize("path", ["single", "xla", "pallas"])
def test_trip_loop_carries_no_narrow_table(path):
    """The trip loop keeps its queue tables one array per column: no
    carried array has a 2- or 3-wide minor axis, which a TPU would tile to
    128 lanes (the (R, 6) running table is the kernel's own layout)."""
    g, dev = make_diamond(8), uniform_box(4)
    sg = SimGraph.build(g, dev)
    loops = _trip_loop_carries(_oracle_jaxpr(path, sg, g.n))
    assert len(loops) == 1
    shapes = [a.shape for a in loops[0]]
    run = (sg.nd + sg.nd ** 2, 6)
    assert (run if path == "single" else (5, *run)) in shapes
    assert not [s for s in shapes if s and s[-1] in (2, 3)], shapes


@pytest.mark.parametrize("path", ["single", "xla", "pallas"])
def test_trip_loop_scatters_are_flat(path):
    """Every queue and indegree write in the trip loop is one 1-D scatter
    over a flat, episode-major array for the whole batch: the start
    pass's head and tail, and the readiness's key, ready time, next
    pointer, head, tail and indegree.  A vmapped scatter into a (B, X)
    array costs about twelve times as much per update on a TPU.  The
    only other scatters are the running table's (..., R, 6) writes."""
    g, dev = make_diamond(8), uniform_box(4)
    sg = SimGraph.build(g, dev)
    (loop,) = _trip_loops(_oracle_jaxpr(path, sg, g.n))
    shapes = [s for sub in _subjaxprs(loop) for s in _scatter_operands(sub)]
    R, N = sg.nd + sg.nd ** 2, sg.n + sg.esrc.shape[0]
    B = 1 if path == "single" else 5
    flat = [s for s in shapes if len(s) == 1]
    assert sorted(flat) == sorted([(B * R,)] * 4 + [(B * N,)] * 3
                                  + [(B * sg.n,)]), shapes
    assert all(s[-2:] == (R, 6) for s in shapes if len(s) > 1), shapes
