"""Cross-engine conformance matrix.

One parametrized test runs the SAME (graph, fleet, assignment, seed)
through every reward engine and asserts the documented exactness tiers
(docs/SIMULATOR.md):

* ``SimRewardEngine(serial)`` vs ``SimRewardEngine(batched)`` —
  BIT-IDENTICAL, for every strategy and noise level;
* ``JaxOracleEngine`` vs the f64 serial engine — <= 1e-6 relative
  (f32 cost tables; noise-free 'fifo' scope);
* ``JaxOracleEngine(backend="pallas")`` vs ``backend="xla"`` —
  BIT-IDENTICAL (decision-exact: the kernel reproduces the oracle's f32
  scheduling decisions bit-for-bit), hence also <= 1e-6 vs serial;
* ``CallableEngine``-wrapped variants — exactly the wrapped engine's
  numbers (the adapter adds no arithmetic).

Engine drift now fails loudly instead of silently skewing Stage II.
"""
import numpy as np
import pytest

from conftest import make_chain, make_diamond, random_dag
from repro.core.devices import (HETERO_FLEETS, get_device_model,
                                mixed_generation_box, uniform_box)
from repro.core.engine import (CallableEngine, JaxOracleEngine,
                               SimRewardEngine)
from repro.core.simulator import WCSimulator

JAX_RTOL = 1e-6          # documented f32-oracle tier (observed ~1e-7)


def _graph(name):
    if name == "diamond":
        return make_diamond(8)
    if name == "chain":
        return make_chain(12)
    return random_dag(np.random.default_rng(5), 24)


GRAPHS = ("diamond", "chain", "rand24")
FLEETS = ("uniform4", "mixed_gen4", "two_pod_2x2")


def _fleet(name):
    if name == "uniform4":
        return uniform_box(4)
    return get_device_model(name)


@pytest.fixture(scope="module")
def matrix_case(request):
    g = _graph(request.param[0])
    dev = _fleet(request.param[1])
    A = np.stack([np.random.default_rng(7 + k).integers(0, dev.n, g.n)
                  for k in range(4)])
    return g, dev, A


@pytest.mark.parametrize(
    "matrix_case", [(gn, fn) for gn in GRAPHS for fn in FLEETS],
    indirect=True, ids=[f"{gn}-{fn}" for gn in GRAPHS for fn in FLEETS])
@pytest.mark.parametrize("choose,sigma", [("fifo", 0.0), ("fifo", 0.1),
                                          ("dfs", 0.0), ("random", 0.05)])
def test_engine_conformance_matrix(matrix_case, choose, sigma):
    g, dev, A = matrix_case
    episode = 13

    sim = WCSimulator(g, dev, choose=choose, noise_sigma=sigma)
    serial = SimRewardEngine(sim, sim_engine="serial")
    batched = SimRewardEngine(sim, sim_engine="batched")

    t_serial = serial.exec_times(A, episode)
    t_batched = batched.exec_times(A, episode)

    # tier 1: serial <-> batched, bit-identical (any strategy, any noise)
    np.testing.assert_array_equal(t_serial, t_batched)

    # tier 2: the engine seed convention — row k is the serial reference
    # run at seed episode*K + k
    K = A.shape[0]
    ref = np.array([sim.run(A[k], seed=episode * K + k).makespan
                    for k in range(K)])
    np.testing.assert_array_equal(t_serial, ref)

    # tier 3: CallableEngine wrapping adds no arithmetic
    wrapped = CallableEngine(
        lambda rows: batched.exec_times(rows, episode), batched=True,
        deterministic=batched.deterministic)
    np.testing.assert_array_equal(wrapped.exec_times(A, episode), t_batched)


@pytest.mark.parametrize(
    "matrix_case", [(gn, fn) for gn in GRAPHS for fn in FLEETS],
    indirect=True, ids=[f"{gn}-{fn}" for gn in GRAPHS for fn in FLEETS])
def test_jax_oracle_conformance(matrix_case):
    """The f32 oracle's tier: <= 1e-6 relative vs the f64 serial engine,
    on its documented scope (noise-free 'fifo')."""
    g, dev, A = matrix_case
    sim = WCSimulator(g, dev, choose="fifo", noise_sigma=0.0)
    serial = SimRewardEngine(sim, sim_engine="serial")
    oracle = JaxOracleEngine(g, dev)
    t_serial = serial.exec_times(A, 0)
    t_oracle = oracle.exec_times(A, 0)
    np.testing.assert_allclose(t_oracle, t_serial, rtol=JAX_RTOL)
    # deterministic engines: evaluate_repeats is one episode broadcast
    reps = oracle.evaluate_repeats(A[0], n_runs=4)
    assert (reps == reps[0]).all()


# --------------------------------------------------------- backend axis
BACKEND_GRAPHS = ("diamond", "rand24", "chainmm", "ffnn", "layered16x8",
                  "model:gemma_2b")
BACKEND_FLEETS = ("uniform4",) + HETERO_FLEETS     # every hetero entry


def _backend_graph(name):
    from repro.graphs.workloads import (chainmm, ffnn, get_workload,
                                        synthetic_layered)
    if name == "chainmm":
        return chainmm()
    if name == "ffnn":
        return ffnn()
    if name == "layered16x8":
        return synthetic_layered(16, 8)
    if name.startswith("model:"):
        return get_workload(name, seq=64)
    return _graph(name)


@pytest.mark.parametrize("fleet", BACKEND_FLEETS)
@pytest.mark.parametrize("graph", BACKEND_GRAPHS)
def test_oracle_backend_axis(graph, fleet):
    """Pallas oracle vs XLA oracle vs serial engine, across the synthetic
    suite, a zoo layer graph, and every HETERO_FLEETS entry.

    Exactness tier: the Pallas trip-step kernel reproduces the XLA
    oracle's f32 scheduling decisions exactly, so the two backends are
    BIT-IDENTICAL per assignment (decision-exact) and both sit inside the
    oracle's documented f32 band vs the f64 serial reference (~1e-4
    conservatively per docs/SIMULATOR.md; long chainmm-style graphs
    accumulate past 1e-6, e.g. 5.7e-6 on chainmm x straggler8)."""
    g = _backend_graph(graph)
    dev = _fleet(fleet)
    rng = np.random.default_rng(17)
    A = rng.integers(0, dev.n, (3, g.n))

    sim = WCSimulator(g, dev, choose="fifo", noise_sigma=0.0)
    t_serial = SimRewardEngine(sim, sim_engine="serial").exec_times(A, 0)
    xla = JaxOracleEngine(g, dev, backend="xla")
    pl = JaxOracleEngine(g, dev, backend="pallas")
    assert xla.name == "jax_oracle" and pl.name == "jax_oracle[pallas]"

    t_xla = xla.exec_times(A, 0)
    t_pl = pl.exec_times(A, 0)
    np.testing.assert_array_equal(t_pl, t_xla)
    np.testing.assert_allclose(t_pl, t_serial, rtol=1e-4)

    # engine seed convention: deterministic engines ignore the episode
    # seed entirely — row k of episode e is the serial run at seed
    # e*K + k only for stochastic engines; here every episode is equal
    np.testing.assert_array_equal(pl.exec_times(A, 99), t_pl)


def test_oracle_backend_validation():
    g, dev = make_diamond(4), uniform_box(2)
    with pytest.raises(ValueError, match="backend"):
        JaxOracleEngine(g, dev, backend="tpu")


# ----------------------------------------------- trip-trimmed batch loop
def test_trip_trimmed_batch_decision_exact():
    """The batched oracle's early-exit trip loop (stop when every episode
    has completed, instead of always paying the static n_trips + 1
    bound) is decision-exact: a batch mixing episodes with very
    different completion counts — an all-on-one-device assignment has no
    transfer tasks, random spread assignments have many — reproduces the
    per-episode single-scan makespans exactly, on both backends."""
    import jax.numpy as jnp

    from repro.core.sim_jax import (SimGraph, makespan_fifo,
                                    makespan_fifo_batch)

    g, dev = make_diamond(8), uniform_box(4)
    sg = SimGraph.build(g, dev)
    rng = np.random.default_rng(11)
    A = np.concatenate([np.zeros((1, g.n), np.int64),
                        rng.integers(0, dev.n, (5, g.n))])
    singles = np.asarray([float(makespan_fifo(sg, jnp.asarray(a))[0])
                          for a in A], np.float32)
    for backend in ("xla", "pallas"):
        ms, ok = makespan_fifo_batch(sg, jnp.asarray(A), backend=backend)
        assert np.asarray(ok).all()
        np.testing.assert_array_equal(np.asarray(ms), singles)


def _parallel_edge_graph():
    """Vertex 0 is a compute root that fans out to five consumers; two
    producers each reach one consumer over two parallel edges (``freeze``
    drops parallel edges, so they are put back after it, last in both the
    edge list and the producer's successor list)."""
    from repro.core.graph import DataflowGraph
    g = DataflowGraph("parallel")
    for k in range(11):
        g.add_vertex("input" if k == 1 else "matmul",
                     flops=(1 + k % 3) * 1e9, out_bytes=(1 + k % 4) * 1e6)
    for s, d in [(0, 2), (1, 2), (0, 3), (0, 4), (2, 4), (0, 5), (0, 6),
                 (3, 7), (5, 7), (4, 8), (6, 8), (7, 8), (1, 9), (8, 10),
                 (9, 10)]:
        g.add_edge(s, d)
    g.freeze()
    for s, d in [(0, 4), (3, 7)]:
        g.edges.append((s, d))
        g.succs[s].append(d)
        g.preds[d].append(s)
    return g


def test_flat_trip_writes_decision_exact_on_parallel_edges():
    """The batched trip loop writes every episode's queues and indegrees
    into flat episode-major arrays, deduplicated: one write per resource
    from repeated candidates, one indegree write per destination however
    many triggered edges reach it.  A graph whose parallel edges make an
    indegree fall by 2 in one trip, and whose root's five consumers on
    one device repeat that device among a trip's candidates, keeps both
    batch backends bit-equal to the per-episode single scan and inside
    the serial engine's f32 band.  Vertex 0 is a compute task, so a
    dropped write that aliased the next episode's slot 0 would show."""
    import jax.numpy as jnp

    from repro.core.sim_jax import (SimGraph, makespan_fifo,
                                    makespan_fifo_batch)

    g, dev = _parallel_edge_graph(), uniform_box(3)
    sg = SimGraph.build(g, dev)
    assert int(np.asarray(sg.need0)[4]) == 3 and g.m == 17
    rng = np.random.default_rng(23)
    A = np.concatenate([np.zeros((1, g.n), np.int64),
                        rng.integers(0, dev.n, (5, g.n))])
    singles = np.asarray([float(makespan_fifo(sg, jnp.asarray(a))[0])
                          for a in A], np.float32)
    sim = WCSimulator(g, dev, choose="fifo", noise_sigma=0.0)
    serial = np.array([sim.run(a).makespan for a in A])
    np.testing.assert_allclose(singles, serial, rtol=1e-4)
    for backend in ("xla", "pallas"):
        ms, ok = makespan_fifo_batch(sg, jnp.asarray(A), backend=backend)
        assert np.asarray(ok).all()
        np.testing.assert_array_equal(np.asarray(ms), singles)


def test_oracle_ok_flag_flags_starved_trip_loop():
    """Both batched backends and the single-episode scan must report
    ok=False (not a garbage makespan) when the trip budget is too small
    to drain the heap — the condition the fused trainer surfaces as a
    RuntimeError."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core.sim_jax import (SimGraph, makespan_fifo,
                                    makespan_fifo_batch)

    g, dev = make_diamond(4), uniform_box(2)
    starved = dataclasses.replace(SimGraph.build(g, dev), n_trips=1)
    A = np.random.default_rng(3).integers(0, dev.n, (3, g.n))
    for backend in ("xla", "pallas"):
        ms, ok = makespan_fifo_batch(starved, jnp.asarray(A),
                                     backend=backend)
        assert not np.asarray(ok).any()
    _, ok1 = makespan_fifo(starved, jnp.asarray(A[0]))
    assert not bool(ok1)


def test_encoder_backend_on_olmo_segment_graph():
    """The gnn_mp Pallas encoder matches the XLA encoder to <= 1e-5 on
    the full-model coarsening target: model:olmo_1b:full segment graphs
    (the graphs the hierarchical placer actually encodes)."""
    import jax

    from repro.core.assign import build_graph_data
    from repro.core.policies import episode_encodings, init_policies
    from repro.graphs.partition import coarsen
    from repro.graphs.workloads import get_workload

    g = get_workload("model:olmo_1b:full", seq=64)
    part = coarsen(g, 64)
    gd = build_graph_data(part.seg_graph, uniform_box(4))
    params = init_policies(jax.random.PRNGKey(0), d_hidden=32)
    Hx, sx, zx = episode_encodings(params, gd.x, gd.edges, gd.edge_feat,
                                   gd.b_path, gd.t_path)
    Hp, sp, zp = episode_encodings(params, gd.x, gd.edges, gd.edge_feat,
                                   gd.b_path, gd.t_path, backend="pallas")
    np.testing.assert_allclose(np.asarray(Hp), np.asarray(Hx),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sx),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(zp), np.asarray(zx))
