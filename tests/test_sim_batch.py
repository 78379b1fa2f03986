"""Batched WC engine (sim_batch.py): equivalence contract + invariants.

The contract under test: the compiled batch engine reproduces the serial
``WCSimulator.run`` bit-for-bit — same makespans for every choose strategy
and noise level given the same seed — while being the fast path for
K assignments x S seeds.  Plus simulator physics invariants (critical-path
lower bound, total-work upper bound, determinism, no deadlock) and the
Stage-II training integration.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # container has no hypothesis
    from _hypothesis_fallback import given, settings, st

from conftest import make_chain, make_diamond, random_dag
from repro.core.devices import (p100_box, tpu_v5e_slice, uniform_box,
                                v100_two_groups)
from repro.core.sim_batch import (BatchWCEngine, CompiledGraph,
                                  compile_assignment, run_plan)
from repro.core.simulator import WCSimulator, synchronous_exec_time
from repro.core.training import DopplerTrainer, FleetTrainer

DEVICE_MODELS = [uniform_box(1), uniform_box(4), p100_box(),
                 v100_two_groups(), tpu_v5e_slice(2, 2)]


# ----------------------------------------------------------- equivalence
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(6, 48),
       di=st.integers(0, len(DEVICE_MODELS) - 1),
       choose=st.sampled_from(["fifo", "dfs", "random"]))
def test_property_batched_equals_serial_noise_free(seed, n, di, choose):
    """noise_sigma=0: batched engine == serial run, exactly (1e-9 is the
    contract; bit-equality is what the engine delivers)."""
    rng = np.random.default_rng(seed)
    g = random_dag(rng, n)
    dev = DEVICE_MODELS[di]
    sim = WCSimulator(g, dev, choose=choose)
    a = rng.integers(0, dev.n, g.n)
    ref = sim.run(a, seed=seed).makespan
    out = sim.run_batch(a, seeds=[seed])[0, 0]
    assert out == pytest.approx(ref, abs=1e-9)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000),
       choose=st.sampled_from(["fifo", "dfs", "random"]),
       sigma=st.sampled_from([0.05, 0.2]))
def test_property_batched_equals_serial_noisy(seed, choose, sigma):
    """Same seed => the engine replays the serial engine's RNG call
    sequence, so even noisy makespans match bit-for-bit."""
    rng = np.random.default_rng(seed)
    g = random_dag(rng, int(rng.integers(8, 40)))
    dev = DEVICE_MODELS[int(rng.integers(len(DEVICE_MODELS)))]
    sim = WCSimulator(g, dev, choose=choose, noise_sigma=sigma)
    a = rng.integers(0, dev.n, g.n)
    assert sim.run_batch(a, seeds=[seed])[0, 0] == \
        sim.run(a, seed=seed).makespan


def test_batch_grid_matches_serial_grid(diamond, dev4):
    sim = WCSimulator(diamond, dev4, noise_sigma=0.1)
    rng = np.random.default_rng(0)
    A = rng.integers(0, 4, (5, diamond.n))
    seeds = [3, 7, 11]
    got = sim.run_batch(A, seeds=seeds)
    ref = sim.run_batch(A, seeds=seeds, engine="serial")
    assert got.shape == (5, 3)
    np.testing.assert_array_equal(got, ref)


def test_batch_structured_graphs_all_strategies(dev4):
    for g in (make_diamond(), make_diamond(16), make_chain(12)):
        rng = np.random.default_rng(1)
        A = rng.integers(0, 4, (4, g.n))
        for choose in ("fifo", "dfs", "random"):
            sim = WCSimulator(g, dev4, choose=choose)
            np.testing.assert_array_equal(
                sim.run_batch(A, seeds=[0]),
                sim.run_batch(A, seeds=[0], engine="serial"))


def test_run_paired_matches_per_episode(diamond, dev4):
    sim = WCSimulator(diamond, dev4, noise_sigma=0.05)
    rng = np.random.default_rng(2)
    A = rng.integers(0, 4, (6, diamond.n))
    seeds = list(range(100, 106))
    got = sim.run_paired(A, seeds)
    ref = np.array([sim.run(A[k], seed=seeds[k]).makespan
                    for k in range(6)])
    np.testing.assert_array_equal(got, ref)


def test_noise_free_dedup_consistent(diamond, dev4):
    """With sigma=0 the seed axis collapses; repeated assignment rows must
    still map to their own (identical) makespans."""
    sim = WCSimulator(diamond, dev4)
    a = np.zeros(diamond.n, dtype=int)
    b = np.arange(diamond.n) % 4
    A = np.stack([a, b, a, b])
    out = sim.run_batch(A, seeds=[1, 2])
    assert out.shape == (4, 2)
    assert (out[0] == out[2]).all() and (out[1] == out[3]).all()
    assert (out[:, 0] == out[:, 1]).all()
    assert out[0, 0] == sim.run(a).makespan


# ------------------------------------------------------------- invariants
def _total_work(g, dev, a):
    """Every task's duration summed: each exec plus each unique
    (producer, destination device) transfer.  A work-conserving engine
    advances time only while a task runs, so no schedule takes longer."""
    execs = sum(dev.exec_time(g.vertices[v].flops, a[v])
                for v in range(g.n) if not g.is_input(v))
    xfers = {(s, int(a[d])) for s, d in g.edges
             if not g.is_input(s) and a[s] != a[d]}
    return execs + sum(dev.transfer_time(g.vertices[s].out_bytes, int(a[s]),
                                         d) for s, d in xfers)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(6, 40),
       nd=st.sampled_from([2, 4, 8]))
def test_property_makespan_bounds_and_no_deadlock(seed, n, nd):
    """Batched makespan sandwiched between the critical-path lower bound
    and the total work (the work-conserving upper bound); random DAGs
    never deadlock."""
    rng = np.random.default_rng(seed)
    g = random_dag(rng, n)
    dev = uniform_box(nd)
    sim = WCSimulator(g, dev)
    a = rng.integers(0, nd, g.n)
    ms = sim.run_batch(a)[0, 0]         # deadlock would raise
    lower = g.critical_path_lower_bound(float(dev.flops_per_sec[0]))
    assert ms >= lower * (1 - 1e-9)
    assert ms <= _total_work(g, dev, a) * (1 + 1e-9)


def test_wc_can_exceed_bulk_synchronous_time():
    """Greedy WC scheduling is not bounded by the bulk-synchronous time
    (ROADMAP C10): on this random DAG a FIFO choice delays the critical
    path past what level-wise barriers allow."""
    rng = np.random.default_rng(20)
    g = random_dag(rng, 20)
    dev = uniform_box(4)
    a = rng.integers(0, 4, g.n)
    ms = WCSimulator(g, dev).run_batch(a)[0, 0]
    assert synchronous_exec_time(g, dev, a) < ms <= _total_work(g, dev, a)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_identical_seeds_identical_noise(seed):
    rng = np.random.default_rng(seed)
    g = random_dag(rng, int(rng.integers(8, 30)))
    dev = uniform_box(4)
    sim = WCSimulator(g, dev, noise_sigma=0.1)
    a = rng.integers(0, 4, g.n)
    t1 = sim.run_batch(a, seeds=[seed, seed, seed + 1])[0]
    assert t1[0] == t1[1]
    assert t1[0] != t1[2]


def test_deadlock_detection():
    """A plan whose dependencies can never be satisfied must raise, not
    hang — forced by corrupting the compiled indegrees."""
    g = make_chain(4)
    dev = uniform_box(2)
    cg = CompiledGraph.build(g, dev)
    plan = compile_assignment(cg, np.zeros(g.n, dtype=int))
    plan.need0[1] = 99                  # vertex 1 waits forever
    with pytest.raises(RuntimeError, match="deadlock"):
        run_plan(cg, plan)


def test_compiled_graph_cost_tables(diamond, dev4):
    cg = CompiledGraph.build(diamond, dev4)
    assert cg.exec_cost.shape == (diamond.n, 4)
    v = next(i for i in range(diamond.n) if not diamond.is_input(i))
    assert cg.exec_cost[v, 2] == dev4.exec_time(diamond.vertices[v].flops, 2)
    assert cg.n_compute == sum(1 for i in range(diamond.n)
                               if not diamond.is_input(i))


def test_plan_transfer_tasks_match_cross_edges(diamond, dev4):
    cg = CompiledGraph.build(diamond, dev4)
    a = np.arange(diamond.n) % 4
    plan = compile_assignment(cg, a)
    want = {(s, int(a[d])) for (s, d) in diamond.edges
            if not diamond.is_input(s) and a[s] != a[d]}
    got = set(zip(plan.xfer_src, plan.xfer_dst))
    assert got == want
    for j, (s, dst) in enumerate(zip(plan.xfer_src, plan.xfer_dst)):
        assert plan.dur[diamond.n + j] == dev4.transfer_time(
            diamond.vertices[s].out_bytes, int(a[s]), dst)


# ---------------------------------------------------- training integration
def test_stage2_batched_engine_matches_serial_bookkeeping(diamond, dev4):
    """The batched Stage II must preserve the serial path's episode
    counting, reward statistics, history, and best-so-far semantics."""
    def run(engine):
        tr = DopplerTrainer(diamond, dev4, seed=0, d_hidden=16,
                            total_episodes=100)
        sim = WCSimulator(diamond, dev4, noise_sigma=0.05)
        times = tr.stage2_sim_batched(5, sim, batch_size=4,
                                      sim_engine=engine)
        return (times, tr.episode, tr.best_time, tr._r_count, tr._r_sum,
                [(h.episode, h.stage, h.exec_time, h.best_so_far)
                 for h in tr.history])

    serial, batched = run("serial"), run("batched")
    assert serial == batched
    times, episode, best, r_count, _, history = batched
    assert episode == 5 * 4 and len(times) == 20 and r_count == 20
    assert best == pytest.approx(min(times))
    assert [h[0] for h in history] == [4, 8, 12, 16, 20]
    assert all(h[1] == "sim_batch" for h in history)


def test_fleet_exec_time_batched_matches_serial(diamond, dev4):
    ft = FleetTrainer({"blk": diamond}, dev4, n_replicas=4, seed=0,
                      d_hidden=16, total_episodes=50)
    a = np.arange(diamond.n) % 4
    assert ft.fleet_exec_time("blk", a, episode=7) == \
        ft.fleet_exec_time("blk", a, episode=7, sim_engine="serial")
