"""Fused Stage-II engine (train_fused.py): parity with the reference path.

The contract under test: ``stage2_fused`` reproduces
``stage2_sim_batched(engine='serial', noise_sigma=0)`` — the same
episodes are sampled (bit-identical actions at eps=0 for the same
seeds), rewards match the serial WC engine to float tolerance, the
scan-free parallel gradient equals the forced-replay gradient, and the
trainer bookkeeping (episode counter, running reward stats, best-so-far,
history) stays in lockstep.  Plus the fused Stage-I imitation path and
the Table-3 ablation plumbing of `_pg_loss_and_grad_batch`.
"""
import dataclasses
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_diamond
from repro.core.assign import build_graph_data, rollout_batch
from repro.core.devices import uniform_box
from repro.core.policies import episode_encodings, init_policies
from repro.core.simulator import WCSimulator
from repro.core.train_fused import (_sample_scan, fused_pg_loss,
                                    fused_pg_loss_reduced, sample_episodes)
from repro.core.training import (DopplerTrainer, FleetTrainer,
                                 _pg_loss_and_grad_batch)


def make_trainer(graph, dev, seed=0, **kw):
    kw.setdefault("d_hidden", 16)
    kw.setdefault("total_episodes", 200)
    return DopplerTrainer(graph, dev, seed=seed, **kw)


# -------------------------------------------------------- exact sampling
def test_sampler_bit_identical_to_rollout(diamond, dev4):
    """At eps=0 the recorded sampler replays rollout's RNG stream
    bit-for-bit (same key chain, same gumbel tables)."""
    gd = build_graph_data(diamond, dev4)
    params = init_policies(jax.random.PRNGKey(0), d_hidden=16)
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    rec = sample_episodes(params, gd, keys, jnp.float32(0.0))
    ref = rollout_batch(params, gd, keys, jnp.float32(0.0))
    assert (np.asarray(rec["actions"]) == np.asarray(ref["actions"])).all()
    assert (np.asarray(rec["assignment"])
            == np.asarray(ref["assignment"])).all()


def test_sampler_eps_explores_validly(diamond, dev4):
    gd = build_graph_data(diamond, dev4)
    params = init_policies(jax.random.PRNGKey(0), d_hidden=16)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    rec = sample_episodes(params, gd, keys, jnp.float32(0.5))
    for k in range(4):
        order = np.asarray(rec["actions"][k, :, 0])
        assert sorted(order.tolist()) == list(range(diamond.n))
        a = np.asarray(rec["assignment"][k])
        assert ((0 <= a) & (a < dev4.n)).all()


# ------------------------------------------------------- exact gradients
def test_fused_gradient_matches_replay(diamond, dev4):
    """The scan-free loss (linearized SEL + prefix-sum PLC) must equal the
    forced-replay loss and gradient to float tolerance."""
    gd = build_graph_data(diamond, dev4)
    params = init_policies(jax.random.PRNGKey(0), d_hidden=32, d_z=16,
                           d_y=16)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    rec = sample_episodes(params, gd, keys, jnp.float32(0.0))
    advs = jnp.asarray([0.5, -0.3, 1.2, -0.8])
    l_ref, g_ref = _pg_loss_and_grad_batch(
        params, gd, keys, rec["actions"], advs, jnp.float32(1e-2))
    l_fus, g_fus = jax.value_and_grad(fused_pg_loss)(
        params, gd, rec, advs, jnp.float32(1e-2))
    assert float(l_fus) == pytest.approx(float(l_ref), rel=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_fus)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6)


# ------------------------------------- chunked / reduced engine parity
def _reduced_recordings(params, gd, keys):
    enc = episode_encodings(params, gd.x, gd.edges, gd.edge_feat,
                            gd.b_path, gd.t_path, backend="xla")
    return _sample_scan(params, gd, keys, jnp.float32(0.0), "learned",
                        "learned", enc, record="reduced")


def test_reduced_recordings_match_full(diamond, dev4):
    """record='reduced' samples the same episodes as record='full' and its
    trimmed x_dyn recording is exactly x_dev's dynamic columns; the
    reduced loss matches the full loss/gradient to float-order
    tolerance."""
    gd = build_graph_data(diamond, dev4)
    params = init_policies(jax.random.PRNGKey(0), d_hidden=16)
    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    rec_full = sample_episodes(params, gd, keys, jnp.float32(0.0))
    rec_red = _reduced_recordings(params, gd, keys)
    np.testing.assert_array_equal(np.asarray(rec_red["actions"]),
                                  np.asarray(rec_full["actions"]))
    np.testing.assert_array_equal(
        np.asarray(rec_red["x_dyn"]),
        np.asarray(rec_full["x_dev"][..., :-gd.dev_x.shape[1]]))
    advs = jnp.linspace(-1.0, 1.0, 8)
    l_f, g_f = jax.value_and_grad(fused_pg_loss)(
        params, gd, rec_full, advs, jnp.float32(1e-2))
    l_r, g_r = jax.value_and_grad(fused_pg_loss_reduced)(
        params, gd, rec_red, advs, jnp.float32(1e-2))
    assert float(l_r) == pytest.approx(float(l_f), abs=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_f),
                    jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6)


def test_chunked_gradient_parity(diamond, dev4):
    """Gradient accumulated over equal micro-chunks == the monolithic
    batch gradient to <= 1e-6, pre-optimizer (mean of chunk means is the
    batch mean — the contract the chunked engine's accumulation scan
    relies on)."""
    gd = build_graph_data(diamond, dev4)
    params = init_policies(jax.random.PRNGKey(0), d_hidden=16)
    keys = jax.random.split(jax.random.PRNGKey(4), 16)
    rec = _reduced_recordings(params, gd, keys)
    advs = jnp.linspace(-1.0, 1.0, 16)
    grad = jax.jit(jax.grad(fused_pg_loss_reduced))
    g_full = grad(params, gd, rec, advs, jnp.float32(1e-2))
    gc = 4
    g_sum = None
    for c in range(16 // gc):
        sl = slice(c * gc, (c + 1) * gc)
        rec_c = {k: v[sl] for k, v in rec.items()}
        g_c = grad(params, gd, rec_c, advs[sl], jnp.float32(1e-2))
        g_sum = g_c if g_sum is None else jax.tree_util.tree_map(
            jnp.add, g_sum, g_c)
    for a, b in zip(jax.tree_util.tree_leaves(g_full),
                    jax.tree_util.tree_leaves(g_sum)):
        np.testing.assert_allclose(np.asarray(b) / (16 // gc),
                                   np.asarray(a), atol=1e-6)


def test_stage2_fused_chunked_matches_monolithic(diamond, dev4):
    """Trainer-level: explicit micro-chunking reproduces the monolithic
    engine's episode stream bit-for-bit (same keys, same gumbel draws,
    same oracle decisions) and lands on the same params."""
    def run(cs, gc):
        tr = make_trainer(diamond, dev4, eps0=0.0, eps1=0.0)
        t = tr.stage2_fused(2, batch_size=8, updates_per_dispatch=2,
                            chunk_size=cs, grad_chunk_size=gc)
        return np.asarray(t), tr.params

    t_c, p_c = run(4, 4)
    t_m, p_m = run(0, None)
    np.testing.assert_array_equal(t_c, t_m)
    for a, b in zip(jax.tree_util.tree_leaves(p_c),
                    jax.tree_util.tree_leaves(p_m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3)


def test_stage2_fused_raises_on_nonconverged_oracle(diamond, dev4):
    """The Pallas/XLA oracle validity flag must surface: a sim graph
    doctored to starve the trip loop (n_trips too small to drain the
    heap) makes every episode non-converged, and the dispatch raises
    instead of training on garbage makespans."""
    from repro.core.sim_jax import SimGraph

    tr = make_trainer(diamond, dev4)
    sg = SimGraph.build(diamond, dev4)
    tr._fused_cache = {"sim_graph": dataclasses.replace(sg, n_trips=1)}
    with pytest.raises(RuntimeError, match="converge"):
        tr.stage2_fused(2, batch_size=4, updates_per_dispatch=2)
    # the dispatch donated the old state: the trainer holds the new one,
    # with its episode index and reward statistics advanced to match
    leaves = jax.tree_util.tree_leaves((tr.params, tr.opt_state))
    assert not any(x.is_deleted() for x in leaves)
    assert tr.episode == 2 * 4 and tr._r_count == 2 * 4


def test_stage2_fused_donation_keeps_transfer_source(diamond, dev4):
    """A fused update donates its trainer's params; a trainer made by
    transfer() from it must keep its own copy, and vice versa."""
    from repro.core.training import transfer
    src = make_trainer(diamond, dev4)
    dst = transfer(src, diamond, dev4, seed=1, d_hidden=16,
                   total_episodes=200)
    before = [np.asarray(x) for x in jax.tree_util.tree_leaves(src.params)]
    dst.stage2_fused(1, batch_size=4)
    after = jax.tree_util.tree_leaves(src.params)
    assert not any(x.is_deleted() for x in after)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, np.asarray(b))
    src.stage2_fused(1, batch_size=4)
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(dst.params))


# ------------------------------------------------------------ trace names
PHASES = ("doppler.encoder", "doppler.sample", "doppler.oracle",
          "doppler.grad", "doppler.adamw")


@pytest.mark.parametrize("chunk_size", [4, 0], ids=["chunked", "monolithic"])
def test_fused_step_names_its_phases(diamond, dev4, chunk_size):
    """Each phase of the compiled step carries its named scope in the HLO
    ``op_name`` metadata, and the WC oracle's kernel lies under
    ``doppler.oracle``."""
    import re

    from repro.core.sim_jax import SimGraph
    from repro.core.train_fused import (FusedStage2Config, RewardStats,
                                        build_fused_stage2)
    tr = make_trainer(diamond, dev4, oracle_backend="pallas")
    cfg = FusedStage2Config(batch_size=8, updates=1,
                            oracle_backend="pallas", chunk_size=chunk_size)
    step = build_fused_stage2(cfg, tr.gd, SimGraph.build(diamond, dev4),
                              tr.lr_sched, tr.eps_sched)
    hlo = step.lower(tr.params, tr.opt_state, RewardStats.make(), tr.key,
                     jnp.int32(0)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)

    def outermost(op_name):
        return next((p for p in op_name.split("/")
                     if p.startswith("doppler.")), "")

    assert {outermost(o) for o in op_names} >= set(PHASES)
    # opened outside any transformation, every scope stays a plain path
    # component (not only ``transpose(doppler.grad)``)
    assert all(outermost(o) for o in op_names if "doppler." in o)
    kernel = [o for o in op_names if "jit(_wc_step)" in o]
    assert kernel
    assert {outermost(o) for o in kernel} == {"doppler.oracle"}


def test_stage2_fused_host_spans(diamond, dev4, tmp_path):
    """One dispatch writes its three host spans once each, in the order
    dispatch, sync, record, inside the caller's span, each with the index
    of the dispatch's first update."""
    tr = make_trainer(diamond, dev4)
    tr.stage2_fused(1, batch_size=4)             # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.call"):
            tr.stage2_fused(1, batch_size=4)
    assert tr.stage2_updates == 2
    (xspace,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(xspace))
    names = ("test.call", "doppler.stage2.dispatch", "doppler.stage2.sync",
             "doppler.stage2.record")
    spans = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    assert ev.name not in spans, ev.name
                    spans[ev.name] = (ev.start_ns, ev.end_ns,
                                      {k: v for k, v in ev.stats})
    assert set(spans) == set(names)
    call, *phases = (spans[n] for n in names)
    assert call[0] <= phases[0][0]
    for (_, end, _), (start, _, _) in zip(phases, phases[1:]):
        assert end <= start
    assert phases[-1][1] <= call[1]
    assert [int(p[2]["update"]) for p in phases] == [1, 1, 1]


def test_shard_map_matches_pmap_two_devices():
    """Same-seed trajectory bit-parity: the shard_map engine (single
    fused all-reduce, donated buffers) vs the legacy pmap engine on two
    forced host devices.  Subprocess: the device count must be baked
    into XLA_FLAGS before jax initializes."""
    root = pathlib.Path(__file__).resolve().parents[1]
    code = textwrap.dedent("""
        import numpy as np
        import jax
        import jax.numpy as jnp
        from conftest import make_diamond
        from repro.core.devices import uniform_box
        from repro.core.sim_jax import SimGraph
        from repro.core.train_fused import (FusedStage2Config, RewardStats,
                                            build_fused_stage2)
        from repro.core.training import DopplerTrainer

        assert jax.local_device_count() == 2
        g, dev = make_diamond(8), uniform_box(4)

        def run(spmd):
            tr = DopplerTrainer(g, dev, seed=0, d_hidden=16,
                                total_episodes=200)
            fn = build_fused_stage2(
                FusedStage2Config(batch_size=8, updates=2), tr.gd,
                SimGraph.build(g, dev), tr.lr_sched, tr.eps_sched,
                n_devices=2, spmd=spmd)
            return fn(tr.params, tr.opt_state,
                      RewardStats.make(0.0, 0.0, 0), tr.key, jnp.int32(0))

        a, b = run("shard_map"), run("pmap")
        assert np.array_equal(np.asarray(a["makespans"]),
                              np.asarray(b["makespans"]))
        assert np.array_equal(np.asarray(a["oracle_ok"]),
                              np.asarray(b["oracle_ok"]))
        for x, y in zip(jax.tree_util.tree_leaves(a["params"]),
                        jax.tree_util.tree_leaves(b["params"])):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        print("SPMD_PARITY_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root / "tests"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SPMD_PARITY_OK" in proc.stdout


# -------------------------------------------------- fused vs reference
def _run_pair(graph, dev, n_updates=6, batch_size=4, updates_per_dispatch=3,
              **kw):
    sim0 = WCSimulator(graph, dev, choose="fifo", noise_sigma=0.0)
    ref = make_trainer(graph, dev, eps0=0.0, eps1=0.0, **kw)
    t_ref = ref.stage2_sim_batched(n_updates, sim0, batch_size=batch_size,
                                   sim_engine="serial")
    fus = make_trainer(graph, dev, eps0=0.0, eps1=0.0, **kw)
    t_fus = fus.stage2_fused(n_updates, batch_size=batch_size,
                             updates_per_dispatch=updates_per_dispatch)
    return ref, t_ref, fus, t_fus


def test_stage2_fused_matches_reference(diamond, dev4):
    """Same seeds -> same reward trajectory (float tolerance), same final
    params, and lockstep trainer bookkeeping."""
    ref, t_ref, fus, t_fus = _run_pair(diamond, dev4)
    np.testing.assert_allclose(t_fus, t_ref, rtol=2e-4)
    assert fus.episode == ref.episode == 24
    assert fus.best_time == pytest.approx(ref.best_time, rel=2e-4)
    assert (fus.best_assignment == ref.best_assignment).all()
    assert fus._r_count == ref._r_count
    assert fus._r_sum == pytest.approx(ref._r_sum, rel=1e-4)
    assert [h.episode for h in fus.history] == \
        [h.episode for h in ref.history]
    for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(fus.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3)


def test_stage2_fused_remainder_chunks(diamond, dev4):
    """n_updates not divisible by updates_per_dispatch runs a tail chunk
    with identical results."""
    _, t_a, _, t_b = _run_pair(diamond, dev4, n_updates=5,
                               updates_per_dispatch=2)
    assert len(t_b) == len(t_a) == 5 * 4
    np.testing.assert_allclose(t_b, t_a, rtol=2e-4)


def test_stage2_fused_ablations_run(diamond, dev4):
    for kw in ({"sel_mode": "cp"}, {"plc_mode": "etf"}):
        tr = make_trainer(diamond, dev4, **kw)
        times = tr.stage2_fused(2, batch_size=4, updates_per_dispatch=2)
        assert len(times) == 8 and np.isfinite(times).all()


def test_stage2_fused_learns(diamond, dev4):
    tr = make_trainer(diamond, dev4, d_hidden=32, total_episodes=400,
                      lr0=3e-3, lr1=1e-4)
    times = tr.stage2_fused(40, batch_size=8, updates_per_dispatch=10)
    assert np.mean(times[-40:]) < np.mean(times[:40])
    assert tr.best_time <= min(times) + 1e-12


# ------------------------------------------------------- fused Stage I
def test_stage1_fused_matches_loop(diamond, dev4):
    a = make_trainer(diamond, dev4)
    losses_loop = a.stage1_imitation(6, seed=3)
    b = make_trainer(diamond, dev4)
    losses_fused = b.stage1_imitation_fused(6, seed=3)
    np.testing.assert_allclose(losses_fused, losses_loop, rtol=1e-3,
                               atol=1e-5)
    assert b.episode == a.episode
    for x, y in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=5e-3)


def test_stage1_fused_batched(diamond, dev4):
    tr = make_trainer(diamond, dev4)
    losses = tr.stage1_imitation_fused(8, seed=0, batch_size=4)
    assert len(losses) == 2 and tr.episode == 8


# ------------------------------------------------- ablation gradient fix
def test_pg_batch_ablation_gates_gradients(diamond, dev4):
    """Table-3 modes: the heuristic-replaced policy's parameters must get
    zero gradient from the batched loss (the PR-2 path silently trained
    them)."""
    gd = build_graph_data(diamond, dev4)
    params = init_policies(jax.random.PRNGKey(0), d_hidden=16)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    out = rollout_batch(params, gd, keys, jnp.float32(0.1))
    advs = jnp.ones(3)

    _, g = _pg_loss_and_grad_batch(params, gd, keys, out["actions"], advs,
                                   jnp.float32(1e-2), sel_learned=False)
    assert all(float(np.abs(np.asarray(x)).max()) == 0.0
               for x in jax.tree_util.tree_leaves(g["sel_head"]))
    _, g = _pg_loss_and_grad_batch(params, gd, keys, out["actions"], advs,
                                   jnp.float32(1e-2), plc_learned=False)
    assert all(float(np.abs(np.asarray(x)).max()) == 0.0
               for x in jax.tree_util.tree_leaves(g["plc_head1"]))
    _, g = _pg_loss_and_grad_batch(params, gd, keys, out["actions"], advs,
                                   jnp.float32(1e-2))
    assert any(float(np.abs(np.asarray(x)).max()) > 0.0
               for x in jax.tree_util.tree_leaves(g["sel_head"]))


def test_stage2_sim_batched_accepts_ablation(diamond, dev4):
    tr = make_trainer(diamond, dev4, sel_mode="cp")
    sim = WCSimulator(diamond, dev4, choose="fifo", noise_sigma=0.0)
    times = tr.stage2_sim_batched(2, sim, batch_size=3)
    assert len(times) == 6


# ------------------------------------------------------- fleet batching
def test_fleet_train_batched_matches_episode_budget(diamond, dev4):
    ft = FleetTrainer({"blk": diamond}, dev4, n_replicas=3, seed=0,
                      d_hidden=16, total_episodes=60)
    ft.train(10, batch_size=4)
    tr = ft.trainers["blk"]
    assert tr.episode == 10
    assert [h.stage for h in tr.history] == ["fleet"] * 3  # 4+4+2
    assert tr.best_assignment is not None
