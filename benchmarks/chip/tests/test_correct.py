"""``correct`` at tiny sizes: the program passes; the control, and each
fault planted in the program's timed path, fail the cell's limits."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest


S2 = "olmo_full.stage2_b1024"


def test_stage2_program_is_correct(tiny_cell):
    line = tiny_cell(S2)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"episodes_per_s", "setup_s"}


def test_stage2_traced_run_reads_its_layer_metrics(tiny_cell):
    line = tiny_cell(S2, trace=True)
    assert line["correct"], line["checks"]
    assert {"device_idle.stage2", "stage2_mfu"} <= set(line["metrics"])
    assert line["device"]["window_s"] > 0


def _plant(monkeypatch, fault):
    import repro.core.train_fused as tf
    if fault == "unchanged":
        monkeypatch.setattr(tf, "adamw_update",
                            lambda g, state, params, lr: (params, state))
    elif fault == "half_batch":
        loss = tf.fused_pg_loss_reduced

        def half(params, gd, rec, advs, *a, **k):
            h = advs.shape[0] // 2
            return loss(params, gd, {key: v[:h] for key, v in rec.items()},
                        advs[:h], *a, **k)
        monkeypatch.setattr(tf, "fused_pg_loss_reduced", half)
    elif fault == "answer":
        oracle = tf._makespan_fifo_batch_pallas

        def off(sg, a, interpret):
            ms, ok = oracle(sg, a, interpret)
            return ms * 1.001, ok
        monkeypatch.setattr(tf, "_makespan_fifo_batch_pallas", off)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "answer"])
def test_stage2_fault_is_not_correct(tiny_cell, monkeypatch, fault):
    _plant(monkeypatch, fault)
    assert not tiny_cell(S2)["correct"]


def test_stage2_control_fails_a_limit(run_mod):
    """The reference in bfloat16, put in the program's place, on the
    cell's own graph (the OLMo-1B step coarsened to 580 segments) at a
    batch of 16."""
    load = run_mod.load_file
    ref = load(run_mod.HERE / "reference" / "stage2.py")
    drv = load(run_mod.HERE / "drivers" / "stage2.py")
    common = load(run_mod.HERE / "common.py")
    cfg = run_mod.load_json(run_mod.HERE / "configs" / "olmo_1b.json")
    traffic = run_mod.load_json(run_mod.HERE / "traffic"
                                / "stage2_full_b1024.json")
    from repro.core.devices import get_device_model
    from repro.core.training import DopplerTrainer
    from repro.graphs.workloads import get_workload
    name, kw = drv.graph_name(cfg, traffic)
    g = DopplerTrainer(get_workload(name, **kw),
                       get_device_model(cfg["fleet"]["name"]),
                       hierarchy=traffic["hierarchy"]).g
    inst = ref.build_instance(g.edge_array(), g.flops_array(),
                              g.out_bytes_array(), g.input_mask(),
                              cfg["fleet"], cfg["comm_factor"])
    hp = {"batch": 16, **traffic["schedule"]}
    p0 = common.make_params(common.seed_key(7, 0),
                            common.policy_sizes(cfg["policy"]))
    key = jax.random.PRNGKey(3)
    r32 = ref.Stage2Reference(inst, hp, block=8).follow(p0, key, 0, 3)
    r16 = ref.Stage2Reference(inst, hp, dtype=jnp.bfloat16,
                              block=8).follow(p0, key, 0, 3)
    prog = {"p0": common.tree_to_host(p0),
            "makespans": [u["makespans"] for u in r16],
            "loss": [u["loss"] for u in r16], "grad": r16[0]["grad"],
            "params": r16[-1]["params"]}
    checks = drv.checks_of(drv.compare(prog, r32, common),
                           traffic["limits"])
    assert any(c["value"] > c["limit"] for c in checks), checks


def test_reference_blocks_agree(run_mod):
    """Sampling and scoring the whole batch at once, or block by block,
    gives the same episodes."""
    load = run_mod.load_file
    ref = load(run_mod.HERE / "reference" / "stage2.py")
    common = load(run_mod.HERE / "common.py")
    cfg = run_mod.load_json(run_mod.HERE / "configs" / "olmo_1b.json")
    traffic = run_mod.load_json(run_mod.HERE / "traffic"
                                / "stage2_full_b1024.json")
    from repro.graphs.workloads import get_workload
    g = get_workload("model:olmo_1b", seq=256)
    inst = ref.build_instance(g.edge_array(), g.flops_array(),
                              g.out_bytes_array(), g.input_mask(),
                              cfg["fleet"], cfg["comm_factor"])
    hp = {"batch": 16, **traffic["schedule"]}
    p0 = common.make_params(common.seed_key(9, 0),
                            common.policy_sizes(cfg["policy"]))
    key = jax.random.PRNGKey(4)
    a = ref.Stage2Reference(inst, hp, block=4).follow(p0, key, 0, 2)
    b = ref.Stage2Reference(inst, hp, block=4,
                            sample_block=16).follow(p0, key, 0, 2)
    for x, y in zip(a, b):
        assert (x["makespans"] == y["makespans"]).all()
        assert abs(x["loss"] - y["loss"]) <= 1e-6 * x["loss_scale"]
