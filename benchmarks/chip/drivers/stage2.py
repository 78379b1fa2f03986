"""Stage-II training driver: ``DopplerTrainer.stage2_fused`` on one chip.

Set-up traces the configuration's training step into a dataflow graph
(``model:<arch>`` for one pattern period, ``model:<arch>:full`` for the
whole step, coarsened to ``hierarchy`` segments where the mix says so),
builds the trainer with the Pallas encoder and oracle, gives it policy
weights made from the seed, and drives its compiled fused step through
the first ``check_updates`` updates, one update per dispatch, keeping
what the check needs.  The first dispatch compiles (or loads from the
persistent cache); the later ones and the window compile nothing.

The window calls ``stage2_fused`` one update at a time until
``--seconds`` have passed; ``episodes_per_s`` is every episode completed
over the whole window, which ends when the last dispatch's results are
on the host.

The check, after the window: the plain reference (``reference/stage2``)
follows the same first updates from the same weights, key and episode
counter, and ``compare`` reads update 1's makespans, each update's loss,
the first gradient as AdamW took it (its first moment over 1 - beta1)
and the parameters' change over the updates, leaf by leaf; the mix's
``limits`` say which of these are compared.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SPAN_DISPATCH = "bench.stage2_fused"


class LossTap:
    """Wraps the fused chunk the trainer builds (``build_fused_stage2``),
    keeping each call's per-update surrogate losses, a device array: no
    sync, one Python call per dispatch."""

    def __init__(self):
        import repro.core.train_fused as tf
        self.losses = None
        build = tf.build_fused_stage2

        def tapped(*args, **kwargs):
            fn = build(*args, **kwargs)

            def chunk(*a):
                out = fn(*a)
                self.losses = out["losses"]
                return out
            return chunk

        tf.build_fused_stage2 = tapped


def graph_name(config: dict, traffic: dict) -> tuple[str, dict]:
    a = config["assumed"]
    kw = {"seq": a["seq"], "batch": a["trace_batch"]}
    name = "model:" + config["zoo_arch"]
    if traffic["scope"] == "full":
        name += ":full"
        kw["microbatches"] = a["microbatches"]
    return name, kw


def check_model(config: dict) -> None:
    """The zoo's architecture must be the one the configuration states."""
    from repro.configs.registry import get_config
    mc = get_config(config["zoo_arch"])
    want = config["zoo_fields"]
    got = {k: getattr(mc, k) for k in want}
    got = {k: (list(v) if isinstance(v, tuple) else v)
           for k, v in got.items()}
    if got != want:
        raise SystemExit(f"benchmark: the zoo's {config['zoo_arch']} differs "
                         f"from the configuration: {got} != {want}")


def check_fleet(dev, fleet_ref: dict) -> None:
    ok = (np.array_equal(dev.flops_per_sec, fleet_ref["fps"])
          and np.array_equal(dev.link_bw, fleet_ref["bw"])
          and np.array_equal(dev.link_latency, fleet_ref["lat"])
          and np.array_equal(dev.exec_overhead_vec, fleet_ref["overhead"]))
    if not ok:
        raise SystemExit("benchmark: the program's fleet differs from the "
                         "configuration's")


def build(ctx: dict, seed: int):
    """The system under test: the trainer over the graph the policy
    places, with the Pallas encoder and oracle and policy weights made
    from ``seed``; and the window's call, one update per dispatch."""
    load = ctx["load"]
    common = load(ctx["here"] / "common.py")
    ref = load(ctx["here"] / "reference" / "stage2.py")
    config, traffic = ctx["config"], ctx["traffic"]
    sched = traffic["schedule"]
    if traffic["updates_per_dispatch"] != 1:
        # the check reads the optimizer state after the first update
        raise SystemExit("benchmark: stage2 mixes dispatch one update")
    jax.config.update("jax_default_matmul_precision",
                      config["precision"]["matmul"])

    from repro.core.devices import get_device_model
    from repro.core.training import DopplerTrainer
    from repro.graphs.workloads import get_workload
    from repro.train.optim import adamw_init

    check_model(config)
    name, kw = graph_name(config, traffic)
    g = get_workload(name, **kw)
    dev = get_device_model(config["fleet"]["name"])
    check_fleet(dev, ref.fleet_arrays(config["fleet"]))
    pol = config["policy"]
    tr = DopplerTrainer(
        g, dev, seed=int(seed) % 2**32, d_hidden=pol["d_hidden"],
        gnn_layers=pol["gnn_layers"], lr0=sched["lr0"], lr1=sched["lr1"],
        eps0=sched["eps0"], eps1=sched["eps1"],
        entropy_weight=sched["entropy_weight"],
        total_episodes=sched["total_episodes"],
        comm_factor=config["comm_factor"],
        hierarchy=traffic.get("hierarchy"),
        encoder_backend="pallas", oracle_backend="pallas")
    params = common.make_params(common.seed_key(seed, 0),
                                common.policy_sizes(pol))
    if (jax.tree_util.tree_structure(params)
            != jax.tree_util.tree_structure(tr.params)
            or [x.shape for x in jax.tree_util.tree_leaves(params)]
            != [x.shape for x in jax.tree_util.tree_leaves(tr.params)]):
        raise SystemExit("benchmark: the policy's layout differs from the "
                         "configuration's")
    tr.params, tr.opt_state = params, adamw_init(params)

    def dispatch():
        with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
            return tr.stage2_fused(
                1, batch_size=traffic["batch"],
                updates_per_dispatch=traffic["updates_per_dispatch"],
                chunk_size=traffic["chunk_size"],
                grad_chunk_size=traffic["grad_chunk_size"])

    return tr, dev, dispatch


def first_updates(tr, dispatch, tap: LossTap, n: int, common) -> dict:
    """Drive the trainer's compiled step through its first ``n`` updates
    by the window's own call; keep where they started (weights, key,
    episode counter) and what the check compares."""
    prog = {"p0": common.tree_to_host(tr.params),
            "key0": np.asarray(tr.key), "ep0": tr.episode,
            "makespans": [], "loss": []}
    for u in range(n):
        prog["makespans"].append(np.asarray(dispatch(), np.float32))
        prog["loss"].append(float(np.asarray(tap.losses)[0]))
        if u == 0:
            beta1 = 0.9
            prog["grad"] = jax.tree_util.tree_map(
                lambda m: np.asarray(m) / (1 - beta1), tr.opt_state.mu)
    prog["params"] = common.tree_to_host(tr.params)
    return prog


def reference(ctx: dict, pg, prog: dict, **kw) -> list:
    """The plain reference over the graph ``pg``, following the program's
    first updates from where they started; ``kw`` plants a defect or
    lowers its precision (``control.py``)."""
    ref = ctx["load"](ctx["here"] / "reference" / "stage2.py")
    config, traffic = ctx["config"], ctx["traffic"]
    inst = ref.build_instance(pg.edge_array(), pg.flops_array(),
                              pg.out_bytes_array(), pg.input_mask(),
                              config["fleet"], config["comm_factor"])
    hp = {"batch": traffic["batch"], **traffic["schedule"]}
    follower = ref.Stage2Reference(
        inst, hp, block=traffic["reference_grad_block"],
        sample_block=traffic["reference_sample_block"], **kw)
    return follower.follow(
        jax.tree_util.tree_map(jnp.asarray, prog["p0"]),
        jnp.asarray(prog["key0"]), prog["ep0"], len(prog["makespans"]))


def checks_of(read: dict, limits: dict) -> list[dict]:
    """The numbers compared, each beside its limit; the others printed."""
    print("read, not compared: " + json.dumps(
        {k: v for k, v in read.items() if k not in limits}),
        file=sys.stderr, flush=True)
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in read.items() if k in limits]


def run(ctx: dict) -> dict:
    common = ctx["load"](ctx["here"] / "common.py")
    trace = ctx["load"](ctx["here"] / "trace.py")
    traffic = ctx["traffic"]
    K = traffic["batch"]
    tap = LossTap()
    tr, dev, dispatch = build(ctx, ctx["seed"])
    pg = tr.g                                   # the graph the policy places
    pol = ctx["config"]["policy"]
    # ---- set-up: the first updates through the window's own call
    prog = first_updates(tr, dispatch, tap, traffic["check_updates"],
                         common)

    # ---- the window
    counter = common.CompileCounter()
    n_disp = traffic["trace_dispatches"] if ctx["trace"] else None
    episodes = failed = 0
    setup_s = time.perf_counter() - ctx["t_start"]
    counter.on = True
    with common.traced(ctx["trace"]) as tinfo:
        t0 = time.perf_counter()
        while True:
            try:
                dispatch()
            except RuntimeError:
                failed += K
            episodes += K
            done = time.perf_counter() - t0
            if (n_disp is not None and episodes >= n_disp * K) or \
                    (n_disp is None and done >= ctx["seconds"]):
                break
        window = time.perf_counter() - t0
    t_stop = time.perf_counter()
    counter.on = False
    mem = common.memory_peak_bytes(jax.devices()[:1])
    if counter.count:
        print(f"warning: {counter.count} compilations inside the window",
              file=sys.stderr, flush=True)

    edges, flops = pg.edge_array(), pg.flops_array()
    out_bytes, is_input = pg.out_bytes_array(), pg.input_mask()
    # the oracle's candidate rows per trip: one per resource whose queue
    # can gain a task (a producer's out-edges) plus the freed one
    outdeg = np.bincount(edges[:, 0][~is_input[edges[:, 0]]],
                         minlength=pg.n)
    shapes = {"n": pg.n, "m": pg.m, "nd": dev.n, "batch": K,
              "oracle_batch": traffic["chunk_size"],
              "oracle_R": dev.n + dev.n * dev.n,
              "oracle_K": max(dev.n, max(int(outdeg.max()), 1) + 1),
              "policy": pol}
    del tr, tap                                  # free the program's state

    layer_ctx = None
    breakdown = None
    if ctx["trace"]:
        summ = trace.summarize(tinfo["dir"], common.WINDOW_SPAN, [0],
                               [SPAN_DISPATCH])
        common.drop_trace(tinfo)
        print(f"trace: written in {t_stop - t0 - window:.1f} s, read in "
              f"{time.perf_counter() - t_stop:.1f} s", file=sys.stderr,
              flush=True)
        layer_ctx = {"kind": "stage2", "events": summ["events"],
                     "busy_s": summ["busy_s"], "window_s": summ["window_s"],
                     "episodes": episodes,
                     "chips": 1, "peaks": ctx["peaks"], "shapes": shapes,
                     "pallas_calls": trace.pallas_calls}
        breakdown = summ["breakdown"]

    # ---- the check against the plain reference
    r = reference(ctx, pg, prog)
    print("reference seconds per update: " + json.dumps(
        [u["seconds"] for u in r]), file=sys.stderr, flush=True)
    checks = checks_of(compare(prog, r, common), traffic["limits"])
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks)
    return {"correct": bool(correct), "attempted": episodes,
            "failed": failed, "memory_peak_bytes": mem,
            "end_to_end": {"episodes_per_s": episodes / window,
                           "setup_s": setup_s},
            "layer_ctx": layer_ctx, "breakdown": breakdown,
            "checks": checks}


def compare(prog: dict, r: list, common) -> dict:
    """Every number the check reads, by name; the mix's ``limits`` say
    which are compared.  ``makespan_mismatch_share``: share of update 1's
    episodes whose makespan is off by more than 1e-6 relative;
    ``loss_gap.<u>``: update u's loss gap over the mean magnitude of its
    per-episode terms; ``grad_gap``: the first gradient as AdamW took it,
    worst leaf; ``delta_gap``: the parameters' change over the updates,
    worst leaf, leaving out leaves whose reference gradient is nought to
    rounding.  Both runs start update 1 from the same weights; rounding
    flips a few decisions there, and AdamW's first, sign-like steps spread
    them, so the later updates' makespans are not steady enough to
    compare."""
    ms_p, ms_r = prog["makespans"], [u["makespans"] for u in r]
    off = np.abs(ms_p[0] - ms_r[0]) > 1e-6 * np.abs(ms_r[0])
    out = {"makespan_mismatch_share": float(off.mean())}
    for u, (lp, ru) in enumerate(zip(prog["loss"], r), 1):
        out[f"loss_gap.{u}"] = abs(lp - ru["loss"]) / ru["loss_scale"]
    out["grad_gap"] = common.rel_gap_by_leaf(prog["grad"], r[0]["grad"])[0]
    tm = jax.tree_util.tree_map
    d_p = tm(lambda a, b: a - b, prog["params"], prog["p0"])
    d_r = tm(lambda a, b: a - b, r[-1]["params"], prog["p0"])
    out["delta_gap"] = common.rel_gap_by_leaf(
        d_p, d_r, skip=common.null_leaves(r[0]["grad"]))[0]
    return {k: float(v) for k, v in out.items()}
