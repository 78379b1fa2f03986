"""Smoke run of DOPPLER's main path on a TPU v5e, in one process.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the multi-chip path, 4-chip host

One chip: Stage I -> fused Stage II (sampling scan -> ``gnn_mp`` encoder ->
``wc_oracle`` -> gradient -> AdamW) on ``model:olmo_1b``, run once with
the Pallas kernels and once with their XLA twins, which must agree
decision for decision; the WC oracle checked against the serial
simulator; then the full-depth hierarchical CLI run with Stage III on
the real executor.  ``--four-chips`` runs only the paths that span
chips: sharded Stage II against one device, and the executor placing
the fleet's four devices on four chips.

The first act is the device check: on any platform other than ``tpu``
the script exits non-zero before any work.  No exception is caught, so
a failing phase fails the script.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed on the way include compilation and are not measurements.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

WORKLOAD = "model:olmo_1b"          # the zoo's default seq 256: 95 vertices
FLEET = "tpu_v5e_2x2"
SEED = 0
BATCH = 256                         # Stage-II episodes per update
UPDATES = 4
STAGE1_EPISODES, STAGE1_BATCH = 16, 8
EXEC_REPEATS = 5                    # executor replays per placement
# f64 serial-reference misses allowed, each explained by f32 arithmetic
MAX_TIE_MISSES = 2
CLI_ARGS = ["--graph", "model:olmo_1b:full", "--devices", FLEET,
            "--engine", "fused", "--hierarchy", "512",
            "--stage1", "4", "--stage2", "2", "--stage2-batch", "1024",
            "--stage3", "2", "--stage3-batch", "2", "--system", "executor"]


def check_device(n_chips: int) -> dict:
    """The TPU JAX sees, as it reports it; anything else is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX found "
                         f"{len(devs)}")
    print(f"device: {devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}", flush=True)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def count_cache_hits() -> list:
    """A one-element counter of persistent compilation-cache hits."""
    import jax
    hits = [0]

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits[0] += 1

    jax.monitoring.register_event_listener(on_event)
    return hits


def check_compiled_kernels(step, *args) -> int:
    """Compile ``step`` for ``args`` and count the Pallas kernels that
    reached the chip compiled (``tpu_custom_call``), not interpreted."""
    hlo = step.lower(*args).compile().as_text()
    n = hlo.count("tpu_custom_call")
    if n == 0:
        raise AssertionError("the Pallas Stage-II step holds no "
                             "tpu_custom_call: its kernels were interpreted")
    return n


class F32Costs:
    """A fleet's cost model in float32, as the WC oracle evaluates it
    (``SimGraph.build``).  The serial ``WCSimulator`` on these costs is
    the reference at the oracle's precision: where it meets the oracle
    but the f64 simulator does not, only rounding separates the two
    schedules, i.e. two equal completion times were ordered differently."""

    def __init__(self, dev):
        self.dev, self.n = dev, dev.n
        self.lat = dev.link_latency.astype(np.float32)
        self.bw = dev.link_bw.astype(np.float32)

    def exec_time(self, flops, d):
        return np.float32(self.dev.exec_time(flops, d))

    def transfer_time(self, nbytes, s, d):
        if s == d:
            return np.float32(0.0)
        return self.lat[s, d] + np.float32(nbytes) / self.bw[s, d]


def flat_phase():
    """Pallas vs XLA fused Stage II from one seed, the oracle against the
    serial simulator, and the compiled kernels in the Pallas step."""
    import jax
    import jax.numpy as jnp

    from repro.core.assign import rollout_batch
    from repro.core.devices import get_device_model
    from repro.core.sim_jax import SimGraph, makespan_fifo_batch
    from repro.core.simulator import WCSimulator
    from repro.core.train_fused import (FusedStage2Config, RewardStats,
                                        build_fused_stage2)
    from repro.core.training import DopplerTrainer
    from repro.graphs.workloads import get_workload

    g, dev = get_workload(WORKLOAD), get_device_model(FLEET)
    print(f"[flat] {WORKLOAD}: {g.n} vertices, {g.m} edges on {FLEET}",
          flush=True)

    def train(backend):
        t0 = time.perf_counter()
        tr = DopplerTrainer(g, dev, seed=SEED, encoder_backend=backend,
                            oracle_backend=backend)
        nll = tr.stage1_imitation_fused(STAGE1_EPISODES,
                                        batch_size=STAGE1_BATCH)
        ms = np.asarray(tr.stage2_fused(UPDATES, batch_size=BATCH))
        ms = ms.reshape(UPDATES, BATCH)
        if not np.isfinite(ms).all():
            raise AssertionError(f"{backend}: non-finite makespans")
        print(f"[flat] {backend}: stage I nll {nll[0]:.4f} -> "
              f"{nll[-1]:.4f}; stage II mean makespan per update (ms) "
              f"{np.round(ms.mean(1) * 1e3, 4).tolist()}; "
              f"wall incl. compile {time.perf_counter() - t0:.1f}s",
              flush=True)
        return tr, np.asarray(nll), ms

    tr_p, nll_p, ms_p = train("pallas")
    _, nll_x, ms_x = train("xla")
    print(f"[flat] stage I nll |pallas - xla| max "
          f"{np.abs(nll_p - nll_x).max():.3g}", flush=True)
    same = (ms_p == ms_x).all(axis=1)
    print(f"[flat] stage II makespans identical per update: "
          f"{same.tolist()}", flush=True)
    if not same.all():
        raise AssertionError("Pallas and XLA fused Stage II diverged: "
                             f"{int((ms_p != ms_x).sum())} of {ms_p.size} "
                             "episode makespans differ")

    # the oracle alone, on one batch of sampled assignments: both backends
    # on the chip against the serial simulator, in f32 and in f64
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), BATCH)
    A = np.asarray(rollout_batch(tr_p.params, tr_p.gd, keys,
                                 jnp.float32(0.2),
                                 encoder_backend="pallas")["assignment"])
    sg = SimGraph.build(g, dev)
    ms_pl, ok_pl = makespan_fifo_batch(sg, A, backend="pallas")
    ms_xl, ok_xl = makespan_fifo_batch(sg, A, backend="xla")
    if not (np.asarray(ok_pl).all() and np.asarray(ok_xl).all()):
        raise AssertionError("WC oracle flagged non-converged episodes")
    ms_pl, ms_xl = np.asarray(ms_pl), np.asarray(ms_xl)
    if not np.array_equal(ms_pl, ms_xl):
        raise AssertionError("Pallas and XLA oracles disagree on "
                             f"{int((ms_pl != ms_xl).sum())} episodes")
    ref32, ref64 = (np.array([WCSimulator(g, d, choose="fifo",
                                          noise_sigma=0.0).run(a).makespan
                              for a in A])
                    for d in (F32Costs(dev), dev))
    np.testing.assert_allclose(ms_pl, ref32, rtol=2e-4, atol=0)
    miss = np.flatnonzero(~np.isclose(ms_pl, ref64, rtol=2e-4, atol=0))
    print(f"[flat] oracle on {BATCH} assignments: pallas == xla on the "
          f"chip; the serial WCSimulator on f32 costs agrees on all "
          f"{BATCH} ({int((ms_pl == ref32).sum())} bit-identical); on "
          f"f64 costs within rtol 2e-4 on {BATCH - len(miss)} (max rel "
          f"err {np.abs(ms_pl / ref64 - 1).max():.3g}; misses "
          f"{miss.tolist()}, each met by the f32 run: a tie order)",
          flush=True)
    if len(miss) > MAX_TIE_MISSES:
        raise AssertionError(f"{len(miss)} of {BATCH} oracle makespans "
                             "miss the f64 serial reference")

    cfg = FusedStage2Config(batch_size=BATCH, updates=UPDATES,
                            encoder_backend="pallas",
                            oracle_backend="pallas")
    step = build_fused_stage2(cfg, tr_p.gd, sg, tr_p.lr_sched,
                              tr_p.eps_sched)
    n = check_compiled_kernels(step, tr_p.params, tr_p.opt_state,
                               RewardStats.make(), tr_p.key,
                               jnp.int32(tr_p.episode))
    print(f"[flat] compiled Pallas Stage-II step: {n} tpu_custom_call "
          f"sites", flush=True)


def cli_phase():
    """The full-depth hierarchical CLI run, Stage III on the executor."""
    from repro.core.simulator import WCSimulator
    from repro.launch.doppler_train import main as train_main

    t0 = time.perf_counter()
    out = train_main(list(CLI_ARGS))
    sim = WCSimulator(out["graph"], out["devices"], choose="fifo",
                      noise_sigma=0.0)
    sim_t, sim_cp = sim.run_batch(
        np.stack([out["assignment"], out["cp_assignment"]]), seeds=[0])[:, 0]
    got = np.array([out["mean_s"], out["cp_mean_s"], sim_t, sim_cp])
    if not np.isfinite(got).all():
        raise AssertionError(f"non-finite makespans: {got}")
    print(f"[cli] final placement vs CP, measured on {out['engine']}: "
          f"{out['mean_s'] * 1e3:.3f} ms vs {out['cp_mean_s'] * 1e3:.3f} ms; "
          f"simulated (noise-free WC twin of {out['devices'].name}): "
          f"{sim_t * 1e3:.3f} ms vs {sim_cp * 1e3:.3f} ms; "
          f"wall incl. compile {time.perf_counter() - t0:.1f}s", flush=True)


def four_chip_phase():
    """Sharded Stage II against one device, and the executor's plan
    placed over four real chips."""
    import jax

    from repro.core.devices import get_device_model
    from repro.core.executor import WCExecutor
    from repro.core.heuristics import critical_path_assignment
    from repro.core.simulator import WCSimulator
    from repro.core.training import DopplerTrainer
    from repro.graphs.workloads import get_workload

    g, dev = get_workload(WORKLOAD), get_device_model(FLEET)
    print(f"[4chip] {WORKLOAD}: {g.n} vertices on {FLEET}", flush=True)

    def one_update(n_devices):
        tr = DopplerTrainer(g, dev, seed=SEED, encoder_backend="pallas",
                            oracle_backend="pallas")
        ms = np.asarray(tr.stage2_fused(1, batch_size=BATCH,
                                        n_devices=n_devices))
        leaves = jax.tree_util.tree_leaves
        return (ms, [np.asarray(x) for x in leaves(tr.params)],
                [np.asarray(x) for x in leaves(tr.opt_state.mu)],
                float(tr.lr_sched(0)))

    ms4, p4, mu4, lr = one_update(4)
    ms1, p1, mu1, _ = one_update(1)
    if not np.array_equal(ms4, ms1):
        raise AssertionError("n_devices=4 and n_devices=1 sampled "
                             f"different episodes: "
                             f"{int((ms4 != ms1).sum())} makespans differ")
    # after one AdamW step the first moment is (1 - b1) x the all-reduced
    # gradient: only its reduction order differs across chips
    scale = max(float(np.abs(m).max()) for m in mu1)
    dmu = max(float(np.abs(a - b).max()) for a, b in zip(mu4, mu1))
    dp = max(float(np.abs(a - b).max()) for a, b in zip(p4, p1))
    print(f"[4chip] stage II, one update of {BATCH} episodes: makespans "
          f"identical on 4 chips and on 1; first moment max |diff| "
          f"{dmu:.3g} of max {scale:.3g}; params max |diff| {dp:.3g} "
          f"(lr {lr:.3g})", flush=True)
    for a, b in zip(mu4, mu1):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * scale)
    # Adam's first step moves a param by lr * g / (|g| + eps), whose slope
    # in g is at most 1 / eps: a param may differ by lr / eps times its
    # gradient's difference (g = mu / (1 - b1); AdamW's defaults), plus
    # f32 rounding.  This bites on gradients near eps, such as the
    # rounding noise of the biases whose exact gradient is zero.
    for pa, pb, ma, mb in zip(p4, p1, mu4, mu1):
        bound = (lr / 1e-8 * np.abs(ma - mb) / 0.1
                 + 2.0 ** -22 * np.abs(pb) + 1e-6 * lr)
        if (np.abs(pa - pb) > bound).any():
            raise AssertionError("params on 4 chips and on 1 differ by "
                                 "more than their gradients explain")

    ex = WCExecutor(g, n_virtual=dev.n)
    if len(set(ex.devices)) != dev.n:
        raise AssertionError(f"{dev.n} fleet devices share "
                             f"{len(set(ex.devices))} chips")
    placements = {"spread (CP)": critical_path_assignment(g, dev, seed=SEED),
                  "all on device 0": np.zeros(g.n, np.int64)}
    for name, a in placements.items():
        plan = ex.compile_plan(a)
        for v, d, _, _, _, base in plan.steps:
            if base.devices() != {ex.devices[d]}:
                raise AssertionError(f"{name}: vertex {v}'s operand sits "
                                     f"on {base.devices()}, not on "
                                     f"{ex.devices[d]}")
        chips = {next(iter(s[5].devices())) for s in plan.steps}
        print(f"[4chip] {name}: {len(plan.steps)} steps on chips "
              f"{sorted(c.id for c in chips)}, {plan.n_transfers} transfers",
              flush=True)
        if name.startswith("spread") and len(chips) != dev.n:
            raise AssertionError(f"spread placement covers {len(chips)} "
                                 f"chips, not {dev.n}")
    A = np.stack(list(placements.values()))
    measured = np.median(ex.execute_batch(A, repeats=EXEC_REPEATS), axis=1)
    sim = WCSimulator(g, dev, choose="fifo", noise_sigma=0.0)
    for (name, a), t in zip(placements.items(), measured):
        print(f"[4chip] {name}: measured on the executor {t * 1e3:.3f} ms "
              f"(median of {EXEC_REPEATS}); simulated (noise-free WC twin) "
              f"{sim.run(a).makespan * 1e3:.3f} ms", flush=True)
    if not np.isfinite(measured).all():
        raise AssertionError(f"non-finite executor times: {measured}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span 4 chips")
    args = ap.parse_args(argv)
    device = check_device(4 if args.four_chips else 1)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    hits = count_cache_hits()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        flat_phase()
        cli_phase()
    print(f"done in {time.perf_counter() - t0:.1f}s; persistent compile "
          f"cache {cache_dir}: {hits[0]} hits", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
