"""End-to-end Stage-II training throughput: the PR-2 batched path
(`stage2_sim_batched`: vmapped sampling + numpy reward sweep + forced-
replay gradient) vs the fused device-resident engine (`stage2_fused`:
one jitted sample->score->update step, U updates per dispatch,
train_fused.py), in updates/sec at batch=32.

Rows (per workload: 512-vertex synthetic layered + the paper's
llama layer):

    train_<tag>_batched,    us_per_update, upd_per_sec + eps_per_sec
    train_<tag>_fused,      us_per_update, upd_per_sec + eps_per_sec
                            + speedup + devices
    train_<tag>_fused_b{K}, us_per_update, upd_per_sec + eps_per_sec
                            (fused path only — the Pallas-oracle scaling
                            regime; the host-reward path has no
                            large-batch story to tell).  K=256 and a
                            K=512 smoke row (one timed update,
                            interpret-mode-safe on CPU) run by default;
                            the K=1024 / K=2048 scale rows ride
                            REPRO_FULL=1 or --scale.

Protocol: both trainers run the canonical noise-free fifo Stage-II
configuration (the zoo_sweep setting).  Timing alternates R rounds of
each path and reports the per-path median (robust to the shared-CPU
drift this container shows); the speedup is the ratio of medians.
Correctness is cross-checked on every run: a small fused run must
reproduce the reference `stage2_sim_batched(engine='serial')` reward
trajectory (the same episodes are sampled bit-for-bit at eps=0).

The acceptance bar for the 512-vertex case is >= 3x; a miss prints a
warning, not a hard failure (wall-clock on shared CI boxes is noisy).

Run via `python -m benchmarks.run train` (sets the 2-device XLA flag) or
standalone: python benchmarks/bench_training.py
"""
from __future__ import annotations

import os

# must be set before jax initializes: the fused engine shards its episode
# batch across XLA CPU devices (benchmarks/run.py injects the same flag)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")

import time

import numpy as np

from common import FULL, budget, emit

import jax

from repro.core.devices import p100_box
from repro.core.simulator import WCSimulator
from repro.core.training import DopplerTrainer
from repro.graphs.workloads import llama_layer, synthetic_layered

BATCH = 32
ROUNDS = budget(3, 6)
UPD_OLD = budget(2, 6)        # timed updates per round, old path
UPD_FUSED = budget(12, 24)    # timed updates per round, fused path


def _check_fused_matches_reference(graph, dev) -> None:
    """Small-run guard: fused == reference trajectories (eps=0)."""
    kw = dict(seed=0, d_hidden=16, total_episodes=200, eps0=0.0, eps1=0.0)
    sim0 = WCSimulator(graph, dev, choose="fifo", noise_sigma=0.0)
    ref = DopplerTrainer(graph, dev, **kw)
    t_ref = ref.stage2_sim_batched(2, sim0, batch_size=4,
                                   sim_engine="serial")
    fus = DopplerTrainer(graph, dev, **kw)
    t_fus = fus.stage2_fused(2, batch_size=4, updates_per_dispatch=2)
    assert np.allclose(t_ref, t_fus, rtol=2e-4), \
        "fused engine diverged from the reference Stage-II path"


def bench_graph(tag: str, graph, dev, *, check_speedup: float | None = None):
    n_devices = jax.local_device_count()
    sim = WCSimulator(graph, dev, choose="fifo", noise_sigma=0.0)
    tr_old = DopplerTrainer(graph, dev, seed=0, total_episodes=100_000)
    tr_fused = DopplerTrainer(graph, dev, seed=0, total_episodes=100_000)

    # compile both paths outside the timed region
    tr_old.stage2_sim_batched(1, sim, batch_size=BATCH)
    tr_fused.stage2_fused(UPD_FUSED, batch_size=BATCH,
                          updates_per_dispatch=UPD_FUSED,
                          n_devices=n_devices)

    t_old, t_fused = [], []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        tr_old.stage2_sim_batched(UPD_OLD, sim, batch_size=BATCH)
        t_old.append((time.perf_counter() - t0) / UPD_OLD)
        t0 = time.perf_counter()
        tr_fused.stage2_fused(UPD_FUSED, batch_size=BATCH,
                              updates_per_dispatch=UPD_FUSED,
                              n_devices=n_devices)
        t_fused.append((time.perf_counter() - t0) / UPD_FUSED)
    med_old = sorted(t_old)[len(t_old) // 2]
    med_fused = sorted(t_fused)[len(t_fused) // 2]
    speedup = med_old / med_fused

    emit(f"train_{tag}_batched", med_old * 1e6,
         f"upd_per_sec={1.0 / med_old:.2f} eps_per_sec={BATCH / med_old:.1f} "
         f"batch={BATCH} n={graph.n}")
    emit(f"train_{tag}_fused", med_fused * 1e6,
         f"upd_per_sec={1.0 / med_fused:.2f} "
         f"eps_per_sec={BATCH / med_fused:.1f} batch={BATCH} "
         f"speedup={speedup:.2f}x devices={n_devices}")
    if check_speedup is not None and speedup < check_speedup:
        print(f"# WARNING: train_{tag} fused speedup {speedup:.2f}x below "
              f"the {check_speedup:.0f}x acceptance bar")
    return speedup


def bench_fused_large_batch(tag: str, graph, dev, *, batch: int = 256,
                            upd: int | None = None,
                            rounds: int | None = None,
                            n_devices: int | None = None):
    """Fused-path throughput at Stage-II scale-out batch sizes.

    Batches above 512 default to one timed update per round — at ~1e6
    episode-steps per update the per-update wall clock already dwarfs
    dispatch overhead, and CI smoke rows must stay cheap.
    The engine auto-chunks (sampling chunks of <=128 episodes, gradient
    accumulation chunks of <=64), so peak memory stays flat in batch.

    ``n_devices=1`` measures the chunked engine alone — the right
    protocol for the per-episode scaling rows on hosts where the forced
    2-virtual-device XLA split shares one physical core (the shard
    threads time-slice and the all-reduce busy-waits, taxing every row
    by a constant factor that has nothing to do with batch scaling).
    The default (all local devices) exercises shard_map + chunking
    together, which is what the CI smoke row wants."""
    if n_devices is None:
        n_devices = jax.local_device_count()
    if upd is None:
        upd = budget(3, 8) if batch <= 256 else 1
    if rounds is None:
        rounds = ROUNDS
    tr = DopplerTrainer(graph, dev, seed=0, total_episodes=1_000_000)
    tr.stage2_fused(upd, batch_size=batch, updates_per_dispatch=upd,
                    n_devices=n_devices)            # compile
    ts = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        tr.stage2_fused(upd, batch_size=batch, updates_per_dispatch=upd,
                        n_devices=n_devices)
        ts.append((time.perf_counter() - t0) / upd)
    # min, not median: a compiled dispatch's wall time has a hard floor
    # and one-sided noise (external load only ever adds time), and at
    # tens of seconds per round we can't afford enough rounds for a
    # stable median — the fastest round is the least-contaminated sample
    best = min(ts)
    emit(f"train_{tag}_fused_b{batch}", best * 1e6,
         f"upd_per_sec={1.0 / best:.2f} batch={batch} "
         f"eps_per_sec={batch / best:.1f} devices={n_devices}")


def main(argv: list[str] | None = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--scale", action="store_true",
                    default=os.environ.get("REPRO_SCALE", "0") == "1",
                    help="also run the batch-1024/2048 scale rows "
                         "(or REPRO_SCALE=1; always on under "
                         "REPRO_FULL=1)")
    args, _ = ap.parse_known_args(argv)

    dev = p100_box()
    g512 = synthetic_layered(32, 16)
    _check_fused_matches_reference(g512, dev)
    bench_graph("512v", g512, dev, check_speedup=3.0)
    bench_graph("llama_layer", llama_layer(), dev)
    # per-episode scaling rows: single-device = pure chunked engine
    bench_fused_large_batch("512v", g512, dev, batch=256, n_devices=1)
    # CI smoke at the chunked-engine threshold: one timed update, batch
    # 512, sharded over all local devices (shard_map + chunking
    # together; oracle interpret-mode on CPU)
    bench_fused_large_batch("512v", g512, dev, batch=512, upd=1)
    if FULL or args.scale:
        # thousands-of-episodes dispatches: the tentpole scaling regime
        bench_fused_large_batch("512v", g512, dev, batch=1024,
                                n_devices=1)
        bench_fused_large_batch("512v", g512, dev, batch=2048,
                                n_devices=1)
    if FULL:
        bench_graph("1024v", synthetic_layered(64, 16), dev)
        bench_fused_large_batch("1024v", synthetic_layered(64, 16), dev,
                                batch=1024)


if __name__ == "__main__":
    main()
