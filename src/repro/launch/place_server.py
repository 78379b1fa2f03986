"""Zero-shot placement serving: pretrained cross-graph dual policy behind
a fingerprint-keyed LRU cache.

The offline story (ROADMAP item 1): ``training.pretrain`` learns ONE
dual-policy parameter set across the model zoo x heterogeneous fleets.
This module is the online half — a :class:`PlacementServer` that answers
"place this graph on this fleet" requests:

* **cache hit** — the (graph topo-hash, fleet fingerprint) pair was
  served before; the stored placement is returned in microseconds.
  ``topo_hash`` ignores labels, so a cosmetically relabeled graph is the
  same key, and two graphs with equal hashes are placement-equivalent.
* **cache miss** — a zero-shot greedy rollout of the pretrained policy
  (``core.zero_shot``, pure numpy: no XLA compile on the serving path)
  plus a couple of CRITICAL-PATH candidates are scored by the noise-free
  batched simulator and the best one is served.  Because CP is always in
  the candidate pool, the served makespan is <= CP's by construction.
* **fine-tune (optional)** — with a positive ``fine_tune_budget_s`` the
  miss path additionally warm-starts a :class:`DopplerTrainer` from the
  pretrained params and runs batched REINFORCE updates until the
  wall-clock budget is spent, serving the best assignment seen anywhere.

CPU smoke:
  PYTHONPATH=src python -m repro.launch.place_server \
      --workload model:olmo_1b --fleet mixed_gen4 --seq 32
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import time

import numpy as np

from ..core.devices import DeviceModel, get_device_model
from ..core.features import COMM_FACTOR_DEFAULT
from ..core.graph import DataflowGraph, topo_hash
from ..core.heuristics import critical_path_assignment
from ..core.simulator import WCSimulator
from ..core.zero_shot import greedy_place, to_numpy_params


@dataclasses.dataclass
class PlaceRequest:
    graph: DataflowGraph
    dev: DeviceModel
    fine_tune_budget_s: float = 0.0


@dataclasses.dataclass
class PlaceResult:
    assignment: np.ndarray
    makespan: float          # noise-free WC-sim makespan (seconds)
    source: str              # 'policy' | 'cp' | 'fine_tuned'
    cache_hit: bool
    latency_s: float         # server-side wall clock for this request


class PlacementServer:
    """Batch placement API over one pretrained parameter set.

    ``params`` is a ``training.pretrain()['params']`` pytree (jax or
    numpy leaves — converted to float32 numpy up front so the serving hot
    path never touches jax).  ``meta`` is the matching ``['meta']`` dict;
    it is only needed when fine-tuning is requested (the trainer has to
    rebuild the policy hyper-shape)."""

    def __init__(self, params, meta: dict | None = None,
                 cache_size: int = 256,
                 comm_factor: float = COMM_FACTOR_DEFAULT,
                 cp_seeds: int = 2):
        self.params = to_numpy_params(params)
        self.meta = dict(meta or {})
        self.comm_factor = comm_factor
        self.cp_seeds = cp_seeds
        self.cache_size = cache_size
        self._cache: collections.OrderedDict[tuple, PlaceResult] = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    @classmethod
    def from_checkpoint(cls, ckpt_dir, **kwargs) -> "PlacementServer":
        from ..core.policy_io import load_pretrained
        pre = load_pretrained(ckpt_dir)
        return cls(pre["params"], meta=pre["meta"], **kwargs)

    # ------------------------------------------------------------- cache
    def cache_key(self, g: DataflowGraph, dev: DeviceModel) -> tuple:
        return (topo_hash(g), dev.fingerprint())

    # ------------------------------------------------------------- serve
    def place(self, g: DataflowGraph, dev: DeviceModel,
              fine_tune_budget_s: float = 0.0) -> PlaceResult:
        t0 = time.perf_counter()
        key = self.cache_key(g, dev)
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
            self.hits += 1
            return dataclasses.replace(
                hit, cache_hit=True, latency_s=time.perf_counter() - t0)
        self.misses += 1

        # candidate pool: zero-shot policy rollout + CP heuristic seeds —
        # CP in the pool makes "served <= CP" structural, not statistical
        cands = [greedy_place(self.params, g, dev, self.comm_factor)]
        sources = ["policy"]
        for s in range(self.cp_seeds):
            cands.append(critical_path_assignment(g, dev, seed=s))
            sources.append("cp")
        sim = WCSimulator(g, dev, choose="fifo", noise_sigma=0.0)
        ms = sim.run_batch(np.stack(cands), engine="batched")[:, 0]
        best = int(np.argmin(ms))
        res = PlaceResult(assignment=np.asarray(cands[best]),
                          makespan=float(ms[best]), source=sources[best],
                          cache_hit=False, latency_s=0.0)

        if fine_tune_budget_s > 0.0:
            res = self._fine_tune(g, dev, sim, res, fine_tune_budget_s)

        self._cache[key] = res
        if len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)
        return dataclasses.replace(res,
                                   latency_s=time.perf_counter() - t0)

    def place_batch(self, requests) -> list[PlaceResult]:
        """Serve a batch of :class:`PlaceRequest` (or (graph, dev)
        tuples).  Requests are independent; duplicates within the batch
        hit the cache populated by their first occurrence."""
        out = []
        for r in requests:
            if not isinstance(r, PlaceRequest):
                r = PlaceRequest(*r)
            out.append(self.place(r.graph, r.dev, r.fine_tune_budget_s))
        return out

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "cached": len(self._cache)}

    # --------------------------------------------------------- fine-tune
    def _fine_tune(self, g, dev, sim, seed_res: PlaceResult,
                   budget_s: float) -> PlaceResult:
        """Few-update Stage-II refinement under a wall-clock budget,
        warm-started from the pretrained params.  This path DOES pay jax
        dispatch/compile — that is what the budget is for; the caller
        opted out of pure zero-shot latency."""
        import jax.numpy as jnp
        import jax.tree_util as jtu

        from ..core.engine import SimRewardEngine
        from ..core.training import DopplerTrainer
        t0 = time.perf_counter()
        batch = 8
        tr = DopplerTrainer(
            g, dev, seed=0,
            d_hidden=int(self.meta.get("d_hidden", 64)),
            gnn_layers=int(self.meta.get("gnn_layers", 2)),
            lr0=3e-3, lr1=1e-5, total_episodes=max(batch * 64, 1),
            comm_factor=self.comm_factor)
        tr.params = jtu.tree_map(jnp.asarray, self.params)
        eng = SimRewardEngine(sim, sim_engine="batched")
        while time.perf_counter() - t0 < budget_s:
            tr._batched_rl_update(eng, batch, "serve_ft")
        if tr.best_time < seed_res.makespan:
            return dataclasses.replace(
                seed_res, assignment=np.asarray(tr.best_assignment),
                makespan=float(tr.best_time), source="fine_tuned")
        return seed_res


# ----------------------------------------------------------------- CLI
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ckpt", default=None,
                    help="pretrained checkpoint dir (policy_io."
                         "save_pretrained); omitted = quick in-process "
                         "pretrain on a reduced zoo")
    ap.add_argument("--workload", default="model:olmo_1b")
    ap.add_argument("--fleet", default="mixed_gen4")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--fine-tune-budget", type=float, default=0.0)
    ap.add_argument("--repeat", type=int, default=2,
                    help="re-issue the request to demonstrate the cache")
    args = ap.parse_args()
    from .compile_cache import use_compile_cache
    use_compile_cache()

    from ..graphs.workloads import get_workload
    if args.ckpt:
        server = PlacementServer.from_checkpoint(args.ckpt)
    else:
        from ..core.training import pretrain, zoo_pretrain_tasks
        tasks = zoo_pretrain_tasks(archs=("gemma_2b", "phi4_mini_3p8b"),
                                   seq=16, n_synthetic=1)
        pre = pretrain(tasks, rounds=1, batch_size=4,
                       imitation_episodes=1)
        server = PlacementServer(pre["params"], meta=pre["meta"])

    kwargs = {"seq": args.seq} if args.workload.startswith("model:") else {}
    g = get_workload(args.workload, **kwargs)
    dev = get_device_model(args.fleet)
    for i in range(max(args.repeat, 1)):
        r = server.place(g, dev, fine_tune_budget_s=args.fine_tune_budget)
        print(f"[{i}] {args.workload} on {args.fleet}: "
              f"makespan={r.makespan*1e3:.2f}ms source={r.source} "
              f"cache_hit={r.cache_hit} latency={r.latency_s*1e3:.1f}ms")
    print(f"server stats: {server.stats()}")


if __name__ == "__main__":
    main()
