"""DOPPLER three-stage training (paper §5).

Stage I   imitation of the CRITICAL-PATH teacher (Eq. 9)
Stage II  REINFORCE against the WC digital-twin simulator (Eq. 10)
Stage III REINFORCE against the real system (same objective, rewards from
          observed wall-clock of a real WC executor)

Policy-gradient details per §6.1: lr 1e-4 linearly decayed to 1e-7,
exploration eps 0.2 linearly decayed to 0, entropy weight 1e-2, baseline =
running mean of all previous episode rewards (§4.1).  Advantage
normalization by the running reward std is an addition for stability
(recorded in DESIGN.md §10) and can be disabled.

`FleetTrainer` at the bottom implements Appendix I's scale-out recipe: one
policy per unique (repeated) block graph, the assignment replicated across
data-parallel replicas/pods, with rewards aggregated across the fleet.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..train.optim import AdamState, adamw_init, adamw_update, linear_schedule
from .assign import GraphData, build_graph_data, rollout, rollout_batch
from .devices import DeviceModel, FleetEvent
from .engine import RewardEngine, SimRewardEngine, as_engine
from .graph import DataflowGraph
from .heuristics import critical_path_assignment
from .policies import init_policies
from .simulator import WCSimulator


# ------------------------------------------------------------------ losses
@partial(jax.jit, static_argnames=("sel_learned", "plc_learned",
                                   "encoder_backend"))
def _pg_loss_and_grad(params, gd: GraphData, key, actions, advantage,
                      entropy_w, sel_learned: bool = True,
                      plc_learned: bool = True,
                      encoder_backend: str = "xla"):
    def loss_fn(p):
        out = rollout(p, gd, key, jnp.float32(0.0), actions,
                      jnp.array(True), greedy=False,
                      encoder_backend=encoder_backend)
        logp = 0.0
        ent = 0.0
        if sel_learned:
            logp = logp + out["sel_logp"].sum()
            ent = ent + out["sel_ent"].mean()
        if plc_learned:
            logp = logp + out["plc_logp"].sum()
            ent = ent + out["plc_ent"].mean()
        return -(advantage * logp + entropy_w * ent)

    return jax.value_and_grad(loss_fn)(params)


@partial(jax.jit, static_argnames=("sel_learned", "plc_learned",
                                   "encoder_backend"))
def _pg_loss_and_grad_batch(params, gd: GraphData, keys, actions,
                            advantages, entropy_w,
                            sel_learned: bool = True,
                            plc_learned: bool = True,
                            encoder_backend: str = "xla"):
    """Batch-averaged REINFORCE: K replayed episodes, one gradient.

    Like `_pg_loss_and_grad`, the Table-3 ablation modes drop the
    heuristic-replaced policy's log-prob/entropy terms from the loss, so
    `stage2_sim_batched` trains only the learned head(s)."""
    def loss_fn(p):
        def one(key, act, adv):
            out = rollout(p, gd, key, jnp.float32(0.0), act,
                          jnp.array(True), greedy=False,
                          encoder_backend=encoder_backend)
            logp = 0.0
            ent = 0.0
            if sel_learned:
                logp = logp + out["sel_logp"].sum()
                ent = ent + out["sel_ent"].mean()
            if plc_learned:
                logp = logp + out["plc_logp"].sum()
                ent = ent + out["plc_ent"].mean()
            return -(adv * logp + entropy_w * ent)

        return jax.vmap(one)(keys, actions, advantages).mean()

    return jax.value_and_grad(loss_fn)(params)


@partial(jax.jit, static_argnames=("encoder_backend",))
def _imitation_loss_and_grad(params, gd: GraphData, key, teacher_actions,
                             encoder_backend: str = "xla"):
    def loss_fn(p):
        out = rollout(p, gd, key, jnp.float32(0.0), teacher_actions,
                      jnp.array(True), greedy=False,
                      encoder_backend=encoder_backend)
        return -(out["sel_logp"].mean() + out["plc_logp"].mean())

    return jax.value_and_grad(loss_fn)(params)


# ----------------------------------------------------------------- trainer
@dataclasses.dataclass
class EpisodeRecord:
    episode: int
    stage: str
    exec_time: float
    best_so_far: float


@dataclasses.dataclass
class ReplaceResult:
    """Outcome of one :meth:`DopplerTrainer.replace` call.

    ``makespan_before`` is the surviving-device projection of the OLD
    placement scored on the NEW fleet — what the system would run at if
    it kept the stale placement; ``makespan`` is the re-placed result.
    ``cp_makespan`` is the best CRITICAL-PATH candidate in the pool (on
    the new fleet), so ``makespan <= cp_makespan`` is structural whenever
    CP seeds made it into the pool."""
    assignment: np.ndarray          # flat-graph assignment on the new fleet
    makespan: float
    makespan_before: float
    cp_makespan: float
    source: str                     # 'projected' | 'policy' | 'cp' | 'refined'
    latency_s: float
    budget_s: float
    within_budget: bool
    fleet_fingerprint: str
    event: "FleetEvent | None" = None
    refine_rounds: int = 0
    refine_moves: int = 0
    n_candidates: int = 0


class DopplerTrainer:
    """Owns the dual-policy parameters and runs the three stages."""

    def __init__(self, graph: DataflowGraph, dev: DeviceModel, seed: int = 0,
                 d_hidden: int = 64, gnn_layers: int = 2,
                 lr0: float = 1e-4, lr1: float = 1e-7,
                 eps0: float = 0.2, eps1: float = 0.0,
                 entropy_weight: float = 1e-2,
                 total_episodes: int = 4000,
                 normalize_adv: bool = True,
                 comm_factor: float = 4.0,
                 sel_mode: str = "learned", plc_mode: str = "learned",
                 hierarchy=None, encoder_backend: str = "xla",
                 oracle_backend: str = "xla"):
        # Hierarchical mode (core/hierarchy.py): coarsen the flat graph and
        # train the *unchanged* dual policy on the segment graph — every
        # stage, engine, and checkpoint below operates at segment level;
        # `place()` expands + refines back to the flat graph.
        self.flat_graph = graph
        self.hier = None
        self.hierarchy = None
        if hierarchy is not None:
            from ..graphs.partition import coarsen_multilevel
            from .hierarchy import HierarchicalPolicy, HierarchyConfig
            if isinstance(hierarchy, int):
                hierarchy = HierarchyConfig(n_segments=hierarchy)
            # V-cycle coarsening: bounded contraction per level, so 100k+
            # vertex graphs reach the policy through a stack of partitions
            # instead of one extreme-ratio contraction.  Graphs within
            # max_ratio of n_segments get exactly one level — identical
            # to the old single-shot coarsen.
            part = coarsen_multilevel(graph, hierarchy.n_segments,
                                      cap_factor=hierarchy.cap_factor,
                                      max_ratio=hierarchy.max_ratio,
                                      max_levels=hierarchy.max_levels)
            self.hierarchy = hierarchy
            self.hier = HierarchicalPolicy(part, hierarchy, dev)
            graph = part.seg_graph
        self.g, self.dev = graph, dev
        self.comm_factor = comm_factor
        self.gd = build_graph_data(graph, dev, comm_factor)
        key = jax.random.PRNGKey(seed)
        self.key, pkey = jax.random.split(key)
        self.params = init_policies(pkey, d_hidden=d_hidden,
                                    gnn_layers=gnn_layers)
        self.opt_state: AdamState = adamw_init(self.params)
        self.lr_sched = linear_schedule(lr0, lr1, total_episodes)
        self.eps_sched = linear_schedule(eps0, eps1, total_episodes)
        self.entropy_weight = entropy_weight
        self.total_episodes = total_episodes
        self.normalize_adv = normalize_adv
        # Table-3 ablation modes: 'learned' | 'cp' (SEL) / 'etf' (PLC)
        self.sel_mode, self.plc_mode = sel_mode, plc_mode
        # accelerator backends: "xla" reference paths or the Pallas
        # kernels (gnn.ENCODER_BACKENDS / sim_jax.ORACLE_BACKENDS) —
        # decision-exact twins, pinned by the conformance/property suites
        from .gnn import ENCODER_BACKENDS
        from .sim_jax import ORACLE_BACKENDS
        if encoder_backend not in ENCODER_BACKENDS:
            raise ValueError(f"unknown encoder backend {encoder_backend!r};"
                             f" expected one of {ENCODER_BACKENDS}")
        if oracle_backend not in ORACLE_BACKENDS:
            raise ValueError(f"unknown oracle backend {oracle_backend!r};"
                             f" expected one of {ORACLE_BACKENDS}")
        self.encoder_backend = encoder_backend
        self.oracle_backend = oracle_backend
        # running reward statistics (baseline = mean of past rewards, §4.1)
        self._r_sum = 0.0
        self._r_sqsum = 0.0
        self._r_count = 0
        self.episode = 0
        self.stage2_updates = 0      # fused Stage-II updates taken
        self.history: list[EpisodeRecord] = []
        self.best_assignment: np.ndarray | None = None
        self.best_time = np.inf
        self._dummy_actions = jnp.zeros((graph.n, 2), jnp.int32)

    # ------------------------------------------------------------- utils
    def _next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def _baseline(self) -> tuple[float, float]:
        if self._r_count == 0:
            return 0.0, 1.0
        mean = self._r_sum / self._r_count
        var = max(self._r_sqsum / self._r_count - mean * mean, 1e-12)
        return mean, float(np.sqrt(var))

    def _update_reward_stats(self, r: float):
        self._r_sum += r
        self._r_sqsum += r * r
        self._r_count += 1

    def sample_assignment(self, eps: float | None = None):
        eps = self.eps_sched(self.episode) if eps is None else eps
        out = rollout(self.params, self.gd, self._next_key(),
                      jnp.float32(eps), self._dummy_actions,
                      jnp.array(False), greedy=False,
                      sel_mode=self.sel_mode, plc_mode=self.plc_mode,
                      encoder_backend=self.encoder_backend)
        return np.asarray(out["assignment"]), np.asarray(out["actions"])

    def greedy_assignment(self) -> np.ndarray:
        out = rollout(self.params, self.gd, self._next_key(),
                      jnp.float32(0.0), self._dummy_actions,
                      jnp.array(False), greedy=True,
                      sel_mode=self.sel_mode, plc_mode=self.plc_mode,
                      encoder_backend=self.encoder_backend)
        return np.asarray(out["assignment"])

    def _greedy_on(self, gd: GraphData) -> np.ndarray:
        """Greedy rollout against an arbitrary GraphData (e.g. the policy
        graph re-featurized for a derived fleet) WITHOUT advancing the
        trainer's PRNG state — greedy decoding is deterministic, so
        re-placement stays replayable and side-effect-free until commit."""
        out = rollout(self.params, gd, jax.random.fold_in(self.key, 0x5EAF),
                      jnp.float32(0.0), self._dummy_actions,
                      jnp.array(False), greedy=True,
                      sel_mode=self.sel_mode, plc_mode=self.plc_mode,
                      encoder_backend=self.encoder_backend)
        return np.asarray(out["assignment"])

    def _apply_grads(self, grads):
        lr = self.lr_sched(self.episode)
        self.params, self.opt_state = adamw_update(
            grads, self.opt_state, self.params, lr)

    # ----------------------------------------------------------- Stage I
    def stage1_imitation(self, n_episodes: int, seed: int = 0,
                         log_every: int = 0) -> list[float]:
        """Teach SEL+PLC to replicate CRITICAL PATH decisions (Eq. 9)."""
        losses = []
        for i in range(n_episodes):
            _, acts = critical_path_assignment(self.g, self.dev,
                                               seed=seed + i,
                                               return_actions=True)
            loss, grads = _imitation_loss_and_grad(
                self.params, self.gd, self._next_key(), jnp.asarray(acts),
                encoder_backend=self.encoder_backend)
            self._apply_grads(grads)
            self.episode += 1
            losses.append(float(loss))
            if log_every and (i + 1) % log_every == 0:
                print(f"[stage1] ep {i+1}/{n_episodes} nll={loss:.4f}")
        return losses

    # ------------------------------------------------------ Stage II/III
    def train_rl(self, system, n_updates: int, batch_size: int = 8,
                 stage: str | None = None, serial: bool = False,
                 log_every: int = 0, **ablation) -> list[float]:
        """The engine-driven REINFORCE core shared by every RL stage.

        ``system`` is anything :func:`engine.as_engine` accepts — a
        :class:`RewardEngine`, a ``WCSimulator``, a ``WCExecutor``, a
        ``JaxWCEngine``, or a plain callable.  Each update samples
        ``batch_size`` episodes in one vmapped rollout, scores them with
        ONE ``engine.exec_times`` call, and takes one batch-averaged
        gradient step; ``serial=True`` (requires ``batch_size == 1``)
        instead replays the per-episode loop of the legacy
        ``stage2_sim`` / ``stage3_system`` paths bit-for-bit (single-
        episode advantage against the running baseline, per-episode
        gradient)."""
        eng = as_engine(system)
        if serial and batch_size != 1:
            raise ValueError("serial mode is the batch_size=1 loop")
        stage = stage or eng.name
        times: list[float] = []
        for i in range(n_updates):
            if serial:
                t = self._rl_episode(
                    lambda a: eng.exec_time(a, self.episode),
                    stage, **ablation)
                times.append(t)
            else:
                ts = self._batched_rl_update(eng, batch_size, stage,
                                             **ablation)
                times.extend(ts.tolist())
            if log_every and (i + 1) % log_every == 0:
                print(f"[{stage}] upd {i+1}/{n_updates} "
                      f"t={times[-1]*1e3:.2f}ms "
                      f"best={self.best_time*1e3:.2f}ms")
        return times

    def _rl_episode(self, exec_time_fn: Callable[[np.ndarray], float],
                    stage: str, sel_learned=None, plc_learned=None):
        if sel_learned is None:
            sel_learned = self.sel_mode == "learned"
        if plc_learned is None:
            plc_learned = self.plc_mode == "learned"
        assignment, actions = self.sample_assignment()
        t = float(exec_time_fn(assignment))
        r = -t                                   # reward = -ExecTime (§4.1)
        mean, std = self._baseline()
        adv = r - mean
        if self.normalize_adv:
            adv = adv / (std + 1e-9)
        self._update_reward_stats(r)
        _, grads = _pg_loss_and_grad(
            self.params, self.gd, self._next_key(), jnp.asarray(actions),
            jnp.float32(adv), jnp.float32(self.entropy_weight),
            sel_learned=sel_learned, plc_learned=plc_learned,
            encoder_backend=self.encoder_backend)
        self._apply_grads(grads)
        self.episode += 1
        if t < self.best_time:
            self.best_time, self.best_assignment = t, assignment
        self.history.append(EpisodeRecord(self.episode, stage, t,
                                          self.best_time))
        return t

    def stage2_sim(self, n_episodes: int, sim: WCSimulator | None = None,
                   log_every: int = 0, **ablation) -> list[float]:
        """Per-episode Stage II (the paper's serial protocol), routed
        through the engine adapter: at K=1 the engine's ``episode*K + k``
        seeds reduce to ``seed=episode`` — the legacy reward call — so
        same-seed trajectories are unchanged."""
        sim = sim or WCSimulator(self.g, self.dev, choose="fifo",
                                 noise_sigma=0.05)
        times = []
        eng = as_engine(sim)
        for i in range(n_episodes):
            t = self._rl_episode(
                lambda a: eng.exec_time(a, self.episode),
                "sim", **ablation)
            times.append(t)
            if log_every and (i + 1) % log_every == 0:
                print(f"[stage2] ep {i+1}/{n_episodes} t={t*1e3:.2f}ms "
                      f"best={self.best_time*1e3:.2f}ms")
        return times

    def _batched_rl_update(self, reward, batch_size: int, stage: str,
                           sel_learned=None, plc_learned=None) -> np.ndarray:
        """One population REINFORCE update: sample `batch_size` episodes in
        a single vmapped rollout, score them with ONE reward query —
        ``reward`` is a :class:`RewardEngine` (queried as
        ``exec_times(assignments, episode)``) or a legacy callable
        ``reward_fn(assignments) -> (K,)`` — and take one batch-averaged
        gradient step.  Shared by every engine-backed stage and
        `FleetTrainer.train`."""
        if sel_learned is None:
            sel_learned = self.sel_mode == "learned"
        if plc_learned is None:
            plc_learned = self.plc_mode == "learned"
        eps = self.eps_sched(self.episode)
        keys = jax.random.split(self._next_key(), batch_size)
        out = rollout_batch(self.params, self.gd, keys,
                            jnp.float32(eps),
                            sel_mode=self.sel_mode,
                            plc_mode=self.plc_mode,
                            encoder_backend=self.encoder_backend)
        assigns = np.asarray(out["assignment"])
        if isinstance(reward, RewardEngine):
            ts = np.asarray(reward.exec_times(assigns, self.episode))
        else:
            ts = np.asarray(reward(assigns))
        rs = -ts
        mean, std = self._baseline()
        advs = rs - (mean if self._r_count else rs.mean())
        if self.normalize_adv:
            advs = advs / (max(std, float(rs.std())) + 1e-9)
        for r in rs:
            self._update_reward_stats(float(r))
        _, grads = _pg_loss_and_grad_batch(
            self.params, self.gd, keys, out["actions"],
            jnp.asarray(advs, jnp.float32),
            jnp.float32(self.entropy_weight),
            sel_learned=sel_learned, plc_learned=plc_learned,
            encoder_backend=self.encoder_backend)
        self._apply_grads(grads)
        self.episode += batch_size
        best_k = int(ts.argmin())
        if ts[best_k] < self.best_time:
            self.best_time = float(ts[best_k])
            self.best_assignment = assigns[best_k]
        self.history.append(EpisodeRecord(self.episode, stage,
                                          float(ts.mean()), self.best_time))
        return ts

    def stage2_sim_batched(self, n_updates: int, sim: WCSimulator | None = None,
                           batch_size: int = 8, log_every: int = 0,
                           sim_engine: str = "batched", **ablation):
        """Population variant of Stage II: sample `batch_size` episodes in
        ONE vmapped rollout, evaluate their rewards against the compiled
        batch simulator (sim_batch.py), and take one batch-averaged
        REINFORCE step.  Same total-episode budget as
        `stage2_sim(n_updates * batch_size)` with ~batch_size x fewer XLA
        dispatches, a lower-variance gradient (the batch itself acts as a
        per-update baseline), and the reward oracle off the Python
        event-loop hot path.  `sim_engine='serial'` keeps the reference
        per-episode `WCSimulator.run` loop (identical results; used by the
        integration tests).  Table-3 ablations plumb through **ablation
        (`sel_learned=` / `plc_learned=`) exactly like `stage2_sim`.

        Since the engine refactor this is a thin wrapper over
        :meth:`train_rl` with a :class:`SimRewardEngine`; the engine's
        ``episode*K + k`` seed convention is exactly the seed list this
        method always built, so same-seed trajectories, params, and
        bookkeeping are bit-identical to the pre-engine path
        (tests/test_engine.py)."""
        sim = sim or WCSimulator(self.g, self.dev, choose="fifo",
                                 noise_sigma=0.05)
        eng = SimRewardEngine(sim, sim_engine=sim_engine)
        times = []
        for i in range(n_updates):
            ts = self._batched_rl_update(eng, batch_size, "sim_batch",
                                         **ablation)
            times.extend(ts.tolist())
            if log_every and (i + 1) % log_every == 0:
                print(f"[stage2b] upd {i+1}/{n_updates} "
                      f"mean={ts.mean()*1e3:.2f}ms "
                      f"best={self.best_time*1e3:.2f}ms")
        return times

    # ------------------------------------------------------ fused Stage II
    def stage2_fused(self, n_updates: int, batch_size: int = 8,
                     updates_per_dispatch: int | None = None,
                     log_every: int = 0, n_devices: int | None = None,
                     chunk_size: int | None = None,
                     grad_chunk_size: int | None = None,
                     **ablation):
        """Device-resident Stage II: rollout, reward oracle, advantage,
        gradient, and AdamW fused into one jitted step, scanned
        `updates_per_dispatch` updates per XLA call (train_fused.py).

        Rewards come from the on-device JAX WC oracle (sim_jax.py), i.e.
        the noise-free 'fifo' twin of the numpy engines; the reference
        `stage2_sim_batched(sim=WCSimulator(..., noise_sigma=0))` path
        samples the exact same episodes for the same seeds (bit-identical
        at eps=0) and is the cross-check in tests/test_train_fused.py.
        `n_devices > 1` shards the episode batch across XLA devices
        (data-parallel fused updates, single fused pmean all-reduce via
        shard_map).  `chunk_size` bounds peak memory at large batch by
        sampling/scoring in micro-chunks and accumulating the gradient
        (None auto-chunks per-device batches above 64 episodes; 0
        forces the monolithic engine); `grad_chunk_size` sizes the
        gradient-accumulation micro-chunk (None = auto).  The engine
        raises RuntimeError if the WC oracle flags any episode as
        non-converged (the flags also mask those episodes' advantages
        in-update, so no garbage makespan reaches the gradient); the
        trainer then holds that dispatch's params, with its episode index
        and reward statistics advanced to match.

        Each dispatch writes three host spans on the profiler's clock:
        ``doppler.stage2.dispatch`` (the asynchronous enqueue),
        ``doppler.stage2.sync`` (waiting for the results) and
        ``doppler.stage2.record`` (the per-update bookkeeping), each with
        ``update=`` the index in ``stage2_updates`` of the dispatch's
        first update."""
        from .sim_jax import SimGraph
        from .train_fused import (FusedStage2Config, RewardStats,
                                  build_fused_stage2)
        if n_devices is None:
            n_devices = 1
        U = updates_per_dispatch or min(n_updates, 8)
        cfg = FusedStage2Config(
            batch_size=batch_size, updates=U,
            sel_mode=self.sel_mode, plc_mode=self.plc_mode,
            sel_learned=ablation.get("sel_learned",
                                     self.sel_mode == "learned"),
            plc_learned=ablation.get("plc_learned",
                                     self.plc_mode == "learned"),
            normalize_adv=self.normalize_adv,
            entropy_weight=self.entropy_weight,
            encoder_backend=self.encoder_backend,
            oracle_backend=self.oracle_backend,
            chunk_size=chunk_size, grad_chunk_size=grad_chunk_size)
        cache = getattr(self, "_fused_cache", None)
        if cache is None:
            cache = self._fused_cache = {}
        chunk = cache.get((cfg, n_devices))
        if chunk is None:
            sg = cache.get("sim_graph")
            if sg is None:
                sg = cache["sim_graph"] = SimGraph.build(self.g, self.dev)
            chunk = cache[(cfg, n_devices)] = build_fused_stage2(
                cfg, self.gd, sg, self.lr_sched, self.eps_sched,
                n_devices=n_devices)

        rstats = RewardStats.make(self._r_sum, self._r_sqsum, self._r_count)
        times = []
        done = 0
        while done < n_updates:
            u = min(U, n_updates - done)
            if u < U:     # remainder: recompile once for the tail size
                tail_key = (cfg, n_devices, u)
                tail = cache.get(tail_key)
                if tail is None:
                    tail = cache[tail_key] = build_fused_stage2(
                        dataclasses.replace(cfg, updates=u), self.gd,
                        cache["sim_graph"], self.lr_sched, self.eps_sched,
                        n_devices=n_devices)
                step = tail
            else:
                step = chunk
            first = self.stage2_updates
            with TraceAnnotation("doppler.stage2.dispatch", update=first):
                out = step(self.params, self.opt_state, rstats,
                           self.key, jnp.int32(self.episode))
            # the dispatch donated the old params/opt state: adopt the
            # new state before anything can raise
            self.params = out["params"]
            self.opt_state = out["opt_state"]
            self.key = out["key"]
            self.stage2_updates += u
            rstats = out["rstats"]
            with TraceAnnotation("doppler.stage2.sync", update=first):
                ok = np.asarray(out["oracle_ok"])             # (u, K)
                ms = np.asarray(out["makespans"])             # (u, K)
                best_as = np.asarray(out["best_assignments"])  # (u, n)
            if not ok.all():
                # the params took these u updates: keep the schedules'
                # episode index and the reward statistics in step
                self.episode += u * batch_size
                self._keep_reward_stats(rstats)
                raise RuntimeError(
                    f"WC oracle failed to converge on "
                    f"{int((~ok).sum())}/{ok.size} episodes (deadlock); "
                    f"their advantages were masked in-update and their "
                    f"makespans discarded")
            with TraceAnnotation("doppler.stage2.record", update=first):
                for j in range(ms.shape[0]):
                    ts = ms[j]
                    self.episode += batch_size
                    if ts.min() < self.best_time:
                        self.best_time = float(ts.min())
                        self.best_assignment = best_as[j]
                    self.history.append(EpisodeRecord(
                        self.episode, "sim_fused", float(ts.mean()),
                        self.best_time))
                    times.extend(ts.tolist())
            done += ms.shape[0]
            if log_every:
                print(f"[stage2f] upd {done}/{n_updates} "
                      f"mean={ms[-1].mean()*1e3:.2f}ms "
                      f"best={self.best_time*1e3:.2f}ms")
        self._keep_reward_stats(rstats)
        return times

    def _keep_reward_stats(self, rstats):
        self._r_sum = float(rstats.r_sum)
        self._r_sqsum = float(rstats.r_sqsum)
        self._r_count = int(rstats.r_count)

    # ------------------------------------------------------- fused Stage I
    def stage1_imitation_fused(self, n_episodes: int, seed: int = 0,
                               batch_size: int = 1,
                               log_every: int = 0) -> list[float]:
        """Stage I with teacher actions precomputed once and imitation
        updates batched: the CP teacher's `n_episodes` action sequences
        are generated host-side up front, their (parameter-free) episode
        dynamics replayed in one vmapped scan, and all updates run as one
        jitted chunk of step-parallel NLL steps (train_fused.py).  With
        `batch_size=1` the update sequence matches `stage1_imitation`
        (same teacher episodes, same per-episode LR schedule) to float
        tolerance; larger batches average `batch_size` teacher episodes
        per update at the same total-episode budget."""
        from .train_fused import build_fused_stage1
        if n_episodes % batch_size:
            raise ValueError("n_episodes must be divisible by batch_size")
        acts = np.stack([
            critical_path_assignment(self.g, self.dev, seed=seed + i,
                                     return_actions=True)[1]
            for i in range(n_episodes)])
        updates = n_episodes // batch_size
        replay_dynamics, chunk = build_fused_stage1(
            self.gd, self.lr_sched, batch_size, updates,
            encoder_backend=self.encoder_backend)
        masks, x_devs = replay_dynamics(jnp.asarray(acts, jnp.int32))
        shape = (updates, batch_size)
        out = chunk(self.params, self.opt_state, self.key,
                    jnp.int32(self.episode),
                    masks.reshape(shape + masks.shape[1:]),
                    x_devs.reshape(shape + x_devs.shape[1:]),
                    jnp.asarray(acts, jnp.int32).reshape(
                        shape + acts.shape[1:]))
        self.params = out["params"]
        self.opt_state = out["opt_state"]
        self.key = out["key"]
        self.episode += n_episodes
        losses = np.asarray(out["losses"]).tolist()
        if log_every:
            print(f"[stage1f] {updates} updates nll={losses[-1]:.4f}")
        return losses

    def stage3_system(self, n_episodes: int,
                      system_exec_time: Callable[[np.ndarray], float],
                      log_every: int = 0, **ablation) -> list[float]:
        """Online refinement against the real WC executor: the reward is the
        observed wall-clock of serving real requests ("for free", §5).

        The legacy serial protocol: one episode, one real measurement,
        one gradient.  For the amortized path — one batch-averaged
        gradient per K plan-compiled executor measurements — use
        :meth:`stage3_system_batched`."""
        times = []
        for i in range(n_episodes):
            t = self._rl_episode(system_exec_time, "sys", **ablation)
            times.append(t)
            if log_every and (i + 1) % log_every == 0:
                print(f"[stage3] ep {i+1}/{n_episodes} t={t*1e3:.2f}ms "
                      f"best={self.best_time*1e3:.2f}ms")
        return times

    def stage3_system_batched(self, n_updates: int, system,
                              batch_size: int = 8, repeats: int = 1,
                              log_every: int = 0, **ablation) -> list[float]:
        """Batched Stage III: each update samples `batch_size` candidate
        assignments in one vmapped rollout, measures all of them through
        the system's batch path (for a ``WCExecutor``: one
        ``execute_batch`` call — plans cached, warmup amortized,
        `repeats` interleaved for common-random-numbers denoising), and
        takes ONE batch-averaged REINFORCE step per K measurements —
        instead of the serial loop's one gradient per episode.

        ``repeats`` is an executor-measurement concept: it applies when
        ``system`` is a ``WCExecutor`` (or an ``ExecutorRewardEngine``,
        whose executor is re-wrapped at the requested repeat count);
        passing ``repeats != 1`` with any other system is an error
        rather than a silent no-op."""
        from .engine import ExecutorRewardEngine
        from .executor import WCExecutor
        if isinstance(system, WCExecutor):
            system = ExecutorRewardEngine(system, repeats=repeats)
        elif repeats != 1:
            if isinstance(system, ExecutorRewardEngine):
                system = ExecutorRewardEngine(system.executor,
                                              repeats=repeats,
                                              reduce=system.reduce)
            else:
                raise ValueError(
                    "repeats is only meaningful for executor-backed "
                    "systems; seeded/deterministic engines replay instead")
        return self.train_rl(system, n_updates, batch_size=batch_size,
                             stage="sys_batch", log_every=log_every,
                             **ablation)

    # --------------------------------------------------- flat placement
    def place(self, engine=None, refine: bool = True,
              include_cp: bool = True, include_flat_cp: bool = False,
              episode: int | None = None) -> tuple[np.ndarray, float]:
        """Produce a *flat-graph* assignment (and its engine score).

        Flat trainers: the best-so-far (or greedy) assignment, scored.
        Hierarchical trainers: candidate segment assignments — the
        policy's greedy rollout, the best Stage-II sample, and (with
        ``include_cp``) CRITICAL-PATH runs on the segment graph — are
        expanded and scored in ONE batched engine call; the winner then
        takes a bounded boundary-refinement pass on the flat graph
        (``HierarchicalPolicy.refine``, monotone w.r.t. ``engine``).
        Multi-level trainers additionally descend the V-cycle from the
        best segment candidate (``HierarchicalPolicy.refine_levels``) and
        pool the result before the final flat refinement.

        ``include_flat_cp`` additionally seeds the candidate pool with
        CRITICAL-PATH runs on the FLAT graph (O(n x devices) python —
        seconds on 10k-vertex models, hence opt-in).  Because refinement
        is monotone, this makes ``place() <= flat CP`` a guarantee
        rather than an expectation — the warm-started hierarchical
        search never loses to the heuristic it refines.

        ``engine`` is anything :func:`engine.as_engine` accepts and must
        score FLAT assignments; default: the noise-free compiled twin.
        """
        if engine is None:
            engine = WCSimulator(self.flat_graph, self.dev, choose="fifo",
                                 noise_sigma=0.0)
        eng = as_engine(engine)
        ep = self.episode if episode is None else episode
        if self.hier is None:
            a = (self.best_assignment if self.best_assignment is not None
                 else self.greedy_assignment())
            return np.asarray(a), float(eng.exec_times(
                np.asarray(a)[None, :], ep)[0])
        cands = [self.greedy_assignment()]
        if self.best_assignment is not None:
            cands.append(np.asarray(self.best_assignment))
        if include_cp:
            # CP on the SEGMENT graph is cheap — try a few tie-break seeds
            cands += [critical_path_assignment(self.g, self.dev, seed=s)
                      for s in range(3)]
        flat = [self.hier.expand(c) for c in cands]
        if include_flat_cp:
            flat += [critical_path_assignment(self.flat_graph, self.dev,
                                              seed=s) for s in range(3)]
        flat = np.stack(flat)
        ts = np.asarray(eng.exec_times(flat, ep), dtype=float)
        k = int(ts.argmin())
        a, t = flat[k], float(ts[k])
        if self.hier.n_levels > 1:
            # V-cycle descent from the best *segment* candidate: bounded
            # refinement against each level's exact WC twin on the way
            # down recovers the quality a single extreme-ratio expand
            # throws away.  Pooled with the straight-expansion winner, so
            # it can only help.
            kseg = int(ts[:len(cands)].argmin())
            vc = self.hier.refine_levels(cands[kseg], episode=ep)
            tv = float(eng.exec_times(vc[None, :], ep)[0])
            if tv < t:
                a, t = vc, tv
        if refine:
            a, t = self.hier.refine(a, eng, episode=ep)
        return a, t

    # -------------------------------------------- dynamic-fleet re-place
    def replace(self, event: "FleetEvent | DeviceModel",
                budget_s: float = 5.0, engine=None, cp_seeds: int = 2,
                refine: bool = True, commit: bool = True) -> ReplaceResult:
        """Re-place the graph after a fleet event, warm-starting from the
        trained policy and the previous placement, under a hard
        ``budget_s`` wall-clock contract.

        ``event`` is a :class:`FleetEvent` (applied to the current fleet)
        or a same-size replacement :class:`DeviceModel` (e.g. measured
        post-degradation rates).  The candidate pool is:

        1. the surviving-device PROJECTION of the previous placement
           (:func:`hierarchy.project_assignment` — orphans of a lost
           device LPT-redistributed on the new fleet),
        2. the policy's greedy rollout against the graph RE-FEATURIZED
           for the new fleet (fleet-agnostic params, PR 6 — no gradient
           step needed),
        3. CRITICAL-PATH seeds on the new fleet (the first seed is
           unconditional, so ``makespan <= cp_makespan`` is structural;
           extra seeds only while within budget).

        All candidates are scored in ONE batched ``exec_times`` call
        through the ``RewardEngine`` protocol, then the winner takes
        deadline-bounded monotone refinement.  With ``commit=True`` the
        trainer swaps to the new fleet (graph data, fused caches, reward
        normalizer reset — old-fleet reward scale is stale) and training
        can resume immediately; ``commit=False`` leaves the trainer
        untouched (used by benchmarks for repeated timing)."""
        from .hierarchy import (RefineState, project_assignment,
                                refine_assignment)
        t0 = time.perf_counter()
        deadline = t0 + float(budget_s)
        if isinstance(event, FleetEvent):
            new_dev, smap = event.apply(self.dev)
            ev: FleetEvent | None = event
        elif isinstance(event, DeviceModel):
            if event.n != self.dev.n:
                raise ValueError(
                    "fleet size changed: pass a FleetEvent so the "
                    "survivor map can project the old placement")
            new_dev, smap, ev = event, np.arange(self.dev.n), None
        else:
            raise TypeError(f"event must be a FleetEvent or DeviceModel, "
                            f"got {type(event).__name__}")
        fp = new_dev.fingerprint()
        if engine is None:
            # the noise-free twin's compiled plan is fleet-specific and
            # dominates repeat latency — cache it per fingerprint (the
            # supervisor re-places on the same degraded fleet whenever
            # events oscillate, e.g. straggler onset/recovery)
            cache = getattr(self, "_twin_cache", None)
            if cache is None:
                cache = self._twin_cache = {}
            engine = cache.get(fp)
            if engine is None:
                if len(cache) >= 4:
                    cache.pop(next(iter(cache)))
                engine = cache[fp] = as_engine(
                    WCSimulator(self.flat_graph, new_dev, choose="fifo",
                                noise_sigma=0.0))
        eng = as_engine(engine)
        ep = self.episode
        gd_new = build_graph_data(self.g, new_dev, self.comm_factor)
        # 1. warm-start projection (at the POLICY graph level: segment
        #    assignments for hierarchical trainers, flat otherwise)
        a_prev = (np.asarray(self.best_assignment)
                  if self.best_assignment is not None
                  else self._greedy_on(self.gd))
        cands = [project_assignment(self.g, new_dev, a_prev, smap)]
        sources = ["projected"]
        # 2. policy greedy on the re-featurized graph
        cands.append(self._greedy_on(gd_new))
        sources.append("policy")
        # 3. CP seeds — first one unconditional (the <= CP gate), the
        #    rest only while the budget allows.  CP is deterministic per
        #    (fleet, seed), so seeds are cached by fingerprint: repeated
        #    or oscillating events (straggler onset/recovery) skip the
        #    O(n x devices) python heuristic entirely
        cp_cache = getattr(self, "_cp_cache", None)
        if cp_cache is None:
            cp_cache = self._cp_cache = {}
        cp_rows: list[int] = []
        for s in range(max(int(cp_seeds), 1)):
            if s > 0 and time.perf_counter() >= deadline:
                break
            a_cp = cp_cache.get((fp, s))
            if a_cp is None:
                if len(cp_cache) >= 16:
                    cp_cache.pop(next(iter(cp_cache)))
                a_cp = cp_cache[(fp, s)] = critical_path_assignment(
                    self.g, new_dev, seed=s)
            cp_rows.append(len(cands))
            cands.append(a_cp)
            sources.append("cp")
        seg = np.stack(cands)
        flat = self.hier.expand(seg) if self.hier is not None else seg
        ts = np.asarray(eng.exec_times(flat, ep), dtype=float)
        k = int(ts.argmin())
        a, t, source = flat[k].copy(), float(ts[k]), sources[k]
        makespan_before = float(ts[0])
        cp_makespan = float(ts[cp_rows].min()) if cp_rows else float("inf")
        rounds_done = moves = 0
        if refine and time.perf_counter() < deadline:
            gf = self.flat_graph
            cost = (new_dev.exec_overhead_vec[None, :]
                    + gf.flops_array()[:, None]
                    / new_dev.flops_per_sec[None, :])
            cost[gf.input_mask()] = 0.0
            cfg = self.hierarchy
            a2, t2, rounds_done, moves = refine_assignment(
                gf, cost, a, eng, int(new_dev.n), episode=ep + 1,
                rounds=cfg.refine_rounds if cfg is not None else 2,
                top_k=cfg.refine_top_k if cfg is not None else 16,
                deadline=deadline)
            if t2 < t:
                a, t, source = a2, float(t2), "refined"
        latency = time.perf_counter() - t0
        result = ReplaceResult(
            assignment=a, makespan=t, makespan_before=makespan_before,
            cp_makespan=cp_makespan, source=source, latency_s=latency,
            budget_s=float(budget_s),
            within_budget=latency <= float(budget_s),
            fleet_fingerprint=fp, event=ev,
            refine_rounds=rounds_done, refine_moves=moves,
            n_candidates=len(cands))
        if commit:
            self.dev = new_dev
            self.gd = gd_new
            self._fused_cache = {}      # SimGraph/chunks were fleet-specific
            # reward normalizer tracks the OLD fleet's makespan scale
            self._r_sum = self._r_sqsum = 0.0
            self._r_count = 0
            if self.hier is not None:
                self.hier.rebind_devices(new_dev)
                self.hier.refine_state = RefineState(a.copy(), float(t),
                                                     rounds_done, moves)
                # Stage II resumes at the segment level: keep the best
                # SEGMENT candidate (the refined flat winner has no
                # segment-level preimage)
                self.best_assignment = seg[k]
                self.best_time = float(ts[k])
            else:
                self.best_assignment = a.copy()
                self.best_time = float(t)
        return result

    # -------------------------------------------------------- evaluation
    def evaluate(self, sim_or_fn, n_runs: int = 10,
                 assignment: np.ndarray | None = None):
        """Paper protocol: mean +/- std of `n_runs` executions of the best
        found assignment.

        Any reward source goes through the engine adapter: simulators
        keep the historical seeds ``1000..1000+n_runs-1``, batch-capable
        engines (executor, batched callables) evaluate all repeats in
        one call, and noise-free deterministic engines dedup the repeats
        into a single episode."""
        a = assignment if assignment is not None else self.best_assignment
        if a is None:
            a = self.greedy_assignment()
        ts = as_engine(sim_or_fn).evaluate_repeats(a, n_runs)
        return float(np.mean(ts)), float(np.std(ts)), a


# --------------------------------------------------------------- transfer
def transfer(trainer: DopplerTrainer, target_graph: DataflowGraph,
             dev: DeviceModel, **kwargs) -> DopplerTrainer:
    """Few-shot transfer (Table 4 / App. J): carry the policy parameters to
    a new graph and/or device model; the caller then runs k-shot episodes.
    The params are copied: a fused Stage-II update donates its trainer's
    params, which must not delete the other trainer's."""
    new = DopplerTrainer(target_graph, dev, **kwargs)
    new.params = jax.tree_util.tree_map(jnp.copy, trainer.params)
    new.opt_state = adamw_init(new.params)
    return new


# ---------------------------------------------------------------- pretrain
@dataclasses.dataclass
class PretrainTask:
    """One (graph, fleet) cell of the cross-graph pretraining zoo."""
    name: str
    graph: DataflowGraph
    dev: DeviceModel
    noise_sigma: float = 0.0


def zoo_pretrain_tasks(archs: Sequence[str] | None = None,
                       fleets: Sequence[str] | None = None,
                       holdout: Sequence[str] = (),
                       seq: int = 32, n_synthetic: int = 2,
                       seed: int = 0) -> list[PretrainTask]:
    """The pretraining zoo: every (non-held-out) registry architecture's
    block graph paired round-robin with a heterogeneous fleet, plus
    synthetic layered/tiled graph augmentation (graphs/builder.py's
    sharded decomposer at randomized grids, and random layered DAGs) so
    the policy sees structure beyond the model zoo.  ``holdout``
    architectures are excluded end to end — they are the zero-shot
    evaluation set."""
    from ..configs.registry import ARCH_IDS
    from ..graphs.workloads import get_workload, synthetic_layered
    from .devices import HETERO_FLEETS, get_device_model
    fleets = tuple(fleets or HETERO_FLEETS)
    archs = [a for a in (archs or ARCH_IDS) if a not in set(holdout)]
    tasks = []
    for i, arch in enumerate(archs):
        fleet = fleets[i % len(fleets)]
        tasks.append(PretrainTask(
            f"{arch}|{fleet}", get_workload(f"model:{arch}", seq=seq),
            get_device_model(fleet)))
    rng = np.random.default_rng(seed)
    for j in range(n_synthetic):
        if j % 2 == 0:
            g = synthetic_layered(int(rng.integers(4, 9)),
                                  int(rng.integers(6, 13)),
                                  seed=seed + 17 * j)
        else:           # tiled: the sharded decomposer at a random grid
            g = get_workload("ffnn", batch_log2=int(rng.integers(8, 11)),
                             hidden_log2=int(rng.integers(8, 11)),
                             grid=int(rng.integers(2, 4)))
        fleet = fleets[(len(archs) + j) % len(fleets)]
        tasks.append(PretrainTask(f"synth{j}|{g.name}|{fleet}", g,
                                  get_device_model(fleet)))
    return tasks


def pretrain(tasks: Sequence[PretrainTask], seed: int = 0,
             rounds: int = 4, batch_size: int = 8,
             imitation_episodes: int = 2,
             d_hidden: int = 64, d_z: int = 32, d_y: int = 32,
             gnn_layers: int = 2,
             lr0: float = 3e-3, lr1: float = 1e-5,
             eps0: float = 0.2, eps1: float = 0.0,
             entropy_weight: float = 1e-2, normalize_adv: bool = True,
             sim_engine: str = "batched", log_every: int = 0) -> dict:
    """Train ONE dual-policy parameter set across many graph x fleet
    tasks (GDP/Placeto-style cross-graph generalization).

    The GNN-featurized policy is dimensionally graph- and fleet-agnostic
    (node embeddings + fleet descriptors, no per-graph parameter
    shapes), so a single (params, opt_state) pair round-robins over the
    tasks: per visit one task takes one batched REINFORCE update (after
    ``imitation_episodes`` CP-imitation warm-start passes).  Each task
    keeps its OWN reward statistics — makespans differ by orders of
    magnitude across graphs, so advantages must normalize per task, not
    against a pooled baseline.

    Returns ``{"params", "meta", "per_task"}``; feed ``params`` to
    :class:`~repro.launch.place_server.PlacementServer` (or
    ``policy_io.save_pretrained``) for zero-shot serving."""
    if not tasks:
        raise ValueError("pretrain needs at least one task")
    total = imitation_episodes + rounds * batch_size
    trainers, engines = [], []
    for i, t in enumerate(tasks):
        tr = DopplerTrainer(t.graph, t.dev, seed=seed + i,
                            d_hidden=d_hidden, gnn_layers=gnn_layers,
                            lr0=lr0, lr1=lr1, eps0=eps0, eps1=eps1,
                            entropy_weight=entropy_weight,
                            normalize_adv=normalize_adv,
                            total_episodes=max(total, 1))
        tr.params = init_policies(jax.random.PRNGKey(seed),
                                  d_hidden=d_hidden, d_z=d_z, d_y=d_y,
                                  gnn_layers=gnn_layers)
        tr.opt_state = adamw_init(tr.params)
        trainers.append(tr)
        engines.append(SimRewardEngine(
            WCSimulator(t.graph, t.dev, choose="fifo",
                        noise_sigma=t.noise_sigma),
            sim_engine=sim_engine))
    params, opt_state = trainers[0].params, trainers[0].opt_state

    # Stage I warm start, round-robin so no task dominates the schedule
    for ep in range(imitation_episodes):
        for tr in trainers:
            tr.params, tr.opt_state = params, opt_state
            tr.stage1_imitation(1, seed=seed + ep)
            params, opt_state = tr.params, tr.opt_state
    # Stage II: one batched update per task per round on shared params
    for rnd in range(rounds):
        for t, tr, eng in zip(tasks, trainers, engines):
            tr.params, tr.opt_state = params, opt_state
            ts = tr._batched_rl_update(eng, batch_size, "pretrain")
            params, opt_state = tr.params, tr.opt_state
            if log_every and (rnd + 1) % log_every == 0:
                print(f"[pretrain] round {rnd+1}/{rounds} {t.name}: "
                      f"mean={ts.mean()*1e3:.2f}ms "
                      f"best={tr.best_time*1e3:.2f}ms")
    meta = {"d_hidden": d_hidden, "d_z": d_z, "d_y": d_y,
            "gnn_layers": gnn_layers, "seed": seed, "rounds": rounds,
            "batch_size": batch_size,
            "imitation_episodes": imitation_episodes,
            "tasks": [t.name for t in tasks]}
    per_task = {t.name: {"best_time": float(tr.best_time)}
                for t, tr in zip(tasks, trainers)}
    return {"params": params, "meta": meta, "per_task": per_task}


# ------------------------------------------------------------------ fleet
class FleetTrainer:
    """Appendix I: at 1000+-node scale the dataflow graph of each *repeated*
    block/layer is assigned once and replicated across every data-parallel
    replica in the fleet (uniform hardware).  Each unique block graph gets
    its own DopplerTrainer; per-episode rewards are aggregated (mean) over
    the replica measurements — here simulated as independently-seeded noisy
    WC runs, in production the wall-clocks collected across the cluster."""

    def __init__(self, block_graphs: dict[str, DataflowGraph],
                 dev: DeviceModel, n_replicas: int = 8, seed: int = 0,
                 noise_sigma: float = 0.1, **trainer_kwargs):
        self.n_replicas = n_replicas
        self.trainers = {
            name: DopplerTrainer(g, dev, seed=seed + i, **trainer_kwargs)
            for i, (name, g) in enumerate(block_graphs.items())}
        self.sims = {name: WCSimulator(g, dev, choose="fifo",
                                       noise_sigma=noise_sigma)
                     for name, g in block_graphs.items()}

    def fleet_exec_time(self, name: str, assignment, episode: int,
                        sim_engine: str = "batched") -> float:
        """Mean exec time of the replicated assignment across the fleet —
        one batched K=1 x S=n_replicas sweep instead of a Python loop."""
        sim = self.sims[name]
        seeds = [episode * self.n_replicas + r for r in range(self.n_replicas)]
        ts = sim.run_batch(assignment, seeds=seeds, engine=sim_engine)[0]
        return float(np.mean(ts))

    def train(self, n_episodes: int, log_every: int = 0,
              batch_size: int = 8):
        """Train every block policy for `n_episodes` episodes through the
        batched update path: each update samples a whole population in one
        vmapped rollout, scores every member across all replicas with one
        batched-simulator sweep per member, and takes one batch-averaged
        REINFORCE step (one gradient dispatch per `batch_size` episodes
        instead of one per episode)."""
        for name, tr in self.trainers.items():
            sim = self.sims[name]

            def fleet_rewards(assigns: np.ndarray) -> np.ndarray:
                # row k plays the episode counter the serial path would
                # have used, so replica seeds line up with fleet_exec_time
                return np.array([
                    sim.run_batch(
                        a, seeds=[(tr.episode + k) * self.n_replicas + r
                                  for r in range(self.n_replicas)])[0].mean()
                    for k, a in enumerate(assigns)])

            remaining = n_episodes
            while remaining > 0:
                b = min(batch_size, remaining)
                tr._batched_rl_update(fleet_rewards, b, "fleet")
                remaining -= b
            if log_every:
                print(f"[fleet] {name}: best={tr.best_time*1e3:.2f}ms")

    def assignments(self) -> dict[str, np.ndarray]:
        return {n: t.best_assignment for n, t in self.trainers.items()}
