"""Device-resident WC reward oracle — a jit/vmap twin of the serial engine.

``WCSimulator.run`` (and its compiled numpy twin ``sim_batch.run_plan``)
evaluate Stage-II rewards on the host, which forces every fused training
step to round-trip assignments through numpy.  This module keeps the whole
reward computation inside XLA: :func:`makespan_fifo` replays one
work-conserving episode as a fixed-trip ``lax.scan`` whose per-trip work is
a handful of tiny array ops, so a K-episode reward batch is one fused
device computation (`vmap`) that composes with the sampling rollout and
the policy update into a single jitted train step (train_fused.py).

Scope — the **noise-free 'fifo'** strategy only.  That is exactly the
Stage-II sampling configuration of the fused engine; 'dfs'/'random'
strategies and lognormal noise draw host RNG in a serial-dependent order
and stay on the numpy engines (the bit-exact references).

Equivalence contract (enforced by tests/test_sim_jax.py): the oracle makes
the *same scheduling decisions* as ``WCSimulator.run(choose='fifo',
noise_sigma=0)`` — identical task systems (one exec task per non-input
vertex, one transfer per unique cross (producer, destination-device) pair),
identical FIFO queue order (ready time, then the serial engine's insertion
sequence), identical work-conserving start passes, identical completion
order (end time, then start order) — but evaluates costs in float32
(jax's default), so makespans match the float64 serial engine to floating
-point tolerance rather than bit-for-bit.  See docs/SIMULATOR.md.

How the serial schedule is replayed with static shapes and XLA-CPU
friendly per-trip work (no large dense ops, no large scatters):

* The task system is derived **on device** from the assignment: exec
  durations are a gather from the ``(n, n_dev)`` cost table; each
  non-input edge computes its canonical transfer slot (the first out-edge
  of its producer targeting the same device — the insertion-ordered
  ``consumers_on`` dedup of simulator.py) with one vectorized pass over
  the padded out-edge rows.  Tasks live in one index space: exec ``v`` at
  slot ``v``, the transfer of edge ``e`` at slot ``n + e``.
* Each resource (``n_dev`` devices + ``n_dev²`` directed channels) keeps
  its FIFO queue as an intrusive linked list (head/tail pointers plus a
  per-task ``next``).  Insertion keys are globally increasing (trip index
  × row width + emission position), replicating the serial ``(ready_time,
  insertion order)`` queue keys, so append-at-tail preserves FIFO order.
  The queue state is stored one array per column (``Queues``): a table
  with a 2- or 3-wide minor axis would be tiled to 128 lanes on a TPU
  and converted between layouts on every trip.  A batch stores each
  column, and the indegrees, flat and episode-major: the per-episode
  helpers only *plan* a trip's writes (gathers and unique slots), and
  each column takes the whole batch's writes as one 1-D scatter — a
  vmapped scatter into a ``(B, X)`` array costs a TPU about twelve times
  as much per update.
* One scan trip = one serial heap pop: a work-conserving start pass over
  a small carried *candidate list* (only the resource freed by the last
  completion and the ≤2C resources whose queue gained a task can start
  anything — every other resource is busy or free-and-empty), then the
  earliest completion is popped from a compact per-resource running
  table, and the readiness updates it triggers are computed inside the
  completed producer's padded out-edge row (≤C entries).  Completion ties
  replay the serial heap's ``(end, start counter)`` via lexicographic
  ``(end, start trip, ready time, kind/sequence key)`` argmin.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .devices import DeviceModel
from .graph import DataflowGraph
from ..kernels import default_interpret
from ..kernels.wc_oracle.ops import wc_step

F32_INF = jnp.float32(np.inf)
I32_BIG = jnp.int32(2**31 - 1)

ORACLE_BACKENDS = ("xla", "pallas")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SimGraph:
    """Static per-(graph, fleet) arrays for the device-resident oracle."""
    # ---- arrays (pytree children)
    is_input: jnp.ndarray      # (n,) bool
    need0: jnp.ndarray         # (n,) int32 non-input indegree; inputs = -1
    esrc: jnp.ndarray          # (m,) int32 producer of each non-input edge
    edst: jnp.ndarray          # (m,) int32 consumer
    edge_pos: jnp.ndarray      # (m,) int32 position in producer's out row
    edge_valid: jnp.ndarray    # (m,) bool (False on padding)
    out_row: jnp.ndarray       # (n, C) int32 out-edge ids per producer, -1 pad
    exec_cost: jnp.ndarray     # (n, nd) f32, 0 rows for inputs
    link_lat: jnp.ndarray      # (nd, nd) f32
    link_bw: jnp.ndarray       # (nd, nd) f32
    out_bytes: jnp.ndarray     # (n,) f32
    # ---- static metadata (aux)
    n: int = 0
    nd: int = 0
    m: int = 0                 # non-input edges (before padding)
    C: int = 0                 # max non-input out-degree
    n_compute: int = 0
    n_trips: int = 0           # n_compute + m: upper bound on heap pops
    seqw: int = 0              # per-trip insertion-sequence row width (2C)
    koff: int = 0              # kind offset: transfer keys sort after execs

    _ARRAYS = ("is_input", "need0", "esrc", "edst", "edge_pos", "edge_valid",
               "out_row", "exec_cost", "link_lat", "link_bw", "out_bytes")
    _AUX = ("n", "nd", "m", "C", "n_compute", "n_trips", "seqw", "koff")

    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in self._ARRAYS),
                tuple(getattr(self, f) for f in self._AUX))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @classmethod
    def build(cls, graph: DataflowGraph, devices: DeviceModel) -> "SimGraph":
        n, nd = graph.n, devices.n
        is_input = np.array([graph.is_input(v) for v in range(n)], bool)
        edges = graph.edge_array().reshape(-1, 2)
        ni = edges[~is_input[edges[:, 0]]] if len(edges) else edges
        m = len(ni)
        mp = max(m, 1)                            # pad so shapes stay >0
        esrc = np.zeros(mp, np.int32)
        edst = np.zeros(mp, np.int32)
        valid = np.zeros(mp, bool)
        esrc[:m], edst[:m], valid[:m] = ni[:, 0], ni[:, 1], True
        # position of each edge within its producer's out row — graph edge
        # order, i.e. the serial engine's succs / consumers_on iteration
        # order.
        edge_pos = np.zeros(mp, np.int32)
        rows: list[list[int]] = [[] for _ in range(n)]
        for e in range(m):
            p = int(esrc[e])
            edge_pos[e] = len(rows[p])
            rows[p].append(e)
        C = max((len(r) for r in rows), default=0)
        C = max(C, 1)
        out_row = np.full((n, C), -1, np.int32)
        for p, r in enumerate(rows):
            out_row[p, :len(r)] = r
        need0 = np.zeros(n, np.int64)
        np.add.at(need0, edst[:m], 1)
        need0[is_input] = -1
        # tight trip bound: one completion per exec plus at most
        # min(out-degree, n_dev - 1) canonical transfers per producer
        outdeg = np.zeros(n, np.int64)
        np.add.at(outdeg, esrc[:m], 1)
        x_max = int(np.minimum(outdeg, nd - 1).sum()) if nd > 1 else 0
        # same IEEE expressions as DeviceModel.exec_time / transfer_time,
        # evaluated in f32 (the oracle's tolerance-bounded cost model)
        flops = graph.flops_array()
        exec_cost = (devices.exec_overhead_vec[None, :]
                     + flops[:, None] / devices.flops_per_sec[None, :])
        exec_cost[is_input] = 0.0
        n_compute = int(n - is_input.sum())
        seqw = 2 * C
        # largest insertion sequence: n (init block) + trips * seqw
        koff = n + (n_compute + m + 2) * seqw
        if 2 * koff >= 2 ** 24:
            raise ValueError(
                f"graph too large for exact f32 queue keys "
                f"(2*koff={2 * koff} >= 2^24); use the numpy engines")
        return cls(
            is_input=jnp.asarray(is_input),
            need0=jnp.asarray(need0, jnp.int32),
            esrc=jnp.asarray(esrc), edst=jnp.asarray(edst),
            edge_pos=jnp.asarray(edge_pos), edge_valid=jnp.asarray(valid),
            out_row=jnp.asarray(out_row),
            exec_cost=jnp.asarray(exec_cost, jnp.float32),
            link_lat=jnp.asarray(devices.link_latency, jnp.float32),
            link_bw=jnp.asarray(devices.link_bw, jnp.float32),
            out_bytes=jnp.asarray(graph.out_bytes_array(), jnp.float32),
            n=n, nd=nd, m=m, C=C, n_compute=n_compute,
            n_trips=n_compute + x_max, seqw=seqw, koff=koff,
        )


def _derive_tasks(sg: SimGraph, A):
    """On-device per-assignment task system (the jit twin of
    sim_batch.compile_assignment)."""
    av = A.astype(jnp.int32)
    sdev = av[sg.esrc]
    ddev = av[sg.edst]
    cross = sg.edge_valid & (sdev != ddev)
    # canonical transfer slot per edge: first out-edge of the same producer
    # with the same destination device (consumers_on first-edge order)
    row = sg.out_row[sg.esrc]                            # (m, C)
    row_dst = jnp.where(row >= 0, av[sg.edst[jnp.maximum(row, 0)]], -1)
    same = row_dst == ddev[:, None]                      # (m, C)
    first = jnp.argmax(same, axis=1).astype(jnp.int32)   # first True
    canon_id = jnp.take_along_axis(row, first[:, None], axis=1)[:, 0]
    is_canon = cross & (first == sg.edge_pos)
    # an edge's readiness requirement: producer's exec if co-located, else
    # the canonical transfer bringing the producer's result over
    req = jnp.where(cross, sg.n + canon_id,
                    jnp.where(sg.edge_valid, sg.esrc, -1))
    edur = jnp.take_along_axis(sg.exec_cost, av[:, None], axis=1)[:, 0]
    xdur = (sg.link_lat[sdev, ddev]
            + sg.out_bytes[sg.esrc] / sg.link_bw[sdev, ddev])
    res_x = sg.nd + sdev * sg.nd + ddev                  # channel resource id
    return av, is_canon, req, edur, xdur, res_x


class Queues(NamedTuple):
    """Per-task and per-resource FIFO queue state, one array per column:
    every per-trip read and write is a scalar-window gather or scatter,
    so no table has a narrow minor axis (on a TPU a 2- or 3-wide minor
    axis is tiled to 128 lanes).  A batch of B episodes stores each
    column flat and episode-major (episode b's slot i at ``b*X + i``), so
    a trip writes all B episodes with one 1-D scatter per column."""
    key: jnp.ndarray    # (B*N,) f32 insertion key (exact integer)
    rdy: jnp.ndarray    # (B*N,) f32 ready time
    nxt: jnp.ndarray    # (B*N,) int32 linked-list next task, -1 = end
    hd: jnp.ndarray     # (B*R,) int32 head task, -1 = empty
    tl: jnp.ndarray     # (B*R,) int32 tail task, -1 = empty


class TripCarry(NamedTuple):
    """Trip-loop state of B episodes (B = 1 for the single scan): the
    queues and indegrees flat and episode-major, the rest stacked on a
    leading batch axis (none when unbatched)."""
    q: Queues
    run: jnp.ndarray    # (R, 6) running table
    need: jnp.ndarray   # (B*n,) int32 unmet indegree
    cand: jnp.ndarray   # (K,) int32 candidate resources, R = none
    t: jnp.ndarray      # f32 simulated time
    ms: jnp.ndarray     # f32 makespan so far
    n_done: jnp.ndarray  # int32 completed exec tasks


def _init_episode(sg: SimGraph, av) -> TripCarry:
    """Initial trip-loop state for one episode."""
    n, nd, C = sg.n, sg.nd, sg.C
    mm = sg.esrc.shape[0]
    R = nd + nd * nd
    F_BIG = jnp.float32(I32_BIG)

    ready0 = (sg.need0 == 0) & ~sg.is_input
    fseq = jnp.arange(n, dtype=jnp.float32)

    # initial per-device FIFO queues (vertex order): next pointer = the
    # next seeded vertex on the same device (suffix-scan per device column)
    oh = av[:, None] == jnp.arange(nd)[None, :]          # (n, nd)
    colidx = jnp.where(oh & ready0[:, None],
                       jnp.arange(n, dtype=jnp.int32)[:, None], I32_BIG)
    sufmin = jax.lax.cummin(colidx[::-1], axis=0)[::-1]  # inclusive suffix
    nxt0 = jnp.concatenate([sufmin[1:], jnp.full((1, nd), I32_BIG)])
    nxt_v = jnp.take_along_axis(nxt0, av[:, None], axis=1)[:, 0]
    hd0 = jnp.where(oh & ready0[:, None], colidx, I32_BIG).min(0)
    tl0 = jnp.where(oh & ready0[:, None],
                    jnp.arange(n, dtype=jnp.int32)[:, None], -1).max(0)
    q = Queues(
        key=jnp.concatenate([jnp.where(ready0, fseq, F_BIG),
                             jnp.full(mm, F_BIG)]),
        rdy=jnp.zeros(n + mm),
        nxt=jnp.concatenate([
            jnp.where(ready0 & (nxt_v < I32_BIG), nxt_v, -1),
            jnp.full(mm, -1, jnp.int32)]),
        hd=jnp.full(R, -1, jnp.int32).at[:nd].set(
            jnp.where(hd0 < I32_BIG, hd0, -1)),
        tl=jnp.full(R, -1, jnp.int32).at[:nd].set(tl0))

    # run[:, :] = (end, start trip, ready time, key, task, free) per
    # resource — one row scatter per start
    run = jnp.zeros((R, 6))
    run = run.at[:, 0].set(F32_INF)
    run = run.at[:, 4].set(-1.0)

    K = max(nd, C + 1)
    cand = jnp.full(K, R, jnp.int32).at[:nd].set(
        jnp.arange(nd, dtype=jnp.int32))
    return TripCarry(q, run, sg.need0, cand, jnp.float32(0.0),
                     jnp.float32(0.0), jnp.int32(0))


def _flat_set(arr, idx, val):
    """Write every episode's planned ``(slot, value)`` pairs into the flat
    episode-major ``arr`` as one 1-D scatter.  ``idx`` is ``(B, k)`` (or
    ``(k,)`` for one episode) per-episode slots; a slot ``>= X``, the
    per-episode size, drops.  A dropped write points past the whole
    array: episode b's own ``b*X + X`` would alias episode b+1's slot 0.
    Each episode's kept slots are unique."""
    idx = idx.reshape(-1, idx.shape[-1])
    B = idx.shape[0]
    X = arr.shape[0] // B
    off = X * jnp.arange(B, dtype=jnp.int32)[:, None]
    flat = jnp.where(idx < X, idx + off, B * X)
    return arr.at[flat.ravel()].set(val.ravel(), unique_indices=True,
                                    mode="drop")


def _write_queues(q: Queues, qw: Queues) -> Queues:
    """Apply a plan's ``(slot, value)`` writes, one column at a time
    (``None`` = the column is not written)."""
    return Queues(*(a if w is None else _flat_set(a, *w)
                    for a, w in zip(q, qw)))


def _start_pass(sg: SimGraph, dur, q: Queues, run, cand, t, ftrip, b):
    """Plan of the work-conserving start pass of episode ``b`` over its
    candidate resources: a free resource starts its queue head (duplicate
    candidates are idempotent — same head, same row).  Reads the flat
    queues ``q`` at the episode's slots and writes nothing.  Returns
    ``(ridx, rows, qw)`` where ``ridx == R`` drops the row and ``qw``
    holds the queue-head pops (advance head; clear tail when the queue
    empties) as per-episode ``(slot, value)`` writes for
    :func:`_write_queues`, from each resource's first candidate only."""
    R = sg.nd + sg.nd * sg.nd
    N = sg.n + sg.esrc.shape[0]
    cc = jnp.minimum(cand, R - 1)
    crow = run[cc]                                   # (K, 6)
    h = jnp.where(cand < R, q.hd[b * R + cc], -1)    # head task or -1
    # a resource whose task ends exactly at t counts as free in the
    # serial engine before its completion pops; its run slot is still
    # occupied here, so defer that start one trip (the pop at the same
    # simulated time re-candidates the resource — start times, and
    # therefore the schedule, are unchanged)
    go = (h >= 0) & (crow[:, 5] <= t) & ~jnp.isfinite(crow[:, 0])
    hh = jnp.maximum(h, 0)
    th = b * N + hh
    end_c = t + dur[hh]
    ridx = jnp.where(go, cc, R)                      # OOB drops
    rows = jnp.stack(
        [end_c, jnp.full_like(end_c, ftrip), q.rdy[th], q.key[th],
         hh.astype(jnp.float32), end_c], axis=1)
    hn = q.nxt[th]
    # a repeated candidate's first occurrence writes the resource's
    # (head, tail), so each write's slots are unique
    kpos = jnp.arange(cand.shape[0])
    dup = ((cand[:, None] == cand[None, :])
           & (kpos[None, :] < kpos[:, None])).any(1)
    w = jnp.where(go & ~dup, cc, R)
    tl = jnp.where(hn < 0, -1, q.tl[b * R + cc])
    return ridx, rows, Queues(None, None, None, (w, hn), (w, tl))


def _lex_pop(run):
    """Pop the earliest completion from the running table; ties replay the
    serial heap's (end, start counter) via (end, start trip, ready time,
    kind/sequence key).  Returns ``(rho, e1, alive)``."""
    F_BIG = jnp.float32(I32_BIG)
    e1 = run[:, 0].min()
    alive = jnp.isfinite(e1)
    mk = run[:, 0] == e1
    s1 = jnp.where(mk, run[:, 1], F_BIG).min()
    mk &= run[:, 1] == s1
    r1 = jnp.where(mk, run[:, 2], F32_INF).min()
    mk &= run[:, 2] == r1
    k1 = jnp.where(mk, run[:, 3], F_BIG).min()
    rho = jnp.argmax(mk & (run[:, 3] == k1)).astype(jnp.int32)
    return rho, e1, alive


def _readiness(sg: SimGraph, is_canon, req, res_of, q: Queues, need, t,
               trip_idx, c, c_is_exec, alive, b):
    """Plan of the readiness triggered by episode ``b``'s completion
    ``c``, computed in the completed producer's out-edge row (≤C
    entries), in the serial emission order: same-device successors (succ
    position), then transfers (C offset, consumers_on first-edge order).
    Same-device edges and cross edges are disjoint, so one C-wide row
    covers both.  Reads the flat ``q`` and ``need`` at the episode's
    slots and writes nothing.  Returns ``(qw, need_w, i_res)``: the queue
    and indegree writes as per-episode ``(slot, value)`` pairs with
    unique slots."""
    n, nd, C = sg.n, sg.nd, sg.C
    mm = sg.esrc.shape[0]
    N = n + mm
    R = nd + nd * nd
    cpos = jnp.arange(C, dtype=jnp.int32)
    cx = jnp.minimum(jnp.maximum(c - n, 0), mm - 1)
    p = jnp.where(c_is_exec, c, sg.esrc[cx])
    prow = sg.out_row[jnp.clip(p, 0, n - 1)]         # (C,)
    pe = jnp.maximum(prow, 0)
    pvalid = (prow >= 0) & alive
    ptrig = pvalid & (req[pe] == c)
    pdst = sg.edst[pe]
    # each triggered edge decrements its destination's indegree once
    # (parallel edges each count); the last decrement wins the emission
    # slot: max triggered succ position per destination vertex (tiny
    # C x C pass), which writes the decremented indegree
    trig_w = (pdst[:, None] == pdst[None, :]) & ptrig[None, :]
    maxpos = jnp.where(trig_w, cpos[None, :], -1).max(1)
    last = ptrig & (cpos == maxpos)
    left = need[b * n + pdst] - trig_w.sum(1, dtype=jnp.int32)
    nw = last & (left == 0)
    nx = pvalid & c_is_exec & is_canon[pe]
    i_live = nw | nx
    base = n + trip_idx * sg.seqw
    i_task = jnp.where(nw, pdst, jnp.where(nx, n + pe, N))
    i_key = jnp.where(nw, base + maxpos, sg.koff + base + C + cpos)
    i_res = jnp.where(i_live, res_of[jnp.minimum(i_task, N - 1)], R)
    # within-trip chaining: link each entry to the next entry bound
    # for the same resource (C x C pass); execs and transfers target
    # disjoint resources, so row order = per-queue emission order
    samer = (i_res[:, None] == i_res[None, :]) & i_live[None, :]
    after = samer & (cpos[None, :] > cpos[:, None])
    succ_k = jnp.where(after, cpos[None, :], C).min(1)
    has_succ = succ_k < C
    succ_task = i_task[jnp.minimum(succ_k, C - 1)]
    is_first = ~(samer & (cpos[None, :] < cpos[:, None])).any(1) & i_live
    is_last = ~has_succ & i_live
    # the new entries' (key, ready, chain-next) plus the tail-append link
    # from each queue's old tail: new tasks and old tails are disjoint and
    # internally deduped; each resource's first entry writes its FINAL
    # (head, tail)
    rq = b * R + jnp.minimum(i_res, R - 1)
    rtl = q.tl[rq]
    link_idx = jnp.where(is_first & (rtl >= 0), jnp.maximum(rtl, 0), N)
    lst = jnp.where(samer & is_last[None, :], i_task[None, :], -1).max(1)
    w = jnp.where(is_first, i_res, R)
    qw = Queues(
        key=(i_task, i_key.astype(jnp.float32)),
        rdy=(i_task, jnp.broadcast_to(t, (C,))),
        nxt=(jnp.concatenate([i_task, link_idx]),
             jnp.concatenate([jnp.where(has_succ, succ_task, -1), i_task])),
        hd=(w, jnp.where(rtl < 0, i_task, q.hd[rq])),
        tl=(w, lst))
    return qw, (jnp.where(last, pdst, n), left), i_res


def _next_cand(sg: SimGraph, i_res, rho, alive):
    """Next trip's candidate list: the resources whose queue gained a
    task plus the resource freed by the pop."""
    R = sg.nd + sg.nd * sg.nd
    K = max(sg.nd, sg.C + 1)
    cand = jnp.concatenate([i_res, jnp.where(alive, rho, R)[None]])
    if K > sg.C + 1:
        cand = jnp.concatenate([cand, jnp.full(K - sg.C - 1, R,
                                               jnp.int32)])
    return cand


@partial(jax.jit, static_argnames=())
def makespan_fifo(sg: SimGraph, assignment) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Noise-free 'fifo' WC makespan of one assignment.

    Returns ``(makespan, ok)``; ``ok`` is False when the episode deadlocks
    (the host wrapper raises, matching the numpy engines).

    Performance shape: each resource's FIFO queue is an intrusive linked
    list (head/tail pointers plus a per-task ``next``), kept with the
    per-task keys and ready times as one array per column (``Queues``),
    so every per-trip read and write of it is a scalar-window gather or
    scatter — through the same plan (``_start_pass``, ``_readiness``) and
    flat write (``_flat_set``) helpers as the batch loop, with B = 1.
    The running tasks live in a compact (R, 6) per-resource table, and
    every per-trip update is a gather or a ≤C-index scatter —
    the work-conserving start pass only examines the carried *candidate
    list* (the resource freed by the last completion plus the ≤C whose
    queue gained a task; every other resource is busy or free-and-empty,
    an invariant the pass maintains).  The trip loop is a fixed-trip
    ``scan`` of ``n_trips + 1`` trips; trips after the heap drains are
    no-ops.  Queue keys are exact-integer float32 (SimGraph.build
    guarantees keys < 2**24).
    """
    n = sg.n
    R = sg.nd + sg.nd * sg.nd       # devices then directed channels
    av, is_canon, req, edur, xdur, res_x = _derive_tasks(sg, assignment)
    dur = jnp.concatenate([edur, xdur])
    res_of = jnp.concatenate([av, res_x])

    def trip(state):
        s, trip_idx = state
        ftrip = trip_idx.astype(jnp.float32)

        ridx, rows, qw = _start_pass(sg, dur, s.q, s.run, s.cand, s.t,
                                     ftrip, 0)
        q = _write_queues(s.q, qw)
        run = s.run.at[ridx].set(rows)

        rho, e1, alive = _lex_pop(run)
        c = jnp.where(alive, run[rho, 4].astype(jnp.int32), -1)
        run = run.at[jnp.where(alive, rho, R), 0].set(F32_INF)
        c_is_exec = alive & (c < n)
        t = jnp.where(alive, e1, s.t)

        qw, need_w, i_res = _readiness(sg, is_canon, req, res_of, q, s.need,
                                       t, trip_idx, c, c_is_exec, alive, 0)
        q, need = _write_queues(q, qw), _flat_set(s.need, *need_w)
        return TripCarry(q, run, need, _next_cand(sg, i_res, rho, alive), t,
                         jnp.where(alive, e1, s.ms),
                         s.n_done + jnp.where(c_is_exec, 1, 0)
                         ), trip_idx + 1

    # fixed-trip scan: completions are bounded by n_trips; drained trips
    # no-op (vmapped while_loop would pay a full-carry select per trip)
    s, _ = jax.lax.scan(lambda st, _: (trip(st), None),
                        (_init_episode(sg, av), jnp.int32(0)), None,
                        length=sg.n_trips + 1)[0]
    return s.ms, s.n_done == sg.n_compute


def _batch_setup(sg: SimGraph, assignments):
    """Vmapped per-episode task systems + initial trip-loop carry, its
    queues and indegrees flattened episode-major."""
    av, is_canon, req, edur, xdur, res_x = jax.vmap(
        lambda a: _derive_tasks(sg, a))(assignments)
    dur = jnp.concatenate([edur, xdur], axis=1)
    res_of = jnp.concatenate([av, res_x], axis=1)
    carry = jax.vmap(lambda a: _init_episode(sg, a))(av)
    carry = carry._replace(q=Queues(*(x.reshape(-1) for x in carry.q)),
                           need=carry.need.reshape(-1))
    return dur, is_canon, req, res_of, carry


def _run_trips(sg: SimGraph, dur, is_canon, req, res_of, carry: TripCarry,
               pop_fn):
    """Shared batched trip loop: one iteration = one serial heap pop per
    episode, with the running-table work (start writes, lexicographic pop,
    popped-slot clear) delegated to ``pop_fn`` (vmapped XLA ops or the
    fused Pallas ``wc_step`` kernel).

    **Trip trimming**: the loop is a batch-level ``while_loop`` that exits
    as soon as every episode in the batch has completed all its compute
    tasks (or at the static ``n_trips + 1`` bound).  Trips past an
    episode's own completion are no-ops in the fixed-trip formulation
    (the heap is drained, ``alive`` is False, every scatter is masked), so
    skipping the drained tail is decision-exact — the batch pays for the
    *longest* episode's completion count instead of the static worst case.
    A single ``any()`` across the batch drives the exit; there is no
    per-episode carry select (the cost that rules out a vmapped
    per-episode ``while_loop``).

    Returns ``(makespans, ok)``; ``ok`` is False for episodes whose heap
    drained before all compute tasks ran (deadlock — those makespans are
    garbage and callers must raise or mask).
    """
    n = sg.n
    ep = jnp.arange(dur.shape[0], dtype=jnp.int32)

    def cond(state):
        s, trip_idx = state
        return ((trip_idx < sg.n_trips + 1)
                & jnp.any(s.n_done < sg.n_compute))

    def body(state):
        s, trip_idx = state
        ftrip = trip_idx.astype(jnp.float32)

        # the plans are vmapped and only gather from the flat queues,
        # closed over (unbatched); the writes are applied outside the vmap
        ridx, rows, qw = jax.vmap(
            lambda b, du, rn, cd, tt: _start_pass(
                sg, du, s.q, rn, cd, tt, ftrip, b)
        )(ep, dur, s.run, s.cand, s.t)
        q = _write_queues(s.q, qw)
        run, rho, e1 = pop_fn(s.run, rows, ridx)
        alive = jnp.isfinite(e1)
        c = jnp.where(alive, jnp.take_along_axis(
            run[:, :, 4], rho[:, None], axis=1)[:, 0].astype(jnp.int32), -1)
        c_is_exec = alive & (c < n)
        t = jnp.where(alive, e1, s.t)

        qw, need_w, i_res = jax.vmap(
            lambda b, ic, rq, ro, tt, cv, ce, al: _readiness(
                sg, ic, rq, ro, q, s.need, tt, trip_idx, cv, ce, al, b)
        )(ep, is_canon, req, res_of, t, c, c_is_exec, alive)
        q, need = _write_queues(q, qw), _flat_set(s.need, *need_w)
        cand = jax.vmap(
            lambda ir, rh, al: _next_cand(sg, ir, rh, al))(i_res, rho, alive)
        return TripCarry(q, run, need, cand, t, jnp.where(alive, e1, s.ms),
                         s.n_done + jnp.where(c_is_exec, 1, 0)
                         ), trip_idx + 1

    s, _ = jax.lax.while_loop(cond, body, (carry, jnp.int32(0)))
    return s.ms, s.n_done == sg.n_compute


@jax.jit
def _makespan_fifo_batch_xla(sg: SimGraph, assignments):
    """Batched :func:`makespan_fifo`: the running-table work as vmapped
    XLA ops, driven by the trip-trimmed ``_run_trips`` loop."""
    R = sg.nd + sg.nd * sg.nd
    dur, is_canon, req, res_of, carry = _batch_setup(sg, assignments)

    def pop(run, rows, ridx):
        run = jax.vmap(lambda rn, ri, ro: rn.at[ri].set(ro))(run, ridx, rows)
        rho, e1, alive = jax.vmap(_lex_pop)(run)
        # clear only column 0; the popped task id (column 4) survives for
        # the caller's read, exactly like the single-episode trip
        run = jax.vmap(
            lambda rn, rh, al: rn.at[jnp.where(al, rh, R), 0].set(F32_INF)
        )(run, rho, alive)
        return run, rho, e1

    return _run_trips(sg, dur, is_canon, req, res_of, carry, pop)


@partial(jax.jit, static_argnames=("interpret",))
def _makespan_fifo_batch_pallas(sg: SimGraph, assignments, interpret: bool):
    """Batched twin of :func:`makespan_fifo` whose per-trip running-table
    work (start writes, lexicographic pop, popped-slot clear) is one fused
    Pallas kernel over the whole episode batch instead of B vmapped
    scatters/reductions.  Decision-exact with the XLA path: both consume
    the same helper ops through ``_run_trips`` (including its trip
    trimming) and the kernel is bit-pinned to kernels.wc_oracle.ref
    (tests/test_kernels.py, tests/test_conformance.py)."""
    R = sg.nd + sg.nd * sg.nd
    dur, is_canon, req, res_of, carry = _batch_setup(sg, assignments)

    def pop(run, rows, ridx):
        # the kernel's drop sentinel is -1 (R would alias a padded lane)
        return wc_step(run, rows, jnp.where(ridx < R, ridx, -1),
                       interpret=interpret)

    return _run_trips(sg, dur, is_canon, req, res_of, carry, pop)


def makespan_fifo_batch(sg: SimGraph, assignments, backend: str = "xla",
                        interpret: bool | None = None):
    """(K, n) assignments -> ((K,) makespans, (K,) ok flags), one dispatch.

    ``backend="xla"`` runs the running-table work as vmapped XLA ops;
    ``backend="pallas"`` routes the per-trip running-table work through
    the fused kernels.wc_oracle step (``interpret=None`` resolves
    through :func:`repro.kernels.default_interpret`).  Both share the
    trip-trimmed ``_run_trips`` driver — the batch stops as soon as its
    longest episode completes instead of always paying the static
    ``n_trips + 1`` bound — and both are decision-exact twins of the
    serial engine."""
    if backend == "pallas":
        if interpret is None:
            interpret = default_interpret()
        return _makespan_fifo_batch_pallas(sg, assignments, interpret)
    if backend != "xla":
        raise ValueError(f"unknown oracle backend {backend!r}; "
                         f"expected one of {ORACLE_BACKENDS}")
    return _makespan_fifo_batch_xla(sg, assignments)


class JaxWCEngine:
    """Host-friendly wrapper mirroring BatchWCEngine's surface for the
    noise-free fifo case (the configuration the fused trainer uses).

    ``backend`` selects the batched evaluation path ("xla" | "pallas");
    single-assignment ``exec_time`` always uses the XLA scan (a batch of
    one has nothing to fuse)."""

    def __init__(self, graph: DataflowGraph, devices: DeviceModel,
                 backend: str = "xla", interpret: bool | None = None):
        if backend not in ORACLE_BACKENDS:
            raise ValueError(f"unknown oracle backend {backend!r}; "
                             f"expected one of {ORACLE_BACKENDS}")
        self.graph, self.devices = graph, devices
        self.sim_graph = SimGraph.build(graph, devices)
        self.backend = backend
        self.interpret = interpret

    def exec_time(self, assignment) -> float:
        ms, ok = makespan_fifo(self.sim_graph,
                               jnp.asarray(np.asarray(assignment)))
        if not bool(ok):
            raise RuntimeError("deadlock: episode never completed")
        return float(ms)

    def run_batch(self, assignments) -> np.ndarray:
        A = np.asarray(assignments)
        if A.ndim == 1:
            A = A[None, :]
        ms, ok = makespan_fifo_batch(self.sim_graph, jnp.asarray(A),
                                     backend=self.backend,
                                     interpret=self.interpret)
        if not bool(np.asarray(ok).all()):
            raise RuntimeError("deadlock: episode never completed")
        return np.asarray(ms)
