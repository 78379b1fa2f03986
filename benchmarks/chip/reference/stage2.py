"""Plain reference of DOPPLER's Stage II, independent of the program.

It follows the paper's definitions (and the program's documented
contracts where the paper leaves a choice open) in straightforward
``jax.numpy`` and float32, with matmuls at ``highest`` precision:

* static graph features X_G (Appendix E.1) and fleet descriptors X_F;
* the GNN encoder (Eq. 2, both edge directions, sum aggregation, residual)
  and the SEL / PLC heads (Eq. 3-8);
* an episode: |V| steps, each picking a vertex from the candidate set and
  a device for it, with the ETF estimator of the dynamic device features
  (Appendix E.2).  Draws follow the fused engine's stream: per step
  ``key, kv, kd = split(key, 3)``; a pick splits its key in three and uses
  ``argmax(logp + gumbel(k0))`` for the policy draw,
  ``argmax(gumbel(k0) over the candidates)`` for exploration and
  ``uniform(k2) < eps`` to choose between them;
* the work-conserving FIFO makespan of an assignment (Alg. 1 + 2): one
  task started or one completion popped per iteration, ready tasks ordered
  by (ready time, execs before transfers, insertion order), completions by
  (end time, start order), times in float32;
* the REINFORCE update: advantages against the running reward baseline,
  the gradient of the surrogate by a forced replay of the recorded
  actions, global-norm clipping at 1 and AdamW.

Nothing here imports the program.  Its input instance is the graph the
policy places (vertex costs and edges) and the fleet as the configuration
states it; its weights come from the benchmark.  ``dtype`` lowers the
policy's arithmetic (``jnp.bfloat16`` is the control run).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

BIG = 1e30
I32_BIG = 2**31 - 1
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------- instance
def fleet_arrays(fleet: dict) -> dict:
    """A ``rows x cols`` TPU torus slice as the configuration states it:
    one compute rate, one launch overhead, single-link bandwidth between
    chips and ``hop_latency_s`` per torus hop."""
    rows, cols = fleet["rows"], fleet["cols"]
    nd = rows * cols
    bw = np.full((nd, nd), float(fleet["link_bw_bytes_per_s"]))
    np.fill_diagonal(bw, np.inf)
    lat = np.zeros((nd, nd))
    for i in range(nd):
        for j in range(nd):
            if i != j:
                dr = abs(i // cols - j // cols)
                dc = abs(i % cols - j % cols)
                hops = max(1, min(dr, rows - dr) + min(dc, cols - dc))
                lat[i, j] = fleet["hop_latency_s"] * hops
    return {"fps": np.full(nd, float(fleet["flops_per_s"])), "bw": bw,
            "lat": lat, "overhead": np.full(nd, float(fleet["overhead_s"]))}


def _topo(n, succs, npred):
    left = npred.copy()
    order, stack = [], [v for v in range(n) if left[v] == 0][::-1]
    while stack:
        v = stack.pop()
        order.append(v)
        for w in reversed(succs[v]):
            left[w] -= 1
            if left[w] == 0:
                stack.append(w)
    return order


def _paths(nxt):
    n = len(nxt)
    rows = []
    for v in range(n):
        p = [v]
        while nxt[p[-1]] >= 0:
            p.append(nxt[p[-1]])
        rows.append(p)
    out = np.full((n, max(len(r) for r in rows)), -1, np.int32)
    for v, r in enumerate(rows):
        out[v, :len(r)] = r
    return out


def _col_normalize(x):
    s = np.abs(x).max(axis=0, keepdims=True)
    return x / np.where(s > 0, s, 1.0)


def build_instance(edges, flops, out_bytes, is_input, fleet: dict,
                   comm_factor: float) -> dict:
    """Every static array the policy and the makespan need, from the
    graph (edges in the graph's order) and the fleet."""
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    flops = np.asarray(flops, np.float64)
    ob = np.asarray(out_bytes, np.float64)
    is_input = np.asarray(is_input, bool)
    n = len(flops)
    fl = fleet_arrays(fleet)
    nd = len(fl["fps"])
    succs = [[] for _ in range(n)]
    preds = [[] for _ in range(n)]
    for s, d in edges:
        succs[s].append(int(d))
        preds[d].append(int(s))
    npred = np.array([len(p) for p in preds])
    order = _topo(n, succs, npred)

    # X_G: comp cost, comm in, comm out, t-level, b-level (first of equal
    # maxima wins; a zero-cost step ends a path)
    ec = ob[edges[:, 0]] * comm_factor
    comm_in, comm_out = np.zeros(n), np.zeros(n)
    np.add.at(comm_in, edges[:, 1], ec)
    np.add.at(comm_out, edges[:, 0], ec)
    t_level, b_level = np.zeros(n), np.zeros(n)
    t_next, b_next = np.full(n, -1), np.full(n, -1)
    for v in reversed(order):
        best = 0.0
        for w in succs[v]:
            c = ob[v] * comm_factor + t_level[w]
            if c > best:
                best, t_next[v] = c, w
        t_level[v] = flops[v] + best
    for v in order:
        best = 0.0
        for p in preds[v]:
            c = ob[p] * comm_factor + b_level[p]
            if c > best:
                best, b_next[v] = c, p
        b_level[v] = flops[v] + best
    x = np.stack([flops, comm_in, comm_out, t_level, b_level], axis=1)

    # X_F: rate, overhead, memory (unmodelled: 1), mean out / in link
    # bandwidth and mean out latency, each over its fleet maximum
    off = ~np.eye(nd, dtype=bool)
    bwf = np.where(np.isfinite(fl["bw"]), fl["bw"], 0.0)
    cols = [fl["fps"], fl["overhead"], np.ones(nd),
            np.where(off, bwf, 0.0).sum(1) / max(nd - 1, 1),
            np.where(off, bwf, 0.0).sum(0) / max(nd - 1, 1),
            np.where(off, fl["lat"], 0.0).sum(1) / max(nd - 1, 1)]
    dev_x = np.stack([c / max(c.max(), 1e-30) for c in cols], axis=1)

    exec_t = fl["overhead"][None, :] + flops[:, None] / fl["fps"][None, :]
    exec_t[is_input] = 0.0
    spb = 1.0 / fl["bw"]
    np.fill_diagonal(spb, 0.0)
    P = max(int(npred.max(initial=0)), 1)
    C = max(max((len(s) for s in succs), default=0), 1)
    pred_pad = np.full((n, P), -1, np.int32)
    succ_pad = np.full((n, C), -1, np.int32)
    for v in range(n):
        pred_pad[v, :len(preds[v])] = preds[v]
        succ_pad[v, :len(succs[v])] = succs[v]
    f32 = lambda a: jnp.asarray(a, jnp.float32)             # noqa: E731
    return {
        "n": n, "nd": nd,
        "x": f32(_col_normalize(x)),
        "src": jnp.asarray(edges[:, 0], jnp.int32),
        "dst": jnp.asarray(edges[:, 1], jnp.int32),
        "ef": f32(_col_normalize(ec[:, None])),
        "b_path": jnp.asarray(_paths(b_next)),
        "t_path": jnp.asarray(_paths(t_next)),
        "pred": jnp.asarray(pred_pad), "succ": jnp.asarray(succ_pad),
        "npred": jnp.asarray(npred, jnp.int32),
        "n_input_preds": jnp.asarray(
            [sum(is_input[p] for p in preds[v]) for v in range(n)],
            jnp.int32),
        "is_input": jnp.asarray(is_input),
        "exec_t": f32(exec_t), "lat": f32(fl["lat"]), "spb": f32(spb),
        "bw": f32(fl["bw"]), "out_bytes": f32(ob), "flops": f32(flops),
        "total_flops": f32(max(flops.sum(), 1e-9)), "dev_x": f32(dev_x),
    }


# --------------------------------------------------------------- policy
def _mlp(p, x, dtype):
    layers = p["layers"]
    x = x.astype(dtype)
    for i, lp in enumerate(layers):
        x = (jnp.dot(x, lp["w"].astype(dtype), precision=HIGHEST)
             + lp["b"].astype(dtype))
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def _path_mean(h, path):
    m = (path >= 0)[..., None].astype(h.dtype)
    return (h[jnp.maximum(path, 0)] * m).sum(1) / jnp.maximum(m.sum(1), 1)


def encode(params, inst, dtype=jnp.float32):
    """Once-per-episode encodings: H, SEL logits, PLC's Z."""
    x, src, dst, ef = inst["x"], inst["src"], inst["dst"], inst["ef"]
    n = inst["n"]
    h = _mlp(params["gnn"]["embed"], x, dtype)
    for lp in params["gnn"]["layers"]:
        hs, hd = h[src], h[dst]
        e = ef.astype(dtype)
        mf = _mlp(lp["psi_fwd"], jnp.concatenate([hs, hd, e], -1), dtype)
        mb = _mlp(lp["psi_bwd"], jnp.concatenate([hd, hs, e], -1), dtype)
        a_in = jnp.zeros((n, mf.shape[1]), dtype).at[dst].add(mf)
        a_out = jnp.zeros((n, mb.shape[1]), dtype).at[src].add(mb)
        h = h + _mlp(lp["phi"], jnp.concatenate([h, a_in, a_out], -1), dtype)
    sel_in = jnp.concatenate([h, _path_mean(h, inst["b_path"]),
                              _path_mean(h, inst["t_path"]),
                              _mlp(params["sel_z"], x, dtype)], -1)
    sel = _mlp(params["sel_head"], sel_in, dtype)[:, 0].astype(jnp.float32)
    return h, sel, _mlp(params["plc_z"], x, dtype)


def _masked_logp(logits, mask):
    return jax.nn.log_softmax(
        jnp.where(mask, logits, jnp.finfo(jnp.float32).min))


def episode(params, inst, enc, key, eps, forced=None,
            dtype=jnp.float32):
    """One episode.  Returns the actions (n, 2), the assignment (n,) and
    per-step SEL / PLC log-probabilities and entropies (n,) each.  With
    ``forced`` (n, 2) the actions are replayed instead of drawn."""
    H, sel, zp = enc
    n, nd = inst["n"], inst["nd"]
    pred = inst["pred"]
    vid, did = jnp.arange(n), jnp.arange(nd)

    def step(carry, xs):
        key, placed, assigned, est_end, avail, comp, left, hsum, cnt = carry
        if forced is None:
            key, kv, kd = jax.random.split(key, 3)
        cand = ~placed & (left == 0)
        lpv = _masked_logp(sel, cand)
        if forced is None:
            k = jax.random.split(kv, 3)
            g = jax.random.gumbel(k[0], (n,))
            soft = jnp.argmax(lpv + g)
            unif = jnp.argmax(jnp.where(cand, g, -jnp.inf))
            v = jnp.where(jax.random.uniform(k[2]) < eps, unif, soft)
        else:
            v = xs[0]
        ent_v = -jnp.where(cand, jnp.exp(lpv) * lpv, 0.0).sum()

        # dynamic device features of v (ETF estimator)
        p = pred[v]
        pm = (p >= 0) & placed[jnp.maximum(p, 0)]
        ps = jnp.maximum(p, 0)
        s = assigned[ps]
        arr = (est_end[ps][:, None] + inst["lat"][s]
               + inst["out_bytes"][ps][:, None] * inst["spb"][s])
        anyp = pm.any()
        f2 = jnp.where(anyp, jnp.where(pm[:, None], arr, BIG).min(0), 0.0)
        f3 = jnp.where(anyp, jnp.where(pm[:, None], arr, -BIG).max(0), 0.0)
        f4 = jnp.maximum(avail, f3)
        on = jnp.zeros(nd).at[s].add(jnp.where(pm, inst["flops"][ps], 0.0))
        scale = jnp.maximum(jnp.maximum(avail.max(), f4.max()), 1e-9)
        tf = inst["total_flops"]
        xdev = jnp.concatenate(
            [jnp.stack([comp / tf, on / tf, f2 / scale, f3 / scale,
                        f4 / scale], 1), inst["dev_x"]], 1)
        hdev = hsum / jnp.maximum(cnt, 1.0)[:, None]
        y = _mlp(params["plc_y"], xdev, dtype)
        inp = jnp.concatenate([jnp.broadcast_to(H[v], (nd, H.shape[1])),
                               hdev.astype(dtype), y,
                               jnp.broadcast_to(zp[v], (nd, zp.shape[1]))],
                              -1)
        hid = _mlp(params["plc_head1"], inp, dtype)
        hid = jnp.where(hid >= 0, hid, 0.01 * hid)
        lpd = jax.nn.log_softmax(
            _mlp(params["plc_head2"], hid, dtype)[:, 0].astype(jnp.float32))
        if forced is None:
            k = jax.random.split(kd, 3)
            g = jax.random.gumbel(k[0], (nd,))
            d = jnp.where(jax.random.uniform(k[2]) < eps, jnp.argmax(g),
                          jnp.argmax(lpd + g))
        else:
            d = xs[1]
        ent_d = -(jnp.exp(lpd) * lpd).sum()

        start = jnp.maximum(avail[d], f3[d])
        end = start + inst["exec_t"][v, d]
        succ = inst["succ"][v]
        # one-element updates as selects: a batched scatter is slow
        on_v, on_d = vid == v, did == d
        n_succ = ((succ[:, None] == vid[None, :])
                  & (succ >= 0)[:, None]).sum(0)
        carry = (key, placed | on_v, jnp.where(on_v, d, assigned),
                 jnp.where(on_v, end, est_end), jnp.where(on_d, end, avail),
                 comp + jnp.where(on_d, inst["flops"][v], 0.0),
                 left - n_succ.astype(left.dtype),
                 hsum + jnp.where(on_d[:, None],
                                  H[v].astype(jnp.float32), 0.0),
                 cnt + on_d)
        return carry, (v, d, lpv[v], lpd[d], ent_v, ent_d)

    if forced is not None:
        key = jnp.zeros(2, jnp.uint32)          # unused by a replay
    init = (key, jnp.zeros(n, bool), jnp.zeros(n, jnp.int32),
            jnp.zeros(n), jnp.zeros(nd), jnp.zeros(nd), inst["npred"],
            jnp.zeros((nd, H.shape[1])), jnp.zeros(nd))
    xs = None if forced is None else (forced[:, 0], forced[:, 1])
    carry, (v, d, lv, ld, ev, ed) = jax.lax.scan(step, init, xs, length=n)
    return {"actions": jnp.stack([v, d], 1).astype(jnp.int32),
            "assignment": carry[2], "logp": lv + ld, "ent_v": ev,
            "ent_d": ed}


# ------------------------------------------------------------- makespan
def makespan(inst, A):
    """Work-conserving FIFO makespan of assignment ``A`` (n,), float32.

    Inputs are resident on every device at t = 0.  Tasks: one exec per
    non-input vertex on its device, one transfer per (producer, consumer
    device) pair off the producer's device, on the directed channel.  Each
    iteration either starts the first startable task, ordered by (ready
    time, execs first, insertion order), or, when none can start, pops the
    earliest completion, ordered by (end time, start order).

    Transfer state is one flat vector over slots k = d * n + v (v's output
    on device d), and every one-element update or per-slot lookup of a
    device's state is a select: under ``vmap`` a scatter, a gather or a
    short minor axis costs the chip many times the work."""
    n, nd = inst["n"], inst["nd"]
    A = A.astype(jnp.int32)
    is_in, succ = inst["is_input"], inst["succ"]
    need = inst["npred"]
    C = succ.shape[1]
    INF = jnp.float32(jnp.inf)
    dev_ids = jnp.arange(nd)
    vid = jnp.arange(n)
    slots = jnp.arange(nd * n)
    kd, kv = slots // n, slots % n
    A_k = A[kv]
    edur = inst["exec_t"][vid, A]
    xdur = inst["lat"][A_k, kd] + inst["out_bytes"][kv] / inst["bw"][A_k, kd]
    onA = A[None, :] == dev_ids[:, None]                            # (nd, n)
    chan_k = A_k * nd + kd                          # slot's channel A[v] -> d

    have = inst["n_input_preds"]
    ready0 = ~is_in & (have == need)

    def at1(x, i, val):
        """``x.at[i].set(val)`` for a vector ``x``, as a select."""
        return jnp.where(jnp.arange(x.shape[0]) == i, val, x)

    def at2(x, i, j, val):
        """``x.at[i, j].set(val)``, as a select."""
        m = ((jnp.arange(x.shape[0]) == i)[:, None]
             & (jnp.arange(x.shape[1]) == j)[None, :])
        return jnp.where(m, val, x)

    def col(x, v):                  # (nd,): the slots of vertex v
        return jnp.stack([x[d * n + v] for d in range(nd)])

    def set_col(x, v, c):           # the slots of vertex v set to c (nd,)
        spread = c[0]
        for d in range(1, nd):
            spread = jnp.where(kd == d, c[d], spread)
        return jnp.where(kv == v, spread, x)

    def dev_free_of(st):            # (n,): v's device is free at
        return jnp.where(onA, st["dev_free"][:, None], 0.0).sum(0)

    def chan_free_of(st):           # per slot: its channel is free at
        cf = st["chan_free"].ravel()
        out = jnp.zeros(nd * n)
        for c in range(nd * nd):
            out = jnp.where(chan_k == c, cf[c], out)
        return out

    st = dict(
        rdy=is_in[kv],
        have=have,
        rt_e=jnp.where(ready0, 0.0, INF),
        seq_e=jnp.where(ready0, jnp.cumsum(ready0) - 1, I32_BIG),
        start_e=is_in, run_e=jnp.full(n, INF), ctr_e=jnp.zeros(n, jnp.int32),
        rt_x=jnp.full(nd * n, INF), seq_x=jnp.full(nd * n, I32_BIG),
        start_x=jnp.zeros(nd * n, bool), run_x=jnp.full(nd * n, INF),
        ctr_x=jnp.zeros(nd * n, jnp.int32),
        dev_free=jnp.zeros(nd), chan_free=jnp.zeros((nd, nd)),
        t=jnp.float32(0.0), seq=ready0.sum().astype(jnp.int32),
        ctr=jnp.int32(0), done=jnp.int32(0), it=jnp.int32(0))
    n_compute = (~is_in).sum()
    max_it = 2 * (n + n * nd) + 2

    def materialize(st, v, d, is_exec):
        t = st["t"]
        w = jnp.maximum(succ[v], 0)
        valid = succ[v] >= 0
        hit = valid & (A[w] == d)
        onw = (w[:, None] == vid[None, :])                          # (C, n)
        have = st["have"] + (onw & hit[:, None]).sum(0)
        newly = hit & (have[w] == need[w]) & ~st["start_e"][w]
        rank = jnp.cumsum(newly) - 1
        tgt = onw & newly[:, None]
        rt_e = jnp.where(tgt.any(0), t, st["rt_e"])
        seq_e = jnp.where(tgt.any(0),
                          jnp.where(tgt, (st["seq"] + rank)[:, None],
                                    -1).max(0), st["seq_e"])
        n_new = newly.sum()
        # transfers out of v's device, in first-consumer order
        onj = valid[None, :] & (A[w][None, :] == dev_ids[:, None])
        firstj = jnp.where(onj, jnp.arange(C)[None, :], C).min(1)   # (nd,)
        emit = (is_exec & (firstj < C) & (dev_ids != d)
                & ~col(st["rdy"], v) & ~col(st["start_x"], v))
        xrank = (emit[None, :] & (firstj[None, :] < firstj[:, None])).sum(1)
        rt_x = set_col(st["rt_x"], v,
                       jnp.where(emit, t, col(st["rt_x"], v)))
        seq_x = set_col(st["seq_x"], v,
                        jnp.where(emit, st["seq"] + n_new + xrank,
                                  col(st["seq_x"], v)))
        return {**st, "rdy": at1(st["rdy"], d * n + v, True), "have": have,
                "rt_e": rt_e, "seq_e": seq_e, "rt_x": rt_x, "seq_x": seq_x,
                "seq": st["seq"] + n_new + emit.sum()}

    def start(st):
        t = st["t"]
        se = (st["rt_e"] < INF) & ~st["start_e"] & (dev_free_of(st) <= t)
        sx = ((st["rt_x"] < INF) & ~st["start_x"] & ~st["rdy"]
              & (chan_free_of(st) <= t))
        me = jnp.where(se, st["rt_e"], INF).min()
        mx = jnp.where(sx, st["rt_x"], INF).min()
        pick_e = se.any() & (me <= mx)
        ve = jnp.argmin(jnp.where(se & (st["rt_e"] == me), st["seq_e"],
                                  I32_BIG))
        kx = jnp.argmin(jnp.where(sx & (st["rt_x"] == mx), st["seq_x"],
                                  I32_BIG))
        dx, vx = kx // n, kx % n
        end_e = t + edur[ve]
        end_x = t + xdur[kx]
        de = A[ve]
        sx_dev = A[vx]
        return {**st,
                "dev_free": jnp.where(pick_e, at1(st["dev_free"], de, end_e),
                                      st["dev_free"]),
                "start_e": jnp.where(pick_e, at1(st["start_e"], ve, True),
                                     st["start_e"]),
                "run_e": jnp.where(pick_e, at1(st["run_e"], ve, end_e),
                                   st["run_e"]),
                "ctr_e": jnp.where(pick_e, at1(st["ctr_e"], ve, st["ctr"]),
                                   st["ctr_e"]),
                "chan_free": jnp.where(
                    pick_e, st["chan_free"],
                    at2(st["chan_free"], sx_dev, dx, end_x)),
                "start_x": jnp.where(pick_e, st["start_x"],
                                     at1(st["start_x"], kx, True)),
                "run_x": jnp.where(pick_e, st["run_x"],
                                   at1(st["run_x"], kx, end_x)),
                "ctr_x": jnp.where(pick_e, st["ctr_x"],
                                   at1(st["ctr_x"], kx, st["ctr"])),
                "ctr": st["ctr"] + 1}

    def pop(st):
        e = jnp.minimum(st["run_e"].min(), st["run_x"].min())
        ce = jnp.where(st["run_e"] == e, st["ctr_e"], I32_BIG)
        cx = jnp.where(st["run_x"] == e, st["ctr_x"], I32_BIG)
        is_exec = ce.min() < cx.min()
        v_e = jnp.argmin(ce)
        kx = jnp.argmin(cx)
        d_x, v_x = kx // n, kx % n
        st = {**st, "t": e,
              "run_e": jnp.where(is_exec, at1(st["run_e"], v_e, INF),
                                 st["run_e"]),
              "run_x": jnp.where(is_exec, st["run_x"],
                                 at1(st["run_x"], kx, INF)),
              "done": st["done"] + is_exec.astype(jnp.int32)}
        v = jnp.where(is_exec, v_e, v_x)
        d = jnp.where(is_exec, A[v_e], d_x)
        return materialize(st, v, d, is_exec)

    def body(st):
        t = st["t"]
        can = (((st["rt_e"] < INF) & ~st["start_e"]
                & (dev_free_of(st) <= t)).any()
               | ((st["rt_x"] < INF) & ~st["start_x"] & ~st["rdy"]
                  & (chan_free_of(st) <= t)).any())
        st = jax.lax.cond(can, start, pop, st)
        return {**st, "it": st["it"] + 1}

    def cond(st):
        return (st["done"] < n_compute) & (st["it"] < max_it)

    st = jax.lax.while_loop(cond, body, st)
    return st["t"], st["done"] == n_compute


# --------------------------------------------------------------- update
def linear(v0, v1, total, step):
    """The paper's linear schedule, evaluated in float32."""
    frac = jnp.clip(jnp.int32(step) / max(total, 1), 0.0, 1.0)
    return v0 + (v1 - v0) * frac


def _tree_norm(t):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree_util.tree_leaves(t)))


def adamw(params, grads, mu, nu, step, lr, b1=0.9, b2=0.999, eps=1e-8,
          clip=1.0):
    """Global-norm clipping, then AdamW without weight decay."""
    scale = jnp.minimum(1.0, clip / jnp.maximum(_tree_norm(grads), 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    step = step + 1
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = tm(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    bc1 = 1 - b1 ** jnp.float32(step)
    bc2 = 1 - b2 ** jnp.float32(step)
    params = tm(lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2)
                                                       + eps)),
                params, mu, nu)
    return params, grads, mu, nu, step


class Stage2Reference:
    """Follows the program's first updates from the same weights, trainer
    key and episode counter.  ``hp``: batch, entropy_weight, lr0, lr1,
    eps0, eps1, total_episodes.  ``block`` episodes are sampled, scored
    and differentiated at a time (``sample_block`` sampled and scored,
    where given), so the reference fits beside nothing else on the chip."""

    def __init__(self, inst, hp: dict, dtype=jnp.float32, block: int = 128,
                 fault: str | None = None, sample_block: int | None = None):
        self.inst, self.hp, self.dtype = inst, hp, dtype
        self.block = min(block, hp["batch"])
        self.sample_block = min(sample_block or block, hp["batch"])
        self.fault = fault
        inst_s = {k: v for k, v in inst.items() if k not in ("n", "nd")}
        n, nd = inst["n"], inst["nd"]

        def full(i):
            return {**i, "n": n, "nd": nd}

        @jax.jit
        def sample(params, keys, eps, i):
            i = full(i)
            enc = encode(params, i, dtype)
            return jax.vmap(lambda k: episode(params, i, enc, k, eps,
                                              dtype=dtype))(keys)

        @jax.jit
        def score(assign, i):
            return jax.vmap(lambda a: makespan(full(i), a))(assign)

        @jax.jit
        def grad(params, actions, advs, i):
            i = full(i)
            w = hp["entropy_weight"]

            def loss(p):
                enc = encode(p, i, dtype)
                out = jax.vmap(lambda a: episode(p, i, enc, None, 0.0,
                                                 forced=a, dtype=dtype))(
                    actions)
                ent = out["ent_v"].mean(1) + out["ent_d"].mean(1)
                return -(advs * out["logp"].sum(1) + w * ent).sum()

            return jax.grad(loss)(params)

        self._inst = inst_s
        self._sample, self._score, self._grad = sample, score, grad

    def follow(self, params, key, episode0: int, n_updates: int):
        """Run ``n_updates`` updates; return per update the makespans (K,),
        the surrogate loss, the clipped gradient the optimizer took, the
        parameters after it (all on the host) and the seconds spent
        sampling, scoring and differentiating."""
        hp, K, B, S = (self.hp, self.hp["batch"], self.block,
                       self.sample_block)
        tm = jax.tree_util.tree_map
        mu = tm(jnp.zeros_like, params)
        nu = tm(jnp.zeros_like, params)
        step = 0
        r_sum = r_sq = 0.0
        r_cnt = 0
        ep = episode0
        out = []
        for _ in range(n_updates):
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, K)
            eps = linear(hp["eps0"], hp["eps1"], hp["total_episodes"], ep)
            lr = linear(hp["lr0"], hp["lr1"], hp["total_episodes"], ep)
            t0 = time.perf_counter()
            recs = [self._sample(params, keys[i:i + S], eps, self._inst)
                    for i in range(0, K, S)]
            rec = jax.block_until_ready(
                {k: jnp.concatenate([r[k] for r in recs]) for k in recs[0]})
            t1 = time.perf_counter()
            ms, ok = zip(*[self._score(rec["assignment"][i:i + S],
                                       self._inst)
                           for i in range(0, K, S)])
            ms = np.concatenate([np.asarray(m) for m in ms])
            t2 = time.perf_counter()
            if not all(np.asarray(o).all() for o in ok):
                raise RuntimeError("reference makespan did not complete")
            if self.fault == "answer":
                ms = ms * np.float32(1.001)
            rs = -ms.astype(np.float64)
            if r_cnt:
                mean = r_sum / r_cnt
                std = np.sqrt(max(r_sq / r_cnt - mean * mean, 1e-12))
            else:
                mean, std = rs.mean(), 1.0
            advs = (rs - mean) / (max(std, rs.std()) + 1e-9)
            advs = advs.astype(np.float32)
            used = K if self.fault != "half_batch" else K // 2
            w = hp["entropy_weight"]
            ent = np.asarray(rec["ent_v"].mean(1) + rec["ent_d"].mean(1))
            logp = np.asarray(rec["logp"].sum(1))
            terms = -(advs * logp + w * ent)[:used]
            loss = float(np.mean(terms))
            g = None
            for i in range(0, used, B):
                gi = self._grad(params, rec["actions"][i:i + B],
                                jnp.asarray(advs[i:i + B]), self._inst)
                g = gi if g is None else tm(jnp.add, g, gi)
            g = jax.block_until_ready(tm(lambda x: x / used, g))
            t3 = time.perf_counter()
            if self.fault == "unchanged":
                gc = tm(jnp.zeros_like, g)
            else:
                params, gc, mu, nu, step = adamw(params, g, mu, nu, step, lr)
            r_sum += rs.sum()
            r_sq += (rs * rs).sum()
            r_cnt += K
            ep += K
            # the loss is a mean of per-episode terms that nearly cancel;
            # its rounding scales with the terms, so gaps are read on it
            out.append({"makespans": ms, "loss": loss,
                        "loss_scale": float(np.mean(np.abs(terms))),
                        "grad": tm(np.asarray, gc),
                        "params": tm(np.asarray, params),
                        "seconds": {"sample": t1 - t0, "score": t2 - t1,
                                    "grad": t3 - t2}})
        return out
