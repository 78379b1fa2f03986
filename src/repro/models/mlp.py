"""Feed-forward layers: dense (SwiGLU/GeGLU/GELU) and token-choice MoE.

The MoE uses sort-based capacity dispatch (sort token-expert assignments
by expert, bucket into an (E, C, D) buffer, batched expert einsum, scatter
back).  This lowers to sort + gather + batched-matmul + scatter in XLA —
no (T, E, C) one-hot blow-up — and when the expert axis is sharded over
the mesh's 'model' axis GSPMD turns the gather/scatter into the
expert-parallel collectives whose cost the roofline analysis measures.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import get_abstract_mesh

from ..parallel.annotate import constrain, constrain_first
from .common import dense_init, gated_act
from .config import MoEConfig


# ------------------------------------------------------------------ dense
def init_dense_ffn(key, d_model: int, d_ff: int, act: str, dtype):
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], d_model, d_ff, dtype),
                "w_up": dense_init(ks[1], d_model, d_ff, dtype),
                "w_down": dense_init(ks[2], d_ff, d_model, dtype)}
    return {"w_up": dense_init(ks[0], d_model, d_ff, dtype),
            "w_down": dense_init(ks[1], d_ff, d_model, dtype)}


def dense_ffn(params, x, act: str):
    if "w_gate" in params:
        h = gated_act(act, x @ params["w_gate"], x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"])
    return h @ params["w_down"]


# -------------------------------------------------------------------- MoE
def init_moe_ffn(key, d_model: int, cfg: MoEConfig, act: str, dtype):
    ks = jax.random.split(key, 4)
    E, F = cfg.n_experts, cfg.d_expert
    s_in = jnp.sqrt(1.0 / d_model)
    s_out = jnp.sqrt(1.0 / F)
    return {
        "router": dense_init(ks[0], d_model, E, jnp.float32),
        "w_gate": jax.random.normal(ks[1], (E, d_model, F), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (E, d_model, F), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (E, F, d_model), dtype) * s_out,
    }


def moe_ffn(params, x, cfg: MoEConfig, act: str):
    """x: (B, S, D) -> (B, S, D), plus aux load-balancing loss."""
    if cfg.dispatch == "shard_map":
        mesh = get_abstract_mesh()
        if (mesh is not None and "model" in mesh.axis_names
                and cfg.n_experts % dict(mesh.shape)["model"] == 0):
            return _moe_ffn_shard_map(params, x, cfg, act, mesh)
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, D)

    logits = (xf.astype(jnp.float32) @ params["router"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # aux loss (Switch-style): mean prob * mean assignment fraction
    me = probs.mean(0)
    ce = jnp.zeros(E).at[expert_idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    cap = int(max(1, round(T * K / E * cfg.capacity_factor)))

    flat_e = expert_idx.reshape(-1)                             # (TK,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)                                 # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.zeros(E, jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K) - starts[se]
    keep = pos < cap
    slot = se * cap + jnp.clip(pos, 0, cap - 1)                 # (TK,)

    buf = jnp.zeros((E * cap, D), x.dtype)
    gathered = jnp.where(keep[:, None], xf[st], 0.0)
    buf = buf.at[slot].add(gathered)                            # (E*cap, D)
    # expert-parallel dispatch: bucketed tokens sharded over the expert
    # axis ('model') when E divides it -> GSPMD lowers the scatter/gather
    # to all-to-alls.  When it doesn't (granite: 40 experts), the
    # 'token_parallel' fallback shards the capacity dim instead (§Perf).
    dims = (0, 1) if cfg.fallback == "token_parallel" else (0,)
    buf = constrain_first(buf.reshape(E, cap, D), "model", dims)

    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = gated_act(act if act in ("swiglu", "geglu") else "swiglu", g, u)
    out_buf = constrain_first(jnp.einsum("ecf,efd->ecd", h,
                                         params["w_down"]), "model", dims)

    vals = out_buf.reshape(E * cap, D)[slot]                    # (TK, D)
    contrib = jnp.where(keep[:, None], sw[:, None].astype(x.dtype) * vals,
                        0.0)
    y = jnp.zeros((T, D), x.dtype).at[st].add(contrib)
    return y.reshape(B, S, D), aux


# --------------------------------------------------- shard_map dispatch
def _moe_ffn_shard_map(params, x, cfg: MoEConfig, act: str, mesh):
    """Expert-parallel dispatch with explicit locality (§Perf).

    Layout: tokens sharded over the batch axes and REPLICATED over
    'model'; each model shard owns E/m contiguous experts.  Every shard
    buckets only the assignments routed to ITS experts (pure local sort /
    scatter — the GSPMD baseline turns these into giant all-reduces), runs
    the local expert einsums, and the partial token outputs are combined
    with ONE psum over 'model' per layer: collective bytes drop from
    O(E*cap*D) all-reduces to exactly T_loc*D.
    Capacity is per-shard-local (cap ~ T_loc*K/E * factor), so dropping
    statistics differ slightly from the gspmd path (documented)."""
    from jax.sharding import PartitionSpec as P

    E, K = cfg.n_experts, cfg.top_k
    B, S, D = x.shape
    sizes = dict(mesh.shape)
    m_size = sizes["model"]
    E_loc = E // m_size
    batch_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_ax if len(batch_ax) > 1 else (batch_ax[0] if batch_ax
                                                else None)

    def body(x_l, router, wg, wu, wd):
        midx = jax.lax.axis_index("model")
        Bl, Sl, _ = x_l.shape
        T = Bl * Sl
        xf = x_l.reshape(T, D)
        logits = xf.astype(jnp.float32) @ router            # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(0)
        cevec = jnp.zeros(E).at[expert_idx.reshape(-1)].add(1.0) / (T * K)
        aux = E * jnp.sum(me * cevec)

        cap = int(max(1, round(T * K / E * cfg.capacity_factor)))
        flat_e = expert_idx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), K)
        flat_w = gate_vals.reshape(-1)
        lo = midx * E_loc
        local = (flat_e >= lo) & (flat_e < lo + E_loc)
        le = jnp.where(local, flat_e - lo, E_loc)           # E_loc = trash
        order = jnp.argsort(le)
        se, st, sw = le[order], flat_t[order], flat_w[order]
        counts = jnp.zeros(E_loc + 1, jnp.int32).at[se].add(1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(T * K) - starts[se]
        keep = (se < E_loc) & (pos < cap)
        slot = jnp.clip(se, 0, E_loc - 1) * cap + jnp.clip(pos, 0, cap - 1)

        buf = jnp.zeros((E_loc * cap, D), x_l.dtype)
        buf = buf.at[slot].add(jnp.where(keep[:, None], xf[st], 0.0))
        buf = buf.reshape(E_loc, cap, D)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = gated_act(act if act in ("swiglu", "geglu") else "swiglu", g, u)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_loc * cap, D)

        vals = out_buf[slot]
        contrib = jnp.where(keep[:, None],
                            sw[:, None].astype(x_l.dtype) * vals, 0.0)
        y = jnp.zeros((T, D), x_l.dtype).at[st].add(contrib)
        y = jax.lax.psum(y, "model")                        # the ONE psum
        if batch_ax:
            aux = jax.lax.pmean(aux, batch_ax)
        return y.reshape(Bl, Sl, D), aux

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)
    return mapped(x, params["router"].astype(jnp.float32),
                  params["w_gate"], params["w_up"], params["w_down"])
