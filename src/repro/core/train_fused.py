"""Device-resident fused Stage-II engine: rollout -> reward -> update in
one jitted dispatch.

``stage2_sim_batched`` (the PR-2 reference path) pays three dispatches and
two host<->device round-trips per update: a vmapped sampling rollout, a
numpy reward sweep over the pulled-back assignments, and a forced-replay
gradient pass that re-runs the whole |V|-step scan just to recompute the
log-probs the sampling pass already evaluated.  This module collapses the
update into one XLA computation, and scans U updates per dispatch:

1. **Recorded sampling** (:func:`sample_episodes`): one forward scan per
   episode that makes the *same decisions* as ``assign.rollout`` but draws
   no RNG inside the loop — the whole per-step key chain
   (``split(key, 3)`` per step, ``split(kv, 3)`` per pick) is precomputed
   and the categorical draws become ``argmax(logp + G[s])`` against
   precomputed gumbel tables, which is exactly how
   ``jax.random.categorical`` is defined.  With ``eps == 0`` the sampled
   actions are **bit-identical** to ``rollout``'s (the parity contract
   with ``stage2_sim_batched``); with ``eps > 0`` the exploration draw
   reuses the policy draw's gumbel row (each branch stays marginally
   correct — only one is kept — but the joint stream differs from the
   serial path's independent draw).  The scan records what the gradient
   pass needs: actions, candidate masks, and the dynamic device features.
2. **Reward oracle**: the sampled assignments are scored on-device by
   ``sim_jax.makespan_fifo_batch`` — no host round-trip, rewards stay
   inside the jit.
3. **Scan-free policy gradient** (:func:`fused_pg_loss`): because the
   candidate masks and device features are recorded (they depend only on
   actions, not parameters), every step's SEL/PLC log-prob and entropy is
   recomputed *in parallel over steps* — batched masked log-softmaxes and
   an exclusive cumulative sum for the placed-vertex device embeddings —
   instead of a second sequential scan.  Differentiating this gives the
   same REINFORCE gradient as ``_pg_loss_and_grad_batch``'s forced
   replay, to float tolerance, at a fraction of the cost.
4. **Optimizer + running stats on device**: advantages use the same
   running baseline/std bookkeeping as the host trainer (values carried
   as f32 scalars), AdamW applies in the same dispatch, and
   ``lax.scan`` over U updates makes a whole training chunk one XLA call.

Ablation modes (paper Table 3) are plumbed through exactly like the
reference path: heuristic-replaced policies still sample (their actions
come from the CP/ETF rules) and their log-prob terms drop out of the
loss.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from ..kernels import default_interpret
from ..train.optim import AdamState, adamw_update
from .assign import BIG, GraphData, _device_features, _etf_update
from .nn import apply_mlp, leaky_relu, masked_log_softmax
from .policies import episode_encodings, plc_logits
from .sim_jax import (SimGraph, _makespan_fifo_batch_pallas,
                      _makespan_fifo_batch_xla)


class RewardStats(NamedTuple):
    """Device twin of DopplerTrainer's running reward statistics."""
    r_sum: jnp.ndarray
    r_sqsum: jnp.ndarray
    r_count: jnp.ndarray

    @classmethod
    def make(cls, r_sum=0.0, r_sqsum=0.0, r_count=0):
        return cls(jnp.float32(r_sum), jnp.float32(r_sqsum),
                   jnp.int32(r_count))

    def baseline(self):
        """(mean, std) with the trainer's exact (0, 1) empty-stats case."""
        cnt = jnp.maximum(self.r_count, 1).astype(jnp.float32)
        mean = self.r_sum / cnt
        var = jnp.maximum(self.r_sqsum / cnt - mean * mean, 1e-12)
        has = self.r_count > 0
        return (jnp.where(has, mean, 0.0),
                jnp.where(has, jnp.sqrt(var), 1.0))

    def update(self, rs):
        return RewardStats(self.r_sum + rs.sum(),
                           self.r_sqsum + (rs * rs).sum(),
                           self.r_count + rs.shape[0])


# ------------------------------------------------------------- RNG stream
def _episode_key_chain(keys, n: int):
    """Per-step ``(kv, kd)`` pick keys for K episodes, step-major
    ``(n, K, 2)`` each.

    Replays ``rollout``'s exact key chain: per step
    ``key, kv, kd = split(key, 3)``.  The chain is inherently sequential
    but tiny (two u32 per episode-step), so it is precomputed; the *wide*
    per-step draws are generated inside the sampling scan body
    (:func:`_step_draws`), so no (K, S, n) gumbel table is ever
    materialized — the streamed-sampling half of the memory-bounded
    engine."""

    def chain(ks, _):
        out = jax.vmap(lambda k: jax.random.split(k, 3))(ks)  # (K, 3, 2)
        return out[:, 0], (out[:, 1], out[:, 2])

    _, (kvs, kds) = jax.lax.scan(chain, keys, None, length=n)  # (n, K, 2)
    return kvs, kds


def _step_draws(kv_row, kd_row, n: int, nd: int):
    """One step's categorical gumbel rows and exploration uniforms for K
    episodes, generated on the fly from that step's pick keys.

    Each ``pick`` splits its key into (categorical, uniform-categorical,
    bernoulli); the gumbel rows reproduce ``jax.random.categorical``'s
    ``argmax(gumbel(k, shape) + logits)`` draw bit-for-bit.  Values are
    bit-identical to the corresponding :func:`_episode_rng_tables` slices
    (same keys, same shapes) — only the materialization point differs."""
    sel = jax.vmap(lambda k: jax.random.split(k, 3))(kv_row)   # (K, 3, 2)
    plc = jax.vmap(lambda k: jax.random.split(k, 3))(kd_row)
    gs = jax.vmap(lambda k: jax.random.gumbel(k, (n,)))(sel[:, 0])
    gp = jax.vmap(lambda k: jax.random.gumbel(k, (nd,)))(plc[:, 0])
    us = jax.vmap(jax.random.uniform)(sel[:, 2])
    up = jax.vmap(jax.random.uniform)(plc[:, 2])
    return gs, gp, us, up


def _episode_rng_tables(keys, n: int, nd: int):
    """Materialized step-major draw tables (kept as the reference /
    debugging form of the stream; the sampling scan itself consumes
    :func:`_step_draws` rows and never builds these)."""
    K = keys.shape[0]
    kvs, kds = _episode_key_chain(keys, n)
    sel = jax.vmap(lambda k: jax.random.split(k, 3))(kvs.reshape(-1, 2))
    plc = jax.vmap(lambda k: jax.random.split(k, 3))(kds.reshape(-1, 2))
    g_sel = jax.vmap(lambda k: jax.random.gumbel(k, (n,)))(
        sel[:, 0]).reshape(n, K, n)
    g_plc = jax.vmap(lambda k: jax.random.gumbel(k, (nd,)))(
        plc[:, 0]).reshape(n, K, nd)
    u_sel = jax.vmap(jax.random.uniform)(sel[:, 2]).reshape(n, K)
    u_plc = jax.vmap(jax.random.uniform)(plc[:, 2]).reshape(n, K)
    return g_sel, g_plc, u_sel, u_plc


# ------------------------------------------------- phase 1: record sample
def _sample_scan(params, gd: GraphData, keys, eps, sel_mode: str,
                 plc_mode: str, enc, record: str):
    """Shared recorded-sampling scan over K episodes.

    ``enc`` is the precomputed ``(H, sel_logits, z_plc)`` episode
    encodings (hoisted so a chunked caller evaluates the GNN once per
    update, not once per chunk).  ``record`` selects what the scan emits:

    * ``"full"`` — the classic recordings: per-step SEL softmax rows
      ``sel_p`` (K, S, n) plus ``sel_lse`` / ``sel_ex`` scalars, for
      :func:`fused_pg_loss`.
    * ``"reduced"`` — the SEL-linearization recordings pre-reduced
      *inside the scan carry* to their (K, n) / (K,) sufficient
      statistics (``sel_P = Σ_s p_s``, ``sel_Q = Σ_s p_s·ex_s``,
      ``sel_lse_sum``, ``sel_ex_sum``) for
      :func:`fused_pg_loss_reduced`; nothing O(K·S·n) is ever stacked.
      The device-feature recording is also trimmed to its episode-dynamic
      columns (``x_dyn``, (K, S, nd, 5)) — the trailing fleet columns are
      the episode-static ``gd.dev_x``, re-concatenated bit-identically
      inside the loss.

    RNG is streamed: the per-step gumbel rows / uniforms are generated in
    the scan body from the precomputed key chain (:func:`_step_draws`),
    bit-identical to the materialized tables.
    """
    n, nd = gd.n, gd.nd
    K = keys.shape[0]
    H, sel_logits, z_plc = enc
    dh = H.shape[1]
    kvs, kds = _episode_key_chain(keys, n)
    feats = jax.vmap(_device_features, in_axes=(None, 0, 0, 0, 0, 0, 0))
    upd = jax.vmap(_etf_update, in_axes=(None, 0, 0, 0, 0))
    karange = jnp.arange(K)

    placed = jnp.zeros((K, n), dtype=bool)
    assigned = jnp.zeros((K, n), dtype=jnp.int32)
    est_end = jnp.zeros((K, n), dtype=jnp.float32)
    device_avail = jnp.zeros((K, nd), dtype=jnp.float32)
    dev_comp = jnp.zeros((K, nd), dtype=jnp.float32)
    n_preds = (gd.preds >= 0).sum(1).astype(jnp.int32)
    unassigned_preds = jnp.broadcast_to(
        jnp.concatenate([n_preds, jnp.zeros(1, jnp.int32)]),
        (K, n + 1))
    dev_hsum = jnp.zeros((K, nd, dh), dtype=jnp.float32)
    dev_cnt = jnp.zeros((K, nd), dtype=jnp.float32)
    acc0 = (jnp.zeros((K, n)), jnp.zeros((K, n)),
            jnp.zeros(K), jnp.zeros(K))

    def step(carry, xs):
        state, acc = carry
        kv_row, kd_row = xs                       # (K, 2) each
        gs, gp, us, up = _step_draws(kv_row, kd_row, n, nd)
        (placed, assigned, est_end, device_avail, dev_comp,
         unassigned_preds, dev_hsum, dev_cnt) = state

        cand = (~placed) & (unassigned_preds[:, :n] == 0)
        logp_v = jax.vmap(masked_log_softmax, in_axes=(None, 0))(
            sel_logits, cand)
        v_soft = jnp.argmax(logp_v + gs, axis=-1)
        # == argmax(where(cand, 0, -inf) + gs): -inf + g = -inf, 0 + g = g
        v_unif = jnp.argmax(jnp.where(cand, gs, -jnp.inf), axis=-1)
        v = jnp.where(us < eps, v_unif, v_soft).astype(jnp.int32)
        if sel_mode == "cp":
            v = jnp.argmax(jnp.where(cand, gd.t_level, -BIG),
                           axis=-1).astype(jnp.int32)

        x_dev, ready = feats(gd, v, placed, assigned, est_end,
                             device_avail, dev_comp)
        h_dev = dev_hsum / jnp.maximum(dev_cnt[..., None], 1.0)
        logits_d = jax.vmap(plc_logits, in_axes=(None, 0, 0, 0, 0))(
            params, H[v], h_dev, x_dev, z_plc[v])
        logp_d = jax.vmap(masked_log_softmax, in_axes=(0, None))(
            logits_d, jnp.ones(nd, dtype=bool))
        d_soft = jnp.argmax(logp_d + gp, axis=-1)
        d_unif = jnp.argmax(gp, axis=-1)
        d = jnp.where(up < eps, d_unif, d_soft).astype(jnp.int32)
        if plc_mode == "etf":
            finish = (jnp.maximum(device_avail, ready)
                      + gd.exec_time[v])
            d = jnp.argmin(finish, axis=-1).astype(jnp.int32)

        state = upd(gd, v, d, ready[karange, d], state)
        (placed, assigned, est_end, device_avail, dev_comp,
         unassigned_preds, dev_hsum, dev_cnt) = state
        dev_hsum = dev_hsum.at[karange, d].add(H[v])
        dev_cnt = dev_cnt.at[karange, d].add(1.0)
        state = (placed, assigned, est_end, device_avail, dev_comp,
                 unassigned_preds, dev_hsum, dev_cnt)
        # the SEL softmax row + scalars that make the SEL loss term
        # linear in sel_logits (see fused_pg_loss)
        p_row = jnp.exp(logp_v)
        lse = (sel_logits[v]
               - jnp.take_along_axis(logp_v, v[:, None], 1)[:, 0])
        ex = (p_row * jnp.where(cand, sel_logits[None, :], 0.0)).sum(-1)
        if record == "full":
            return (state, acc), (v, d, x_dev, p_row, lse, ex)
        selP, selQ, lse_sum, ex_sum = acc
        acc = (selP + p_row, selQ + p_row * ex[:, None],
               lse_sum + lse, ex_sum + ex)
        # drop the episode-static fleet columns (gd.dev_x) — the loss
        # re-concatenates them, so only the 5 dynamic columns are stored
        return (state, acc), (v, d, x_dev[..., :-gd.dev_x.shape[1]])

    init = (placed, assigned, est_end, device_avail, dev_comp,
            unassigned_preds, dev_hsum, dev_cnt)
    (state, acc), outs = jax.lax.scan(step, (init, acc0), (kvs, kds))
    if record == "full":
        v_seq, d_seq, x_devs, sel_p, sel_lse, sel_ex = outs
        # step-major -> episode-major
        return {"actions": jnp.stack([v_seq, d_seq], -1).swapaxes(0, 1),
                "assignment": state[1],
                "x_dev": x_devs.swapaxes(0, 1),
                "sel_p": sel_p.swapaxes(0, 1),
                "sel_lse": sel_lse.swapaxes(0, 1),
                "sel_ex": sel_ex.swapaxes(0, 1)}
    v_seq, d_seq, x_dyns = outs
    selP, selQ, lse_sum, ex_sum = acc
    return {"actions": jnp.stack([v_seq, d_seq], -1).swapaxes(0, 1),
            "assignment": state[1],
            "x_dyn": x_dyns.swapaxes(0, 1),
            "sel_P": selP, "sel_Q": selQ,
            "sel_lse_sum": lse_sum, "sel_ex_sum": ex_sum}


@partial(jax.jit, static_argnames=("sel_mode", "plc_mode",
                                   "encoder_backend"))
def sample_episodes(params, gd: GraphData, keys, eps,
                    sel_mode: str = "learned", plc_mode: str = "learned",
                    encoder_backend: str = "xla"):
    """K recorded sampling episodes in one batch-explicit forward scan.

    Returns dict with ``actions`` (K, n, 2), ``assignment`` (K, n),
    ``x_dev`` (K, n, nd, F) dynamic device features per step, and the
    SEL-linearization recordings ``sel_p`` (K, n, n) softmax rows /
    ``sel_lse`` / ``sel_ex`` (K, n) — everything :func:`fused_pg_loss`
    needs to recompute log-probs without a second scan.

    Actions are **bit-identical** to ``rollout``'s for the same keys when
    ``eps == 0`` (the parity contract with ``stage2_sim_batched``): the
    per-step key chain and streamed gumbel draws replay
    ``jax.random.categorical``'s draws exactly.  With ``eps > 0`` the
    exploration pick reuses the policy pick's gumbel row (each branch
    stays marginally correct — only one is kept — so the sampling
    distribution is unchanged, but the joint stream differs from the
    serial path's independent draw; see the module docstring).
    """
    with jax.named_scope("doppler.encoder"):
        enc = episode_encodings(
            params, gd.x, gd.edges, gd.edge_feat, gd.b_path, gd.t_path,
            backend=encoder_backend)
    return _sample_scan(params, gd, keys, eps, sel_mode, plc_mode, enc,
                        record="full")


# ------------------------------------------- phase 2: parallel log-probs
def _plc_step_logps(params, H, z_plc, nd: int, x_devs, v, d):
    """Per-step PLC log-probs/entropies, parallel over steps.

    PLC head1 on [H_v || h_dev || y || z_v] is evaluated as split
    matmuls: the H_v / z_v blocks are (n, dh) matmuls gathered per step,
    and the h_dev block commutes with the exclusive prefix sum (matmul
    is linear), so the (K, S, nd, 2dh+dy+dz) concat never materializes.
    Shared by the fused REINFORCE and imitation losses.
    """
    w1 = params["plc_head1"]["layers"][0]
    dh = H.shape[1]
    dy = params["plc_y"]["layers"][-1]["b"].shape[0]
    w_h, w_hd, w_y, w_z = (w1["w"][:dh], w1["w"][dh:2 * dh],
                           w1["w"][2 * dh:2 * dh + dy],
                           w1["w"][2 * dh + dy:])
    GH = H @ w_h + z_plc @ w_z + w1["b"]                # (n, hid)
    GD = H @ w_hd                                       # (n, hid)
    onehot = (d[..., None] == jnp.arange(nd)).astype(jnp.float32)
    contrib = onehot[..., None] * GD[v][:, :, None, :]  # (K, S, nd, hid)
    gsum = jnp.cumsum(contrib, axis=1) - contrib        # exclusive
    cnt = jnp.cumsum(onehot, axis=1) - onehot
    y = apply_mlp(params["plc_y"], x_devs)              # (K, S, nd, dy)
    hid = leaky_relu(GH[v][:, :, None, :]
                     + gsum / jnp.maximum(cnt[..., None], 1.0)
                     + y @ w_y)
    logits_d = apply_mlp(params["plc_head2"], hid)[..., 0]  # (K, S, nd)
    pl = jax.nn.log_softmax(logits_d)
    plc_logp = jnp.take_along_axis(pl, d[..., None], -1)[..., 0]
    plc_ent = -(jnp.exp(pl) * pl).sum(-1)
    return plc_logp, plc_ent


def _parallel_step_logps(params, gd: GraphData, masks, x_devs, actions,
                         sel: bool = True, plc: bool = True,
                         encoder_backend: str = "xla"):
    """Per-step SEL/PLC log-probs and entropies for recorded episodes,
    evaluated in parallel over steps (no scan).

    Returns ``(sel_logp, sel_ent, plc_logp, plc_ent)``, each (K, S) (or
    None when the corresponding policy is disabled).
    """
    H, sel_logits, z_plc = episode_encodings(
        params, gd.x, gd.edges, gd.edge_feat, gd.b_path, gd.t_path,
        backend=encoder_backend)
    v = actions[..., 0]                                     # (K, S)
    d = actions[..., 1]
    neg = jnp.finfo(sel_logits.dtype).min

    sel_logp = sel_ent = plc_logp = plc_ent = None
    if sel:
        # one masked softmax pass yields the chosen log-prob and the
        # entropy: H(p) = lse - E_p[logits] over the candidate set
        z = jnp.where(masks, sel_logits[None, None, :], neg)
        zmax = z.max(-1)
        ez = jnp.exp(z - zmax[..., None])
        sez = ez.sum(-1)
        lse = jnp.log(sez) + zmax
        sel_logp = (jnp.take_along_axis(z, v[..., None], -1)[..., 0]
                    - lse)                                  # (K, S)
        e_logits = jnp.where(masks, ez * z, 0.0).sum(-1) / sez
        sel_ent = lse - e_logits
    if plc:
        plc_logp, plc_ent = _plc_step_logps(params, H, z_plc, gd.nd,
                                            x_devs, v, d)
    return sel_logp, sel_ent, plc_logp, plc_ent


def fused_pg_loss(params, gd: GraphData, rec, advs, entropy_w,
                  sel_learned: bool = True, plc_learned: bool = True,
                  encoder_backend: str = "xla"):
    """Batch REINFORCE surrogate with all steps evaluated in parallel.

    Same math as ``training._pg_loss_and_grad_batch``'s forced replay —
    per episode ``-(adv * logp + w * ent)`` with ``logp`` the summed step
    log-probs and ``ent`` the mean step entropies, averaged over the
    batch — but evaluated without a second |V|-step scan:

    * **SEL** is linear in the episode-static ``sel_logits``, so with the
      softmax rows recorded at the sampling parameters the whole term is
      written as ``value + coeff · (x - stop_grad(x))``: exact value AND
      exact gradient (``d logp/dx = onehot - p``,
      ``d ent/dx_j = -p_j (x_j - E_p[x])``), with the (K, S, n)
      recordings pre-reduced to (K, n) coefficients outside autodiff.
    * **PLC** is rebuilt from the recorded (parameter-free) device
      features and placement order: the placed-vertex mean embeddings
      become an exclusive prefix sum and head1 splits into per-block
      matmuls, so gradients flow through the GNN exactly as in the
      replay.
    """
    H, sel_logits, z_plc = episode_encodings(
        params, gd.x, gd.edges, gd.edge_feat, gd.b_path, gd.t_path,
        backend=encoder_backend)
    nd = gd.nd
    actions = rec["actions"]
    v = actions[..., 0]                                     # (K, S)
    d = actions[..., 1]
    S = v.shape[1]

    logp = 0.0
    ent = 0.0
    if sel_learned:
        x = sel_logits
        dx = x - jax.lax.stop_gradient(x)                   # 0-valued
        p = jax.lax.stop_gradient(rec["sel_p"])             # (K, S, n)
        lse0 = jax.lax.stop_gradient(rec["sel_lse"])        # (K, S)
        ex0 = jax.lax.stop_gradient(rec["sel_ex"])          # (K, S)
        P = p.sum(1)                                        # (K, n)
        Q = jnp.einsum("ksn,ks->kn", p, ex0)                # (K, n)
        sel_logp_sum = (x[v].sum(-1) - lse0.sum(-1)
                        - (P * dx[None, :]).sum(-1))
        coeff = -(P * jax.lax.stop_gradient(x)[None, :] - Q) / S
        sel_ent_mean = ((lse0 - ex0).mean(-1)
                        + (coeff * dx[None, :]).sum(-1))
        logp = logp + sel_logp_sum
        ent = ent + sel_ent_mean
    if plc_learned:
        plc_logp, plc_ent = _plc_step_logps(params, H, z_plc, nd,
                                            rec["x_dev"], v, d)
        logp = logp + plc_logp.sum(-1)
        ent = ent + plc_ent.mean(-1)
    return (-(advs * logp + entropy_w * ent)).mean()


def fused_pg_loss_reduced(params, gd: GraphData, rec, advs, entropy_w,
                          sel_learned: bool = True,
                          plc_learned: bool = True,
                          encoder_backend: str = "xla"):
    """:func:`fused_pg_loss` on the pre-reduced SEL recordings.

    Identical math: the SEL term of the REINFORCE surrogate only touches
    the recordings through ``P = Σ_s p_s``, ``Q = Σ_s p_s·ex_s``,
    ``Σ_s lse_s`` and ``Σ_s ex_s`` — sums the sampling scan already
    accumulated in its carry (``record="reduced"``), so the (K, S, n)
    softmax rows never exist.  Values/gradients match the full-recording
    loss up to float summation order.  The PLC term is unchanged (its
    recordings are O(K·S·nd)).
    """
    H, sel_logits, z_plc = episode_encodings(
        params, gd.x, gd.edges, gd.edge_feat, gd.b_path, gd.t_path,
        backend=encoder_backend)
    actions = rec["actions"]
    v = actions[..., 0]                                     # (K, S)
    d = actions[..., 1]
    S = v.shape[1]

    logp = 0.0
    ent = 0.0
    if sel_learned:
        x = sel_logits
        dx = x - jax.lax.stop_gradient(x)                   # 0-valued
        P = jax.lax.stop_gradient(rec["sel_P"])             # (K, n)
        Q = jax.lax.stop_gradient(rec["sel_Q"])             # (K, n)
        lse_sum = jax.lax.stop_gradient(rec["sel_lse_sum"])
        ex_sum = jax.lax.stop_gradient(rec["sel_ex_sum"])
        sel_logp_sum = (x[v].sum(-1) - lse_sum
                        - (P * dx[None, :]).sum(-1))
        coeff = -(P * jax.lax.stop_gradient(x)[None, :] - Q) / S
        sel_ent_mean = ((lse_sum - ex_sum) / S
                        + (coeff * dx[None, :]).sum(-1))
        logp = logp + sel_logp_sum
        ent = ent + sel_ent_mean
    if plc_learned:
        # rebuild the full device features bit-identically: the recording
        # keeps only the dynamic columns, the fleet tail is gd.dev_x
        x_dyn = rec["x_dyn"]
        x_devs = jnp.concatenate(
            [x_dyn, jnp.broadcast_to(gd.dev_x,
                                     x_dyn.shape[:3] + (gd.dev_x.shape[1],))],
            axis=-1)
        plc_logp, plc_ent = _plc_step_logps(params, H, z_plc, gd.nd,
                                            x_devs, v, d)
        logp = logp + plc_logp.sum(-1)
        ent = ent + plc_ent.mean(-1)
    return (-(advs * logp + entropy_w * ent)).mean()


# --------------------------------------------------------- fused updates
@dataclasses.dataclass(frozen=True)
class FusedStage2Config:
    """Static configuration of one fused Stage-II chunk.

    ``encoder_backend`` routes the GNN aggregation ("xla" | "pallas"
    kernels.gnn_mp); ``oracle_backend`` routes the batched WC reward
    oracle ("xla" | "pallas" kernels.wc_oracle).  Both default to the
    reference XLA paths and are decision-exactness-pinned by the
    conformance/property suites.

    ``chunk_size`` bounds peak memory at large batch: the per-shard
    episode batch is sampled and scored in micro-chunks of this size
    (``None`` auto-chunks when the shard exceeds 64 episodes, with
    chunks of at most 128; ``0`` forces the monolithic engine).
    ``grad_chunk_size`` is the gradient
    accumulation micro-chunk (``None`` = auto, ≤ 64); the accumulated
    gradient equals the monolithic batch gradient up to float summation
    order (parity-tested at 1e-6)."""
    batch_size: int
    updates: int                  # scan length of one dispatch
    sel_mode: str = "learned"
    plc_mode: str = "learned"
    sel_learned: bool = True
    plc_learned: bool = True
    normalize_adv: bool = True
    entropy_weight: float = 1e-2
    encoder_backend: str = "xla"
    oracle_backend: str = "xla"
    chunk_size: int | None = None
    grad_chunk_size: int | None = None


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``cap`` (≥ 1)."""
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


# auto-chunk threshold: shards up to AUTO_CHUNK episodes stay on the
# monolithic engine (bit-compatible with the pre-chunking path); larger
# shards switch to the reduced-recording engine, sampled/scored in
# micro-chunks of at most AUTO_CHUNK_CAP episodes.  The threshold sits
# below the cap so a 128-episode shard — where the monolithic
# (K, S, n) SEL recording already costs ~140 MB on a 512-vertex graph —
# runs reduced even though it fits in a single micro-chunk.
AUTO_CHUNK = 64
AUTO_CHUNK_CAP = 128


def build_fused_stage2(cfg: FusedStage2Config, gd: GraphData,
                       sg: SimGraph, lr_sched, eps_sched,
                       n_devices: int = 1, spmd: str = "shard_map"):
    """Compile a ``train_chunk(params, opt, rstats, key, episode)`` that
    runs ``cfg.updates`` fused Stage-II updates in one XLA dispatch.

    Each inner update replays the reference path's bookkeeping exactly:
    the trainer key splits once per update, the batch keys split off it,
    eps/lr come from the schedules at the pre-update episode counter, the
    advantage uses the running baseline (batch mean when empty) and the
    ``max(running std, batch std)`` normalizer, and the running stats are
    updated after the gradient — see ``DopplerTrainer.stage2_sim_batched``.

    **Chunking** (``cfg.chunk_size``): large shards are processed in two
    memory-bounded passes — a ``lax.map`` over sampling micro-chunks
    (streamed RNG, pre-reduced SEL recordings, per-chunk trip-trimmed
    oracle), then advantages over the full batch, then a donated-carry
    gradient-accumulation ``lax.scan`` over grad micro-chunks.  The
    sampled trajectories are bit-identical to the monolithic engine's
    (same per-episode key chain); the accumulated gradient matches to
    float summation order.

    **Sharding**: with ``n_devices > 1`` every device carries replicated
    policy/optimizer state, samples and scores its ``batch_size /
    n_devices`` episode shard, and gradients / advantage statistics are
    combined with a single fused ``pmean`` all-reduce over the flattened
    gradient vector.  ``spmd="shard_map"`` (default) lowers through
    ``jax.shard_map`` with donated buffers; ``spmd="pmap"``
    keeps the legacy per-device dispatch (bit-parity-tested against
    shard_map).  The same episode keys are drawn in either mode, so the
    sampled population is identical to the single-device path; only
    float reduction order differs.

    Every update also returns the oracle validity flags (``oracle_ok``):
    non-converged episodes have their advantage masked to zero in-update
    and the host trainer raises — garbage makespans are never trained on
    silently.

    **Trace names**: the phases run under ``jax.named_scope``s, which
    reach the device trace as HLO ``op_name`` metadata and cost nothing
    at run time: ``doppler.encoder`` (the sampling pass's GNN),
    ``doppler.sample``, ``doppler.oracle``, ``doppler.grad`` (opened
    outside ``value_and_grad``, so the loss's own encoder and the
    backward pass keep it as a prefix) and ``doppler.adamw``.  An op
    belongs to the outermost ``doppler.*`` scope on its path.
    """
    if cfg.batch_size % n_devices:
        raise ValueError(f"batch_size {cfg.batch_size} not divisible by "
                         f"{n_devices} devices")
    if spmd not in ("shard_map", "pmap"):
        raise ValueError(f"unknown spmd mode {spmd!r}")
    kb = cfg.batch_size // n_devices
    sharded = n_devices > 1
    # resolve the Pallas interpret mode once, at build time (a traced
    # value cannot pick it)
    oracle_interpret = default_interpret()

    # ---- micro-chunk resolution (None = auto, 0 = force monolithic)
    if cfg.chunk_size is None:
        sc = (_largest_divisor(kb, AUTO_CHUNK_CAP)
              if kb > AUTO_CHUNK else None)
    elif cfg.chunk_size <= 0:
        sc = None
    else:
        if kb % cfg.chunk_size:
            raise ValueError(f"chunk_size {cfg.chunk_size} does not divide "
                             f"the per-device batch {kb}")
        sc = cfg.chunk_size
    if sc is not None:
        gc = cfg.grad_chunk_size or _largest_divisor(kb, min(sc, 64))
        if kb % gc:
            raise ValueError(f"grad_chunk_size {gc} does not divide "
                             f"the per-device batch {kb}")
        nsc, ngc = kb // sc, kb // gc

    def oracle(assignments):
        with jax.named_scope("doppler.oracle"):
            if cfg.oracle_backend == "pallas":
                return _makespan_fifo_batch_pallas(sg, assignments,
                                                   oracle_interpret)
            return _makespan_fifo_batch_xla(sg, assignments)

    def advantages(rs, rstats):
        """Running-baseline advantages + post-update stats, with the
        cross-shard batch moments pmean-combined when sharded."""
        if sharded:
            batch_mean = jax.lax.pmean(rs.mean(), "batch")
            batch_sq = jax.lax.pmean((rs * rs).mean(), "batch")
            batch_std = jnp.sqrt(jnp.maximum(
                batch_sq - batch_mean * batch_mean, 0.0))
        else:
            batch_mean, batch_std = rs.mean(), rs.std()
        mean, std = rstats.baseline()
        advs = rs - jnp.where(rstats.r_count > 0, mean, batch_mean)
        if cfg.normalize_adv:
            advs = advs / (jnp.maximum(std, batch_std) + 1e-9)
        return jax.lax.stop_gradient(advs)

    def all_reduce_and_step(params, opt_state, rstats, grads, loss, rs,
                            episode):
        """AdamW step, with the sharded case folding the flattened grads
        + loss + reward sums into one fused pmean all-reduce."""
        if sharded:
            flat, unravel = ravel_pytree(grads)
            flat = jnp.concatenate([
                flat, jnp.stack([loss, rs.sum(), (rs * rs).sum()])])
            flat = jax.lax.pmean(flat, "batch")
            grads = unravel(flat[:-3])
            loss = flat[-3]
            rstats = RewardStats(
                rstats.r_sum + flat[-2] * n_devices,
                rstats.r_sqsum + flat[-1] * n_devices,
                rstats.r_count + cfg.batch_size)
        else:
            rstats = rstats.update(rs)
        params, opt_state = adamw_update(grads, opt_state, params,
                                         lr_sched(episode))
        return params, opt_state, rstats, loss

    def shard_keys(sub):
        keys = jax.random.split(sub, cfg.batch_size)
        if sharded:
            keys = jax.lax.dynamic_slice_in_dim(
                keys, jax.lax.axis_index("batch") * kb, kb)
        return keys

    def one_update_monolithic(carry, _):
        params, opt_state, rstats, key, episode = carry
        key, sub = jax.random.split(key)
        eps = eps_sched(episode)
        with jax.named_scope("doppler.sample"):
            rec = sample_episodes(params, gd, shard_keys(sub), eps,
                                  sel_mode=cfg.sel_mode,
                                  plc_mode=cfg.plc_mode,
                                  encoder_backend=cfg.encoder_backend)
        ms, ok = oracle(rec["assignment"])
        rs = jax.lax.stop_gradient(jnp.where(ok, -ms, 0.0))
        advs = jnp.where(ok, advantages(rs, rstats), 0.0)

        with jax.named_scope("doppler.grad"):
            loss, grads = jax.value_and_grad(fused_pg_loss)(
                params, gd, rec, advs, jnp.float32(cfg.entropy_weight),
                sel_learned=cfg.sel_learned, plc_learned=cfg.plc_learned,
                encoder_backend=cfg.encoder_backend)
        with jax.named_scope("doppler.adamw"):
            params, opt_state, rstats, loss = all_reduce_and_step(
                params, opt_state, rstats, grads, loss, rs, episode)
        episode = episode + cfg.batch_size
        # ship only this shard's best (valid) assignment back to the host
        best_k = jnp.argmin(jnp.where(ok, ms, jnp.inf))
        return ((params, opt_state, rstats, key, episode),
                (ms, ok, rec["assignment"][best_k], loss))

    def one_update_chunked(carry, _):
        params, opt_state, rstats, key, episode = carry
        key, sub = jax.random.split(key)
        eps = eps_sched(episode)
        keys = shard_keys(sub)
        with jax.named_scope("doppler.encoder"):
            enc = episode_encodings(
                params, gd.x, gd.edges, gd.edge_feat, gd.b_path, gd.t_path,
                backend=cfg.encoder_backend)

        # ---- pass 1: sample + score, O(chunk) working set per chunk
        def score_chunk(ck):
            with jax.named_scope("doppler.sample"):
                rec = _sample_scan(params, gd, ck, eps, cfg.sel_mode,
                                   cfg.plc_mode, enc, record="reduced")
            ms, ok = oracle(rec["assignment"])
            return {**rec, "ms": ms, "ok": ok}

        recs = jax.lax.map(score_chunk, keys.reshape(nsc, sc, 2))
        ms = recs.pop("ms").reshape(kb)
        ok = recs.pop("ok").reshape(kb)
        rs = jax.lax.stop_gradient(jnp.where(ok, -ms, 0.0))
        advs = jnp.where(ok, advantages(rs, rstats), 0.0)

        # ---- pass 2: donated-carry gradient accumulation over chunks
        recs = {k: v.reshape((ngc, gc) + v.shape[2:])
                for k, v in recs.items()}

        def grad_chunk(carry, xs):
            gsum, lsum = carry
            rec_c, adv_c = xs
            loss_c, grads_c = jax.value_and_grad(fused_pg_loss_reduced)(
                params, gd, rec_c, adv_c, jnp.float32(cfg.entropy_weight),
                sel_learned=cfg.sel_learned, plc_learned=cfg.plc_learned,
                encoder_backend=cfg.encoder_backend)
            return (jax.tree_util.tree_map(jnp.add, gsum, grads_c),
                    lsum + loss_c), None

        gz = jax.tree_util.tree_map(jnp.zeros_like, params)
        with jax.named_scope("doppler.grad"):
            (gsum, lsum), _ = jax.lax.scan(
                grad_chunk, (gz, jnp.float32(0.0)),
                (recs, advs.reshape(ngc, gc)))
        # equal chunk sizes: mean of chunk means == batch mean
        grads = jax.tree_util.tree_map(lambda g: g / ngc, gsum)
        loss = lsum / ngc

        with jax.named_scope("doppler.adamw"):
            params, opt_state, rstats, loss = all_reduce_and_step(
                params, opt_state, rstats, grads, loss, rs, episode)
        episode = episode + cfg.batch_size
        assignment = recs["assignment"].reshape(kb, gd.n)
        best_k = jnp.argmin(jnp.where(ok, ms, jnp.inf))
        return ((params, opt_state, rstats, key, episode),
                (ms, ok, assignment[best_k], loss))

    one_update = one_update_monolithic if sc is None else one_update_chunked

    def chunk(params, opt_state: AdamState, rstats: RewardStats,
              key, episode, _dev_dummy=None):
        carry = (params, opt_state, rstats, key, episode)
        carry, (ms, ok, best_a, losses) = jax.lax.scan(
            one_update, carry, None, length=cfg.updates)
        params, opt_state, rstats, key, episode = carry
        return {"params": params, "opt_state": opt_state, "rstats": rstats,
                "key": key, "episode": episode, "makespans": ms,
                "oracle_ok": ok, "best_assignments": best_a,
                "losses": losses}

    # params, optimizer state and reward stats are donated: callers must
    # hold no other reference to them (DopplerTrainer.stage2_fused swaps
    # in the returned state)
    donate = (0, 1, 2)

    if not sharded:
        return jax.jit(lambda p, o, r, k, e: chunk(p, o, r, k, e),
                       donate_argnums=donate)

    if spmd == "pmap":
        inner = jax.pmap(chunk, axis_name="batch",
                         in_axes=(None, None, None, None, None, 0),
                         devices=jax.local_devices()[:n_devices])
        dev_dummy = jnp.arange(n_devices)

        def sharded_chunk(params, opt_state, rstats, key, episode):
            out = inner(params, opt_state, rstats, key, episode, dev_dummy)
            # replicated leaves -> first copy; per-device episode shards
            # -> episode-major makespans + the globally best shard row
            first = jax.tree_util.tree_map(lambda x: x[0], out)
            ms = out["makespans"]                       # (ndev, U, kb)
            first["makespans"] = jnp.concatenate(
                [ms[d] for d in range(n_devices)], axis=1)
            first["oracle_ok"] = jnp.concatenate(
                [out["oracle_ok"][d] for d in range(n_devices)], axis=1)
            windev = jnp.argmin(
                jnp.where(out["oracle_ok"], ms, jnp.inf).min(axis=2),
                axis=0)                                 # (U,)
            first["best_assignments"] = jnp.take_along_axis(
                out["best_assignments"], windev[None, :, None], axis=0)[0]
            first["losses"] = out["losses"][0]
            return first

        return sharded_chunk

    # ---- shard_map: replicated state in/out, episode-sharded outputs
    from jax.sharding import Mesh, PartitionSpec

    P = PartitionSpec
    mesh = Mesh(np.array(jax.local_devices()[:n_devices]), ("batch",))
    out_specs = {"params": P(), "opt_state": P(), "rstats": P(),
                 "key": P(), "episode": P(), "losses": P(),
                 "makespans": P(None, "batch"),      # (U, K) episode-major
                 "oracle_ok": P(None, "batch"),
                 "best_assignments": P("batch")}     # (ndev*U, n)
    inner = jax.jit(jax.shard_map(
        lambda p, o, r, k, e: chunk(p, o, r, k, e), mesh=mesh,
        in_specs=(P(), P(), P(), P(), P()), out_specs=out_specs,
        check_vma=False), donate_argnums=donate)

    def sharded_chunk(params, opt_state, rstats, key, episode):
        out = inner(params, opt_state, rstats, key, episode)
        ms = out["makespans"]                           # (U, K)
        ok = out["oracle_ok"]
        U = ms.shape[0]
        # per-shard best rows stacked shard-major -> pick the global best
        best = out["best_assignments"].reshape(n_devices, U, gd.n)
        shard_best = jnp.where(ok, ms, jnp.inf).reshape(
            U, n_devices, kb).min(axis=2)               # (U, ndev)
        windev = jnp.argmin(shard_best, axis=1)
        out["best_assignments"] = jnp.take_along_axis(
            best, windev[None, :, None], axis=0)[0]
        return out

    return sharded_chunk


# ----------------------------------------------------- fused imitation
def build_fused_stage1(gd: GraphData, lr_sched, batch_size: int,
                       updates: int, encoder_backend: str = "xla"):
    """Compile a Stage-I chunk: `updates` imitation steps per dispatch,
    each averaging the NLL of `batch_size` pre-computed teacher episodes.

    The teacher's dynamics (candidate masks, device features) are
    parameter-free, so they are derived once per episode by a light
    replay scan outside the update loop; every update is then a parallel
    ``fused_pg_loss``-style NLL over its slice of teacher actions.
    """

    @jax.jit
    def replay_dynamics(actions):
        """(E, n, 2) teacher actions -> masks (E, n, n), x_dev."""
        n, nd = gd.n, gd.nd

        def one(acts):
            placed = jnp.zeros(n, dtype=bool)
            assigned = jnp.zeros(n, dtype=jnp.int32)
            est_end = jnp.zeros(n, dtype=jnp.float32)
            device_avail = jnp.zeros(nd, dtype=jnp.float32)
            dev_comp = jnp.zeros(nd, dtype=jnp.float32)
            n_preds = (gd.preds >= 0).sum(1).astype(jnp.int32)
            unassigned_preds = jnp.concatenate(
                [n_preds, jnp.zeros(1, jnp.int32)])
            dev_hsum = jnp.zeros((nd, 1), dtype=jnp.float32)
            dev_cnt = jnp.zeros(nd, dtype=jnp.float32)

            def step(state, act):
                v, dv = act[0], act[1]
                (placed, assigned, est_end, device_avail, dev_comp,
                 unassigned_preds, dev_hsum, dev_cnt) = state
                cand = (~placed) & (unassigned_preds[:n] == 0)
                x_dev, ready = _device_features(
                    gd, v, placed, assigned, est_end, device_avail,
                    dev_comp)
                state = _etf_update(gd, v, dv, ready[dv], state)
                return state, (cand, x_dev)

            init = (placed, assigned, est_end, device_avail, dev_comp,
                    unassigned_preds, dev_hsum, dev_cnt)
            _, (masks, x_devs) = jax.lax.scan(step, init, acts)
            return masks, x_devs

        return jax.vmap(one)(actions)

    def imitation_loss(params, masks, x_devs, actions):
        """-(mean sel logp + mean plc logp) per episode, averaged over the
        batch — the step-parallel twin of ``_imitation_loss_and_grad``."""
        sel_logp, _, plc_logp, _ = _parallel_step_logps(
            params, gd, masks, x_devs, actions,
            encoder_backend=encoder_backend)
        return -(sel_logp.mean() + plc_logp.mean())

    @jax.jit
    def train_chunk(params, opt_state, key, episode, masks, x_devs,
                    actions):
        """masks/x_devs/actions: (updates, batch_size, ...) slices."""

        def one_update(carry, xs):
            params, opt_state, key, episode = carry
            mk, xd, act = xs
            loss, grads = jax.value_and_grad(imitation_loss)(
                params, mk, xd, act)
            params, opt_state = adamw_update(grads, opt_state, params,
                                             lr_sched(episode))
            # the loop path consumes one trainer key per teacher episode
            key = jax.lax.fori_loop(
                0, batch_size,
                lambda _, k: jax.random.split(k)[0], key)
            episode = episode + batch_size
            return (params, opt_state, key, episode), loss

        carry = (params, opt_state, key, episode)
        carry, losses = jax.lax.scan(one_update, carry,
                                     (masks, x_devs, actions),
                                     length=updates)
        params, opt_state, key, episode = carry
        return {"params": params, "opt_state": opt_state, "key": key,
                "episode": episode, "losses": losses}

    return replay_dynamics, train_chunk
