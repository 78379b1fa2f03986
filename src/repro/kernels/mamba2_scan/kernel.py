"""Mamba2/SSD chunked gated-linear-attention Pallas TPU kernel.

One (batch*head) stream per grid row; chunks are the sequential grid axis
with the (P, N) matrix state carried in VMEM scratch — the TPU analogue of
the SSD "chunkwise parallel + recurrent state" algorithm:

  intra-chunk: decay-masked (q k^T) (L x L) einsum + (L,L)@(L,P) on MXU
  inter-chunk: q @ state with the cumulative-decay prefix
  state:       tot * state + (decay-to-end * v)^T k

Tiling: chunk L=128 x state N<=128 x head dim P<=128 blocks; working set
(q,k: L*N + v,y: L*P + state: P*N + (L,L) scores) * fp32 ~= 0.3 MB, well
inside VMEM.  log-decay is passed pre-summed (cumulative within chunk) to
keep the kernel free of 1D-scan idioms the VPU dislikes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import default_interpret


def _gla_kernel(q_ref, k_ref, v_ref, cum_ref, y_ref, state_scr, *,
                chunk: int, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    q = q_ref[0].astype(jnp.float32)          # (L, N)
    k = k_ref[0].astype(jnp.float32)          # (L, N)
    v = v_ref[0].astype(jnp.float32)          # (L, P)
    cum = cum_ref[0].astype(jnp.float32)      # (L, 1) within-chunk cumsum

    # intra-chunk: M[t,s] = exp(cum[t]-cum[s]) for s<=t
    diff = cum - cum.T                        # (L, L)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(tri, jnp.exp(diff), 0.0)
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y = jax.lax.dot(qk * m, v, preferred_element_type=jnp.float32)

    # inter-chunk: q @ state^T scaled by decay prefix exp(cum)
    state = state_scr[...]                    # (P, N)
    y += jax.lax.dot_general(q, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cum)
    y_ref[0, ...] = y.astype(y_ref.dtype)

    # state update: tot * state + sum_s exp(cum[-1]-cum[s]) v_s k_s^T
    tot = jnp.exp(cum[chunk - 1:chunk, :])    # (1, 1)
    w = jnp.exp(cum[chunk - 1:chunk, :] - cum)  # (L, 1) decay to chunk end
    vk = jax.lax.dot_general(v * w, k, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state * tot + vk


def mamba2_chunk_scan(q, k, v, log_a, *, chunk: int = 128,
                      interpret: bool | None = None):
    """q, k: (BH, S, N); v: (BH, S, P); log_a: (BH, S) (log decay <= 0).
    Returns y: (BH, S, P).  Within-chunk cumulative log-decay is computed
    outside (cheap, bandwidth-bound) so the kernel is pure MXU work.
    ``interpret=None`` resolves through
    :func:`repro.kernels.default_interpret`."""
    if interpret is None:
        interpret = default_interpret()
    bh, s, n = q.shape
    p = v.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    # within-chunk inclusive cumsum of log decay, gate applied for r in
    # (s, t] -- matches repro.models.ssm._chunk_gla
    cum = jnp.cumsum(log_a.reshape(bh, nc, chunk), axis=-1)
    cum = cum.reshape(bh, s, 1)
    kernel = functools.partial(_gla_kernel, chunk=chunk, n_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), v.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(q, k, v, cum)
