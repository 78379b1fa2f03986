"""Flash-attention forward Pallas TPU kernel.

Tiling: grid (batch*heads, n_q_blocks, n_kv_blocks); the kv dimension is
the innermost ("arbitrary" = sequential) axis so the online-softmax
running state (m, l, acc) lives in VMEM scratch across kv steps.  Block
shapes are MXU-aligned (multiples of 128 on the lane dim by default) and
sized so q-block + kv-block + acc fit VMEM:

  q (1, Bq, d)  +  k,v (1, Bk, d)  +  acc/m/l (Bq, d + 2)  in fp32
  default Bq=Bk=128, d<=256  ->  ~0.5 MB  <<  16 MB VMEM/core.

Validated in interpret mode against ref.py (pure-jnp oracle); on TPU the
same code lowers to MXU matmuls with HBM->VMEM pipelining handled by
pallas_call's BlockSpec machinery.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import default_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_kv_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (Bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (Bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_scr[...]                                 # (Bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                              # (Bq, Bk)
    alpha = jnp.exp(m_prev - m_new)                     # (Bq, 1)
    l_new = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(kj == n_kv_blocks - 1)
    def _done():
        o_ref[0, ...] = (acc_scr[...]
                         / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bh(q, k, v, *, causal: bool = True, block_q: int = 128,
                       block_k: int = 128, interpret: bool | None = None):
    """q, k, v: (BH, S, d) with matching head counts (GQA expansion is done
    by ops.py).  Returns (BH, S, d).  ``interpret=None`` resolves through
    :func:`repro.kernels.default_interpret`."""
    if interpret is None:
        interpret = default_interpret()
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               n_kv_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),     # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
