"""Jit'd public wrapper for the flash-attention kernel (GQA-aware)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import default_interpret
from .kernel import flash_attention_bh


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """q: (B, S, Hq, d); k, v: (B, S, Hkv, d) with Hq % Hkv == 0.
    Returns (B, S, Hq, d).  Off a TPU the kernel body runs in interpret
    mode (same code path, Python evaluation)."""
    if interpret is None:
        interpret = default_interpret()
    B, S, Hq, d = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    # GQA: expand kv heads to match q heads, fold heads into batch
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, d)
    kb = kr.transpose(0, 2, 1, 3).reshape(B * Hq, S, d)
    vb = vr.transpose(0, 2, 1, 3).reshape(B * Hq, S, d)
    ob = flash_attention_bh(qb, kb, vb, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=interpret)
    return ob.reshape(B, Hq, S, d).transpose(0, 2, 1, 3)
