"""Jit'd public wrapper for the Mamba2/SSD chunk-scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from .. import default_interpret
from .kernel import mamba2_chunk_scan


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(q, k, v, log_a, *, chunk: int = 128,
             interpret: bool | None = None):
    """Gated-linear-attention scan.  q, k: (B, S, H, N); v: (B, S, H, P);
    log_a: (B, S, H).  Returns (B, S, H, P)."""
    if interpret is None:
        interpret = default_interpret()
    B, S, H, N = q.shape
    P = v.shape[-1]

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, x.shape[-1])

    y = mamba2_chunk_scan(fold(q), fold(k), fold(v),
                          log_a.transpose(0, 2, 1).reshape(B * H, S),
                          chunk=chunk, interpret=interpret)
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
