"""Blocked GNN message-aggregation Pallas TPU kernel.

DOPPLER's per-episode hot loop is the GNN message pass (paper §4.3):
agg[v] = sum_{(u,v) in E} msg_{uv}.  A random-scatter is hostile to the
TPU's vector memory, so we restructure it MXU-style (DESIGN.md §3):

  preprocessing (ops.py, bandwidth-bound, XLA):
    sort edges by destination; split into fixed-size edge tiles (Eb);
    for each tile, build the (Nb x Eb) one-hot assignment A_t mapping the
    tile's edges to the node block their destinations fall in.
  kernel (compute-bound, MXU):
    agg_block += A_t @ msg_tile     -- a (Nb x Eb) x (Eb x d) matmul.

Grid: (node_blocks, edge_tiles) with the edge axis sequential, the
(Nb, d) accumulator living in VMEM scratch.  Because edges are sorted by
destination, each edge tile touches at most two node blocks and the
assignment matrix is near-diagonal — the tiles that contribute nothing to
the current node block multiply by an all-zero A_t (cheap on MXU, skipped
entirely on TPU via the near-diagonal tile schedule in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_kernel(assign_ref, msg_ref, out_ref, acc_scr, *, n_edge_tiles):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    a = assign_ref[0, 0].astype(jnp.float32)       # (Nb, Eb)
    m = msg_ref[0].astype(jnp.float32)             # (Eb, d)
    # HIGHEST: Mosaic's default f32 matmul rounds the messages to bf16 on
    # the MXU (~1e-3 relative), which breaks parity with segment_sum
    acc_scr[...] += jax.lax.dot(a, m, precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)

    @pl.when(t == n_edge_tiles - 1)
    def _done():
        out_ref[0, ...] = acc_scr[...].astype(out_ref.dtype)


def segment_aggregate_blocked(assign, msg, *, interpret: bool = False):
    """assign: (n_blocks, n_tiles, Nb, Eb) one-hot; msg: (n_tiles, Eb, d).
    Returns (n_blocks, Nb, d) = per-block sum_t assign[b,t] @ msg[t]."""
    nb, nt, Nb, Eb = assign.shape
    d = msg.shape[-1]
    kernel = functools.partial(_agg_kernel, n_edge_tiles=nt)
    return pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((1, 1, Nb, Eb), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, Eb, d), lambda b, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Nb, d), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, Nb, d), msg.dtype),
        scratch_shapes=[pltpu.VMEM((Nb, d), jnp.float32)],
        interpret=interpret,
    )(assign.reshape(nb, nt, Nb, Eb), msg)
