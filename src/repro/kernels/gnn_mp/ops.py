"""Public wrapper: segment-sum over edge messages via the blocked kernel.

`segment_sum_mp(msg, dst, n)` == jax.ops.segment_sum(msg, dst, n) but
restructured for the MXU (see kernel.py).  The one-hot assignment build is
pure XLA (sort + compare), done once per episode alongside the GNN pass.

Two properties the policy stack relies on (tests/test_kernels.py):

* differentiable — ``pallas_call`` has no autodiff rule, so the wrapper
  carries a ``custom_vjp`` whose backward pass is the same cotangent
  gather ``g[dst]`` that ``segment_sum``'s VJP lowers to: gradients match
  the XLA encoder bit-for-bit whenever the forward does.
* total on degenerate shapes — an empty edge set (m == 0, the no-edge
  graphs the trainer's featurization can produce) short-circuits to
  zeros instead of tracing a zero-size kernel grid.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import default_interpret
from .kernel import segment_aggregate_blocked


def _pad_to(x, size, axis=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _segment_sum_impl(msg, dst, n: int, node_block: int, edge_tile: int,
                      interpret: bool):
    m, d = msg.shape
    order = jnp.argsort(dst)
    msg_s = msg[order]
    dst_s = dst[order]

    n_pad = ((n + node_block - 1) // node_block) * node_block
    m_pad = ((m + edge_tile - 1) // edge_tile) * edge_tile
    msg_s = _pad_to(msg_s, m_pad)
    dst_s = _pad_to(dst_s, m_pad).at[m:].set(n_pad)     # park pads off-range

    nb = n_pad // node_block
    nt = m_pad // edge_tile
    # one-hot assignment per (node block, edge tile):
    # A[b, t, i, e] = 1 iff dst of edge (t, e) == node (b, i)
    dst_tiles = dst_s.reshape(nt, edge_tile)            # (nt, Eb)
    node_ids = (jnp.arange(n_pad).reshape(nb, node_block))
    assign = (dst_tiles[None, :, None, :] ==
              node_ids[:, None, :, None]).astype(msg.dtype)
    out = segment_aggregate_blocked(assign, msg_s.reshape(nt, edge_tile, d),
                                    interpret=interpret)
    return out.reshape(n_pad, d)[:n]


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _segment_sum_vjp(msg, dst, n, node_block, edge_tile, interpret):
    return _segment_sum_impl(msg, dst, n, node_block, edge_tile, interpret)


def _segment_sum_fwd(msg, dst, n, node_block, edge_tile, interpret):
    out = _segment_sum_impl(msg, dst, n, node_block, edge_tile, interpret)
    return out, dst


def _segment_sum_bwd(n, node_block, edge_tile, interpret, dst, g):
    # d/dmsg of sum-by-destination is the cotangent gather — identical to
    # segment_sum's own VJP; int dst gets the mandatory float0 zero
    return (g[dst], np.zeros(dst.shape, dtype=jax.dtypes.float0))


_segment_sum_vjp.defvjp(_segment_sum_fwd, _segment_sum_bwd)


def segment_sum_mp(msg, dst, *, n: int, node_block: int = 128,
                   edge_tile: int = 128, interpret: bool | None = None):
    """msg: (m, d) edge messages; dst: (m,) destination node ids.
    Returns (n, d) with out[v] = sum over edges with dst==v.
    ``interpret=None`` resolves through :func:`default_interpret`."""
    if interpret is None:
        interpret = default_interpret()
    return _segment_sum_mp(msg, dst, n=n, node_block=node_block,
                           edge_tile=edge_tile, interpret=interpret)


@partial(jax.jit, static_argnames=("n", "node_block", "edge_tile",
                                   "interpret"))
def _segment_sum_mp(msg, dst, *, n: int, node_block: int, edge_tile: int,
                    interpret: bool):
    if msg.shape[0] == 0:
        return jnp.zeros((n, msg.shape[1]), msg.dtype)
    return _segment_sum_vjp(msg, dst, n, node_block, edge_tile, interpret)
