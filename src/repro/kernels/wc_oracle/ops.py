"""Public wrapper: fused batched WC-oracle trip step.

``wc_step(run, rows, ridx)`` applies one trip's start-row writes, pops
the lexicographic-minimum completion per episode, and clears the popped
slot — semantics pinned by ref.wc_step_ref (and transitively by the XLA
single-episode path in core.sim_jax).  The wrapper owns the layout work:
transpose the (B, R, 6) table column-major, pad columns 6 -> 8, lanes
R -> multiple of 128 (padded lanes get end = +inf so they never win a
pop), batch B -> multiple of block_b, then slice everything back.

The production caller is ``core.sim_jax._run_trips``: a batch-level
``while_loop`` that invokes one ``wc_step`` per trip and exits as soon
as every episode in the batch has completed (trip trimming).  A drained
episode's step is a no-op (its pop returns e1 = +inf, so the returned
``rho`` row is dead and the caller masks on ``isfinite(e1)``), which is
what makes the early exit decision-exact.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import default_interpret
from .kernel import wc_step_blocked


def _ceil_to(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


def wc_step(run, rows, ridx, *, block_b: int = 8,
            interpret: bool | None = None):
    """run: (B, R, 6) running table; rows: (B, K, 6) start rows;
    ridx: (B, K) int32 target resource per row, -1 drops.
    Returns (run_out (B, R, 6), rho (B,) int32, e1 (B,) f32).
    ``interpret=None`` resolves through :func:`default_interpret`."""
    if interpret is None:
        interpret = default_interpret()
    return _wc_step(run, rows, ridx, block_b=block_b, interpret=interpret)


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def _wc_step(run, rows, ridx, *, block_b: int, interpret: bool):
    B, R, _ = run.shape
    K = ridx.shape[1]
    Rp = _ceil_to(R, 128)
    Kp = _ceil_to(K, 128)
    Bp = _ceil_to(B, block_b)

    run_t = jnp.pad(jnp.transpose(run, (0, 2, 1)),
                    ((0, Bp - B), (0, 2), (0, Rp - R)))
    # padded lanes and padded episodes must never win the pop
    run_t = run_t.at[:, 0, R:].set(jnp.inf)
    if Bp > B:
        run_t = run_t.at[B:, 0, :].set(jnp.inf)
    rows_t = jnp.pad(jnp.transpose(rows, (0, 2, 1)),
                     ((0, Bp - B), (0, 2), (0, Kp - K)))
    ridx_p = jnp.pad(ridx.astype(jnp.int32),
                     ((0, Bp - B), (0, Kp - K)), constant_values=-1)

    out_run, rho, e1 = wc_step_blocked(run_t, rows_t, ridx_p, R=R,
                                       block_b=block_b, interpret=interpret)
    return (jnp.transpose(out_run[:B, :6, :R], (0, 2, 1)),
            rho[:B, 0], e1[:B, 0])
