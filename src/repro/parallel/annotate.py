"""Activation sharding annotations.

GSPMD propagates input shardings, but without explicit constraints it is
free to (and on these models does) replicate the batch dimension through
attention — every chip then computes the full global batch.  `constrain`
applies `with_sharding_constraint` against the ambient mesh
(jax.set_mesh), silently degrading to a no-op outside a mesh context
(smoke tests) and dropping axes that don't exist or don't divide the dim
(long_500k's batch of 1, MQA's single KV head, ...).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import PartitionSpec as P, get_abstract_mesh

BATCH = ("pod", "data")          # filtered against the ambient mesh
MODEL = "model"


def _axes_tuple(entry):
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def constrain(x, *spec):
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    sizes = dict(mesh.shape)
    clean = []
    for dim, entry in zip(x.shape, spec):
        axes = tuple(a for a in _axes_tuple(entry) if a in sizes)
        total = int(np.prod([sizes[a] for a in axes])) if axes else 1
        if axes and dim % total == 0 and dim >= total:
            clean.append(axes if len(axes) > 1 else axes[0])
        else:
            clean.append(None)
    # pad remaining dims
    clean += [None] * (x.ndim - len(clean))
    return jax.lax.with_sharding_constraint(x, P(*clean))


def constrain_batch(x):
    """(B, S, ...) residual-stream activation: batch over ('pod','data')
    and, for sequence-bearing tensors, sequence over 'model'
    (Megatron-style sequence parallelism).  Without the seq shard, the
    remat-saved per-layer residuals are replicated across the model axis
    and a 4k x 16-seq/device batch of an 80-layer model needs 86 GB/chip;
    with it, 5.4 GB (DESIGN.md §6).  Decode (S=1) and non-divisible
    lengths fall back automatically via the divisibility guard."""
    if x.ndim >= 3:
        return constrain(x, BATCH, MODEL, *([None] * (x.ndim - 2)))
    return constrain(x, BATCH, *([None] * (x.ndim - 1)))


def constrain_first(x, axis, dims):
    """Shard `axis` over the FIRST dim in `dims` that divides it; others
    None.  Used by the MoE dispatch: experts over 'model' when the expert
    count divides (EP), else capacity over 'model' (token-parallel — the
    granite-40-experts fallback)."""
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    sizes = dict(mesh.shape)
    if axis not in sizes:
        return x
    size = sizes[axis]
    spec = [None] * x.ndim
    for d in dims:
        if x.shape[d] % size == 0 and x.shape[d] >= size:
            spec[d] = axis
            break
    return jax.lax.with_sharding_constraint(x, P(*spec))
