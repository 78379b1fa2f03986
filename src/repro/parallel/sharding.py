"""Sharding rules: parameter / optimizer / activation PartitionSpecs.

Baseline layout (the paper-era "replicate-and-pray" layouts don't survive
110B params on 16 GB chips, so the baseline is already 2D):

  * batch           -> ('pod', 'data') when the pod axis exists, else 'data'
  * params          -> FSDP over 'data' x tensor-parallel over 'model'
  * optimizer state -> same spec as its parameter (ZeRO)
  * MoE experts     -> expert-parallel over 'model' when divisible,
                       else hidden-dim TP fallback (granite's 40 experts)
  * KV caches       -> kv-heads over 'model' when divisible, else sequence
                       dim over 'model' (sequence-parallel decode — gemma/
                       paligemma MQA)

Every rule is divisibility-guarded: a dim that doesn't divide the mesh axis
falls back (next rule or replication) instead of relying on GSPMD padding.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def batch_axis_size(mesh: Mesh) -> int:
    return int(np.prod([axis_size(mesh, a) for a in batch_axes(mesh)]))


def _ok(dim: int, mesh: Mesh, axes) -> bool:
    if axes is None:
        return True
    if isinstance(axes, str):
        axes = (axes,)
    total = int(np.prod([axis_size(mesh, a) for a in axes]))
    return dim % total == 0


def guarded(mesh: Mesh, shape, *spec):
    """PartitionSpec with divisibility guard per dim (None on failure)."""
    out = []
    for dim, axes in zip(shape, spec):
        out.append(axes if _ok(dim, mesh, axes) else None)
    return P(*out)


# ------------------------------------------------------------- parameters
def _leaf_spec(path: str, shape, mesh: Mesh, cfg: ModelConfig,
               fsdp: bool = True) -> P:
    d = "data" if fsdp else None
    nd = len(shape)
    stacked = path.startswith("unit/") and nd >= 1
    core = shape[1:] if stacked else shape

    def wrap(spec: P) -> P:
        return P(None, *spec) if stacked else spec

    name = path.split("/")[-1]
    # ---- embeddings / head
    if name == "embed":
        return guarded(mesh, shape, "model", d)
    if name == "head":
        return guarded(mesh, shape, d, "model")
    # ---- 1D (norm scales, biases, gates)
    if len(core) == 1:
        if name in ("bq", "bk", "bv"):
            return wrap(guarded(mesh, core, "model"))
        return wrap(P(None))
    # ---- MoE
    if "/moe/" in path or path.endswith("/router"):
        if name == "router":
            return wrap(guarded(mesh, core, d, None))
        E = core[0]
        if _ok(E, mesh, "model"):
            if name == "w_down":
                return wrap(guarded(mesh, core, "model", None, d))
            return wrap(guarded(mesh, core, "model", d, None))
        # fallback when E doesn't divide 'model' (granite's 40 experts):
        if cfg.moe is not None and cfg.moe.fallback == "token_parallel":
            # token-parallel dispatch (capacity over 'model' in mlp.py) +
            # expert weights FSDP-only — per-layer weight all-gathers
            # instead of capacity-buffer collectives (§Perf optimization)
            return wrap(guarded(mesh, core, None, d, None))
        # baseline: hidden-dim tensor parallelism
        if name == "w_down":
            return wrap(guarded(mesh, core, None, "model", d))
        return wrap(guarded(mesh, core, None, d, "model"))
    # ---- attention
    if name in ("wq", "wk", "wv"):
        return wrap(guarded(mesh, core, d, "model"))
    if name == "wo":
        return wrap(guarded(mesh, core, "model", d))
    # ---- dense FFN / SSM projections
    if name in ("w_gate", "w_up", "w_in", "w_q", "w_k", "w_v", "w_if",
                "w_gates"):
        return wrap(guarded(mesh, core, d, "model"))
    if name in ("w_down", "w_out"):
        return wrap(guarded(mesh, core, "model", d))
    if name == "conv":
        return wrap(guarded(mesh, core, None, "model"))
    if name == "r_gates":           # (H, P, 4P) tiny — replicate
        return wrap(P(*([None] * len(core))))
    return wrap(P(*([None] * len(core))))


def _path_str(kp) -> str:
    parts = []
    for e in kp:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


def param_specs(params: Any, mesh: Mesh, cfg: ModelConfig,
                fsdp: bool = True):
    """Spec tree mirroring `params` (works on arrays or ShapeDtypeStructs)."""
    def spec_of(kp, leaf):
        return _leaf_spec(_path_str(kp), leaf.shape, mesh, cfg, fsdp)

    return jax.tree_util.tree_map_with_path(spec_of, params)


def opt_specs(opt_state, pspecs):
    """AdamState(step, mu, nu) -> (None, pspecs, pspecs)."""
    from ..train.optim import AdamState
    return AdamState(P(), pspecs, pspecs)


# ------------------------------------------------------------------- data
def data_specs(batch: dict, mesh: Mesh):
    ba = batch_axes(mesh)

    def spec_of(kp, leaf):
        b = leaf.shape[0]
        first = ba if _ok(b, mesh, ba) else None
        return P(first, *([None] * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map_with_path(spec_of, batch)


# ----------------------------------------------------------- decode state
def decode_state_specs(state: Any, mesh: Mesh, cfg: ModelConfig):
    """KV caches (..., B, T, Hkv, hd): kv-heads over 'model' if divisible
    else sequence over 'model'; batch over data axes if divisible.
    SSM states (..., B, H, P, N): heads over 'model' when divisible."""
    ba = batch_axes(mesh)

    def spec_of(kp, leaf):
        path = _path_str(kp)
        stacked = path.startswith("unit/")
        shape = leaf.shape[1:] if stacked else leaf.shape
        nd = len(shape)
        spec = [None] * nd
        if nd >= 1 and _ok(shape[0], mesh, ba):
            spec[0] = ba
        if nd == 4:                       # (B, T, Hkv, hd) KV cache
            if _ok(shape[2], mesh, "model") and shape[2] >= \
                    axis_size(mesh, "model"):
                spec[2] = "model"
            elif _ok(shape[1], mesh, "model"):
                spec[1] = "model"         # sequence-parallel KV
        elif nd == 3 and _ok(shape[1], mesh, "model") and shape[1] >= \
                axis_size(mesh, "model"):
            spec[1] = "model"             # (B, H, P) slstm state
        elif nd >= 3:                     # (B, H, P, N) GLA/mamba state
            if _ok(shape[1], mesh, "model") and shape[1] >= \
                    axis_size(mesh, "model"):
                spec[1] = "model"
        out = P(*spec)
        return P(None, *out) if stacked else out

    return jax.tree_util.tree_map_with_path(spec_of, state)


def shard_array(x, mesh: Mesh, spec: P):
    return jax.device_put(x, NamedSharding(mesh, spec))
