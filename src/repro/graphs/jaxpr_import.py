"""jaxpr -> DataflowGraph importer.

Bridges the pjit model zoo and the DOPPLER assignment stack (DESIGN.md §3):
trace any JAX function (e.g. one transformer layer's forward from
repro/models) and obtain a DataflowGraph whose vertices carry FLOP/byte
costs estimated from the equation primitives.  The resulting graph is what
DOPPLER assigns at the block level; per Appendix I, the per-block
assignment is replicated across the repeated structure of the full model.

Cost model (per primitive):
  dot_general / conv:  2 * prod(contract dims) * prod(batch/free dims)
  reductions, split:   input size
  elementwise & rest:  output size
Bytes: output nbytes (dtype-aware).
"""
from __future__ import annotations

import jax
import numpy as np

from ..core.graph import DataflowGraph

_ELEMWISE_HINT = ("add", "sub", "mul", "div", "exp", "log", "tanh", "logistic",
                  "max", "min", "pow", "rsqrt", "sqrt", "neg", "erf",
                  "integer_pow", "select_n", "convert_element_type",
                  "custom_jvp_call", "stop_gradient", "square")

_KIND_MAP = {
    "dot_general": "matmul",
    "conv_general_dilated": "matmul",
    "reduce_sum": "sum_reduction",
    "reduce_max": "max_reduction",
    "reduce_min": "min_reduction",
    "reduce_prod": "product_reduction",
    "argmax": "max_reduction",
    "reshape": "squeezer",
    "squeeze": "squeezer",
    "broadcast_in_dim": "squeezer",
    "transpose": "squeezer",
    "concatenate": "select",
    "slice": "select",
    "split": "select",
    "dynamic_slice": "select",
    "gather": "select",
    "scatter": "select",
    "scatter_add": "select",
    "iota": "fill",
    "cumsum": "sum_reduction",
    "cumlogsumexp": "sum_reduction",
}


def _out_size_bytes(aval) -> float:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0.0
    elems = float(np.prod(shape, dtype=np.float64)) if len(shape) else 1.0
    dtype = getattr(aval, "dtype", None)
    try:
        itemsize = np.dtype(dtype).itemsize
    except Exception:
        # non-numpy dtypes (prng keys, float0, ...): trust the dtype's own
        # itemsize when it has one, else assume 4 bytes — never 0, which
        # would make every downstream transfer of this value free.
        itemsize = getattr(dtype, "itemsize", None) or 4
    return elems * float(itemsize)


def _flops_of(eqn) -> float:
    prim = eqn.primitive.name
    out_aval = eqn.outvars[0].aval
    out_elems = float(np.prod(out_aval.shape, dtype=np.float64)) \
        if out_aval.shape else 1.0
    if prim == "dot_general":
        dims = eqn.params["dimension_numbers"]
        (lc, rc), _ = dims
        lhs = eqn.invars[0].aval
        contract = float(np.prod([lhs.shape[i] for i in lc],
                                 dtype=np.float64)) if lc else 1.0
        return 2.0 * out_elems * contract
    # split: all its pieces, i.e. its input — jax 0.9 traces jnp.split as
    # one multi-output `split` where jax 0.4.37 emitted a `slice` per piece
    if prim.startswith("reduce") or prim.startswith("cum") or prim == "split":
        in_aval = eqn.invars[0].aval
        return float(np.prod(in_aval.shape, dtype=np.float64)) \
            if in_aval.shape else 1.0
    return out_elems


def _kind_of(eqn) -> str:
    prim = eqn.primitive.name
    if prim in _KIND_MAP:
        return _KIND_MAP[prim]
    if any(h in prim for h in _ELEMWISE_HINT):
        return "straight_elemwise"
    return "input_elemwise"


def jaxpr_to_graph(fn, *example_args, name: str = "jaxpr",
                   fuse_cheap: bool = True,
                   cheap_flops: float = 1e4,
                   arg_labels=None) -> DataflowGraph:
    """Trace `fn` on example args (arrays or ShapeDtypeStructs) and import
    the closed jaxpr as a DataflowGraph.

    fuse_cheap: absorb near-zero-cost vertices (reshapes, tiny scalars) into
    their consumer — keeps the assignment problem at kernel granularity,
    matching the paper's graphs (which are kernel calls, not HLO
    minutiae).  Vertex labels are stable: primitives that carry a
    ``name=`` param (pjit, custom calls) keep it, and fusion preserves the
    surviving root's label (see :func:`_fuse_cheap`).

    arg_labels: optional input-vertex labels, one per *flattened* invar
    (e.g. pytree key paths); falls back to ``arg{i}``."""
    closed = jax.make_jaxpr(fn)(*example_args)
    jaxpr = closed.jaxpr
    g = DataflowGraph(name)
    producer: dict = {}

    def ensure_const_input(var, lbl):
        if var not in producer:
            producer[var] = g.add_vertex(
                "input", out_bytes=_out_size_bytes(var.aval), label=lbl,
                out_shape=tuple(var.aval.shape))
        return producer[var]

    for i, var in enumerate(jaxpr.invars):
        lbl = (arg_labels[i] if arg_labels is not None
               and i < len(arg_labels) else f"arg{i}")
        producer[var] = g.add_vertex(
            "input", out_bytes=_out_size_bytes(var.aval), label=lbl,
            out_shape=tuple(var.aval.shape))
    for i, var in enumerate(jaxpr.constvars):
        producer[var] = g.add_vertex(
            "input", out_bytes=_out_size_bytes(var.aval), label=f"const{i}",
            out_shape=tuple(var.aval.shape))

    meta = 0
    for eqn in jaxpr.eqns:
        kind = _kind_of(eqn)
        flops = _flops_of(eqn)
        out_bytes = sum(_out_size_bytes(ov.aval) for ov in eqn.outvars)
        # stable op name: prefer the primitive's own name= param (pjit,
        # custom_jvp_call, ...) over the generic primitive name
        custom = eqn.params.get("name") if isinstance(
            eqn.params.get("name"), str) else None
        v = g.add_vertex(kind, flops=flops, out_bytes=out_bytes,
                         meta_op=meta, role="shard",
                         label=custom or eqn.primitive.name,
                         out_shape=tuple(eqn.outvars[0].aval.shape))
        meta += 1
        for iv in eqn.invars:
            if hasattr(iv, "val"):          # literal
                continue
            src = producer.get(iv)
            if src is None:
                src = ensure_const_input(iv, "captured")
            g.add_edge(src, v)
        for ov in eqn.outvars:
            producer[ov] = v

    g.outputs = [producer[ov] for ov in jaxpr.outvars if ov in producer]
    g.freeze()
    if fuse_cheap:
        g = _fuse_cheap(g, cheap_flops)
    return g


def _fuse_cheap(g: DataflowGraph, cheap_flops: float) -> DataflowGraph:
    """Collapse vertices with negligible cost and exactly one consumer into
    that consumer (kernel-granularity view).

    The surviving root keeps its own (stable) label — or, for graphs from
    other sources whose roots may be unlabeled, inherits the label of the
    topo-first absorbed vertex that has one — and absorbs the fused
    vertices' flops so the graph's total compute is conserved.

    Fully vectorized (pointer-jumping root resolution + np.add.at flop
    accumulation in topo order) so fusing a 100k-vertex tiled graph is
    milliseconds, with outputs bit-identical to the per-vertex loops it
    replaced."""
    n = g.n
    flops = g.flops_array()
    out_deg = np.array([len(g.succs[v]) for v in range(n)])
    absorbed = (~g.input_mask()) & (flops <= cheap_flops) & (out_deg == 1)
    nxt = np.arange(n, dtype=np.int64)
    av = np.flatnonzero(absorbed)
    nxt[av] = np.array([g.succs[v][0] for v in av.tolist()],
                       dtype=np.int64) if len(av) else av
    root_of = nxt.copy()                 # pointer jumping to the fixpoint
    while True:
        hop = root_of[root_of]
        if (hop == root_of).all():
            break
        root_of = hop

    # flop accumulation + label inheritance in topo order (np.add.at adds
    # in element order, matching the sequential loop bit-for-bit; earliest
    # absorbed label per root wins)
    topo = np.asarray(g.topo_order, dtype=np.int64)
    sel = topo[absorbed[topo]]
    extra = np.zeros(n)
    np.add.at(extra, root_of[sel], flops[sel])
    lab_sel = sel[[bool(g.vertices[v].label) for v in sel.tolist()]]
    rr = root_of[lab_sel]
    uniq_r, first = np.unique(rr, return_index=True)
    inherited_label = {int(r): g.vertices[int(lab_sel[i])].label
                       for r, i in zip(uniq_r, first)}

    keep = np.flatnonzero(~absorbed)
    remap = np.full(n, -1, dtype=np.int64)
    remap[keep] = np.arange(len(keep))
    kl = keep.tolist()
    E = g.edge_array().astype(np.int64)
    if len(E):
        rs, rd = root_of[E[:, 0]], root_of[E[:, 1]]
        m = rs != rd
        K = len(keep)
        keys = np.unique(remap[rs[m]] * K + remap[rd[m]])   # sorted+dedup
        new_edges = np.stack([keys // K, keys % K], axis=1)
    else:
        new_edges = np.zeros((0, 2), dtype=np.int64)
    return DataflowGraph.from_arrays(
        g.name,
        [g.vertices[v].kind for v in kl],
        flops[keep] + extra[keep],
        g.out_bytes_array()[keep],
        meta_op=[g.vertices[v].meta_op for v in kl],
        roles=[g.vertices[v].role for v in kl],
        labels=[g.vertices[v].label or inherited_label.get(v, "")
                for v in kl],
        out_shapes=[g.vertices[v].out_shape for v in kl],
        edges=new_edges,
        # an absorbed output's value is produced (cost-model-wise) by
        # its root
        outputs=[int(remap[root_of[v]]) for v in g.outputs])
