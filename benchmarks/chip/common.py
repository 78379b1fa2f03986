"""Helpers that every driver shares: parameters from the seed, the
compile counter, the traced window and the memory reading."""
from __future__ import annotations

import contextlib
import shutil
import tempfile
import time

import jax
import numpy as np

WINDOW_SPAN = "bench.window"


def seed_key(seed: int, stream: int):
    """The PRNG key of one of a run's streams (weights, trainer, plans)
    from its ``--seed``."""
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) % 2**32), stream)


def policy_sizes(p: dict) -> dict:
    """Layer sizes of the dual policy (paper Eq. 2-8) from the
    configuration's ``policy`` block."""
    dh, dz, dy = p["d_hidden"], p["d_z"], p["d_y"]
    fs, fd, fe = p["static_features"], p["device_features"], \
        p["edge_features"]
    return {
        "gnn": {"embed": [fs, dh],
                "layers": [{"psi_fwd": [2 * dh + fe, dh, dh],
                            "psi_bwd": [2 * dh + fe, dh, dh],
                            "phi": [3 * dh, dh, dh]}
                           for _ in range(p["gnn_layers"])]},
        "sel_z": [fs, dz], "sel_head": [3 * dh + dz, dh, 1],
        "plc_z": [fs, dz], "plc_y": [fd, dy],
        "plc_head1": [2 * dh + dy + dz, dh], "plc_head2": [dh, 1]}


def make_params(key, sizes: dict):
    """Policy weights from one key, made on the device in one jitted
    call: w ~ N(0, 1/d_in), b ~ N(0, 0.1^2), float32."""
    def mlp_shapes(dims):
        return [(a, b) for a, b in zip(dims[:-1], dims[1:])]

    flat = []

    def walk(s, path):
        if isinstance(s, list) and s and isinstance(s[0], int):
            flat.append((path, mlp_shapes(s)))
        elif isinstance(s, list):
            for i, x in enumerate(s):
                walk(x, path + (i,))
        else:
            for k in sorted(s):
                walk(s[k], path + (k,))

    walk(sizes, ())

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(flat))
        out = {}
        for (path, shapes), k in zip(flat, keys):
            ks = jax.random.split(k, 2 * len(shapes))
            layers = [{"w": jax.random.normal(ks[2 * i], (a, b))
                       / np.sqrt(a),
                       "b": 0.1 * jax.random.normal(ks[2 * i + 1], (b,))}
                      for i, (a, b) in enumerate(shapes)]
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = {"layers": layers}
        gl = out["gnn"]["layers"]
        out["gnn"]["layers"] = [gl[i] for i in sorted(gl)]
        return out

    return init(key)


def tree_to_host(tree):
    return jax.tree_util.tree_map(lambda x: np.array(x), tree)


class CompileCounter:
    """Counts XLA compilations (``backend_compile``) while it is on."""

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``, as the backend
    reports it (0 where it reports nothing)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@contextlib.contextmanager
def traced(enabled: bool):
    """Profile the block into a temporary directory when ``enabled``;
    yields a dict that holds ``dir`` and the host-clock ``window_s``.
    The directory is removed by :func:`drop_trace`."""
    info = {"dir": None, "window_s": None}
    if not enabled:
        yield info
        return
    info["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
    with jax.profiler.trace(info["dir"]):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield info
        info["window_s"] = time.perf_counter() - t0


def drop_trace(info: dict) -> None:
    if info.get("dir"):
        shutil.rmtree(info["dir"], ignore_errors=True)


def rel_gap_by_leaf(prog, ref, skip=None) -> tuple[float, str]:
    """Worst leaf of | |prog_leaf| - |ref_leaf| |, each over the larger of
    that leaf's reference norm and the median leaf's.  ``skip``: leaf
    paths left out.  Returns (gap, leaf path)."""
    pl = jax.tree_util.tree_flatten_with_path(prog)[0]
    rl = jax.tree_util.tree_leaves(ref)
    names = [jax.tree_util.keystr(p) for p, _ in pl]
    pn = np.array([np.linalg.norm(np.asarray(x, np.float64)) for _, x in pl])
    rn = np.array([np.linalg.norm(np.asarray(x, np.float64)) for x in rl])
    keep = np.array([skip is None or n not in skip for n in names])
    med = float(np.median(rn[keep]))
    gaps = np.abs(pn - rn) / np.maximum(rn, med)
    gaps[~keep] = -1.0
    i = int(np.argmax(gaps))
    return float(gaps[i]), names[i]


def null_leaves(grad, rel: float = 1e-3) -> set[str]:
    """Leaves whose reference gradient is nought to rounding: norm under
    ``rel`` of the median leaf's (e.g. a bias under a softmax)."""
    pl = jax.tree_util.tree_flatten_with_path(grad)[0]
    norms = np.array([np.linalg.norm(np.asarray(x, np.float64))
                      for _, x in pl])
    med = float(np.median(norms))
    return {jax.tree_util.keystr(p) for (p, _), nrm in zip(pl, norms)
            if nrm < rel * med}
