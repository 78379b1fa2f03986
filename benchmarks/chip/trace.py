"""Reduction of a JAX profiler trace to the events the metrics read.

A ``--trace 1`` run wraps a short window in ``jax.profiler.trace``; the
profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.  This
module reads it with ``jax.profiler.ProfileData`` and nothing else:

* ``device_ops``: per chip, the operations that ran on it, from the
  ``XLA Ops`` line of each ``/device:TPU:<i>`` plane, as
  ``(name, start_ns, end_ns)``;
* ``host_spans``: the benchmark's own ``TraceAnnotation`` spans on the
  host threads, by name;
* ``busy_ns`` / ``idle_gaps``: the union of a chip's operation intervals
  inside the window, and the gaps between them.

Times are the profiler's nanoseconds, on one clock for host and device.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xspace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xspace(trace_dir))


def device_ops(pd) -> dict[int, list[tuple[str, int, int]]]:
    """{chip id: [(op name, start ns, end ns), ...]} sorted by start."""
    out: dict[int, list] = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        chip = int(plane.name[len(DEVICE_PREFIX):].split()[0])
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                evs.append((ev.name, int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns)))
        out[chip] = sorted(evs, key=lambda e: e[1])
    return out


def pallas_calls(events, fn: str):
    """The events of the Pallas kernel that the jitted function ``fn``
    wraps.  A TPU op event is named by its HLO text,
    ``%<instruction> = <shape> <opcode>(...``; XLA names the custom call
    of a ``pallas_call`` after the jit around it (``%_wc_step.9``), or,
    under differentiation, ``%jvp_jit__<fn>__.<k>``."""
    pat = re.compile(r"%(?:jvp_jit__)?" + re.escape(fn)
                     + r"(?:__)?(?:\.\d+)? = [^=]*? custom-call\(")
    return [e for e in events if pat.match(e[0])]


def host_spans(pd, names) -> list[tuple[str, int, int]]:
    """The host events named in ``names`` (the benchmark's annotations),
    as ``(name, start ns, end ns)`` sorted by start."""
    names = set(names)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns)))
    return sorted(out, key=lambda e: e[1])


def clip(events, lo: int, hi: int):
    """Events cut to the window [lo, hi]; those outside it dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def busy_intervals(events) -> list[tuple[int, int]]:
    """Union of the events' intervals, as sorted disjoint intervals."""
    out: list[list[int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events) -> int:
    return sum(e - s for s, e in busy_intervals(events))


def idle_gaps(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """The intervals of [lo, hi] in which no event ran."""
    gaps, t = [], lo
    for s, e in busy_intervals(clip(events, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_totals(events) -> list[tuple[str, float]]:
    """(op name, total seconds) over the events, largest first."""
    tot: dict[str, int] = {}
    for n, s, e in events:
        tot[n] = tot.get(n, 0) + (e - s)
    return sorted(((n, v / 1e9) for n, v in tot.items()),
                  key=lambda x: -x[1])


def label_gaps(gaps, spans, default: str = "no span"):
    """Each gap named by the host span that covers its midpoint (the
    innermost, i.e. the latest started), longest gap first."""
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        name = default
        for n, ss, se in spans:
            if ss <= mid < se:
                name = n
            elif ss > mid:
                break
        out.append((name, (e - s) / 1e9))
    return sorted(out, key=lambda x: -x[1])


def summarize(trace_dir: str, window_span: str, chips, span_names,
              top: int = 10) -> dict:
    """Everything a traced run reports, for the window that the host span
    ``window_span`` covers, on the chips it used: per-chip events inside
    the window, the window's length and the busy seconds averaged over the
    chips, the busiest ops and the longest idle gaps by host span.  The
    trace is read once."""
    pd = load(trace_dir)
    win = host_spans(pd, [window_span])
    if not win:
        raise RuntimeError(f"no {window_span!r} span in the trace")
    lo, hi = win[0][1], win[0][2]
    ops = device_ops(pd)
    spans = host_spans(pd, span_names)
    per_chip = {c: clip(ops.get(c, []), lo, hi) for c in chips}
    busy = [busy_ns(per_chip[c]) / 1e9 for c in chips]
    all_ops = [e for c in chips for e in per_chip[c]]
    gaps = [g for c in chips for g in label_gaps(
        idle_gaps(per_chip[c], lo, hi), spans)]
    return {"events": per_chip, "window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "breakdown": {
                "device_ops": [[n, s / len(chips)]
                               for n, s in op_totals(all_ops)[:top]],
                "idle_gaps": [[n, s] for n, s in
                              sorted(gaps, key=lambda x: -x[1])[:top]]}}
