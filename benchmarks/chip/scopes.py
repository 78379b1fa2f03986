"""The fused Stage-II step's phases on the device trace's clock.

The program runs each phase of an update under a ``jax.named_scope``
(``core/train_fused.py``): ``doppler.encoder``, ``doppler.sample``,
``doppler.oracle``, ``doppler.grad`` and ``doppler.adamw``.  A scope is
written into the compiled step as each HLO instruction's ``op_name``
metadata, a ``/``-separated path such as
``jit(<lambda>)/while/body/closed_call/doppler.oracle/jit(_wc_step)/...``.
An op belongs to the outermost ``doppler.*`` component of its path, so
the encoder that the loss recomputes counts as ``doppler.grad``; an op
with none is unscoped (``""``): the update loop's own work, advantages,
key splits.  An instruction that carries no ``op_name`` at all (layout
copies and fusions the compiler adds) belongs to the event that encloses
it on the device's timeline: a copy inside the oracle's trip loop counts
as ``doppler.oracle``.

The trainer writes three host spans per dispatch
(``core/training.py``), named in ``HOST_SPANS``.

Device events nest: a ``while`` op's event covers its body's.  A scope's
self time is the time in which the innermost running event is one of its
ops, so the scopes' self times add up to the chip's busy time, with no
instant counted twice.
"""
from __future__ import annotations

import re

SCOPE_PREFIX = "doppler."
HOST_SPANS = ("doppler.stage2.dispatch", "doppler.stage2.sync",
              "doppler.stage2.record")

_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The outermost ``doppler.*`` component of an ``op_name`` path, or
    ``""``."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return ""


def instruction(event_name: str) -> str:
    """The HLO instruction an op event names: a TPU op event is named by
    the instruction's text, ``%<instruction> = <shape> <opcode>(...``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """{instruction: op_name} over a compiled module's text
    (``compiled.as_text()``), ``""`` where an instruction has none."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            o = _OP_NAME.search(line)
            out[m.group(1)] = o.group(1) if o else ""
    return out


def self_ns(events, scope, lo: int, hi: int) -> dict[str, int]:
    """{scope: self nanoseconds} over the window [lo, hi]: each instant in
    which some event runs goes to the innermost one (the latest started),
    and to its scope ``scope(name)``; where that is None, to the scope of
    the event that encloses it (``""`` if none).  ``events``: ``(name,
    start ns, end ns)``."""
    evs = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi), key=lambda x: (x[1], -x[2]))
    out: dict[str, int] = {}
    stack: list[tuple[int, str]] = []      # (end, scope), innermost last
    t = lo

    def run_to(to):
        nonlocal t
        while t < to:
            while stack and stack[-1][0] <= t:
                stack.pop()
            if not stack:
                break
            end = min(stack[-1][0], to)
            sc = stack[-1][1]
            out[sc] = out.get(sc, 0) + end - t
            t = end
        t = max(t, to)

    for n, s, e in evs:
        run_to(s)
        while stack and stack[-1][0] <= s:
            stack.pop()
        sc = scope(n)
        if sc is None:
            sc = stack[-1][1] if stack else ""
        stack.append((e, sc))
    run_to(hi)
    return out
