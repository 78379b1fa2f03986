"""The trace reduction: interval arithmetic on hand-made events, and
reading a small trace recorded here."""
from __future__ import annotations

import jax
import jax.numpy as jnp

import run

trace = run.load_file(run.HERE / "trace.py")


def test_busy_union_and_gaps():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert trace.busy_intervals(evs) == [(0, 15), (20, 30)]
    assert trace.busy_ns(evs) == 25
    assert trace.idle_gaps(evs, -5, 40) == [(-5, 0), (15, 20), (30, 40)]
    assert trace.clip(evs, 8, 21) == [("a", 8, 10), ("b", 8, 15),
                                      ("c", 20, 21)]


def test_op_totals_and_gap_labels():
    evs = [("a", 0, 10), ("b", 10, 15), ("a", 20, 30)]
    assert trace.op_totals(evs) == [("a", 20e-9), ("b", 5e-9)]
    spans = [("outer", 0, 100), ("inner", 16, 19)]
    assert trace.label_gaps([(15, 20), (30, 90)], spans) == [
        ("outer", 60e-9), ("inner", 5e-9)]
    assert trace.label_gaps([(40, 50)], spans) == [("outer", 10e-9)]


def test_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
    pd = trace.load(str(tmp_path))
    steps = trace.host_spans(pd, ["bench.step"])
    win = trace.host_spans(pd, ["bench.window"])
    assert len(steps) == 3 and len(win) == 1
    lo, hi = win[0][1], win[0][2]
    assert all(lo <= s <= e <= hi for _, s, e in steps)
    # a CPU run has no TPU plane: nothing is read as device time
    summ = trace.summarize(str(tmp_path), "bench.window", [0],
                             ["bench.step"])
    assert summ["window_s"] == (hi - lo) / 1e9
    assert summ["busy_s"] == 0.0
    assert summ["breakdown"]["idle_gaps"][0][1] > 0
