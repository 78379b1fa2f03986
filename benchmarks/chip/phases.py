"""Where a Stage-II update's device time goes, by the program's own names.

    python benchmarks/chip/phases.py --workload <cell> --seed <n> \
        [--out <file.json>]

A reading taken by hand, beside the harness (``run.py``): it builds the
cell's trainer as the cell's driver does (``drivers/stage2.py``), warms
it, times untraced dispatches, traces as many more as a traced run of
the cell does (the mix's ``trace_dispatches``), and then reads the
trace.  Each device op is given its phase (``scopes.py``) by
mapping the HLO instruction that names its event to that instruction's
``op_name`` in the compiled step's text, which is taken after the traced
window; an instruction without one takes the phase of the op that
encloses it.  It prints one JSON object: each phase's self seconds per update,
the oracle's kernel launches per update and its time per launch, the
host spans and the longest idle gaps, each by its offset from the
window's start, and the dispatch seconds untraced and traced.

Like the harness, it needs a TPU and exits before any work without one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                         # noqa: E402
import json                             # noqa: E402
import statistics                       # noqa: E402
import sys                              # noqa: E402

import run                              # noqa: E402  (sets TPU_LOG_DIR)

UNMAPPED = "?"          # an event whose instruction the text lacks


def capture_steps() -> list:
    """Keep every fused step the trainer builds (``build_fused_stage2``),
    so that its compiled text can be had after the window."""
    import repro.core.train_fused as tf
    steps, build = [], tf.build_fused_stage2

    def kept(*args, **kwargs):
        fn = build(*args, **kwargs)
        steps.append(fn)
        return fn

    tf.build_fused_stage2 = kept
    return steps


def step_text(step, tr) -> str:
    """The compiled text of the step the trainer dispatches, lowered from
    its own state (lowering runs nothing and donates nothing)."""
    import jax.numpy as jnp
    from repro.core.train_fused import RewardStats
    return step.lower(tr.params, tr.opt_state, RewardStats.make(), tr.key,
                      jnp.int32(tr.episode)).compile().as_text()


def result_shape(hlo: str) -> str:
    """The result shape of ``%<instruction> = <shape> <opcode>(...``, a
    tuple's included: an event whose shape differs from its instruction's
    in the text was read against another program."""
    rest = hlo.strip().removeprefix("ROOT ").partition(" = ")[2]
    if not rest.startswith("("):
        return rest.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i + 1]
    return rest


def read_phases(trace_dir: str, hlo_text: str, updates: int,
                top: int = 5) -> dict:
    """The window's device time by phase, per update."""
    import common
    import scopes
    trace = run.load_file(run.HERE / "trace.py")
    pd = trace.load(trace_dir)
    (_, lo, hi), = trace.host_spans(pd, [common.WINDOW_SPAN])
    evs = trace.clip(trace.device_ops(pd).get(0, []), lo, hi)
    op_names = scopes.hlo_op_names(hlo_text)
    shapes = {scopes.instruction(line): result_shape(line)
              for line in hlo_text.splitlines() if " = " in line}
    scope_by_event: dict[str, str | None] = {}

    def scope(name):
        """None: no op_name, the enclosing op's scope is taken."""
        if name not in scope_by_event:
            o = op_names.get(scopes.instruction(name))
            scope_by_event[name] = (UNMAPPED if o is None else
                                    scopes.scope_of(o) if o else None)
        return scope_by_event[name]

    busy = trace.busy_ns(evs)
    self_s = {k: v / 1e9 / updates
              for k, v in scopes.self_ns(evs, scope, lo, hi).items()}
    per_op = scopes.self_ns(evs, lambda n: n, lo, hi)
    tops: dict[str, list] = {}
    for name, ns in sorted(per_op.items(), key=lambda x: -x[1]):
        rows = tops.setdefault(str(scope(name)), [])
        if len(rows) < top:
            rows.append([name[:120], ns / 1e9 / updates])
    kernel = [e for e in trace.pallas_calls(evs, "_wc_step")
              if scope(e[0]) == "doppler.oracle"]
    oracle_s = self_s.get("doppler.oracle", 0.0)
    spans = trace.host_spans(pd, ["bench.stage2_fused", *scopes.HOST_SPANS])
    gaps = sorted(trace.idle_gaps(evs, lo, hi), key=lambda g: g[0] - g[1])
    return {
        "updates": updates, "window_s": (hi - lo) / 1e9,
        "busy_s_per_update": busy / 1e9 / updates,
        "self_s_per_update": self_s,
        "scoped_share_of_busy": {k: v * updates * 1e9 / busy
                                 for k, v in self_s.items()},
        "trips_per_update": len(kernel) / updates,
        "oracle_us_per_trip": (1e6 * oracle_s * updates / len(kernel)
                               if kernel else None),
        "wc_step_kernel_s_per_update":
            sum(e - s for _, s, e in kernel) / 1e9 / updates,
        "events": len(evs),
        "events_unmapped": sum(1 for n, _, _ in evs
                               if scope(n) == UNMAPPED),
        "events_without_op_name": sum(1 for n, _, _ in evs
                                      if scope(n) is None),
        "ops_unlike_their_instruction": sorted(
            n[:80] for n in scope_by_event
            if " = " in n and scopes.instruction(n) in shapes
            and result_shape(n) != shapes[scopes.instruction(n)])[:top],
        # [name, offset from the window's start in s, seconds]
        "host_spans": [[n, (s - lo) / 1e9, (e - s) / 1e9]
                       for n, s, e in spans],
        "idle_gaps": [[trace.label_gaps([g], spans)[0][0], (g[0] - lo) / 1e9,
                       (g[1] - g[0]) / 1e9] for g in gaps[:top]],
        "top_ops_self_s_per_update": tops}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    device = run.check_device(cell["chips"])
    run.setup_jax()
    import common
    config = run.load_json(run.HERE / "configs" / f"{cell['config']}.json")
    traffic = run.load_json(run.HERE / "traffic"
                            / f"{cell['traffic']}.json")
    driver = run.load_file(run.HERE / "drivers" / f"{traffic['driver']}.py")
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "here": run.HERE, "root": run.ROOT, "load": run.load_file}
    steps = capture_steps()
    tr, _, dispatch = driver.build(ctx, args.seed)

    def timed():
        t = time.perf_counter()
        dispatch()
        return time.perf_counter() - t

    first = timed()                       # compiles or loads the cache
    setup_s = time.perf_counter() - T_START
    untraced = [timed(), timed()]
    with common.traced(True) as tinfo:
        traced = [timed() for _ in range(traffic["trace_dispatches"])]
    untraced.append(timed())
    t = time.perf_counter()
    text = step_text(steps[0], tr)
    text_s = time.perf_counter() - t
    updates = len(traced) * traffic["updates_per_dispatch"]
    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "setup_s": setup_s, "first_dispatch_s": first,
           "untraced_dispatch_s": untraced, "traced_dispatch_s": traced,
           "traced_over_untraced": statistics.median(traced)
           / statistics.median(untraced),
           "step_text_s": text_s,
           **read_phases(tinfo["dir"], text, updates)}
    common.drop_trace(tinfo)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
