"""Readings that set the limits of ``correct``, at a cell's own size, on
the chip: the program on many seeds, and the control and the planted
faults on a few.

    python benchmarks/chip/control.py --workload <cell> \
        --seeds 1,2,...,12 --mode-seeds 1,2,3

For each seed the program's trainer is built as a benchmark run builds
it and driven through the cell's first updates by the window's own call
(``program``); the plain reference then follows the same updates in
float32 (what the program is held to), and, on ``--mode-seeds``, again
with one defect put in the program's place:

* ``control``: the policy's arithmetic in bfloat16, the nearest
  precision below the configuration's float32;
* ``half_batch``: the gradient and loss over half of the batch;
* ``answer``: every makespan the oracle returns off by one part in 1000;
* ``unchanged``: a step that returns its state unchanged.

Each is compared with the float32 reference exactly as a benchmark run
compares the program.  Each line printed is ``{"seed", "mode",
"seconds", "read"}``: every number the check reads.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def readings(ctx, seeds, mode_seeds, modes):
    import jax.numpy as jnp
    load = ctx["load"]
    common = load(ctx["here"] / "common.py")
    drv = load(ctx["here"] / "drivers" / "stage2.py")
    traffic = ctx["traffic"]
    tap = drv.LossTap()
    for seed in seeds:
        t = time.perf_counter()
        tr, _, dispatch = drv.build(ctx, seed)
        pg = tr.g
        prog = drv.first_updates(tr, dispatch, tap,
                                 traffic["check_updates"], common)
        del tr
        t_prog = time.perf_counter() - t
        r32 = drv.reference(ctx, pg, prog)
        emit(seed, "program", t_prog, drv.compare(prog, r32, common),
             [u["seconds"] for u in r32])
        if seed not in mode_seeds:
            continue
        for mode in modes:
            t = time.perf_counter()
            kw = ({"dtype": jnp.bfloat16} if mode == "control"
                  else {"fault": mode})
            r = drv.reference(ctx, pg, prog, **kw)
            alt = {"p0": prog["p0"],
                   "makespans": [u["makespans"] for u in r],
                   "loss": [u["loss"] for u in r], "grad": r[0]["grad"],
                   "params": r[-1]["params"]}
            emit(seed, mode, time.perf_counter() - t,
                 drv.compare(alt, r32, common), None)


def emit(seed, mode, seconds, read, ref_seconds):
    line = {"seed": seed, "mode": mode, "seconds": seconds, "read": read}
    if ref_seconds is not None:
        line["reference_seconds"] = ref_seconds
    print(json.dumps(line), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mode-seeds", default="")
    ap.add_argument("--modes", default="control,half_batch,answer,unchanged")
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    run.check_device(cell["chips"])
    run.setup_jax()
    ctx = {"cell": cell,
           "config": run.load_json(run.HERE / "configs"
                                   / f"{cell['config']}.json"),
           "traffic": run.load_json(run.HERE / "traffic"
                                    / f"{cell['traffic']}.json"),
           "root": run.ROOT, "here": run.HERE, "load": run.load_file}
    seeds = [int(s) for s in args.seeds.split(",")]
    mode_seeds = {int(s) for s in args.mode_seeds.split(",") if s}
    readings(ctx, seeds, mode_seeds, args.modes.split(","))
    return 0


if __name__ == "__main__":
    sys.exit(main())
