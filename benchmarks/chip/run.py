"""Chip benchmark of DOPPLER: one cell of ``BENCHMARK.json`` per run.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The harness is driven by data.  A cell names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
the mix names its driver (``drivers/<driver>.py``), which builds the
system under test from the seed, warms it, runs the timed window and
checks what the window produced against the plain reference
(``reference/``).  Each per-layer metric is read by its own file,
``metrics/<metric>.py``, from the device trace of a ``--trace 1`` run.
Adding a cell, a mix or a metric adds files and entries; no file here
changes.

There is no CPU fallback: off a TPU, or with fewer chips than the cell
asks for, the run exits non-zero before any work and prints no result.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``), then ``checks``: each compared number beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is timed from here

import argparse                         # noqa: E402
import importlib.util                   # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import pathlib                          # noqa: E402
import sys                              # noqa: E402
import tempfile                         # noqa: E402

# the TPU runtime's logs go under the run's own TMPDIR, not /tmp/tpu_logs
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]                  # the checkout


def load_file(path: pathlib.Path, name: str | None = None):
    """Import one of the benchmark's own files by path."""
    if not path.is_file():
        raise SystemExit(f"benchmark: missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SystemExit(f"benchmark: missing {path}")
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")


def check_device(chips: int) -> dict:
    """The accelerator JAX sees, as it reports it; anything but a TPU with
    at least ``chips`` chips ends the run before any work."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def setup_jax() -> str:
    """Persistent compile cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), caching every program, so that
    only a cell's first run in a checkout compiles."""
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def layer_metrics(bench: dict, cell: dict) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_metrics(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if (cell["name"] in cells) if cells is not None else \
                (m["moves"] in e2e):
            out.append(m)
    return out


def end_to_end_metrics(bench: dict, cell: dict) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             device: dict, peaks: dict | None = None) -> dict:
    """Run one cell on the devices JAX holds; return the result line."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, cell_name)
    config = load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    driver = load_file(HERE / "drivers" / f"{traffic['driver']}.py")
    if peaks is None:
        table = load_json(HERE / "peaks.json")["devices"]
        if device["kind"] not in table:
            raise SystemExit(f"benchmark: no peaks for device kind "
                             f"{device['kind']!r} in peaks.json")
        peaks = table[device["kind"]]
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "seed": seed, "seconds": seconds, "trace": trace,
           "t_start": T_START, "peaks": peaks, "chips": cell["chips"],
           "root": ROOT, "here": HERE, "load": load_file}
    res = driver.run(ctx)

    metrics = {}
    if not trace:
        for m in end_to_end_metrics(bench, cell):
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in layer_metrics(bench, cell):
            reader = load_file(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(res["layer_ctx"])
            if value is None:
                # left out of the line, as the contract asks, but never
                # silently: a kernel renamed or taken off the path shows
                print(f"error: {m['name']} read nothing in {cell_name}",
                      file=sys.stderr, flush=True)
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": {**device,
                       "memory_peak_bytes": res["memory_peak_bytes"]}}
    if trace:
        line["device"]["busy_s"] = res["layer_ctx"]["busy_s"]
        line["device"]["window_s"] = res["layer_ctx"]["window_s"]
        line["breakdown"] = res["breakdown"]
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in res["checks"]}
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    device = check_device(find_cell(bench, args.workload)["chips"])
    setup_jax()
    line = run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace), device)
    print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
