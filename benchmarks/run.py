"""Benchmark orchestrator — one module per paper table/figure.

Each module prints ``name,us_per_call,derived`` CSV rows; this runner
executes every selected module in its own subprocess (isolated jax
runtime, per-module env such as the multi-device XLA flag the fused
training benchmark wants), streams the output through, and writes the
parsed rows to ``BENCH_<tag>.json`` so the perf trajectory is machine
readable.  Default budgets are CPU-reduced; set REPRO_FULL=1 for the
paper's episode counts.  Select subsets: python -m benchmarks.run sim
train table1 ...
"""
from __future__ import annotations

# This parent process must never import jax, and it runs its children one
# at a time: a chip belongs to one process, so a parent holding it (or two
# children at once) would leave the running child without the device.
import json
import os
import re
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# (tag, module, extra env) — env is applied before the subprocess starts,
# i.e. before jax initializes in it.  XLA_FLAGS entries are *merged* with
# (appended to) any user-set value rather than clobbering it, and
# JAX_PLATFORMS / backend selectors pass through untouched, so
# `JAX_PLATFORMS=cpu python -m benchmarks.run train` benches the backend
# you asked for — and BENCH_<tag>.json records which backend actually
# resolved in the child.
MODULES = [
    ("sim", "bench_simulator", {}),
    ("train", "bench_training",
     {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"}),
    ("exec", "bench_executor", {}),
    ("serve", "bench_serving", {}),
    ("dyn", "bench_dynamic", {}),
    ("table1", "table1_wc_vs_sync", {}),
    ("table2", "table2_methods", {}),
    ("table3", "table3_ablation", {}),
    ("table4", "table4_transfer", {}),
    ("fig4", "fig4_stages", {}),
    # reworked Fig. 6: flat-vs-hierarchical scalability sweep (was "fig6")
    ("hier", "fig6_scalability", {}),
    ("table6", "table6_mp_ablation", {}),
    ("table9", "table9_hardware", {}),
    ("g1", "g1_sim_fidelity", {}),
    ("roofline", "roofline", {}),
    ("zoo", "zoo_sweep", {}),
]

ROW_RE = re.compile(r"^([A-Za-z0-9_.:/\-]+),(-?[0-9.eE+\-]+),(.*)$")
BACKEND_RE = re.compile(r"^# resolved_backend=(\S+)")


def merge_env(base: dict, extra: dict) -> dict:
    """Child env = parent env + per-tag extras.  XLA_FLAGS is additive
    (the tag's flags append to the user's, which win on conflict since
    XLA takes the last occurrence); everything else the tag sets wins."""
    env = {**base}
    for k, v in extra.items():
        if k == "XLA_FLAGS" and base.get(k):
            env[k] = f"{v} {base[k]}"
        else:
            env[k] = v
    return env


def parse_derived(text: str) -> dict:
    """'eps_per_sec=123.4 speedup=6.1x n=512' -> typed dict (trailing
    'x' multipliers stripped); bare tokens become boolean flags."""
    out: dict = {}
    for tok in text.split():
        if "=" in tok:
            k, v = tok.split("=", 1)
            raw = v[:-1] if v.endswith("x") and v[:-1].replace(
                ".", "", 1).replace("-", "", 1).isdigit() else v
            try:
                out[k] = int(raw)
            except ValueError:
                try:
                    out[k] = float(raw)
                except ValueError:
                    out[k] = v
        else:
            out[tok] = True
    return out


def run_module(tag: str, mod_name: str, env_extra: dict
               ) -> tuple[bool, list[dict], str | None]:
    """Run one benchmark module in a subprocess; return
    (ok, rows, resolved_backend)."""
    env = merge_env(dict(os.environ), env_extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), BENCH_DIR,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    # probe the backend that actually resolved AFTER main() ran, when jax
    # is guaranteed initialized (modules may set XLA flags at import)
    code = (f"import sys; sys.path.insert(0, {BENCH_DIR!r}); "
            f"sys.path.insert(0, {ROOT!r}); "
            f"import {mod_name}; {mod_name}.main(); "
            f"import jax; print('# resolved_backend=' "
            f"+ jax.default_backend(), flush=True)")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    rows = []
    backend = None
    assert proc.stdout is not None
    for line in proc.stdout:
        print(line, end="", flush=True)
        b = BACKEND_RE.match(line.strip())
        if b:
            backend = b.group(1)
            continue
        m = ROW_RE.match(line.strip())
        if m:
            try:
                us = float(m.group(2))
            except ValueError:      # comma-bearing log line, not a row
                continue
            rows.append({"name": m.group(1),
                         "us_per_call": us,
                         "derived": parse_derived(m.group(3)),
                         "derived_raw": m.group(3)})
    proc.wait()
    return proc.returncode == 0, rows, backend


def write_json(tag: str, rows: list[dict], elapsed: float,
               backend: str | None) -> str:
    out_dir = os.environ.get("REPRO_BENCH_DIR", os.getcwd())
    path = os.path.join(out_dir, f"BENCH_{tag}.json")
    with open(path, "w") as f:
        json.dump({"tag": tag, "elapsed_sec": round(elapsed, 1),
                   "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                   "backend": backend,
                   "jax_platforms": os.environ.get("JAX_PLATFORMS"),
                   "rows": rows}, f, indent=1)
    return path


def main() -> None:
    want = set(sys.argv[1:])
    failures = []
    for tag, mod_name, env_extra in MODULES:
        if want and tag not in want:
            continue
        t0 = time.time()
        print(f"# === {tag} ({mod_name}) ===", flush=True)
        ok, rows, backend = run_module(tag, mod_name, env_extra)
        elapsed = time.time() - t0
        if not ok:
            failures.append(tag)
            print(f"# {tag} FAILED after {elapsed:.0f}s", flush=True)
            continue
        path = write_json(tag, rows, elapsed, backend)
        print(f"# {tag} done in {elapsed:.0f}s -> {path}", flush=True)
    if failures:
        print(f"# FAILURES: {failures}")
        raise SystemExit(1)
    print("# all benchmarks done")


if __name__ == "__main__":
    main()
