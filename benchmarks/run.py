"""Benchmark harness — one function per paper table/figure plus system
benches. Prints ``name,value,derived`` CSV; ``--json PATH`` additionally
records the rows (plus run metadata) to a JSON file, which is how the repo
keeps a perf trajectory (e.g. BENCH_population.json).

Each suite runs in a child process of its own, and this process never
imports jax: a TPU chip belongs to one process at a time, so a harness
that held it would leave every suite that starts JAX worker processes
without one.

  PYTHONPATH=src python -m benchmarks.run [--only substring] [--fast]
      [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (suite, module under benchmarks/, function); --fast runs the first group
_FAST_SUITES = [
    ("toy_problem", "metaopt_benches", "bench_toy_problem"),  # Figs 2/3/8/9
    ("completion_rate", "metaopt_benches", "bench_completion_rate"),  # Tab 1
    ("hyperband_brackets", "metaopt_benches",
     "bench_hyperband_brackets"),                              # Table 2
    ("ht_vs_hyperband", "metaopt_benches",
     "bench_ht_vs_hyperband"),                                 # Tab 3/Fig 6
    ("hparam_importance", "metaopt_benches",
     "bench_hparam_importance"),                               # Table 4
    ("beyond_paper", "metaopt_benches",
     "bench_beyond_paper_policies"),                           # §6
    ("roofline", "system_benches", "bench_roofline"),          # Roofline
    ("kernels", "system_benches", "bench_kernels"),
]
_SLOW_SUITES = [
    ("server_load", "server_load", "bench_server_load"),
    ("ga3c_throughput", "system_benches", "bench_ga3c_throughput"),
    ("lm_train_step", "system_benches", "bench_lm_train_step"),
    ("metaopt_rl_real", "metaopt_benches", "bench_metaopt_rl_real"),
    ("backend_overhead", "metaopt_benches", "bench_backend_overhead"),
    ("population_throughput", "population_benches",
     "bench_population_throughput"),
    ("population_lm", "population_benches", "bench_population_lm"),
    ("sharded_population", "sharded_benches", "bench_sharded_population"),
    ("population_multihost", "multihost_benches",
     "bench_population_multihost"),
    ("population_pbt", "pbt_benches", "bench_population_pbt"),
    ("telemetry_overhead", "telemetry_benches", "bench_telemetry_overhead"),
    ("trace_overhead", "trace_benches", "bench_trace_overhead"),
]
_RESULT = "BENCH_SUITE_RESULT "


def _suites(fast: bool):
    return _FAST_SUITES + ([] if fast else _SLOW_SUITES)


def run_suite(module: str, fn: str) -> None:
    """Child side: run one suite and print its rows and the jax runtime it
    ran on as one marked JSON line."""
    import importlib
    rows = getattr(importlib.import_module(f"benchmarks.{module}"), fn)()
    import jax
    meta = {"jax_version": jax.__version__,
            "device_count": jax.device_count(),
            "backend": jax.default_backend()}
    print(_RESULT + json.dumps({"rows": rows, "meta": meta}, default=float),
          flush=True)


def _run_child(module: str, fn: str) -> dict:
    code = ("from benchmarks.run import run_suite; "
            f"run_suite({module!r}, {fn!r})")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          stdout=subprocess.PIPE, text=True)
    result = None
    for line in proc.stdout.splitlines():
        if line.startswith(_RESULT):
            result = json.loads(line[len(_RESULT):])
        else:
            print(line, file=sys.stderr)
    if proc.returncode != 0 or result is None:
        raise RuntimeError(f"suite process exited {proc.returncode}")
    return result


def _env_meta() -> dict:
    """Attribution for the perf trajectory: which commit, which jax, how
    many devices (the jax fields are filled in from the suite processes).
    Each field degrades to None rather than failing the bench run."""
    meta = {"git_sha": None, "jax_version": None, "device_count": None,
            "backend": None}
    try:
        meta["git_sha"] = subprocess.check_output(
            ["git", "rev-parse", "HEAD"], text=True, cwd=ROOT,
            stderr=subprocess.DEVNULL).strip()
    except (OSError, subprocess.CalledProcessError):
        pass
    return meta


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows + metadata to this JSON file")
    args = ap.parse_args()

    print("name,value,derived")
    failures = 0
    all_rows = []
    meta = _env_meta()
    for name, module, fn in _suites(args.fast):
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            result = _run_child(module, fn)
        except RuntimeError as e:
            print(f"{name},ERROR,{e}")
            failures += 1
            continue
        meta.update(result["meta"])
        for rname, value, derived in result["rows"]:
            v = f"{value:.6g}" if isinstance(value, float) else value
            print(f'{rname},{v},"{derived}"')
            all_rows.append({"name": rname, "value": value,
                             "derived": derived})
        print(f"# {name} took {time.time()-t0:.1f}s", file=sys.stderr)
    if args.json:
        doc = {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "argv": sys.argv[1:],
            **meta,
            "rows": all_rows,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(all_rows)} rows to {args.json}",
              file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
