"""Readings that set a cell's limits, on the chip at the cell's own size:

  python3 bench/calibrate.py --workload lm.phi3.ht2 --seeds 1 2 3 ...

For each seed, the program's first steps (the run's set-up, no window)
against the plain reference, and in the program's place the reference's
control (one precision below the configuration's) and the fault that
leaves half the batch out, each against the reference. A state left
unchanged reads 1 on ``dparam_gap`` by construction and needs no run.
Prints one JSON line per seed and reading, then the largest program
reading and the smallest control and fault readings of each number.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["control", "half_batch"])
    args = ap.parse_args(argv)
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.execve(sys.executable, [sys.executable] + sys.argv,
                  dict(os.environ, PYTHONHASHSEED="0"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness
    cell = harness.Cell(args.workload)
    worst = {}
    for seed in args.seeds:
        t = time.monotonic()
        prog, hparams = harness.first_steps(args.workload, seed)
        refs = harness.reference_readings(cell, seed, hparams)
        rows = {"program": harness.compare(prog, refs)}
        for variant in args.variants:
            alt = harness.reference_readings(cell, seed, hparams, variant)
            rows[variant] = harness.compare(harness.as_program(alt), refs)
        for who, values in rows.items():
            print(json.dumps({"seed": seed, "reading": who, **values}),
                  flush=True)
            for k, v in values.items():
                lo, hi = worst.get((who, k), (v, v))
                worst[(who, k)] = (min(lo, v), max(hi, v))
        print(f"seed {seed}: {time.monotonic() - t:.1f} s", file=sys.stderr)
    for (who, k), (lo, hi) in sorted(worst.items()):
        print(f"{who:10s} {k:10s} min {lo!r} max {hi!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
