"""Run one cell of the benchmark on the chips of this machine.

  python3 bench/run.py --workload lm.phi3.ht2 --seed 7 --seconds 51 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the reference decides,
with its limit. The same comparisons end standard error. Where JAX finds
no TPU, or fewer chips than the cell asks for, the run prints no result
and exits non-zero.

Python's string hash seeds each trial's weights (the program derives a
trial's key from ``hash`` of its hyperparameters), so the run re-executes
itself with a fixed ``PYTHONHASHSEED``: the same seed then gives the same
weights in every run. JAX's persistent compile cache is kept at
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_DEVICE = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("PYTHONHASHSEED") != "0":
        env = dict(os.environ, PYTHONHASHSEED="0",
                   BENCH_PROCESS_T0=repr(T_START))
        os.execve(sys.executable, [sys.executable] + sys.argv, env)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace))
    except harness.NoAccelerator as e:
        print(f"no accelerator for {args.workload}: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
