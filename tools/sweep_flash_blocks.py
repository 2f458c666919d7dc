"""Sweep the training flash kernel's block sizes on a TPU.

    PYTHONPATH=src python tools/sweep_flash_blocks.py [--seq 2048] [--out F]

Attention alone, forward and backward (``jax.grad`` of a weighted sum),
vmapped over 2 slots of 1 x ``seq`` tokens at 32 heads of 96 (Phi-3-mini),
bf16 operands. Each of the three kernels (forward, dK/dV, dQ) takes each
(block_q, block_k) in {256, 512, 1024}^2 while the other two stay at 512;
then the best of each together, and the chunked scan for comparison, each
also forward alone. Prints one JSON line per setting (milliseconds a call,
median of 5 timed rounds of 10 calls); a setting the compiler refuses
prints its error.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

SIZES = (256, 512, 1024)


def _time_ms(fn, args, rounds=5, calls=10):
    import jax
    jax.block_until_ready(fn(*args))
    per = []
    for _ in range(rounds):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        per.append((time.perf_counter() - t) / calls * 1e3)
    return statistics.median(per)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--out", default=None, help="also append the lines here")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.train import (block_sizes,
                                                     flash_attention_train)
    from repro.models.attention import chunked_attention

    slots, S, H, hd = 2, args.seq, 32, 96
    rng = np.random.default_rng(0)
    q, k, v, ct = (jnp.asarray(rng.standard_normal((slots, 1, S, H, hd)),
                               jnp.bfloat16) for _ in range(4))

    def train(attend):
        loss = lambda q, k, v, ct: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * ct)
        return jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1, 2))))

    def forward(attend):
        return jax.jit(jax.vmap(lambda q, k, v, ct: attend(q, k, v)))

    def emit(row):
        line = json.dumps({"seq": S, **row})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    def measure(kernel, fwd, dkv, dq, only_forward=False):
        blocks = block_sizes(fwd, dkv, dq)
        attend = lambda q, k, v: flash_attention_train(q, k, v, blocks)
        make = forward if only_forward else train
        row = {"kernel": kernel, "fwd": fwd, "dkv": dkv, "dq": dq,
               "pass": "fwd" if only_forward else "fwd+bwd"}
        try:
            row["ms"] = _time_ms(make(attend), (q, k, v, ct))
        except Exception as e:  # noqa: BLE001 — a refused setting is a result
            row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        emit(row)
        return row.get("ms", float("inf"))

    base = (min(512, S),) * 2
    grid = [(bq, bk) for bq in SIZES for bk in SIZES if bq <= S and bk <= S]
    best = {}
    best["fwd"] = min(grid, key=lambda b: measure("fwd", b, base, base))
    best["dkv"] = min(grid, key=lambda b: measure("dkv", base, b, base))
    best["dq"] = min(grid, key=lambda b: measure("dq", base, base, b))
    for only_forward in (True, False):
        measure("base", base, base, base, only_forward)
        measure("best", best["fwd"], best["dkv"], best["dq"], only_forward)
    for only_forward in (True, False):
        make = forward if only_forward else train
        emit({"kernel": "chunked", "pass": "fwd" if only_forward
              else "fwd+bwd", "ms": _time_ms(make(
                  lambda q, k, v: chunked_attention(q, k, v, chunk=512)),
                  (q, k, v, ct))})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
