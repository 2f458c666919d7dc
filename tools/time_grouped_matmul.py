"""Time the held experts' grouped matmuls on a TPU: XLA's ``ragged_dot``
against JAX's Pallas megablox ``gmm``.

    PYTHONPATH=src python tools/time_grouped_matmul.py [--out F]

One MoE layer's expert FFN at Moonlight-16B-A3B's shapes on one chip's
share (``lm.moonlight.ht1``): 8192 tokens routed 6 of 64, this chip holding
experts 0-7, so a static buffer of 8192 x 6 rows sorted with the held
assignments first (about 6144 of them) and the foreign ones after;
SwiGLU of width 1408 on rows of 2048, bf16 operands. Forward alone, and
forward and backward (``jax.grad`` of a weighted sum over the rows,
zero on the foreign ones, as the layer's combine makes it), for
``ragged_dot`` over the 8 held groups and for ``gmm`` at a few tilings
with the foreign rows as a ninth group it skips. Prints one JSON line per
setting (milliseconds a call, median of 5 timed rounds of 10 calls, and
the held rows' relative distance from the first setting's output); a
setting the compiler refuses prints its error.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

TILINGS = ((128, 128, 128), (512, 512, 128), (512, 1024, 256),
           (1024, 512, 512))


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
    ap.add_argument("--out", default=None, help="also append the lines here")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    T, k, E, held, d, f = 8192, 6, 64, 8, 2048, 1408
    rng = np.random.default_rng(0)
    ids = np.argsort(rng.random((T, E)), -1)[:, :k].reshape(-1)
    key = np.where(ids < held, ids, held)
    sizes = np.bincount(key, minlength=held + 1).astype(np.int32)
    rows = T * k
    xs = jnp.asarray(rng.standard_normal((rows, d)), jnp.bfloat16)
    kept = jnp.asarray(np.arange(rows) < sizes[:held].sum())
    ct = jnp.where(kept[:, None], jnp.asarray(
        rng.standard_normal((rows, d)), jnp.bfloat16), 0)
    w = [jnp.asarray(rng.standard_normal(s) / np.sqrt(s[1]), jnp.bfloat16)
         for s in ((held, d, f), (held, d, f), (held, f, d))]
    gs_held, gs_all = jnp.asarray(sizes[:held]), jnp.asarray(sizes)

    def ffn(product):
        def run(xs, gate, up, down):
            h = jax.nn.silu(product(xs, gate)) * product(xs, up)
            return product(h, down)
        return run

    def ragged(x, w):
        return jax.lax.ragged_dot(x, w, gs_held)

    def mb(tiling):
        return lambda x, w: megablox.gmm(
            x, w, gs_all, preferred_element_type=jnp.bfloat16,
            tiling=tiling)

    settings = [("ragged_dot", None, ragged)] + [
        ("megablox_gmm", t, mb(t)) for t in TILINGS]
    lines = [{"held_rows": int(sizes[:held].sum()), "rows": rows}]
    first = None
    for name, tiling, product in settings:
        run = ffn(product)
        loss = lambda *a, run=run: jnp.sum(run(*a).astype(jnp.float32) * ct)
        fwd = jax.jit(run)
        train = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
        line = {"impl": name, "tiling": tiling}
        try:
            line["fwd_ms"] = _time_ms(fwd, (xs, *w))
            out = jnp.where(kept[:, None], fwd(xs, *w), 0).astype(jnp.float32)
            first = out if first is None else first
            line["rel_diff"] = float(jnp.linalg.norm(out - first)
                                     / jnp.linalg.norm(first))
            line["fwd_bwd_ms"] = _time_ms(train, (xs, *w))
        except Exception as e:          # a tiling the compiler refuses
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "a") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
