"""System-side benchmarks: kernels (vs refs), GA3C throughput vs t_max
(the cost coupling of paper §5.1), LM step timing, roofline table."""
from __future__ import annotations

import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_kernels():
    """Kernel vs reference timings. interpret=True executes the Pallas body
    on CPU — correctness-representative, NOT TPU-performance-representative;
    the ref timing is the production-CPU number."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.selective_scan.ops import selective_scan
    rows = []
    rng = np.random.default_rng(0)
    t = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)

    q, k, v = t(1, 256, 4, 64), t(1, 256, 2, 64), t(1, 256, 2, 64)
    us_ref = _time(lambda: flash_attention(q, k, v, use_pallas=False))
    us_pal = _time(lambda: flash_attention(q, k, v, bq=64, bk=64,
                                           interpret=True))
    rows.append(("kernel/flash_attention/ref_us", us_ref,
                 f"pallas_interpret_us={us_pal:.0f}"))

    x, sc = t(512, 1024), t(1024)
    rows.append(("kernel/rmsnorm/ref_us",
                 _time(lambda: rmsnorm(x, sc, use_pallas=False)),
                 f"pallas_interpret_us="
                 f"{_time(lambda: rmsnorm(x, sc, interpret=True)):.0f}"))

    u = t(1, 256, 64)
    dt = jnp.abs(t(1, 256, 64)) * 0.1
    a = -jnp.abs(t(64, 8))
    b, c = t(1, 256, 8), t(1, 256, 8)
    h0 = t(1, 64, 8)
    dk = jnp.ones(64)
    rows.append(("kernel/selective_scan/ref_us",
                 _time(lambda: selective_scan(u, dt, a, b, c, dk, h0,
                                              use_pallas=False)),
                 f"pallas_interpret_us="
                 f"{_time(lambda: selective_scan(u, dt, a, b, c, dk, h0, interpret=True, bd=64, bs=64)):.0f}"))
    return rows


def bench_ga3c_throughput():
    """Steps/s and samples/s vs t_max: shows the compute-cost coupling that
    motivates HyperTrick (t_max sets the batch AND the update rate)."""
    from repro.rl.ga3c import GA3CHyperParams, GA3CTrainer
    rows = []
    for t_max in (2, 8, 32):
        tr = GA3CTrainer("pong", GA3CHyperParams(t_max=t_max), n_envs=16,
                         seed=0)
        tr.run_episodes(4, max_updates=30)  # compile + warmup
        t0 = time.perf_counter()
        n = 30
        for _ in range(n):
            tr.params, tr.opt_state, tr.loop, _ = tr._step(
                tr.params, tr.opt_state, tr.loop)
        jax.block_until_ready(tr.loop.obs_stack)
        dt = time.perf_counter() - t0
        rows.append((f"ga3c/t_max={t_max}/updates_per_s", n / dt,
                     f"env_steps_per_s={n * 16 * t_max / dt:.0f}"))
    return rows


def bench_lm_train_step():
    """Reduced-config LM train-step latency for three families."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.train.trainer import Trainer
    rows = []
    for arch in ("yi-9b", "jamba-v0.1-52b", "xlstm-1.3b"):
        cfg = get_config(arch).reduced()
        tr = Trainer(cfg, TrainConfig(loss_chunk=32), batch=4, seq=64)
        tr.run(3)  # compile + warmup
        t0 = time.perf_counter()
        tr.run(10)
        us = (time.perf_counter() - t0) / 10 * 1e6
        rows.append((f"lm_step/{arch}/us", us,
                     f"loss={tr.losses[-1]:.3f}"))
    return rows


def bench_roofline():
    """The roofline table: per (arch x shape), single-pod mesh, from the
    dry-run artifacts in experiments/dryrun/."""
    rows = []
    base = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "dryrun")
    for path in sorted(glob.glob(os.path.join(base, "*_single.json"))):
        with open(path) as f:
            d = json.load(f)
        tag = os.path.basename(path)[:-5]
        if d.get("status") == "skip":
            rows.append((f"roofline/{tag}", 0.0, f"SKIP: {d['reason']}"))
            continue
        if d.get("status") != "ok":
            rows.append((f"roofline/{tag}", -1.0,
                         f"FAIL: {d.get('error', '?')[:80]}"))
            continue
        dom = d["bottleneck"]
        rows.append((
            f"roofline/{tag}", d[f"t_{dom}"],
            f"bottleneck={dom} tc={d['t_compute']:.3g} "
            f"tm={d['t_memory']:.3g} tx={d['t_collective']:.3g} "
            f"useful={d['useful_flops_ratio']:.2f} "
            f"fits_hbm={d['fits_hbm']}"))
    return rows
