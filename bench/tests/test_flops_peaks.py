"""Operations and bytes reckoned from shapes against hand counts, the
peaks table's lookup, and the command's refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
FLOPS = os.path.join(ROOT, "bench", "flops")


def flops(kind):
    return harness.load_module(os.path.join(FLOPS, f"{kind}.py"),
                               f"test_flops_{kind}")


def test_lm_operations_and_bytes_by_hand():
    cfg = {"hidden_size": 8, "intermediate_size": 16,
           "num_attention_heads": 2, "num_key_value_heads": 2,
           "num_hidden_layers": 1, "vocab_size": 32}
    lm = flops("lm")
    # q, k, v, o 4 * 64; gate, up, down 3 * 128; head 8 * 32
    assert lm.matmul_params(cfg) == 256 + 384 + 256
    # plus the embedding 256 and three norms of 8
    assert lm.all_params(cfg) == 896 + 256 + 24
    # 3 * (2 * 896 + causal attention at seq 4: 2 * 2 * 8 * 5 / 2)
    assert lm.flops_per_token(cfg, 4) == pytest.approx(3 * (1792 + 80))
    # forward and backward read 2 bytes a parameter each; AdamW reads the
    # weight and gradient (2 + 2), reads and writes m and v (4 * 4) and
    # writes the weight (2)
    assert lm.update_bytes(cfg) == 1176 * (4 + 22)


def test_peaks_are_keyed_by_device_kind():
    v5e = harness.peaks(ROOT, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks(ROOT, "TPU v9 imaginary")


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONHASHSEED", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lm.phi3.ht2",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_a_host_without_tpu(tmp_path):
    # a copy of the committed files, so no cache is written to the repo
    shutil.copytree(ROOT, tmp_path / "co", ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", ".hypothesis", "chiprun_out", "__pycache__",
        ".scratch", ".bench_trace", ".proof", "build"))
    out = _run(tmp_path / "co", {})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


def test_the_command_needs_the_program(tmp_path):
    co = tmp_path / "co"
    co.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), co)
    shutil.copytree(os.path.join(ROOT, "bench"), co / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(co, {})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
