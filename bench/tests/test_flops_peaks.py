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


def test_lm_attention_work_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "num_hidden_layers": 3}
    ops, moved = flops("lm").attention_work(cfg, 5, 4)
    # 3 layers x 3 forwards x 2 * 8 * (4 + 1) a token x 20 tokens
    assert ops == 3 * 3 * 80 * 20
    # 3 layers x (12 bf16 tensors of 20 x 8 + an f32 per token and head,
    # written and read)
    assert moved == 3 * (12 * 2 * 8 * 20 + 2 * 4 * 2 * 20)


def test_lm_attention_work_at_the_cell():
    """Two slots of 2048 tokens at Phi-3-mini widths: 2 * 3072 * 2049
    operations a token forward, three forwards, 4096 tokens; twelve bf16
    tensors of 2048 x 3072 a slot."""
    cfg = harness.read_json(ROOT, "bench/configs/phi3-mini-3.8b-1L.json")
    ops, moved = flops("lm").attention_work(cfg, 2, 2048)
    assert ops == 3 * 2 * 3072 * 2049 * 4096
    assert ops == pytest.approx(154.7e9, rel=1e-3)
    assert moved == 12 * 2 * 2048 * 3072 * 2 + 2 * 4 * 32 * 4096
    assert moved == pytest.approx(302e6, rel=5e-3)


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
