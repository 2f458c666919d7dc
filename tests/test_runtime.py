"""Launch rules for running on TPU chips (``repro.launch.runtime`` and
``ProcessCluster.worker_envs``): one chip per JAX worker process, a refused
launch when workers outnumber chips, a compile cache placed from outside,
and kernels that never fall back to interpret mode by default."""
import importlib
import inspect
import json
import os
import subprocess
import sys
import time
import types

import pytest

from repro.core import executor
from repro.core.executor import ProcessCluster
from repro.core.hypertrick import RandomSearchPolicy
from repro.core.search_space import LogUniform, SearchSpace
from repro.launch import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RL = {"kind": "rl", "game": "pong"}


@pytest.fixture
def chips(monkeypatch):
    """Inject the host's chip count; JAX_PLATFORMS unset, as on a chip
    host."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    def set_count(n):
        monkeypatch.setattr(executor, "count_tpu_chips", lambda: n)
    return set_count


def test_each_jax_worker_gets_its_own_chip(chips):
    chips(4)
    envs = ProcessCluster(3, RL, slots=8).worker_envs()
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2"]
    for e in envs:
        assert e["JAX_PLATFORMS"] == "tpu"       # no fallback to the CPU
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["PYTHONPATH"].split(os.pathsep)[0] == os.path.join(REPO,
                                                                    "src")


@pytest.mark.parametrize("kind", ["rl", "lm"])
def test_more_jax_workers_than_chips_is_refused(chips, kind):
    chips(2)
    with pytest.raises(RuntimeError, match="need one TPU chip each.*has 2"):
        ProcessCluster(3, {"kind": kind}).worker_envs()


def test_refused_launch_fails_before_any_worker_starts(chips, monkeypatch):
    chips(1)
    started = []
    monkeypatch.setattr(executor.subprocess, "Popen",
                        lambda *a, **k: started.append(a))
    policy = RandomSearchPolicy(SearchSpace({"x": LogUniform(0.1, 1.0)}),
                                2, 1, seed=0)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="has 1"):
        ProcessCluster(2, RL).run(policy)
    assert time.monotonic() - t0 < 10.0 and not started


def test_synthetic_and_cpu_workers_are_not_restricted(chips, monkeypatch):
    chips(0)
    for env in ProcessCluster(3, {"kind": "synthetic"}).worker_envs():
        assert "TPU_VISIBLE_CHIPS" not in env
        assert "JAX_PLATFORMS" not in env
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for env in ProcessCluster(3, RL).worker_envs():
        assert "TPU_VISIBLE_CHIPS" not in env
        assert env["JAX_PLATFORMS"] == "cpu"


def test_chips_are_counted_from_device_nodes(monkeypatch):
    nodes = {"/dev/accel[0-9]*": [],
             "/dev/vfio/*": ["/dev/vfio/3", "/dev/vfio/5", "/dev/vfio/vfio"]}
    monkeypatch.setattr(runtime, "glob",
                        types.SimpleNamespace(glob=nodes.__getitem__))
    assert runtime.count_tpu_chips() == 2
    nodes["/dev/accel[0-9]*"] = ["/dev/accel0"]
    nodes["/dev/vfio/*"] = []
    assert runtime.count_tpu_chips() == 1


@pytest.fixture
def cache_config():
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield jax
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_set_means_nothing_set_in_code(monkeypatch,
                                                         tmp_path,
                                                         cache_config):
    jax = cache_config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


def test_compile_cache_defaults_to_fixed_path_in_checkout(monkeypatch,
                                                          cache_config):
    jax = cache_config
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.use_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # exported, so worker processes find the same directory
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
    assert runtime.use_compile_cache() == path


KERNEL_ENTRY_POINTS = [
    ("repro.kernels.flash_attention.ops", "flash_attention"),
    ("repro.kernels.flash_attention.flash_attention",
     "flash_attention_pallas"),
    ("repro.kernels.rmsnorm.ops", "rmsnorm"),
    ("repro.kernels.rmsnorm.rmsnorm", "rmsnorm_pallas"),
    ("repro.kernels.selective_scan.ops", "selective_scan"),
    ("repro.kernels.selective_scan.selective_scan", "selective_scan_pallas"),
    ("repro.kernels.gmm.ops", "gmm"),
    ("repro.kernels.gmm.gmm", "gmm_pallas"),
]


@pytest.mark.parametrize("module,name", KERNEL_ENTRY_POINTS)
def test_kernels_compile_for_the_device_by_default(module, name):
    fn = getattr(importlib.import_module(module), name)
    assert inspect.signature(fn).parameters["interpret"].default is False


def test_chip_smoke_fails_without_a_chip(tmp_path):
    """On a host with no TPU the smoke script exits non-zero and prints
    no result line."""
    if runtime.count_tpu_chips():
        pytest.skip("this host has TPU chips")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
