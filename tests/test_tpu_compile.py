"""Ahead-of-time compiles for a described TPU v5e (``v5e:2x2``): the
engine's bucket steps and the Pallas kernels at published widths go
through the chip's own compiler here, with no chip attached. Nothing
runs, so these tests say nothing about results or times; they catch
what interpret mode cannot (unlowerable primitives, misaligned blocks,
programs that do not fit).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _bucket_args(objective, capacity, sharding):
    """Shapes of a bucket step's inputs: the stacked (learner, carry), one
    (capacity,) array per traced hyperparameter, and the active mask."""
    hp = {"t_max": 4, "loss_chunk": 32}
    slot = jax.eval_shape(objective.init_slot_state, jax.random.PRNGKey(0),
                          hp)
    stack = lambda x: _spec((capacity,) + x.shape, x.dtype, sharding)
    learner, carry = (jax.tree.map(stack, t) for t in slot)
    n_traced = len(objective.hparam_spec().traced)
    hyper = [_spec((capacity,), jnp.float32, sharding)] * n_traced
    return (learner, carry, *hyper, _spec((capacity,), jnp.bool_, sharding))


def _compile_bucket(objective, key, capacity, sharding, mesh=None):
    from repro.population.engine import _bucket_step
    step = _bucket_step(objective, key, capacity, mesh)
    return step.lower(*_bucket_args(objective, capacity, sharding)).compile()


def test_ga3c_bucket_step_compiles_for_one_chip(one_chip):
    from repro.population.objectives.ga3c import GA3CObjective
    compiled = _compile_bucket(GA3CObjective("pong", n_envs=16), 4, 2,
                               one_chip)
    assert compiled.memory_analysis() is not None


def test_lm_bucket_step_compiles_for_one_chip(one_chip):
    from repro.population.objectives.lm import LMObjective
    compiled = _compile_bucket(LMObjective("yi-9b"), 32, 2, one_chip)
    assert compiled.memory_analysis() is not None


def test_ga3c_bucket_step_shard_maps_over_four_chips(topo):
    from repro.population.objectives.ga3c import GA3CObjective
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("slots", "data"))
    sharding = NamedSharding(mesh, PartitionSpec("slots"))
    compiled = _compile_bucket(GA3CObjective("pong", n_envs=16), 4, 8,
                               sharding, mesh)
    text = compiled.as_text()
    # trials are independent: the sharded step needs no collective
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute"):
        assert op not in text, op


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_yi_9b_widths(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    q = _spec((1, 4096, 32, 128), jnp.bfloat16, one_chip)
    kv = _spec((1, 4096, 4, 128), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention.lower(q, kv, kv).compile())


def test_rmsnorm_compiles_at_d_model_4096(one_chip):
    from repro.kernels.rmsnorm.ops import rmsnorm
    x = _spec((4096, 4096), jnp.bfloat16, one_chip)
    scale = _spec((4096,), jnp.bfloat16, one_chip)
    _assert_kernel(rmsnorm.lower(x, scale).compile())


def test_gmm_compiles_at_published_expert_width(one_chip):
    from repro.kernels.gmm.gmm import gmm_pallas
    # mixtral/jamba expert FFN: D 4096 -> F 14336, 8 experts
    x = _spec((1024, 4096), jnp.bfloat16, one_chip)
    w = _spec((8, 4096, 14336), jnp.bfloat16, one_chip)
    tiles = _spec((1024 // 128,), jnp.int32, one_chip)
    _assert_kernel(jax.jit(gmm_pallas).lower(x, w, tiles).compile())


def test_selective_scan_compiles_at_jamba_widths(one_chip):
    from repro.kernels.selective_scan.ops import selective_scan
    B, S, di, st = 1, 2048, 8192, 16      # jamba-v0.1: d_inner 8192
    f32 = jnp.float32
    seq = _spec((B, S, di), f32, one_chip)
    bc = _spec((B, S, st), f32, one_chip)
    compiled = selective_scan.lower(
        seq, seq, _spec((di, st), f32, one_chip), bc, bc,
        _spec((di,), f32, one_chip), _spec((B, di, st), f32, one_chip),
        bs=128).compile()
    _assert_kernel(compiled)
