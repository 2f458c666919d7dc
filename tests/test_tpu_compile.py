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
import dataclasses
import json
import os
import re

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


# instructions that hand a value on without computing it
_PASS_ON = ("get-tuple-element", "bitcast", "copy", "copy-start",
            "copy-done")


def _entry(text):
    """The entry computation of an optimized HLO text: each instruction's
    name mapped to (opcode, operand names, the whole line), and the name
    of its root."""
    body = text[text.index("\nENTRY "):]
    body = body[:body.index("\n}\n")]
    instrs, root = {}, None
    for line in body.splitlines()[1:]:
        m = re.match(r"\s*(ROOT )?%(\S+) = .*?\b([a-z][a-z-]*)\((.*?)\)"
                     r"(, |$)", line)
        if m:
            instrs[m.group(2)] = (m.group(3),
                                  re.findall(r"%([\w.-]+)", m.group(4)),
                                  line)
            root = m.group(2) if m.group(1) else root
    return instrs, root


def _writers(text):
    """For each output of the entry computation, in order, the
    instruction that computes it (past those that only hand it on)."""
    instrs, root = _entry(text)
    opcode, outputs, _ = instrs[root]
    assert opcode == "tuple", opcode
    found = []
    for name in outputs:
        while instrs[name][0] in _PASS_ON:
            name = instrs[name][1][0]
        found.append(name)
    return found, instrs


def test_adamw_writes_each_leaf_in_one_fusion(one_chip):
    """The LM bucket step at reduced widths, with the benchmark's bfloat16
    weights, at capacity 2: each learner leaf's parameter and both AdamW
    moments are written by one fusion, the update under the
    ``optim.update`` scope, so the update reads and writes each leaf once.
    The engine's select of the step's outputs splits that pass in two
    (the moments, then the parameter with them recomputed), which is why
    the step freezes masked slots itself."""
    from repro.population.objectives.lm import LMObjective
    objective = LMObjective("yi-9b")
    objective.cfg = dataclasses.replace(objective.cfg, dtype="bfloat16")
    compiled = _compile_bucket(objective, 32, 2, one_chip)
    text = compiled.as_text()
    writers, instrs = _writers(text)
    n = len(jax.tree.leaves(jax.eval_shape(
        objective.init_slot_state, jax.random.PRNGKey(0), {})[0][0]))
    params, m, v = writers[:n], writers[n + 1:2 * n + 1], \
        writers[2 * n + 1:3 * n + 1]
    for p_by, m_by, v_by in zip(params, m, v):
        opcode, _, line = instrs[m_by]
        called = re.search(r"calls=%([\w.-]+)", line).group(1)
        fused = text[text.index(f"\n%{called} "):]
        assert opcode == "fusion"
        assert "optim.update" in fused[:fused.index("\n}\n")]
        assert p_by == m_by == v_by, (p_by, m_by, v_by)


# the held experts' grouped product on the chip (``models/moe.py``)
GROUPED_MATMUL = "gmm"


def _phi3_1l_objective(seq):
    """The benchmark's ``phi3-mini-3.8b-1L`` objective at ``seq`` tokens."""
    from bench.kinds.lm import build_objective
    path = os.path.join(os.path.dirname(__file__), os.pardir, "bench",
                        "configs", "phi3-mini-3.8b-1L.json")
    with open(path) as f:
        config = json.load(f)
    return build_objective(config, {"batch": 1, "seq": seq})


@pytest.fixture(scope="module")
def phi3_1l_step(one_chip):
    """The benchmark's ``lm.phi3.ht2`` bucket step: two slots of 1 x 2048
    tokens at Phi-3-mini widths, loss chunk 1024."""
    return _compile_bucket(_phi3_1l_objective(2048), 1024, 2, one_chip)


def test_phi3_1l_bucket_step_runs_the_flash_kernel(phi3_1l_step):
    """Two slots of 1 x 2048 tokens at Phi-3-mini widths (32 heads of 96,
    MHA, causal): attention takes the Pallas kernel, whose working set is
    a fraction of the chunked scan's float32 score blocks (6.65 GiB of
    temporaries with the scan)."""
    _assert_kernel(phi3_1l_step)
    assert phi3_1l_step.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30


def test_phi3_1l_loss_keeps_no_logit_stash(phi3_1l_step):
    """The LM head and loss (``train/steps.py:lm_loss``) compute each
    chunk's gradients in the forward scan. Autodiff through the scan kept
    every chunk's float32 exponentials, f32[2 chunks, 2 slots, 1, 1024,
    32064], for a reverse loop, and its derivative of the gold-logit
    gather was a scatter; with both gone the step needs fewer temporaries
    than its 3,066,229,760 bytes."""
    text = phi3_1l_step.as_text()
    vocab, chunks = 32064, 2048 // 1024
    assert "loss.xent" in text
    for tuple_type in re.findall(r"= \((.*?)\) while\(", text):
        for dims in re.findall(r"f32\[([\d,]+)\]", tuple_type):
            dims = [int(d) for d in dims.split(",")]
            assert not (dims[0] == chunks and dims[-1] == vocab), dims
    for line in text.splitlines():
        assert not (" scatter(" in line and "loss.xent" in line), line
    assert phi3_1l_step.memory_analysis().temp_size_in_bytes \
        < 3_066_229_760


def test_moonlight_cell_step_runs_flash_and_grouped_matmul_kernels(
        one_chip):
    """The benchmark's ``lm.moonlight.ht1`` step at its own size: one slot
    of 1 x 8192 tokens through Moonlight-16B-A3B's leading dense layer and
    four MoE layers on one chip's share. Latent attention, padded to one
    head width of 256, takes the flash kernels forward and backward; the
    held experts take the grouped-matmul kernels; and the step with its
    state fits the chip's 15.75 GiB."""
    from bench import harness
    cell = harness.Cell("lm.moonlight.ht1")
    objective = cell.kind_module().build_objective(cell.config,
                                                   cell.traffic)
    compiled = _compile_bucket(objective, 1024, 1, one_chip)
    text = compiled.as_text()
    for kernel in ("flash_attention", "flash_mha_bwd_dkv", "flash_mha_bwd_dq",
                   GROUPED_MATMUL):
        assert f"%{kernel}" in text or f"_{kernel}" in text, kernel
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * 2 ** 30


def test_held_experts_take_the_grouped_matmul_kernel_under_vmap(one_chip):
    """Two slots of the reduced Moonlight model: the held experts' grouped
    products run the Pallas kernels batched over the slot axis (XLA's
    ``ragged-dot`` is refused there with a batch dimension). Batched, the
    kernels' instructions lose their names (``closed_call.N``), so they
    are counted: 6 flash calls (the dense and the MoE layer, forward and
    two backward) and the MoE layer's 9 grouped products (3 forward, 3
    for the rows' gradient, 3 for the weights')."""
    from repro.population.objectives.lm import LMObjective
    compiled = _compile_bucket(LMObjective("moonlight-16b-a3b", batch=1,
                                           seq=512), 512, 2, one_chip)
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert text.count('custom_call_target="tpu_custom_call"') == 6 + 9


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
