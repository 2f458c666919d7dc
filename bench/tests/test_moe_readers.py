"""The MoE LM kind's operations and bytes against hand counts, and
``expert_roofline.lm`` on a trace written by hand."""
import os
from types import SimpleNamespace

import pytest

from bench import harness, trace
from bench.tests.test_trace import _hlo, kernel_planes

ROOT = harness.ROOT


def flops():
    return harness.load_module(os.path.join(ROOT, "bench", "flops",
                                            "lm_moe.py"), "test_flops_lm_moe")


def reader():
    return harness.load_module(
        os.path.join(ROOT, "bench", "metrics", "expert_roofline.lm.py"),
        "bench_metric_expert_roofline_lm")


TINY = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 4,
        "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 3,
        "intermediate_size": 16, "moe_intermediate_size": 4,
        "n_routed_experts": 2, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "first_k_dense_replace": 1,
        "num_hidden_layers": 3, "vocab_size": 32,
        "published": {"n_routed_experts": 8}}


def test_moe_operations_and_bytes_by_hand():
    f = flops()
    # W_q 8 x 2*5, W_kv_a 8 x (4+2), W_kv_b 4 x 2*(3+3), W_o 2*3 x 8
    assert f.mla_params(TINY) == 80 + 48 + 48 + 48
    assert f.expert_params(TINY) == 3 * 8 * 4
    # 2 of 8 experts per token, 2 held: half an expert a token
    assert f.experts_per_token(TINY) == 0.5
    # 3 MLA; 1 dense MLP 3*8*16; 2 MoE of router 8*8, shared 96 and half
    # an expert 48; head 8*32
    assert f.matmul_params(TINY) == 3 * 224 + 384 + 2 * (64 + 96 + 48) + 256
    # plus the router bias 8 and 2 held experts a MoE layer, the embedding,
    # norms (2*8 + 4) a layer and the final 8
    assert f.all_params(TINY) == (3 * 224 + 384 + 2 * (64 + 8 + 96 + 192)
                                  + 2 * 256 + 3 * 20 + 8)
    # causal attention at seq 4: 2 * 2 heads * (3+2+3) * 5/2 a layer
    assert f.flops_per_token(TINY, 4) == pytest.approx(
        3 * (2 * f.matmul_params(TINY) + 3 * 2 * 2 * 8 * 5 / 2))
    assert f.update_bytes(TINY) == f.all_params(TINY) * 26


def test_moe_attention_and_expert_work_by_hand():
    f = flops()
    ops, moved = f.attention_work(TINY, 5, 4)
    # 3 layers x 3 forwards x 2 * 2 * 8 * 5 / 2 a token x 20 tokens
    assert ops == 3 * 3 * 80 * 20
    # per head and token 6 q/k-wide and 6 v-wide bf16 tensors, and the
    # f32 log-sum-exp written and read
    assert moved == 3 * (2 * 2 * (6 * 5 + 6 * 3) * 20 + 2 * 4 * 2 * 20)
    ops, moved = f.expert_work(TINY, 5, 4)
    # 20 tokens x 0.5 = 10 rows; 2 MoE layers of 3 products of 10 x 8 x 4,
    # three forwards
    assert ops == 2 * 3 * 2 * 10 * 96
    # 9 reads and writes of rows x d, rows x f and the held weights 2*8*4
    assert moved == 2 * 2 * 9 * (80 + 40 + 64)


def test_moe_work_at_the_cell():
    """Moonlight's share: 2.28 GFLOP a token, 28 % of it attention's."""
    cfg = harness.read_json(ROOT, "bench/configs/moonlight-16b-a3b-1d4m.json")
    f = flops()
    assert f.all_params(cfg) == 568_484_608
    per_token = f.flops_per_token(cfg, 8192)
    assert per_token == pytest.approx(2.283e9, rel=1e-3)
    ops, _ = f.attention_work(cfg, 1, 8192)
    assert ops / (per_token * 8192) == pytest.approx(0.2756, rel=1e-3)
    ops, moved = f.expert_work(cfg, 1, 8192)
    # 6144 rows a layer, 4 layers, 3 products of 2048 x 1408, x3
    assert ops == 4 * 3 * 2 * 6144 * 3 * 2048 * 1408
    assert moved == 4 * 2 * 9 * (6144 * 2048 + 6144 * 1408 + 8 * 2048 * 1408)


def _ctx(reduced):
    cell = SimpleNamespace(
        config=harness.read_json(
            ROOT, "bench/configs/moonlight-16b-a3b-1d4m.json"),
        traffic=harness.read_json(ROOT, "bench/traffic/lm-ht1-8k.json"))
    return {"cell": cell, "trace": reduced, "flops": flops(),
            "peaks": harness.peaks(ROOT, "TPU v5 lite")}


GMM = _hlo("gmm.44", "bf16[49152,1408]{1,0:T(8,128)(2,1)}")
TGMM = _hlo("tgmm.13", "bf16[8,2048,1408]{2,1,0}")
# an op that takes a kernel's output in: named after it in its text only
NEAR = ("%fusion.12 = bf16[49152,1408]{1,0} fusion(%gmm.44, %gmm.45), "
        "kind=kLoop")


def test_expert_roofline_from_kernel_durations():
    ops = [(GMM, 2.0), (TGMM, 1.0), (NEAR, 0.5),
           ("%fusion.7 = bf16[8192,2048]", 5.0)] * 2
    reduced = trace.reduce_trace(kernel_planes(ops))
    assert trace.kernel_s(reduced["step_ops"], reader().KERNELS) == \
        pytest.approx(2 * 3e-3)
    # 1.2756 TFLOP at 197 TFLOP/s over 3 ms of kernels a step
    least = 4 * 3 * 2 * 6144 * 3 * 2048 * 1408 / 197e12
    assert reader().read(_ctx(reduced)) == pytest.approx(100 * least / 3e-3)
    assert reader().note(_ctx(reduced)).startswith("compute-bound")


def test_expert_roofline_is_nothing_without_grouped_matmuls():
    ops = [(_hlo("flash_mha_bwd_dq_block_q_major_512.1"), 1.0),
           ("%while.70 = (s32[]) while(%tuple)", 11.0)]
    reduced = trace.reduce_trace(kernel_planes(ops))
    assert reader().read(_ctx(reduced)) is None
    assert reader().read(_ctx(None)) is None
