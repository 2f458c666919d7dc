"""Operations and bytes of a DeepSeek-V3-style MoE LM training update on
one chip's share, reckoned from its shapes
(``configs/moonlight-16b-a3b-1d4m.json``): what ``harness.py`` asks of a
kind's flops module, for latent attention, a leading dense layer and MoE
layers whose routed experts this chip holds ``n_routed_experts`` of
``published.n_routed_experts``; and ``expert_work``, the held experts'
grouped matmuls.

Operations are the model's, as in ``flops/lm.py``: a multiply-add counts
two, the forward and the backward count three forwards, causal attention
counts only the keys a query sees, nothing recomputed is counted, and the
heads are at their native widths (no padding). The held experts see, at a
balanced load, ``num_experts_per_tok * n_routed_experts /
published.n_routed_experts`` assignments a token. Bytes of an update are
the least that it must move: each parameter read by the forward and the
backward, and the optimizer reading and writing the parameter and its two
moments, plus reading the gradient. Activations are not counted, so both
numbers are lower bounds of the work.
"""
from __future__ import annotations


def _widths(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _layers(cfg: dict):
    """(dense layers, MoE layers)."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def mla_params(cfg: dict) -> int:
    """One layer's latent attention projections: W_q, W_kv_a, W_kv_b,
    W_o."""
    d, H, r, dn, dr, dv = _widths(cfg)
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def expert_params(cfg: dict) -> int:
    """One routed expert's gate, up and down projections."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_per_token(cfg: dict) -> float:
    """Held experts a token takes at a balanced load."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])


def matmul_params(cfg: dict) -> float:
    """Parameters that multiply a token's activations: every layer's
    attention projections, the dense MLP, each MoE layer's router, shared
    experts and the held experts the token takes, and the LM head (the
    embedding is a lookup)."""
    d = cfg["hidden_size"]
    dense, moe = _layers(cfg)
    router = d * cfg["published"]["n_routed_experts"]
    shared = cfg["n_shared_experts"] * expert_params(cfg)
    routed = experts_per_token(cfg) * expert_params(cfg)
    return ((dense + moe) * mla_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + moe * (router + shared + routed)
            + d * cfg["vocab_size"])


def all_params(cfg: dict) -> int:
    """Every parameter this chip holds."""
    d, _, r, *_ = _widths(cfg)
    dense, moe = _layers(cfg)
    e_all = cfg["published"]["n_routed_experts"]
    mlp = 3 * d * cfg["intermediate_size"]
    moe_layer = (d * e_all + e_all
                 + (cfg["n_shared_experts"] + cfg["n_routed_experts"])
                 * expert_params(cfg))
    norms = (dense + moe) * (2 * d + r) + d
    return ((dense + moe) * mla_params(cfg) + dense * mlp + moe * moe_layer
            + 2 * d * cfg["vocab_size"] + norms)


def attention_fwd_per_token(cfg: dict, seq: int) -> float:
    """Forward operations of causal latent attention per token: scores at
    the q/k width, values at the v width."""
    _, H, _, dn, dr, dv = _widths(cfg)
    return 2 * H * (dn + dr + dv) * (seq + 1) / 2 * cfg["num_hidden_layers"]


def flops_per_token(cfg: dict, seq: int) -> float:
    """Training operations per token at sequence length ``seq``."""
    return 3 * (2 * matmul_params(cfg) + attention_fwd_per_token(cfg, seq))


def attention_work(cfg: dict, batch: int, seq: int):
    """(operations, bytes) of causal latent attention, forward and
    backward, over ``batch`` sequences of ``seq`` tokens, at the native
    widths. Bytes are the least the attention must move a layer, per head
    and token in bfloat16: the forward reads q, k (q/k width), v and
    writes the output (v width); the backward reads q, k, v, the output
    and its gradient and writes the gradients of q, k and v; plus each
    query's float32 log-sum-exp per head, written and read."""
    _, H, _, dn, dr, dv = _widths(cfg)
    tokens = batch * seq
    ops = 3 * attention_fwd_per_token(cfg, seq) * tokens
    tensors = 2 * H * (6 * (dn + dr) + 6 * dv) * tokens
    lse = 2 * 4 * H * tokens
    return ops, (tensors + lse) * cfg["num_hidden_layers"]


def expert_work(cfg: dict, batch: int, seq: int):
    """(operations, bytes) of the held experts' grouped matmuls, forward
    and backward, over ``batch`` sequences of ``seq`` tokens at a balanced
    load. Each MoE layer runs three grouped products forward (gate and up,
    rows x d by d x f; down, rows x f by f x d) and two for each backward
    (the rows' gradient and the weights'); each product reads its two
    operands and writes its result once, in bfloat16."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    _, moe = _layers(cfg)
    rows = batch * seq * experts_per_token(cfg)
    weights = cfg["n_routed_experts"] * d * f
    ops = 3 * 2 * rows * expert_params(cfg) * moe
    moved = 2 * 9 * (rows * d + rows * f + weights) * moe
    return ops, moved


def update_bytes(cfg: dict, param_bytes: int = 2,
                 moment_bytes: int = 4) -> float:
    """Bytes one slot's update must move, at the least."""
    n = all_params(cfg)
    forward_backward = 2 * param_bytes * n
    optimizer = n * (2 * param_bytes + 4 * moment_bytes + param_bytes)
    return forward_backward + optimizer
