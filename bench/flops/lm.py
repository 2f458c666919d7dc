"""Operations and bytes of a decoder LM training update, reckoned from
its shapes (``configs/phi3-mini-3.8b-1L.json``): what ``harness.py``
asks of a kind's flops module, for dense layers of multi-head or
grouped-query attention and a gated MLP.

Operations are the model's: a multiply-add counts two, the forward and
the backward count three forwards, causal attention counts only the
keys a query sees, and nothing recomputed is counted. Bytes of an
update are the least that it must move: each parameter read by the
forward and the backward, and the optimizer reading and writing the
parameter and its two moments, plus reading the gradient. Activations
are not counted, so both numbers are lower bounds of the work.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply activations: every layer's projections
    and the LM head (the embedding is a lookup)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    attn = d * d + 2 * d * kv + d * d
    mlp = 3 * d * f
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def all_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return matmul_params(cfg) + d * cfg["vocab_size"] + norms


def attention_fwd_per_token(cfg: dict, seq: int) -> float:
    """Forward operations of causal self-attention per token."""
    d = cfg["hidden_size"]
    # causal: query i sees i + 1 keys; scores and values, 2 ops a MAC
    return 2 * 2 * d * (seq + 1) / 2 * cfg["num_hidden_layers"]


def flops_per_token(cfg: dict, seq: int) -> float:
    """Training operations per token at sequence length ``seq``."""
    return 3 * (2 * matmul_params(cfg) + attention_fwd_per_token(cfg, seq))


def attention_work(cfg: dict, batch: int, seq: int):
    """(operations, bytes) of causal self-attention, forward and
    backward, over ``batch`` sequences of ``seq`` tokens. Operations as
    in ``flops_per_token``. Bytes are the least the attention must move
    a layer: the forward reads q, k, v and writes the output; the
    backward reads q, k, v, the output and its gradient and writes the
    gradients of q, k and v; twelve tensors of ``seq`` x width in the
    weights' bfloat16, plus each query's float32 log-sum-exp per head,
    written by the forward and read by the backward."""
    tokens = batch * seq
    ops = 3 * attention_fwd_per_token(cfg, seq) * tokens
    tensors = 12 * 2 * cfg["hidden_size"] * tokens
    lse = 2 * 4 * cfg["num_attention_heads"] * tokens
    return ops, (tensors + lse) * cfg["num_hidden_layers"]


def update_bytes(cfg: dict, param_bytes: int = 2,
                 moment_bytes: int = 4) -> float:
    """Bytes one slot's update must move, at the least."""
    n = all_params(cfg)
    forward_backward = 2 * param_bytes * n
    optimizer = n * (2 * param_bytes + 4 * moment_bytes + param_bytes)
    return forward_backward + optimizer
