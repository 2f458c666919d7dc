"""Parameter schema: single source of truth for shapes, shardings, and init.

Every model is described by a nested dict of ``ParamDef`` leaves. From the
schema we derive, consistently:
  * materialized parameters        (``init_params``)
  * ShapeDtypeStruct stand-ins     (``abstract_params``, dry-run)
  * PartitionSpec pytree           (``param_specs``)
  * analytic parameter counts      (``count_params`` -> MODEL_FLOPS)

Sharding convention (mesh axes 'data'/'model', optional 'pod'):
  * FFN / expert hidden dims: sharded over 'model' (divisible for all archs).
  * Attention heads: sharded over 'model'; head counts not divisible by the
    model-axis size are PADDED up to the next multiple (the overhead shows up
    honestly in the MODEL_FLOPS/HLO_FLOPS roofline ratio; see DESIGN.md).
  * Vocab: embedding/unembedding sharded over 'model'.
  * Weights are replicated over 'data' and 'pod' (ZeRO sharding of optimizer
    accumulators is a separate, optional transform in repro.optim).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class ParamDef:
    shape: tuple
    spec: P = P()
    init: str = "normal"       # normal | zeros | ones | ssm_a | ssm_dt | eye
    scale: float = 0.0         # 0 -> 1/sqrt(fan_in)


def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@dataclass(frozen=True)
class Dims:
    """Derived dimensions under a given model-axis size (padding rule)."""
    cfg: ModelConfig
    model_shards: int = 1

    @property
    def hq(self) -> int:
        return _pad_to(self.cfg.n_heads, self.model_shards)

    @property
    def hkv(self) -> int:
        return _pad_to(self.cfg.n_kv_heads, self.model_shards)

    @property
    def hd(self) -> int:
        return self.cfg.head_dim

    @property
    def d(self) -> int:
        return self.cfg.d_model

    @property
    def v(self) -> int:
        # vocab padded to the model-axis size (embedding/unembedding are
        # vocab-parallel); padded logits are masked to -inf in the loss
        return _pad_to(self.cfg.vocab_size, self.model_shards)


# ---------------------------------------------------------------------------
# per-block schemas
# ---------------------------------------------------------------------------
def _norm_schema(cfg: ModelConfig, name: str = "norm") -> dict:
    d = {f"{name}_scale": ParamDef((cfg.d_model,), P(), "ones")}
    if cfg.norm == "layernorm":
        d[f"{name}_bias"] = ParamDef((cfg.d_model,), P(), "zeros")
    return d


def attn_schema(cfg: ModelConfig, dims: Dims, cross: bool = False) -> dict:
    hq, hkv, hd, d = dims.hq, dims.hkv, dims.hd, dims.d
    sch = {
        "wq": ParamDef((d, hq * hd), P(None, "model")),
        "wk": ParamDef((d, hkv * hd), P(None, "model")),
        "wv": ParamDef((d, hkv * hd), P(None, "model")),
        "wo": ParamDef((hq * hd, d), P("model", None)),
    }
    sch.update(_norm_schema(cfg))
    if cross:
        sch.update({
            "c_wq": ParamDef((d, hq * hd), P(None, "model")),
            "c_wk": ParamDef((d, hkv * hd), P(None, "model")),
            "c_wv": ParamDef((d, hkv * hd), P(None, "model")),
            "c_wo": ParamDef((hq * hd, d), P("model", None)),
        })
        sch.update({f"c_{k}": v for k, v in _norm_schema(cfg).items()})
    return sch


def mla_schema(cfg: ModelConfig, dims: Dims) -> dict:
    """Latent attention (``layers.mla_block``): heads are not padded."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    sch = {
        "wq": ParamDef((d, h * cfg.qk_head_dim), P(None, "model")),
        "wkv_a": ParamDef((d, r + cfg.qk_rope_head_dim), P()),
        "wkv_b": ParamDef((r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                          P(None, "model")),
        "wo": ParamDef((h * cfg.v_head_dim, d), P("model", None)),
        "kv_norm_scale": ParamDef((r,), P(), "ones"),
    }
    sch.update(_norm_schema(cfg))
    return sch


def mlp_schema(cfg: ModelConfig, dims: Dims) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    sch = {
        "w_up": ParamDef((d, f), P(None, "model")),
        "w_down": ParamDef((f, d), P("model", None)),
    }
    if cfg.act == "silu":  # SwiGLU
        sch["w_gate"] = ParamDef((d, f), P(None, "model"))
    sch.update(_norm_schema(cfg))
    return sch


def moe_schema(cfg: ModelConfig, dims: Dims) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_held
    if e >= dims.model_shards and e % max(dims.model_shards, 1) == 0:
        # expert-parallel: experts sharded over 'model'
        espec3 = P("model", None, None)
        dspec3 = P("model", None, None)
    else:
        # tensor-parallel small-E path: d_ff sharded, experts replicated
        espec3 = P(None, None, "model")
        dspec3 = P(None, "model", None)
    sch = {
        "router": ParamDef((d, cfg.n_experts), P()),
        "we_up": ParamDef((e, d, f), espec3),
        "we_down": ParamDef((e, f, d), dspec3),
    }
    if cfg.act == "silu":
        sch["we_gate"] = ParamDef((e, d, f), espec3)
    if cfg.router == "sigmoid":
        sch["router_bias"] = ParamDef((cfg.n_experts,), P(), "zeros")
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        sch["ws_up"] = ParamDef((d, fs), P(None, "model"))
        sch["ws_down"] = ParamDef((fs, d), P("model", None))
        if cfg.act == "silu":
            sch["ws_gate"] = ParamDef((d, fs), P(None, "model"))
    sch.update(_norm_schema(cfg))
    return sch


def mamba_schema(cfg: ModelConfig, dims: Dims) -> dict:
    d = cfg.d_model
    di = cfg.ssm_d_inner
    st = cfg.ssm_d_state
    dtr = cfg.dt_rank
    sch = {
        "in_proj": ParamDef((d, 2 * di), P(None, "model")),
        "conv_w": ParamDef((cfg.ssm_conv, di), P(None, "model")),
        "conv_b": ParamDef((di,), P("model"), "zeros"),
        "x_proj": ParamDef((di, dtr + 2 * st), P("model", None)),
        "dt_proj": ParamDef((dtr, di), P(None, "model")),
        "dt_bias": ParamDef((di,), P("model"), "ssm_dt"),
        "a_log": ParamDef((di, st), P("model", None), "ssm_a"),
        "d_skip": ParamDef((di,), P("model"), "ones"),
        "out_proj": ParamDef((di, d), P("model", None)),
    }
    sch.update(_norm_schema(cfg))
    return sch


def mlstm_schema(cfg: ModelConfig, dims: Dims) -> dict:
    # xLSTM is deployed data-parallel-only (1.3B params replicate comfortably);
    # di is padded to head granularity, not to the model-axis size.
    d = cfg.d_model
    di = _pad_to(int(cfg.xlstm_pf_mlstm * d), cfg.n_heads)
    h = cfg.n_heads
    sch = {
        "up_proj": ParamDef((d, 2 * di), P()),
        "wq": ParamDef((h, di // h, di // h), P()),
        "wk": ParamDef((h, di // h, di // h), P()),
        "wv": ParamDef((h, di // h, di // h), P()),
        "w_igate": ParamDef((di, h), P()),
        "b_igate": ParamDef((h,), P(), "zeros"),
        "w_fgate": ParamDef((di, h), P()),
        "b_fgate": ParamDef((h,), P(), "ssm_dt"),
        "down_proj": ParamDef((di, d), P()),
    }
    sch.update(_norm_schema(cfg))
    return sch


def slstm_schema(cfg: ModelConfig, dims: Dims) -> dict:
    d = cfg.d_model
    h = cfg.n_heads
    hd = d // h
    fup = _pad_to(int(cfg.xlstm_pf_slstm * d), max(dims.model_shards, 1))
    sch = {
        # 4 gates (i, f, z, o): input weights + per-head recurrent blocks
        "w_gates": ParamDef((d, 4 * d), P()),
        "r_gates": ParamDef((h, hd, 4 * hd), P()),
        "b_gates": ParamDef((4 * d,), P(), "zeros"),
        # gated feed-forward (pf = 4/3)
        "w_up": ParamDef((d, fup), P(None, "model")),
        "w_gate": ParamDef((d, fup), P(None, "model")),
        "w_down": ParamDef((fup, d), P("model", None)),
    }
    sch.update(_norm_schema(cfg))
    return sch


_MIXER_SCHEMAS = {
    "attn": attn_schema,
    "attn_local": attn_schema,
    "attn_global": attn_schema,
    "mla": mla_schema,
    "mamba": mamba_schema,
    "mlstm": mlstm_schema,
    "slstm": slstm_schema,
}
_FFN_SCHEMAS = {"mlp": mlp_schema, "moe": moe_schema}


# ---------------------------------------------------------------------------
# whole-model schema
# ---------------------------------------------------------------------------
def _stack(sch: dict, n: int) -> dict:
    return {
        k: ParamDef((n,) + v.shape, P(*((None,) + tuple(v.spec))), v.init, v.scale)
        for k, v in sch.items()
    }


def _blocks_schema(cfg: ModelConfig, dims: Dims, pattern, n: int) -> dict:
    """``pattern``'s blocks, each stacked ``n`` deep."""
    out: dict = {}
    for i, (mixer, ffn) in enumerate(pattern):
        cross = cfg.is_encdec and mixer.startswith("attn")
        if mixer.startswith("attn"):
            out[f"b{i}_{mixer}"] = _stack(
                attn_schema(cfg, dims, cross=cross), n)
        else:
            out[f"b{i}_{mixer}"] = _stack(_MIXER_SCHEMAS[mixer](cfg, dims), n)
        if ffn:
            out[f"b{i}_{ffn}"] = _stack(_FFN_SCHEMAS[ffn](cfg, dims), n)
    return out


def model_schema(cfg: ModelConfig, model_shards: int = 1) -> dict:
    dims = Dims(cfg, model_shards)
    sch: dict = {
        "embed": ParamDef((dims.v, cfg.d_model), P("model", None), "normal",
                          1.0),
        "unembed": ParamDef((cfg.d_model, dims.v), P(None, "model")),
    }
    sch.update({f"final_{k}": v for k, v in _norm_schema(cfg).items()})

    sch["dec"] = _blocks_schema(cfg, dims, cfg.pattern, cfg.n_repeat)
    if cfg.first_dense:
        sch["first"] = _blocks_schema(cfg, dims, cfg.first_pattern,
                                      cfg.first_dense)

    if cfg.is_encdec:
        enc: dict = {
            "b0_attn": _stack(attn_schema(cfg, dims), cfg.n_enc_layers),
            "b0_mlp": _stack(mlp_schema(cfg, dims), cfg.n_enc_layers),
        }
        sch["enc"] = enc
        sch.update({f"enc_final_{k}": v for k, v in _norm_schema(cfg).items()})

    if cfg.family == "vlm":
        sch["img_proj"] = ParamDef((cfg.d_model, cfg.d_model), P(None, "model"))
    return sch


# ---------------------------------------------------------------------------
# derivations
# ---------------------------------------------------------------------------
def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _tree_map_defs(fn: Callable, sch):
    return jax.tree.map(fn, sch, is_leaf=_is_def)


def abstract_params(cfg: ModelConfig, model_shards: int = 1):
    dt = jnp.dtype(cfg.dtype)
    return _tree_map_defs(
        lambda d: jax.ShapeDtypeStruct(d.shape, dt), model_schema(cfg, model_shards))


def param_specs(cfg: ModelConfig, model_shards: int = 1):
    return _tree_map_defs(lambda d: d.spec, model_schema(cfg, model_shards))


def _init_leaf(d: ParamDef, key, dtype):
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "ssm_a":
        # mamba: A = -exp(a_log), a_log = log(1..d_state) broadcast
        st = d.shape[-1]
        a = jnp.log(jnp.arange(1, st + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, d.shape).astype(dtype)
    if d.init == "ssm_dt":
        return jnp.full(d.shape, math.log(math.e - 1), dtype)  # softplus^-1(1)
    scale = d.scale or 1.0 / math.sqrt(max(d.shape[0] if len(d.shape) == 1
                                           else d.shape[-2], 1))
    return (scale * jax.random.normal(key, d.shape)).astype(dtype)


def init_params(cfg: ModelConfig, rng, model_shards: int = 1):
    sch = model_schema(cfg, model_shards)
    leaves, treedef = jax.tree.flatten(sch, is_leaf=_is_def)
    keys = jax.random.split(rng, len(leaves))
    dt = jnp.dtype(cfg.dtype)
    out = [_init_leaf(d, k, dt) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def count_params(cfg: ModelConfig, active_only: bool = False,
                 model_shards: int = 1) -> int:
    sch = model_schema(cfg, model_shards)
    total = 0
    for path, d in jax.tree.flatten_with_path(sch, is_leaf=_is_def)[0]:
        n = int(np.prod(d.shape))
        keys = "/".join(str(getattr(p, "key", p)) for p in path)
        if active_only and "we_" in keys and cfg.n_experts:
            n = n * cfg.top_k // cfg.n_experts
        total += n
    return total
