"""The LM kind of DeepSeek-V3-style mixtures of experts: the program's
``LMObjective`` around latent attention, a leading dense layer, and MoE
layers with a sigmoid router, shared experts and a held share of the
routed experts.

``model_config`` builds the program's ``ModelConfig`` from the file: the
router spans the published expert count, this chip holds the file's
``n_routed_experts`` of them from ``expert_offset``, and the vocabulary is
the file's slice. Every field the file states and the program does not
take as a number is checked against what the program runs. The rest is
``kinds/lm.py``'s.
"""
from __future__ import annotations

import dataclasses
import os

from bench import harness

lm = harness.load_module(os.path.join(os.path.dirname(__file__), "lm.py"),
                         "bench_kind_lm")
engine_kwargs, params, grad_moment, counter, loss_sum = (
    lm.engine_kwargs, lm.params, lm.grad_moment, lm.counter, lm.loss_sum)


def model_config(config: dict):
    """The program's ``ModelConfig`` for the file, checked field by field
    against what the file states."""
    from repro.configs.registry import get_config
    cfg = dataclasses.replace(
        get_config(config["program_arch"]),
        n_layers=config["num_hidden_layers"],
        first_dense=config["first_k_dense_replace"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        n_experts=config["published"]["n_routed_experts"],
        experts_held=config["n_routed_experts"],
        expert_offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        routed_scale=config["routed_scaling_factor"],
        norm_eps=config["rms_norm_eps"], dtype=config["torch_dtype"],
        remat_moe=config["remat"] == "moe")
    stated = {
        "norm": "rmsnorm", "act": config["hidden_act"],
        "tie": config["tie_word_embeddings"],
        "router": config["scoring_func"],
        "selection bias": config["topk_method"] == "noaux_tc",
        "expert groups": (config["n_group"], config["topk_group"]),
        "gates normed": config["norm_topk_prob"],
        "q latent": config["q_lora_rank"],
        "attention bias": config["attention_bias"],
        "pattern": (("mla", "moe"),) if config["moe_layer_freq"] == 1
        else None,
        "extra predict layers": config["num_nextn_predict_layers"],
        "aux coefficient": 0.0,
        "vocab offset": config["vocab_offset"],
        "window": 0,
    }
    runs = {
        "norm": cfg.norm, "act": cfg.act, "tie": cfg.tie_embeddings,
        "router": cfg.router, "selection bias": cfg.router == "sigmoid",
        "expert groups": (1, 1), "gates normed": True, "q latent": None,
        "attention bias": False, "pattern": cfg.pattern,
        "extra predict layers": 0, "aux coefficient": cfg.router_aux_coef,
        "vocab offset": 0, "window": cfg.window,
    }
    if stated != runs:
        diff = {k: (runs[k], stated[k]) for k in stated
                if stated[k] != runs[k]}
        raise ValueError(f"{config['name']}: the program runs, against "
                         f"what the configuration states: {diff}")
    return cfg


def build_objective(config: dict, traffic: dict):
    return lm.objective(model_config(config), config, traffic)
