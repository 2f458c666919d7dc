"""The LM model kind: the program's ``LMObjective`` around the model
configuration of the file.

``LMObjective.__init__`` builds ``get_config(arch).reduced()``;
``objective`` runs it, then puts in its place the program's
``ModelConfig`` it is given and the data table at that vocabulary, drawn
as the program draws it. The engine, the step and the model code are the
program's own. ``model_config`` states this kind's configuration: the
registry's dense architecture at the file's widths, cut in depth. A kind
of the same family (``bench/kinds/<kind>.py``) builds its own
``ModelConfig`` and reuses ``objective``, ``params``, ``grad_moment``,
``counter`` and ``loss_sum`` from this module (see ``harness.py``).
"""
from __future__ import annotations

import dataclasses

import numpy as np


def model_config(config: dict):
    """The program's ``ModelConfig`` for the file, checked field by
    field against what the file states."""
    from repro.configs.registry import get_config
    heads = config["num_attention_heads"]
    cfg = dataclasses.replace(
        get_config(config["program_arch"]),
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=heads, n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // heads,
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=config["rope_theta"], dtype=config["torch_dtype"])
    stated = ("rmsnorm", config["hidden_act"], config["tie_word_embeddings"],
              0 if config["sliding_window"] is None
              else config["sliding_window"], (("attn", "mlp"),))
    runs = (cfg.norm, cfg.act, cfg.tie_embeddings, cfg.window, cfg.pattern)
    if stated != runs:
        raise ValueError(f"{config['name']}: the program runs {runs}, the "
                         f"configuration states {stated}")
    return cfg


def objective(cfg, config: dict, traffic: dict):
    """The program's ``LMObjective`` training the program's ``ModelConfig``
    ``cfg`` on the traffic's batch and sequence, its optimizer checked
    against what the file ``config`` states. ``cfg.name`` is an
    architecture of the program's registry, which ``LMObjective.__init__``
    looks up before ``cfg`` takes its place."""
    import jax.numpy as jnp
    from repro.population.objectives.lm import LMObjective

    class LMAtConfig(LMObjective):
        def __init__(self, cfg, batch, seq, data_seed):
            super().__init__(cfg.name, batch, seq, data_seed)
            self.cfg = cfg
            rng = np.random.default_rng(data_seed)
            self.table = jnp.asarray(
                rng.integers(0, cfg.vocab_size,
                             size=(cfg.vocab_size, 8)).astype(np.int32))

        def cache_key(self):
            return ("lm", self.cfg, self.batch, self.seq, self.data_seed)

    obj = LMAtConfig(cfg, int(traffic["batch"]), int(traffic["seq"]),
                     int(config["data_seed"]))
    tc = obj.tc
    stated = (config["optimizer"], config["adam_b1"], config["adam_b2"],
              config["weight_decay"])
    runs = (tc.optimizer, tc.adam_b1, tc.adam_b2, tc.weight_decay)
    if stated != runs:
        raise ValueError(f"{config['name']}: the program's optimizer is "
                         f"{runs}, the configuration states {stated}")
    return obj


def build_objective(config: dict, traffic: dict):
    return objective(model_config(config), config, traffic)


def engine_kwargs(config: dict, traffic: dict) -> dict:
    return {}


def params(learner):
    return learner[0]


def grad_moment(learner, config: dict):
    """AdamW's second moment after one step from zero is
    ``(1 - b2) g**2`` of the clipped gradient ``g``."""
    return learner[1].acc2, 1.0 / (1.0 - config["adam_b2"])


def counter(learner):
    return learner[1].step


def loss_sum(carry):
    """The carry sums ``-loss`` over the slot's updates."""
    return carry["loss_sum"]
