"""Configuration dataclasses for the model zoo, input shapes, and runtime.

Every assigned architecture gets a ``ModelConfig`` in ``src/repro/configs/<id>.py``
citing its source. ``reduced()`` returns the CPU smoke-test variant of the same
family (<=2 layers, d_model<=512, <=4 experts) used by per-arch smoke tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Block descriptors
# ---------------------------------------------------------------------------
# A model is a repeated *pattern* of (mixer, ffn) blocks, scanned over
# ``n_repeat`` repetitions (scan-over-layers keeps compile time O(1) in depth).
#   mixer: 'attn' | 'attn_local' | 'attn_global' | 'mla' | 'mamba' | 'mlstm'
#          | 'slstm'
#   ffn:   'mlp' | 'moe' | None
# ``first_dense`` leading (pattern[0] mixer, 'mlp') layers run ahead of the
# scanned pattern (DeepSeek-V3's ``first_k_dense_replace``).
BlockSpec = Tuple[str, Optional[str]]


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    source: str                      # citation for the assigned config
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # decoder block pattern (repeated n_layers/len(pattern) times)
    pattern: Tuple[BlockSpec, ...] = (("attn", "mlp"),)

    # attention
    rope_theta: float = 10000.0
    use_rope: bool = True
    window: int = 0                  # sliding window size for 'attn_local' (0 = full)
    attn_softcap: float = 0.0        # gemma2-style logit soft capping
    final_softcap: float = 0.0
    attn_chunk: int = 512            # kv chunk for flash-style chunked attention

    # latent attention (mixer 'mla', DeepSeek-V2/V3): K and V come from a
    # normed latent of width kv_lora_rank; q/k heads have qk_nope_head_dim
    # position-free dims plus qk_rope_head_dim RoPE dims (one RoPE key head
    # shared by all heads); v heads have v_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                # expert hidden size (0 -> d_ff)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_impl: str = "psum"           # psum (baseline) | a2a (perf iteration)
    router: str = "softmax"          # softmax (+ Switch aux) | sigmoid (+ bias)
    routed_scale: float = 1.0        # gates x this (routed_scaling_factor)
    n_shared_experts: int = 0        # one shared MLP of n x moe_d_ff, every token
    experts_held: int = 0            # experts this device holds (0 -> all)
    expert_offset: int = 0           # first expert id held
    remat_moe: bool = False          # recompute each MoE layer in the
                                     # backward (its T*k-row buffers)

    # SSM (mamba)
    ssm_d_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0             # 0 -> ceil(d_model / 16)

    # xLSTM
    xlstm_pf_mlstm: float = 2.0      # projection factor of the mLSTM block
    xlstm_pf_slstm: float = 4.0 / 3.0

    # encoder (enc-dec families); encoder reuses d_model/n_heads
    n_enc_layers: int = 0
    enc_seq: int = 0                 # fixed encoder sequence (whisper: 1500 frames)

    # VLM frontend stub
    n_image_tokens: int = 0

    # norms / activations
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    norm_eps: float = 1e-6
    norm_f32: bool = True            # False: norm stats in input dtype (perf)
    seq_parallel: bool = False       # shard residual stream over 'model' (SP)
    act: str = "silu"                # silu (SwiGLU) | gelu (plain MLP, whisper)
    tie_embeddings: bool = False
    scale_embed: bool = False        # gemma2: embeddings scaled by sqrt(d_model)
    abs_pos: bool = False            # whisper: sinusoidal absolute positions

    # long-context variant: sliding window used when serving long_500k on a
    # full-attention arch (documented deviation; 0 = native support or skip)
    long_context_window: int = 0

    first_dense: int = 0             # leading dense layers (see BlockSpec)

    dtype: str = "bfloat16"
    use_pallas: bool = False         # kernels are TPU-targeted; refs used on CPU

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        assert (self.n_layers - self.first_dense) % len(self.pattern) == 0, (
            f"{self.name}: n_layers={self.n_layers} less first_dense="
            f"{self.first_dense} not divisible by pattern length "
            f"{len(self.pattern)}")

    @property
    def n_repeat(self) -> int:
        return (self.n_layers - self.first_dense) // len(self.pattern)

    @property
    def first_pattern(self) -> Tuple[BlockSpec, ...]:
        """The block of the ``first_dense`` leading layers: the pattern's
        first mixer and a dense MLP."""
        return ((self.pattern[0][0], "mlp"),)

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.experts_held or self.n_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def attention_free(self) -> bool:
        return not any(m.startswith("attn") for m, _ in self.pattern)

    @property
    def subquadratic(self) -> bool:
        """True if every attention block is windowed or the arch is SSM/hybrid
        with at most windowed attention -> native long-context support."""
        for mixer, _ in self.pattern:
            if mixer == "attn" or mixer == "attn_global":
                return False
        return True

    def supports_long_context(self) -> bool:
        return self.subquadratic or self.long_context_window > 0

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        from repro.models.schema import count_params
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.schema import count_params
        return count_params(self, active_only=True)

    # ------------------------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: same family/pattern, tiny dims."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, 2))
        while n_heads % n_kv:
            n_kv -= 1
        pattern = self.pattern[: max(1, min(2, len(self.pattern)))]
        # keep one of each distinct mixer so smoke covers every block type
        mixers = []
        seen = set()
        for blk in self.pattern:
            if blk[0] not in seen:
                seen.add(blk[0])
                mixers.append(blk)
        pattern = tuple(mixers[:4]) or pattern
        n_experts = min(self.n_experts, 4)
        first_dense = min(self.first_dense, 1)
        return dataclasses.replace(
            self,
            n_layers=first_dense + len(pattern),
            first_dense=first_dense,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=d_model // n_heads,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            moe_d_ff=min(self.expert_d_ff, 256) if self.n_experts else 0,
            vocab_size=min(self.vocab_size, 512),
            n_experts=n_experts,
            top_k=min(self.top_k, 2),
            experts_held=min(self.experts_held, n_experts),
            expert_offset=0,
            kv_lora_rank=min(self.kv_lora_rank, 32),
            qk_nope_head_dim=min(self.qk_nope_head_dim, 32),
            qk_rope_head_dim=min(self.qk_rope_head_dim, 16),
            v_head_dim=min(self.v_head_dim, 32),
            pattern=pattern,
            n_enc_layers=min(self.n_enc_layers, 1),
            enc_seq=min(self.enc_seq, 16) if self.enc_seq else 0,
            n_image_tokens=min(self.n_image_tokens, 8),
            window=min(self.window, 8) if self.window else 0,
            long_context_window=min(self.long_context_window, 8)
            if self.long_context_window else 0,
            attn_chunk=8,
            ssm_d_state=min(self.ssm_d_state, 8),
            ssm_dt_rank=8,
            dtype="float32",
        )


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


TRAIN_4K = InputShape("train_4k", 4096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32768, 128, "decode")
LONG_500K = InputShape("long_500k", 524288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


# ---------------------------------------------------------------------------
# Runtime / training config
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    optimizer: str = "rmsprop"       # rmsprop (paper: non-centered) | adamw
    rmsprop_decay: float = 0.99
    rmsprop_eps: float = 0.1
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    warmup_steps: int = 0
    seed: int = 0
    remat: str = "none"              # none | full | dots
    microbatch: int = 0              # 0 = no gradient accumulation
    zero_sharded_opt: bool = False   # shard optimizer accumulators over 'data'
    loss_chunk: int = 1024           # sequence chunking for vocab xent
