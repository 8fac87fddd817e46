"""Config dataclasses for models, shapes, training lanes, and meshes.

Every assigned architecture is expressed as a ``ModelConfig``; the four
assigned input shapes are ``ShapeConfig``s.  A ``Cell`` = (arch, shape) is
the unit of the dry-run matrix.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

# Block kinds used in ``block_pattern`` (one scan period).
ATTN = "attn"
MAMBA = "mamba"
RWKV = "rwkv"


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # -- attention details --
    qk_norm: bool = False
    sliding_window: int = 0          # 0 = full attention
    rope_theta: float = 1_000_000.0
    # -- latent attention (MLA, DeepSeek-V2): kv_lora_rank > 0 selects it;
    #    queries and keys take qk_nope + qk_rope dims a head, values v_head --
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # -- YaRN RoPE scaling (yarn_factor 0 = plain RoPE) --
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # -- MoE (dropless: every routed slot of a held expert is computed) --
    num_experts: int = 0             # the router's outputs
    experts_per_token: int = 0
    moe_d_ff: int = 0                # routed expert width (0 = d_ff)
    num_shared_experts: int = 0      # one SwiGLU of width shared * moe_d_ff
    norm_topk_prob: bool = True      # renormalise the top-k gates
    routed_scaling: float = 1.0      # gates times this when not renormalised
    # this chip's share: experts [experts_first, experts_first + experts_held)
    # of the router's num_experts (0 = all of them)
    experts_held: int = 0
    experts_first: int = 0
    first_dense_layers: int = 0      # dense layers before the MoE periods
    # -- block pattern: one scan period; default = all-attention --
    block_pattern: Tuple[str, ...] = ()
    # -- MoE interleave within a period: indices of MoE FFN positions.
    #    Empty + num_experts>0 means "every layer is MoE".
    moe_every: int = 1               # FFN is MoE when (layer_idx % moe_every)==moe_offset
    moe_offset: int = 0
    # -- SSM (mamba / rwkv6) --
    ssm_state_dim: int = 16          # mamba N
    ssm_expand: int = 2              # mamba d_inner = expand * d_model
    ssm_conv_width: int = 4
    rwkv_head_dim: int = 64
    # -- encoder-decoder (whisper) --
    encoder_layers: int = 0
    encoder_seq: int = 0             # precomputed frame embeddings (stub frontend)
    # -- VLM (llava) --
    num_image_tokens: int = 0        # precomputed patch embeddings (stub frontend)
    # -- misc --
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # long-context capable (sub-quadratic attention path): drives long_500k
    subquadratic: bool = False
    notes: str = ""

    # ---- derived ----
    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def pattern(self) -> Tuple[str, ...]:
        return self.block_pattern if self.block_pattern else (ATTN,)

    @property
    def num_periods(self) -> int:
        """Periods of the scanned stack (the leading dense layers apart)."""
        p = len(self.pattern)
        n = self.num_layers - self.first_dense_layers
        if n % p:
            raise ValueError(
                f"{self.name}: num_layers={self.num_layers} less "
                f"{self.first_dense_layers} leading dense is not a "
                f"multiple of the {p}-block pattern")
        return n // p

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.num_experts

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def padded_vocab(self) -> int:
        return pad_to(self.vocab_size, 256)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    def param_count(self, active_only: bool = False) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6·N·D)."""
        d, ff, V = self.d_model, self.d_ff, self.padded_vocab
        n = V * d                                    # embed
        if not self.tie_embeddings:
            n += V * d                               # unembed
        hd = self.head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd \
            + self.num_heads * hd * d
        if self.is_mla:
            H, r = self.num_heads, self.kv_lora_rank
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            attn = d * H * qk + d * (r + self.qk_rope_head_dim) \
                + r * H * (self.qk_nope_head_dim + self.v_head_dim) \
                + H * self.v_head_dim * d
        dense_ffn = 3 * d * ff                       # SwiGLU
        if self.is_moe:
            e = self.experts_per_token if active_only else self.held_experts
            eff = self.expert_d_ff
            moe_ffn = e * 3 * d * eff + d * self.num_experts  # + router
            moe_ffn += 3 * d * eff * self.num_shared_experts
        else:
            moe_ffn = dense_ffn
        d_inner = self.ssm_expand * d
        mamba = (d * 2 * d_inner                     # in_proj (x, z)
                 + d_inner * self.ssm_conv_width     # conv
                 + d_inner * (self.ssm_state_dim * 2 + d // 16)  # B,C,dt proj
                 + (d // 16) * d_inner               # dt up
                 + d_inner * self.ssm_state_dim      # A
                 + d_inner * d)                      # out proj
        # rwkv6: time-mix ~5 d² (r,k,v,g,o) + channel-mix (k: d->ff, v: ff->d, r: d->d)
        rwkv = 5 * d * d + (d * self.d_ff + self.d_ff * d + d * d)
        per_layer = self.first_dense_layers * (attn + dense_ffn)
        for li in range(self.num_layers - self.first_dense_layers):
            kind = self.pattern[li % len(self.pattern)]
            if kind == ATTN:
                per_layer += attn
                per_layer += moe_ffn if (self.is_moe and li % self.moe_every == self.moe_offset) else dense_ffn
            elif kind == MAMBA:
                per_layer += mamba
                per_layer += moe_ffn if (self.is_moe and li % self.moe_every == self.moe_offset) else dense_ffn
            elif kind == RWKV:
                per_layer += rwkv
        n += per_layer
        if self.encoder_layers:
            n += self.encoder_layers * (attn + dense_ffn)   # encoder blocks
            n += self.num_layers * attn                     # cross-attention
        return n


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode
    long_context: bool = False


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode", long_context=True)
SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclass(frozen=True)
class LaneConfig:
    """Training-lane hyperparameters (the paper's knobs)."""
    lane: str = "elastic_zo"          # full_bp | full_zo | elastic_zo | elastic_zo_int8
    bp_tail_layers: int = 1           # K;  C = L - K  (paper: last 1-2 FC layers)
    bp_unembed: bool = True           # LM head trained via BP (part of the tail)
    zo_eps: float = 1e-3
    zo_num_probes: int = 1            # antithetic pairs (multi-probe variance reduction)
    zo_clip: float = 100.0            # g-clipping (paper: clip to [-g_clip, g_clip])
    learning_rate: float = 1e-2
    tail_learning_rate: Optional[float] = None
    # the paper's schedule: lr *= factor every `every` steps (0 = constant)
    lr_decay_factor: float = 1.0
    lr_decay_every: int = 0
    bp_grad_mode: str = "avg_perturbed"   # avg_perturbed (Alg.1) | clean (3rd fwd)
    # no effect: every LM elastic_zo build perturbs inside the layer scan
    # (core/api.py); kept only for the benchmark's tests that still set it
    fused_probes: bool = False
    # int8 lane (Alg. 2)
    int8_loss_mode: str = "int"       # int (INT8*, Eq. 7-12) | float (sgn of fp32 diff)
    int8_r_max: int = 3
    int8_p_zero: float = 0.33
    int8_b_zo: int = 1
    int8_b_bp: int = 5
    # distributed
    allow_partial_probes: bool = True
    compress_tail_grads: bool = False


def pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    pattern = cfg.pattern
    small = dict(
        num_layers=cfg.first_dense_layers
        + (len(pattern) if len(pattern) > 1 else 2),
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads < cfg.num_heads else 4,
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        num_experts=4 if cfg.num_experts else 0,
        experts_per_token=2 if cfg.num_experts else 0,
        ssm_state_dim=8,
        rwkv_head_dim=16,
        encoder_layers=2 if cfg.encoder_layers else 0,
        encoder_seq=16 if cfg.encoder_seq else 0,
        num_image_tokens=8 if cfg.num_image_tokens else 0,
        sliding_window=16 if cfg.sliding_window else 0,
        kv_lora_rank=32 if cfg.kv_lora_rank else 0,
        qk_nope_head_dim=16 if cfg.kv_lora_rank else 0,
        qk_rope_head_dim=8 if cfg.kv_lora_rank else 0,
        v_head_dim=16 if cfg.kv_lora_rank else 0,
        moe_d_ff=32 if cfg.moe_d_ff else 0,
        experts_held=0,
        experts_first=0,
        name=cfg.name + "-smoke",
    )
    small.update(overrides)
    return dataclasses.replace(cfg, **small)
