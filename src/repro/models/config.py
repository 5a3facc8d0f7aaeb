"""Unified model configuration covering all 10 assigned architectures.

One dataclass describes every LM-family member the framework supports:
dense GQA transformers, MoE, mixed local/global attention, hybrid
attention+SSM (Hymba), attention-free RWKV6, encoder-decoder (Seamless
backbone) and embedding-frontend VLM/audio stubs.

The exact assigned configs live in ``repro/configs/<arch>.py``; reduced
smoke-test variants are derived with ``.reduced()``.
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Sequence


class Family(str, enum.Enum):
    DENSE = "dense"
    MOE = "moe"
    ENCDEC = "encdec"  # audio: seamless backbone, frontend stubbed
    HYBRID = "hybrid"  # hymba: parallel attn + SSM heads
    SSM = "ssm"  # rwkv6: attention-free
    VLM = "vlm"  # internvl2: LM backbone, ViT frontend stubbed


# Marker for "global attention" entries in layer window patterns.
GLOBAL = -1


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family

    # Transformer trunk.
    num_layers: int
    d_model: int
    num_heads: int  # query heads (0 for pure-SSM rwkv6)
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # Attention details.
    qkv_bias: bool = False
    qk_norm: bool = False  # gemma3-style per-head RMSNorm on q/k
    scale_embeddings: bool = False  # gemma-style sqrt(d) embedding scale
    # Per-layer attention window pattern, cycled over layers.
    # GLOBAL means full causal attention; a positive int is an SWA window.
    window_pattern: tuple[int, ...] = (GLOBAL,)
    rope_theta_global: float = 1_000_000.0
    rope_theta_local: float = 10_000.0
    logit_softcap: float = 0.0  # gemma-style final-logit softcapping (0 = off)

    # MoE.
    num_experts: int = 0  # 0 => dense FFN
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25

    # SSM / hybrid.
    ssm_state: int = 0  # Mamba state size (hymba) or rwkv head state flag
    ssm_conv: int = 4
    ssm_dt_rank: int = 0  # 0 => d_model // 16

    # Encoder-decoder.
    num_encoder_layers: int = 0  # >0 only for ENCDEC

    # Frontend stubs (VLM / audio): fraction of the sequence that arrives as
    # precomputed embeddings rather than token ids.
    embed_frontend_fraction: float = 0.0

    # Norm/act details.
    rms_eps: float = 1e-6
    act: str = "silu"  # "silu" (SwiGLU) or "gelu" (GeGLU)
    tie_embeddings: bool = False

    # Dtypes.
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    # Runtime/optimization knobs (hillclimb surface; not architecture).
    attn_impl: str = "auto"  # "auto" | "xla" | "xla_chunked" | "flash"
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024
    remat: bool = True
    remat_policy: str = "nothing"  # "nothing" | "dots" | "none"
    loss_chunk: int = 1024  # sequence chunking for the CE loss (0 = off)
    scan_layers: bool = True
    # Nested remat-scan: checkpoint BLOCKS of this many layers instead of
    # every layer. Bounds autodiff-saved residuals to L/block carries plus
    # one block's transient recompute (0 = flat scan, checkpoint per layer).
    scan_block: int = 0
    # Split local/global KV-cache stacks for mixed-window archs (perf knob;
    # shrinks SWA-layer caches to the window size during decode).
    split_local_global_cache: bool = False

    def __post_init__(self):
        if self.family is not Family.SSM:
            assert self.num_heads > 0 and self.head_dim > 0
            assert self.num_heads % max(self.num_kv_heads, 1) == 0, (
                f"{self.name}: q heads {self.num_heads} must be a multiple of "
                f"kv heads {self.num_kv_heads}"
            )
        if self.family is Family.MOE:
            assert self.num_experts > 0 and self.experts_per_token > 0
        if self.family is Family.ENCDEC:
            assert self.num_encoder_layers > 0

    # ------------------------------------------------------------------ #
    # Derived quantities.
    # ------------------------------------------------------------------ #
    @property
    def padded_vocab(self) -> int:
        """Embedding/LM-head rows padded to a multiple of 128 so the vocab
        dim shards on any model-axis factor (hymba's 32001, internvl's
        92553 and seamless' 256206 are not 16-divisible). Logits over the
        pad are masked to -inf; the architecture's true vocab is
        ``vocab_size`` everywhere else."""
        return -(-self.vocab_size // 128) * 128

    @property
    def attn_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or max(self.d_model // 16, 1)

    @property
    def d_inner(self) -> int:
        """SSM inner width (hybrid family)."""
        return self.d_model

    def layer_windows(self) -> tuple[int, ...]:
        """Resolved per-layer window sizes, GLOBAL -> -1 sentinel kept."""
        pat = self.window_pattern
        return tuple(pat[i % len(pat)] for i in range(self.num_layers))

    def layer_period(self) -> int:
        """Smallest number of layers whose window pattern, repeated a
        whole number of times, gives the model's depth (1 for a uniform
        stack, the whole depth for hymba's first/middle/last global
        layers)."""
        windows = self.layer_windows()
        n = len(windows)
        return next(
            p for p in range(1, n + 1)
            if n % p == 0 and windows == windows[:p] * (n // p)
        )

    def with_depth(self, num_layers: int) -> "ModelConfig":
        """The same model cut to ``num_layers`` layers: every width kept,
        depth cut only in whole periods of the layer pattern, so each
        kind of layer stays present in its published ratio."""
        period = self.layer_period()
        if not 1 <= num_layers <= self.num_layers or num_layers % period:
            raise ValueError(
                f"{self.name}: a depth of {num_layers} layers is not a whole "
                f"number of {period}-layer pattern periods within its "
                f"{self.num_layers} layers"
            )
        return dataclasses.replace(self, num_layers=num_layers)

    def is_subquadratic(self) -> bool:
        """True if decode-state size is bounded (SWA/SSM/linear-attention),
        i.e. the arch qualifies for the long_500k cell (DESIGN.md §5)."""
        if self.family is Family.SSM:
            return True
        if self.family is Family.ENCDEC:
            return False
        windows = [w for w in self.layer_windows()]
        n_global = sum(1 for w in windows if w == GLOBAL)
        # Mostly-local patterns (gemma3 5:1, mixtral all-SWA, hymba) qualify.
        return n_global <= max(1, self.num_layers // 6)

    # ------------------------------------------------------------------ #
    # Parameter / FLOP accounting (roofline §MODEL_FLOPS).
    # ------------------------------------------------------------------ #
    def param_count(self) -> int:
        return _param_count(self)

    def active_param_count(self) -> int:
        return _param_count(self, active_only=True)

    def model_flops_per_token(self, train: bool = True) -> float:
        """6·N_active per token (train) or 2·N_active (inference fwd)."""
        n = self.active_param_count() - self.embedding_params()
        mult = 6.0 if train else 2.0
        return mult * n

    def embedding_params(self) -> int:
        n = self.vocab_size * self.d_model
        if not self.tie_embeddings:
            n *= 2
        return n

    def reduced(self, **overrides) -> "ModelConfig":
        """Tiny same-family variant for CPU smoke tests."""
        small = dict(
            num_layers=min(self.num_layers, 2),
            d_model=64,
            num_heads=4 if self.num_heads else 0,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_heads else 0,
            head_dim=16 if self.num_heads else 0,
            d_ff=128,
            vocab_size=256,
            num_experts=min(self.num_experts, 4) if self.num_experts else 0,
            experts_per_token=min(self.experts_per_token, 2)
            if self.experts_per_token
            else 0,
            num_encoder_layers=min(self.num_encoder_layers, 2)
            if self.num_encoder_layers
            else 0,
            ssm_state=min(self.ssm_state, 8) if self.ssm_state else 0,
            ssm_dt_rank=8 if self.ssm_state else 0,
            window_pattern=tuple(
                (w if w == GLOBAL else min(w, 32)) for w in self.window_pattern
            ),
            loss_chunk=0,
            remat=False,
            name=self.name + "-reduced",
        )
        small.update(overrides)
        return dataclasses.replace(self, **small)


def _param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Closed-form parameter count (matches init_params; tested)."""
    d, ff, v = cfg.d_model, cfg.d_ff, cfg.vocab_size

    if cfg.family is Family.SSM:  # RWKV6
        # time-mix: r/k/v/g/o (5 d*d) + decay lora (d*64*2) + maa lora
        # (d*32*5 + 5*32*d) + u (d) + ln params; channel-mix: k (d*ff),
        # v (ff*d), r (d*d).
        tm = 5 * d * d + 2 * 64 * d + 5 * 32 * d * 2 + d + 2 * d + 2 * d
        cm = d * ff + ff * d + d * d
        per_layer = tm + cm + 2 * d  # + two lns
        emb = v * d * (1 if cfg.tie_embeddings else 2)
        return cfg.num_layers * per_layer + emb + d

    attn = d * cfg.attn_dim + 2 * d * cfg.kv_dim + cfg.attn_dim * d
    if cfg.qkv_bias:
        attn += cfg.attn_dim + 2 * cfg.kv_dim
    if cfg.num_experts:
        ffn_total = cfg.num_experts * 3 * d * ff + d * cfg.num_experts
        ffn_active = cfg.experts_per_token * 3 * d * ff + d * cfg.num_experts
    else:
        ffn_total = ffn_active = 3 * d * ff
    norms = 2 * d

    per_layer_total = attn + ffn_total + norms
    per_layer_active = attn + ffn_active + norms

    if cfg.family is Family.HYBRID:
        # SSM branch: in_proj (d -> 2*d_inner), conv, dt/B/C proj, A, D, out.
        di, st, dtr = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
        ssm = (
            d * 2 * di
            + di * cfg.ssm_conv
            + di * (dtr + 2 * st)
            + dtr * di
            + di * st
            + 2 * di
            + di * d
        )
        per_layer_total += ssm
        per_layer_active += ssm

    emb = v * d * (1 if cfg.tie_embeddings else 2)
    n_layers = cfg.num_layers + cfg.num_encoder_layers
    if cfg.family is Family.ENCDEC:
        # decoder layers add cross-attention
        cross = d * cfg.attn_dim + 2 * d * cfg.kv_dim + cfg.attn_dim * d + d
        extra = cfg.num_layers * cross
    else:
        extra = 0

    total = n_layers * (per_layer_active if active_only else per_layer_total)
    return total + extra + emb + d  # + final norm
