"""Base model configuration for all architecture families.

Every assigned architecture (and the Galaxy paper's own evaluation models)
is expressed as a single ``ModelConfig``.  The transformer assembly in
``repro.models.transformer`` consumes only this dataclass, so new
architectures are added by writing one config file.

Block patterns
--------------
``block_pattern`` is the repeating unit of the layer stack, e.g.::

    dense            ("attn",)
    recurrentgemma   ("rec", "rec", "attn")      # Griffin 1:2 ratio
    xlstm            ("mlstm", "slstm")
    llama-vision     ("attn",)*4 + ("xattn",)    # cross-attn every 5th

``num_layers`` need not be a multiple of ``len(block_pattern)``; the
remainder blocks (``num_layers % len(pattern)``) are instantiated
individually after the scanned groups (see models/transformer.py).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

# Attention kinds usable inside a block pattern.
ATTN_KINDS = ("attn", "xattn")
RECURRENT_KINDS = ("rec", "mlstm", "slstm")
BLOCK_KINDS = ATTN_KINDS + RECURRENT_KINDS


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # --- identity -------------------------------------------------------
    name: str
    family: str  # dense | moe | hybrid | ssm | vlm | audio
    source: str  # citation for the config (paper / model card)

    # --- core dims ------------------------------------------------------
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    num_kv_heads: int = 12
    d_ff: int = 3072          # dense MLP width; for MoE: per-expert width
    vocab_size: int = 32000
    head_dim: int = 0          # 0 -> d_model // num_heads

    # --- block structure --------------------------------------------------
    block_pattern: Tuple[str, ...] = ("attn",)
    norm: str = "rmsnorm"      # rmsnorm | layernorm
    activation: str = "swiglu"  # swiglu | geglu | gelu
    qkv_bias: bool = False
    tie_embeddings: bool = False
    pos_embedding: str = "rope"  # rope | sinusoidal | none
    rope_theta: float = 10000.0
    dropout_rate: float = 0.0   # paper's connective block includes dropout

    # --- attention ------------------------------------------------------
    window: int = 0             # 0 = full causal; >0 = sliding-window (hybrid local attn)
    # sliding-window width substituted for full attention ONLY for the
    # long_500k input shape on otherwise-quadratic archs (see DESIGN.md §4)
    long_context_window: int = 4096

    # --- MoE --------------------------------------------------------------
    num_experts: int = 0
    experts_per_token: int = 0
    router_jitter: float = 0.0
    load_balance_loss_weight: float = 0.01
    moe_capacity_factor: float = 2.0   # GShard capacity; dispatch cost ∝ cf

    # --- recurrent (RG-LRU / Griffin) -------------------------------------
    lru_width: int = 0          # 0 -> d_model
    conv_width: int = 4

    # --- xLSTM ------------------------------------------------------------
    proj_factor: float = 2.0    # up-projection inside m/sLSTM blocks
    mlstm_chunk: int = 128      # chunkwise-parallel scan chunk

    # --- multimodal stubs ---------------------------------------------------
    # "token": inputs are int token ids; "embed": inputs are precomputed
    # frontend embeddings (B, S, d_model) — audio/vlm stub carve-out.
    input_mode: str = "token"
    num_image_tokens: int = 0   # vlm: patch-embedding count fed to cross-attn
    num_codebooks: int = 0      # audio: parallel codebook heads (0 = single head)

    # --- numerics ---------------------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    remat: bool = True          # checkpoint each block group during training
    # "full" recomputes everything; "dots" saves matmul outputs (cheaper
    # backward compute, more activation memory); "none" disables remat.
    remat_policy: str = "full"
    # query-chunked attention for long prefill (0 = off): caps the live
    # score buffer at (B, H, chunk, S) instead of (B, H, S, S)
    attn_chunk: int = 0

    # ----------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.lru_width == 0:
            object.__setattr__(self, "lru_width", self.d_model)
        for kind in self.block_pattern:
            if kind not in BLOCK_KINDS:
                raise ValueError(f"unknown block kind {kind!r}")
        if self.num_heads % max(self.num_kv_heads, 1) != 0:
            raise ValueError("num_heads must be divisible by num_kv_heads")

    # --- derived ------------------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    def padded_vocab(self, multiple: int = 256) -> int:
        """Vocab rounded up so the vocab dim shards evenly over the mesh."""
        return _round_up(self.vocab_size, multiple)

    def padded_experts(self, multiple: int) -> int:
        """Experts padded so the expert dim shards evenly (padding experts
        receive -inf router logits and are never selected)."""
        if not self.is_moe:
            return 0
        return _round_up(self.num_experts, multiple)

    @property
    def num_groups(self) -> int:
        return self.num_layers // len(self.block_pattern)

    @property
    def tail_pattern(self) -> Tuple[str, ...]:
        r = self.num_layers % len(self.block_pattern)
        return self.block_pattern[:r]

    def layer_kinds(self) -> Tuple[str, ...]:
        """Kind of every layer, in order."""
        return self.block_pattern * self.num_groups + self.tail_pattern

    def count_kind(self, kind: str) -> int:
        return sum(1 for k in self.layer_kinds() if k == kind)

    @property
    def attention_free(self) -> bool:
        return all(k in RECURRENT_KINDS for k in self.block_pattern)

    @property
    def sub_quadratic(self) -> bool:
        """True if prefill/decode cost is sub-quadratic in sequence length
        natively (recurrent blocks and/or windowed attention only)."""
        for k in self.block_pattern:
            if k in ATTN_KINDS and self.window == 0:
                return False
        return True

    # --- parameter counting (used for roofline MODEL_FLOPS = 6·N·D) ---------
    def param_count(self, active_only: bool = False) -> int:
        """Approximate parameter count (embedding + blocks + head)."""
        d, hd = self.d_model, self.head_dim
        h, kv = self.num_heads, self.num_kv_heads
        n = 0
        if self.input_mode == "token":
            n += self.vocab_size * d
        if not self.tie_embeddings:
            n += self.vocab_size * d * max(1, self.num_codebooks or 1)
        gate_mats = {"swiglu": 3, "geglu": 3, "gelu": 2}[self.activation]
        for kind in self.layer_kinds():
            if kind in ("attn", "xattn"):
                n += d * (h * hd) + 2 * d * (kv * hd) + (h * hd) * d  # qkvo
                if self.is_moe:
                    e = self.experts_per_token if active_only else self.num_experts
                    n += e * gate_mats * d * self.d_ff + d * self.num_experts
                elif self.d_ff > 0:
                    n += gate_mats * d * self.d_ff
            elif kind == "rec":
                w = self.lru_width
                n += 2 * d * w + w * d          # in/out projections (gated)
                n += self.conv_width * w + 3 * w  # conv + lru gates
                n += gate_mats * d * self.d_ff    # hybrid blocks keep MLP
            elif kind == "mlstm":
                f = self.proj_factor
                di = int(d * f)
                n += 2 * d * di + di * d + 3 * di * di // max(self.num_heads, 1)
            elif kind == "slstm":
                f = self.proj_factor
                di = int(d * f)
                n += d * 4 * di + di * 4 * di + di * d  # in, recurrent, out
        return int(n)


def reduced(cfg: ModelConfig, d_model: int = 256, vocab: int = 512) -> ModelConfig:
    """Smoke-test variant: one pattern group of layers (>=2 for dense),
    d_model <= 512, <= 4 experts — same family/code paths, CPU-runnable."""
    pat = cfg.block_pattern
    layers = max(2, len(pat))
    heads = min(cfg.num_heads, 4)
    kv = min(cfg.num_kv_heads, heads)
    while heads % kv:
        kv -= 1
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=layers,
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=0,
        d_ff=0 if cfg.d_ff == 0 else max(64, d_model * 2),
        vocab_size=vocab,
        num_experts=min(cfg.num_experts, 4),
        experts_per_token=min(cfg.experts_per_token, 2),
        lru_width=0,
        window=min(cfg.window, 32) if cfg.window else 0,
        long_context_window=64,
        num_image_tokens=min(cfg.num_image_tokens, 16),
        dtype="float32",
        param_dtype="float32",
        remat=False,
    )


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    """A DeepSeek-V3 block stack: latent attention (MLA) with no query
    compression, ``first_dense`` dense SwiGLU layers, then mixture-of-experts
    layers (sigmoid router over ``num_experts`` with a selection bias,
    top-``experts_per_token``, normalised and scaled weights, plus shared
    experts), pre-norm RMSNorm, a final norm and an untied head.

    ``experts_held`` is how many routed experts this executor holds of each
    layer (the first ones; the whole layer when equal to ``num_experts``):
    the router still scores all ``num_experts``.  Served through
    ``GalaxyMLAExecutor`` (``core/mla_moe.py``), not the model zoo."""

    name: str
    source: str
    num_layers: int = 27
    first_dense: int = 1
    d_model: int = 2048
    num_heads: int = 16
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    d_ff: int = 11264             # the dense layers' SwiGLU width
    d_expert: int = 1408          # each routed expert's SwiGLU width
    num_shared: int = 2           # shared experts, one SwiGLU of their sum
    num_experts: int = 64         # the router's width
    experts_per_token: int = 6
    experts_held: int = 64
    routed_scale: float = 2.446
    rope_theta: float = 50000.0
    eps: float = 1e-5
    vocab_size: int = 163840
    max_position: int = 8192

    def __post_init__(self):
        if not 0 < self.experts_held <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} not in "
                             f"(0, {self.num_experts}]")
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError("first_dense must lie within num_layers")


def reduced_mla_moe(cfg: MLAMoEConfig) -> MLAMoEConfig:
    """CPU smoke variant of an MLA/MoE stack: 3 layers (one dense), narrow
    widths, 8 routed experts of which 4 are held, top-2."""
    return dataclasses.replace(
        cfg, name=cfg.name + "-smoke", num_layers=3, first_dense=1,
        d_model=64, num_heads=4, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, kv_lora_rank=32, d_ff=128, d_expert=32, num_shared=1,
        num_experts=8, experts_per_token=2, experts_held=4, vocab_size=512,
        max_position=1024)
