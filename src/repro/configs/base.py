"""Config system: model/shape configs and the architecture registry.

Every assigned architecture is a `ModelConfig`; the four assigned input
shapes are `ShapeConfig`s. Configs are plain frozen dataclasses so they
hash/compare and can be used as static args under `jax.jit`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds making up a layer pattern. A model is a repeated "super-block"
# pattern of these, which lets heterogeneous stacks (gemma3 5:1 local:global,
# jamba 1 attn : 7 mamba) lower as scans over homogeneous groups.
# ---------------------------------------------------------------------------
ATTN = "attn"            # full (global) softmax attention
ATTN_LOCAL = "attn_local"  # sliding-window attention
MLA = "mla"              # multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
RWKV = "rwkv6"           # RWKV-6 "Finch" token-mix block (attention-free)
MAMBA = "mamba"          # Mamba selective-SSM block
CONV = "conv"            # gated short convolution (LFM2)

SUBQUADRATIC = frozenset({ATTN_LOCAL, RWKV, MAMBA, CONV})


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """Mixture-of-experts FFN spec.

    The router scores all `n_experts` and picks `top_k`; a layer holds
    the experts `held` = (first, stop) of them (None: all), its share
    under expert parallelism, and computes only their part of the
    result. `expert_bias` adds a constant per-expert bias to the scores
    when choosing (not when weighting) the experts."""
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden dim
    n_shared: int = 0              # always-on shared experts (DeepSeek-MoE)
    every: int = 1                 # MoE FFN every `every` layers (llama4 alternates)
    first_dense: int = 0           # leading dense layers (DeepSeek-MoE layer 0)
    aux_loss_coef: float = 0.01
    score: str = "softmax"         # softmax | sigmoid
    expert_bias: bool = False
    routed_scale: float = 1.0      # times the normalised top-k weights
    held: Optional[Tuple[int, int]] = None

    def held_range(self) -> Tuple[int, int]:
        return self.held or (0, self.n_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held_range()
        return hi - lo


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | ssm | moe | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    layer_pattern: Tuple[str, ...] = (ATTN,)   # repeated to cover n_layers
    window: int = 0                # sliding window size for ATTN_LOCAL
    moe: Optional[MoESpec] = None
    # MLA (only when MLA in pattern)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    rope_head_dim: int = 64        # decoupled-rope dims for MLA
    # SSM
    ssm_state: int = 16            # mamba state dim per channel
    ssm_conv: int = 4
    ssm_expand: int = 2
    # gated short convolution (CONV)
    conv_cache: int = 3            # kernel length L; decode keeps L - 1 inputs
    # encoder-decoder (whisper)
    enc_layers: int = 0
    enc_tokens: int = 0            # encoder sequence length (stub frontend output)
    # multimodal frontend stub
    frontend: str = "none"         # none | audio_stub | vision_stub
    frontend_tokens: int = 0       # prepended embedding tokens (vlm)
    frontend_dim: int = 0          # stub embedding dim (0 -> d_model)
    # misc
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-6
    qk_norm: bool = False          # per-head RMSNorm of q and k before rope
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    source: str = ""               # citation

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    def pattern(self) -> Tuple[str, ...]:
        """Full per-layer block-kind list of length n_layers."""
        reps = math.ceil(self.n_layers / len(self.layer_pattern))
        return tuple((self.layer_pattern * reps)[: self.n_layers])

    def is_moe_layer(self, i: int) -> bool:
        m = self.moe
        if m is None:
            return False
        if i < m.first_dense:
            return False
        return (i - m.first_dense) % m.every == 0

    def subquadratic(self) -> bool:
        """True if decode at very long context is feasible (no full-attn
        layer whose KV cache must span the whole context... full attention
        layers are allowed only if every layer kind is sub-quadratic OR the
        arch is hybrid/ssm/local-windowed)."""
        kinds = set(self.pattern())
        full = {ATTN, MLA} & kinds
        if not full:
            return True
        # hybrid archs with a minority of full-attn layers still run 500k
        # (cache shards over the data axis); pure full-attn archs do not.
        n_full = sum(1 for k in self.pattern() if k in (ATTN, MLA))
        return n_full <= self.n_layers // 4

    # -- parameter counting (for roofline MODEL_FLOPS = 6*N*D) ----------
    def param_count(self, active_only: bool = False) -> int:
        d, hd = self.d_model, self.head_dim
        total = self.vocab * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab * d
        for i, kind in enumerate(self.pattern()):
            # token mixer
            if kind == ATTN or kind == ATTN_LOCAL:
                q = d * self.n_heads * hd
                kv = 2 * d * self.n_kv_heads * hd
                o = self.n_heads * hd * d
                total += q + kv + o + (2 * hd if self.qk_norm else 0)
            elif kind == MLA:
                rq = self.q_lora_rank or d
                total += d * rq + rq * self.n_heads * (hd + self.rope_head_dim)
                total += d * (self.kv_lora_rank + self.rope_head_dim)
                total += self.kv_lora_rank * self.n_heads * 2 * hd
                total += self.n_heads * hd * d
            elif kind == RWKV:
                # r,k,v,g,o projections + decay/low-rank mixers (approx)
                total += 5 * d * d + 4 * d * 64
            elif kind == MAMBA:
                di = self.ssm_expand * d
                total += d * 2 * di + di * d        # in_proj, out_proj
                total += di * self.ssm_conv          # conv
                total += di * (2 * self.ssm_state)   # B,C proj (x-dependent)
                total += di * 2                      # dt proj (rank-1 approx) + A,D
            elif kind == CONV:
                total += 4 * d * d + self.conv_cache * d  # in/out proj, conv
            # channel mixer (FFN) — every block has one except RWKV's
            # built-in channel-mix
            if kind in (RWKV,):
                total += 2 * d * int(self.d_ff) + d * d  # k,v + receptance
            elif self.is_moe_layer(i):
                m = self.moe
                e = (m.top_k if active_only else m.n_held) + m.n_shared
                total += e * 3 * d * m.d_ff + d * m.n_experts  # router
                total += m.n_experts if m.expert_bias else 0
            else:
                total += 3 * d * self.d_ff  # swiglu
        # encoder (whisper): same-dim encoder layers, full attn + mlp
        for _ in range(self.enc_layers):
            total += 4 * d * d + 3 * d * self.d_ff
        return int(total)

    # -- one chip's share of a deployment -------------------------------
    def cut(self, n_layers: int = 0, vocab: int = 0,
            experts_held: Optional[Tuple[int, int]] = None
            ) -> "ModelConfig":
        """The part of the model one chip holds: the first `n_layers`
        (a pipeline stage), `vocab` rows of the vocabulary, and of each
        expert layer the experts `experts_held` = (first, stop) (expert
        parallelism; the router keeps all its outputs). 0 / None keeps
        a size as it is. Every width stays."""
        moe = self.moe
        if experts_held is not None:
            moe = dataclasses.replace(moe, held=tuple(experts_held))
        return dataclasses.replace(self, n_layers=n_layers or self.n_layers,
                                   vocab=vocab or self.vocab, moe=moe)

    # -- reduced variant for CPU smoke tests ----------------------------
    def reduced(self) -> "ModelConfig":
        d = min(self.d_model, 128)
        n_heads = max(2, min(self.n_heads, 4))
        hd = max(8, d // n_heads)
        kv = 1 if self.n_kv_heads == 1 else max(1, min(self.n_kv_heads, 2))
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe, n_experts=4, top_k=min(self.moe.top_k, 2),
                d_ff=64, n_shared=min(self.moe.n_shared, 1),
                first_dense=min(self.moe.first_dense, 1), held=None)
        # keep at least one full super-block of the pattern (a published
        # per-layer list, as LFM2's, keeps its first 8 layers)
        n_layers = max(2, min(len(self.layer_pattern), 8))
        return dataclasses.replace(
            self, n_layers=n_layers, d_model=d, n_heads=n_heads,
            n_kv_heads=kv, head_dim=hd, d_ff=128, vocab=512, moe=moe,
            q_lora_rank=min(self.q_lora_rank, 32) if self.q_lora_rank else 0,
            kv_lora_rank=min(self.kv_lora_rank, 32) if self.kv_lora_rank else 0,
            rope_head_dim=min(self.rope_head_dim, 16),
            window=min(self.window, 64) if self.window else 0,
            enc_layers=min(self.enc_layers, 2),
            enc_tokens=min(self.enc_tokens, 32) if self.enc_tokens else 0,
            frontend_tokens=min(self.frontend_tokens, 16)
            if self.frontend_tokens else 0,
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # train | prefill | decode


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        # import the per-arch modules lazily on first miss
        from repro import configs as _c  # noqa
        _c.load_all()
    return _REGISTRY[name]


def list_archs():
    from repro import configs as _c
    _c.load_all()
    return sorted(_REGISTRY)
