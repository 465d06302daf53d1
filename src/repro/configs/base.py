"""Model / run configuration schema.

One ``ModelConfig`` fully describes an architecture; ``src/repro/configs/``
holds one module per assigned architecture returning the exact paper/model-
card config plus a reduced ``smoke()`` variant for CPU tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class AttnCfg:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1_000_000.0
    window: Optional[int] = None       # sliding-window size (positions); None = full
    softmax_scale: Optional[float] = None

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


@dataclass(frozen=True)
class MoECfg:
    num_experts: int
    top_k: int
    d_expert: int                       # per-expert FFN hidden dim
    num_shared_experts: int = 0
    first_dense_layers: int = 0         # leading dense layers (DeepSeek/Kimi style)
    router_aux_weight: float = 0.01     # load-balance coefficient, per branch
    router_z_weight: float = 1e-4       # router z-loss coefficient, per branch
    # [lo, hi): the experts this device holds (expert parallelism); the
    # router still scores all num_experts.  None: every expert.
    held: Optional[tuple[int, int]] = None


@dataclass(frozen=True)
class SSMCfg:
    kind: str                           # 'mamba2' | 'rwkv6' | 'gdn'
    d_state: int = 64
    head_dim: int = 64
    expand: int = 2
    conv_kernel: int = 4                # causal conv width (mamba2/gdn)
    chunk_size: int = 64                # tree chunk grid

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class HybridCfg:
    """Zamba2-style: shared full-attention block every k SSM layers."""
    attn_every: int = 6
    concat_embed: bool = True           # shared block consumes [h ; embed0]


@dataclass(frozen=True)
class EncDecCfg:
    enc_layers: int
    dec_layers: int
    src_len: int = 1024                 # frontend frames for dry-run specs


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                         # dense|moe|ssm|hybrid|vlm|audio
    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attn: Optional[AttnCfg] = None
    mlp_activation: str = "swiglu"      # swiglu | squared_relu | relu_sq_glu
    mlp_bias: bool = False
    moe: Optional[MoECfg] = None
    ssm: Optional[SSMCfg] = None
    hybrid: Optional[HybridCfg] = None
    encdec: Optional[EncDecCfg] = None
    frontend: Optional[str] = None      # None | 'audio' | 'vision'
    frontend_len: int = 0               # stub prefix length (patches/frames)
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    vocab_pad_multiple: int = 256
    remat: str = "none"                 # none | full (checkpoint scan body)
    source: str = ""                    # citation

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter counting (roofline MODEL_FLOPS) ----------------------
    def param_count(self, active_only: bool = False) -> int:
        """Approximate parameter count; active_only counts routed experts
        at top_k/num_experts utilization (for 6·N_active·D)."""
        D, F, L = self.d_model, self.d_ff, self.n_layers
        emb = self.padded_vocab * D * (1 if self.tie_embeddings else 2)
        per_attn = 0
        if self.attn is not None:
            a = self.attn
            per_attn = D * a.q_dim + 2 * D * a.kv_dim + a.q_dim * D

        def mlp_params(ff: int) -> int:
            mult = 3 if self.mlp_activation == "swiglu" else 2
            return mult * D * ff

        if self.moe is not None:
            m = self.moe
            dense_l = m.first_dense_layers
            moe_l = L - dense_l
            routed = m.num_experts * mlp_params(m.d_expert)
            if active_only:
                routed = m.top_k * mlp_params(m.d_expert)
            shared = m.num_shared_experts * mlp_params(m.d_expert)
            router = D * m.num_experts
            body = (dense_l * (per_attn + mlp_params(F))
                    + moe_l * (per_attn + routed + shared + router))
        elif self.ssm is not None and self.family == "ssm":
            s = self.ssm
            di = s.d_inner(D)
            if s.kind == "rwkv6":
                per_tm = 4 * D * D + D * D  # r,k,v,g,o (+ small loras ignored)
                per_cm = 2 * D * self.d_ff
                body = L * (per_tm + per_cm)
            else:
                per_ssm = D * (2 * di + 2 * s.d_state * s.n_heads(D)) + di * D
                body = L * (per_ssm + mlp_params(F))
        elif self.hybrid is not None:
            s = self.ssm
            di = s.d_inner(D)
            per_ssm = D * 2 * di + di * D + di * s.d_state * 2
            shared_attn = per_attn + mlp_params(F) + (2 * D) * D
            body = L * per_ssm + shared_attn
        else:
            body = L * (per_attn + mlp_params(F))
            if self.encdec is not None:
                e = self.encdec
                body = (e.enc_layers + e.dec_layers) * (per_attn + mlp_params(F))
                body += e.dec_layers * per_attn  # cross attention
        return emb + body


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                           # 'train' | 'prefill' | 'decode'


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}
