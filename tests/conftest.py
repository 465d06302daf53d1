import numpy as np

from repro.configs.base import (AttnCfg, EncDecCfg, HybridCfg, ModelConfig,
                                MoECfg, SSMCfg)
from repro.data.synthetic import random_tree


def branching_tree(seed: int = 0, min_leaves: int = 3, vocab: int = 89):
    """A random tree guaranteed to branch (otherwise equivalence is trivial)."""
    for s in range(seed, seed + 200):
        t = random_tree(np.random.default_rng(s), vocab_size=vocab)
        if t.num_leaves() >= min_leaves and t.num_unique_tokens() <= 120:
            return t
    raise RuntimeError("no branching tree found")


TINY = dict(n_layers=2, d_model=32, d_ff=64, vocab_size=89,
            dtype="float32", vocab_pad_multiple=8)


def tiny_cfg(family: str, **kw) -> ModelConfig:
    base = dict(TINY)
    if family == "dense":
        base["attn"] = AttnCfg(n_heads=4, n_kv_heads=2, head_dim=8,
                               qk_norm=True, qkv_bias=True)
    elif family == "moe":
        base["attn"] = AttnCfg(n_heads=4, n_kv_heads=2, head_dim=8)
        base["moe"] = MoECfg(num_experts=4, top_k=2, d_expert=32,
                             num_shared_experts=1, first_dense_layers=1)
    elif family == "ssm_rwkv6":
        family = "ssm"
        base["ssm"] = SSMCfg(kind="rwkv6", head_dim=8, expand=1, chunk_size=8)
    elif family == "ssm_mamba2":
        family = "ssm"
        base["ssm"] = SSMCfg(kind="mamba2", d_state=8, head_dim=8, expand=2,
                             chunk_size=8)
    elif family == "ssm_gdn":
        family = "ssm"
        base["ssm"] = SSMCfg(kind="gdn", head_dim=8, expand=1, chunk_size=8)
    elif family == "hybrid":
        base["n_layers"] = 4
        base["attn"] = AttnCfg(n_heads=4, n_kv_heads=4, head_dim=8)
        base["ssm"] = SSMCfg(kind="mamba2", d_state=8, head_dim=8,
                             chunk_size=8)
        base["hybrid"] = HybridCfg(attn_every=2)
    elif family == "audio":
        base["attn"] = AttnCfg(n_heads=4, n_kv_heads=4, head_dim=8)
        base["encdec"] = EncDecCfg(enc_layers=2, dec_layers=2, src_len=8)
        base["frontend"] = "audio"
        base["frontend_len"] = 8
    elif family == "vlm":
        base["attn"] = AttnCfg(n_heads=4, n_kv_heads=4, head_dim=8)
        base["frontend"] = "vision"
        base["frontend_len"] = 6
    base.update(kw)
    return ModelConfig(name=f"tiny-{family}", family=family, **base)
