"""Qwen1.5-0.5B [hf:Qwen/Qwen1.5-0.5B] — dense, MHA 16H (kv=16),
QKV bias.

At published widths one 16 GB chip holds the train step only with
activation rematerialization: the bf16 weights, the fp32 gradient
accumulator and the fp32 AdamW moments take ~8.7 GB before any
activation (``remat="none"`` needs ~39 GB at 2 rows × 2048).  The smoke
reduction keeps ``remat="none"``."""
from repro.configs.base import AttnCfg, ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen1.5-0.5b", family="dense",
        n_layers=24, d_model=1024, d_ff=2816, vocab_size=151936,
        attn=AttnCfg(n_heads=16, n_kv_heads=16, head_dim=64,
                     qkv_bias=True),
        mlp_activation="swiglu", remat="full",
        source="hf:Qwen/Qwen1.5-0.5B",
    )


def smoke() -> ModelConfig:
    return config().replace(
        n_layers=2, d_model=64, d_ff=128, vocab_size=512,
        attn=AttnCfg(n_heads=4, n_kv_heads=4, head_dim=16, qkv_bias=True),
        dtype="float32", vocab_pad_multiple=8, remat="none",
        name="qwen1.5-smoke")
