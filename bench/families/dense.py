"""The dense family: the Qwen2 block (RMSNorm, QKV bias or none, rotary
positions, grouped-query attention, SwiGLU), one kind of layer stacked.

The family interface is described in ``bench/families/__init__.py``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import ein, rms, rope, visible
from bench.work import tree_counts

SCALES = ("ln1", "ln2")
DECAY = ("embed", "lm_head", "wq", "wk", "wv", "wo", "wg", "wu", "wd")
# keys of a published config.json that this family would silently ignore
EXPERT_KEYS = ("num_experts", "n_routed_experts", "num_local_experts",
               "moe_intermediate_size")


def check(c: dict) -> None:
    for k in EXPERT_KEYS:
        if k in c:
            raise ValueError(f"{k}: the dense family has no experts")
    if "layer_types" in c:
        raise ValueError("layer_types: the dense family stacks one kind of "
                         "layer, full attention")
    if c.get("use_sliding_window"):
        raise ValueError("use_sliding_window: the dense family attends "
                         "over the whole path")
    if c["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {c['hidden_act']!r}: the dense family "
                         "runs SwiGLU, whose gate is silu")
    if c["program"]["mlp_activation"] != "swiglu":
        raise ValueError(f"program.mlp_activation "
                         f"{c['program']['mlp_activation']!r}: the dense "
                         "family runs SwiGLU")


def dims(c: dict) -> dict:
    """The sizes the reference and the work counts read."""
    H, Kh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim", c["hidden_size"] // H)
    m = c["program"]["vocab_pad_multiple"]
    V = c["vocab_size"]
    return dict(L=c["num_hidden_layers"], D=c["hidden_size"],
                F=c["intermediate_size"], H=H, Kh=Kh, hd=hd, V=V,
                Vp=-(-V // m) * m, tied=bool(c["tie_word_embeddings"]),
                theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
                std=float(c["initializer_range"]))


def program_config(c: dict, name: str = "bench"):
    from repro.configs.base import AttnCfg, ModelConfig
    d, p = dims(c), c["program"]
    return ModelConfig(
        name=name, family="dense", n_layers=d["L"], d_model=d["D"],
        d_ff=d["F"], vocab_size=d["V"],
        attn=AttnCfg(n_heads=d["H"], n_kv_heads=d["Kh"], head_dim=d["hd"],
                     qkv_bias=p["qkv_bias"], rope_theta=d["theta"]),
        mlp_activation=p["mlp_activation"], norm_eps=d["eps"],
        tie_embeddings=d["tied"], dtype=p["dtype"],
        vocab_pad_multiple=p["vocab_pad_multiple"], remat=p["remat"],
        source=c["source"])


def shapes(c: dict) -> dict:
    d = dims(c)
    L, D, F, Vp = d["L"], d["D"], d["F"], d["Vp"]
    q, kv = d["H"] * d["hd"], d["Kh"] * d["hd"]
    layer = dict(ln1=(L, D), wq=(L, D, q), bq=(L, q), wk=(L, D, kv),
                 bk=(L, kv), wv=(L, D, kv), bv=(L, kv), wo=(L, q, D),
                 ln2=(L, D), wg=(L, D, F), wu=(L, D, F), wd=(L, F, D))
    out = dict(embed=(Vp, D), final_norm=(D,), layers=layer)
    if not d["tied"]:
        out["lm_head"] = (D, Vp)
    return out


def to_program(c: dict, w: dict) -> dict:
    """Benchmark layout → the program's parameter tree (no copies)."""
    ly = w["layers"]
    p = {"embed": {"table": w["embed"]},
         "final_norm": {"scale": w["final_norm"]},
         "layer_stacks": [{
             "ln1": {"scale": ly["ln1"]},
             "attn": {k: ly[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk",
                                         "bv")},
             "ln2": {"scale": ly["ln2"]},
             "mlp": {"wi_gate": ly["wg"], "wi_up": ly["wu"],
                     "wo": ly["wd"]}}]}
    if "lm_head" in w:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


def from_program(p: dict) -> dict:
    """The program's parameter-shaped tree (weights, moments, changes)
    → the benchmark layout."""
    s = p["layer_stacks"][0]
    w = {"embed": p["embed"]["table"], "final_norm": p["final_norm"]["scale"],
         "layers": {"ln1": s["ln1"]["scale"], "ln2": s["ln2"]["scale"],
                    "wg": s["mlp"]["wi_gate"], "wu": s["mlp"]["wi_up"],
                    "wd": s["mlp"]["wo"], **s["attn"]}}
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]["w"]
    return w


def body(c: dict, fp8: bool, w: dict, x, row: dict):
    d = dims(c)
    H, Kh, hd, eps = d["H"], d["Kh"], d["hd"], d["eps"]
    pos = row["pos"]
    R = x.shape[0]
    vis = visible(row)
    f32 = lambda a: a.astype(jnp.float32)

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        h = rms(x, lw["ln1"], eps)
        q = (ein("rd,de->re", h, lw["wq"], fp8) + lw["bq"]).reshape(R, H, hd)
        k = (ein("rd,de->re", h, lw["wk"], fp8) + lw["bk"]).reshape(R, Kh, hd)
        v = (ein("rd,de->re", h, lw["wv"], fp8) + lw["bv"]).reshape(R, Kh, hd)
        q, k = rope(q, pos, d["theta"]), rope(k, pos, d["theta"])
        k, v = jnp.repeat(k, H // Kh, axis=1), jnp.repeat(v, H // Kh, axis=1)
        s = ein("ihd,jhd->hij", q, k, fp8) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(vis[None], s, -1e30), axis=-1)
        o = ein("hij,jhd->ihd", p, v, fp8).reshape(R, H * hd)
        x = x + ein("re,ed->rd", o, lw["wo"], fp8)
        h = rms(x, lw["ln2"], eps)
        m = jax.nn.silu(ein("rd,df->rf", h, lw["wg"], fp8)) \
            * ein("rd,df->rf", h, lw["wu"], fp8)
        return x + ein("rf,fd->rd", m, lw["wd"], fp8), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["layers"])
    return x, jnp.zeros((), jnp.float32)


def matmul_params(d: dict) -> int:
    q, kv = d["H"] * d["hd"], d["Kh"] * d["hd"]
    per_layer = d["D"] * (2 * q + 2 * kv) + 3 * d["D"] * d["F"]
    return d["L"] * per_layer + d["D"] * d["V"]


def step_work(trees, d: dict) -> dict:
    """Model FLOPs = 6 · (matmul parameters of the layers and the LM head)
    · unique tokens + 12 · H · hd · layers · visible pairs; the attention
    kernels' useful bytes are q, k, v, o, dO, dq, dk and dv of the unique
    tokens in bfloat16, once per layer (``bench/work.py``)."""
    tokens = pairs = 0
    for t in trees:
        a, b = tree_counts(t)
        tokens += a
        pairs += b
    attn_flops = 12 * d["H"] * d["hd"] * d["L"] * pairs
    attn_bytes = 2 * 4 * (d["H"] + d["Kh"]) * d["hd"] * d["L"] * tokens
    return dict(tokens=tokens, pairs=pairs,
                flops=6 * matmul_params(d) * tokens + attn_flops,
                attn_flops=attn_flops, attn_bytes=attn_bytes)
