"""The moe family: the Qwen3-MoE block (RMSNorm, grouped-query attention
with q/k RMSNorm and no bias, rotary positions; a router over every
expert, top-k renormalised, SwiGLU experts), one kind of layer stacked.

A configuration may hold a share of the experts (expert parallelism):
``program.router_experts`` is the router's width (every expert), and
``program.held_experts`` = [lo, hi) the experts whose weights this chip
keeps; ``num_experts`` is their count.  A token's slot whose expert is
not held is another chip's share and is left out, in the program and
here alike.

The router loss is per branch: for each root-to-leaf path of T tokens,
``router_aux_loss_coef`` · E · Σ_e (slots routed to e / T) · (mean router
probability of e), summed over the layers, weighted as the path's own
tokens are (its share 1 / (K · trees)).

The family interface is described in ``bench/families/__init__.py``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import ein, rms, rope, visible
from bench.work import tree_counts

SCALES = ("ln1", "ln2", "q_norm", "k_norm")
DECAY = ("embed", "lm_head", "wq", "wk", "wv", "wo", "router", "wg", "wu",
         "wd")
SHARED_KEYS = ("shared_expert_intermediate_size", "num_shared_experts",
               "n_shared_experts")


def check(c: dict) -> None:
    for k in SHARED_KEYS:
        if c.get(k):
            raise ValueError(f"{k}: the moe family has no shared expert")
    if c.get("mlp_only_layers"):
        raise ValueError("mlp_only_layers: the moe family stacks expert "
                         "layers only")
    if c.get("decoder_sparse_step", 1) != 1:
        raise ValueError(f"decoder_sparse_step {c['decoder_sparse_step']}: "
                         "the moe family puts experts in every layer")
    if c.get("use_sliding_window") or c.get("layer_types"):
        raise ValueError("use_sliding_window: the moe family attends over "
                         "the whole path")
    if c.get("attention_bias"):
        raise ValueError("attention_bias: the moe family's attention has "
                         "no bias")
    if c["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {c['hidden_act']!r}: the moe family "
                         "runs SwiGLU experts, whose gate is silu")
    if not c["norm_topk_prob"]:
        raise ValueError("norm_topk_prob: the program renormalises the "
                         "top-k routing probabilities")
    p = c["program"]
    lo, hi = p["held_experts"]
    if not 0 <= lo < hi <= p["router_experts"] or \
            hi - lo != c["num_experts"]:
        raise ValueError(f"program.held_experts {[lo, hi]}: num_experts "
                         f"{c['num_experts']} held of "
                         f"{p['router_experts']} routed")


def dims(c: dict) -> dict:
    """The sizes the reference and the work counts read."""
    H, Kh = c["num_attention_heads"], c["num_key_value_heads"]
    m = c["program"]["vocab_pad_multiple"]
    V = c["vocab_size"]
    lo, hi = c["program"]["held_experts"]
    return dict(L=c["num_hidden_layers"], D=c["hidden_size"],
                F=c["moe_intermediate_size"], H=H, Kh=Kh,
                hd=c.get("head_dim", c["hidden_size"] // H), V=V,
                Vp=-(-V // m) * m, tied=bool(c["tie_word_embeddings"]),
                theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
                std=float(c["initializer_range"]),
                E=c["program"]["router_experts"], lo=lo, hi=hi,
                X=hi - lo, K=c["num_experts_per_tok"],
                aux=float(c.get("router_aux_loss_coef", 0.0)))


def program_config(c: dict, name: str = "bench"):
    from repro.configs.base import AttnCfg, ModelConfig, MoECfg
    d, p = dims(c), c["program"]
    return ModelConfig(
        name=name, family="moe", n_layers=d["L"], d_model=d["D"],
        d_ff=c["intermediate_size"], vocab_size=d["V"],
        attn=AttnCfg(n_heads=d["H"], n_kv_heads=d["Kh"], head_dim=d["hd"],
                     qk_norm=True, rope_theta=d["theta"]),
        moe=MoECfg(num_experts=d["E"], top_k=d["K"], d_expert=d["F"],
                   router_aux_weight=d["aux"], router_z_weight=0.0,
                   held=(d["lo"], d["hi"])),
        mlp_activation="swiglu", norm_eps=d["eps"],
        tie_embeddings=d["tied"], dtype=p["dtype"],
        vocab_pad_multiple=p["vocab_pad_multiple"], remat=p["remat"],
        source=c["source"])


def shapes(c: dict) -> dict:
    d = dims(c)
    L, D, F, X, Vp = d["L"], d["D"], d["F"], d["X"], d["Vp"]
    q, kv = d["H"] * d["hd"], d["Kh"] * d["hd"]
    layer = dict(ln1=(L, D), wq=(L, D, q), wk=(L, D, kv), wv=(L, D, kv),
                 wo=(L, q, D), q_norm=(L, d["hd"]), k_norm=(L, d["hd"]),
                 ln2=(L, D), router=(L, D, d["E"]), wg=(L, X, D, F),
                 wu=(L, X, D, F), wd=(L, X, F, D))
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
             "attn": {"wq": ly["wq"], "wk": ly["wk"], "wv": ly["wv"],
                      "wo": ly["wo"], "q_norm": {"scale": ly["q_norm"]},
                      "k_norm": {"scale": ly["k_norm"]}},
             "ln2": {"scale": ly["ln2"]},
             "moe": {"router": ly["router"], "wi_gate": ly["wg"],
                     "wi_up": ly["wu"], "wo": ly["wd"]}}]}
    if "lm_head" in w:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


def from_program(p: dict) -> dict:
    """The program's parameter-shaped tree (weights, moments, changes)
    → the benchmark layout."""
    s = p["layer_stacks"][0]
    a, m = s["attn"], s["moe"]
    w = {"embed": p["embed"]["table"], "final_norm": p["final_norm"]["scale"],
         "layers": {"ln1": s["ln1"]["scale"], "ln2": s["ln2"]["scale"],
                    "wq": a["wq"], "wk": a["wk"], "wv": a["wv"],
                    "wo": a["wo"], "q_norm": a["q_norm"]["scale"],
                    "k_norm": a["k_norm"]["scale"], "router": m["router"],
                    "wg": m["wi_gate"], "wu": m["wi_up"], "wd": m["wo"]}}
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]["w"]
    return w


def body(c: dict, fp8: bool, w: dict, x, row: dict):
    d = dims(c)
    H, Kh, hd, eps = d["H"], d["Kh"], d["hd"], d["eps"]
    E, K, lo, X = d["E"], d["K"], d["lo"], d["X"]
    pos = row["pos"]
    R = x.shape[0]
    vis = visible(row)
    f32 = lambda a: a.astype(jnp.float32)
    # each path of the row: its tokens [R, paths] and its share of the
    # step, which every one of its trained tokens' weights carries
    # (trained / (K · trees), bench/reference.step_rows)
    on = (row["seg"][:, None] == jnp.arange(R)[None, :]).astype(jnp.float32)
    T = jnp.maximum(on.sum(0), 1.0)
    share = jnp.max(on * row["weight"][:, None], axis=0)

    def layer(carry, lw):
        x, aux = carry
        lw = jax.tree.map(f32, lw)
        h = rms(x, lw["ln1"], eps)
        q = rms(ein("rd,de->re", h, lw["wq"], fp8).reshape(R, H, hd),
                lw["q_norm"], eps)
        k = rms(ein("rd,de->re", h, lw["wk"], fp8).reshape(R, Kh, hd),
                lw["k_norm"], eps)
        v = ein("rd,de->re", h, lw["wv"], fp8).reshape(R, Kh, hd)
        q, k = rope(q, pos, d["theta"]), rope(k, pos, d["theta"])
        k, v = jnp.repeat(k, H // Kh, axis=1), jnp.repeat(v, H // Kh, axis=1)
        s = ein("ihd,jhd->hij", q, k, fp8) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(vis[None], s, -1e30), axis=-1)
        o = ein("hij,jhd->ihd", p, v, fp8).reshape(R, H * hd)
        x = x + ein("re,ed->rd", o, lw["wo"], fp8)
        h = rms(x, lw["ln2"], eps)
        # the router over every expert; top-k, renormalised
        probs = jax.nn.softmax(ein("rd,de->re", h, lw["router"], fp8), -1)
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        # the held experts, each over every token, weighted by the
        # token's routing probability of it (0 where not routed there)
        gate = jnp.sum(jnp.where(top_e[..., None] == lo + jnp.arange(X),
                                 top_p[..., None], 0.0), axis=1)   # [R, X]
        a = jax.nn.silu(ein("rd,xdf->rxf", h, lw["wg"], fp8)) \
            * ein("rd,xdf->rxf", h, lw["wu"], fp8)
        x = x + ein("rxf,xfd->rd", a * gate[..., None], lw["wd"], fp8)
        # the router loss of each path
        cnt = jax.nn.one_hot(top_e, E).sum(1)                       # [R, E]
        frac = ein("rp,re->pe", on, cnt, fp8) / T[:, None]
        pmean = ein("rp,re->pe", on, probs, fp8) / T[:, None]
        lb = d["aux"] * E * jnp.sum(frac * pmean, axis=-1)
        return (x, aux + jnp.sum(share * lb)), None

    (x, aux), _ = jax.lax.scan(jax.checkpoint(layer),
                               (x, jnp.zeros((), jnp.float32)), w["layers"])
    return x, aux


def step_work(trees, d: dict) -> dict:
    """Active work on this chip, from the trees: model FLOPs = 6 ·
    (attention, router, the K·X/E held expert MLPs a token reaches on
    average, and the head over its vocabulary slice) · unique tokens + 12
    · H · hd · layers · visible pairs.  ``expert_rows``: the slots that
    reach a held expert, expected over a uniform router (K·X/E a token
    and layer); ``expert_flops`` = 18 · D · F per such row (forward and
    backward, recomputation not counted); ``expert_bytes``: the held
    experts' weights read and their gradients written, and the routed
    rows in and out, in bfloat16, per layer."""
    tokens = pairs = 0
    for t in trees:
        a, b = tree_counts(t)
        tokens += a
        pairs += b
    L, D, F, X = d["L"], d["D"], d["F"], d["X"]
    q, kv = d["H"] * d["hd"], d["Kh"] * d["hd"]
    reach = d["K"] * X / d["E"]
    per_token = L * (D * (2 * q + 2 * kv) + D * d["E"]
                     + reach * 3 * D * F) + D * d["V"]
    attn_flops = 12 * d["H"] * d["hd"] * L * pairs
    rows = tokens * reach * L
    return dict(tokens=tokens, pairs=pairs,
                flops=6 * per_token * tokens + attn_flops,
                attn_flops=attn_flops,
                attn_bytes=2 * 4 * (d["H"] + d["Kh"]) * d["hd"] * L * tokens,
                expert_rows=rows, expert_flops=18 * D * F * rows,
                expert_bytes=2 * (L * 2 * 3 * X * D * F + rows * 2 * D))
