"""Model assembly: decoder-only (dense / MoE / SSM / hybrid), enc-dec, and
multimodal-prefix variants — all tree-aware, layers stacked for lax.scan.

``forward(cfg, params, batch, impl)`` returns per-token hidden states for
the *text* positions; ``loss_and_metrics`` turns them into the tree loss
(Eq. 4): gather each token's path-predecessor hidden row (prev_idx), apply
the LM head, weighted CE with λ_t.  Branching nodes' children gather the
same parent row, so gradients aggregate there exactly like the per-branch
baseline (Eq. 5).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ops import LIVE_TABLES
from repro.models.attention import (attention, init_attention,
                                    project_cross_kv)
from repro.models.layers import (embed, init_embedding, init_lm_head,
                                 init_mlp, init_rmsnorm, logits_from_hidden,
                                 mlp, rmsnorm, _dense_init)
from repro.models.moe import init_moe, moe, router_loss
from repro.models.ssm.gdn import gdn, init_gdn
from repro.models.ssm.mamba2 import init_mamba2, mamba2
from repro.models.ssm.rwkv6 import (init_rwkv6_channelmix,
                                    init_rwkv6_timemix, rwkv6_channelmix,
                                    rwkv6_timemix)
from repro.sharding import shard_activation, shard_logits


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# a layer's auxiliary outputs, summed over the layers: the router loss
# (per branch, ``models/moe.router_loss``) and the slots the held experts
# computed (``moe_held_rows``)
N_AUX = 2


def _no_aux() -> jax.Array:
    return jnp.zeros((N_AUX,), jnp.float32)


def _vocab_logits(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    """The head's rows past ``vocab_size`` (padding to a multiple) are no
    tokens: no probability falls on them."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(col < cfg.vocab_size, logits, -jnp.inf)


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key, kind: str) -> dict:
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    D = cfg.d_model
    p: dict = {}
    if kind == "dense":
        p["ln1"] = init_rmsnorm(D, dt)
        p["attn"] = init_attention(ks[0], cfg.attn, D, dt)
        p["ln2"] = init_rmsnorm(D, dt)
        p["mlp"] = init_mlp(ks[1], D, cfg.d_ff, cfg.mlp_activation,
                            cfg.mlp_bias, dt)
    elif kind == "moe":
        p["ln1"] = init_rmsnorm(D, dt)
        p["attn"] = init_attention(ks[0], cfg.attn, D, dt)
        p["ln2"] = init_rmsnorm(D, dt)
        p["moe"] = init_moe(ks[1], cfg.moe, D, cfg.mlp_activation, dt)
    elif kind == "rwkv6":
        p["ln1"] = init_rmsnorm(D, dt)
        p["tm"] = init_rwkv6_timemix(ks[0], cfg.ssm, D, dt)
        p["ln2"] = init_rmsnorm(D, dt)
        p["cm"] = init_rwkv6_channelmix(ks[1], D, cfg.d_ff, dt)
    elif kind == "mamba2":
        p["ln1"] = init_rmsnorm(D, dt)
        p["ssm"] = init_mamba2(ks[0], cfg.ssm, D, dt)
    elif kind == "gdn":
        p["ln1"] = init_rmsnorm(D, dt)
        p["ssm"] = init_gdn(ks[0], cfg.ssm, D, dt)
        p["ln2"] = init_rmsnorm(D, dt)
        p["mlp"] = init_mlp(ks[1], D, cfg.d_ff, cfg.mlp_activation,
                            cfg.mlp_bias, dt)
    elif kind == "encoder":
        p["ln1"] = init_rmsnorm(D, dt)
        p["attn"] = init_attention(ks[0], cfg.attn, D, dt)
        p["ln2"] = init_rmsnorm(D, dt)
        p["mlp"] = init_mlp(ks[1], D, cfg.d_ff, cfg.mlp_activation,
                            cfg.mlp_bias, dt)
    elif kind == "decoder_cross":
        p["ln1"] = init_rmsnorm(D, dt)
        p["attn"] = init_attention(ks[0], cfg.attn, D, dt)
        p["ln_x"] = init_rmsnorm(D, dt)
        p["xattn"] = init_attention(ks[1], cfg.attn, D, dt, cross=True)
        p["ln2"] = init_rmsnorm(D, dt)
        p["mlp"] = init_mlp(ks[2], D, cfg.d_ff, cfg.mlp_activation,
                            cfg.mlp_bias, dt)
    else:
        raise ValueError(kind)
    return p


def _apply_layer(cfg: ModelConfig, p: dict, kind: str, x: jax.Array,
                 meta: dict, impl: str, gw=None, capspec=None
                 ) -> tuple[jax.Array, jax.Array, dict]:
    """Returns (x, aux [N_AUX], captures).

    gw: per-layer partition-gateway inputs (ancestor KV / SSM state / conv
    and shift contexts) — None outside partition mode.
    capspec: static per-cut capture plan — dict cut_name →
    {path_idx, cut_chunk, conv_pos, shift_pos} (numpy index arrays).
    """
    eps = cfg.norm_eps
    aux = _no_aux()
    caps: dict = {}
    gw = gw or {}
    if kind in ("dense", "moe", "encoder"):
        bidir = kind == "encoder"
        cap_idx = None if capspec is None else \
            {n: s["path_idx"] for n, s in capspec.items()}
        egw = gw.get("attn")
        if egw is not None:
            egw = {**egw, "pos": meta["anc_pos"],
                   "valid": meta.get("anc_valid")}
        a = attention(p["attn"], cfg.attn, rmsnorm(p["ln1"], x, eps),
                      pos_ids=meta["pos_ids"], kv_last=meta["kv_last"],
                      valid=meta["valid"], impl=impl, bidirectional=bidir,
                      extra_kv=egw, capture_idx=cap_idx)
        if cap_idx is not None:
            a, caps_a = a
            caps["attn"] = caps_a
        x = shard_activation(x + a)
        h = rmsnorm(p["ln2"], x, eps)
        if kind == "moe":
            m, route = moe(p["moe"], cfg.moe, h, meta["valid"],
                           cfg.mlp_activation)
            rl = jnp.zeros((), jnp.float32)
            if "aux_weight" in meta:
                gm = gw.get("moe")
                rl, caps_m = router_loss(
                    cfg.moe, route, meta,
                    None if gm is None else gm["state"], cap_idx)
                if cap_idx is not None:
                    caps["moe"] = caps_m
            aux = aux + jnp.stack([rl, route["held_rows"].astype(
                jnp.float32)])
        else:
            m = mlp(p["mlp"], h, cfg.mlp_activation)
        x = shard_activation(x + m)
    elif kind == "rwkv6":
        gtm, gcm = gw.get("tm", {}), gw.get("cm", {})
        cap_tm = None if capspec is None else \
            {n: {"chunk": s["cut_chunk"], "shift_pos": s["shift_pos"]}
             for n, s in capspec.items()}
        t = rwkv6_timemix(p["tm"], cfg.ssm, rmsnorm(p["ln1"], x, eps),
                          chunk_parent=meta["chunk_parent"],
                          prev_idx=meta["prev_idx"], valid=meta["valid"],
                          initial_state=gtm.get("state"),
                          shift_ctx=gtm.get("shift"), capture=cap_tm)
        if cap_tm is not None:
            t, caps_tm = t
            caps["tm"] = caps_tm
        x = shard_activation(x + t)
        cap_cm = None if capspec is None else \
            {n: {"shift_pos": s["shift_pos"]} for n, s in capspec.items()}
        c = rwkv6_channelmix(p["cm"], rmsnorm(p["ln2"], x, eps),
                             meta["prev_idx"], gcm.get("shift"), cap_cm)
        if cap_cm is not None:
            c, caps_cm = c
            caps["cm"] = caps_cm
        x = shard_activation(x + c)
    elif kind == "mamba2":
        gs = gw.get("ssm", {})
        cap = None if capspec is None else \
            {n: {"chunk": s["cut_chunk"], "conv_pos": s["conv_pos"]}
             for n, s in capspec.items()}
        s = mamba2(p["ssm"], cfg.ssm, rmsnorm(p["ln1"], x, eps),
                   chunk_parent=meta["chunk_parent"],
                   prev_pows=meta["prev_pows"], valid=meta["valid"],
                   initial_state=gs.get("state"), conv_ctx=gs.get("conv"),
                   capture=cap)
        if cap is not None:
            s, caps_s = s
            caps["ssm"] = caps_s
        x = shard_activation(x + s)
    elif kind == "gdn":
        gs = gw.get("ssm", {})
        cap = None if capspec is None else \
            {n: {"chunk": s["cut_chunk"], "conv_pos": s["conv_pos"]}
             for n, s in capspec.items()}
        s = gdn(p["ssm"], cfg.ssm, rmsnorm(p["ln1"], x, eps),
                chunk_parent=meta["chunk_parent"],
                prev_pows=meta["prev_pows"], valid=meta["valid"],
                initial_state=gs.get("state"), conv_ctx=gs.get("conv"),
                capture=cap)
        if cap is not None:
            s, caps_s = s
            caps["ssm"] = caps_s
        x = shard_activation(x + s)
        m = mlp(p["mlp"], rmsnorm(p["ln2"], x, eps), cfg.mlp_activation)
        x = shard_activation(x + m)
    elif kind == "decoder_cross":
        a = attention(p["attn"], cfg.attn, rmsnorm(p["ln1"], x, eps),
                      pos_ids=meta["pos_ids"], kv_last=meta["kv_last"],
                      valid=meta["valid"], impl=impl)
        x = x + a
        kv = project_cross_kv(p["xattn"], cfg.attn, meta["enc_out"])
        c = attention(p["xattn"], cfg.attn, rmsnorm(p["ln_x"], x, eps),
                      pos_ids=meta["pos_ids"], kv_last=meta["kv_last"],
                      valid=meta["valid"], cross_kv=kv,
                      cross_valid=meta["enc_valid"])
        x = x + c
        m = mlp(p["mlp"], rmsnorm(p["ln2"], x, eps), cfg.mlp_activation)
        x = shard_activation(x + m)
    else:
        raise ValueError(kind)
    return x, aux, caps


def _layer_kinds(cfg: ModelConfig) -> list[str]:
    """Kind of every decoder layer, in order."""
    if cfg.family in ("dense", "vlm"):
        return ["dense"] * cfg.n_layers
    if cfg.family == "moe":
        fd = cfg.moe.first_dense_layers
        return ["dense"] * fd + ["moe"] * (cfg.n_layers - fd)
    if cfg.family == "ssm":
        if cfg.ssm.kind == "rwkv6":
            return ["rwkv6"] * cfg.n_layers
        if cfg.ssm.kind == "gdn":
            return ["gdn"] * cfg.n_layers
        return ["mamba2"] * cfg.n_layers
    if cfg.family == "hybrid":
        return ["mamba2"] * cfg.n_layers      # shared attn handled separately
    if cfg.family == "audio":
        return ["decoder_cross"] * cfg.encdec.dec_layers
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> dict:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": init_embedding(keys[0], cfg.padded_vocab, cfg.d_model, dt),
        "final_norm": init_rmsnorm(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_lm_head(keys[1], cfg.d_model,
                                         cfg.padded_vocab, dt)

    groups = layer_groups(cfg)
    gkeys = jax.random.split(keys[2], len(groups))
    stacks = []
    for (kind, n), gk in zip(groups, gkeys):
        lkeys = jax.random.split(gk, n)
        stacked = jax.vmap(
            lambda k, kind=kind: _init_layer(cfg, k, kind))(lkeys)
        stacks.append(stacked)
    params["layer_stacks"] = stacks

    if cfg.family == "hybrid":
        params["shared_attn"] = _init_layer(cfg, keys[3], "dense")
        if cfg.hybrid.concat_embed:
            params["shared_in"] = _dense_init(
                keys[4], (2 * cfg.d_model, cfg.d_model), dtype=dt)
    if cfg.family == "audio":
        e = cfg.encdec
        ekeys = jax.random.split(keys[5], e.enc_layers)
        params["encoder"] = jax.vmap(
            lambda k: _init_layer(cfg, k, "encoder"))(ekeys)
        params["enc_norm"] = init_rmsnorm(cfg.d_model, dt)
    return params


def layer_groups(cfg: ModelConfig) -> list[tuple[str, int]]:
    """Consecutive same-kind layer runs → (kind, count) scan groups."""
    groups: list[tuple[str, int]] = []
    for k in _layer_kinds(cfg):
        if groups and groups[-1][0] == k:
            groups[-1] = (k, groups[-1][1] + 1)
        else:
            groups.append((k, 1))
    return groups


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _scan_group(cfg: ModelConfig, stacked: dict, kind: str, x: jax.Array,
                meta: dict, impl: str, gw=None, capspec=None):
    """Scan a stacked layer group.  gw leaves have a leading per-layer dim
    (scan xs); captured tensors come back stacked the same way."""
    def body(carry, inp):
        x, aux = carry
        lp, gw_l = inp
        x, a, caps = _apply_layer(cfg, lp, kind, x, meta, impl, gw_l,
                                  capspec)
        return (x, aux + a), caps

    if cfg.remat == "full":
        # activation checkpointing: recompute the layer in the backward
        # pass instead of saving its internals (per-chunk attention
        # probabilities etc. dominate temp memory otherwise — §Perf);
        # the attention kernels' live-block tables (a few KiB, the same
        # in every layer) are kept rather than rebuilt in each layer
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                LIVE_TABLES))

    (x, aux), caps = jax.lax.scan(
        body, (x, _no_aux()), (stacked, gw or {}))
    if capspec is None:
        return x, aux
    return x, aux, caps


def _mm_prefix_meta(cfg: ModelConfig, batch: dict) -> dict:
    """Combine a multimodal embedding prefix with the text metadata."""
    F = batch["extra_embeds"].shape[1]
    B, S = batch["tokens"].shape
    tot = F + S
    pos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F)),
         batch["pos_ids"] + F], axis=1)
    kv_last = jnp.concatenate(
        [jnp.full((B, F), tot - 1, jnp.int32),
         jnp.where(batch["kv_last"] >= 0, batch["kv_last"] + F, -1)], axis=1)
    prev = jnp.concatenate(
        [jnp.full((B, F), -1, jnp.int32),
         jnp.where(batch["prev_idx"] >= 0, batch["prev_idx"] + F, -1)],
        axis=1)
    valid = jnp.concatenate(
        [jnp.ones((B, F), bool), batch["valid"]], axis=1)
    return dict(pos_ids=pos, kv_last=kv_last, prev_idx=prev, valid=valid,
                prefix_len=F)


def forward(cfg: ModelConfig, params: dict, batch: dict,
            impl: str = "ref") -> tuple[jax.Array, jax.Array]:
    """Returns (hidden [B, S_text, D] post-final-norm, aux [N_AUX])."""
    group_kinds = [g[0] for g in layer_groups(cfg)]
    dt = _dtype(cfg)
    F = 0
    if cfg.family == "vlm" or (cfg.frontend and cfg.family != "audio"):
        meta = _mm_prefix_meta(cfg, batch)
        F = meta.pop("prefix_len")
        x = jnp.concatenate(
            [batch["extra_embeds"].astype(dt),
             embed(params["embed"], batch["tokens"])], axis=1)
    else:
        meta = dict(pos_ids=batch["pos_ids"], kv_last=batch["kv_last"],
                    prev_idx=batch["prev_idx"], valid=batch["valid"])
        x = embed(params["embed"], batch["tokens"])
    for k in ("chunk_parent", "prev_pows", "aux_weight"):
        if k in batch:
            meta[k] = batch[k]
    x = shard_activation(x)

    if cfg.family == "audio":
        B, Fr = batch["extra_embeds"].shape[:2]
        enc_valid = batch.get("extra_valid",
                              jnp.ones((B, Fr), bool))
        enc_meta = dict(pos_ids=jnp.broadcast_to(
            jnp.arange(Fr, dtype=jnp.int32), (B, Fr)),
            kv_last=jnp.full((B, Fr), Fr - 1, jnp.int32),
            prev_idx=jnp.full((B, Fr), -1, jnp.int32), valid=enc_valid)
        enc_x = batch["extra_embeds"].astype(dt)
        enc_x, _ = _scan_group(cfg, params["encoder"], "encoder", enc_x,
                               enc_meta, impl)
        meta["enc_out"] = rmsnorm(params["enc_norm"], enc_x, cfg.norm_eps)
        meta["enc_valid"] = enc_valid

    aux = _no_aux()
    if cfg.family == "hybrid":
        x, aux = _hybrid_forward(cfg, params, x, meta, impl)
    else:
        for stacked, kind in zip(params["layer_stacks"], group_kinds):
            x, a = _scan_group(cfg, stacked, kind, x, meta, impl)
            aux = aux + a

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if F:
        x = x[:, F:]
    return x, aux


def _hybrid_forward(cfg: ModelConfig, params: dict, x: jax.Array,
                    meta: dict, impl: str) -> tuple[jax.Array, jax.Array]:
    """Zamba2-style: scan mamba2 stages, shared attn block every k layers."""
    emb0 = x
    stacked = params["layer_stacks"][0]
    L = cfg.n_layers
    k = cfg.hybrid.attn_every
    aux = _no_aux()
    i = 0
    while i < L:
        j = min(i + k, L)
        stage = jax.tree.map(lambda a: a[i:j], stacked)
        x, a = _scan_group(cfg, stage, "mamba2", x, meta, impl)
        aux = aux + a
        # shared attention block after each stage (same params every time);
        # input optionally [x ; embed0] down-projected (Zamba2), output
        # contributes its block *delta* to the residual stream.
        if cfg.hybrid.concat_embed:
            h_in = jnp.concatenate([x, emb0], axis=-1) @ params["shared_in"]
        else:
            h_in = x
        h_out, a2, _ = _apply_layer(cfg, params["shared_attn"], "dense",
                                    h_in, meta, impl)
        x = x + (h_out - h_in)
        aux = aux + a2
        i = j
    return x, aux


# ---------------------------------------------------------------------------
# Partition mode (Redundancy-Free Tree Partitioning, paper §3.3 / App. B)
# ---------------------------------------------------------------------------

def partition_forward(cfg: ModelConfig, params: dict, batch: dict,
                      gw_in, capspecs: dict, impl: str):
    """One partition's DFS forward with gateway inputs and captures.

    gw_in: None (root partition) or dict:
      "g{i}"      → per-scan-group gateway (leaves with leading layer dim):
                    attention {"attn": {k, v, pos}}, SSM {"ssm": {state,
                    conv}}, rwkv6 {"tm": {state, shift}, "cm": {shift}}.
      "shared{s}" → hybrid shared-block application s (single-layer gw).
    capspecs: static dict cut_name → {path_idx, cut_chunk, conv_pos,
      shift_pos} (host-planned, core/partition.py).

    Returns (hidden, aux_loss, captures) — captures mirror gw structure and
    retain grad_fn: the orchestrator (core/gateway.py) relays them to child
    partitions and chains their cotangents back (paper App. B.6).
    """
    if cfg.family in ("vlm", "audio"):
        raise NotImplementedError(
            "partitioned training currently covers dense/moe/ssm/hybrid")
    groups = layer_groups(cfg)
    meta = dict(pos_ids=batch["pos_ids"], kv_last=batch["kv_last"],
                prev_idx=batch["prev_idx"], valid=batch["valid"])
    for k in ("chunk_parent", "prev_pows", "anc_pos", "anc_valid",
              "aux_weight"):
        if k in batch:
            meta[k] = batch[k]
    x = shard_activation(embed(params["embed"], batch["tokens"]))

    aux = _no_aux()
    caps_all: dict = {}
    gw_in = gw_in or {}
    if cfg.family == "hybrid":
        emb0 = x
        stacked = params["layer_stacks"][0]
        gw0 = gw_in.get("g0")
        L, step = cfg.n_layers, cfg.hybrid.attn_every
        i = si = 0
        caps_stages = []
        while i < L:
            j = min(i + step, L)
            stage = jax.tree.map(lambda a: a[i:j], stacked)
            gws = None if gw0 is None else \
                jax.tree.map(lambda a: a[i:j], gw0)
            x, a, caps = _scan_group(cfg, stage, "mamba2", x, meta, impl,
                                     gw=gws, capspec=capspecs)
            caps_stages.append(caps)
            aux = aux + a
            if cfg.hybrid.concat_embed:
                h_in = jnp.concatenate([x, emb0], axis=-1) \
                    @ params["shared_in"]
            else:
                h_in = x
            gw_sh = gw_in.get(f"shared{si}")
            if gw_sh is not None:           # stored with leading layer axis
                gw_sh = jax.tree.map(lambda a: a[0], gw_sh)
            h_out, a2, caps_sh = _apply_layer(
                cfg, params["shared_attn"], "dense", h_in, meta, impl,
                gw_sh, capspecs)
            caps_all[f"shared{si}"] = jax.tree.map(lambda a: a[None],
                                                   caps_sh)
            x = x + (h_out - h_in)
            aux = aux + a2
            i = j
            si += 1
        # stitch stage captures back into one [L, ...] stack per leaf
        caps_all["g0"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *caps_stages)
    else:
        for gi, (stacked, (kind, _)) in enumerate(
                zip(params["layer_stacks"], groups)):
            x, a, caps = _scan_group(cfg, stacked, kind, x, meta, impl,
                                     gw=gw_in.get(f"g{gi}"),
                                     capspec=capspecs)
            caps_all[f"g{gi}"] = caps
            aux = aux + a

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, caps_all


def partition_loss(cfg: ModelConfig, params: dict, batch: dict, gw_in,
                   capspecs: dict, impl: str = "ref"):
    """Loss *sum* for one partition (λ already full-tree) + boundary
    losses: child partitions' first tokens are predicted by this
    partition's hidden states at the cut nodes (extra_pos/label/weight).

    Returns ((loss, captures), metrics)."""
    hidden, aux, caps = partition_forward(cfg, params, batch, gw_in,
                                          capspecs, impl)
    head = params.get("lm_head")

    prev = batch["prev_idx"]
    w = jnp.where(prev >= 0, batch["weight"], 0.0)
    h_prev = jnp.take_along_axis(hidden, jnp.maximum(prev, 0)[..., None],
                                 axis=1)
    with jax.named_scope("lm_head"):
        logits = _vocab_logits(cfg, shard_logits(logits_from_hidden(
            params["embed"], head, h_prev)))
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, batch["tokens"][..., None],
                                  axis=-1)
        nll = lse - lab[..., 0]
        loss = jnp.sum(w * nll)

    if "extra_pos" in batch and batch["extra_pos"].shape[-1] > 0:
        h_b = jnp.take_along_axis(hidden, batch["extra_pos"][..., None],
                                  axis=1)
        with jax.named_scope("lm_head"):
            lg = _vocab_logits(cfg, logits_from_hidden(params["embed"],
                                                       head, h_b))
            lse_b = jax.nn.logsumexp(lg, axis=-1)
            lab_b = jnp.take_along_axis(lg, batch["extra_label"][..., None],
                                        axis=-1)[..., 0]
            loss = loss + jnp.sum(batch["extra_weight"] * (lse_b - lab_b))

    metrics = {"weight_sum": jnp.sum(w)
               + (jnp.sum(batch["extra_weight"])
                  if "extra_pos" in batch else 0.0),
               # token CE only (no router/z aux) — the drivers aggregate
               # this into a per-token nll comparable to token_nll_mean
               "nll_sum": loss, "moe_held_rows": aux[1]}
    return (loss + aux[0], caps), metrics


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_and_metrics(cfg: ModelConfig, params: dict, batch: dict,
                     impl: str = "ref") -> tuple[jax.Array, dict]:
    """Tree loss (Eq. 4): (Σ_t λ_t · CE(logits[prev(t)], token_t) + the
    router loss of the branches) / #trees."""
    hidden, aux = forward(cfg, params, batch, impl)
    prev = batch["prev_idx"]
    w = jnp.where(prev >= 0, batch["weight"], 0.0)
    h_prev = jnp.take_along_axis(hidden, jnp.maximum(prev, 0)[..., None],
                                 axis=1)
    head = params.get("lm_head")
    with jax.named_scope("lm_head"):
        logits = logits_from_hidden(params["embed"], head, h_prev)
        logits = _vocab_logits(cfg, shard_logits(logits))
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, batch["tokens"][..., None].astype(
            jnp.int32), axis=-1)[..., 0]
        nll = lse - lab
    denom = jnp.asarray(batch.get("num_trees", 1), jnp.float32)
    loss = jnp.sum(w * nll) / denom
    total = loss + aux[0] / denom
    metrics = {"loss": loss, "aux_loss": aux[0] / denom,
               "moe_held_rows": aux[1],
               "weight_sum": jnp.sum(w),
               # un-normalized token CE (no MoE aux, no tree denominator):
               # the engine accumulates this on-device across microbatch
               # executions and divides by weight_sum once at logging time
               "nll_sum": jnp.sum(w * nll),
               "token_nll_mean": jnp.sum(w * nll) / jnp.maximum(
                   jnp.sum(w), 1e-9)}
    return total, metrics
