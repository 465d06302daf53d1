"""Differentiable partition boundaries — the runtime half of
Redundancy-Free Tree Partitioning (paper §3.3 + App. B), in JAX.

PyTorch version: detached leaf KV tensors + retain_graph + float32
accumulator hooks.  JAX version: each partition is a pure function
``(params, gw_in) → ((loss, captures), metrics)``; we take ``jax.vjp`` per
partition, recurse into child partitions (relaying captured KV / SSM state
/ conv & token-shift context), then invoke the parent's vjp with the loss
cotangent AND the children's gateway cotangents — the same gradient relay
as pipeline parallelism (paper's own analogy).  Peak residency = vjp
closures along one root-to-leaf partition path (the paper's memory
bound).  Gateway cotangents are accumulated in float32 before the parent
vjp call (App. B.5's accumulator, the natural JAX idiom).

This module is the host-side *planner* plus the per-partition device
primitives; three entry points share the plumbing:
  ``partitioned_value_and_grad``        one tree, depth-first B=1
                                        recursion (strict path bound);
  ``build_partition_plan``              many trees → a ``PartitionPlan``
                                        (per-wave numpy batches, capture
                                        plans, gateway topology) that the
                                        unified engine executes
                                        (train/engine.run_partition_plan);
  ``packed_partitioned_value_and_grad`` thin compatibility wrapper:
                                        build the plan, run it through
                                        the engine executor.

The gateway is *ancestor-compacted*: we gather exactly the ancestor-token
rows host-side instead of slicing ``[:past_len+e]`` + a −∞ bias
(App. B.3) — smaller tensors, no bias mask.  Ancestor RoPE positions
(App. B.4) travel as static per-partition data, not differentiable leaves.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.packing import pack_partition_waves
from repro.core.partition import TreePartition, partition_tree
from repro.core.plan_cost import (CUT_FLOOR, balanced_row_order, pow2,
                                  wave_path_floor)
from repro.core.tree import TrajectoryTree
from repro.models.layers import prev_powers
from repro.models.model import has_router_loss, max_conv_taps, needs_chunks
from repro.models.transformer import partition_loss


# ---------------------------------------------------------------------------
# Host-side batch / capture planning per partition
# ---------------------------------------------------------------------------

def make_part_batch(cfg: ModelConfig, part: TreePartition,
                    chunk_size: Optional[int],
                    anc_pos: np.ndarray) -> dict:
    ser = part.ser
    b: dict[str, Any] = {
        "tokens": jnp.asarray(ser.tokens[None]),
        "pos_ids": jnp.asarray(ser.pos_ids[None]),
        "kv_last": jnp.asarray(ser.kv_last[None]),
        "weight": jnp.asarray(ser.weight[None]),
        "prev_idx": jnp.asarray(ser.prev_idx[None]),
        "valid": jnp.asarray(ser.valid[None]),
        "anc_pos": jnp.asarray(anc_pos[None].astype(np.int32)),
    }
    if has_router_loss(cfg):
        b["aux_weight"] = jnp.asarray(ser.aux_weight[None])
    if needs_chunks(cfg):
        b["chunk_parent"] = jnp.asarray(
            ser.chunk_parent_map(chunk_size)[None])
        k = max(1, max_conv_taps(cfg))
        b["prev_pows"] = jnp.asarray(prev_powers(ser.prev_idx[None], k))
    if part.cuts:
        b["extra_pos"] = jnp.asarray(
            [[c.boundary_pos for c in part.cuts]], jnp.int32)
        b["extra_label"] = jnp.asarray(
            [[c.boundary_label for c in part.cuts]], jnp.int32)
        b["extra_weight"] = jnp.asarray(
            [[c.boundary_weight for c in part.cuts]], jnp.float32)
    return b


def make_capspecs(cfg: ModelConfig, part: TreePartition) -> dict:
    taps = max(1, max_conv_taps(cfg))
    specs = {}
    for i, c in enumerate(part.cuts):
        idx = np.asarray(c.path_token_idx, np.int32)
        specs[f"c{i}"] = {
            "path_idx": idx,
            "cut_chunk": np.int32(max(c.cut_chunk, 0)),
            "conv_pos": idx[-taps:],
            "shift_pos": idx[-1:],
        }
    return specs


# ---------------------------------------------------------------------------
# Gateway assembly (parent → child) and cotangent routing (child → parent)
# ---------------------------------------------------------------------------

def _concat_tail(gw_arr: Optional[jax.Array], cap_arr: jax.Array,
                 keep: int) -> jax.Array:
    """Concat along the token axis (2), keep the last ``keep`` entries."""
    z = cap_arr if gw_arr is None else jnp.concatenate(
        [gw_arr, cap_arr], axis=2)
    return z[:, :, -keep:] if z.shape[2] > keep else z


def _route_tail(gw_shape, cap_shape, keep: int, cot_child: jax.Array):
    """Transpose of _concat_tail → cotangents for (gw_arr, cap_arr)."""
    T_in = 0 if gw_shape is None else gw_shape[2]
    T_c = cap_shape[2]
    T = T_in + T_c
    kept = min(keep, T)
    cz = jnp.zeros(cap_shape[:2] + (T,) + cap_shape[3:], cot_child.dtype)
    cz = cz.at[:, :, T - kept:].set(cot_child[:, :, -kept:])
    return (None if T_in == 0 else cz[:, :, :T_in]), cz[:, :, T_in:]


def assemble_child_gw(cfg: ModelConfig, gw_in: Optional[dict], caps: dict,
                      cut_name: str) -> dict:
    taps = max(1, max_conv_taps(cfg))
    child: dict = {}
    for gkey, group_caps in caps.items():
        if not group_caps:
            continue
        gw_g = (gw_in or {}).get(gkey, {})
        cg: dict = {}
        if "attn" in group_caps:
            cap = group_caps["attn"][cut_name]
            prev = gw_g.get("attn")
            cg["attn"] = {
                t: (cap[t] if prev is None else
                    jnp.concatenate([prev[t], cap[t]], axis=2))
                for t in ("k", "v")}
        if "moe" in group_caps:
            # the path sums at the cut already hold this partition's own
            # gateway sums (models/moe.router_loss)
            cg["moe"] = {"state": group_caps["moe"][cut_name]["state"]}
        if "ssm" in group_caps:
            cap = group_caps["ssm"][cut_name]
            prev = gw_g.get("ssm")
            cg["ssm"] = {
                "state": cap["state"],
                "conv": _concat_tail(None if prev is None else prev["conv"],
                                     cap["conv"], taps)}
        if "tm" in group_caps:
            cap = group_caps["tm"][cut_name]
            prev = gw_g.get("tm")
            cg["tm"] = {
                "state": cap["state"],
                "shift": _concat_tail(None if prev is None
                                      else prev["shift"], cap["shift"], 1)}
        if "cm" in group_caps:
            cap = group_caps["cm"][cut_name]
            prev = gw_g.get("cm")
            cg["cm"] = {
                "shift": _concat_tail(None if prev is None
                                      else prev["shift"], cap["shift"], 1)}
        if cg:
            child[gkey] = cg
    return child


def route_child_cot(cfg: ModelConfig, gw_in: Optional[dict], caps: dict,
                    cut_name: str, cot_child: dict,
                    cot_gw_acc: Optional[dict], cot_caps: dict):
    """Split child's gateway cotangent into pass-through ancestors (adds to
    this partition's gw_in cotangent, float32) and this partition's capture
    cotangents.  Mutates cot_caps in place; returns cot_gw_acc."""
    taps = max(1, max_conv_taps(cfg))
    for gkey, cg in cot_child.items():
        group_caps = caps[gkey]
        gw_g = (gw_in or {}).get(gkey, {})
        if "attn" in cg:
            prev = gw_g.get("attn")
            A_in = 0 if prev is None else prev["k"].shape[2]
            for t in ("k", "v"):
                cot = cg["attn"][t]
                if A_in:
                    cot_gw_acc[gkey]["attn"][t] = (
                        cot_gw_acc[gkey]["attn"][t]
                        + cot[:, :, :A_in].astype(jnp.float32))
                cc = cot_caps[gkey]["attn"][cut_name][t]
                cot_caps[gkey]["attn"][cut_name][t] = cc + cot[:, :, A_in:]
        if "moe" in cg:
            cot_caps[gkey]["moe"][cut_name]["state"] = jax.tree.map(
                lambda a, b: a + b.astype(a.dtype),
                cot_caps[gkey]["moe"][cut_name]["state"],
                cg["moe"]["state"])
        if "ssm" in cg:
            cap = group_caps["ssm"][cut_name]
            prev = gw_g.get("ssm")
            cot_caps[gkey]["ssm"][cut_name]["state"] = jax.tree.map(
                lambda a, b: a + b.astype(a.dtype),
                cot_caps[gkey]["ssm"][cut_name]["state"],
                cg["ssm"]["state"])
            cgw, cc = _route_tail(None if prev is None
                                  else prev["conv"].shape,
                                  cap["conv"].shape, taps, cg["ssm"]["conv"])
            if cgw is not None:
                cot_gw_acc[gkey]["ssm"]["conv"] = (
                    cot_gw_acc[gkey]["ssm"]["conv"]
                    + cgw.astype(jnp.float32))
            cot_caps[gkey]["ssm"][cut_name]["conv"] = (
                cot_caps[gkey]["ssm"][cut_name]["conv"] + cc)
        for tkey in ("tm", "cm"):
            if tkey not in cg:
                continue
            cap = group_caps[tkey][cut_name]
            prev = gw_g.get(tkey)
            if "state" in cg[tkey]:
                cot_caps[gkey][tkey][cut_name]["state"] = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype),
                    cot_caps[gkey][tkey][cut_name]["state"],
                    cg[tkey]["state"])
            cgw, cc = _route_tail(None if prev is None
                                  else prev["shift"].shape,
                                  cap["shift"].shape, 1, cg[tkey]["shift"])
            if cgw is not None:
                cot_gw_acc[gkey][tkey]["shift"] = (
                    cot_gw_acc[gkey][tkey]["shift"]
                    + cgw.astype(jnp.float32))
            cot_caps[gkey][tkey][cut_name]["shift"] = (
                cot_caps[gkey][tkey][cut_name]["shift"] + cc)
    return cot_gw_acc


# ---------------------------------------------------------------------------
# Cached jitted per-partition forward / backward
#
# jax.vjp re-traces on every call; across training steps (and across
# same-shaped partitions) that tracing dominates host time.  We instead
# cache two jitted callables per (cfg, cut-name structure, gw-structure)
# signature:
#   fwd(params, batch, gw, capspecs)       → ((loss, caps), metrics)
#   bwd(params, batch, gw, capspecs, cot)  → (g_params, g_gw) [rematerialized]
# Capture plans travel as *runtime* index arrays (dynamic gathers), not
# static constants, so partitions that merely differ in where their cuts
# sit reuse one executable — only the array *shapes* (bucketed by the wave
# scheduler below) key the jit cache.  The backward *recomputes* the
# partition forward inside jit (activation remat) — so no residuals are
# held between the two phases at all, which strictly improves on the
# paper's peak-memory bound at ~1/3 extra FLOPs (standard remat
# trade-off), and lets XLA cache the executable.
# ---------------------------------------------------------------------------

from functools import lru_cache  # noqa: E402


def _names_sig(capspecs: dict) -> tuple:
    return tuple(sorted(capspecs))


@lru_cache(maxsize=64)
def _part_fns(cfg: ModelConfig, names: tuple, impl: str, has_gw: bool):
    if has_gw:
        def fwd(params, batch, gw, capspecs):
            return partition_loss(cfg, params, batch, gw, capspecs, impl)

        def bwd(params, batch, gw, capspecs, cot):
            return _vjp2(cfg, params, batch, gw, capspecs, impl, cot)
    else:
        def fwd(params, batch, gw, capspecs):
            return partition_loss(cfg, params, batch, None, capspecs, impl)

        def bwd(params, batch, gw, capspecs, cot):
            return _vjp1(cfg, params, batch, capspecs, impl, cot)

    return jax.jit(fwd), jax.jit(bwd)


def _vjp1(cfg, params, batch, capspecs, impl, cot):
    _, vjp, _ = jax.vjp(
        lambda p: partition_loss(cfg, p, batch, None, capspecs, impl),
        params, has_aux=True)
    (g_params,) = vjp(cot)
    return g_params, None


def _vjp2(cfg, params, batch, gw, capspecs, impl, cot):
    _, vjp, _ = jax.vjp(
        lambda p, g: partition_loss(cfg, p, batch, g, capspecs, impl),
        params, gw, has_aux=True)
    return vjp(cot)


# ---------------------------------------------------------------------------
# The partitioned train-step driver
# ---------------------------------------------------------------------------

def partitioned_value_and_grad(
    cfg: ModelConfig,
    params: dict,
    tree: TrajectoryTree,
    capacity: int,
    *,
    impl: str = "ref",
    loss_mode: str = "sep_avg",
) -> tuple[float, dict, dict]:
    """Loss + grads for ONE tree with ≤capacity tokens resident per
    partition — every token computed exactly once (paper Fig. 5, right).

    Returns (loss, grads (float32), info)."""
    chunk_size = cfg.ssm.chunk_size if needs_chunks(cfg) else None
    parts = partition_tree(tree, capacity, chunk_size=chunk_size,
                           loss_mode=loss_mode)
    grads_acc = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                             params)
    # loss accumulates as a device array; float() once after the recursion
    # so partition dispatch pipelines instead of host-syncing per partition
    total_loss = jnp.zeros((), jnp.float32)
    total_weight = jnp.zeros((), jnp.float32)
    total_nll = jnp.zeros((), jnp.float32)
    info = {"num_partitions": len(parts),
            "tokens": sum(p.ser.n for p in parts)}

    def process(pid: int, gw_in: Optional[dict], anc_pos: np.ndarray):
        nonlocal grads_acc, total_loss, total_weight, total_nll
        part = parts[pid]
        batch = make_part_batch(cfg, part, chunk_size, anc_pos)
        capspecs = make_capspecs(cfg, part)
        fwd, bwd = _part_fns(cfg, _names_sig(capspecs), impl,
                             gw_in is not None)

        (loss, caps), metrics = fwd(params, batch, gw_in, capspecs)
        total_loss = total_loss + loss.astype(jnp.float32)
        total_weight = total_weight + \
            metrics["weight_sum"].astype(jnp.float32)
        total_nll = total_nll + metrics["nll_sum"].astype(jnp.float32)

        cot_gw_acc = None if gw_in is None else jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), gw_in)
        cot_caps = jax.tree.map(jnp.zeros_like, caps)

        for i, cut in enumerate(part.cuts):
            cut_name = f"c{i}"
            child_gw = assemble_child_gw(cfg, gw_in, caps, cut_name)
            child_anc_pos = np.concatenate(
                [anc_pos, part.ser.pos_ids[cut.path_token_idx]])
            cot_child = process(cut.child_pid, child_gw, child_anc_pos)
            cot_gw_acc = route_child_cot(cfg, gw_in, caps, cut_name,
                                         cot_child, cot_gw_acc, cot_caps)

        g_params, g_gw = bwd(params, batch, gw_in, capspecs,
                             (jnp.ones((), loss.dtype), cot_caps))
        grads_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                 grads_acc, g_params)
        if gw_in is None:
            return None
        return jax.tree.map(
            lambda own, acc: (own.astype(jnp.float32) + acc
                              ).astype(own.dtype),
            g_gw, cot_gw_acc)

    process(0, None, np.zeros((0,), np.int32))
    info["weight_sum"] = float(total_weight)
    info["nll_sum"] = float(total_nll)
    return float(total_loss), grads_acc, info


# ---------------------------------------------------------------------------
# Batched wave-scheduled planning (Tree Packing over partitions, §3.3–3.4)
#
# The recursive driver above runs one partition at a time (B=1).  Training
# needs the transpose: MANY trees' partitions per step, batched.  The wave
# scheduler packs every partition of every tree into per-wave [B, S] rows
# (core/packing.pack_partition_waves); ``build_partition_plan`` turns that
# into a pure-host PartitionPlan, and the engine's executor
# (train/engine.run_partition_plan) runs
#
#   forward  waves 0..W−1: each wave is ONE jitted call; a child's gateway
#            is assembled per row from its parent's captures (the parent is
#            always in the previous wave);
#   backward waves W−1..0: children's gateway cotangents are routed to
#            their parents' capture cotangents per row, then the wave's
#            remat backward runs as one jitted call.
#
# Rows in a wave have different ancestor depths and cut plans, so gateway
# tensors are front-padded to a shared (bucketed) ancestor length — padded
# slots are masked invisible (attention anc_valid; conv front-zeros are
# exactly the out-of-range-reads-zero semantics) — and capture plans are
# front-padded index arrays whose padded entries are trimmed host-side
# before any use.  Shape buckets (powers of two for B, ancestor length,
# cut count, path length) keep the jit cache small across steps.
#
# Memory: unlike the depth-first recursion (peak = one root-to-leaf
# partition path), wave scheduling keeps each wave's gateway inputs and
# captures resident between the two sweeps — the usual
# throughput-for-memory trade of pipelined schedules; each wave's
# *activations* are still rematerialized inside the jitted backward.
# ---------------------------------------------------------------------------


# THE shape-bucket rule, shared with the schedule cost model so planner
# signature estimates match the buckets the engine actually compiles
_pow2 = pow2


def _pad_rows(a: np.ndarray, Bb: int, fill) -> np.ndarray:
    if a.shape[0] == Bb:
        return a
    pad = np.full((Bb - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad], axis=0)


def _pad_tok(a: jax.Array, T: int) -> jax.Array:
    """Front-pad the token axis (2) with zeros to length T."""
    t = a.shape[2]
    if t >= T:
        return a[:, :, -T:]
    z = jnp.zeros(a.shape[:2] + (T - t,) + a.shape[3:], a.dtype)
    return jnp.concatenate([z, a], axis=2)


def _slice_gw_row(gw: dict, r: int, A_real: int) -> dict:
    """Row r of a stacked wave gateway, stripped of front padding."""
    def attn_sl(a):
        return a[:, r:r + 1, a.shape[2] - A_real:]

    out = {}
    for gkey, g in gw.items():
        h = {}
        for kind, sub in g.items():
            if kind == "attn":
                h[kind] = {t: attn_sl(sub[t]) for t in ("k", "v")}
            else:
                h[kind] = jax.tree.map(lambda a: a[:, r:r + 1], sub)
        out[gkey] = h
    return out


def _stack_gw_rows(rows: list[dict], A_max: int, Bb: int,
                   rows_idx: Optional[list[int]] = None) -> dict:
    """Stack per-row (B=1) gateways along the row axis, front-padding
    token axes (attention ancestors to A_max; conv/shift tails to their
    wave max) and adding zero rows up to Bb.  ``rows_idx`` scatters entry
    i to row ``rows_idx[i]`` (the wave's load-balance permutation);
    omitted, entry i lands at row i."""
    perm = None
    if rows_idx is not None and list(rows_idx) != list(range(len(rows))):
        src = np.full(Bb, -1, np.int64)
        for i, r in enumerate(rows_idx):
            src[r] = i
        pads = iter(range(len(rows), Bb))
        perm = jnp.asarray([p if p >= 0 else next(pads) for p in src])

    def catB(xs):
        x = jnp.concatenate(xs, axis=1)
        if Bb > len(xs):
            z = jnp.zeros((x.shape[0], Bb - len(xs)) + x.shape[2:],
                          x.dtype)
            x = jnp.concatenate([x, z], axis=1)
        if perm is not None:
            x = jnp.take(x, perm, axis=1)
        return x

    out: dict = {}
    for gkey in rows[0]:
        g: dict = {}
        for kind in rows[0][gkey]:
            sub: dict = {}
            for leaf in rows[0][gkey][kind]:
                vals = [r[gkey][kind][leaf] for r in rows]
                if kind == "attn" or leaf in ("conv", "shift"):
                    T = A_max if kind == "attn" else \
                        max(v.shape[2] for v in vals)
                    sub[leaf] = catB([_pad_tok(v, T) for v in vals])
                else:       # "state": nested pytree, no token axis
                    sub[leaf] = jax.tree.map(
                        lambda *xs: catB(list(xs)), *vals)
            g[kind] = sub
        out[gkey] = g
    return out


def _wave_capspecs(cfg: ModelConfig, cuts: list, taps: int,
                   lo: int = 1, pad: bool = False) -> dict:
    """Bucketed, front-padded capture plans for one wave (runtime arrays):
    the path pad at least ``lo``, at least ``CUT_FLOOR`` cuts, none for a
    wave without cuts unless ``pad``.

    Padded entries index position 0; their captures are trimmed before any
    use and receive zero cotangents, so they are inert."""
    if not cuts and not pad:
        return {}
    plen_b = _pow2(max((len(c.path_idx) for c in cuts), default=0), lo=lo)
    ncut_b = _pow2(len(cuts), lo=CUT_FLOOR)
    specs = {}
    for i in range(ncut_b):
        if i < len(cuts):
            idx = np.asarray(cuts[i].path_idx, np.int32)
            pad = np.concatenate(
                [np.zeros(plen_b - len(idx), np.int32), idx])
            cc = np.int32(max(cuts[i].cut_chunk, 0))
        else:
            pad = np.zeros(plen_b, np.int32)
            cc = np.int32(0)
        specs[f"c{i}"] = {"path_idx": pad, "cut_chunk": cc,
                          "conv_pos": pad[-taps:], "shift_pos": pad[-1:]}
    return specs


def _cut_caps_view(cfg: ModelConfig, caps: dict, cname: str, r: int,
                   true_len: int) -> dict:
    """Row r's capture for one cut, trimmed to its real (unpadded) token
    entries — the exact tensors a child partition's gateway glues in."""
    taps = max(1, max_conv_taps(cfg))
    creal = min(taps, true_len)
    out: dict = {}
    for gkey, g in caps.items():
        h: dict = {}
        for kind, cuts_d in g.items():
            if cname not in cuts_d:
                continue
            c = cuts_d[cname]
            if kind == "attn":
                h[kind] = {cname: {
                    t: c[t][:, r:r + 1, c[t].shape[2] - true_len:]
                    for t in ("k", "v")}}
            elif kind == "ssm":
                h[kind] = {cname: {
                    "state": jax.tree.map(lambda a: a[:, r:r + 1],
                                          c["state"]),
                    "conv": c["conv"][:, r:r + 1,
                                      c["conv"].shape[2] - creal:]}}
            elif kind == "tm":
                h[kind] = {cname: {
                    "state": jax.tree.map(lambda a: a[:, r:r + 1],
                                          c["state"]),
                    "shift": c["shift"][:, r:r + 1]}}
            elif kind == "cm":
                h[kind] = {cname: {"shift": c["shift"][:, r:r + 1]}}
            elif kind == "moe":
                h[kind] = {cname: {
                    "state": jax.tree.map(lambda a: a[:, r:r + 1],
                                          c["state"])}}
        if h:
            out[gkey] = h
    return out


def _embed_cut_cot(cot_caps: dict, cot_view: dict, cname: str, r: int
                   ) -> None:
    """Scatter a trimmed per-cut cotangent (mirror of _cut_caps_view) back
    into the wave-level capture cotangent, in place."""
    def emb_tok(full, part):
        t = part.shape[2]
        return full.at[:, r, full.shape[2] - t:].add(
            part[:, 0].astype(full.dtype))

    def emb_row(full, part):
        return full.at[:, r].add(part[:, 0].astype(full.dtype))

    for gkey, g in cot_view.items():
        for kind, cuts_d in g.items():
            c = cuts_d[cname]
            tgt = cot_caps[gkey][kind][cname]
            if kind == "attn":
                for t in ("k", "v"):
                    tgt[t] = emb_tok(tgt[t], c[t])
            else:
                if "state" in c:
                    tgt["state"] = jax.tree.map(emb_row, tgt["state"],
                                                c["state"])
                for leaf in ("conv", "shift"):
                    if leaf in c:
                        tgt[leaf] = emb_tok(tgt[leaf], c[leaf])


@dataclass
class GatewayRef:
    """Where one gateway-bearing fragment's parent captures live: wave
    index, cut index within that wave (cname = f"c{cut}"), the parent's
    row, and the real (unpadded) captured path length."""
    wave: int
    cut: int
    row: int
    path_len: int


@dataclass
class WavePlan:
    """Host-side plan for ONE wave: fixed-shape numpy batch columns (rows
    already padded to the pow2 bucket), bucketed capture plans, and the
    gateway topology — everything the executor needs except the runtime
    capture tensors themselves."""
    batch: dict[str, np.ndarray]          # [Bb, S] columns (+anc_*, extra_*)
    capspecs: dict                        # bucketed runtime index arrays
    has_gw: bool
    num_rows: int                         # real rows (before pow2 padding)
    parents: list[GatewayRef] = field(default_factory=list)  # per slot
    slot_rows: list[int] = field(default_factory=list)       # slot → row
    A_real: list[int] = field(default_factory=list)          # per row [Bb]
    anc_A_max: int = 0                    # bucketed ancestor length
    anc_pos_rows: list[np.ndarray] = field(default_factory=list)  # per row


@dataclass
class PartitionPlan:
    """Plan for the partitioned share of one step: waves in topological
    order (parents strictly earlier), ready for the engine's forward and
    backward sweeps (train/engine.run_partition_plan)."""
    waves: list[WavePlan]
    num_trees: int
    info: dict


def build_partition_plan(
    cfg: ModelConfig,
    trees: list[TrajectoryTree],
    capacity: int,
    *,
    seq_len: Optional[int] = None,
    loss_mode: str = "sep_avg",
    max_rows: Optional[int] = None,
    row_multiple: int = 1,
    forest: Optional[list[list[TreePartition]]] = None,
) -> PartitionPlan:
    """Plan (host-side only) the wave-scheduled partitioned execution of
    MANY oversized trees: partition each tree, pack every partition into
    per-wave [B, S] rows, pad/bucket every shape, precompute ancestor
    positions and capture plans, and record the gateway topology.

    No device work happens here — the plan is pure numpy + static
    metadata.  ``train/engine.py`` executes it (one jitted forward and one
    jitted remat-backward per wave, gradients accumulated on-device).

    ``row_multiple`` rounds every wave's bucketed row count up to a
    multiple (the mesh's data-axis size) so wave batches shard evenly
    across replicas; ``forest`` passes precomputed partitions (the
    scheduler partitions each tree exactly once and reuses the result
    here — must match ``partition_tree`` on the same args)."""
    chunk_size = cfg.ssm.chunk_size if needs_chunks(cfg) else None
    seq_len = capacity if seq_len is None else seq_len
    assert capacity <= seq_len, (capacity, seq_len)
    taps = max(1, max_conv_taps(cfg))
    # short capture paths and ancestor runs share one bucket (a path
    # never exceeds the capacity)
    plen_lo = min(wave_path_floor(seq_len), _pow2(capacity))
    anc_lo = max(8, wave_path_floor(seq_len))
    info: dict[str, Any] = {"num_trees": len(trees)}
    if not trees:
        return PartitionPlan(waves=[], num_trees=0, info=info)

    if forest is None:
        forest = [partition_tree(t, capacity, chunk_size=chunk_size,
                                 loss_mode=loss_mode) for t in trees]
    assert len(forest) == len(trees)
    waves = pack_partition_waves(forest, seq_len, chunk_size=chunk_size,
                                 max_rows=max_rows)
    cut_of_child: dict[tuple[int, int], tuple[int, int]] = {}
    for w, wv in enumerate(waves):
        for ci, c in enumerate(wv.cuts):
            cut_of_child[(c.tree, c.child_pid)] = (w, ci)

    info.update(num_partitions=sum(len(p) for p in forest),
                num_waves=len(waves),
                rows=sum(wv.num_rows for wv in waves),
                max_wave_rows=max(wv.num_rows for wv in waves),
                tokens=sum(p.ser.n for ps in forest for p in ps),
                unique_tokens=sum(int(p.ser.valid.sum())
                                  for ps in forest for p in ps))

    plans: list[WavePlan] = []
    rowmaps: list[np.ndarray] = []     # per wave: packer row → balanced row
    cells = 0
    for wv in waves:
        B = wv.num_rows
        # bucket in per-replica units: identical to pow2 for power-of-two
        # replica counts, but never inflates past ~the max_rows budget the
        # way rounding pow2(B) up to an odd multiple would (e.g. B=6,
        # R=6 → 6, not round_to_multiple(8, 6)=12)
        Bb = row_multiple * _pow2(-(-B // row_multiple))
        cells += Bb * seq_len
        a = wv.arrays
        prev_np = _pad_rows(a["prev_idx"], Bb, -1)
        batch = {
            "tokens": _pad_rows(a["tokens"], Bb, 0),
            "pos_ids": _pad_rows(a["pos_ids"], Bb, 0),
            "kv_last": _pad_rows(a["kv_last"], Bb, -1),
            "weight": _pad_rows(a["weight"], Bb, 0),
            "prev_idx": prev_np,
            "valid": _pad_rows(a["valid"], Bb, False),
        }
        if has_router_loss(cfg):
            batch["aux_weight"] = _pad_rows(a["aux_weight"], Bb, 0)
        if chunk_size is not None:
            batch["chunk_parent"] = _pad_rows(a["chunk_parent"], Bb, -1)
            batch["prev_pows"] = prev_powers(prev_np, taps)
        # waves are depth-homogeneous: either all root fragments (no
        # gateway) or all gateway-bearing; parents may sit several waves
        # back once a too-wide depth level is split under max_rows
        has_gw = forest[wv.slots[0].tree][wv.slots[0].pid].parent_pid >= 0
        # a root wave that cuts nothing (trees of one fragment) takes inert
        # cuts and boundary extras, and so the program of one that cuts
        root_pad = not has_gw and not wv.cuts
        if wv.cuts or root_pad:
            Eb = _pow2(max(sum(1 for c in wv.cuts if c.row == r)
                           for r in range(B)), lo=CUT_FLOOR)
            pos = np.zeros((Bb, Eb), np.int32)
            lab = np.zeros((Bb, Eb), np.int32)
            wgt = np.zeros((Bb, Eb), np.float32)
            cnt = [0] * B
            for c in wv.cuts:
                j = cnt[c.row]
                cnt[c.row] += 1
                pos[c.row, j] = c.boundary_pos
                lab[c.row, j] = c.boundary_label
                wgt[c.row, j] = c.boundary_weight
            batch["extra_pos"] = pos
            batch["extra_label"] = lab
            batch["extra_weight"] = wgt
        capspecs = _wave_capspecs(cfg, wv.cuts, taps, lo=plen_lo,
                                  pad=root_pad)
        parents: list[GatewayRef] = []
        A_max = 0
        anc_pos_rows: list[np.ndarray] = \
            [np.zeros((0,), np.int32) for _ in range(Bb)]
        if has_gw:
            # wave ≥ 1: one fragment per row, slot i at packer row i
            for sl in wv.slots:
                wp, ci = cut_of_child[(sl.tree, sl.pid)]
                c = waves[wp].cuts[ci]
                prow = int(rowmaps[wp][c.row])
                parents.append(GatewayRef(wave=wp, cut=ci, row=prow,
                                          path_len=len(c.path_idx)))
                anc_pos_rows[sl.row] = np.concatenate(
                    [plans[wp].anc_pos_rows[prow],
                     waves[wp].arrays["pos_ids"][c.row, c.path_idx]]
                ).astype(np.int32)
                assert len(anc_pos_rows[sl.row]) == \
                    forest[sl.tree][sl.pid].anc_len
            # at least 8: ancestor buckets stay TPU-sublane-aligned so the
            # fused pallas kernels get an MXU-friendly front-padded KV
            # extension (the chunked path is indifferent; padded slots are
            # masked)
            A_max = _pow2(max(len(p) for p in anc_pos_rows), lo=anc_lo)
            anc_pos = np.zeros((Bb, A_max), np.int32)
            anc_valid = np.zeros((Bb, A_max), bool)
            for r, p in enumerate(anc_pos_rows):
                anc_pos[r, A_max - len(p):] = p
                anc_valid[r, A_max - len(p):] = True
            batch["anc_pos"] = anc_pos
            batch["anc_valid"] = anc_valid

        # wave-level replica balance: permute rows by gateway + token
        # load the way packed rows are snake-dealt (train/planner), so
        # contiguous per-replica shards carry non-empty-row counts within
        # 1 of each other.  Pure row permutation — identity when
        # row_multiple ≤ 1, and gradient-neutral always (row metadata is
        # row-local; the gateway topology is remapped alongside).
        loads = [int(batch["valid"][r].sum()) + len(anc_pos_rows[r])
                 for r in range(Bb)]
        order = balanced_row_order(loads, row_multiple)
        new_of = np.empty(Bb, np.int64)
        new_of[np.asarray(order)] = np.arange(Bb)
        if order != list(range(Bb)):
            batch = {k: v[np.asarray(order)] for k, v in batch.items()}
            anc_pos_rows = [anc_pos_rows[r] for r in order]
        rowmaps.append(new_of)

        plans.append(WavePlan(batch=batch, capspecs=capspecs,
                              has_gw=has_gw, num_rows=B, parents=parents,
                              slot_rows=[int(new_of[sl.row])
                                         for sl in wv.slots],
                              A_real=[len(p) for p in anc_pos_rows],
                              anc_A_max=A_max,
                              anc_pos_rows=anc_pos_rows))

    info["cells"] = cells     # materialized row cells (bucketed rows × S)
    return PartitionPlan(waves=plans, num_trees=len(trees), info=info)


def packed_partitioned_value_and_grad(
    cfg: ModelConfig,
    params: dict,
    trees: list[TrajectoryTree],
    capacity: int,
    *,
    seq_len: Optional[int] = None,
    impl: str = "ref",
    loss_mode: str = "sep_avg",
    max_rows: Optional[int] = None,
) -> tuple[float, dict, dict]:
    """Loss-*sum* + grads for MANY trees via wave-scheduled Tree Packing
    over partitions — thin compatibility wrapper: builds a PartitionPlan
    and executes it through the unified engine's wave executor
    (``train/engine.run_partition_plan``).  Every token of every tree is
    computed exactly once, with ≤ ``seq_len`` tokens per row and one
    jitted fwd / one jitted bwd call per wave.  ``max_rows`` caps every
    wave's row count (too-wide waves split), bounding per-wave activation
    residency to a ``max_rows × seq_len`` step like the packed path's
    row budget.

    Returns ``(loss_sum, grads (float32), info)``; divide by the number of
    trees to match ``loss_and_metrics``'s mean-over-trees normalizer."""
    from repro.train.engine import NUM_SCALARS, run_partition_plan

    plan = build_partition_plan(cfg, trees, capacity, seq_len=seq_len,
                                loss_mode=loss_mode, max_rows=max_rows)
    grads_acc = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                             params)
    if not plan.waves:
        return 0.0, grads_acc, plan.info
    scal = jnp.zeros((NUM_SCALARS,), jnp.float32)
    grads_acc, scal = run_partition_plan(
        cfg, params, plan, grads_acc, scal, impl=impl,
        loss_scale=jnp.ones((), jnp.float32), donate=False)
    # one host sync point for the scalars (loss reporting + per-token nll)
    total_loss, nll_sum, weight_sum = np.asarray(scal)[:3]
    info = dict(plan.info)
    info["weight_sum"] = float(weight_sum)
    info["nll_sum"] = float(nll_sum)
    return float(total_loss), grads_acc, info


def _embed_gw_row_cot(acc: dict, row_cot: dict, r: int) -> dict:
    """Add a per-row gateway cotangent (stripped shapes) into the stacked
    wave accumulator at row r (front-padded axes)."""
    out: dict = {}
    for gkey, g in acc.items():
        h: dict = {}
        for kind, sub in g.items():
            src = row_cot[gkey][kind]
            if kind == "attn":
                h[kind] = {t: sub[t].at[:, r, sub[t].shape[2]
                                        - src[t].shape[2]:].add(
                    src[t][:, 0].astype(sub[t].dtype))
                    for t in ("k", "v")}
            else:
                hh: dict = {}
                for leaf in sub:
                    if leaf in ("conv", "shift"):
                        hh[leaf] = sub[leaf].at[
                            :, r, sub[leaf].shape[2]
                            - src[leaf].shape[2]:].add(
                            src[leaf][:, 0].astype(sub[leaf].dtype))
                    else:
                        hh[leaf] = jax.tree.map(
                            lambda a, b: a.at[:, r].add(
                                b[:, 0].astype(a.dtype)),
                            sub[leaf], src[leaf])
                h[kind] = hh
        out[gkey] = h
    return out
