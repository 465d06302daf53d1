"""Kernel mask-soundness checker: prove ``block_live`` never skips work.

The Pallas tree-attention kernels (fwd and bwd) run a (q-block,
kv-block) pair only when ``kernels/tree_attention.block_live`` (through
``block_live_mask`` and the kernels' ``live_table``) says a visible
(query, key) pair can exist inside it.  An unsound predicate silently
zeroes attention — gradients stay finite and training "works", just
wrong.  This pass verifies soundness *statically*, with no kernel
launch:

  boundary sweep    for every bucketed (block shape, q_off, window)
                    combination the configs can reach, enumerate the
                    predicate's scalar inputs at their boundary values
                    (block_max at q_start±1/q_end±1, window gap at
                    window±1, …) and check the predicate against an
                    independent per-pair oracle — the ref.py visibility
                    ``j ≤ i ∧ kv_last[j] ≥ i ∧ pos_q−pos_k < window``
                    evaluated on the *extremal* in-block assignment
                    (every kv_last at the block max, every position at
                    its extremum).  Visibility is monotone in kv_last and
                    anti-monotone in the position gap, so the extremal
                    assignment dominates every concrete block and the
                    boundary values dominate the integer ranges between
                    them: the finite sweep is exhaustive over the bucket
                    universe.
  empirical sweep   pack real random trees into rows and require
                    ``block_live_mask`` ⊇ the dense per-pair visibility,
                    reporting the proven block-skip rate.

Also pins the fwd/bwd kernels to the SAME tables, built in one call
from one mask — a fork of the skip logic between them is exactly the
drift this file exists to prevent.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from repro.kernels.tree_attention import block_live, block_live_mask

BIG = 1 << 20


@dataclass
class MaskPoint:
    """One predicate evaluation point (all *global* query indices)."""
    q_start: int
    q_end: int
    kv_start: int
    kv_end: int
    block_max: int
    window: Optional[int] = None
    gap: int = 0              # qp_min − kp_max (windowed only)


def oracle_any_visible(pt: MaskPoint) -> bool:
    """Independent per-pair oracle (ref.py visibility, evaluated on the
    extremal in-block assignment): does ANY (query i, key j) pair inside
    the block admit visibility under the block's summary scalars?"""
    i = np.arange(pt.q_start, pt.q_end + 1)[:, None]
    j = np.arange(pt.kv_start, pt.kv_end + 1)[None, :]
    vis = (j <= i) & (pt.block_max >= i)      # kv_last[j] ≡ block_max
    if pt.window is not None:                 # pos at extrema: gap const
        vis = vis & (pt.gap < pt.window)
    return bool(vis.any())


def predicate_live(pt: MaskPoint, live_fn: Callable = block_live) -> bool:
    if pt.window is None:
        return bool(live_fn(pt.q_start, pt.q_end, pt.kv_start,
                            pt.block_max))
    kp_max = BIG
    return bool(live_fn(pt.q_start, pt.q_end, pt.kv_start, pt.block_max,
                        qp_min=kp_max + pt.gap, kp_max=kp_max,
                        window=pt.window))


def boundary_points(block_q: int, block_k: int, q_off: int,
                    window: Optional[int], nq: int = 3):
    """Boundary-value enumeration for one bucket: all q blocks of a
    small grid, kv blocks straddling each causal/visibility boundary,
    block_max and window gap at ±1 around every decision threshold."""
    S_kv = q_off + nq * block_q
    nk = -(-S_kv // block_k)
    gaps = ([0] if window is None else
            sorted({-3, 0, window - 2, window - 1, window, window + 1,
                    BIG // 2}))
    for qi in range(nq):
        q_start = q_off + qi * block_q
        q_end = q_start + block_q - 1
        kis = sorted({0, q_start // block_k - 1, q_start // block_k,
                      q_end // block_k, q_end // block_k + 1, nk - 1})
        for ki in kis:
            if ki < 0 or ki >= nk:
                continue
            kv_start = ki * block_k
            kv_end = kv_start + block_k - 1
            for m in sorted({-1, q_start - 1, q_start, q_end, q_end + 1,
                             S_kv + 7}):
                for g in gaps:
                    yield MaskPoint(q_start, q_end, kv_start, kv_end, m,
                                    window, g)


def _fit_blocks(seq_lens, want: int = 128) -> set:
    from repro.kernels.ops import _fit_block
    return {_fit_block(S, want) for S in seq_lens}


def bucket_universe(fast: bool = False) -> list[tuple]:
    """(block_q, block_k, q_off, window) combinations reachable from
    configs/*: seq buckets → ``ops._fit_block`` block sizes, gateway
    ancestor pads → pow2 q_off ≥ 8, windows → {None} plus the
    long-context 8192 and adversarial small values."""
    seq_caps = [128, 256] if fast else [128, 256, 512, 1024, 2048, 4096]
    blocks = sorted(_fit_blocks(seq_caps) | {8, 16})
    off_cap = 64 if fast else 1024
    q_offs = [0] + [b for b in
                    (8 << i for i in range(20)) if b <= off_cap]
    windows = [None, 63, 8192] if fast else [None, 1, 7, 63, 257, 8192]
    return [(bq, bq, q_off, w)
            for bq in blocks for q_off in q_offs for w in windows]


def check_predicate(live_fn: Callable = block_live, *,
                    buckets=None, fast: bool = False
                    ) -> tuple[list, dict]:
    """Sweep the bucket universe; a finding is a block the predicate
    skips while the oracle proves a visible pair exists (unsoundness).
    The report carries the proven-safe skip fraction and the count of
    live-but-empty blocks (completeness, informational only)."""
    from repro.analysis.jaxpr_audit import Finding
    buckets = bucket_universe(fast) if buckets is None else buckets
    findings: list = []
    total = skipped_safe = live_empty = 0
    for bq, bk, q_off, window in buckets:
        for pt in boundary_points(bq, bk, q_off, window):
            total += 1
            live = predicate_live(pt, live_fn)
            vis = oracle_any_visible(pt)
            if vis and not live:
                findings.append(Finding(
                    "kernels.block_live", "mask",
                    f"UNSOUND skip: block q[{pt.q_start},{pt.q_end}] × "
                    f"kv[{pt.kv_start},{pt.kv_end}] block_max="
                    f"{pt.block_max} window={pt.window} gap={pt.gap} "
                    f"holds a visible pair but the predicate skips it"))
                if len(findings) >= 20:
                    report = {"points": total, "buckets": len(buckets),
                              "truncated": True}
                    return findings, report
            elif not live:
                skipped_safe += 1
            elif not vis:
                live_empty += 1
    report = {
        "points": total,
        "buckets": len(buckets),
        "proven_skip_rate": skipped_safe / max(total, 1),
        "live_empty_blocks": live_empty,
        "unsound_skips": len(findings),
    }
    return findings, report


def check_bwd_shares_predicate() -> list:
    """The backward kernels must walk THE SAME live blocks as the forward
    — the op builds all three kernels' tables in one call, from one mask
    (``tree_attention.live_tables``); proven by identity, so the skip
    logic cannot fork."""
    from repro.analysis.jaxpr_audit import Finding
    from repro.kernels import ops
    from repro.kernels import tree_attention as fwd
    out = []
    if getattr(ops, "live_tables", None) is not fwd.live_tables:
        out.append(Finding(
            "kernels.ops", "mask",
            "the op does not build its tables with "
            "tree_attention.live_tables — fwd/bwd skip predicates can "
            "drift apart"))
    return out


def empirical_mask_check(*, seeds=(0, 1, 2), seq_len: int = 128,
                         block: int = 32, window: Optional[int] = None
                         ) -> tuple[list, dict]:
    """Pack real random trees and require the kernel's block mask to
    cover every block holding a dense-visible pair; report the proven
    skip rate on realistic packings."""
    from repro.analysis.jaxpr_audit import Finding
    from repro.core.packing import materialize_tree_rows, plan_tree_rows
    from repro.core.tree import serialize_tree
    from repro.data.synthetic import random_tree

    findings: list = []
    total = live = 0
    for seed in seeds:
        rng = np.random.default_rng(seed)
        sers = [serialize_tree(random_tree(rng, vocab_size=97))
                for _ in range(6)]
        sers = [s for s in sers if s.n <= seq_len]
        rows = plan_tree_rows([s.n for s in sers], seq_len)
        tb = materialize_tree_rows(sers, rows, seq_len)
        nq = nk = seq_len // block
        for r in range(tb.tokens.shape[0]):
            kv_last = tb.kv_last[r]
            pos = tb.pos_ids[r]
            mask = np.asarray(block_live_mask(
                kv_last, seq_len, block, block,
                pos_q=pos if window else None,
                pos_k=pos if window else None, window=window))
            i = np.arange(seq_len)[:, None]
            j = np.arange(seq_len)[None, :]
            vis = (j <= i) & (kv_last[None, :] >= i)
            if window is not None:
                vis &= (pos[:, None] - pos[None, :]) < window
            vis_blocks = vis.reshape(nq, block, nk, block).any((1, 3))
            bad = vis_blocks & ~mask
            if bad.any():
                qi, ki = map(int, np.argwhere(bad)[0])
                findings.append(Finding(
                    "kernels.block_live_mask", "mask",
                    f"seed {seed} row {r}: visible pair in block "
                    f"({qi},{ki}) skipped by the packed-row mask"))
            total += mask.size
            live += int(mask.sum())
    report = {"blocks": total,
              "proven_skip_rate": 1.0 - live / max(total, 1)}
    return findings, report
