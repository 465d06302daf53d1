"""Pallas TPU kernel: tree flash attention (FlashMask → TPU adaptation).

The paper implements its tree mask as a FlashAttention-V3 + FlashMask GPU
kernel (App. A.1).  The TPU-native equivalent built here:

  - visibility is one int per key: visible(i,j) ⇔ j ≤ i ∧ kv_last[j] ≥ i;
  - MXU-aligned blocks (default 128×128), fp32 accumulation;
  - **live blocks only**: a (q-block, kv-block) pair is dead when it is
    entirely anti-causal (kv_start > q_end), entirely invisible
    (max_j kv_last[j] < q_start — every key's subtree ends before this
    query block), or — with sliding-window attention — entirely out of
    window (min_i pos_q[i] − max_j pos_k[j] ≥ window).  ``block_live`` is
    that predicate.  XLA-side, ``live_table`` packs the live pairs of
    every row into one int32 array, prefetched to SMEM, and the grid is
    (q_head, n) with ``n`` the table's live count, a dynamic grid bound:
    the kernel runs a step, and its pipeline issues a DMA, only for a
    live pair.  This is the FlashMask block-sparsity analogue;
  - the table's entries run in (batch, q-block, kv-block) order, kv
    blocks increasing, so the online-softmax accumulators live in VMEM
    scratch across one q block's consecutive entries; each entry's
    first/last flags open and close them.  A q block with no live kv
    block (padding, an empty row) gets one write-only entry, so every
    output block is written (zeros, ``NEG_INF`` lse);
  - **partition gateways** (paper §3.3): queries may attend a KV sequence
    longer than themselves — ``q_off`` ancestor keys are front-concatenated
    (k/v: [B, q_off + S, ...]).  Query i's global DFS index is
    ``q_off + i``; ancestors are marked always-visible (kv_last = BIG) or
    padding (kv_last = −1) by the caller, so one predicate covers plain,
    windowed, and gateway-extended attention.
  - **sliding window** (long-context variants): with ``window`` set,
    visibility additionally requires pos_q[i] − pos_k[j] < window —
    *positions*, not DFS indices, so the window applies along the path and
    across partition gateways (ancestor positions travel in ``pos_k``).
  - ``save_residuals=True`` additionally emits the per-row logsumexp
    ``lse[b, h, 0, i] = m_i + log(l_i)`` (``NEG_INF`` for fully-masked rows),
    the O(S) statistic the fused backward (tree_attention_bwd.py) needs to
    regenerate softmax probabilities without the O(S²) matrix.

SMEM bound: a row's output block gets at most as many entries as it has
reduction blocks, so the table holds at most B·nq·nk int32 entries —
4·B·nq·nk bytes of the scalar memory (1 MiB on a v5e), 2 KiB at B=2,
S=2048 and 128×128 blocks, 256 KiB at B=1, S=32768.

GQA: q head h reads kv head h // (H/Kh) via the BlockSpec index map.
Operands are re-laid out head-major (``head_major``) so that every
block's last two dimensions are (8, 128)-aligned or whole, as the TPU
compiler requires.  Validated on CPU with interpret=True against
kernels/ref.py; ``tests/test_tpu_compile.py`` compiles it for a v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# One live-table entry, packed into an int32: the reduction block
# (kv block in the forward and dq, q block in dk/dv), the output block,
# the batch row, and three flags.
_INNER_BITS = _OUTER_BITS = 10
_ROW_BITS = 8
ENTRY_LIVE = 1 << 28           # compute this pair (else write-only)
ENTRY_FIRST = 1 << 29          # first entry of its output block
ENTRY_LAST = 1 << 30           # last entry of its output block


def block_live(q_start, q_end, kv_start, block_max,
               qp_min=None, kp_max=None, window: Optional[int] = None):
    """The block-skip predicate (forward AND backward): a (q-block,
    kv-block) pair is live unless entirely anti-causal (kv_start > q_end),
    entirely invisible (block_max = max_j kv_last[j] < q_start), or —
    windowed — entirely out of window (min_i pos_q − max_j pos_k ≥ window).
    q_start/q_end are *global* query indices (ancestor offset applied).
    Works on numpy and jax arrays alike."""
    live = (kv_start <= q_end) & (block_max >= q_start)
    if window is not None:
        live = live & ((qp_min - kp_max) < window)
    return live


def block_live_mask(kv_last, S: int, block_q: int, block_k: int,
                    *, q_off: int = 0, pos_q=None, pos_k=None,
                    window: Optional[int] = None):
    """[..., nq, nk] bool: which (q-block, kv-block) pairs the kernels
    compute, for kv_last [..., Skv] (one row or a batch of rows).  ``S``
    is the query length; the kv length is ``kv_last``'s (= q_off + S for
    gateway layouts).  numpy in, numpy out (the planner's counters and
    the mask checks); a jax array in builds the kernels' live tables."""
    xp = jnp if isinstance(kv_last, jax.Array) else np
    kv_last = xp.asarray(kv_last)
    lead, Skv = kv_last.shape[:-1], kv_last.shape[-1]
    nq, nk = S // block_q, Skv // block_k
    kmax = kv_last.reshape(*lead, nk, block_k).max(-1)[..., None, :]
    qi = xp.arange(nq)[:, None]
    ki = xp.arange(nk)[None, :]
    qpmin = kpmax = None
    if window is not None:
        qpmin = xp.asarray(pos_q).reshape(*lead, nq, block_q).min(-1)
        kpmax = xp.asarray(pos_k).reshape(*lead, nk, block_k).max(-1)
        qpmin, kpmax = qpmin[..., :, None], kpmax[..., None, :]
    return block_live(q_off + qi * block_q,
                      q_off + qi * block_q + block_q - 1,
                      ki * block_k, kmax, qpmin, kpmax, window)


def live_table(live):
    """Pack a live mask [B, X, Y] (X output blocks, Y reduction blocks
    each) into the kernels' entry table, in (b, x, y) order.

    Returns ``(table, n)``: int32 [B·X·Y] and the int32 live count ``n``.
    Entry e < n holds ``y | x << 10 | b << 20`` and the flags
    ``ENTRY_LIVE`` (a live pair), ``ENTRY_FIRST`` / ``ENTRY_LAST`` (first
    and last entry of its (b, x) output block).  An output block with no
    live pair gets one entry at y = 0 with both flags and no
    ``ENTRY_LIVE``, so it is still written.  A (b, x) run has at most Y
    entries, so n ≤ B·X·Y; the grid never reads the zeros beyond n."""
    B, X, Y = live.shape
    assert B <= 1 << _ROW_BITS and max(X, Y) <= 1 << _INNER_BITS, \
        (B, X, Y)
    y = jnp.arange(Y, dtype=jnp.int32)
    emit = live | ((y == 0) & ~live.any(-1, keepdims=True))
    run = jnp.cumsum(emit, -1, dtype=jnp.int32)
    code = (y + (jnp.arange(X, dtype=jnp.int32)[:, None] << _INNER_BITS)
            + (jnp.arange(B, dtype=jnp.int32)[:, None, None]
               << (_INNER_BITS + _OUTER_BITS))
            + jnp.where(live, ENTRY_LIVE, 0)
            + jnp.where(emit & (run == 1), ENTRY_FIRST, 0)
            + jnp.where(emit & (run == run[..., -1:]), ENTRY_LAST, 0))
    emit, code = emit.reshape(-1), code.reshape(-1)
    L = emit.shape[0]
    pos = jnp.cumsum(emit, dtype=jnp.int32) - 1
    n = pos[-1] + 1
    table = jnp.zeros((L,), jnp.int32).at[jnp.where(emit, pos, L)].set(
        code, mode="drop", unique_indices=True)
    return table, n


@functools.partial(jax.jit, static_argnums=(1, 2, 3),
                   static_argnames=("q_off", "window"))
def live_tables(kv_last, S: int, block_q: int, block_k: int, *,
                q_off: int = 0, pos_q=None, pos_k=None,
                window: Optional[int] = None):
    """The three kernels' tables from one ``block_live_mask`` of kv_last
    [B, Skv]: ``(fwd, dkv)``, each a ``live_table`` pair.  ``fwd`` is in
    (b, q-block, kv-block) order, for the forward and dq; ``dkv`` in
    (b, kv-block, q-block) order.  Jitted, so a program that calls it
    again with the same shapes reuses one trace."""
    live = block_live_mask(kv_last.astype(jnp.int32), S, block_q, block_k,
                           q_off=q_off, pos_q=pos_q, pos_k=pos_k,
                           window=window)
    return live_table(live), live_table(live.transpose(0, 2, 1))


def entry_blocks(code):
    """(b, x, y) of a table entry: batch row, output block, reduction
    block.  Scalar integer ops, for index maps and kernel bodies; lax,
    not jnp, since every index map of every kernel call traces them."""
    mask = (1 << _INNER_BITS) - 1
    shr, band = lax.shift_right_logical, lax.bitwise_and
    return (band(shr(code, _INNER_BITS + _OUTER_BITS), (1 << _ROW_BITS) - 1),
            band(shr(code, _INNER_BITS), mask), band(code, mask))


def entry_flags(code):
    """(live, first, last) of a table entry, as scalar booleans."""
    return tuple(lax.bitwise_and(code, f) != 0
                 for f in (ENTRY_LIVE, ENTRY_FIRST, ENTRY_LAST))


def head_major(q, k, v, kv_last, pos_q=None, pos_k=None):
    """The kernels' operand layout: q [B,H,S,hd], k/v [B,Kh,Skv,hd] and
    the per-token int arrays as [B,1,len] rows.  Every block then spans
    whole (8, 128) tiles or a whole dimension, which the TPU compiler
    requires; the per-query rows are turned into columns inside the
    kernels.  Shared by the forward and both backward kernels."""
    ints = [None if a is None else a.astype(jnp.int32)[:, None, :]
            for a in (kv_last, pos_q, pos_k)]
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), *ints)


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_q", "block_k", "q_off", "window", "save_residuals",
    "interpret"))
def tree_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   kv_last: jax.Array, scale: float, *,
                   block_q: int = 128, block_k: int = 128,
                   q_off: int = 0, window: Optional[int] = None,
                   pos_q: Optional[jax.Array] = None,
                   pos_k: Optional[jax.Array] = None,
                   save_residuals: bool = False,
                   interpret: bool = False, table):
    """q: [B,S,H,hd]; k/v: [B,Skv,Kh,hd]; kv_last: [B,Skv] int32
    → [B,S,H,hd].  ``table``: the ``fwd`` pair of ``live_tables`` for
    the same kv_last, blocks and layout.

    ``q_off``: static ancestor offset — query i has global DFS index
    q_off + i (Skv ≥ q_off + S; any key beyond that is padding the caller
    marked kv_last = −1).  ``window``: static sliding-window size over
    *positions*; requires pos_q [B,S] / pos_k [B,Skv].

    With ``save_residuals`` returns ``(o, lse)`` where lse is [B,H,1,S]
    f32 (one row of per-query statistics per head).

    Jitted, like the backward: the programs of a run (packed steps,
    partition waves, their remat recomputations) call the kernels with
    few distinct shapes, and each shape is traced once.
    """
    B, S, H, hd = q.shape
    Skv, Kh = k.shape[1], k.shape[2]
    G = max(1, H // Kh)
    block_q = min(block_q, S)
    block_k = min(block_k, Skv)
    assert S % block_q == 0 and Skv % block_k == 0, \
        (S, Skv, block_q, block_k)
    assert Skv >= q_off + S, (Skv, q_off, S)
    windowed = window is not None
    if windowed:
        assert pos_q is not None and pos_k is not None
    table, n = table
    qh, kh, vh, kl, pq, pk = head_major(q, k, v, kv_last, pos_q, pos_k)

    def kernel(tab_ref, *refs):
        q_ref, k_ref, v_ref, kl_ref = refs[:4]
        rest = refs[4:]
        if windowed:
            pq_ref, pk_ref = rest[:2]
            rest = rest[2:]
        o_ref = rest[0]
        if save_residuals:
            lse_ref, m_scr, l_scr, acc_scr = rest[1:]
        else:
            m_scr, l_scr, acc_scr = rest[1:]
        code = tab_ref[pl.program_id(1)]
        _, qi, ki = entry_blocks(code)
        live, first, last = entry_flags(code)
        q_start = q_off + qi * block_q          # global DFS index
        kv_start = ki * block_k

        @pl.when(first)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(live)
        def _compute():
            qq = q_ref[...].astype(jnp.float32)              # [BQ, hd]
            kk = k_ref[...].astype(jnp.float32)              # [BK, hd]
            vv = v_ref[...].astype(jnp.float32)
            kl = kl_ref[...]                                 # [1, BK]
            logits = jax.lax.dot_general(
                qq, kk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            i_idx = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            j_idx = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            vis = (j_idx <= i_idx) & (kl >= i_idx)
            if windowed:
                vis = vis & ((pq_ref[...].reshape(block_q, 1)
                              - pk_ref[...]) < window)
            lg = jnp.where(vis, logits, NEG_INF)
            m_prev = m_scr[...]                              # [BQ, 1]
            m_new = jnp.maximum(m_prev, lg.max(axis=1, keepdims=True))
            p = jnp.where(vis, jnp.exp(lg - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
                p, vv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        @pl.when(last)
        def _finalize():
            l = l_scr[...]
            o = acc_scr[...] / jnp.maximum(l, 1e-37)
            o = jnp.where(l > 0, o, 0.0)
            o_ref[...] = o.astype(o_ref.dtype)
            if save_residuals:
                m = m_scr[...]
                lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)),
                                NEG_INF)
                lse_ref[...] = lse.reshape(1, block_q)

    def at(fn):
        """An index map over grid (h, e) from (b, h, qi, ki)."""
        return lambda h, e, tab: fn(h, *entry_blocks(tab[e]))

    in_specs = [
        pl.BlockSpec((None, None, block_q, hd),
                     at(lambda h, b, qi, ki: (b, h, qi, 0))),
        pl.BlockSpec((None, None, block_k, hd),
                     at(lambda h, b, qi, ki: (b, h // G, ki, 0))),
        pl.BlockSpec((None, None, block_k, hd),
                     at(lambda h, b, qi, ki: (b, h // G, ki, 0))),
        pl.BlockSpec((None, 1, block_k),
                     at(lambda h, b, qi, ki: (b, 0, ki))),
    ]
    inputs = [qh, kh, vh, kl]
    if windowed:
        in_specs += [
            pl.BlockSpec((None, 1, block_q),
                         at(lambda h, b, qi, ki: (b, 0, qi))),
            pl.BlockSpec((None, 1, block_k),
                         at(lambda h, b, qi, ki: (b, 0, ki))),
        ]
        inputs += [pq, pk]

    out_shape = [jax.ShapeDtypeStruct((B, H, S, hd), q.dtype)]
    out_specs = [pl.BlockSpec((None, None, block_q, hd),
                              at(lambda h, b, qi, ki: (b, h, qi, 0)))]
    if save_residuals:
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (None, None, 1, block_q),
            at(lambda h, b, qi, ki: (b, h, 0, qi))))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, n),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, hd), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="tree_attn_fwd",
    )(table, *inputs)
    o = out[0].transpose(0, 2, 1, 3)
    if save_residuals:
        return o, out[1]
    return o
