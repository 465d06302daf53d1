"""Pallas TPU kernel: tree flash attention (FlashMask → TPU adaptation).

The paper implements its tree mask as a FlashAttention-V3 + FlashMask GPU
kernel (App. A.1).  The TPU-native equivalent built here:

  - visibility is one int per key: visible(i,j) ⇔ j ≤ i ∧ kv_last[j] ≥ i;
  - grid (batch, q_head, q_blocks, kv_blocks); the innermost dim is
    sequential on TPU, so online-softmax accumulators live in VMEM scratch
    across kv steps;
  - MXU-aligned blocks (default 128×128), fp32 accumulation;
  - **block skipping**: a kv block is skipped when it is entirely
    anti-causal (kv_start > q_end), entirely invisible
    (max_j kv_last[j] < q_start — every key's subtree ends before this
    query block), or — with sliding-window attention — entirely out of
    window (min_i pos_q[i] − max_j pos_k[j] ≥ window).  Per-block extrema
    are precomputed XLA-side and prefetched as scalars, so the predicate
    is resolved before any MXU work.  This is the FlashMask block-sparsity
    analogue; skipped blocks still have their DMA issued by the pipeline
    (removing it needs a data-dependent grid — logged as a §Perf follow-up
    in EXPERIMENTS.md).
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

GQA: q head h reads kv head h // (H/Kh) via the BlockSpec index map.
Operands are re-laid out head-major (``head_major``) so that every
block's last two dimensions are (8, 128)-aligned or whole, as the TPU
compiler requires.  Validated on CPU with interpret=True against
kernels/ref.py; ``tests/test_tpu_compile.py`` compiles it for a v5e.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def block_kmax_flat(kv_last, B: int, nk: int, block_k: int):
    """Per-(batch, kv-block) max of kv_last, flattened to 1-D for scalar
    prefetch; indexed with b*nk + ki inside the kernels.  Shared by the
    forward and both backward kernels so the skip inputs cannot drift."""
    return kv_last.reshape(B, nk, block_k).max(-1).reshape(B * nk)


def skip_scalars(kv_last, B: int, nq: int, nk: int, block_q: int,
                 block_k: int, pos_q=None, pos_k=None, window=None):
    """The flat int32 scalar-prefetch array driving ``block_live``.

    Layout: ``[kmax (B·nk)]`` and, when windowed, additionally
    ``[kpos_max (B·nk), qpos_min (B·nq)]`` — indexed with the static
    offsets B·nk and 2·B·nk inside the kernels.  One array (not three)
    keeps ``num_scalar_prefetch=1`` and the index-map signatures stable."""
    kmax = block_kmax_flat(kv_last, B, nk, block_k)
    if window is None:
        return kmax
    kpmax = pos_k.astype(jnp.int32).reshape(B, nk, block_k).max(-1)
    qpmin = pos_q.astype(jnp.int32).reshape(B, nq, block_q).min(-1)
    return jnp.concatenate(
        [kmax, kpmax.reshape(B * nk), qpmin.reshape(B * nq)])


def block_live(q_start, q_end, kv_start, block_max,
               qp_min=None, kp_max=None, window: Optional[int] = None):
    """The block-skip predicate (forward AND backward): a (q-block,
    kv-block) pair is live unless entirely anti-causal (kv_start > q_end),
    entirely invisible (block_max = max_j kv_last[j] < q_start), or —
    windowed — entirely out of window (min_i pos_q − max_j pos_k ≥ window).
    q_start/q_end are *global* query indices (ancestor offset applied).
    Works on traced kernel scalars and on numpy arrays alike."""
    live = (kv_start <= q_end) & (block_max >= q_start)
    if window is not None:
        live = live & ((qp_min - kp_max) < window)
    return live


def block_live_mask(kv_last, S: int, block_q: int, block_k: int,
                    *, q_off: int = 0, pos_q=None, pos_k=None,
                    window: Optional[int] = None):
    """[nq, nk] bool per batch row: which (q-block, kv-block) pairs the
    kernel actually computes.  ``S`` is the query length; the kv length is
    ``kv_last``'s (= q_off + S for gateway layouts).  Used by benchmarks
    to report block sparsity."""
    import numpy as np
    kv_last = np.asarray(kv_last)
    Skv = kv_last.shape[-1]
    nq, nk = S // block_q, Skv // block_k
    kmax = kv_last.reshape(nk, block_k).max(-1)
    qi = np.arange(nq)[:, None]
    ki = np.arange(nk)[None, :]
    qpmin = kpmax = None
    if window is not None:
        qpmin = np.asarray(pos_q).reshape(nq, block_q).min(-1)[:, None]
        kpmax = np.asarray(pos_k).reshape(nk, block_k).max(-1)[None, :]
    return block_live(q_off + qi * block_q,
                      q_off + qi * block_q + block_q - 1,
                      ki * block_k, kmax[None, :], qpmin, kpmax, window)


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


def tree_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   kv_last: jax.Array, scale: float, *,
                   block_q: int = 128, block_k: int = 128,
                   q_off: int = 0, window: Optional[int] = None,
                   pos_q: Optional[jax.Array] = None,
                   pos_k: Optional[jax.Array] = None,
                   save_residuals: bool = False,
                   interpret: bool = False):
    """q: [B,S,H,hd]; k/v: [B,Skv,Kh,hd]; kv_last: [B,Skv] int32
    → [B,S,H,hd].

    ``q_off``: static ancestor offset — query i has global DFS index
    q_off + i (Skv ≥ q_off + S; any key beyond that is padding the caller
    marked kv_last = −1).  ``window``: static sliding-window size over
    *positions*; requires pos_q [B,S] / pos_k [B,Skv].

    With ``save_residuals`` returns ``(o, lse)`` where lse is [B,H,1,S]
    f32 (one row of per-query statistics per head).
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
    nq, nk = S // block_q, Skv // block_k
    kv_last = kv_last.astype(jnp.int32)
    skip = skip_scalars(kv_last, B, nq, nk, block_q, block_k,
                        pos_q, pos_k, window)
    qh, kh, vh, kl, pq, pk = head_major(q, k, v, kv_last, pos_q, pos_k)

    def kernel(skip_ref, *refs):
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
        b = pl.program_id(0)
        qi = pl.program_id(2)
        ki = pl.program_id(3)
        num_kv = pl.num_programs(3)
        q_start = q_off + qi * block_q          # global DFS index
        q_end = q_start + block_q - 1
        kv_start = ki * block_k

        @pl.when(ki == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        if windowed:
            live = block_live(q_start, q_end, kv_start,
                              skip_ref[b * nk + ki],
                              skip_ref[2 * B * nk + b * nq + qi],
                              skip_ref[B * nk + b * nk + ki], window)
        else:
            live = block_live(q_start, q_end, kv_start,
                              skip_ref[b * nk + ki])

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

        @pl.when(ki == num_kv - 1)
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

    in_specs = [
        pl.BlockSpec((None, None, block_q, hd),
                     lambda b, h, qi, ki, skip: (b, h, qi, 0)),
        pl.BlockSpec((None, None, block_k, hd),
                     lambda b, h, qi, ki, skip: (b, h // G, ki, 0)),
        pl.BlockSpec((None, None, block_k, hd),
                     lambda b, h, qi, ki, skip: (b, h // G, ki, 0)),
        pl.BlockSpec((None, 1, block_k),
                     lambda b, h, qi, ki, skip: (b, 0, ki)),
    ]
    inputs = [qh, kh, vh, kl]
    if windowed:
        in_specs += [
            pl.BlockSpec((None, 1, block_q),
                         lambda b, h, qi, ki, skip: (b, 0, qi)),
            pl.BlockSpec((None, 1, block_k),
                         lambda b, h, qi, ki, skip: (b, 0, ki)),
        ]
        inputs += [pq, pk]

    out_shape = [jax.ShapeDtypeStruct((B, H, S, hd), q.dtype)]
    out_specs = [pl.BlockSpec((None, None, block_q, hd),
                              lambda b, h, qi, ki, skip: (b, h, qi, 0))]
    if save_residuals:
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (None, None, 1, block_q),
            lambda b, h, qi, ki, skip: (b, h, 0, qi)))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, nk),
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
    )(skip, *inputs)
    o = out[0].transpose(0, 2, 1, 3)
    if save_residuals:
        return o, out[1]
    return o
