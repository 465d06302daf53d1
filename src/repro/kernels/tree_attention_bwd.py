"""Pallas TPU kernels: fused backward for tree flash attention.

Flash-style recomputation: the forward (tree_attention.py with
``save_residuals=True``) saves only the per-row logsumexp
``lse[b,h,0,i] = m_i + log l_i`` — O(S) instead of the O(S²) probability
matrix — and the backward regenerates ``p_ij = exp(s_ij − lse_i)`` block
by block on the fly.  With ``Δ_i = Σ_d do_id·o_id`` (precomputed XLA-side,
one elementwise reduction):

    dv_j = Σ_i p_ij do_i
    ds_ij = p_ij (do_i·v_j − Δ_i) · scale
    dq_i = Σ_j ds_ij k_j
    dk_j = Σ_i ds_ij q_i

Both kernels reuse the forward's visibility predicate — global query
index ``i = q_off + i_local``, ``j ≤ i ∧ kv_last[j] ≥ i``, and (windowed)
``pos_q[i] − pos_k[j] < window`` — and walk only the live blocks of the
forward's ``block_live_mask``, through ``live_table``, so gateway-extended
KV layouts
(front-concatenated ancestors, paper §3.3) and sliding-window configs
backprop through exactly the visibility the forward computed.  dk/dv are
produced for the FULL KV length: rows [0, q_off) are the ancestor
cotangents (``d_extra_k``/``d_extra_v``) the partition driver routes back
to the parent partition.  Fully-masked rows (padding, lse = NEG_INF)
contribute nothing because the visibility mask already zeroes every p
entry in their row.

Two kernels because the two reductions run along opposite grid axes and
TPU output revisiting must be consecutive:

  - **dq**: grid (H, n) over the forward's table — (b, q-block, kv-block)
    entries, kv blocks increasing; dq accumulated in VMEM scratch from a
    q block's first entry and written at its last (mirrors forward).
  - **dk/dv**: grid (Kh, n, G) over the transposed table — (b, kv-block,
    q-block) entries — with the G query heads of a group as the static
    innermost axis, so the GQA head-group reduction happens in-kernel in
    the same VMEM accumulator (no [B,S,H,hd] staging buffer + XLA
    reduction afterwards).  A kv block's sum runs over q blocks, and
    within each over the group's heads.  A kv block with no live q block
    gets one write-only entry: zero dk/dv.

``n`` is each table's live count, a dynamic grid bound; both tables come
from one ``block_live_mask`` (``live_tables``) and share its SMEM bound
(B·nq·nk entries).

Both run on the forward's head-major operand layout
(``tree_attention.head_major``).  Validated on CPU with interpret=True
against jax.vjp through kernels/ref.py (tests/test_kernels_bwd.py);
``tests/test_tpu_compile.py`` compiles them for a v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tree_attention import (entry_blocks, entry_flags,
                                         head_major)

NEG_INF = -1e30


def _vis_and_p(qq, kk, kl, lse, scale, q_start, kv_start, block_q, block_k,
               pq=None, pk=None, window=None):
    """Recompute the masked probability block p_ij = exp(s_ij − lse_i).
    ``q_start`` is the GLOBAL query index of the block's first row; kl/pk
    are [1, BK] rows, lse/pq [BQ, 1] columns."""
    logits = jax.lax.dot_general(
        qq, kk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    i_idx = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    j_idx = kv_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    vis = (j_idx <= i_idx) & (kl >= i_idx)
    if window is not None:
        vis = vis & ((pq - pk) < window)
    # clamp the exponent: invisible entries are discarded by the select but
    # must not overflow to inf first (inf is fine for select, but keep the
    # VPU in normal range); visible entries satisfy s ≤ m ≤ lse + log l.
    expo = jnp.where(vis, logits - lse, NEG_INF)
    return jnp.where(vis, jnp.exp(expo), 0.0)


def _bwd_dq(qh, kh, vh, kl, lse, delta, doh, scale, tab,
            block_q, block_k, q_off, window, pq, pk, interpret):
    """dq over ``tab`` = ``live_table(mask)``: (b, q-block, kv-block)."""
    B, H, S, hd = qh.shape
    Kh = kh.shape[1]
    G = max(1, H // Kh)
    windowed = window is not None
    table, n = tab

    def kernel(tab_ref, *refs):
        q_ref, k_ref, v_ref, kl_ref, lse_ref, dl_ref, do_ref = refs[:7]
        rest = refs[7:]
        if windowed:
            pq_ref, pk_ref = rest[:2]
            rest = rest[2:]
        dq_ref, dq_scr = rest
        code = tab_ref[pl.program_id(1)]
        _, qi, ki = entry_blocks(code)
        live, first, last = entry_flags(code)
        q_start = q_off + qi * block_q          # global DFS index
        kv_start = ki * block_k

        @pl.when(first)
        def _init():
            dq_scr[...] = jnp.zeros_like(dq_scr)

        @pl.when(live)
        def _compute():
            qq = q_ref[...].astype(jnp.float32)             # [BQ, hd]
            kk = k_ref[...].astype(jnp.float32)             # [BK, hd]
            vv = v_ref[...].astype(jnp.float32)
            kl = kl_ref[...]                                # [1, BK]
            lse = lse_ref[...].reshape(block_q, 1)
            dlt = dl_ref[...].reshape(block_q, 1)
            dd = do_ref[...].astype(jnp.float32)            # [BQ, hd]
            pqc = pq_ref[...].reshape(block_q, 1) if windowed else None
            pkr = pk_ref[...] if windowed else None
            p = _vis_and_p(qq, kk, kl, lse, scale, q_start, kv_start,
                           block_q, block_k, pqc, pkr, window)
            dp = jax.lax.dot_general(                        # do·vᵀ [BQ,BK]
                dd, vv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dlt) * scale
            dq_scr[...] += jax.lax.dot_general(              # ds·k [BQ,hd]
                ds, kk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _finalize():
            dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)

    def at(fn):
        """An index map over grid (h, e) from (b, h, qi, ki)."""
        return lambda h, e, tab: fn(h, *entry_blocks(tab[e]))

    qspec = pl.BlockSpec((None, None, block_q, hd),
                         at(lambda h, b, qi, ki: (b, h, qi, 0)))
    kspec = pl.BlockSpec((None, None, block_k, hd),
                         at(lambda h, b, qi, ki: (b, h // G, ki, 0)))
    rowq = pl.BlockSpec((None, None, 1, block_q),
                        at(lambda h, b, qi, ki: (b, h, 0, qi)))
    in_specs = [qspec, kspec, kspec,
                pl.BlockSpec((None, 1, block_k),
                             at(lambda h, b, qi, ki: (b, 0, ki))),
                rowq, rowq, qspec]
    inputs = [qh, kh, vh, kl, lse, delta, doh]
    if windowed:
        in_specs += [
            pl.BlockSpec((None, 1, block_q),
                         at(lambda h, b, qi, ki: (b, 0, qi))),
            pl.BlockSpec((None, 1, block_k),
                         at(lambda h, b, qi, ki: (b, 0, ki))),
        ]
        inputs += [pq, pk]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, n),
            in_specs=in_specs,
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), qh.dtype),
        interpret=interpret,
        name="tree_attn_dq",
    )(table, *inputs)


def _bwd_dkv(qh, kh, vh, kl, lse, delta, doh, scale, tab,
             block_q, block_k, q_off, window, pq, pk, interpret):
    """dk/dv over ``tab`` = ``live_table`` of the transposed mask:
    (b, kv-block, q-block)."""
    B, H, S, hd = qh.shape
    Kh, Skv = kh.shape[1], kh.shape[2]
    G = max(1, H // Kh)
    windowed = window is not None
    table, n = tab

    def kernel(tab_ref, *refs):
        q_ref, k_ref, v_ref, kl_ref, lse_ref, dl_ref, do_ref = refs[:7]
        rest = refs[7:]
        if windowed:
            pq_ref, pk_ref = rest[:2]
            rest = rest[2:]
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        code = tab_ref[pl.program_id(1)]
        _, ki, qi = entry_blocks(code)
        live, first, last = entry_flags(code)
        g = pl.program_id(2)
        q_start = q_off + qi * block_q          # global DFS index
        kv_start = ki * block_k

        @pl.when(first & (g == 0))
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        @pl.when(live)
        def _compute():
            qq = q_ref[...].astype(jnp.float32)             # [BQ, hd]
            kk = k_ref[...].astype(jnp.float32)             # [BK, hd]
            vv = v_ref[...].astype(jnp.float32)
            kl = kl_ref[...]                                # [1, BK]
            lse = lse_ref[...].reshape(block_q, 1)
            dlt = dl_ref[...].reshape(block_q, 1)
            dd = do_ref[...].astype(jnp.float32)            # [BQ, hd]
            pqc = pq_ref[...].reshape(block_q, 1) if windowed else None
            pkr = pk_ref[...] if windowed else None
            p = _vis_and_p(qq, kk, kl, lse, scale, q_start, kv_start,
                           block_q, block_k, pqc, pkr, window)
            dv_scr[...] += jax.lax.dot_general(              # pᵀ·do [BK,hd]
                p, dd, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                dd, vv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dlt) * scale
            dk_scr[...] += jax.lax.dot_general(              # dsᵀ·q [BK,hd]
                ds, qq, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(last & (g == G - 1))
        def _finalize():
            dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    def at(fn):
        """An index map over grid (kh, e, g) from (b, kh, g, ki, qi)."""
        return lambda kh, e, g, tab: fn(kh, g, *entry_blocks(tab[e]))

    qspec = pl.BlockSpec(
        (None, None, block_q, hd),
        at(lambda kh, g, b, ki, qi: (b, kh * G + g, qi, 0)))
    kspec = pl.BlockSpec(
        (None, None, block_k, hd),
        at(lambda kh, g, b, ki, qi: (b, kh, ki, 0)))
    rowq = pl.BlockSpec(
        (None, None, 1, block_q),
        at(lambda kh, g, b, ki, qi: (b, kh * G + g, 0, qi)))
    in_specs = [qspec, kspec, kspec,
                pl.BlockSpec((None, 1, block_k),
                             at(lambda kh, g, b, ki, qi: (b, 0, ki))),
                rowq, rowq, qspec]
    inputs = [qh, kh, vh, kl, lse, delta, doh]
    if windowed:
        in_specs += [
            pl.BlockSpec((None, 1, block_q),
                         at(lambda kh, g, b, ki, qi: (b, 0, qi))),
            pl.BlockSpec((None, 1, block_k),
                         at(lambda kh, g, b, ki, qi: (b, 0, ki))),
        ]
        inputs += [pq, pk]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Kh, n, G),
            in_specs=in_specs,
            out_specs=[kspec, kspec],
            scratch_shapes=[
                pltpu.VMEM((block_k, hd), jnp.float32),
                pltpu.VMEM((block_k, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Kh, Skv, hd), kh.dtype),
            jax.ShapeDtypeStruct((B, Kh, Skv, hd), vh.dtype),
        ],
        interpret=interpret,
        name="tree_attn_dkv",
    )(table, *inputs)
    return out[0], out[1]


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_q", "block_k", "q_off", "window", "interpret"))
def tree_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                       kv_last: jax.Array, o: jax.Array, lse: jax.Array,
                       do: jax.Array, scale: float, *,
                       block_q: int = 128, block_k: int = 128,
                       q_off: int = 0, window: Optional[int] = None,
                       pos_q: Optional[jax.Array] = None,
                       pos_k: Optional[jax.Array] = None,
                       interpret: bool = False, tables):
    """Fused dq/dk/dv for tree attention.

    q/o/do: [B,S,H,hd]; k/v: [B,Skv,Kh,hd]; kv_last: [B,Skv] int32;
    lse: [B,H,1,S] f32 from the forward's ``save_residuals=True``.
    q_off/window/pos_q/pos_k: same gateway/window layout as the forward;
    ``tables``: the forward's ``live_tables``, ``(fwd, dkv)``.
    Returns (dq, dk, dv) in the input dtypes; dk/dv cover the full Skv,
    including the ancestor rows [0, q_off) (d_extra_k / d_extra_v).
    """
    B, S, H, hd = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, Skv)
    assert S % block_q == 0 and Skv % block_k == 0, \
        (S, Skv, block_q, block_k)
    assert Skv >= q_off + S, (Skv, q_off, S)
    kv_last = kv_last.astype(jnp.int32)
    # Δ_i = Σ_d do_id o_id, [B,H,1,S] — cheap elementwise reduce, XLA-side.
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)
             ).sum(-1).transpose(0, 2, 1)[:, :, None, :]
    qh, kh, vh, kl, pq, pk = head_major(q, k, v, kv_last, pos_q, pos_k)
    doh = do.transpose(0, 2, 1, 3)
    args = (qh, kh, vh, kl, lse, delta, doh, scale)
    rest = (block_q, block_k, q_off, window, pq, pk, interpret)
    with jax.named_scope("tree_attn_dq"):
        dq = _bwd_dq(*args, tables[0], *rest)
    with jax.named_scope("tree_attn_dkv"):
        dk, dv = _bwd_dkv(*args, tables[1], *rest)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))
