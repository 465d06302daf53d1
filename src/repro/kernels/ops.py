"""jit'd public wrapper for the tree-attention kernel.

Dispatches to the Pallas TPU kernels on TPU backends and to interpret mode
on CPU (kernel body executed in Python — bit-level semantics identical).
The custom_vjp is fully fused: the forward saves only O(S) logsumexp
residuals and the backward runs the flash-style recomputation kernels in
kernels/tree_attention_bwd.py (dq, dk, dv) over the same live blocks as
the forward: the op builds the three kernels' live-block tables once per
call, from one mask (``live_tables``), and names them ``LIVE_TABLES``
so that a checkpointed layer stack keeps them instead of rebuilding them
in its backward.  The dense jnp reference
(kernels/ref.py) is no longer on the training path — it survives purely
as the test oracle.

Partition gateways (paper §3.3) and sliding windows ride the same op:
``q_off`` front-concatenated ancestor keys extend the KV axis (query i's
global index is q_off + i), and ``window``/``pos_q``/``pos_k`` add the
position-based sliding-window term to the visibility predicate.  The
backward emits dk/dv for the FULL KV length, so the ancestor cotangents
(d_extra_k/d_extra_v) flow out through the caller's concatenation — XLA's
concat transpose slices them back apart for the fp32 child→parent routing
in core/gateway.py.  Awkward lengths (real ancestor depths are not
MXU-aligned) are back-padded here to a multiple of the block: keys with
invisible ones (kv_last = −1), queries with rows that are sliced off.
The padding lives outside the custom_vjp, so its cotangent slice-off is
free and automatic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro import sharding as sh
from repro.kernels.tree_attention import block_live_mask, live_tables
from repro.kernels.tree_attention import tree_attention as _pallas_fwd
from repro.kernels.tree_attention_bwd import tree_attention_bwd as _pallas_bwd


# the name of the kernels' live-block tables in a checkpoint policy
LIVE_TABLES = "tree_attn_live_tables"
BLOCK = 128                      # the op's default q and kv block


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def platform_impl() -> str:
    """The attention implementation this platform trains with: the fused
    Pallas kernels on a TPU, the dense ``ref`` oracle elsewhere (where the
    kernels could only run interpreted)."""
    return "pallas" if _on_tpu() else "ref"


def _fit_block(n: int, want: int) -> int:
    """Block for a length-``n`` axis: ``want``, or the whole axis when it
    is shorter.  A TPU block must span whole (8, 128) tiles or the whole
    array, so ``tree_attention`` pads a longer axis to a multiple of the
    block instead of shrinking the block to a divisor of ``n``."""
    return min(want, n)


def block_counts(kv_last, pos_ids=None,
                 window: Optional[int] = None) -> tuple[int, int]:
    """(live, dense): the (q-block, kv-block) pairs that ``tree_attention``
    computes for host rows kv_last [B, S] (numpy), and all pairs of its
    grid, with the op's default blocks, fit and padding.  Per head and
    per call; the planner's ``attn_blocks_live`` / ``attn_blocks_dense``."""
    kv_last = np.asarray(kv_last)
    S = kv_last.shape[-1]
    bq = _fit_block(S, BLOCK)
    pad = -S % bq
    kv_last = np.pad(kv_last, ((0, 0), (0, pad)), constant_values=-1)
    if window is not None:
        pos_ids = np.pad(np.asarray(pos_ids), ((0, 0), (0, pad)))
    live = block_live_mask(kv_last, S + pad, bq, bq, pos_q=pos_ids,
                           pos_k=pos_ids, window=window)
    return int(live.sum()), int(live.size)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _tree_attention(q, k, v, kv_last, pos_q, pos_k, tables,
                    scale, window, q_off, block_q, block_k):
    with jax.named_scope("tree_attn_fwd"):
        return _pallas_fwd(q, k, v, kv_last, scale, block_q=block_q,
                           block_k=block_k, q_off=q_off, window=window,
                           pos_q=pos_q, pos_k=pos_k, table=tables[0],
                           interpret=not _on_tpu())


def _fwd(q, k, v, kv_last, pos_q, pos_k, tables, scale, window, q_off,
         block_q, block_k):
    with jax.named_scope("tree_attn_fwd"):
        o, lse = _pallas_fwd(q, k, v, kv_last, scale, block_q=block_q,
                             block_k=block_k, q_off=q_off, window=window,
                             pos_q=pos_q, pos_k=pos_k, save_residuals=True,
                             table=tables[0], interpret=not _on_tpu())
    return o, (q, k, v, kv_last, pos_q, pos_k, tables, o, lse)


def _bwd(scale, window, q_off, block_q, block_k, res, do):
    q, k, v, kv_last, pos_q, pos_k, tables, o, lse = res
    dq, dk, dv = _pallas_bwd(q, k, v, kv_last, o, lse, do, scale,
                             block_q=block_q, block_k=block_k,
                             q_off=q_off, window=window, pos_q=pos_q,
                             pos_k=pos_k, tables=tables,
                             interpret=not _on_tpu())
    return dq, dk, dv, None, None, None, None


_tree_attention.defvjp(_fwd, _bwd)


def _with_tables(q, k, v, kv_last, pos_q, pos_k, scale, window, q_off,
                 block_q, block_k):
    """``_tree_attention`` with the kernels' live-block tables, built from
    kv_last and named ``LIVE_TABLES``.  A layer stack's checkpoint policy
    saves them, so its backward reads them where it would rebuild them
    in every layer; kv_last is the same in every layer of a step."""
    with jax.named_scope("tree_attn_fwd"):
        tables = live_tables(kv_last, q.shape[1], block_q, block_k,
                             q_off=q_off, pos_q=pos_q, pos_k=pos_k,
                             window=window)
    tables = jax.tree.map(lambda t: checkpoint_name(t, LIVE_TABLES), tables)
    return _tree_attention(q, k, v, kv_last, pos_q, pos_k, tables, scale,
                           window, q_off, block_q, block_k)


def _partitioned(q, k, v, kv_last, pos_q, pos_k, scale, window, q_off,
                 block_q, block_k):
    """``_tree_attention`` under the active mesh.  XLA cannot partition a
    Pallas TPU kernel, so on a mesh of several devices each runs it on its
    own rows (batch over the data axes) and, where both head counts
    divide the model axis, its own heads; rows are independent, and so
    are heads (a kv head's query group stays on its device)."""
    mesh = sh.current_mesh()
    if mesh is None or mesh.size == 1:
        return _with_tables(q, k, v, kv_last, pos_q, pos_k, scale, window,
                            q_off, block_q, block_k)
    daxes, m = sh._CTX.data_axes, sh._CTX.model_axis
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    bx = daxes if q.shape[0] % dsize == 0 else None
    hx = m if (q.shape[2] % mesh.shape[m] == 0
               and k.shape[2] % mesh.shape[m] == 0) else None
    heads = P(bx, None, hx, None)
    rows = P(bx, None)
    windowed = window is not None

    def local(q, k, v, kv_last, *pos):
        pq, pk = pos if windowed else (None, None)
        return _with_tables(q, k, v, kv_last, pq, pk, scale, window,
                            q_off, block_q, block_k)

    pos = (pos_q, pos_k) if windowed else ()
    # check_vma=False: pallas_call output shapes carry no varying-axes
    # annotation; every operand is split the same way, so none is needed
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(heads, heads, heads, rows,
                                   *([rows] * len(pos))),
                         out_specs=heads, check_vma=False)(
        q, k, v, kv_last, *pos)


def _pad_axis1(x, pad, value=0):
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def tree_attention(q, k, v, kv_last, scale: float,
                   block_q: int = BLOCK, block_k: int = BLOCK, *,
                   q_off: int = 0, window: Optional[int] = None,
                   pos_q: Optional[jax.Array] = None,
                   pos_k: Optional[jax.Array] = None):
    """Fused tree attention.  q: [B,S,H,hd]; k/v: [B,Skv,Kh,hd] with
    Skv = q_off + S (q_off ancestor keys front-concatenated); kv_last:
    [B,Skv].  ``window`` (static) adds the sliding-window visibility term
    over positions pos_q [B,S] / pos_k [B,Skv].  Differentiable in q, k, v
    — the k/v cotangents cover the ancestor rows too."""
    if window is None:
        pos_q = pos_k = None          # unused: keep them out of residuals
    S, Skv = q.shape[1], k.shape[1]
    # back-pad both axes to whole blocks: extra queries are sliced off (a
    # zero cotangent, so they add nothing to dk/dv), extra keys are
    # invisible (kv_last = −1); the pads sit outside the custom_vjp, so
    # the cotangents slice back automatically
    bq = _fit_block(S, block_q)
    pad_q = -S % bq
    need = max(Skv, q_off + S + pad_q)
    bk = _fit_block(need, block_k)
    pad_k = need - Skv + (-need % bk)
    q = _pad_axis1(q, pad_q)
    k, v = _pad_axis1(k, pad_k), _pad_axis1(v, pad_k)
    kv_last = _pad_axis1(kv_last, pad_k, -1)
    if window is not None:
        pos_q, pos_k = _pad_axis1(pos_q, pad_q), _pad_axis1(pos_k, pad_k)
    o = _partitioned(q, k, v, kv_last, pos_q, pos_k, scale, window, q_off,
                     bq, bk)
    return o[:, :S] if pad_q else o


def prefill_attention(q, k, v, scale: float, *,
                      ctx_k: Optional[jax.Array] = None,
                      ctx_v: Optional[jax.Array] = None,
                      ctx_valid: Optional[jax.Array] = None,
                      window: Optional[int] = None,
                      pos_q: Optional[jax.Array] = None,
                      ctx_pos: Optional[jax.Array] = None,
                      block_q: int = BLOCK, block_k: int = BLOCK):
    """Shared-prefix prefill through the fused tree kernel.

    The decode-session prefill shape: S new chain tokens (q: [B,S,H,hd],
    their already-roped keys/values k/v: [B,S,Kh,hd]) attend causally to
    themselves plus ``ctx_k``/``ctx_v`` [B,A,Kh,hd] — a previously
    prefilled (possibly forked) prefix whose KV was computed ONCE and is
    visible everywhere ``ctx_valid`` [B,A] holds.  That is exactly the
    partition-gateway layout, so it lowers to ``tree_attention`` with
    ``q_off=A``: no re-scoring of the context against itself, and the
    same Pallas kernel that trains the tree serves its rollouts.
    ``window`` adds the sliding-window term over ``pos_q`` [B,S] /
    ``ctx_pos`` [B,A] absolute positions.  Returns [B,S,H,hd]."""
    B, S = q.shape[:2]
    kv_last = jnp.broadcast_to(jnp.asarray(S - 1, jnp.int32), (B, S))
    if ctx_k is None:
        return tree_attention(q, k, v, kv_last, scale, block_q, block_k,
                              window=window, pos_q=pos_q, pos_k=pos_q)
    A = ctx_k.shape[1]
    big = jnp.asarray(1 << 30, jnp.int32)
    ctx_last = jnp.broadcast_to(big, (B, A))
    if ctx_valid is not None:
        ctx_last = jnp.where(ctx_valid, big, -1)
    pos_k = None
    if window is not None:
        if ctx_pos is None or pos_q is None:
            raise ValueError("window needs pos_q and ctx_pos")
        pos_k = jnp.concatenate([ctx_pos, pos_q], axis=1)
    return tree_attention(q, jnp.concatenate([ctx_k, k], axis=1),
                          jnp.concatenate([ctx_v, v], axis=1),
                          jnp.concatenate([ctx_last, kv_last + A], axis=1),
                          scale, block_q, block_k, q_off=A,
                          window=window, pos_q=pos_q, pos_k=pos_k)
