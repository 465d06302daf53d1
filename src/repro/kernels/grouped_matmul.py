"""Grouped matrix products for the expert layer (Pallas, TPU).

The rows of ``lhs`` are sorted by group (expert): group g owns rows
``[offset[g], offset[g] + group_sizes[g])``, and rows past the last group
(the routed slots whose expert this chip does not hold) belong to none.

- ``expert_gmm``: ``out[rows of g] = lhs[rows of g] @ rhs[g]`` (or
  ``@ rhs[g].T``), the forward product and its input gradient;
- ``expert_tgmm``: ``out[g] = lhs[rows of g].T @ rhs[rows of g]``, the
  weight gradient.

Both walk a grid over the row tiles that some group touches — a tile
shared by two groups is visited once for each, its stores masked to the
group's rows — so their work follows the rows routed to the held
experts, not the worst-case buffer they are laid in (the grid's extent
is a traced number, as in the tree-attention kernels).  Row tiles past
the last group are never visited; ``expert_gmm`` writes zeros there
unless told that nothing reads those rows (``zero_tail=False``: the
expert layer, whose permutations read only the held rows,
``kernels/moe_permute``).  With ``rows_out`` it writes its result one
row to a layout tile (``moe_permute.rows_view``), the layout those
permutations copy rows from.  The tile plan follows the ``megablox``
kernels that ship with jax.

``gmm`` is the differentiable op (forward ``expert_gmm``; backward
``gmm_grads``: ``expert_gmm`` against the transposed weights and
``expert_tgmm``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_permute import LANE, pad_rows, row_layout


def _tile(dim: int, pref: int) -> int:
    """The largest block ≤ ``pref`` that divides ``dim`` in whole lanes;
    the whole axis where it is short or not lane-aligned."""
    if dim <= pref or dim % LANE:
        return dim
    t = pref - pref % LANE
    while dim % t:
        t -= LANE
    return t


def _row_tile(m: int, pref: int = 256) -> int:
    return min(pref, max(8, -(-m // 8) * 8))


def group_metadata(group_sizes, m: int, tm: int, visit_empty: bool):
    """(offsets [G+1], tile group [T], tile row-block [T]) and the number
    of grid steps to run: each group visits every row tile it touches,
    in order; with ``visit_empty`` an empty group still gets one step
    (``tgmm`` must write its zeros).  T = m/tm + G − 1 bounds the steps."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends]).astype(
        jnp.int32)
    first = starts // tm
    last = (ends + tm - 1) // tm
    steps = jnp.where(group_sizes == 0, 1 if visit_empty else 0,
                      last - first).astype(jnp.int32)
    cap = tiles_m + G - 1
    tile_group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), steps,
                            total_repeat_length=cap)
    begin = jnp.cumsum(steps) - steps
    # a step's row tile: its group's first tile plus its rank in the group
    rank = jnp.arange(cap, dtype=jnp.int32) - begin[tile_group]
    tile_row = jnp.minimum(first[tile_group] + rank, tiles_m - 1)
    return (offsets, tile_group, tile_row.astype(jnp.int32)), \
        jnp.sum(steps)


def _row_mask(meta, step, tm: int, width: int):
    offsets, tile_group, tile_row = meta
    g = tile_group[step]
    rows = lax.broadcasted_iota(jnp.int32, (tm, width), 0) + tile_row[step] * tm
    return (rows >= offsets[g]) & (rows < offsets[g + 1])


@partial(jax.jit, static_argnames=("transpose_rhs", "interpret",
                                   "zero_tail", "rows_out"))
def expert_gmm(lhs, rhs, group_sizes, transpose_rhs: bool = False,
               interpret: bool = False, zero_tail: bool = True,
               rows_out: bool = False):
    """[M, K] rows by group @ rhs [G, K, N] (``transpose_rhs``: [G, N, K])
    → [M, N] in ``lhs``'s type, zeros past the last group (undefined
    there without ``zero_tail``); ``rows_out``: [M, N/128, 128], one row
    to a layout tile (``moe_permute.rows_view``)."""
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = _row_tile(M)
    Mp = -(-M // tm) * tm
    tk, tn = _tile(K, 512), _tile(N, 512)
    out_tile = row_layout(N)
    if rows_out:
        tn = N
    meta, steps = group_metadata(group_sizes, Mp, tm, visit_empty=False)
    tiles_k = K // tk

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc):
        step, k = pl.program_id(1), pl.program_id(2)

        @pl.when(k == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
            (((1,), (0,)), ((), ()))
        acc[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                    preferred_element_type=jnp.float32)

        @pl.when(k == tiles_k - 1)
        def _():
            # (the row layout is reached in the output's type: a float32
            # relayout lowers to many times the instructions)
            prev = out_ref[...].reshape(tm, tn)
            out = jnp.where(_row_mask(meta, step, tm, tn),
                            acc[...].astype(out_ref.dtype), prev)
            out_ref[...] = out.reshape(out_ref.shape)

    def lhs_map(n, step, k, meta):
        return meta[2][step], k

    def rhs_map(n, step, k, meta):
        g = meta[1][step]
        return (g, n, k) if transpose_rhs else (g, k, n)

    def out_map(n, step, k, meta):
        return (meta[2][step], n, 0) if rows_out else (meta[2][step], n)

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    out_cols = out_tile if rows_out else (N,)
    out_block = (tm, *out_tile) if rows_out else (tm, tn)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((Mp, *out_cols), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec(out_block, out_map),
            grid=(N // tn, steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_gmm",
    )(meta, pad_rows(lhs, Mp), rhs)
    if zero_tail:
        rows = lax.broadcasted_iota(jnp.int32, (Mp,) + (1,) * len(out_cols),
                                    0)
        out = jnp.where(rows < meta[0][-1], out, 0)
    return out[:M] if Mp != M else out


@partial(jax.jit, static_argnames=("num_groups", "interpret"))
def expert_tgmm(lhs, rhs, group_sizes, num_groups: int,
                interpret: bool = False):
    """lhs [M, K], rhs [M, N], rows by group → [G, K, N] = per group
    lhs[rows].T @ rhs[rows], in ``rhs``'s type."""
    M, K = lhs.shape
    N = rhs.shape[1]
    tm = _row_tile(M)
    Mp = -(-M // tm) * tm
    tk, tn = _tile(K, 512), _tile(N, 512)
    meta, steps = group_metadata(group_sizes, Mp, tm, visit_empty=True)

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc):
        step = pl.program_id(2)
        tile_group = meta[1]
        g = tile_group[step]
        prev = tile_group[jnp.maximum(step - 1, 0)]
        nxt = tile_group[jnp.minimum(step + 1, pl.num_programs(2) - 1)]

        @pl.when((step == 0) | (prev != g))
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(meta[0][g + 1] > meta[0][g])
        def _():
            f32 = jnp.float32
            a = jnp.where(_row_mask(meta, step, tm, tk),
                          lhs_ref[...].astype(f32), 0).swapaxes(0, 1)
            b = jnp.where(_row_mask(meta, step, tm, tn),
                          rhs_ref[...].astype(f32), 0)
            acc[...] += lax.dot(a.astype(lhs_ref.dtype),
                                b.astype(rhs_ref.dtype),
                                preferred_element_type=f32)

        @pl.when((step == pl.num_programs(2) - 1) | (nxt != g))
        def _():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    def lhs_map(n, k, step, meta):
        return meta[2][step], k

    def rhs_map(n, k, step, meta):
        return meta[2][step], n

    def out_map(n, k, step, meta):
        return meta[1][step], k, n

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((num_groups, K, N), rhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(N // tn, K // tk, steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="expert_tgmm",
    )(meta, pad_rows(lhs, Mp), pad_rows(rhs, Mp))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(lhs, rhs, group_sizes, interpret: bool = False):
    """Differentiable ``expert_gmm(lhs, rhs, group_sizes)``."""
    return expert_gmm(lhs, rhs, group_sizes, interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return gmm(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def gmm_grads(lhs, rhs, group_sizes, g, interpret: bool = False,
              zero_tail: bool = True, rows_out: bool = False):
    """The cotangents of ``expert_gmm(lhs, rhs, group_sizes)`` for its
    output's cotangent ``g``: (d_lhs, d_rhs); d_lhs as ``expert_gmm``
    writes it under ``zero_tail`` and ``rows_out``."""
    g = g.astype(lhs.dtype)
    d_lhs = expert_gmm(g, rhs, group_sizes, transpose_rhs=True,
                       interpret=interpret, zero_tail=zero_tail,
                       rows_out=rows_out)
    d_rhs = expert_tgmm(lhs, g, group_sizes, num_groups=rhs.shape[0],
                        interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype)


def _gmm_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    return (*gmm_grads(lhs, rhs, group_sizes, g, interpret), None)


gmm.defvjp(_gmm_fwd, _gmm_bwd)
