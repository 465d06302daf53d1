"""The expert layer's row permutations over the held slots only (Pallas, TPU).

The layer sorts a token's k routed slots by expert into one buffer of
M = N·k rows; every held slot sorts before every other, so the held rows
are the buffer's prefix ``[0, live)`` and the rest of the buffer is never
computed.  These kernels move exactly those rows:

- ``moe_gather(src, tok [M], live)`` → [M, D]: row ``r < live`` is
  ``src[tok[r]]`` (times ``scale[r]`` when a scale is given); row tiles
  past ``live`` are never visited and their rows are undefined.  The
  grid is ``cdiv(live, tm)``, a traced bound as in the grouped products.
  With ``dot`` ([M, D] in the result's row order) it also returns
  ``⟨src[tok[r]], dot[r]⟩`` per row, in float32.
- ``moe_combine(src, inv [N, k], live, w)`` → [N, D]: token ``t``
  receives ``Σ_j w[t, j] · src[inv[t, j]]`` over its held slots
  (``inv < live``), summed in float32 and stored in ``src``'s type.  A
  slot that is not held issues no copy and is never read, so the
  undefined rows past ``live`` cannot reach the result.

Each row arrives by its own DMA from HBM (``memory_space=pl.ANY``); the
row indices come to SMEM one block at a time.  A DMA moves whole tiles
of the TPU's tiled layout, and a row of a 2-D [R, D] array is a
fraction of one, so the rows they copy from are laid out one row to a
tile: ``rows_view`` gives [R, D/128, 128], which the TPU tiles over its
last two axes, the row axis untiled.  For the token-side operands (the
layer's input and the output's cotangent, N rows) that view is one
relayout copy; the N·k-row buffers ``moe_combine`` reads are written in
it by their producer (``expert_gmm(..., rows_out=True)``), so no pass
over the whole buffer is added.  The rows are staged in VMEM in the same
view and brought to [rows, D] there.

Both kernels carry their names (``name=``, and a ``jax.named_scope`` of
the same name around the call) so that a profile lists them.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# XLA lays a 1-D int32 or float32 array out in tiles of 1024 entries, and
# a block of one in SMEM must take whole tiles
SMEM_TILE = 1024
# bytes of one tile's rows in VMEM, per operand
TILE_BYTES = 1 << 20


def row_layout(d: int) -> tuple[int, int]:
    """A row of width d as one tile of the TPU's layout: (d/128, 128), or
    (1, d) where d is not a whole number of lanes."""
    return (d // LANE, LANE) if d % LANE == 0 else (1, d)


def rows_view(a):
    """[R, D] → [R, *row_layout(D)], the row axis untiled; a 3-D operand
    is returned as it is."""
    if a.ndim == 3:
        return a
    return a.reshape(a.shape[0], *row_layout(a.shape[1]))


def _gather_tile(m: int, row_bytes: int) -> int:
    """Row tile of ``moe_gather``: 256 or 128 (its lane-dense per-row
    operands take whole lanes), the whole axis where it is short."""
    if m <= 256:
        return max(8, -(-m // 8) * 8)
    return 256 if 256 * row_bytes <= TILE_BYTES else 128


def _combine_tile(n: int, k: int, row_bytes: int) -> int:
    """Token tile of ``moe_combine``: its k·tn staged rows fill at most
    4 · ``TILE_BYTES``."""
    tn = max(8, min(128, 4 * TILE_BYTES // (k * row_bytes)) // 8 * 8)
    return min(tn, max(8, -(-n // 8) * 8))


def pad_rows(a, m: int, value=0):
    """``a`` with its first axis padded to ``m`` by ``value``."""
    if a.shape[0] == m:
        return a
    pad = [(0, m - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=value)


def _smem_blocks(per_tile: int, tiles: int):
    """An index operand of ``per_tile`` entries a grid step, ``tiles``
    steps, in SMEM blocks of whole layout tiles: (block, steps a block,
    padded length).  A single step takes the whole operand."""
    if tiles == 1:
        return per_tile, 1, per_tile
    block = math.lcm(per_tile, SMEM_TILE)
    per_block = block // per_tile
    return block, per_block, -(-tiles // per_block) * block


def _row_copy(src_hbm, dst, src_row, dst_row, sem):
    return pltpu.make_async_copy(src_hbm.at[pl.ds(src_row, 1)],
                                 dst.at[pl.ds(dst_row, 1)], sem)


@partial(jax.jit, static_argnames=("interpret",))
def moe_gather(src, tok, live, scale=None, dot=None, interpret=False):
    """Rows ``src[tok[r]]`` (× ``scale[r]``) for ``r < live`` → [M, D] in
    ``src``'s type, rows past ``live`` undefined; with ``dot`` also
    ``(out, dots [M] f32)``, ``dots[r] = ⟨src[tok[r]], dot[r]⟩``.  ``src``
    is [R, D] or its ``rows_view``; ``dot`` [M, D] or its view."""
    src = rows_view(src)
    _, C, L = src.shape
    D = C * L
    M = tok.shape[0]
    tm = _gather_tile(M, D * src.dtype.itemsize)
    tiles = -(-M // tm)
    Mp = tiles * tm
    tb, per_block, Mq = _smem_blocks(tm, tiles)
    live = jnp.asarray(live, jnp.int32).reshape(1)
    steps = (live[0] + tm - 1) // tm
    has_scale, has_dot = scale is not None, dot is not None

    def kernel(live_ref, tok_ref, src_hbm, *refs):
        refs = list(refs)
        scale_ref = refs.pop(0) if has_scale else None
        dot_ref = refs.pop(0) if has_dot else None
        out_ref = refs.pop(0)
        dots_ref = refs.pop(0) if has_dot else None
        stage, sem = refs
        i = pl.program_id(0)
        base = (i % per_block) * tm
        n = jnp.minimum(live_ref[0] - i * tm, tm)

        def start(r, c):
            _row_copy(src_hbm, stage, tok_ref[base + r], r, sem).start()
            return c

        def wait(r, c):
            _row_copy(src_hbm, stage, 0, 0, sem).wait()
            return c

        lax.fori_loop(0, n, start, 0)
        lax.fori_loop(0, n, wait, 0)
        rows = stage[...].reshape(tm, D)
        if has_dot or has_scale:
            rows = rows.astype(jnp.float32)
        if has_dot:
            d = dot_ref[...].reshape(tm, D).astype(jnp.float32)
            dots_ref[...] = jnp.sum(rows * d, axis=1,
                                    keepdims=True).reshape(1, tm)
        if has_scale:
            rows = rows * scale_ref[...].reshape(tm, 1)
        out_ref[...] = rows.astype(out_ref.dtype)

    rows_spec = pl.BlockSpec((tm, D), lambda i, live: (i, 0))
    lane_spec = pl.BlockSpec((1, tm), lambda i, live: (0, i))
    in_specs = [pl.BlockSpec((tb,), lambda i, live: (i // per_block,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [pad_rows(tok.astype(jnp.int32), Mq), src]
    if has_scale:
        in_specs.append(lane_spec)
        args.append(pad_rows(scale.astype(jnp.float32), Mp).reshape(1, Mp))
    if has_dot:
        dot = pad_rows(dot, Mp)
        in_specs.append(pl.BlockSpec((tm,) + dot.shape[1:],
                                     lambda i, live: (i,) + (0,) *
                                     (dot.ndim - 1)))
        args.append(dot)
    out_shape = [jax.ShapeDtypeStruct((Mp, D), src.dtype)]
    out_specs = [rows_spec]
    if has_dot:
        out_shape.append(jax.ShapeDtypeStruct((1, Mp), jnp.float32))
        out_specs.append(lane_spec)
    with jax.named_scope("moe_gather"):
        outs = pl.pallas_call(
            kernel,
            out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                in_specs=in_specs,
                out_specs=out_specs,
                grid=(steps,),
                scratch_shapes=[pltpu.VMEM((tm, C, L), src.dtype),
                                pltpu.SemaphoreType.DMA(())]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="moe_gather",
        )(live, *args)
    out = outs[0][:M] if Mp != M else outs[0]
    if has_dot:
        return out, outs[1][0, :M]
    return out


@partial(jax.jit, static_argnames=("interpret",))
def moe_combine(src, inv, live, w=None, interpret=False):
    """[N, D] in ``src``'s type: token t's ``Σ_j w[t, j] · src[inv[t, j]]``
    over the slots with ``inv[t, j] < live`` (``w`` ones when absent),
    summed in float32; no other row of ``src`` is read.  ``src`` is
    [M, D] or its ``rows_view``, the layout the rows are copied from."""
    src = rows_view(src)
    M, C, L = src.shape
    D = C * L
    N, k = inv.shape
    tn = _combine_tile(N, k, D * src.dtype.itemsize)
    tiles = -(-N // tn)
    Np = tiles * tn
    tb, per_block, Sq = _smem_blocks(tn * k, tiles)
    live = jnp.asarray(live, jnp.int32).reshape(1)
    # slots in token order; a padded slot points past the buffer: not held
    slots = pad_rows(inv.astype(jnp.int32).reshape(N * k), Sq, value=M)
    weighted = w is not None

    def kernel(live_ref, slot_ref, *refs):
        w_ref = refs[0] if weighted else None
        src_hbm, out_ref, stage, acc, sem = refs[-5:]
        live = live_ref[0]
        base = (pl.program_id(0) % per_block) * tn * k

        def start(s, n):
            r = slot_ref[base + s]
            held = r < live

            @pl.when(held)
            def _():
                _row_copy(src_hbm, stage, r, s, sem).start()
            return n + held.astype(jnp.int32)

        def wait(s, c):
            _row_copy(src_hbm, stage, 0, 0, sem).wait()
            return c

        def add(s, c):
            @pl.when(slot_ref[base + s] < live)
            def _():
                row = stage[s].astype(jnp.float32)
                if weighted:
                    row = row * w_ref[base + s]
                acc[s // k] += row
            return c

        n = lax.fori_loop(0, tn * k, start, 0)
        acc[...] = jnp.zeros_like(acc)
        lax.fori_loop(0, n, wait, 0)
        lax.fori_loop(0, tn * k, add, 0)
        out_ref[...] = acc[...].reshape(tn, D).astype(out_ref.dtype)

    smem = pl.BlockSpec((tb,), lambda i, live: (i // per_block,),
                        memory_space=pltpu.SMEM)
    in_specs, args = [smem], [slots]
    if weighted:
        in_specs.append(smem)
        args.append(pad_rows(w.astype(jnp.float32).reshape(N * k), Sq))
    with jax.named_scope("moe_combine"):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((Np, D), src.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tn, D), lambda i, live: (i, 0)),
                grid=(tiles,),
                scratch_shapes=[pltpu.VMEM((tn * k, C, L), src.dtype),
                                pltpu.VMEM((tn, C, L), jnp.float32),
                                pltpu.SemaphoreType.DMA(())]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="moe_combine",
        )(live, *args, src)
    return out[:N] if Np != N else out
