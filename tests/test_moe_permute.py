"""The expert layer's row permutations over the held slots
(kernels/moe_permute.py), in interpret mode, against plain indexing.

The buffer's rows past ``live`` (the slots no held expert takes) are NaN
in every operand the kernels read by slot, so a result or gradient that
is finite and equal to the reference shows that they are never read.
Cases: no held slot, every slot held, a partial last row tile over
several tiles and SMEM index blocks, and fewer tokens than one tile.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_permute import moe_combine, moe_gather, rows_view

CASES = {              # name → (N tokens, k slots a token, D, held share)
    "none_held": (40, 3, 256, 0.0),
    "all_held": (40, 3, 256, 1.0),
    "partial_tile": (300, 3, 256, 0.6),
    "below_one_tile": (3, 2, 256, 0.5),
    "narrow_rows": (50, 4, 40, 0.3),
}


def _routing(rng, N, k, share):
    """A random routing: which slots are held, sorted held-first as the
    layer sorts them.  Returns (order, inv [N, k], live)."""
    held = rng.permutation(N * k) < round(share * N * k)
    gid = np.where(held, rng.integers(0, 4, N * k), 4)
    order = np.argsort(gid, kind="stable").astype(np.int32)
    inv = np.empty_like(order)
    inv[order] = np.arange(N * k, dtype=np.int32)
    return order, inv.reshape(N, k), int(held.sum())


def _nan_tail(a, live):
    return a.at[live:].set(jnp.nan)


@pytest.mark.parametrize("case", sorted(CASES))
def test_permutations_match_indexing(case):
    N, k, D, share = CASES[case]
    rng = np.random.default_rng(N * k + D)
    order, inv, live = _routing(rng, N, k, share)
    tok = order // k
    M = N * k
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    w = jnp.asarray(rng.random((N, k)), jnp.float32)
    held = inv < live
    first = np.arange(M)[:, None] < live

    def plain_gather(x):                      # rows past live: zero
        return jnp.where(first, x[tok], 0.0)

    def plain_combine(ys, w):
        return jnp.sum(jnp.where(held[..., None],
                                 w[..., None] * ys[inv], 0.0), 1)

    # dispatch: forward, and its transpose (the cotangent's tail is NaN)
    got = moe_gather(x, tok, live, interpret=True)
    np.testing.assert_allclose(np.asarray(got[:live]),
                               np.asarray(x[tok][:live]), rtol=0, atol=0)
    g = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    want = jax.vjp(plain_gather, x)[1](jnp.where(first, g, 0.0))[0]
    got = moe_combine(_nan_tail(g, live), inv, live, interpret=True)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)

    # combine: forward from the row layout with a NaN tail, and its VJP
    ys_nan = rows_view(_nan_tail(ys, live))
    got = moe_combine(ys_nan, inv, live, w, interpret=True)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(plain_combine(ys, w)),
                               rtol=1e-6, atol=1e-6)
    want_ys, want_w = jax.vjp(plain_combine, ys, w)[1](dy)
    dys, dots = moe_gather(dy, tok, live, scale=w.reshape(M)[order],
                           dot=ys_nan, interpret=True)
    dw = jnp.where(held, dots[inv], 0.0)
    assert bool(jnp.isfinite(dys[:live]).all())
    assert bool(jnp.isfinite(dw).all())
    np.testing.assert_allclose(np.asarray(dys[:live]),
                               np.asarray(want_ys[:live]), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(want_w),
                               rtol=1e-5, atol=1e-5)
