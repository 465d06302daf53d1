"""Fused Pallas backward (tree_attention_bwd) vs dense-oracle gradients.

The custom_vjp in kernels/ops.py must reproduce jax.vjp through the
materialized-mask reference for every tree topology the packer can emit:
branching, row padding, multiple packed trees per row, GQA/MQA head
groups, rectangular blocks.  Also checks the saved-residual plumbing
(no O(S²) tensor in the residuals) and NaN-safety on fully-padded rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kernels import _gateway_meta, _tree_meta

from repro.core.packing import pack_trees
from repro.core.tree import serialize_tree
from repro.data.synthetic import trees_for_batch
from repro.kernels.ops import tree_attention
from repro.kernels.ref import tree_attention_ref, tree_attention_ref_ext
from repro.kernels.tree_attention import live_tables
from repro.kernels.tree_attention import tree_attention as raw_fwd
from repro.kernels.tree_attention_bwd import tree_attention_bwd


def _tree_kv_last(seed: int, B: int, S: int, fill=0.75) -> jnp.ndarray:
    trees = trees_for_batch(seed, n_trees=6 * B, kind="random",
                            seg_len_range=(1, 4), max_depth=3)
    sers, used = [], 0
    for t in trees:
        s = serialize_tree(t)
        if used + s.n <= int(B * S * fill):
            sers.append(s)
            used += s.n
    tb = pack_trees(sers, S, batch_size=B)
    return jnp.asarray(tb.kv_last)


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)


def _grads(fn, q, k, v, do):
    _, vjp = jax.vjp(fn, q, k, v)
    return vjp(do)


@pytest.mark.parametrize("B,S,H,Kh,hd,bq,bk", [
    (1, 64, 4, 4, 16, 16, 16),     # MHA
    (2, 128, 4, 2, 16, 32, 32),    # GQA 2:1, multi-row packing
    (1, 128, 8, 1, 32, 32, 64),    # MQA, rectangular blocks
    (2, 128, 4, 2, 64, 64, 32),    # wide head
    (1, 256, 2, 2, 8, 128, 128),   # MXU-aligned blocks
])
def test_bwd_shapes_vs_ref(B, S, H, Kh, hd, bq, bk):
    rng = np.random.default_rng(B * 1000 + S + H)
    kv_last = _tree_kv_last(S + H, B, S)
    q = _rand(rng, (B, S, H, hd))
    k = _rand(rng, (B, S, Kh, hd))
    v = _rand(rng, (B, S, Kh, hd))
    do = _rand(rng, (B, S, H, hd))
    scale = hd ** -0.5
    g = _grads(lambda q_, k_, v_:
               tree_attention(q_, k_, v_, kv_last, scale, bq, bk),
               q, k, v, do)
    gr = _grads(lambda q_, k_, v_:
                tree_attention_ref(q_, k_, v_, kv_last, scale),
                q, k, v, do)
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def test_bwd_no_dense_residuals():
    """The vjp residuals stay O(S): no [.., S, S] tensor may be saved."""
    B, S, H, hd = 1, 128, 2, 16
    kv_last = jnp.full((B, S), S - 1, jnp.int32)
    q = k = v = jnp.ones((B, S, H, hd), jnp.float32)
    _, vjp = jax.vjp(lambda q_, k_, v_:
                     tree_attention(q_, k_, v_, kv_last, hd ** -0.5),
                     q, k, v)
    leaves = jax.tree.leaves(vjp)
    assert leaves, "vjp closure saved no residuals?"
    for leaf in leaves:
        assert np.asarray(leaf).shape.count(S) <= 1, (
            f"dense O(S²) residual of shape {np.asarray(leaf).shape}")


def test_bwd_padding_rows_zero_grad_and_finite():
    """Padding keys (kv_last = −1) get zero dk/dv; padded queries zero dq;
    nothing is NaN even when whole rows are masked out."""
    rng = np.random.default_rng(29)
    B, S, H, hd = 1, 64, 2, 16
    kv_last = np.full((B, S), -1, np.int32)
    kv_last[0, :16] = 15
    kv_last = jnp.asarray(kv_last)
    q = _rand(rng, (B, S, H, hd))
    k = _rand(rng, (B, S, H, hd))
    v = _rand(rng, (B, S, H, hd))
    do = _rand(rng, (B, S, H, hd))
    dq, dk, dv = _grads(lambda q_, k_, v_:
                        tree_attention(q_, k_, v_, kv_last, 0.25, 16, 16),
                        q, k, v, do)
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_allclose(np.asarray(dq[0, 16:]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dk[0, 16:]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dv[0, 16:]), 0.0, atol=1e-6)


def test_empty_row_and_sparse_tree_row_vs_ref():
    """A batch whose first row is empty (all padding) and whose second
    holds many small trees, so that under 15 % of the block pairs are
    live: the kernels run only those (and one write-only entry for each
    block with none), and forward and gradients still match the oracle;
    the empty row's outputs and gradients are zero, and all are finite."""
    from repro.kernels.tree_attention import block_live_mask
    rng = np.random.default_rng(53)
    B, S, H, Kh, hd, blk = 2, 512, 4, 2, 16, 32
    trees = trees_for_batch(17, n_trees=40, kind="random",
                            seg_len_range=(1, 4), max_depth=3)
    sers, used = [], 0
    for t in trees:
        ser = serialize_tree(t)
        if used + ser.n <= S:
            sers.append(ser)
            used += ser.n
    tb = pack_trees(sers, S, batch_size=1)
    kv_last = np.concatenate([np.full((1, S), -1, np.int32),
                              np.asarray(tb.kv_last, np.int32)])
    live = block_live_mask(kv_last, S, blk, blk)
    assert not live[0].any() and live.mean() < 0.15, live.mean()
    kv_last = jnp.asarray(kv_last)
    q = _rand(rng, (B, S, H, hd))
    k = _rand(rng, (B, S, Kh, hd))
    v = _rand(rng, (B, S, Kh, hd))
    do = _rand(rng, (B, S, H, hd))
    scale = hd ** -0.5
    fn = lambda q_, k_, v_: tree_attention(q_, k_, v_, kv_last, scale,
                                           blk, blk)
    ref = lambda q_, k_, v_: tree_attention_ref(q_, k_, v_, kv_last, scale)
    o = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(o[0]), 0.0)
    g, gr = _grads(fn, q, k, v, do), _grads(ref, q, k, v, do)
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_array_equal(np.asarray(a[0]), 0.0, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def test_bwd_pure_causal_matches_plain_flash_grads():
    """Single-chain tree = plain causal attention; gradients must agree
    with jax.grad through vanilla softmax attention."""
    rng = np.random.default_rng(31)
    B, S, H, hd = 1, 128, 4, 16
    kv_last = jnp.full((B, S), S - 1, jnp.int32)
    q = _rand(rng, (B, S, H, hd))
    k = _rand(rng, (B, S, H, hd))
    v = _rand(rng, (B, S, H, hd))
    do = _rand(rng, (B, S, H, hd))

    def plain(q_, k_, v_):
        logits = jnp.einsum("bihd,bjhd->bhij", q_, k_) * hd ** -0.5
        mask = jnp.tril(jnp.ones((S, S), bool))
        w = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        return jnp.einsum("bhij,bjhd->bihd", w, v_)

    g = _grads(lambda q_, k_, v_:
               tree_attention(q_, k_, v_, kv_last, hd ** -0.5, 32, 32),
               q, k, v, do)
    gp = _grads(plain, q, k, v, do)
    for name, a, b in zip(("dq", "dk", "dv"), g, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 5e-2)])
def test_bwd_dtypes(dtype, tol):
    rng = np.random.default_rng(37)
    B, S, H, Kh, hd = 1, 128, 4, 2, 32
    kv_last = _tree_kv_last(3, B, S)
    q = _rand(rng, (B, S, H, hd), dtype)
    k = _rand(rng, (B, S, Kh, hd), dtype)
    v = _rand(rng, (B, S, Kh, hd), dtype)
    do = _rand(rng, (B, S, H, hd), dtype)
    g = _grads(lambda q_, k_, v_:
               tree_attention(q_, k_, v_, kv_last, hd ** -0.5, 32, 32),
               q, k, v, do)
    gr = _grads(lambda q_, k_, v_:
                tree_attention_ref(q_, k_, v_, kv_last, hd ** -0.5),
                q, k, v, do)
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        assert a.dtype == dtype, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("A,pad_rows,window", [
    (32, (0, 7), None),    # aligned ancestors, row-1 front padding
    (20, (5, 0), None),    # awkward depth → KV back-pad path
    (32, (4, 11), 12),     # ancestors + sliding window combined
])
def test_bwd_gateway_ancestors_vs_ref(A, pad_rows, window):
    """Fused backward through the gateway layout: dq AND the ancestor
    rows of dk/dv (d_extra_k/d_extra_v, rows [0, A)) match the oracle."""
    rng = np.random.default_rng(200 + A + (window or 0))
    B, S, H, Kh, hd = 2, 64, 4, 2, 16
    kl_all, pos_q, pos_k, _ = _gateway_meta(5, B, S, A, pad_rows)
    q = _rand(rng, (B, S, H, hd))
    k = _rand(rng, (B, A + S, Kh, hd))
    v = _rand(rng, (B, A + S, Kh, hd))
    do = _rand(rng, (B, S, H, hd))
    scale = hd ** -0.5
    g = _grads(lambda q_, k_, v_:
               tree_attention(q_, k_, v_, kl_all, scale, 32, 32, q_off=A,
                              window=window, pos_q=pos_q, pos_k=pos_k),
               q, k, v, do)
    gr = _grads(lambda q_, k_, v_:
                tree_attention_ref_ext(q_, k_, v_, kl_all, scale, q_off=A,
                                       window=window, pos_q=pos_q,
                                       pos_k=pos_k),
                q, k, v, do)
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)
    # ancestor cotangents are real (nonzero) — the routing has something
    # to carry back to the parent partition
    assert float(jnp.abs(g[1][:, :A]).max()) > 1e-3
    assert float(jnp.abs(g[2][:, :A]).max()) > 1e-3


def test_bwd_window_with_tree_branching_vs_ref():
    rng = np.random.default_rng(211)
    B, S, H, hd = 2, 128, 4, 16
    kv_last, pos_ids = _tree_meta(11, B, S)
    q = _rand(rng, (B, S, H, hd))
    k = _rand(rng, (B, S, H, hd))
    v = _rand(rng, (B, S, H, hd))
    do = _rand(rng, (B, S, H, hd))
    scale = hd ** -0.5
    g = _grads(lambda q_, k_, v_:
               tree_attention(q_, k_, v_, kv_last, scale, 32, 32, window=8,
                              pos_q=pos_ids, pos_k=pos_ids),
               q, k, v, do)
    gr = _grads(lambda q_, k_, v_:
                tree_attention_ref_ext(q_, k_, v_, kv_last, scale,
                                       window=8, pos_q=pos_ids,
                                       pos_k=pos_ids),
                q, k, v, do)
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def test_bwd_bf16_gqa_with_ancestors():
    rng = np.random.default_rng(223)
    B, S, A, H, Kh, hd = 1, 128, 32, 4, 2, 32
    kl_all, pos_q, pos_k, _ = _gateway_meta(7, B, S, A, pad_rows=(9,))
    q = _rand(rng, (B, S, H, hd), jnp.bfloat16)
    k = _rand(rng, (B, A + S, Kh, hd), jnp.bfloat16)
    v = _rand(rng, (B, A + S, Kh, hd), jnp.bfloat16)
    do = _rand(rng, (B, S, H, hd), jnp.bfloat16)
    scale = hd ** -0.5
    g = _grads(lambda q_, k_, v_:
               tree_attention(q_, k_, v_, kl_all, scale, 32, 32, q_off=A),
               q, k, v, do)
    gr = _grads(lambda q_, k_, v_:
                tree_attention_ref_ext(q_, k_, v_, kl_all, scale, q_off=A),
                q, k, v, do)
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        assert a.dtype == jnp.bfloat16, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2, err_msg=name)


def test_bwd_direct_entry_point_matches_custom_vjp():
    """tree_attention_bwd called directly (as a library op) agrees with
    the custom_vjp wiring — catches residual-layout drift."""
    rng = np.random.default_rng(41)
    B, S, H, Kh, hd = 2, 128, 4, 2, 16
    kv_last = _tree_kv_last(11, B, S)
    q = _rand(rng, (B, S, H, hd))
    k = _rand(rng, (B, S, Kh, hd))
    v = _rand(rng, (B, S, Kh, hd))
    do = _rand(rng, (B, S, H, hd))
    scale = hd ** -0.5
    tables = live_tables(kv_last, S, 32, 32)
    o, lse = raw_fwd(q, k, v, kv_last, scale, block_q=32, block_k=32,
                     save_residuals=True, interpret=True, table=tables[0])
    dq, dk, dv = tree_attention_bwd(q, k, v, kv_last, o, lse, do, scale,
                                    block_q=32, block_k=32, interpret=True,
                                    tables=tables)
    g = _grads(lambda q_, k_, v_:
               tree_attention(q_, k_, v_, kv_last, scale, 32, 32),
               q, k, v, do)
    for name, a, b in zip(("dq", "dk", "dv"), (dq, dk, dv), g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5, err_msg=name)


_MESH_SNIPPET = """
import numpy as np, jax
from repro import sharding as sh
from repro.kernels.ops import tree_attention
from repro.kernels.ref import tree_attention_ref
from repro.launch.mesh import host_descriptor
from test_kernels_bwd import _rand, _tree_kv_last
mesh = host_descriptor(4).build()
for B in (4, 2):      # rows split over the data axis / rows replicated
    rng = np.random.default_rng(B)
    S, H, Kh, hd = 128, 4, 2, 16
    kv_last = _tree_kv_last(3, B, S)
    q, do = _rand(rng, (B, S, H, hd)), _rand(rng, (B, S, H, hd))
    k, v = _rand(rng, (B, S, Kh, hd)), _rand(rng, (B, S, Kh, hd))
    def run(fn):
        o, vjp = jax.vjp(fn, q, k, v)
        return (o,) + vjp(do)
    with sh.use_mesh(mesh):
        got = jax.jit(lambda q, k, v: run(
            lambda *a: tree_attention(*a, kv_last, 0.25, 32, 32)))(q, k, v)
    want = run(lambda *a: tree_attention_ref(*a, kv_last, 0.25))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
print("ok")
"""


def test_kernel_under_data_parallel_mesh():
    """XLA cannot partition a Pallas TPU kernel, so on a mesh of several
    devices the op runs it per device on its own rows (shard_map).  On
    four host devices, outputs and dq/dk/dv match the dense oracle —
    rows split over the data axis, and rows that do not divide it."""
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(here), "src"), here])
    r = subprocess.run([sys.executable, "-c", _MESH_SNIPPET], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and r.stdout.strip().endswith("ok"), \
        r.stdout[-2000:] + r.stderr[-2000:]
