"""Compile-only checks of the tree-attention kernels for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies in
Python and never asks the TPU compiler about block shapes, tiling or
VMEM.  These tests compile the forward, dq and dk/dv kernels for a
described v5e (no chip needed) at qwen1.5-0.5B attention widths — H=16,
hd=64, S=2048, bf16 — and assert the Pallas kernel is in the compiled
program (``tpu_custom_call``).  Layouts: packed rows, a partition gateway
(``q_off=256`` front-concatenated ancestors, some marked kv_last = −1),
and a sliding window.  One more compiles the whole differentiable op
(``kernels/ops.tree_attention``, padding included) at an ancestor length
that is not a block multiple, and another compiles it on a four-chip
data-parallel mesh, where XLA would refuse to partition the kernel
itself.  The kernels run a data-dependent grid over the live-block
table, so these compiles also show that the TPU compiler takes a dynamic
grid bound.

The same three kernels compile at Qwen3-30B-A3B's attention widths (H=32
query heads over Kh=4 KV heads of hd=128: dk/dv reduces the group of 8
inside the kernel), and the expert layer's grouped products
(``expert_gmm`` forward and input gradient, ``expert_tgmm`` weight
gradient) at its expert widths: D=2048, F=768, 16 held experts, N·k =
131072 slots; so do its row permutations (``moe_gather`` plain and with
a scale and per-row dots, ``moe_combine`` weighted and not) and the
products that write the rows they copy from (``rows_out``).  The
permutations copy one row per DMA, which the TPU compiler takes only
from an array laid out one row to a tile (``moe_permute.rows_view``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.tree_attention import head_major, live_tables
from repro.kernels.tree_attention import tree_attention as fwd_kernel
from repro.kernels.tree_attention_bwd import _bwd_dkv, _bwd_dq

B, S, H, HD = 1, 2048, 16, 64
BLOCK = 128
LAYOUTS = {            # name → (q_off, window)
    "packed": (0, None),
    "gateway": (256, None),
    "window": (0, 512),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library writes logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure: "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(one_chip, q_off, window):
    Skv = q_off + S
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = [sds((B, S, H, HD), jnp.bfloat16),          # q
            sds((B, Skv, H, HD), jnp.bfloat16),        # k
            sds((B, Skv, H, HD), jnp.bfloat16),        # v
            sds((B, Skv), jnp.int32)]                  # kv_last
    if window is not None:
        args += [sds((B, S), jnp.int32), sds((B, Skv), jnp.int32)]
    return args, sds


def _assert_kernel(fn, args, n_kernels=1):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= n_kernels, \
        "the Pallas kernel is not in the compiled program"


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_forward_compiles_for_v5e(one_chip, layout):
    q_off, window = LAYOUTS[layout]
    args, _ = _shapes(one_chip, q_off, window)

    def f(q, k, v, kv_last, pos_q=None, pos_k=None):
        tables = live_tables(kv_last, S, BLOCK, BLOCK, q_off=q_off,
                             pos_q=pos_q, pos_k=pos_k, window=window)
        return fwd_kernel(q, k, v, kv_last, HD ** -0.5, block_q=BLOCK,
                          block_k=BLOCK, q_off=q_off, window=window,
                          pos_q=pos_q, pos_k=pos_k, save_residuals=True,
                          table=tables[0])
    _assert_kernel(f, args)


@pytest.mark.parametrize("kernel", ["dq", "dkv"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_backward_compiles_for_v5e(one_chip, layout, kernel):
    q_off, window = LAYOUTS[layout]
    body = _bwd_dq if kernel == "dq" else _bwd_dkv

    def f(q, k, v, kv_last, *rest):
        pos_q = pos_k = None
        if window is not None:
            pos_q, pos_k, *rest = rest
        lse, delta, do = rest
        tables = live_tables(kv_last, S, BLOCK, BLOCK, q_off=q_off,
                             pos_q=pos_q, pos_k=pos_k, window=window)
        tab = tables[0] if kernel == "dq" else tables[1]
        qh, kh, vh, kl, pq, pk = head_major(q, k, v, kv_last, pos_q, pos_k)
        return body(qh, kh, vh, kl, lse, delta, do.transpose(0, 2, 1, 3),
                    HD ** -0.5, tab, BLOCK, BLOCK, q_off, window, pq, pk,
                    False)
    args, sds = _shapes(one_chip, q_off, window)
    args += [sds((B, H, 1, S), jnp.float32),           # lse
             sds((B, H, 1, S), jnp.float32),           # delta
             sds((B, S, H, HD), jnp.bfloat16)]         # do
    _assert_kernel(f, args)


def test_op_with_unaligned_ancestors_compiles_for_v5e(one_chip,
                                                      monkeypatch):
    """The differentiable op pads an ancestor-extended KV axis
    (A = 200: Skv = 2248, no block multiple) up to whole blocks; forward
    and both backward kernels compile."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    A = 200
    args, _ = _shapes(one_chip, A, None)

    def loss(q, k, v, kv_last):
        o = ops.tree_attention(q, k, v, kv_last, HD ** -0.5, q_off=A)
        return o.astype(jnp.float32).sum()
    _assert_kernel(jax.grad(loss, argnums=(0, 1, 2)), args, n_kernels=3)


def test_op_on_four_chip_mesh_compiles_for_v5e(topo, monkeypatch):
    """On the (4, 1) host mesh each chip runs the kernels on its own rows;
    forward and both backward kernels compile for the 2x2 v5e host."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import sharding as sh
    from repro.launch.mesh import host_descriptor
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    desc = host_descriptor(4)
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(desc.shape),
                             desc.axis_names, axis_types=desc.axis_types)
    rows = NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct((4, S, H, HD), jnp.bfloat16,
                                 sharding=rows) for _ in range(3)]
    args.append(jax.ShapeDtypeStruct((4, S), jnp.int32, sharding=rows))

    def loss(q, k, v, kv_last):
        o = ops.tree_attention(q, k, v, kv_last, HD ** -0.5)
        return o.astype(jnp.float32).sum()
    with sh.use_mesh(mesh):
        _assert_kernel(jax.grad(loss, argnums=(0, 1, 2)), args,
                       n_kernels=3)


# Qwen3-30B-A3B: GQA 8:1 at hd 128; the expert layer's grouped products
GQA = dict(H=32, Kh=4, hd=128)
EXPERTS = dict(M=16384, D=2048, F=768, G=16)


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_gqa_kernels_compile_for_v5e(one_chip, kernel):
    H, Kh, hd = GQA["H"], GQA["Kh"], GQA["hd"]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = [sds((B, S, H, hd), jnp.bfloat16),
            sds((B, S, Kh, hd), jnp.bfloat16),
            sds((B, S, Kh, hd), jnp.bfloat16),
            sds((B, S), jnp.int32)]
    if kernel == "fwd":
        def f(q, k, v, kv_last):
            tables = live_tables(kv_last, S, BLOCK, BLOCK)
            return fwd_kernel(q, k, v, kv_last, hd ** -0.5, block_q=BLOCK,
                              block_k=BLOCK, save_residuals=True,
                              table=tables[0])
    else:
        body = _bwd_dq if kernel == "dq" else _bwd_dkv
        args += [sds((B, H, 1, S), jnp.float32),
                 sds((B, H, 1, S), jnp.float32),
                 sds((B, S, H, hd), jnp.bfloat16)]

        def f(q, k, v, kv_last, lse, delta, do):
            tables = live_tables(kv_last, S, BLOCK, BLOCK)
            tab = tables[0] if kernel == "dq" else tables[1]
            qh, kh, vh, kl, pq, pk = head_major(q, k, v, kv_last, None,
                                                None)
            return body(qh, kh, vh, kl, lse, delta,
                        do.transpose(0, 2, 1, 3), hd ** -0.5, tab, BLOCK,
                        BLOCK, 0, None, pq, pk, False)
    _assert_kernel(f, args)


@pytest.mark.parametrize("kernel", ["expert_gmm", "expert_gmm_t",
                                    "expert_tgmm"])
def test_expert_products_compile_for_v5e(one_chip, kernel):
    from repro.kernels.grouped_matmul import expert_gmm, expert_tgmm
    M, D, F, G = (EXPERTS[k] for k in ("M", "D", "F", "G"))
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((G,), jnp.int32, sharding=one_chip)
    if kernel == "expert_gmm":
        fn, args = expert_gmm, [sds((M, D)), sds((G, D, F)), sizes]
    elif kernel == "expert_gmm_t":
        fn = lambda g, w, gs: expert_gmm(g, w, gs, transpose_rhs=True)
        args = [sds((M, F)), sds((G, D, F)), sizes]
    else:
        fn = lambda x, g, gs: expert_tgmm(x, g, gs, num_groups=G)
        args = [sds((M, D)), sds((M, F)), sizes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    name = "expert_tgmm" if kernel == "expert_tgmm" else "expert_gmm"
    assert "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("kernel", ["moe_gather", "moe_gather_scaled",
                                    "moe_combine", "moe_combine_weighted",
                                    "expert_gmm_rows", "expert_gmm_t_rows"])
def test_moe_permutations_compile_for_v5e(one_chip, kernel):
    from repro.kernels.grouped_matmul import expert_gmm
    from repro.kernels.moe_permute import moe_combine, moe_gather
    D, F, G = (EXPERTS[k] for k in ("D", "F", "G"))
    N, K = 8 * 2048, 8                             # tokens a step, slots
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    i32, f32 = jnp.int32, jnp.float32
    rows = sds((N * K, D // 128, 128))             # one row to a tile
    live = sds((), i32)
    if kernel == "moe_gather":
        fn = lambda x, tok, live: moe_gather(x, tok, live)
        args = [sds((N, D)), sds((N * K,), i32), live]
    elif kernel == "moe_gather_scaled":
        fn = lambda dy, tok, live, s, ys: moe_gather(dy, tok, live,
                                                     scale=s, dot=ys)
        args = [sds((N, D)), sds((N * K,), i32), live, sds((N * K,), f32),
                rows]
    elif kernel == "moe_combine":
        fn = lambda g, inv, live: moe_combine(g, inv, live)
        args = [rows, sds((N, K), i32), live]
    elif kernel == "moe_combine_weighted":
        fn = lambda ys, inv, live, w: moe_combine(ys, inv, live, w)
        args = [rows, sds((N, K), i32), live, sds((N, K), f32)]
    elif kernel == "expert_gmm_rows":
        fn = lambda h, w, gs: expert_gmm(h, w, gs, zero_tail=False,
                                         rows_out=True)
        args = [sds((N * K, F)), sds((G, F, D)), sds((G,), i32)]
    else:
        fn = lambda g, w, gs: expert_gmm(g, w, gs, transpose_rhs=True,
                                         zero_tail=False, rows_out=True)
        args = [sds((N * K, F)), sds((G, D, F)), sds((G,), i32)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    name = "_".join(kernel.split("_")[:2])
    assert "tpu_custom_call" in text and name in text
