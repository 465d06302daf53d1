"""Core paper claim (§3.1–3.2): tree-training loss and gradients equal the
per-branch sep-avg baseline, for every architecture family.

Baseline = linearize every root-to-leaf path, pack, standard causal masks.
Tree     = DFS serialization + tree attention mask + depth positions +
           (for SSM) tree state routing + path-predecessor conv/shift +
           λ_t = g_t/K loss weights.
Both are fed through the *same* model code; only the metadata differs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import branching_tree, tiny_cfg
from repro.core.packing import pack_linear_paths, pack_trees
from repro.core.tree import serialize_tree
from repro.data.synthetic import trees_for_batch
from repro.models.model import (init_params, loss_and_metrics, needs_chunks,
                                prepare_batch)

FAMILIES = ["dense", "moe", "ssm_rwkv6", "ssm_mamba2", "ssm_gdn", "hybrid"]


def _batches(cfg, trees, chunk):
    tb = pack_trees([serialize_tree(t, chunk_size=chunk) for t in trees],
                    512, chunk_size=chunk)
    lb = pack_linear_paths([t.linearize_paths() for t in trees], 1024,
                           chunk_size=chunk)
    return prepare_batch(cfg, tb), prepare_batch(cfg, lb)


@pytest.mark.parametrize("family", FAMILIES)
def test_loss_equivalence(family):
    cfg = tiny_cfg(family)
    chunk = cfg.ssm.chunk_size if needs_chunks(cfg) else None
    params = init_params(cfg, jax.random.key(0))
    trees = trees_for_batch(2, n_trees=3, kind="random", vocab_size=89)
    assert any(t.num_leaves() > 1 for t in trees)
    bt, bl = _batches(cfg, trees, chunk)
    lt, _ = loss_and_metrics(cfg, params, bt)
    ll, _ = loss_and_metrics(cfg, params, bl)
    np.testing.assert_allclose(float(lt), float(ll), rtol=5e-6)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_loss_ignores_vocab_padding(family):
    """The head's rows past ``vocab_size`` (padding to a multiple) are no
    tokens: packed and partitioned, the loss and its gradient are those
    of the unpadded head, whatever the padded rows hold."""
    from repro.core.gateway import partitioned_value_and_grad
    tree = branching_tree(3, min_leaves=3)
    tb = pack_trees([serialize_tree(tree)], 256)
    out = {}
    for pad in (1, 128):
        cfg = tiny_cfg(family, vocab_pad_multiple=pad)
        params = init_params(cfg, jax.random.key(0))
        if pad == 1:
            base = params
        else:
            # the unpadded weights in the first rows, noise past them
            params["embed"]["table"] = params["embed"]["table"].at[
                :cfg.vocab_size].set(base["embed"]["table"])
            params["lm_head"]["w"] = params["lm_head"]["w"].at[
                :, :cfg.vocab_size].set(base["lm_head"]["w"])
            params["layer_stacks"] = base["layer_stacks"]
            params["final_norm"] = base["final_norm"]
        b = prepare_batch(cfg, tb)
        loss, g = jax.value_and_grad(
            lambda p: loss_and_metrics(cfg, p, b)[0])(params)
        part, _, _ = partitioned_value_and_grad(cfg, params, tree, 64)
        out[pad] = (float(loss), part,
                    np.asarray(g["lm_head"]["w"][:, :cfg.vocab_size]),
                    np.asarray(g["lm_head"]["w"][:, cfg.vocab_size:]))
    assert out[1][0] == pytest.approx(out[128][0], rel=1e-6)
    assert out[1][1] == pytest.approx(out[128][1], rel=1e-6)
    np.testing.assert_allclose(out[128][2], out[1][2], rtol=1e-5,
                               atol=1e-7)
    assert not np.any(out[128][3])


@pytest.mark.parametrize("family", ["dense", "ssm_mamba2", "ssm_rwkv6"])
def test_grad_equivalence(family):
    """Eq. (5): ∂L_tree/∂θ = ∂L_sep_avg/∂θ (float32, App. B.8 tolerance)."""
    cfg = tiny_cfg(family)
    chunk = cfg.ssm.chunk_size if needs_chunks(cfg) else None
    params = init_params(cfg, jax.random.key(0))
    trees = trees_for_batch(2, n_trees=2, kind="random", vocab_size=89)
    bt, bl = _batches(cfg, trees, chunk)
    gt = jax.grad(lambda p: loss_and_metrics(cfg, p, bt)[0])(params)
    gl = jax.grad(lambda p: loss_and_metrics(cfg, p, bl)[0])(params)

    def rel(a, b):
        denom = jnp.abs(b).max() + 1e-9
        return float(jnp.abs(a - b).max() / denom)

    max_rel = max(jax.tree.leaves(jax.tree.map(rel, gt, gl)))
    assert max_rel < 1e-4, max_rel   # paper App. B.8: < 1e-4 in f32


@pytest.mark.parametrize("family", ["audio", "vlm"])
def test_multimodal_equivalence(family):
    cfg = tiny_cfg(family)
    tree = branching_tree(5, min_leaves=4)
    params = init_params(cfg, jax.random.key(1))
    tb = pack_trees([serialize_tree(tree)], 128)
    lb = pack_linear_paths([tree.linearize_paths()], 128)
    rng = np.random.default_rng(0)
    ext = rng.normal(size=(1, cfg.frontend_len, cfg.d_model)).astype(
        np.float32)
    bt = prepare_batch(cfg, tb, ext)
    bl = prepare_batch(cfg, lb, np.repeat(ext, lb.tokens.shape[0], 0))
    lt, _ = loss_and_metrics(cfg, params, bt)
    ll, _ = loss_and_metrics(cfg, params, bl)
    np.testing.assert_allclose(float(lt), float(ll), rtol=5e-6)


def test_rl_advantage_weighting():
    """λ_t with per-token advantages (policy-gradient objective, §3.1)."""
    cfg = tiny_cfg("dense")
    tree = branching_tree(0, min_leaves=3)
    rng = np.random.default_rng(1)
    for n in tree.nodes():
        n.advantage = rng.normal(size=n.size).astype(np.float32)
    params = init_params(cfg, jax.random.key(0))
    bt = prepare_batch(cfg, pack_trees([serialize_tree(tree)], 128))
    bl = prepare_batch(cfg, pack_linear_paths([tree.linearize_paths()], 256))
    lt, _ = loss_and_metrics(cfg, params, bt)
    ll, _ = loss_and_metrics(cfg, params, bl)
    np.testing.assert_allclose(float(lt), float(ll), rtol=5e-6)


def test_uniform_loss_mode_differs_but_finite():
    """§3.1: λ_t = 1 is a *different* objective — valid, not equal to
    sep-avg on branching trees."""
    cfg = tiny_cfg("dense")
    tree = branching_tree(0, min_leaves=3)
    params = init_params(cfg, jax.random.key(0))
    b_sep = prepare_batch(cfg, pack_trees([serialize_tree(tree)], 128))
    b_uni = prepare_batch(cfg, pack_trees(
        [serialize_tree(tree, loss_mode="uniform")], 128))
    l_sep, _ = loss_and_metrics(cfg, params, b_sep)
    l_uni, _ = loss_and_metrics(cfg, params, b_uni)
    assert np.isfinite(float(l_uni))
    assert abs(float(l_sep) - float(l_uni)) > 1e-3


def test_gradient_restoration_shared_prefix_sums_branches():
    """Gradient Restoration (paper §3.3) at the kernel level: with the
    fused Pallas vjp, dk/dv on a shared prefix equal the *sum* of the
    gradients that prefix receives in each standalone branch pass, and
    dq on every branch equals its standalone dq."""
    from repro.kernels.ops import tree_attention

    rng = np.random.default_rng(43)
    P, L, H, hd = 16, 24, 4, 16          # prefix len, branch len
    S = P + 2 * L                        # DFS: [prefix, branch1, branch2]
    scale = hd ** -0.5

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))

    q = rand(1, S, H, hd)
    k = rand(1, S, H, hd)
    v = rand(1, S, H, hd)
    # gradient flows in from branch tokens only — a per-branch run counts
    # its own prefix-query loss once, so summing two runs would double it;
    # restoration is about what the *branches* send back to the prefix.
    do = rand(1, S, H, hd).at[:, :P].set(0.0)

    # tree mask: prefix visible to everything, each branch only to itself
    kv_last = np.zeros((1, S), np.int32)
    kv_last[0, :P] = S - 1
    kv_last[0, P:P + L] = P + L - 1
    kv_last[0, P + L:] = S - 1
    kv_last = jnp.asarray(kv_last)
    _, vjp = jax.vjp(lambda q_, k_, v_:
                     tree_attention(q_, k_, v_, kv_last, scale, 8, 8),
                     q, k, v)
    dq_t, dk_t, dv_t = vjp(do)

    # standalone branch passes: rows [prefix + branch_b], plain causal
    acc_dk = np.zeros((P, H, hd), np.float32)
    acc_dv = np.zeros((P, H, hd), np.float32)
    for b0 in (P, P + L):
        sel = np.r_[0:P, b0:b0 + L]
        qb, kb, vb, dob = (x[:, sel] for x in (q, k, v, do))
        kl_b = jnp.full((1, P + L), P + L - 1, jnp.int32)
        _, vjp_b = jax.vjp(lambda q_, k_, v_:
                           tree_attention(q_, k_, v_, kl_b, scale, 8, 8),
                           qb, kb, vb)
        dqb, dkb, dvb = vjp_b(dob)
        acc_dk += np.asarray(dkb[0, :P])
        acc_dv += np.asarray(dvb[0, :P])
        np.testing.assert_allclose(np.asarray(dq_t[0, b0:b0 + L]),
                                   np.asarray(dqb[0, P:]),
                                   atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dk_t[0, :P]), acc_dk,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dv_t[0, :P]), acc_dv,
                               atol=1e-5, rtol=1e-5)


def test_tree_forward_equals_each_branch_forward():
    """Forward equivalence (Eq. 6): per-token log-prob in the DFS pass
    matches the token's log-prob in its standalone branch pass."""
    from repro.models.model import forward
    cfg = tiny_cfg("dense")
    tree = branching_tree(3, min_leaves=3)
    params = init_params(cfg, jax.random.key(0))
    ser = serialize_tree(tree)
    bt = prepare_batch(cfg, pack_trees([ser], 128))
    h_tree, _ = forward(cfg, params, bt)

    # DFS index of every token per path, mapped against standalone runs
    paths = tree.linearize_paths()
    # reconstruct each path's DFS indices by walking nodes
    node_tok_ranges = [(int(s), int(e)) for s, e in
                       zip(ser.node_start, ser.node_end)]
    # walk tree collecting node ids per path
    ids_per_path = []

    def rec(node, nid_counter, acc):
        nid = nid_counter[0]
        nid_counter[0] += 1
        acc = acc + [nid]
        if not node.children:
            ids_per_path.append(acc)
        for c in node.children:
            rec(c, nid_counter, acc)

    rec(tree.root, [0], [])
    for path_nodes, lin in zip(ids_per_path, paths):
        lb = pack_linear_paths([[lin]], 128)
        bl = prepare_batch(cfg, lb)
        h_lin, _ = forward(cfg, params, bl)
        off = 0
        for nid in path_nodes:
            s, e = node_tok_ranges[nid]
            n = e - s
            np.testing.assert_allclose(
                np.asarray(h_tree[0, s:e]), np.asarray(h_lin[0, off:off + n]),
                atol=2e-5, rtol=2e-5)
            off += n
