"""Redundancy-Free Tree Partitioning (paper §3.3, App. B): partitioned
loss/grads equal the whole-tree pass; token accounting matches Fig. 5.

MoE note: router load-balance aux is computed per compute-batch (each
partition), like per-microbatch aux under gradient accumulation — it is
excluded from strict equivalence (router_aux_weight=0 here); the CE part
is exact.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.configs.base import MoECfg
from repro.core.gateway import partitioned_value_and_grad
from repro.core.packing import pack_trees
from repro.core.partition import (partition_token_counts, partition_tree,
                                  standard_partition_token_counts)
from repro.core.tree import serialize_tree
from repro.data.synthetic import random_tree
from repro.models.model import init_params, loss_and_metrics, needs_chunks, \
    prepare_batch

pytestmark = pytest.mark.slow  # multi-minute partition equivalences


def get_tree(seed=0, lo=60, hi=120):
    for s in range(seed, seed + 300):
        t = random_tree(np.random.default_rng(s), vocab_size=97,
                        max_depth=5, seg_len_range=(3, 9))
        if t.num_leaves() >= 4 and lo <= t.num_unique_tokens() <= hi:
            return t
    raise RuntimeError


def _whole_tree_ref(cfg, params, tree, chunk):
    ser = serialize_tree(tree, chunk_size=chunk)
    S = ((ser.n + 31) // 32) * 32
    b = prepare_batch(cfg, pack_trees([ser], S, chunk_size=chunk))
    l, _ = loss_and_metrics(cfg, params, b)
    g = jax.grad(lambda p: loss_and_metrics(cfg, p, b)[0])(params)
    return float(l), g


FAMILIES = ["dense", "moe", "ssm_rwkv6", "ssm_mamba2", "ssm_gdn", "hybrid"]


@pytest.mark.parametrize("family", FAMILIES)
def test_partitioned_equals_whole_tree(family):
    cfg = tiny_cfg(family)
    if family == "moe":
        cfg = cfg.replace(moe=MoECfg(num_experts=4, top_k=2, d_expert=32,
                                     router_aux_weight=0.0,
                                     router_z_weight=0.0))
    chunk = cfg.ssm.chunk_size if needs_chunks(cfg) else None
    params = init_params(cfg, jax.random.key(0))
    tree = get_tree()
    l_ref, g_ref = _whole_tree_ref(cfg, params, tree, chunk)
    l_p, g_p, info = partitioned_value_and_grad(cfg, params, tree,
                                                capacity=40)
    assert info["num_partitions"] >= 2, "capacity too large to test cuts"
    np.testing.assert_allclose(l_p, l_ref, rtol=2e-5)
    rels = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9)),
        g_p, g_ref)
    assert max(jax.tree.leaves(rels)) < 1e-4   # paper App. B.8 f32 bound


def test_partitioned_deep_chain_of_cuts():
    """Gateways must chain across ≥3 partition generations."""
    cfg = tiny_cfg("dense")
    params = init_params(cfg, jax.random.key(1))
    tree = get_tree(7, lo=90, hi=160)
    l_ref, g_ref = _whole_tree_ref(cfg, params, tree, None)
    l_p, g_p, info = partitioned_value_and_grad(cfg, params, tree,
                                                capacity=24)
    assert info["num_partitions"] >= 4
    np.testing.assert_allclose(l_p, l_ref, rtol=2e-5)
    rels = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9)),
        g_p, g_ref)
    assert max(jax.tree.leaves(rels)) < 1e-4


def test_partition_planner_invariants():
    """Every partition ≤ capacity; unique tokens preserved; parents first;
    differentiable boundaries beat ancestor re-inclusion (Fig. 5)."""
    tree = get_tree(3, lo=100, hi=200)
    C = 48
    parts = partition_tree(tree, C)
    counts = partition_token_counts(parts)
    assert all(p.ser.n <= C for p in parts)
    assert counts["unique_tokens"] == tree.num_unique_tokens()
    for p in parts:
        assert p.parent_pid < p.pid   # topological (parents first)
    std = standard_partition_token_counts(tree, C)
    assert std > counts["unique_tokens"]   # boundary recomputation removed
    flat = tree.flat_tokens()
    assert flat >= std                      # and flattening is worst


def test_partition_memory_bound_is_path():
    """#simultaneously-open vjp closures ≤ partition-tree depth — probe via
    the recursion structure: max cuts chain length."""
    tree = get_tree(11, lo=120, hi=250)
    parts = partition_tree(tree, 32)
    depth = {0: 1}
    for p in parts[1:]:
        depth[p.pid] = depth[p.parent_pid] + 1
    # sanity: a path bound exists and is far below #partitions for bushy trees
    assert max(depth.values()) <= len(parts)
