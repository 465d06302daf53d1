"""Cross-tree forest grafting (core/forest + planner --graft) and
planner-chosen partition capacity (core/partition.choose_capacity).

The load-bearing claims:

  - grafting is pure dedup: token conservation (grafted unique + saved
    == summed source unique), bit-exact λ on every reused source node,
    λ summed over members on shared spine nodes, path-count additivity;
  - a grafting-enabled planner schedule is gradient-equal (≤ 1e-6
    max-rel) to independent per-tree training of the same stream —
    compared per-window with each step weighted by its tree count,
    because per-step losses are means over that step's trees and graft
    on/off distribute trees across steps differently;
  - on template-heavy streams the grafted schedule computes measurably
    fewer unique tokens (the paper's cross-tree shared-prefix motivation).

MoE: the router's load-balance and z losses are per branch
(``models/moe.router_loss``): each branch's terms are sums along its own
path, weighted as its tokens are, so a prefix shared by k branches or
trees counts k times whether it is computed once or k times.  The strict
bar holds with them on, against independent per-branch training (every
root-to-leaf path its own sequence), in packed rows and in partition
waves, at the published coefficient 0.001 and at 0.1.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.core.forest import graft_trees
from repro.core.partition import choose_capacity
from repro.core.tree import serialize_tree, tree_lam_map
from repro.data.loader import LoaderConfig
from repro.data.synthetic import (template_stream, template_tokens,
                                  trees_for_batch)
from repro.models.model import init_params
from repro.train.engine import TreeTrainEngine
from repro.train.planner import PlannerConfig, plan_stream


def _template_window(seed, batches=3, trees=5, **kw):
    gen = dict(vocab_size=500, num_templates=2, template_len=48,
               num_turns=2, turn_len_range=(4, 16))
    gen.update(kw)
    out = []
    for b in range(batches):
        out += trees_for_batch(seed * 100_003 + b, n_trees=trees,
                               kind="template", **gen)
    return out


# ---------------------------------------------------------------------------
# pure-dedup invariants (host-only)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss_mode", ["sep_avg", "uniform", "rl"])
def test_graft_conservation_seeded(loss_mode):
    for seed in range(4):
        trees = _template_window(seed)
        if loss_mode == "rl":
            from repro.data.synthetic import assign_branch_advantages
            rng = np.random.default_rng(seed)
            for t in trees:
                assign_branch_advantages(
                    t, rng.normal(size=t.num_leaves()))
        grafts, passthrough = graft_trees(trees, loss_mode=loss_mode,
                                          min_graft=16)
        assert grafts, "template window must produce at least one graft"
        # srcs ∪ passthrough partitions the input indices
        covered = sorted(i for g in grafts for i in g.srcs) + passthrough
        assert sorted(covered) == list(range(len(trees)))
        for g in grafts:
            assert len(g.srcs) >= 2
            src_unique = sum(trees[i].num_unique_tokens() for i in g.srcs)
            # token conservation: dedup only, nothing dropped or invented
            assert g.tree.num_unique_tokens() + g.saved_tokens == src_unique
            assert g.saved_tokens > 0
            assert g.shared_tokens >= 16
            # path-count additivity: every source branch survives
            assert g.tree.num_leaves() == sum(
                trees[i].num_leaves() for i in g.srcs)
            # λ conservation: serialized weight mass equals the sources'
            ser = serialize_tree(g.tree, lam_map=g.lam_map)
            w_src = sum(
                serialize_tree(trees[i], loss_mode=loss_mode)
                .weight.astype(np.float64).sum() for i in g.srcs)
            # rl weights nearly cancel (± advantages), so tolerance is
            # relative to the total weight MASS, not the near-zero sum
            tol = 1e-6 * max(np.abs(ser.weight).sum(), 1.0)
            np.testing.assert_allclose(
                ser.weight.astype(np.float64).sum(), w_src, atol=tol)
            # reused source nodes keep their λ BIT-exactly
            for i in g.srcs:
                lam_src = tree_lam_map(trees[i].root, loss_mode)
                for node in g.tree.nodes():
                    if id(node) in lam_src:
                        assert g.lam_map[id(node)] == lam_src[id(node)]


def test_graft_property():
    """Hypothesis variant of the conservation invariants over arbitrary
    trees — shared prefixes arise from the tiny vocab (skips when
    hypothesis is absent, like the other property suites)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.core.tree import TrajectoryTree, TreeNode

    @st.composite
    def trees(draw, max_depth=3, max_children=3, max_seg=5):
        def node(depth):
            L = draw(st.integers(1, max_seg))
            toks = draw(st.lists(st.integers(0, 2), min_size=L,
                                 max_size=L))
            n = TreeNode(tokens=np.asarray(toks, np.int32))
            if depth < max_depth:
                k = draw(st.integers(0, max_children))
                if k >= 2 or (k == 1 and draw(st.booleans())):
                    n.children = [node(depth + 1) for _ in range(k)]
            return n

        return TrajectoryTree(root=node(0))

    @given(st.lists(trees(), min_size=2, max_size=6),
           st.integers(1, 4))
    @settings(max_examples=40, deadline=None)
    def check(forest, min_graft):
        grafts, passthrough = graft_trees(forest, min_graft=min_graft)
        covered = sorted(i for g in grafts for i in g.srcs) + passthrough
        assert sorted(covered) == list(range(len(forest)))
        for g in grafts:
            src_unique = sum(forest[i].num_unique_tokens()
                             for i in g.srcs)
            assert (g.tree.num_unique_tokens() + g.saved_tokens
                    == src_unique)
            assert g.saved_tokens >= min_graft
            assert g.tree.num_leaves() == sum(
                forest[i].num_leaves() for i in g.srcs)
            ser = serialize_tree(g.tree, lam_map=g.lam_map)
            w_src = sum(serialize_tree(forest[i]).weight.sum()
                        for i in g.srcs)
            np.testing.assert_allclose(ser.weight.sum(), w_src,
                                       rtol=1e-6)

    check()


# ---------------------------------------------------------------------------
# schedule-level: grafted planner ≡ independent per-tree training
# ---------------------------------------------------------------------------

def _lc(loss_mode="sep_avg", **kw):
    base = dict(seq_len=192, batch_rows=3, trees_per_batch=6, mode="tree",
                kind="template", seed=3, loss_mode=loss_mode,
                auto_partition=True,
                gen_kwargs=dict(num_templates=2, template_len=96,
                                num_turns=1, turn_len_range=(4, 12)))
    base.update(kw)
    return LoaderConfig(**base)


def _window_grads(cfg, params, lc, pc, impl, steps=4):
    """Tree-count-weighted loss/grads over the stream: Σ n·(per-step
    mean) / Σ n — invariant to how a schedule distributes trees across
    steps, which is exactly what graft on/off changes."""
    eng = TreeTrainEngine(cfg, impl=impl, donate=False)
    tot_n, tot_l, tot_g, uniq = 0, 0.0, None, 0
    for ps in plan_stream(cfg, lc, steps, pc):
        plan = ps.execution_plan()
        g, scal = eng.accumulate(params, plan)
        n = plan.num_trees
        tot_l += n * float(np.asarray(scal)[0])
        # float64 host accumulation: the weighted combine must not add
        # noise of its own on top of the per-step fp32 engine math
        g = jax.tree.map(lambda a: n * np.asarray(a, np.float64), g)
        tot_g = g if tot_g is None else jax.tree.map(np.add, tot_g, g)
        tot_n += n
        uniq += plan.unique_tokens
    return (tot_l / tot_n,
            jax.tree.map(lambda a: a / tot_n, tot_g), uniq, tot_n)


def _max_rel(g, g_ref):
    rels = jax.tree.map(
        lambda a, b: float(np.abs(a - b).max() /
                           (np.abs(b).max() + 1e-9)), g, g_ref)
    return max(jax.tree.leaves(rels))


def _per_tree_reference(cfg, params, lc, impl, steps=4):
    """Independent per-tree training: every tree serialized alone in its
    own row, per-tree grads averaged in float64 — the ungrafted ground
    truth the ISSUE bar compares against."""
    from repro.core.packing import pack_trees
    from repro.data.loader import tree_stream
    from repro.models.model import prepare_batch
    from repro.train.train_step import make_grad_fn

    fn = make_grad_fn(cfg, impl=impl)
    tot_l, tot_g, n = 0.0, None, 0
    for batch in tree_stream(cfg, lc, steps):
        for t in batch:
            ser = serialize_tree(t, loss_mode=lc.loss_mode)
            assert ser.n <= lc.seq_len
            inputs = prepare_batch(cfg, pack_trees([ser], lc.seq_len),
                                   num_trees=1)
            loss, grads, _ = fn(params, inputs)
            tot_l += float(loss)
            grads = jax.tree.map(lambda a: np.asarray(a, np.float64),
                                 grads)
            tot_g = grads if tot_g is None else jax.tree.map(
                np.add, tot_g, grads)
            n += 1
    return tot_l / n, jax.tree.map(lambda a: a / n, tot_g), n


def _check_graft_grad_equivalence(cfg, impl, loss_mode):
    params = init_params(cfg, jax.random.key(0))
    lc = _lc(loss_mode)
    l_ref, g_ref, n_ref = _per_tree_reference(cfg, params, lc, impl)
    l1, g1, u1, n1 = _window_grads(
        cfg, params, lc,
        PlannerConfig(lookahead=4, graft=True, min_graft=8), impl)
    assert n1 == n_ref                   # every source tree accounted
    assert abs(l1 - l_ref) / max(abs(l_ref), 1e-9) <= 1e-6
    assert _max_rel(g1, g_ref) <= 1e-6


def test_graft_grad_equivalence_dense_ref():
    _check_graft_grad_equivalence(tiny_cfg("dense"), "ref", "rl")


@pytest.mark.slow
@pytest.mark.parametrize("family,impl", [
    ("dense", "chunked"), ("dense", "pallas"),
    ("moe", "chunked"), ("moe", "pallas")])
def test_graft_grad_equivalence_grid(family, impl):
    _check_graft_grad_equivalence(tiny_cfg(family), impl, "sep_avg")


def _per_branch_reference(cfg, params, lc, steps=4):
    """Independent per-branch training: every root-to-leaf path of every
    tree its own causal sequence (``pack_linear_paths``), per-tree grads
    averaged in float64."""
    from repro.core.packing import pack_linear_paths
    from repro.data.loader import tree_stream
    from repro.models.model import prepare_batch
    from repro.train.train_step import make_grad_fn

    fn = make_grad_fn(cfg, impl="ref")
    tot_l, tot_g, n = 0.0, None, 0
    for batch in tree_stream(cfg, lc, steps):
        for t in batch:
            tb = pack_linear_paths([t.linearize_paths()], 4 * lc.seq_len,
                                   loss_mode=lc.loss_mode)
            loss, grads, _ = fn(params, prepare_batch(cfg, tb, num_trees=1))
            tot_l += float(loss)
            grads = jax.tree.map(lambda a: np.asarray(a, np.float64), grads)
            tot_g = grads if tot_g is None else jax.tree.map(
                np.add, tot_g, grads)
            n += 1
    return tot_l / n, jax.tree.map(lambda a: a / n, tot_g), n


@pytest.mark.parametrize("layout", ["packed", "waves"])
@pytest.mark.parametrize("aux", [0.001, 0.1])
def test_router_loss_matches_per_branch_training(aux, layout):
    """Load-balance and z terms at ``aux``: the tree schedule (grafted
    packed rows, or every tree through partition waves of 48 tokens) is
    gradient-equal to per-branch training (≤ 1e-6 max-rel, float32)."""
    cfg = tiny_cfg("moe")
    cfg = replace(cfg, moe=replace(cfg.moe, router_aux_weight=aux,
                                   router_z_weight=aux))
    params = init_params(cfg, jax.random.key(0))
    waves = layout == "waves"
    lc = _lc(seq_len=96 if waves else 192, capacity=48 if waves else None)
    l_ref, g_ref, n_ref = _per_branch_reference(cfg, params, lc)
    l1, g1, _, n1 = _window_grads(
        cfg, params, lc,
        PlannerConfig(lookahead=4, graft=not waves, min_graft=8), "ref")
    assert n1 == n_ref
    assert abs(l1 - l_ref) / max(abs(l_ref), 1e-9) <= 1e-6
    assert _max_rel(g1, g_ref) <= 1e-6


# ---------------------------------------------------------------------------
# saved-token fraction on a template-heavy stream (host-only)
# ---------------------------------------------------------------------------

def test_graft_saves_quarter_on_template_stream():
    cfg = tiny_cfg("dense")
    lc = _lc()

    def stats(pc):
        uniq = trees = dropped = 0
        for ps in plan_stream(cfg, lc, 4, pc):
            sb = ps.step_batch()
            dropped += sb.dropped
            trees += sb.num_trees
            if sb.tb is not None:
                uniq += int(sb.tb.valid.sum())
            uniq += sum(t.num_unique_tokens() for t in sb.oversized)
        return uniq, trees, dropped

    u0, t0, d0 = stats(PlannerConfig(lookahead=4))
    u1, t1, d1 = stats(PlannerConfig(lookahead=4, graft=True,
                                     min_graft=16))
    assert t1 + d1 == t0 + d0            # source-tree accounting intact
    assert d1 == 0
    assert u1 <= 0.75 * u0, (u0, u1)     # ≥ 25% unique tokens saved


# ---------------------------------------------------------------------------
# planner-chosen partition capacity
# ---------------------------------------------------------------------------

def test_choose_capacity_bounds_and_chunk():
    rng = np.random.default_rng(0)
    from repro.data.synthetic import agentic_tree
    trees = [agentic_tree(rng, vocab_size=300, num_turns=3,
                          turn_len_range=(16, 48)) for _ in range(3)]
    for chunk in (None, 8, 16):
        cap = choose_capacity(trees, 256, chunk_size=chunk)
        assert 0 < cap <= 256
        if chunk:
            assert cap % chunk == 0
        # pow2 fraction of seq_len (signature buckets stay enumerable)
        assert 256 % cap == 0


def test_auto_capacity_flows_through_planner():
    cfg = tiny_cfg("dense")
    lc = _lc(seq_len=96, auto_capacity=True,
             gen_kwargs=dict(num_templates=2, template_len=48,
                             num_turns=3, turn_len_range=(8, 32)))
    pc = PlannerConfig(lookahead=2)
    saw_oversized = False
    for ps in plan_stream(cfg, lc, 4, pc):
        sb = ps.step_batch()
        if sb.oversized:
            saw_oversized = True
            assert ps.capacity is not None
            assert 0 < ps.capacity <= lc.seq_len
            assert lc.seq_len % ps.capacity == 0
            plan = ps.execution_plan()      # materializes at that cap
            assert plan.partition is not None
    assert saw_oversized
    # an explicit capacity always wins over auto
    lc2 = replace(lc, capacity=96)
    for ps in plan_stream(cfg, lc2, 2, pc):
        if ps.step_batch().oversized:
            assert ps.capacity in (None, 96)


# ---------------------------------------------------------------------------
# template generator determinism
# ---------------------------------------------------------------------------

def test_template_tokens_deterministic_across_batches():
    a = template_tokens(7, 1, 64, 1000)
    b = template_tokens(7, 1, 64, 1000)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, template_tokens(7, 2, 64, 1000))
    assert not np.array_equal(a, template_tokens(8, 1, 64, 1000))
    # every stream batch opens trees with one of the SAME template heads
    heads = set()
    for batch in template_stream(5, num_batches=3, trees_per_batch=4,
                                 vocab_size=1000, num_templates=2,
                                 template_len=32, num_turns=1,
                                 turn_len_range=(4, 8)):
        for t in batch:
            heads.add(tuple(t.root.tokens[:32].tolist()))
    assert len(heads) == 2
    expect = {tuple(template_tokens(7, tid, 32, 1000).tolist())
              for tid in range(2)}
    assert heads == expect
