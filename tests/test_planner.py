"""Plan-ahead scheduler: cost model units, schedule invariants (token
conservation, wave topology, replica balance) over random forests, async
pipeline ordering/overlap, and the acceptance bar — planner-built plans
are gradient-equivalent to the per-step PR-4 path even with lookahead
re-packing and replica-balanced row padding/permutation."""
import time

import numpy as np
import pytest

from conftest import tiny_cfg
from repro.core.plan_cost import (CompileCacheSim, balanced_row_order,
                                  est_block_skip, packed_signature,
                                  pow2, round_to_multiple, score_packing)
from repro.core.packing import plan_tree_rows
from repro.data.loader import LoaderConfig, tree_stream
from repro.data.synthetic import random_tree
from repro.train.planner import (PlanPipeline, PlannerConfig, plan_stream,
                                 plan_window)

from test_engine import _lc, _max_rel, _two_branch_reference


# ---------------------------------------------------------------------------
# cost model units
# ---------------------------------------------------------------------------

def test_score_packing_counts_padding_and_signatures():
    cache = CompileCacheSim()
    sigs = [packed_signature(4, 128)]
    c = score_packing([[60, 60], [100], []], 128, signatures=sigs,
                      cache=cache)
    assert c.used_tokens == 220
    assert c.padded_tokens == 3 * 128 - 220
    assert c.new_signatures == 1
    cache.commit(sigs)
    c2 = score_packing([[60, 60], [100], []], 128, signatures=sigs,
                       cache=cache)
    assert c2.new_signatures == 0
    assert c2.total < c.total          # cache hit is cheaper


def test_est_block_skip_prefers_many_small_trees():
    # one long tree lights its whole lower-triangle; many small trees
    # stay near the diagonal → higher estimated skip
    one_long = est_block_skip([[256]], 256, 64)
    many_small = est_block_skip([[64, 64, 64, 64]], 256, 64)
    assert many_small > one_long
    assert est_block_skip([[]], 256, 64) == 1.0   # empty row fully skips


def test_pow2_and_round_to_multiple():
    assert [pow2(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    assert pow2(3, lo=8) == 8
    assert round_to_multiple(5, 3) == 6
    assert round_to_multiple(6, 3) == 6
    assert round_to_multiple(7, 1) == 7


def test_balanced_row_order_imbalance_le_one():
    rng = np.random.default_rng(0)
    for R in (2, 3, 4):
        for _ in range(20):
            B = R * rng.integers(1, 5)
            loads = [int(rng.integers(0, 200)) for _ in range(B)]
            k = int(rng.integers(0, B + 1))
            loads[k:] = [0] * (B - k)      # some empty rows
            order = balanced_row_order(loads, R)
            assert sorted(order) == list(range(B))
            per = B // R
            counts = [sum(loads[r] > 0 for r in order[i * per:(i + 1) * per])
                      for i in range(R)]
            assert max(counts) - min(counts) <= 1


def test_plan_tree_rows_bfd_beats_ffd_on_stranded_holes():
    # ffd strands 40 after packing 60 next to 100; bfd fills the tighter
    # row first and needs fewer rows
    sizes = [100, 60, 40, 28]
    ffd = plan_tree_rows(sizes, 128, heuristic="ffd")
    bfd = plan_tree_rows(sizes, 128, heuristic="bfd")
    assert len(bfd) <= len(ffd)
    for rows in (ffd, bfd):
        placed = sorted(i for r in rows for i in r)
        assert placed == list(range(len(sizes)))
        assert all(sum(sizes[i] for i in r) <= 128 for r in rows)


# ---------------------------------------------------------------------------
# schedule invariants over random forests (seeded; hypothesis variant below)
# ---------------------------------------------------------------------------

def _check_window_invariants(cfg, lc, pc, window):
    steps = plan_window(cfg, tiny_lc_copy(lc), pc, window)
    gen_tokens = sum(t.num_unique_tokens() for b in window for t in b)
    gen_trees = sum(len(b) for b in window)
    got_tokens = dropped = seen_trees = 0
    R = pc.num_replicas
    for ps in steps:
        sb = ps.step_batch()
        dropped += sb.dropped
        seen_trees += sb.num_trees
        if sb.tb is not None:
            B = sb.tb.tokens.shape[0]
            # replica-balanced rows: divisible count, non-empty-row
            # imbalance ≤ 1 across contiguous shards
            assert B % max(R, 1) == 0
            got_tokens += int(sb.tb.valid.sum())
            if R > 1:
                per = B // R
                nonempty = sb.tb.valid.any(axis=1)
                counts = [int(nonempty[i * per:(i + 1) * per].sum())
                          for i in range(R)]
                assert max(counts) - min(counts) <= 1
        got_tokens += sum(t.num_unique_tokens() for t in sb.oversized)
        if sb.oversized:
            plan = ps.execution_plan()
            waves = plan.partition.waves
            for w, wp in enumerate(waves):
                for ref in wp.parents:
                    # parents never scheduled later than children
                    assert ref.wave < w
                # wave rows shard evenly too
                Bb = wp.batch["tokens"].shape[0]
                assert Bb % max(R, 1) == 0
                if R > 1:
                    # wave-level load balance: rows are permuted by
                    # gateway + token load (snake-dealt like packed
                    # rows), so contiguous per-replica shards carry
                    # non-empty-row counts within 1 of each other
                    per = Bb // R
                    loads = [int(wp.batch["valid"][r].sum())
                             + wp.A_real[r] for r in range(Bb)]
                    nz = [sum(ld > 0 for ld in loads[i * per:(i + 1) * per])
                          for i in range(R)]
                    assert max(nz) - min(nz) <= 1, (w, loads, nz)
    assert seen_trees + dropped == gen_trees
    if lc.mode != "tree":
        return          # baseline packs replicated path tokens, not unique
    if lc.auto_partition:
        assert dropped == 0
        assert got_tokens == gen_tokens   # every token packed/partitioned
    else:
        assert got_tokens <= gen_tokens


def tiny_lc_copy(lc):
    from dataclasses import replace
    return replace(lc)


def _forest(seed, n, seg=(2, 9), depth=4):
    rng = np.random.default_rng(seed)
    return [random_tree(rng, vocab_size=97, max_depth=depth,
                        seg_len_range=seg) for _ in range(n)]


@pytest.mark.parametrize("mode,route", [
    ("tree", True), ("tree", False), ("baseline", False)])
def test_planner_window_invariants_seeded(mode, route):
    cfg = tiny_cfg("dense")
    for seed in range(3):
        for W, R in ((1, 1), (2, 2), (3, 2)):
            lc = LoaderConfig(seq_len=64, batch_rows=3, trees_per_batch=4,
                              mode=mode, seed=seed,
                              auto_partition=route, capacity=48)
            pc = PlannerConfig(lookahead=W, num_replicas=R)
            window = [_forest(100 * seed + b, 4) for b in range(W)]
            _check_window_invariants(cfg, lc, pc, window)


def test_planner_window_invariants_property():
    """Hypothesis property test: arbitrary forests conserve every token,
    schedule parents no later than children, and keep replica row-load
    imbalance ≤ 1 (the CI fast gate runs this; locally it skips when
    hypothesis is absent)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.core.tree import TrajectoryTree, TreeNode

    @st.composite
    def trees(draw, max_depth=3, max_children=3, max_seg=6):
        def node(depth):
            L = draw(st.integers(1, max_seg))
            toks = draw(st.lists(st.integers(0, 89), min_size=L,
                                 max_size=L))
            n = TreeNode(tokens=np.asarray(toks, np.int32))
            if depth < max_depth:
                k = draw(st.integers(0, max_children))
                if k >= 2 or (k == 1 and draw(st.booleans())):
                    n.children = [node(depth + 1) for _ in range(k)]
            return n

        return TrajectoryTree(root=node(0))

    cfg = tiny_cfg("dense")

    @given(st.lists(st.lists(trees(), min_size=1, max_size=4),
                    min_size=1, max_size=3),
           st.integers(1, 3), st.booleans())
    @settings(max_examples=25, deadline=None)
    def run(window, R, route):
        lc = LoaderConfig(seq_len=64, batch_rows=3,
                          trees_per_batch=max(len(b) for b in window),
                          mode="tree", auto_partition=route, capacity=48)
        pc = PlannerConfig(lookahead=len(window), num_replicas=R)
        _check_window_invariants(cfg, lc, pc, window)

    run()


def test_lookahead_fills_holes_across_batches():
    """The point of plan-ahead: trees from later generator batches fill
    holes the per-step greedy leaves, so the window needs no more padded
    cells and at least as few steps."""
    cfg = tiny_cfg("dense")
    lc = LoaderConfig(seq_len=96, batch_rows=2, trees_per_batch=3,
                      mode="tree", kind="agentic", seed=3,
                      gen_kwargs=dict(turn_len_range=(4, 14), num_turns=2))

    def packed_cells(pc):
        pad = uniq = steps = 0
        for ps in plan_stream(cfg, tiny_lc_copy(lc), 8, pc):
            sb = ps.step_batch()
            if sb.tb is None:
                continue
            steps += 1
            pad += sb.tb.tokens.size - int(sb.tb.valid.sum())
            uniq += int(sb.tb.valid.sum())
        return pad, uniq, steps

    pad_g, uniq_g, steps_g = packed_cells(
        PlannerConfig(lookahead=1, heuristics=("ffd",)))
    pad_p, uniq_p, steps_p = packed_cells(PlannerConfig(lookahead=4))
    assert uniq_p >= uniq_g          # lookahead never trains less data
    assert steps_p <= steps_g
    assert pad_p / max(uniq_p, 1) <= pad_g / max(uniq_g, 1)


# ---------------------------------------------------------------------------
# async pipeline
# ---------------------------------------------------------------------------

def test_plan_pipeline_sync_and_async_agree():
    items = list(range(10))
    build = lambda i: i * i
    sync = PlanPipeline(iter(items), build, workers=0)
    assert list(sync) == [i * i for i in items]
    assert sync.built == 10
    # synchronous: every scheduling/build second is consumer-visible
    assert sync.exposed_s == pytest.approx(
        sync.schedule_s + sync.build_s, rel=1e-6, abs=1e-9)
    for workers in (1, 2):
        pipe = PlanPipeline(iter(items), build, workers=workers)
        assert list(pipe) == [i * i for i in items]   # order preserved
        assert pipe.built == 10


def test_plan_pipeline_overlaps_build_behind_consumer():
    def build(i):
        time.sleep(0.005)
        return i

    pipe = PlanPipeline(iter(range(8)), build, workers=1, depth=2)
    out = []
    for v in pipe:
        time.sleep(0.02)             # the "device step"
        out.append(v)
    assert out == list(range(8))
    # all but the first build hides behind consumer work
    assert pipe.exposed_s < pipe.build_s


def test_plan_pipeline_propagates_errors_in_order():
    def source():
        yield 1
        yield 2
        raise RuntimeError("schedule boom")

    pipe = PlanPipeline(source(), lambda i: i, workers=1)
    it = iter(pipe)
    assert next(it) == 1
    assert next(it) == 2
    with pytest.raises(RuntimeError, match="schedule boom"):
        next(it)

    def bad_build(i):
        if i == 1:
            raise ValueError("build boom")
        return i

    pipe = PlanPipeline(iter(range(3)), bad_build, workers=2)
    it = iter(pipe)
    assert next(it) == 0
    with pytest.raises(ValueError, match="build boom"):
        next(it)


# ---------------------------------------------------------------------------
# the acceptance bar: planner plans ≡ the PR-4 per-step path
# ---------------------------------------------------------------------------

def _check_planner_equivalence(family, impl):
    """Lookahead re-packing, cost-model row layout, replica-balanced
    padding/permutation and wave row rounding must all be gradient-
    neutral: the engine on a planner-built plan matches the pre-refactor
    two-branch math on the very same step data."""
    import jax
    from repro.models.model import init_params
    from repro.train.engine import TreeTrainEngine

    cfg = tiny_cfg(family)
    lc = _lc()
    pc = PlannerConfig(lookahead=2, num_replicas=2)
    steps = list(plan_stream(cfg, lc, 8, pc))
    ps = next(p for p in steps if any(p.rows) and len(p.oversized) >= 1)
    sb = ps.step_batch()
    params = init_params(cfg, jax.random.key(0))
    l_ref, g_ref = _two_branch_reference(cfg, params, sb, lc, impl)

    engine = TreeTrainEngine(cfg, impl=impl, donate=False)
    grads, scal = engine.accumulate(params, ps.execution_plan())
    l_eng = float(np.asarray(scal)[0])
    # 5e-6, not 1e-6: the compile-aware oversized router may co-locate a
    # window's partitioned trees, so the engine's wave grouping (hence
    # its f32 accumulation ORDER) differs from the reference driver's —
    # same math, reordered sums
    assert abs(l_eng - l_ref) / max(abs(l_ref), 1e-9) <= 5e-6
    assert _max_rel(grads, g_ref) <= 5e-6


def test_planner_matches_two_branch_dense_ref():
    _check_planner_equivalence("dense", "ref")


@pytest.mark.slow
@pytest.mark.parametrize("family,impl", [
    ("dense", "chunked"), ("dense", "pallas"),
    ("moe", "chunked"), ("moe", "pallas")])
def test_planner_matches_two_branch(family, impl):
    _check_planner_equivalence(family, impl)


@pytest.mark.parametrize("window", [None, 48])
def test_plan_counts_attention_blocks(window):
    """A packed plan counts the block pairs the attention kernels run
    (``attn_blocks_live``) and their dense grid (``attn_blocks_dense``):
    the sums of ``block_live_mask`` over its rows at the kernels' block
    size, windowed where the model is."""
    from dataclasses import replace

    from repro.kernels.tree_attention import block_live_mask
    cfg = tiny_cfg("dense")
    cfg = replace(cfg, attn=replace(cfg.attn, window=window))
    lc = _lc(seq_len=512, trees_per_batch=12,
             gen_kwargs=dict(turn_len_range=(8, 40), num_turns=3))
    seen = 0
    for ps in plan_stream(cfg, lc, 3):
        packed = ps.execution_plan().packed
        if packed is None:
            continue
        tb = ps.step_batch().tb
        live = block_live_mask(tb.kv_last, 512, 128, 128, pos_q=tb.pos_ids,
                               pos_k=tb.pos_ids, window=window)
        assert packed.attn_blocks_live == int(live.sum())
        assert packed.attn_blocks_dense == live.size == len(tb.kv_last) * 16
        assert 0 < packed.attn_blocks_live < packed.attn_blocks_dense
        seen += 1
    assert seen


def test_cell_pool_live_block_share():
    """The benchmark cell ``qwen15_tree_sft`` plans 96 packed steps of
    2 rows × 2048 from its seed; its kernels run under a tenth of the
    dense grid's block pairs there."""
    from bench.run import Run, load_cell
    cell = load_cell("qwen15_tree_sft")
    run = Run(cell["c"], cell["traffic"], 2147911117, 1)
    live = dense = steps = 0
    for ps in plan_stream(run.cfg, run.lc, iter(run.ptrees), run.pc):
        packed = ps.execution_plan().packed
        live += packed.attn_blocks_live
        dense += packed.attn_blocks_dense
        steps += 1
    assert steps == 96 and dense == steps * 2 * 16 * 16
    assert round(live / dense, 3) == 0.098


def test_planner_streams_deterministic():
    """Every consumer of the planner (``plans``, the deprecated loader
    wrappers) sees the same schedule — one deterministic plan stream."""
    cfg = tiny_cfg("dense")
    lc = _lc()
    a = [(ps.index, len(ps.fits), len(ps.oversized), ps.dropped)
         for ps in plan_stream(cfg, lc, 6)]
    b = [(ps.index, len(ps.fits), len(ps.oversized), ps.dropped)
         for ps in plan_stream(cfg, lc, 6)]
    assert a == b
    n = sum(1 for _ in tree_stream(cfg, lc, 6))
    assert n == 6
