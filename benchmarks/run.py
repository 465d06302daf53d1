"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and (with ``--out``) writes
the same rows as a JSON artifact for CI:

  por_sweep_*        Fig. 8a — tree vs baseline step time across POR
  partition_tokens   Fig. 5  — token counts: flatten / standard / ours
  partition_sweep_*  Fig. 8b — partitioned tree training under memory cap
  realistic_*        Fig. 7  — agentic-tree speedup + loss deviation
  memory_overhead    §4.6    — extra tree-metadata bytes vs activations
  kernel_blocks      App. A.1 — tree-attention kernel block-skip ratio
  kernel_fwd / kernel_fwd_bwd
                     App. A.1 — fused Pallas kernel wall time, forward and
                     forward+backward (jax.grad through the op), tree
                     packing vs linearized packing of the same trees
  packed_partition   §3.4 — batched wave-scheduled partitioned step:
                     timing vs the whole-tree pass + tree-vs-partitioned
                     token accounting (unique / padded)
  gateway_impl       §3.3 — the same partitioned step with impl=pallas
                     (fused kernels on the gateway-extended KV layout)
                     vs impl=chunked (XLA scan fallback)
  engine_step        §3.4 — one optimizer step over a mixed stream
                     (packed rows + oversized trees) through the unified
                     plan→execute TreeTrainEngine vs the pre-refactor
                     two-branch loop; asserts ≤ 1 host sync per step
  plan_efficiency    schedule level — plan-ahead scheduler: padded-vs-
                     unique tokens of global lookahead packing vs greedy
                     per-step first-fit, plus plan-build ms overlapped vs
                     exposed behind engine steps (async pipeline)
  rl_service         §2 RL model-update — the closed async rollout→tree→
                     train loop: shared-prefix KV prefill savings (each
                     group's prefix computed exactly once), generation
                     overlap fraction behind training, bounded staleness,
                     zero dropped trees
  compile_warmup     runtime level — AOT warmup engine (train/warmup):
                     cold vs warm step-1 latency, retrace count (0 after
                     universe warmup on an in-universe stream), exposed
                     compile wait fraction, and persistent-compile-cache
                     restart (second process writes 0 new cache modules);
                     each timed step also emits a CostWeights calibration
                     sample into the --out artifact

Flags:
  --smoke      tiny qwen1.5-0.5B-scale config, CPU-interpret friendly,
               finishes in a few minutes — the CI benchmark gate (the
               compile_warmup row's cold-compile baseline and restart
               probes are inherently compile-bound)
  --impl X     attention impl for the model-level benches (ref/chunked/
               pallas); model benches default to ref, kernel benches
               always exercise the Pallas op
  --out F      write rows + environment metadata as JSON
  --calibrate F
               fit CostWeights from a previous --out artifact's
               calib_samples (least squares, pad-normalized) and print
               the ``CostWeights(...)`` literal; runs no benchmarks
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")  # allow `python -m benchmarks.run` from repo root

from benchmarks.common import (baseline_inputs, bench_model,  # noqa: E402
                               timed_loss_grad, tree_inputs)
from repro.core.gateway import partitioned_value_and_grad  # noqa: E402
from repro.core.partition import (partition_token_counts,  # noqa: E402
                                  partition_tree,
                                  standard_partition_token_counts)
from repro.core.tree import serialize_tree  # noqa: E402
from repro.data.loader import dataset_por  # noqa: E402
from repro.data.synthetic import (agentic_tree,  # noqa: E402
                                  por_controlled_tree, trees_for_batch)
from repro.models.model import init_params  # noqa: E402

ROWS: list[dict] = []
# cost-model calibration samples: one dict per timed compile_warmup step
# (wall seconds + CostWeights features) — written into the --out artifact
# and consumed by ``--calibrate`` to least-squares-fit CostWeights
CALIB: list[dict] = []


def emit(name: str, us: float, derived: str) -> None:
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# Fig. 8a — POR sweep, full tree in memory
# ---------------------------------------------------------------------------

def bench_por_sweep(impl: str = "ref") -> None:
    cfg = bench_model()
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    for por in (0.2, 0.5, 0.8, 0.92):
        trees = [por_controlled_tree(rng, target_por=por, num_paths=8,
                                     tokens_per_path=96) for _ in range(2)]
        real_por = dataset_por(trees)
        # both modes pack into rows of the SAME length (as the paper's
        # sequence-packing baseline does) — baseline simply needs more rows
        n_tree = max(serialize_tree(t).n for t in trees)
        S = ((max(n_tree, 256) + 127) // 128) * 128
        bt, _ = tree_inputs(cfg, trees, S)
        bl, _ = baseline_inputs(cfg, trees, S)
        t_tree, l_tree = timed_loss_grad(cfg, params, bt, impl=impl)
        t_base, l_base = timed_loss_grad(cfg, params, bl, impl=impl)
        bound = 1.0 / (1.0 - real_por)
        emit(f"por_sweep_{int(por * 100)}", t_tree * 1e6,
             f"speedup={t_base / t_tree:.2f}x bound={bound:.2f}x "
             f"por={real_por:.3f} "
             f"loss_rel={abs(float(l_tree - l_base)) / abs(float(l_base)):.1e}")


# ---------------------------------------------------------------------------
# Fig. 5 — partition token accounting
# ---------------------------------------------------------------------------

def bench_partition_tokens() -> None:
    rng = np.random.default_rng(1)
    tree = agentic_tree(rng, num_turns=7, turn_len_range=(40, 200),
                        vocab_size=1024)
    uniq = tree.num_unique_tokens()
    C = max(256, ((uniq // 3) // 64) * 64)
    flat = tree.flat_tokens()
    std = standard_partition_token_counts(tree, C)
    ours = partition_token_counts(partition_tree(tree, C))
    emit("partition_tokens", 0.0,
         f"flatten={flat} standard={std} ours={ours['unique_tokens']} "
         f"unique={uniq} parts={ours['num_partitions']} cap={C}")
    assert ours["unique_tokens"] == uniq


# ---------------------------------------------------------------------------
# Fig. 8b — memory-constrained partitioned training
# ---------------------------------------------------------------------------

def bench_partition_sweep() -> None:
    cfg = bench_model(n_layers=2)
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(2)
    for por in (0.5, 0.8):
        tree = por_controlled_tree(rng, target_por=por, num_paths=8,
                                   tokens_per_path=128)
        C = 256
        partitioned_value_and_grad(cfg, params, tree, C)   # warm traces
        t0 = time.perf_counter()
        l_p, _, info = partitioned_value_and_grad(cfg, params, tree, C)
        t_part = time.perf_counter() - t0
        S_flat = ((tree.max_path_tokens() + 127) // 128) * 128
        bl, _ = baseline_inputs(cfg, [tree], S_flat)
        t_base, l_base = timed_loss_grad(cfg, params, bl)
        emit(f"partition_sweep_{int(por * 100)}", t_part * 1e6,
             f"speedup={t_base / t_part:.2f}x parts={info['num_partitions']} "
             f"cap={C} loss_rel="
             f"{abs(l_p - float(l_base)) / abs(float(l_base)):.1e}")


# ---------------------------------------------------------------------------
# Fig. 7 — realistic agentic trees: speedup + loss deviation
# ---------------------------------------------------------------------------

def bench_realistic(impl: str = "ref") -> None:
    cfg = bench_model()
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(3)
    trees = []
    while len(trees) < 3:
        t = agentic_tree(rng, num_turns=5, turn_len_range=(16, 64),
                         vocab_size=1024)
        if t.num_leaves() > 1 and serialize_tree(t).n <= 1024:
            trees.append(t)
    por = dataset_por(trees)
    bt, _ = tree_inputs(cfg, trees, 1024)
    bl, _ = baseline_inputs(cfg, trees, 1024)
    t_tree, l_tree = timed_loss_grad(cfg, params, bt, impl=impl)
    t_base, l_base = timed_loss_grad(cfg, params, bl, impl=impl)
    emit("realistic_agentic", t_tree * 1e6,
         f"speedup={t_base / t_tree:.2f}x bound={1 / (1 - por):.2f}x "
         f"por={por:.3f} "
         f"loss_rel={abs(float(l_tree - l_base)) / abs(float(l_base)):.1e}")


# ---------------------------------------------------------------------------
# §4.6 — memory overhead of tree metadata
# ---------------------------------------------------------------------------

def bench_memory_overhead() -> None:
    cfg = bench_model()
    rng = np.random.default_rng(4)
    trees = []
    while len(trees) < 2:
        t = agentic_tree(rng, num_turns=4, turn_len_range=(16, 48),
                         vocab_size=1024)
        if serialize_tree(t).n <= 1024:
            trees.append(t)
    bt, tb = tree_inputs(cfg, trees, 1024)
    extra = sum(np.asarray(v).nbytes for k, v in bt.items()
                if k in ("pos_ids", "kv_last", "weight", "prev_idx",
                         "valid"))
    B, S = tb.tokens.shape
    act = B * S * cfg.d_model * 4 * cfg.n_layers  # one residual per layer
    emit("memory_overhead", 0.0,
         f"metadata_bytes={extra} activation_bytes~={act} "
         f"ratio={extra / act:.2e}")


# ---------------------------------------------------------------------------
# App. A.1 — kernel block-skip accounting
# ---------------------------------------------------------------------------

def _pack_greedy(seq_len: int, seed: int, n_trees: int, seg, max_depth=4):
    """Greedily fill one seq_len row with random trees; returns the packed
    TreeBatch and the kept trees (for building the linearized baseline of
    the *same* data)."""
    from repro.core.packing import pack_trees
    trees = trees_for_batch(seed, n_trees=n_trees, kind="random",
                            seg_len_range=seg, max_depth=max_depth)
    used, keep = 0, []
    for t in trees:
        s = serialize_tree(t)
        if used + s.n <= seq_len:
            keep.append((t, s))
            used += s.n
    tb = pack_trees([s for _, s in keep], seq_len, batch_size=1)
    return tb, [t for t, _ in keep]


def bench_kernel_blocks() -> None:
    from repro.kernels.tree_attention import block_live_mask
    tb, _ = _pack_greedy(512, seed=9, n_trees=6, seg=(8, 32))
    kv_last = np.asarray(tb.kv_last)[0]
    S, bq = 512, 64
    nq = S // bq
    live_mask = block_live_mask(kv_last, S, bq, bq)
    live = int(live_mask.sum())
    skipped = live_mask.size - live
    causal_live = nq * (nq + 1) // 2
    emit("kernel_blocks", 0.0,
         f"live={live} skipped={skipped} causal_would_run={causal_live} "
         f"extra_skip_vs_causal={causal_live - live}")


# ---------------------------------------------------------------------------
# App. A.1 — fused kernel wall time, fwd and fwd+bwd, tree vs linearized
# ---------------------------------------------------------------------------

def _timed(fn, *args, iters: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_kernel_fwd_bwd(smoke: bool = False) -> None:
    """Time the Pallas op itself — forward, and forward+backward via
    jax.grad — on tree-packed vs linearized-packed copies of the same
    trees.  The backward now runs the fused kernels, so this measures the
    training-step speedup the paper reports, not just inference."""
    from repro.core.packing import pack_linear_paths
    from repro.kernels.ops import tree_attention

    if smoke:
        S, H, Kh, hd, bq = 256, 4, 4, 16, 64
        n_trees, seg, iters = 4, (8, 24), 2
    else:
        S, H, Kh, hd, bq = 1024, 8, 4, 64, 128
        n_trees, seg, iters = 8, (16, 64), 3
    tb, kept = _pack_greedy(S, seed=9, n_trees=n_trees, seg=seg)
    lb = pack_linear_paths([t.linearize_paths() for t in kept], S)
    rng = np.random.default_rng(9)
    scale = hd ** -0.5

    results = {}
    for tag, kv_last in (("tree", np.asarray(tb.kv_last)),
                         ("linear", np.asarray(lb.kv_last))):
        B = kv_last.shape[0]
        q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, Kh, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, Kh, hd)), jnp.float32)
        kl = jnp.asarray(kv_last)
        fwd = jax.jit(lambda q_, k_, v_:
                      tree_attention(q_, k_, v_, kl, scale, bq, bq))
        loss = lambda q_, k_, v_: (tree_attention(
            q_, k_, v_, kl, scale, bq, bq) ** 2).sum()
        fwd_bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        t_f = _timed(fwd, q, k, v, iters=iters)
        t_fb = _timed(fwd_bwd, q, k, v, iters=iters)
        results[tag] = (t_f, t_fb, B)
        emit(f"kernel_fwd_{tag}", t_f * 1e6, f"rows={B} S={S} block={bq}")
        emit(f"kernel_fwd_bwd_{tag}", t_fb * 1e6,
             f"rows={B} S={S} block={bq}")
    (tf_t, tfb_t, _), (tf_l, tfb_l, _) = results["tree"], results["linear"]
    emit("kernel_tree_vs_linear", 0.0,
         f"fwd_speedup={tf_l / tf_t:.2f}x "
         f"fwd_bwd_speedup={tfb_l / tfb_t:.2f}x")


# ---------------------------------------------------------------------------
# §3.4 — batched wave-scheduled partitioned training (oversized trees)
# ---------------------------------------------------------------------------

def bench_packed_partition(smoke: bool = False) -> None:
    """Step timing + token accounting of the batched partition pipeline:
    trees too big for one row train via wave-scheduled Tree Packing over
    partitions vs the whole-tree pass on one oversized row."""
    from repro.core.gateway import packed_partitioned_value_and_grad

    if smoke:
        cfg = bench_model(n_layers=2, d_model=64)
        S, C, turns, seg = 128, 64, 5, (12, 40)
    else:
        cfg = bench_model(n_layers=2)
        S, C, turns, seg = 512, 256, 7, (40, 160)
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(6)
    trees = []
    while len(trees) < 2:
        t = agentic_tree(rng, num_turns=turns, turn_len_range=seg,
                         vocab_size=1024)
        if serialize_tree(t).n > S:          # genuinely oversized
            trees.append(t)
    uniq = sum(t.num_unique_tokens() for t in trees)

    packed_partitioned_value_and_grad(cfg, params, trees, C,
                                      seq_len=S)      # warm executables
    t0 = time.perf_counter()
    l_p, _, info = packed_partitioned_value_and_grad(cfg, params, trees,
                                                     C, seq_len=S)
    t_part = time.perf_counter() - t0

    # whole-tree reference: each tree on one (oversized) row
    S_ref = ((max(serialize_tree(t).n for t in trees) + 127) // 128) * 128
    bt, _ = tree_inputs(cfg, trees, S_ref)
    t_ref, l_ref = timed_loss_grad(cfg, params, bt, iters=2)
    l_ref = float(l_ref) * len(trees)       # mean-over-trees → sum
    emit("packed_partition", t_part * 1e6,
         f"whole_tree_ratio={t_part / t_ref:.2f}x "
         f"parts={info['num_partitions']} waves={info['num_waves']} "
         f"rows={info['rows']} cap={C} unique={uniq} "
         f"padded={info['tokens']} "
         f"loss_rel={abs(l_p - l_ref) / abs(l_ref):.1e}")
    assert info["unique_tokens"] == uniq


# ---------------------------------------------------------------------------
# §3.3 / App. A.1 — fused pallas kernels on the partition-gateway path
# ---------------------------------------------------------------------------

def bench_gateway_impl(smoke: bool = False) -> None:
    """The same wave-scheduled partitioned step (ancestor gateway KV
    through attention) run with impl='pallas' (fused kernels, incl. fused
    backward with ancestor cotangents) vs impl='chunked' (XLA scan) —
    the downgrade PR 2 shipped with is gone; this row tracks what the
    fused path buys on the gateway-extended KV layout."""
    from repro.core.gateway import packed_partitioned_value_and_grad

    if smoke:
        cfg = bench_model(n_layers=2, d_model=64)
        S, C, turns, seg, n_trees = 128, 64, 5, (12, 40), 1
    else:
        cfg = bench_model(n_layers=2)
        S, C, turns, seg, n_trees = 512, 256, 7, (40, 160), 2
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(8)
    trees = []
    while len(trees) < n_trees:
        t = agentic_tree(rng, num_turns=turns, turn_len_range=seg,
                         vocab_size=1024)
        if serialize_tree(t).n > S:
            trees.append(t)

    res = {}
    for impl in ("chunked", "pallas"):
        packed_partitioned_value_and_grad(cfg, params, trees, C,
                                          seq_len=S, impl=impl)  # warm
        t0 = time.perf_counter()
        l, _, info = packed_partitioned_value_and_grad(
            cfg, params, trees, C, seq_len=S, impl=impl)
        res[impl] = (time.perf_counter() - t0, l, info)
    (t_c, l_c, _), (t_p, l_p, info) = res["chunked"], res["pallas"]
    emit("gateway_impl", t_p * 1e6,
         f"chunked_us={t_c * 1e6:.1f} pallas_vs_chunked={t_c / t_p:.2f}x "
         f"parts={info['num_partitions']} waves={info['num_waves']} "
         f"cap={C} loss_rel={abs(l_p - l_c) / max(abs(l_c), 1e-9):.1e}")


# ---------------------------------------------------------------------------
# unified plan→execute engine vs the PR-3 two-branch step
# ---------------------------------------------------------------------------

def bench_engine_step(smoke: bool = False, impl: str = "ref") -> None:
    """One optimizer step over a mixed stream (packed rows + oversized
    trees) through the unified TreeTrainEngine vs the pre-refactor
    two-branch loop (jitted packed grad + wave driver + host-side
    combine).  Also asserts the engine's host-sync discipline: ≤ 1
    device→host sync per optimizer step."""
    from repro.core.gateway import packed_partitioned_value_and_grad
    from repro.data.loader import LoaderConfig
    from repro.train.engine import TreeTrainEngine
    from repro.train.planner import plans as plan_steps
    from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                       init_opt_state)
    from repro.train.train_step import make_grad_fn

    if smoke:
        cfg = bench_model(n_layers=2, d_model=64)
        S, C, steps = 128, 64, 3
        gen = dict(turn_len_range=(8, 24), num_turns=3)
    else:
        cfg = bench_model(n_layers=2)
        S, C, steps = 512, 256, 5
        gen = dict(turn_len_range=(24, 96), num_turns=5)
    lc = LoaderConfig(seq_len=S, batch_rows=2, trees_per_batch=4,
                      mode="tree", kind="agentic", seed=11,
                      auto_partition=True, capacity=C, gen_kwargs=gen)
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=steps)
    params = init_params(cfg, jax.random.key(0))

    # one planner stream materializes both views of the same schedule
    pss = list(plan_steps(cfg, lc, steps))
    eplans = [ps.execution_plan() for ps in pss
              if not ps.execution_plan().is_empty]
    sbs = [ps.step_batch() for ps in pss
           if ps.step_batch().inputs is not None
           or ps.step_batch().oversized]
    n_oversized = sum(p.num_oversized for p in eplans)

    # ---- unified engine ---------------------------------------------------
    # warm pass over EVERY plan first: each step can carry differently
    # bucketed wave shapes, and compilation must stay out of the timing
    engine = TreeTrainEngine(cfg, opt_cfg, impl=impl, donate=False)
    opt = init_opt_state(params)
    p_e = params
    for plan in eplans:
        p_e, opt, _ = engine.step(p_e, opt, plan)
    syncs0, steps0 = engine.host_syncs, engine.steps_done
    opt = init_opt_state(params)
    p_e = params
    t0 = time.perf_counter()
    loss_e = 0.0
    for plan in eplans:
        p_e, opt, m = engine.step(p_e, opt, plan)
        loss_e = m["loss"]
    t_engine = (time.perf_counter() - t0) / len(eplans)
    syncs_per_step = (engine.host_syncs - syncs0) / (engine.steps_done
                                                     - steps0)
    assert syncs_per_step <= 1.0, syncs_per_step

    # ---- pre-refactor two-branch loop ------------------------------------
    gfn = make_grad_fn(cfg, impl=impl)
    update_fn = jax.jit(lambda p, g, s: adamw_update(opt_cfg, p, g, s))
    cap = lc.capacity or lc.seq_len

    def two_branch(p, opt, sb):
        n = max(sb.num_trees, 1)
        loss, grads = 0.0, None
        if sb.inputs is not None:
            inputs = dict(sb.inputs)      # the engine shares this dict
            inputs["num_trees"] = n
            li, grads, _ = gfn(p, inputs)
            loss += float(li)
        if sb.oversized:
            l_p, g_p, _ = packed_partitioned_value_and_grad(
                cfg, p, sb.oversized, cap, seq_len=lc.seq_len, impl=impl,
                max_rows=lc.batch_rows)
            loss += l_p / n
            g_p = jax.tree.map(lambda a: a / n, g_p)
            grads = g_p if grads is None else jax.tree.map(
                lambda a, b: a.astype(jnp.float32) + b, grads, g_p)
        p, opt, om = update_fn(p, grads, opt)
        return p, opt, loss

    opt = init_opt_state(params)
    p_r = params
    for sb in sbs:                                      # warm executables
        p_r, opt, _ = two_branch(p_r, opt, sb)
    opt = init_opt_state(params)
    p_r = params
    t0 = time.perf_counter()
    loss_r = 0.0
    for sb in sbs:
        p_r, opt, loss_r = two_branch(p_r, opt, sb)
    t_two = (time.perf_counter() - t0) / len(sbs)

    emit("engine_step", t_engine * 1e6,
         f"two_branch_us={t_two * 1e6:.1f} "
         f"speedup={t_two / t_engine:.2f}x steps={len(eplans)} "
         f"oversized={n_oversized} host_syncs_per_step={syncs_per_step:.1f} "
         f"loss_rel={abs(loss_e - loss_r) / max(abs(loss_r), 1e-9):.1e}")


# ---------------------------------------------------------------------------
# schedule level — plan-ahead scheduler efficiency + async overlap
# ---------------------------------------------------------------------------

def bench_plan_efficiency(smoke: bool = False, impl: str = "ref") -> None:
    """The plan-ahead scheduler (train/planner): padded-vs-unique token
    efficiency of global lookahead bin packing (cost-model-chosen
    candidates) vs greedy per-step first-fit on the same tree stream, and
    plan-build time overlapped behind ``TreeTrainEngine.step`` by the
    async double-buffered pipeline (``--plan-workers 1``)."""
    from repro.data.loader import LoaderConfig
    from repro.train.engine import TreeTrainEngine
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.planner import (PlannerConfig, plan_pipeline,
                                     plan_stream)

    if smoke:
        cfg = bench_model(n_layers=2, d_model=64)
        S, rows, trees, steps = 256, 2, 3, 8
        gen = dict(turn_len_range=(6, 30), num_turns=3)
    else:
        cfg = bench_model(n_layers=2)
        S, rows, trees, steps = 512, 4, 6, 16
        gen = dict(turn_len_range=(16, 64), num_turns=4)
    lc = LoaderConfig(seq_len=S, batch_rows=rows, trees_per_batch=trees,
                      mode="tree", kind="agentic", seed=13,
                      gen_kwargs=gen)

    def packed_stats(pc):
        pad = uniq = nsteps = 0
        for ps in plan_stream(cfg, lc, steps, pc):
            sb = ps.step_batch()
            if sb.tb is None:
                continue
            nsteps += 1
            pad += sb.tb.tokens.size - int(sb.tb.valid.sum())
            uniq += int(sb.tb.valid.sum())
        return pad, uniq, nsteps

    pad_g, uniq_g, steps_g = packed_stats(
        PlannerConfig(lookahead=1, heuristics=("ffd",)))
    pad_p, uniq_p, steps_p = packed_stats(PlannerConfig(lookahead=4))
    r_g = pad_g / max(uniq_g, 1)
    r_p = pad_p / max(uniq_p, 1)

    # ---- async overlap: drive the engine from the pipeline ---------------
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=steps)
    params = init_params(cfg, jax.random.key(0))
    engine = TreeTrainEngine(cfg, opt_cfg, impl=impl, donate=False)
    opt = init_opt_state(params)
    p = params
    for plan in plan_pipeline(cfg, lc, steps,
                              PlannerConfig(lookahead=4)):   # warm jit
        p, opt, _ = engine.step(p, opt, plan)
    pipe = plan_pipeline(cfg, lc, steps,
                         PlannerConfig(lookahead=4, plan_workers=1))
    opt = init_opt_state(params)
    p = params
    n = 0
    t0 = time.perf_counter()
    for plan in pipe:
        p, opt, _ = engine.step(p, opt, plan)
        n += 1
    wall = time.perf_counter() - t0
    emit("plan_efficiency", pipe.build_s * 1e6 / max(pipe.built, 1),
         f"pad_per_unique_greedy={r_g:.3f} pad_per_unique_planner={r_p:.3f} "
         f"steps={steps_g}->{steps_p} sched_ms={pipe.schedule_s * 1e3:.1f} "
         f"build_ms={pipe.build_s * 1e3:.1f} "
         f"exposed_ms={pipe.exposed_s * 1e3:.1f} "
         f"exposed_frac_of_wall={pipe.exposed_s / max(wall, 1e-9):.3f}")
    assert r_p <= r_g, (r_p, r_g)   # planner never pads more than greedy


def bench_cross_tree_reuse(smoke: bool = False, impl: str = "ref") -> None:
    """Cross-tree forest grafting (core/forest + train/planner --graft):
    unique computed tokens and pad-per-unique with grafting on vs off on
    a template-heavy stream (N system-prompt templates shared verbatim
    across trees), at matched loss — the schedule-level dedup the
    within-tree Tree Packing cannot reach."""
    from repro.data.loader import LoaderConfig
    from repro.train.engine import TreeTrainEngine
    from repro.train.planner import PlannerConfig, plan_stream

    if smoke:
        cfg = bench_model(n_layers=2, d_model=64)
        S, rows, trees, steps = 256, 2, 4, 4
        gen = dict(num_templates=2, template_len=128, num_turns=1,
                   turn_len_range=(4, 16))
    else:
        cfg = bench_model(n_layers=2)
        S, rows, trees, steps = 512, 4, 6, 12
        gen = dict(num_templates=3, template_len=320, num_turns=1,
                   turn_len_range=(8, 32))
    lc = LoaderConfig(seq_len=S, batch_rows=rows, trees_per_batch=trees,
                      mode="tree", kind="template", seed=17,
                      auto_partition=True, gen_kwargs=gen)
    params = init_params(cfg, jax.random.key(0))

    def run(graft: bool):
        pc = PlannerConfig(lookahead=4, graft=graft, min_graft=32)
        eng = TreeTrainEngine(cfg, impl=impl, donate=False)
        uniq = pad = ntrees = 0
        loss_sum = 0.0
        sched = 0.0
        t0 = time.perf_counter()
        for ps in plan_stream(cfg, lc, steps, pc):
            sched += time.perf_counter() - t0
            plan = ps.execution_plan()
            _, scal = eng.accumulate(params, plan)
            n = plan.num_trees
            loss_sum += n * float(np.asarray(scal)[0])
            ntrees += n
            uniq += plan.unique_tokens
            pad += plan.padded_tokens
            t0 = time.perf_counter()
        return (uniq, pad / max(uniq, 1), loss_sum / max(ntrees, 1),
                ntrees, sched)

    u_off, ppu_off, l_off, n_off, _ = run(False)
    u_on, ppu_on, l_on, n_on, sched_on = run(True)
    assert n_on == n_off, (n_on, n_off)   # no tree gained or lost
    saved = 1.0 - u_on / max(u_off, 1)
    emit("cross_tree_reuse", sched_on * 1e6 / max(steps, 1),
         f"saved_token_frac={saved:.3f} unique={u_off}->{u_on} "
         f"pad_per_unique_off={ppu_off:.3f} pad_per_unique_on={ppu_on:.3f} "
         f"loss_rel={abs(l_on - l_off) / max(abs(l_off), 1e-9):.2e}")
    assert saved >= 0.0, saved            # grafting never computes MORE


# ---------------------------------------------------------------------------
# the closed async RL loop — prefix-KV reuse + generation/training overlap
# ---------------------------------------------------------------------------

def bench_rl_service(smoke: bool = False, impl: str = "ref") -> None:
    """The async tree-RL service end to end (launch/rl_loop's machinery):
    a generator thread decodes K-branch rollout groups off ONE shared-
    prefix KV prefill per group, merges them into advantage trees, and
    streams them through the live planner into engine steps.

    Reported: per-step wall time, the prefix compute saved by KV reuse
    (per-group token accounting — asserted exact: each prefix computed
    once, never K times), and the fraction of generation hidden behind
    training.  Also asserts zero dropped trees and the bounded-staleness
    contract (lag ≤ max_ahead + lookahead − 1)."""
    from repro.data.loader import LoaderConfig
    from repro.serve.rollout import RolloutConfig, rollout_group
    from repro.serve.service import (AsyncTreeRLService, ServiceConfig,
                                     WeightStore)
    from repro.train.engine import TreeTrainEngine
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.planner import PlannerConfig, plan_window
    from repro.train.planner import plans as plan_steps

    if smoke:
        cfg = bench_model(n_layers=2, d_model=64)
        steps, groups = 4, 2
        rc = RolloutConfig(k=4, prompt_len=8, max_new=4, impl=impl)
    else:
        cfg = bench_model(n_layers=2)
        steps, groups = 6, 2
        rc = RolloutConfig(k=4, prompt_len=16, max_new=8, impl=impl)
    seq_len = rc.prompt_len + rc.k * rc.max_new   # any tree fits: 0 drops
    lc = LoaderConfig(seq_len=seq_len, batch_rows=2, trees_per_batch=groups,
                      mode="tree", seed=17, loss_mode="rl",
                      auto_partition=True)
    pcfg = PlannerConfig(lookahead=1, plan_workers=1, max_rows=2)
    sc = ServiceConfig(groups_per_step=groups, max_ahead_steps=1,
                       rollout=rc, seed=17)
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=steps)
    params = init_params(cfg, jax.random.key(0))

    # warm every executable outside the measured loop (as launch/rl_loop
    # does): rollout prefill/decode-scan, the packed train step + two
    # optimizer updates, then a rollout against post-update buffer
    # layouts — so compiles neither starve the generator thread nor
    # masquerade as exposed generation time
    wtrees = [rollout_group(cfg, params,
                            np.zeros(rc.prompt_len, np.int32) + g, rc,
                            jax.random.key(g))[0] for g in range(groups)]
    wsteps = [ps for ps in plan_window(cfg, lc, pcfg, [wtrees])
              if not ps.is_empty]
    weng = TreeTrainEngine(cfg, opt_cfg, impl=impl)
    p2 = jax.tree.map(jnp.copy, params)
    o2 = init_opt_state(p2)
    for _ in range(2):
        p2, o2, _ = weng.step(p2, o2, wsteps[0].execution_plan())
    rollout_group(cfg, jax.tree.map(jnp.copy, p2),
                  np.zeros(rc.prompt_len, np.int32), rc, jax.random.key(0))
    del p2, o2

    store = WeightStore(params, version=0)
    engine = TreeTrainEngine(cfg, opt_cfg, impl=impl, weight_store=store)
    opt = init_opt_state(params)
    svc = AsyncTreeRLService(cfg, store, sc, num_steps=steps).start()
    pipe = plan_steps(cfg, lc, svc.tree_batches(), pcfg)

    dropped = n_steps = 0
    t0 = time.perf_counter()
    for ps in pipe:
        plan = ps.execution_plan()
        dropped += plan.dropped
        if plan.is_empty:
            continue
        params, opt, _ = engine.step(params, opt, plan)
        n_steps += 1
    svc.join(10)
    wall = time.perf_counter() - t0

    st = svc.stats
    # the acceptance numbers: prefix computed once per group, zero drops,
    # staleness inside the bound
    assert st.prefill_tokens == steps * groups * rc.prompt_len
    assert st.saved_prefill_tokens == \
        steps * groups * (rc.k - 1) * rc.prompt_len
    assert dropped == 0, dropped
    bound = sc.max_ahead_steps + pcfg.lookahead - 1
    assert engine.max_lag_seen <= bound, (engine.max_lag_seen, bound)
    exposed = pipe.exposed_s
    overlap = 1.0 - exposed / max(st.gen_busy_s, 1e-9)
    saved_frac = st.saved_prefill_tokens / max(
        st.saved_prefill_tokens + st.prefill_tokens, 1)
    emit("rl_service", wall * 1e6 / max(n_steps, 1),
         f"steps={n_steps} k={rc.k} groups={groups} "
         f"prefill_tok={st.prefill_tokens} "
         f"saved_prefill_tok={st.saved_prefill_tokens} "
         f"saved_prefill_frac={saved_frac:.2f} "
         f"gen_busy_ms={st.gen_busy_s * 1e3:.1f} "
         f"gen_exposed_ms={exposed * 1e3:.1f} "
         f"overlap_frac={max(overlap, 0.0):.2f} "
         f"max_lag={engine.max_lag_seen} dropped={dropped}")


# ---------------------------------------------------------------------------
# --smoke — tiny model fwd+bwd through the packed tree loss (CI gate)
# ---------------------------------------------------------------------------

def bench_smoke_model(impl: str) -> None:
    """qwen1.5-0.5B-scale smoke config: one model-level fwd+bwd timing,
    tree vs linearized packing, through loss_and_metrics."""
    from repro.configs.qwen1p5_0p5b import smoke
    cfg = smoke()
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(5)
    trees = [t for t in trees_for_batch(7, n_trees=4, kind="agentic",
                                        num_turns=3, turn_len_range=(8, 24),
                                        vocab_size=cfg.vocab_size)
             if serialize_tree(t).n <= 256][:2]
    bt, _ = tree_inputs(cfg, trees, 256)
    bl, _ = baseline_inputs(cfg, trees, 256)
    t_tree, l_tree = timed_loss_grad(cfg, params, bt, iters=2, impl=impl)
    t_base, l_base = timed_loss_grad(cfg, params, bl, iters=2, impl=impl)
    emit("smoke_model_fwd_bwd", t_tree * 1e6,
         f"impl={impl} speedup={t_base / t_tree:.2f}x "
         f"loss_rel={abs(float(l_tree - l_base)) / abs(float(l_base)):.1e}")


# ---------------------------------------------------------------------------
# shardlint byte table — audited per-step collective wire bytes
# ---------------------------------------------------------------------------

def bench_compile_warmup(smoke: bool = False, impl: str = "ref") -> None:
    """AOT warmup engine (train/warmup): the compile economics of one
    training stream, cold vs warm.

      cold   the engine's executable cache starts empty: every first-seen
             signature pays a synchronous ``lower().compile()`` inside
             the step it lands in (counted as a retrace + exposed wait);
      warm   a fresh cache is filled by ``AOTWarmupService.warm_all`` —
             the signature universe ordered by ``CompileCacheSim`` hit
             frequency, budgeted to the stream's hot set — before the
             first step runs: the same stream must then replay with ZERO
             retraces and zero exposed compile wait;
      restart  ``python -m repro.train.warmup --persist-probe`` twice in
             fresh subprocesses against one persistent jax compilation
             cache dir: the second process must write 0 new cache files.

    Every cold/warm step also contributes a calibration sample
    (wall time + cost-model features) to the ``--out`` artifact;
    ``--calibrate`` least-squares-fits CostWeights from them."""
    import shutil
    import subprocess

    from repro.analysis.signatures import step_signatures
    from repro.core.plan_cost import CompileCacheSim
    from repro.data.loader import LoaderConfig
    from repro.train.engine import TreeTrainEngine
    from repro.train.exec_cache import ExecutableCache
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.planner import (PlannerConfig, plan_stream,
                                     planned_step_features)
    from repro.train.warmup import AOTWarmupService

    # dims distinct from every other bench in this process so the cold
    # pass pays GENUINE XLA compiles (the in-process compilation cache
    # would otherwise hit on an HLO an earlier bench already built)
    cfg = (bench_model(n_layers=2, d_model=32, vocab=512) if smoke
           else bench_model(n_layers=3, d_model=64))
    S, C, steps = (128, 64, 2) if smoke else (384, 192, 5)
    lc = LoaderConfig(seq_len=S, batch_rows=2, trees_per_batch=2,
                      mode="tree", kind="template", seed=23,
                      auto_partition=True, capacity=C,
                      gen_kwargs=dict(num_templates=1,
                                      template_len=S // 4, num_turns=2,
                                      turn_len_range=(S // 8, S // 4)))
    pc = PlannerConfig(lookahead=2)
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=steps)
    params = init_params(cfg, jax.random.key(1))
    pss = list(plan_stream(cfg, lc, steps, pc))

    def run_stream(engine) -> tuple[list, float]:
        p, opt = params, init_opt_state(params)
        walls = []
        for ps in pss:
            plan = ps.execution_plan()
            sig0 = set(engine.exec_cache.signatures())
            t0 = time.perf_counter()
            p, opt, _ = engine.step(p, opt, plan)
            dt = time.perf_counter() - t0
            walls.append(dt)
            new = engine.exec_cache.signatures() - sig0
            feats = planned_step_features(ps)
            CALIB.append(dict(
                wall_s=dt, padded_tokens=feats["padded_tokens"],
                live_blocks=feats["live_blocks"], block=pc.block,
                new_packed_sigs=len([s for s in new
                                     if s[0] == "packed"]),
                new_wave_sigs=len([s for s in new if s[0] == "wave"])))
        return walls, sum(walls)

    # ---- cold: first-seen signatures compile synchronously in-step
    eng_c = TreeTrainEngine(cfg, opt_cfg, impl=impl, donate=False,
                            exec_cache=ExecutableCache())
    cold_walls, cold_wall = run_stream(eng_c)
    assert eng_c.retraces > 0, "cold baseline saw no compiles"
    assert eng_c.compile_wait_s > 0

    # ---- warm: universe warmup (hit-frequency-ordered, budgeted to the
    # stream's hot set) into a FRESH executable cache, then replay
    sim = CompileCacheSim()
    for ps in pss:
        sim.commit(step_signatures(ps))
    waves = [s for s in sim.seen if s[0] == "wave"]
    caps = [max((s[i] for s in waves), default=0) for i in (3, 4, 5, 6)]
    svc = AOTWarmupService(cfg, lc, pc, params=params, opt_cfg=opt_cfg,
                           impl=impl, donate=False, sim=sim, caps=caps,
                           max_compiles=2 * (len(sim.seen) + 1))
    t0 = time.perf_counter()
    svc.warm_all()
    warmup_s = time.perf_counter() - t0
    assert not svc.errors, svc.errors[:3]
    eng_w = TreeTrainEngine(cfg, opt_cfg, impl=impl, donate=False,
                            exec_cache=svc.cache, universe=svc.universe)
    warm_walls, warm_wall = run_stream(eng_w)
    wait_frac = eng_w.compile_wait_s / max(warm_wall, 1e-9)
    assert eng_w.retraces == 0, \
        f"{eng_w.retraces} retraces after universe warmup"
    assert wait_frac < 0.05, \
        (f"exposed compile wait {wait_frac:.1%} of wall "
         f"(cold baseline: "
         f"{eng_c.compile_wait_s / max(cold_wall, 1e-9):.1%})")

    # ---- restart: persistent compile cache across fresh processes.  The
    # probes count cache files, not times, so they run on the CPU: this
    # process may already hold the chip.  Their cache sits at a fixed
    # path in the checkout (a cache hit needs the same path), emptied
    # first so probe 1 starts cold.
    cache_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache_probe")
    shutil.rmtree(cache_dir, ignore_errors=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    env.pop("XLA_FLAGS", None)
    probes = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-m", "repro.train.warmup",
                            "--persist-probe", cache_dir], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        probes.append(json.loads(r.stdout.strip().splitlines()[-1]))
    shutil.rmtree(cache_dir, ignore_errors=True)
    assert probes[0]["new_cache_files"] > 0, probes[0]
    assert probes[1]["new_cache_files"] == 0, \
        f"warm restart recompiled {probes[1]['new_cache_files']} modules"
    assert probes[1]["loss"] == probes[0]["loss"], probes

    emit("compile_warmup", cold_walls[0] * 1e6,
         f"warm_step1_us={warm_walls[0] * 1e6:.1f} "
         f"cold_retraces={eng_c.retraces} warm_retraces=0 "
         f"cold_wait_ms={eng_c.compile_wait_s * 1e3:.0f} "
         f"warm_wait_frac={wait_frac:.3f} warmup_s={warmup_s:.1f} "
         f"aot_executables={len(svc.cache)} "
         f"restart_new_modules={probes[1]['new_cache_files']} "
         f"restart_warmup_speedup="
         f"{probes[0]['compile_s'] / max(probes[1]['compile_s'], 1e-9):.1f}x")


def bench_comms_table() -> None:
    """shardlint's fast host-mesh audit (``lint --comms --fast``) in a
    subprocess — fake devices need ``XLA_FLAGS`` set before jax
    initializes, which this already-imported process cannot redo.  Emits
    the audited engine-step wire bytes from the ``comms.json`` table (the
    number ``plan_cost.wire_bytes_per_step`` feeds the cost model)."""
    import subprocess
    import tempfile

    from repro.core.plan_cost import wire_bytes_per_step
    fd, out = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # the audit counts bytes, not times, and this process may already
    # hold the chip: run it on the CPU
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    env.pop("XLA_FLAGS", None)     # the audit sets its own fake devices
    t0 = time.perf_counter()
    r = subprocess.run([sys.executable, "-m", "repro.analysis.lint",
                        "--comms", "--fast", "-q", "--out", out],
                       env=env, capture_output=True, text=True,
                       timeout=600)
    us = (time.perf_counter() - t0) * 1e6
    if r.returncode != 0:
        emit("comms_table", us, "shardlint=FAILED")
        return
    with open(out) as fh:
        rep = json.load(fh)
    os.unlink(out)
    mesh, entry = next(iter(rep["meshes"].items()))
    wb = wire_bytes_per_step(entry["engine.packed"])
    dec = wire_bytes_per_step(entry["session.step"])
    emit("comms_table", us,
         f"mesh={mesh} engine_step_wire_bytes={wb} "
         f"decode_step_wire_bytes={dec} findings=0")


def calibrate(path: str) -> None:
    """Least-squares-fit :class:`~repro.core.plan_cost.CostWeights` from a
    nightly artifact's ``calib_samples`` (written by ``compile_warmup``).

    Model per timed engine step::

        wall_s ≈ a·padded_tokens + b·new_packed_sigs + c·new_wave_sigs
                 + d·live_blocks·block² + e

    then normalize by the pad coefficient (``score_packing`` is scale-free
    — only the RATIOS steer the planner) and print a ``CostWeights(...)``
    literal to paste into ``core/plan_cost.py`` or pass programmatically."""
    with open(path) as fh:
        art = json.load(fh)
    samples = art.get("calib_samples") or []
    if len(samples) < 5:
        sys.exit(f"calibrate: need >= 5 calib_samples, artifact at {path} "
                 f"has {len(samples)} — run benchmarks with --out first")
    X = np.array([[s["padded_tokens"],
                   s["new_packed_sigs"],
                   s["new_wave_sigs"],
                   s["live_blocks"] * s["block"] ** 2,
                   1.0] for s in samples])
    y = np.array([s["wall_s"] for s in samples])
    coef, *_ = np.linalg.lstsq(X, y, rcond=None)
    resid = y - X @ coef
    ss_tot = float(((y - y.mean()) ** 2).sum()) or 1.0
    r2 = 1.0 - float((resid ** 2).sum()) / ss_tot
    # a compile class the samples never exercised can come out slightly
    # negative from noise — clamp: costs are non-negative by construction
    pad, miss, wave, live = (max(float(c), 0.0) for c in coef[:4])
    if pad <= 0:
        sys.exit("calibrate: pad coefficient fit <= 0 — samples do not "
                 "vary padded_tokens enough to normalize against")
    print(f"# fit from {len(samples)} samples, R^2={r2:.3f} "
          f"(backend={art.get('backend')}, impl={art.get('impl')})")
    print(f"CostWeights(pad=1.0, compile_miss={miss / pad:.1f}, "
          f"wave_compile={wave / pad:.1f}, live_block={live / pad:.4f})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs, CPU-friendly, < 2 min (CI gate)")
    ap.add_argument("--impl", default="ref",
                    choices=("ref", "chunked", "pallas"),
                    help="attention impl for model-level benches")
    ap.add_argument("--out", default=None,
                    help="write rows as a JSON artifact to this path")
    ap.add_argument("--calibrate", metavar="NIGHTLY_JSON", default=None,
                    help="fit CostWeights from a benchmark artifact's "
                         "calib_samples and print the literal; runs no "
                         "benchmarks")
    args = ap.parse_args(argv)
    if args.calibrate:
        calibrate(args.calibrate)
        return
    if args.out:
        parent = os.path.dirname(os.path.abspath(args.out))
        if not os.path.isdir(parent):
            ap.error(f"--out directory does not exist: {parent}")

    t0 = time.perf_counter()
    print("name,us_per_call,derived")
    if args.smoke:
        bench_kernel_fwd_bwd(smoke=True)
        bench_smoke_model(args.impl)
        bench_kernel_blocks()
        bench_packed_partition(smoke=True)
        bench_gateway_impl(smoke=True)
        bench_engine_step(smoke=True, impl=args.impl)
        bench_plan_efficiency(smoke=True, impl=args.impl)
        bench_cross_tree_reuse(smoke=True, impl=args.impl)
        bench_rl_service(smoke=True, impl=args.impl)
        bench_compile_warmup(smoke=True, impl=args.impl)
        bench_comms_table()
    else:
        bench_por_sweep(args.impl)
        bench_partition_tokens()
        bench_partition_sweep()
        bench_realistic(args.impl)
        bench_memory_overhead()
        bench_kernel_blocks()
        bench_kernel_fwd_bwd()
        bench_packed_partition()
        bench_gateway_impl()
        bench_engine_step(impl=args.impl)
        bench_plan_efficiency(impl=args.impl)
        bench_cross_tree_reuse(impl=args.impl)
        bench_rl_service(impl=args.impl)
        bench_compile_warmup(impl=args.impl)
        bench_comms_table()
    if args.out:
        artifact = {
            "smoke": args.smoke,
            "impl": args.impl,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "wall_s": round(time.perf_counter() - t0, 2),
            "rows": ROWS,
            "calib_samples": CALIB,
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
