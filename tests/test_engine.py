"""Unified plan→execute engine: loss/grads match the pre-refactor
two-branch loop (jitted packed step + host-driven wave driver) on mixed
batches, RL with unit advantages is bit-exactly SFT through the whole
engine, and one optimizer step performs exactly one host sync."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.core.gateway import packed_partitioned_value_and_grad
from repro.data.loader import LoaderConfig
from repro.models.model import init_params
from repro.train.engine import TreeTrainEngine
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.planner import plans
from repro.train.train_step import jitted_update, make_grad_fn


def _lc(**kw):
    base = dict(seq_len=96, batch_rows=2, trees_per_batch=5, mode="tree",
                kind="agentic", seed=5, auto_partition=True,
                gen_kwargs=dict(turn_len_range=(4, 12), num_turns=2))
    base.update(kw)
    return LoaderConfig(**base)


def _find_mixed(cfg, lc, steps=8, min_oversized=2):
    """First step whose batch holds BOTH packed rows and ≥2 oversized
    trees, as (StepBatch, ExecutionPlan) — one PlannedStep materializes
    both views from the same schedule."""
    for ps in plans(cfg, lc, steps):
        sb = ps.step_batch()
        if sb.inputs is not None and len(sb.oversized) >= min_oversized:
            plan = ps.execution_plan()
            assert plan.packed is not None
            assert plan.num_oversized >= min_oversized
            return sb, plan
    raise AssertionError("no mixed step in this stream; adjust seeds")


def _two_branch_reference(cfg, params, sb, lc, impl):
    """The PRE-refactor training math, verbatim: one jitted grad over the
    packed batch + the wave driver for oversized trees, combined host-side
    (grads /= num_trees for the partitioned share)."""
    n = max(sb.num_trees, 1)
    cap = lc.capacity or lc.seq_len
    loss, grads = 0.0, None
    if sb.inputs is not None:
        inputs = dict(sb.inputs)
        inputs["num_trees"] = n
        li, grads, _ = make_grad_fn(cfg, impl=impl)(params, inputs)
        loss += float(li)
    if sb.oversized:
        l_p, g_p, _ = packed_partitioned_value_and_grad(
            cfg, params, sb.oversized, cap, seq_len=lc.seq_len, impl=impl,
            loss_mode=lc.loss_mode, max_rows=lc.batch_rows)
        loss += l_p / n
        g_p = jax.tree.map(lambda a: a / n, g_p)
        grads = g_p if grads is None else jax.tree.map(
            lambda a, b: a.astype(jnp.float32) + b, grads, g_p)
    return loss, grads


def _max_rel(g, g_ref):
    rels = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() /
                           (jnp.abs(b).max() + 1e-9)), g, g_ref)
    return max(jax.tree.leaves(rels))


# ---------------------------------------------------------------------------
# engine ≡ two-branch loop (the refactor's acceptance bar)
# ---------------------------------------------------------------------------

def _check_engine_equivalence(family, impl):
    cfg = tiny_cfg(family)
    lc = _lc()
    sb, plan = _find_mixed(cfg, lc)
    params = init_params(cfg, jax.random.key(0))
    l_ref, g_ref = _two_branch_reference(cfg, params, sb, lc, impl)

    engine = TreeTrainEngine(cfg, impl=impl, donate=False)
    grads, scal = engine.accumulate(params, plan)
    l_eng = float(np.asarray(scal)[0])

    assert abs(l_eng - l_ref) / max(abs(l_ref), 1e-9) <= 1e-6
    assert _max_rel(grads, g_ref) <= 1e-6
    assert engine.host_syncs == 0   # accumulation never touches the host


def test_engine_matches_two_branch_dense_ref():
    _check_engine_equivalence("dense", "ref")


@pytest.mark.slow
@pytest.mark.parametrize("family,impl", [
    ("dense", "chunked"), ("dense", "pallas"),
    ("moe", "chunked"), ("moe", "pallas")])
def test_engine_matches_two_branch(family, impl):
    _check_engine_equivalence(family, impl)


# ---------------------------------------------------------------------------
# RL ≡ SFT at unit advantages, through the WHOLE engine (packed + waves)
# ---------------------------------------------------------------------------

def test_engine_rl_unit_advantages_bitexact_sft():
    cfg = tiny_cfg("dense")
    params = init_params(cfg, jax.random.key(1))
    grads = {}
    for mode in ("sep_avg", "rl"):
        lc = _lc(loss_mode=mode)
        _, plan = _find_mixed(cfg, lc)
        engine = TreeTrainEngine(cfg, donate=False)
        g, scal = engine.accumulate(params, plan)
        grads[mode] = (np.asarray(scal), g)
    np.testing.assert_array_equal(grads["sep_avg"][0], grads["rl"][0])
    for a, b in zip(jax.tree.leaves(grads["sep_avg"][1]),
                    jax.tree.leaves(grads["rl"][1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# host-sync discipline + step mechanics
# ---------------------------------------------------------------------------

def test_engine_one_host_sync_per_step():
    cfg = tiny_cfg("dense")
    lc = _lc()
    params = init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    engine = TreeTrainEngine(cfg, OptimizerConfig(lr=1e-3, warmup_steps=2,
                                                  total_steps=4),
                             donate=False)
    steps = 0
    for ps in plans(cfg, lc, 4):
        plan = ps.execution_plan()
        if plan.is_empty:
            continue
        params, opt, m = engine.step(params, opt, plan)
        steps += 1
        assert engine.host_syncs == steps     # exactly one per step
        assert np.isfinite(m["loss"]) and np.isfinite(m["nll"])
        assert m["weight_sum"] > 0
    assert steps >= 2
    assert int(np.asarray(opt["step"])) == steps


def test_engine_first_step_runs_the_later_steps_programs():
    """Fresh weights and optimizer state are committed to the active mesh
    as the update's outputs are: under a mesh, the packed program and the
    update compile once over several steps, not again for step 2."""
    import dataclasses

    from jax.sharding import Mesh

    from repro import sharding as sh
    from repro.train.engine import _packed_exec_fn
    cfg = dataclasses.replace(tiny_cfg("dense"), name="tiny-first-step")
    lc = _lc(auto_partition=False)
    # an optimizer configuration of its own: the update's jit is shared
    # per configuration
    opt_cfg = OptimizerConfig(lr=1.5e-3, warmup_steps=2, total_steps=4)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    with sh.use_mesh(mesh):
        params = init_params(cfg, jax.random.key(0))
        opt = init_opt_state(params)
        engine = TreeTrainEngine(cfg, opt_cfg, donate=False)
        steps = 0
        for ps in plans(cfg, lc, 4):
            plan = ps.execution_plan()
            if plan.packed is None or plan.partition is not None:
                continue
            params, opt, _ = engine.step(params, opt, plan)
            steps += 1
    assert steps >= 2
    step_fn = _packed_exec_fn(cfg, "ref", False, with_acc=False)
    assert step_fn._cache_size() == 1
    assert jitted_update(opt_cfg, False)._cache_size() == 1


def test_engine_rl_training_descends_on_grpo_trees():
    """The RL model-update workload end to end: grpo trees (non-uniform
    group-normalized advantages), loss_mode="rl", engine steps run and
    produce finite losses and updates."""
    cfg = tiny_cfg("dense")
    lc = _lc(loss_mode="rl", kind="grpo",
             gen_kwargs=dict(turn_len_range=(4, 10), num_turns=2))
    params = init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    engine = TreeTrainEngine(cfg, OptimizerConfig(lr=1e-3, warmup_steps=2,
                                                  total_steps=4),
                             donate=False)
    p0 = jax.tree.leaves(params)[0].copy()
    ran = 0
    for ps in plans(cfg, lc, 4):
        plan = ps.execution_plan()
        if plan.is_empty:
            continue
        params, opt, m = engine.step(params, opt, plan)
        assert np.isfinite(m["loss"])
        ran += 1
    assert ran >= 2
    assert not np.array_equal(np.asarray(p0),
                              np.asarray(jax.tree.leaves(params)[0]))


def test_jitted_update_cache_is_shared():
    """Satellite: apply_grads no longer re-jits per call — the jitted
    AdamW update is cached per OptimizerConfig."""
    a = OptimizerConfig(lr=1e-3)
    b = OptimizerConfig(lr=1e-3)
    c = OptimizerConfig(lr=2e-3)
    assert jitted_update(a) is jitted_update(b)
    assert jitted_update(a) is not jitted_update(c)
    assert jitted_update(a) is not jitted_update(a, donate=True)
