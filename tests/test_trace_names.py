"""The names the profiler reads: the tree-attention kernels, the LM head and
AdamW carry ``jax.named_scope`` names into the compiled step's op metadata,
as do the MoE layer's row permutations (``moe_gather`` / ``moe_combine``
under ``moe_route``), and the engine and planner write ``repro.*`` host
spans into the profiler's own trace (CPU; the Pallas kernels run
interpreted)."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_cfg
from repro.data.loader import LoaderConfig
from repro.models import moe as moe_mod
from repro.models.model import init_params
from repro.train.engine import NUM_SCALARS, TreeTrainEngine, _packed_exec_fn
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.planner import PlannerConfig, plans
from repro.train.train_step import jitted_update


def _lc():
    return LoaderConfig(seq_len=128, batch_rows=2, trees_per_batch=2,
                        mode="tree", kind="agentic", seed=5,
                        gen_kwargs=dict(turn_len_range=(4, 12), num_turns=2))


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


@pytest.fixture(scope="module")
def step_op_names():
    """Op names of the compiled packed step (fused kernels, full remat)
    and of the compiled AdamW update."""
    cfg = tiny_cfg("dense", remat="full")
    ps = next(iter(plans(cfg, _lc(), 2, PlannerConfig(plan_workers=0))))
    batch = dict(ps.execution_plan().packed.inputs)
    batch["num_trees"] = 2
    params = init_params(cfg, jax.random.key(0))
    step = _packed_exec_fn(cfg, "pallas", False, with_acc=False)
    names = _op_names(step.lower(params, batch,
                                 jnp.zeros((NUM_SCALARS,), jnp.float32)
                                 ).compile())
    grads = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    upd = jitted_update(OptimizerConfig()).lower(
        params, grads, init_opt_state(params)).compile()
    return names | _op_names(upd)


def _in_scope(names, scope):
    pat = re.compile(rf"(^|[/(]){scope}($|[/)])")
    return [n for n in names if pat.search(n)]


@pytest.mark.parametrize("scope", ["tree_attn_fwd", "tree_attn_dq",
                                   "tree_attn_dkv", "lm_head", "adamw"])
def test_compiled_step_carries_scope(step_op_names, scope):
    assert _in_scope(step_op_names, scope), scope


def test_remat_recomputation_carries_forward_scope(step_op_names):
    fwd = _in_scope(step_op_names, "tree_attn_fwd")
    remat = [n for n in fwd if "rematted_computation" in n]
    assert remat, "the forward that remat recomputes has no name"
    assert len(remat) < len(fwd), "the first forward has no name"


@pytest.fixture(scope="module")
def moe_step_op_names():
    """Op names of the compiled packed step of a MoE model (full remat) on
    the layer's Pallas path."""
    cfg = tiny_cfg("moe", remat="full")
    ps = next(iter(plans(cfg, _lc(), 2, PlannerConfig(plan_workers=0))))
    batch = dict(ps.execution_plan().packed.inputs)
    batch["num_trees"] = 2
    params = init_params(cfg, jax.random.key(0))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe_mod, "platform_matmul", lambda: "interpret")
        step = _packed_exec_fn(cfg, "pallas", False, with_acc=False)
        return _op_names(step.lower(params, batch,
                                    jnp.zeros((NUM_SCALARS,), jnp.float32)
                                    ).compile())


@pytest.mark.parametrize("kernel", ["moe_gather", "moe_combine"])
def test_moe_step_carries_permutation_names(moe_step_op_names, kernel):
    """Forward and backward; the gather in the remat recomputation too
    (the recomputed forward needs no combine: the layer's output is not
    a residual of its own backward)."""
    named = [n for n in _in_scope(moe_step_op_names, kernel)
             if _in_scope([n], "moe_route")]
    assert [n for n in named if "transpose(" not in n], "forward"
    assert [n for n in named if "transpose(" in n], "backward"
    if kernel == "moe_gather":
        assert [n for n in named if "rematted_computation" in n], "remat"


@pytest.fixture(scope="module", params=[1, 0], ids=["worker", "inline"])
def host_spans(request, tmp_path_factory):
    """Two engine steps under the profiler with a plan pipeline of
    ``workers`` build threads: the ``repro.*`` events of each host line,
    in start order."""
    from jax.profiler import ProfileData
    cfg = tiny_cfg("dense")
    params = init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    eng = TreeTrainEngine(cfg, OptimizerConfig(), impl="ref")
    # enough batches that the worker still builds while the trace runs
    pipe = plans(cfg, _lc(), 8, PlannerConfig(plan_workers=request.param))
    it = iter(pipe)
    ps = next(it)                                 # compile outside the trace
    params, opt, _ = eng.step(params, opt, ps.execution_plan())
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        for _ in range(2):
            ps = next(it)
            params, opt, _ = eng.step(params, opt, ps.execution_plan())
    pipe.close()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = sorted((e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name.startswith("repro."))
            if evs:
                lines.append(evs)
    consumer = [ln for ln in lines
                if any(n == "repro.engine.dispatch" for _, _, n in ln)]
    assert len(consumer) == 1
    others = [ln for ln in lines if ln is not consumer[0]]
    return request.param, consumer[0], others


def test_engine_spans_dispatch_then_sync(host_spans):
    _, consumer, _ = host_spans
    eng = [(a, b, n) for a, b, n in consumer if n.startswith("repro.engine.")]
    assert [n for _, _, n in eng] == ["repro.engine.dispatch",
                                      "repro.engine.sync"] * 2
    for (_, d1, _), (s0, _, _) in zip(eng[::2], eng[1::2]):
        assert d1 <= s0


def test_plan_spans_on_their_threads(host_spans):
    workers, consumer, others = host_spans
    names = [n for _, _, n in consumer]
    assert names.count("repro.plan.wait") == 2
    built = [n for ln in others for _, _, n in ln]
    if workers:
        # plans are built on the worker thread, never on the consumer's
        assert "repro.plan.build" in built
        assert "repro.plan.build" not in names
        assert not any(n.startswith("repro.engine.") for n in built)
    else:
        # in line: the build sits inside the consumer's wait
        waits = [(a, b) for a, b, n in consumer if n == "repro.plan.wait"]
        builds = [(a, b) for a, b, n in consumer if n == "repro.plan.build"]
        assert len(builds) == 2
        for (w0, w1), (b0, b1) in zip(waits, builds):
            assert w0 <= b0 <= b1 <= w1
        assert not built
