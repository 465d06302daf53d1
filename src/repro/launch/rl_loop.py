"""Closed-loop async tree-RL launcher: rollout → tree → train.

  PYTHONPATH=src python -m repro.launch.rl_loop --arch qwen3-8b --smoke

One process, three overlapped stages (ROADMAP item 1 / paper §2's RL
model-update phase):

  generation   a daemon thread decodes ``--groups`` rollout groups per
               optimizer step — ``--k`` branches sharing one prompt's
               prefilled KV (``serve/rollout``, prefix computed ONCE per
               group) — merges each group into a GRPO advantage tree and
               queues it (``serve/service``);
  planning     ``train/planner.plans`` consumes the live queue exactly
               like a synthetic stream: lookahead Tree Packing, replica
               balancing, background materialization;
  training     ``TreeTrainEngine.step`` with ``loss_mode="rl"``; every
               step publishes fresh weights back to the generator's
               :class:`WeightStore`.

Staleness is *bounded*, not best-effort: generation blocks until the
trainer is within ``--max-ahead`` steps, the queue holds at most
``--max-ahead`` step-batches, and the engine audits each consumed plan's
weight versions — the run fails loudly if the observed lag ever exceeds
``max_ahead + lookahead − 1``.

``--check-grads`` freezes one rollout group at the final weights and
verifies the online plan path reproduces the offline ``loss_mode="rl"``
gradients to ≤1e-6 max-rel.  ``--ckpt-every``/``--resume`` give the
long-running service a mid-stream restart point.

``--impl`` defaults to the platform's attention (Pallas kernels on a TPU,
``ref`` elsewhere) and compiled programs persist as in
``repro.launch.train``.  ``main(argv)`` runs in-process and returns the
run's engine and per-step metrics.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as sh
from repro.configs import get_config
from repro.data.loader import LoaderConfig
from repro.kernels.ops import platform_impl
from repro.launch.mesh import data_axis_size, make_host_mesh
from repro.models.model import init_params
from repro.serve.rollout import RolloutConfig, rollout_group
from repro.serve.service import (AsyncTreeRLService, ServiceConfig,
                                 WeightStore)
from repro.train.checkpoint import (load_checkpoint, load_meta,
                                    save_checkpoint)
from repro.train.engine import TreeTrainEngine
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.planner import PlannerConfig, plan_window, plans
from repro.train.train_step import make_grad_fn


def max_rel_err(a, b) -> float:
    """max over leaves of |a−b| / (max|b| + eps)."""
    err = 0.0
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        denom = max(float(np.abs(lb).max()), 1e-12)
        err = max(err, float(np.abs(la - lb).max()) / denom)
    return err


def check_frozen_grads(cfg, lc, pcfg, params, trees, impl) -> float:
    """Online plan path vs offline ``loss_mode="rl"`` gradients for a
    frozen rollout set; returns the max-rel error."""
    steps = [ps for ps in plan_window(cfg, lc, pcfg, [list(trees)])
             if not ps.is_empty]
    assert len(steps) == 1, "frozen rollout set must plan into one step"
    plan = steps[0].execution_plan()
    assert plan.packed is not None and plan.num_oversized == 0, \
        "grad check wants a purely packed plan (raise --seq-len)"
    engine = TreeTrainEngine(cfg, impl=impl, donate=False)
    grads, _ = engine.accumulate(params, plan)
    batch = dict(plan.packed.inputs)
    batch["num_trees"] = plan.num_trees
    _, ref, _ = make_grad_fn(cfg, impl)(params, batch)
    return max_rel_err(grads, ref)


def main(argv: Optional[list[str]] = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=4,
                    help="optimizer steps (= generation step-batches)")
    ap.add_argument("--groups", type=int, default=2,
                    help="rollout groups (prompts) per optimizer step")
    ap.add_argument("--k", type=int, default=4,
                    help="branch rollouts per prompt (share the prefix KV)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8,
                    help="decode steps per branch")
    ap.add_argument("--seq-len", type=int, default=96)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--lookahead", type=int, default=1)
    ap.add_argument("--plan-workers", type=int, default=1)
    ap.add_argument("--max-ahead", type=int, default=1,
                    help="generation may run this many optimizer steps "
                         "ahead of the weights it samples (the staleness "
                         "bound)")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--impl", default=None,
                    choices=["ref", "chunked", "pallas"],
                    help="attention implementation (default: the "
                         "platform's — pallas on TPU, ref elsewhere)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-grads", action="store_true",
                    help="verify online vs offline RL gradients at exit")
    ap.add_argument("--save", default=None)
    ap.add_argument("--ckpt-every", type=int, default=None)
    ap.add_argument("--resume", default=None)
    args = ap.parse_args(argv)
    if args.ckpt_every is not None and not args.save:
        ap.error("--ckpt-every needs --save (the checkpoint directory)")

    cfg = get_config(args.arch, smoke=args.smoke)
    args.impl = args.impl or platform_impl()
    tree_cap = args.prompt_len + args.k * args.max_new
    if tree_cap > args.seq_len:
        ap.error(f"a rollout tree can reach {tree_cap} unique tokens "
                 f"(prompt {args.prompt_len} + {args.k}×{args.max_new}) "
                 f"> --seq-len {args.seq_len}: raise --seq-len to "
                 f"guarantee zero drops")
    lag_bound = args.max_ahead + args.lookahead - 1
    print(f"[rl] arch={cfg.name} impl={args.impl} k={args.k} "
          f"groups={args.groups} steps={args.steps} "
          f"max_ahead={args.max_ahead} (lag bound {lag_bound})")

    mesh, daxes = make_host_mesh(), ("data",)
    ndata = data_axis_size(mesh, daxes)
    rows = args.rows if args.rows is not None else max(2, ndata)
    opt_cfg = OptimizerConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(2, args.steps // 10))
    lc = LoaderConfig(seq_len=args.seq_len, batch_rows=rows,
                      trees_per_batch=args.groups, mode="tree",
                      seed=args.seed, loss_mode="rl", auto_partition=True)
    pcfg = PlannerConfig(lookahead=args.lookahead,
                         plan_workers=args.plan_workers,
                         num_replicas=ndata, max_rows=rows)
    rc = RolloutConfig(k=args.k, prompt_len=args.prompt_len,
                       max_new=args.max_new, temperature=args.temperature,
                       impl=args.impl)
    sc = ServiceConfig(groups_per_step=args.groups,
                       max_ahead_steps=args.max_ahead, rollout=rc,
                       seed=args.seed)

    from repro.train.warmup import configure_compile_cache
    print(f"[rl] persistent compile cache: {configure_compile_cache()}")

    with sh.use_mesh(mesh, data_axes=daxes):
        params = init_params(cfg, jax.random.key(args.seed))
        done = 0
        if args.resume:
            params = load_checkpoint(args.resume, params)
            done = int(load_meta(args.resume).get("steps", 0))
            print(f"[rl] resumed {args.resume} @ step {done}")

        # warm every executable OUTSIDE the measured loop, through the
        # AOT warmup service (train/warmup): a probe rollout window is
        # planned and EVERY signature it produces — packed batch, all
        # partition waves, optimizer update — is AOT-compiled into the
        # executable cache the engine dispatches from (the hand-rolled
        # predecessor warmed only the window's first step).  The rollout
        # prefill/decode-scan warms as a side effect of generating the
        # probe trees; mid-loop, the planner pipeline pre-warms each new
        # step's exact executables on its build threads before the
        # engine can consume it, so the loop never blocks on a compile.
        from repro.core.plan_cost import CompileCacheSim
        from repro.train.warmup import AOTWarmupService
        # The warm passes run on a throwaway copy of the training state,
        # made before the trainer's own optimizer state exists: at
        # published widths one chip cannot hold two fp32 AdamW states.
        warm = AOTWarmupService(cfg, lc, pcfg, params=params,
                                opt_cfg=opt_cfg, impl=args.impl,
                                sim=CompileCacheSim())
        wtrees = [rollout_group(cfg, params,
                                np.zeros(args.prompt_len, np.int32) + g,
                                rc, jax.random.key(g))[0]
                  for g in range(args.groups)]
        wsteps = [ps for ps in plan_window(cfg, lc, pcfg, [wtrees])
                  if not ps.is_empty]
        for ps in wsteps:
            warm.prewarm(step=ps)
        warm.raise_errors("rl")
        if wsteps:
            # run the warm window through the SHARED cache twice: the
            # update's donated inputs switch to its own committed output
            # layout after step one, and the second pass proves the AOT
            # executables absorb that without retracing
            weng = TreeTrainEngine(cfg, opt_cfg, impl=args.impl,
                                   exec_cache=warm.cache,
                                   universe=warm.universe)
            p2 = jax.tree.map(jnp.copy, params)
            o2 = init_opt_state(p2)
            for _ in range(2):
                p2, o2 = weng.warmup(p2, o2, wsteps[0].execution_plan())
            assert weng.host_syncs == 0, "warmup must not sync"
            assert weng.retraces == 0, \
                "prewarmed executables must cover the warm window"
            # updated params can carry different buffer layouts than the
            # init ones — warm the rollout executables for that variant
            # too, or the generator recompiles mid-loop
            rollout_group(cfg, jax.tree.map(jnp.copy, p2),
                          np.zeros(args.prompt_len, np.int32), rc,
                          jax.random.key(0))
            del p2, o2
        print(f"[rl] aot-warmup: {len(warm.cache)} executables "
              f"({warm.cache.compile_s:.1f}s compile) over "
              f"{len(warm.cache.signatures())} signatures")
        opt_state = init_opt_state(params)
        if args.resume:
            _, opt_state = load_checkpoint(args.resume, params, opt_state)

        store = WeightStore(params, version=done)
        engine = TreeTrainEngine(cfg, opt_cfg, impl=args.impl,
                                 weight_store=store,
                                 exec_cache=warm.cache,
                                 universe=warm.universe)
        engine.steps_done = done
        svc = AsyncTreeRLService(cfg, store, sc,
                                 num_steps=args.steps).start()
        pipe = plans(cfg, lc, svc.tree_batches(), pcfg, warmup=warm)

        dropped = 0
        history = []
        t0 = time.time()
        for ps in pipe:
            plan = ps.execution_plan()
            dropped += plan.dropped
            if plan.is_empty:
                continue
            ts = time.time()
            params, opt_state, m = engine.step(params, opt_state, plan)
            m["sec"] = time.time() - ts
            history.append(m)
            print(f"step {engine.steps_done - 1:4d} "
                  f"loss {m['loss']:10.4f} nll/tok {m['nll']:7.4f} "
                  f"lag {m.get('max_lag', 0)} "
                  f"{m['sec'] * 1e3:7.1f}ms", flush=True)
            if args.ckpt_every and engine.steps_done % args.ckpt_every == 0:
                save_checkpoint(args.save, params, opt_state,
                                meta={"arch": cfg.name,
                                      "steps": engine.steps_done})
        svc.join(10)
        wall = time.time() - t0

        st = svc.stats
        losses = [m["loss"] for m in history]
        # trainer-visible stall: every ms the train loop spent waiting on
        # a plan (which transitively waits on generation) — the honest
        # "exposed generation" number; queue-side wait (the planner's
        # prefetch thread blocking ahead of need) is reported separately
        exposed = pipe.exposed_s
        overlap = 1.0 - exposed / max(st.gen_busy_s, 1e-9)
        print(f"[rl] {len(history)} optimizer steps, "
              f"{st.trees_generated} trees, {dropped} dropped, "
              f"{wall:.1f}s wall")
        print(f"[rl] staleness: max lag {engine.max_lag_seen} "
              f"(bound {lag_bound}), min version {st.min_version}")
        print(f"[rl] generation: {st.gen_busy_s * 1e3:.0f}ms busy, "
              f"{exposed * 1e3:.0f}ms exposed to training "
              f"(overlap {overlap:.0%}; queue wait "
              f"{st.exposed_wait_s * 1e3:.0f}ms); "
              f"prefill {st.prefill_tokens} tok "
              f"(+{st.saved_prefill_tokens} reused via shared KV), "
              f"decode {st.decode_tokens} tok")
        print(f"[rl] plan-ahead: {pipe.built} plans, "
              f"{pipe.build_s * 1e3:.0f}ms built")
        print(f"[rl] aot: {engine.retraces} mid-loop retraces, "
              f"{engine.compile_wait_s * 1e3:.0f}ms exposed compile "
              f"wait, {warm.prewarmed} executables prewarmed in-stream"
              + (f", {len(warm.errors)} warmup errors"
                 if warm.errors else ""))
        warm.raise_errors("rl")
        assert dropped == 0, f"{dropped} trees dropped"
        if args.smoke:
            # the loop's whole signature stream was prewarmed on the
            # pipeline's build threads — a retrace means the AOT cache
            # missed a shape the planner emitted
            assert engine.retraces == 0, \
                f"{engine.retraces} mid-loop retraces (AOT cache missed)"
        assert engine.max_lag_seen <= lag_bound, \
            (engine.max_lag_seen, lag_bound)
        assert all(np.isfinite(losses)), losses
        assert len(history) >= min(args.steps, 1)
        if args.steps >= 4:
            # short runs are dominated by the unavoidable pipeline-fill
            # wait on the very first plan; only judge overlap once it
            # can amortize
            assert exposed < 0.5 * st.gen_busy_s, \
                (f"generation not overlapped: {exposed * 1e3:.0f}ms "
                 f"exposed vs {st.gen_busy_s * 1e3:.0f}ms busy")

        if args.check_grads:
            # freeze one rollout group at the final weights and replay it
            # through the offline path
            tree, _ = rollout_group(
                cfg, params, np.arange(args.prompt_len) % cfg.vocab_size,
                rc, jax.random.key(args.seed + 1))
            err = check_frozen_grads(cfg, lc, pcfg, params, [tree],
                                     args.impl)
            print(f"[rl] frozen-rollout grad check: max-rel {err:.2e}")
            assert err <= 1e-6, err

        if args.save:
            save_checkpoint(args.save, params, opt_state,
                            meta={"arch": cfg.name,
                                  "steps": engine.steps_done})
            print(f"[rl] saved → {args.save}")
    return {"args": args, "engine": engine, "history": history}


if __name__ == "__main__":
    main()
