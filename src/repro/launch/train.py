"""Training launcher — ONE loop over the unified engine.

Runs tree-training (or the sep-avg baseline) on synthetic agentic trees:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \\
      --steps 50 --mode tree

Every step is an ``ExecutionPlan`` from the plan-ahead scheduler
(``train/planner``: global cost-model-driven Tree Packing over a
``--lookahead`` window, replica-balanced rows, ``--plan-workers``
background builders double-buffered against the device) executed by
``train/engine.TreeTrainEngine.step`` — the same code path for all of
``--mode tree/baseline`` × ``--auto-partition`` × ``--impl
ref/chunked/pallas`` × ``--loss-mode sep_avg/uniform/rl``.  Gradients
accumulate in a donated fp32 device buffer; each step performs exactly
one host sync (the logging transfer).  ``--rows`` defaults to auto: the
planner picks per-replica row counts sized to the mesh's data axis.

``--auto-partition`` routes trees larger than one row through
Redundancy-Free Tree Partitioning (wave-scheduled, ``--capacity`` token
cap per partition) instead of silently dropping them — zero data loss.
``--capacity`` defaults to ``auto``: the planner sizes the cap per
lookahead window from the oversized trees it actually sees
(``core.partition.choose_capacity``); an integer forces it.

``--graft`` turns on cross-tree forest grafting (``core/forest``): trees
in the lookahead window that open with the same token prefix — shared
system prompts, few-shot preambles — are merged into one grafted forest
so the shared prefix is computed once per window instead of once per
tree (pair with ``--kind template`` for the synthetic version of that
workload).

``--loss-mode rl`` trains the RL model-update objective: per-branch GRPO
advantages scale λ_t (pair with ``--kind grpo`` rollout trees; with
advantages≡1 it reproduces SFT exactly).

``--mesh host`` (default) runs on the local device(s); ``--mesh single``/
``multi`` builds the production mesh (requires the dry-run's fake-device
env when not on a real pod — intended for lowering checks; real training
on hardware uses the same code path).

``--impl`` defaults to the platform's attention: the fused Pallas kernels
on a TPU, the dense ``ref`` oracle elsewhere.  Compiled programs persist
in ``$JAX_COMPILATION_CACHE_DIR``, or in ``.jax_cache`` at the checkout
root when that is unset (``train/warmup.configure_compile_cache``).
``main(argv)`` runs in-process and returns the run's config, mesh, step
history and first executed plan (``chip_smoke.py`` drives it that way).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional

import jax

from repro import sharding as sh
from repro.configs import get_config
from repro.data.loader import LoaderConfig
from repro.kernels.ops import platform_impl
from repro.launch.mesh import data_axes, data_axis_size, make_host_mesh, \
    make_production_mesh
from repro.models.model import init_params
from repro.train.checkpoint import (load_checkpoint, load_meta,
                                    save_checkpoint)
from repro.train.engine import TreeTrainEngine
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.planner import PlannerConfig, plans


def main(argv: Optional[list[str]] = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mode", default="tree", choices=["tree", "baseline"])
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--rows", type=int, default=None,
                    help="row budget per step (default: auto — the "
                         "planner picks the smallest multiple of the "
                         "mesh's data axis ≥ 2)")
    ap.add_argument("--trees", type=int, default=6)
    ap.add_argument("--lookahead", type=int, default=1,
                    help="generator batches the planner bin-packs "
                         "jointly (global Tree Packing; 1 = per-step)")
    ap.add_argument("--plan-workers", type=int, default=1,
                    help="background plan-builder threads (double-"
                         "buffered against engine.step; 0 = synchronous)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--impl", default=None,
                    choices=["ref", "chunked", "pallas"],
                    help="attention implementation (default: the "
                         "platform's — pallas on TPU, ref elsewhere)")
    ap.add_argument("--loss-mode", default="sep_avg",
                    choices=["sep_avg", "uniform", "rl"],
                    help="sep_avg: λ_t = g_t/K (SFT, Eq. 4); uniform: "
                         "λ_t = 1; rl: GRPO per-branch advantages scale "
                         "λ_t (the RL model-update phase)")
    ap.add_argument("--kind", default=None,
                    choices=["agentic", "grpo", "random", "template"],
                    help="synthetic tree generator (default: agentic; "
                         "grpo when --loss-mode rl; template = shared "
                         "system-prompt workload for --graft)")
    ap.add_argument("--auto-partition", action="store_true",
                    help="train oversized trees via wave-scheduled "
                         "partitioning instead of dropping them")
    ap.add_argument("--capacity", default="auto",
                    help="partition token cap: an integer forces it; "
                         "'auto' (default) lets the planner choose per "
                         "lookahead window from the oversized trees' "
                         "partition-count/depth trade-off "
                         "(core.partition.choose_capacity)")
    ap.add_argument("--graft", action="store_true",
                    help="cross-tree forest grafting: merge trees that "
                         "share a token prefix (core/forest) before "
                         "packing, so shared system prompts are computed "
                         "once per window")
    ap.add_argument("--min-graft", type=int, default=16,
                    help="minimum shared-prefix tokens for a graft to be "
                         "considered (shorter matches never pay for the "
                         "merge)")
    ap.add_argument("--aot-warmup", action="store_true",
                    help="AOT warmup engine: background-precompile the "
                         "reachable signature universe at startup and "
                         "pre-warm each window's exact executables from "
                         "the planner's build threads — the engine never "
                         "blocks on a cold jit bucket (train/warmup)")
    ap.add_argument("--warmup-threads", type=int, default=1,
                    help="background AOT compile threads for --aot-warmup")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None)
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="save params+opt_state to --save every N steps "
                         "(mid-stream resume point)")
    ap.add_argument("--resume", default=None,
                    help="checkpoint dir to resume from (replays the "
                         "deterministic plan stream up to the saved step)")
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)
    if args.ckpt_every is not None and not args.save:
        ap.error("--ckpt-every needs --save (the checkpoint directory)")

    cfg = get_config(args.arch, smoke=args.smoke)
    args.impl = args.impl or platform_impl()
    if args.kind is None:
        args.kind = "grpo" if args.loss_mode == "rl" else "agentic"
    print(f"[train] arch={cfg.name} family={cfg.family} mode={args.mode} "
          f"impl={args.impl} loss_mode={args.loss_mode} kind={args.kind}")

    auto_capacity = False
    if str(args.capacity).lower() == "auto":
        args.capacity = None
        auto_capacity = True
    else:
        try:
            args.capacity = int(args.capacity)
        except ValueError:
            ap.error(f"--capacity must be an integer or 'auto', got "
                     f"{args.capacity!r}")
    if args.auto_partition:
        if args.mode != "tree":
            ap.error("--auto-partition requires --mode tree (partitioning "
                     "is a tree-serialization feature; baseline mode "
                     "would silently drop oversized trees)")
        if args.capacity is not None:
            cap = args.capacity
            if not 0 < cap <= args.seq_len:
                ap.error(f"--capacity {cap} must be in (0, --seq-len "
                         f"{args.seq_len}]")
            if cfg.ssm is not None and cap % cfg.ssm.chunk_size != 0:
                ap.error(f"--capacity {cap} must be a multiple of the SSM "
                         f"chunk size {cfg.ssm.chunk_size}")
    if args.graft and args.mode != "tree":
        ap.error("--graft requires --mode tree (grafted forests are "
                 "serialized trees; baseline rows cannot share prefixes)")

    if args.mesh == "host":
        mesh, daxes = make_host_mesh(), ("data",)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        daxes = data_axes(args.mesh == "multi")
    ndata = data_axis_size(mesh, daxes)
    if args.rows is None:
        # planner-chosen rows: one row per replica, minimum 2
        args.rows = max(2, ndata)
        print(f"[train] rows auto-chosen: {args.rows} "
              f"({args.rows // ndata} per replica × {ndata} replicas)")
    elif args.rows % ndata:
        ap.error(f"--rows {args.rows} was forced but is not a multiple "
                 f"of the mesh's data axis ({ndata} replicas) — batch "
                 f"rows cannot shard evenly; drop --rows to let the "
                 f"planner choose, or pick a multiple of {ndata}")

    opt_cfg = OptimizerConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(2, args.steps // 10))
    # generator kwargs differ per kind (agentic/grpo take turn shapes,
    # random takes segment shapes)
    gen_kwargs = (dict(seg_len_range=(8, 48), max_depth=4)
                  if args.kind == "random"
                  else dict(turn_len_range=(8, 48), num_turns=4))
    lc = LoaderConfig(seq_len=args.seq_len, batch_rows=args.rows,
                      trees_per_batch=args.trees, mode=args.mode,
                      kind=args.kind, seed=args.seed,
                      loss_mode=args.loss_mode,
                      auto_partition=args.auto_partition,
                      capacity=args.capacity,
                      auto_capacity=auto_capacity,
                      gen_kwargs=gen_kwargs)

    # before ANY compile (param init included) so every module the run
    # produces lands in — or loads from — the persistent cache
    from repro.train.warmup import configure_compile_cache
    print(f"[train] persistent compile cache: {configure_compile_cache()}")

    with sh.use_mesh(mesh, data_axes=daxes):
        params = init_params(cfg, jax.random.key(args.seed))
        opt_state = init_opt_state(params)
        done = 0
        if args.resume:
            params, opt_state = load_checkpoint(args.resume, params,
                                                opt_state)
            done = int(load_meta(args.resume).get("steps", 0))
            print(f"[train] resumed {args.resume} @ step {done}")

        pcfg = PlannerConfig(lookahead=args.lookahead,
                             plan_workers=args.plan_workers,
                             num_replicas=ndata, max_rows=args.rows,
                             graft=args.graft, min_graft=args.min_graft)

        svc = None
        if args.aot_warmup:
            from repro.core.plan_cost import CompileCacheSim
            from repro.train.warmup import AOTWarmupService
            svc = AOTWarmupService(cfg, lc, pcfg, params=params,
                                   opt_cfg=opt_cfg, opt_state=opt_state,
                                   impl=args.impl,
                                   sim=CompileCacheSim())
            svc.start(threads=args.warmup_threads)
            print(f"[train] AOT warmup: "
                  f"{len(svc.signature_list())} universe signatures "
                  f"compiling on {args.warmup_threads} background "
                  f"thread(s)")

        engine = TreeTrainEngine(
            cfg, opt_cfg, impl=args.impl,
            exec_cache=svc.cache if svc else None,
            universe=svc.universe if svc else None)
        engine.steps_done = done

        pipe = plans(cfg, lc, args.steps, pcfg, warmup=svc)

        tokens_done = padded_total = part_trees = part_tokens = 0
        dropped_total = 0
        t0 = time.time()
        history = []
        first_plan = None
        # THE training loop: every step — packed rows, partition waves,
        # SFT or RL — is one engine.step over its ExecutionPlan; the
        # planner builds the NEXT plan on background threads meanwhile
        executed = 0
        for i, ps in enumerate(pipe):
            plan = ps.execution_plan()
            dropped_total += plan.dropped
            if plan.is_empty:       # nothing trainable this step
                continue
            executed += 1
            if executed <= done:    # resume: replay the plan stream
                continue
            first_plan = first_plan or plan
            ts = time.time()
            params, opt_state, m = engine.step(params, opt_state, plan)
            dt = time.time() - ts
            tokens_done += plan.unique_tokens
            padded_total += plan.padded_tokens
            part_trees += plan.num_oversized
            if plan.partition is not None and plan.partition.waves:
                part_tokens += plan.partition.info["unique_tokens"]
            history.append({"step": i, "loss": m["loss"], "nll": m["nll"],
                            "grad_norm": m["grad_norm"], "sec": dt,
                            "waves": (0 if plan.partition is None
                                      else len(plan.partition.waves)),
                            "oversized": plan.num_oversized,
                            "dropped": plan.dropped})
            if i % args.log_every == 0:
                print(f"step {i:4d} loss {m['loss']:10.4f} "
                      f"nll/tok {m['nll']:7.4f} "
                      f"gnorm {m['grad_norm']:8.3f} "
                      f"parts {plan.num_oversized:2d} "
                      f"{dt * 1e3:7.1f}ms", flush=True)
            if args.ckpt_every and engine.steps_done % args.ckpt_every == 0:
                save_checkpoint(args.save, params, opt_state,
                                meta={"arch": cfg.name,
                                      "steps": engine.steps_done})
                print(f"[train] ckpt @ step {engine.steps_done} "
                      f"→ {args.save}", flush=True)
        wall = time.time() - t0
        print(f"[train] {len(history)} steps, {tokens_done} unique tokens, "
              f"{dropped_total} dropped trees, {wall:.1f}s wall "
              f"({engine.host_syncs} host syncs / {engine.steps_done} "
              f"steps)")
        print(f"[train] plan-ahead: {pipe.built} plans, "
              f"{pipe.schedule_s * 1e3:.0f}ms scheduled + "
              f"{pipe.build_s * 1e3:.0f}ms built / "
              f"{pipe.exposed_s * 1e3:.0f}ms exposed "
              f"(lookahead {args.lookahead}, {args.plan_workers} workers), "
              f"{padded_total} padded tokens "
              f"({padded_total / max(tokens_done, 1):.2f}/unique)")
        if args.auto_partition:
            print(f"[train] partitioned: {part_trees} oversized trees, "
                  f"{part_tokens} tokens, {dropped_total} dropped")
        if svc is not None:
            svc.stop()
            st = svc.stats()
            print(f"[train] aot-warmup: {st['size']} executables "
                  f"({st['compile_s']:.1f}s compile, "
                  f"{svc.prewarmed} prewarmed), engine retraces "
                  f"{engine.retraces}, exposed compile wait "
                  f"{engine.compile_wait_s * 1e3:.0f}ms"
                  + (f", {st['errors']} warmup errors"
                     if st["errors"] else ""))
        if args.save:
            save_checkpoint(args.save, params, opt_state,
                            meta={"arch": cfg.name,
                                  "steps": engine.steps_done})
            with open(args.save + "/history.json", "w") as f:
                json.dump(history, f)
            print(f"[train] saved → {args.save}")
        if svc is not None:
            svc.raise_errors("train")
    return {"cfg": cfg, "args": args, "mesh": mesh, "data_axes": daxes,
            "history": history, "first_plan": first_plan}


if __name__ == "__main__":
    main()
