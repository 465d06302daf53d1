"""Tree-training benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``), whose
``program.family`` names the model code (``bench/families/<family>.py``),
and a traffic mix (``bench/traffic/<traffic>.json``); the metrics are
read by ``bench/metrics/<name>.py``.  The run drives the path users run:
``repro.train.planner.plans`` → ``ExecutionPlan`` →
``TreeTrainEngine.step``, with the arguments ``repro.launch.train``
builds.

Set-up (``setup_s``): the persistent compile cache; the weights, made on
the device from the seed; the optimizer state; the cell's stream of
trees, planned once on the host so that every step with partition waves
can be run once (which compiles or loads every program the window will
use); then the first steps of the stream, through the window's own call,
whose loss, first gradient and weight change the reference checks.

The window runs whole steps for ``--seconds``.  A step's time runs from
the end of the previous step's host sync to the end of its own.  With
``--trace 1`` a short window of its own follows under the profiler, and
the per-layer metrics are printed instead of the end-to-end ones.  Then
the program's state is freed and the reference (``bench/reference.py``)
trains the checked steps; ``bench/check.py`` compares.

The last line of standard output is one JSON object.  With a
configuration its family refuses or has no module for, without a TPU,
with another number of chips than the cell asks for, or without the
program beside ``bench/``, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_SECONDS = 4.0
TRACE_MIN_STEPS = 3


def _process_age() -> float:
    """Seconds since this process started (0 where /proc cannot say)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START -= _process_age()


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Refused(Exception):
    """The run cannot be measured here: no result is printed."""


def load_cell(name: str) -> dict:
    from bench import families
    from bench.adapter import load_config, load_json
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise Refused(f"{path} not found")
    bench = load_json("BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    lim = os.path.join(ROOT, "bench", "limits", f"{name}.json")
    limits = load_json("bench", "limits", f"{name}.json") \
        if os.path.exists(lim) else None
    c = load_config(wl["config"])
    try:
        families.load(c)
    except ValueError as e:
        raise Refused(f"configuration {wl['config']!r}: {e}") from None
    return dict(bench=bench, wl=wl, c=c,
                traffic=load_json("bench", "traffic", f"{wl['traffic']}.json"),
                limits=limits)


class CompileMeter:
    """Backend compiles and persistent-cache loads, from jax's own
    monitoring events."""

    def __init__(self):
        import jax
        self.count = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _finite(m: dict) -> bool:
    return math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"])


class Run:
    """One run of one cell: the program's state, its plan stream, and the
    numbers the check and the readers need."""

    def __init__(self, c: dict, traffic: dict, seed: int, chips: int,
                 name: str = "bench"):
        import jax
        import numpy as np

        from bench import families
        from bench.adapter import program_tree
        from bench.gen import make_pool
        from repro.data.loader import LoaderConfig
        from repro.kernels.ops import platform_impl
        from repro.launch.mesh import data_axis_size, host_descriptor
        from repro.train.optimizer import OptimizerConfig
        from repro.train.planner import PlannerConfig
        self.c, self.traffic, self.seed, self.chips = c, traffic, seed, chips
        self.fam = families.load(c)
        self.d = self.fam.dims(c)
        self.cfg = self.fam.program_config(c, name)
        # the launcher's host mesh over the cell's chips (all of the
        # machine's devices on the chip, which ``main`` checks)
        desc = host_descriptor(chips)
        self.mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:chips]).reshape(desc.shape),
            desc.axis_names, axis_types=desc.axis_types)
        ndata = data_axis_size(self.mesh, ("data",))
        rows = traffic["rows_per_chip"] * chips
        auto = traffic["capacity"] == "auto"
        self.lc = LoaderConfig(
            seq_len=traffic["seq_len"], batch_rows=rows,
            trees_per_batch=traffic["trees_per_step"] * chips, mode="tree",
            kind=traffic["kind"], seed=seed, loss_mode=traffic["loss_mode"],
            auto_partition=traffic["auto_partition"],
            capacity=None if auto else int(traffic["capacity"]),
            auto_capacity=auto)
        self.pc = PlannerConfig(lookahead=traffic["lookahead"],
                                plan_workers=traffic["plan_workers"],
                                num_replicas=ndata, max_rows=rows)
        o = traffic["optimizer"]
        self.opt = o
        self.opt_cfg = OptimizerConfig(
            lr=o["lr"], betas=(o["b1"], o["b2"]), eps=o["eps"],
            weight_decay=o["weight_decay"], warmup_steps=o["warmup_steps"],
            total_steps=o["total_steps"], min_lr_ratio=o["min_lr_ratio"],
            grad_clip=o["grad_clip"])
        self.impl = platform_impl()
        self.pool = make_pool(traffic, seed, self.d["V"], chips)
        self.ptrees = [[program_tree(t) for t in s] for s in self.pool]
        # the program's tree → the tree as generated, to read each
        # executed plan's trees back
        self.node_of = {id(p): t for s, ps in zip(self.pool, self.ptrees)
                        for t, p in zip(s, ps)}
        self.ref_steps = traffic["ref_steps"]
        self.checked: list = []
        self.ctx = contextlib.ExitStack()
        self.jax = jax
        self.failed = 0
        self.attempted = 0
        self.broken = False

    # -- set-up -------------------------------------------------------------
    def start(self, warm: bool = True) -> dict:
        """Weights, optimizer state, warm-up and the checked steps.
        Returns the program's side of the check."""
        jax = self.jax
        from bench.adapter import entry_norms, flatten_norms, init_weights
        from repro import sharding as sh
        from repro.train.engine import TreeTrainEngine
        from repro.train.optimizer import init_opt_state
        from repro.train.planner import plan_stream, plans
        self.ctx.enter_context(sh.use_mesh(self.mesh, data_axes=("data",)))
        self.params = init_weights(self.c, self.seed, program=True,
                                   mesh=self.mesh)
        self.opt_state = init_opt_state(self.params)
        self.engine = TreeTrainEngine(self.cfg, self.opt_cfg, impl=self.impl)
        self.pipe = plans(self.cfg, self.lc, itertools.cycle(self.ptrees),
                          self.pc)
        self.it = iter(self.pipe)
        from_program = self.fam.from_program
        norms = jax.jit(lambda t: entry_norms(from_program(t)))
        prog = {"loss": [], "grad": None, "change": None, "ok": True}
        b1, clip = self.opt_cfg.betas[0], self.opt_cfg.grad_clip
        for s in range(self.ref_steps):
            plan, trees, _, ok = self.next_plan()
            self.checked.append(trees)
            self.params, self.opt_state, m = self.engine.step(
                self.params, self.opt_state, plan)
            prog["ok"] = prog["ok"] and ok and _finite(m)
            prog["loss"].append(m["loss"])
            if s == 0:
                scale = min(1.0, clip / max(m["grad_norm"], 1e-9))
                mu = flatten_norms(norms(self.opt_state["mu"]))
                prog["grad"] = {k: v / (1 - b1) / scale for k, v in mu.items()}
        p0 = init_weights(self.c, self.seed, program=True, mesh=self.mesh)
        diff = jax.jit(lambda a, b: entry_norms(from_program(jax.tree.map(
            lambda x, y: x.astype("float32") - y.astype("float32"), a, b))))
        prog["change"] = flatten_norms(diff(self.params, p0))
        del p0
        if warm:
            # one step without waves and every step with partition waves
            # run once more (gradients only, with the weights as the
            # update leaves them): their programs and gateway assembly
            # compile or load here, not in the window
            packed = False
            for ps in plan_stream(self.cfg, self.lc, iter(self.ptrees),
                                  self.pc):
                if ps.oversized or not packed:
                    packed = packed or not ps.oversized
                    g, s = self.engine.accumulate(self.params,
                                                  ps.execution_plan())
                    jax.block_until_ready(s)
                    del g, s
        return prog

    def next_plan(self):
        """The next plan, the trees it trains (as generated) and their
        work.  Not ``ok`` where the plan dropped a tree or trains one
        that the stream did not hold as it is (a grafted forest)."""
        ps = next(self.it)
        plan = ps.execution_plan()
        entries = [f.tree for f in ps.fits] + [o.tree for o in ps.oversized]
        trees = [self.node_of.get(id(t)) for t in entries]
        ok = (None not in trees and len(trees) == plan.num_trees
              and plan.dropped == 0)
        trees = [t for t in trees if t is not None]
        return plan, trees, self.fam.step_work(trees, self.d), ok

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float, min_steps: int = 0,
               annotate: bool = False) -> tuple[list, float]:
        from jax.profiler import TraceAnnotation
        ann = TraceAnnotation if annotate else \
            (lambda _n: contextlib.nullcontext())
        steps = []
        t0 = time.perf_counter()
        t_prev = t0
        while (time.perf_counter() - t0 < seconds
               or len(steps) < min_steps):
            self.attempted += 1
            with ann("bench.plan_wait"):
                plan, _, work, ok = self.next_plan()
            try:
                with ann("bench.step"):
                    self.params, self.opt_state, m = self.engine.step(
                        self.params, self.opt_state, plan)
            except Exception as e:  # the donated state is gone: stop here
                log(f"step raised: {type(e).__name__}: {e}")
                self.failed += 1
                self.broken = True
                break
            ok = ok and _finite(m)
            self.failed += not ok
            t = time.perf_counter()
            waves = 0 if plan.partition is None else len(plan.partition.waves)
            steps.append(dict(dt=t - t_prev, padded=plan.padded_tokens,
                              cells=plan.padded_tokens + plan.unique_tokens,
                              waves=waves, ok=ok, **work))
            t_prev = t
        return steps, t_prev - t0

    def traced_window(self, tdir: str) -> dict:
        jax = self.jax
        from jax.profiler import TraceAnnotation

        from bench import trace
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with TraceAnnotation("bench.window"):
                steps, _ = self.window(TRACE_SECONDS, TRACE_MIN_STEPS,
                                       annotate=True)
        finally:
            jax.profiler.stop_trace()
        tr = trace.reduce(trace.load(tdir))
        tr["steps"] = steps
        return tr

    def memory_peak(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.jax.local_devices())

    def free(self) -> None:
        """Drop the program's state so that the reference has the chip."""
        if hasattr(self, "pipe"):
            self.pipe.close()
        for k in ("params", "opt_state", "engine", "pipe", "it"):
            self.__dict__.pop(k, None)
        self.ctx.close()
        gc.collect()

    def reference(self, precision: str = "f32", steps_trees=None) -> dict:
        """The reference over the checked plans' trees (or others)."""
        from bench import reference
        trees = steps_trees or self.checked
        longest = max(sum(n.length for n in p) for s in trees for t in s
                      for p in t.paths())
        R = max(self.traffic["seq_len"], 1 << (longest - 1).bit_length())
        return reference.run(self.c, self.seed, trees, self.opt, R,
                             precision=precision)


def metric_list(bench: dict, wl_name: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    return [m for m in bench["per_layer" if trace else "end_to_end"]
            if wl_name in m.get("workloads", [wl_name])]


def read_metrics(mlist: list, rec: dict) -> dict:
    out = {}
    for m in mlist:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise Refused("the program (src/repro) is not beside bench/")
        import jax
        if jax.default_backend() != "tpu":
            raise Refused(f"needs a TPU; jax found {jax.default_backend()}")
        devs = jax.devices()
        chips = cell["wl"]["chips"]
        if len(devs) != chips:
            raise Refused(f"cell asks for {chips} chips, jax sees {len(devs)}")
        from bench.peaks import peaks_for
        peaks = peaks_for(devs[0].device_kind)
    except (Refused, KeyError) as e:
        log(f"refused: {e}")
        return 2

    from bench import check
    from repro.train.warmup import configure_compile_cache
    cache = configure_compile_cache()
    meter = CompileMeter()
    log(f"{args.workload}: {chips} x {devs[0].device_kind}, cache {cache}")
    run = Run(cell["c"], cell["traffic"], args.seed, chips,
              name=cell["wl"]["config"])
    prog = run.start()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f}s; checked losses {prog['loss']}")

    c0, e0 = meter.count, run.pipe.exposed_s
    steps, window_s = run.window(args.seconds)
    compiles, plan_wait = meter.count - c0, run.pipe.exposed_s - e0
    tr = None
    if args.trace and not run.broken:
        tr = run.traced_window(os.path.join(ROOT, ".bench_trace",
                                            args.workload))
    mem = run.memory_peak()
    run.free()

    t_ref = time.perf_counter()
    ref = run.reference()
    t_ref = time.perf_counter() - t_ref
    g = check.gaps(prog, ref)
    correct, checks = check.judge(g, cell["limits"])
    correct = correct and prog["ok"] and run.failed == 0
    log(f"window {window_s:.3f}s, {len(steps)} steps, {compiles} compiles; "
        f"reference {t_ref:.3f}s; "
        f"worst grad entry {g['grad_worst']}, worst change entry "
        f"{g['change_worst']}, left out {g['left_out']}")

    rec = dict(steps=steps, window_s=window_s, setup_s=setup_s,
               plan_wait_s=plan_wait, compiles=compiles, chips=chips,
               peaks=peaks, trace=tr)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": run.attempted,
           "failed": run.failed,
           "metrics": read_metrics(metric_list(cell["bench"], args.workload,
                                               bool(args.trace)), rec),
           "device": device}
    if tr is not None:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["top_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = checks
    for n, v in checks.items():
        log(f"check {n}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
