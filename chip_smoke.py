"""Chip smoke: the tree trainer's main path on a TPU, at published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # data parallelism over a 4-chip host

One process drives the chip; nothing runs in a child.  On one chip it

1. trains ``qwen1.5-0.5b`` (24 layers, d_model 1024, 16 heads of 64,
   vocabulary 151936; random weights from ``--seed``) for 3 tree-mode
   steps through ``repro.launch.train``'s own loop, with the platform's
   attention (the Pallas kernels) and ``--auto-partition``, so that
   oversized trees train through partition waves and the gateway
   kernels run too;
2. checks that the compiled step holds the Pallas kernels
   (``tpu_custom_call``) and that every loss is finite;
3. recomputes the first plan's loss and gradient norm with the dense
   ``impl="ref"`` oracle and compares them with the trainer's step 0;
4. runs ``repro.launch.rl_loop`` for 2 steps at the same widths (K=4
   branches per prompt): shared-prefix prefill through the kernels,
   lockstep decode, and the RL update.

With ``--chips 4`` it runs only the trainer on the (4, 1) host mesh and
compares its first-plan loss and gradient norm with the same plan on a
one-device mesh.

Step times, compile time and peak HBM are printed for information; they
are not a benchmark.  The last line of standard output is one JSON object
naming the device.  Any failure exits non-zero and prints no such line,
as does a run without a TPU or outside a checkout of this repository.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
# rows × seq_len per step: with remat, the step's temporaries (~4.2 GB)
# plus bf16 weights, fp32 gradients and AdamW moments (~8.7 GB) fit the
# 15.75 GB a v5e chip offers
TRAIN_ARGS = ["--arch", ARCH, "--seq-len", "2048", "--trees", "6",
              "--auto-partition", "--seed", "0"]
RL_ARGS = ["--arch", ARCH, "--steps", "2", "--k", "4", "--groups", "2",
           "--prompt-len", "16", "--max-new", "8", "--seq-len", "96",
           "--seed", "0"]
# Tolerances are relative and sized to bf16 (8 mantissa bits: one ulp is
# 2^-8 ≈ 4e-3).  Kernel vs dense oracle: the oracle rounds its logits and
# probabilities to bf16 where the kernel keeps fp32, and that rounding is
# carried through 24 layers (4 layers on CPU, interpreted: 2.6e-5 on the
# loss, 1.1e-4 on the grad norm).
REF_RTOL = {"loss": 5e-3, "grad_norm": 2e-2}
# Four data-parallel chips vs one: the same rows, but the partitioned
# program fuses and reduces in another order (2 layers on 4 CPU devices:
# 1.5e-5 on the loss, 4e-5 on the grad norm).
DP_RTOL = {"loss": 2e-3, "grad_norm": 1e-2}


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileMeter:
    """Compiles (each a backend compile or a persistent-cache load) and
    their seconds, from jax's own monitoring events."""

    def __init__(self):
        import jax
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"{self.count} compiles ({self.seconds:.1f}s), "
                f"{self.cache_hits} of them persistent-cache hits")


def peak_hbm() -> str:
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    limit = max(s.get("bytes_limit", 0) for s in stats)
    return f"peak HBM {peak / 1e9:.2f} GB of {limit / 1e9:.2f} GB"


def rel_err(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def first_plan_metrics(cfg, plan, mesh, daxes, impl: str, seed: int):
    """(loss, grad_norm) of one plan from freshly initialised weights —
    the quantities the trainer's step 0 logs, through the same engine."""
    import jax
    import numpy as np

    from repro import sharding as sh
    from repro.models.model import init_params
    from repro.train.engine import TreeTrainEngine
    from repro.train.optimizer import global_norm
    with sh.use_mesh(mesh, data_axes=daxes):
        params = init_params(cfg, jax.random.key(seed))
        grads, scal = TreeTrainEngine(cfg, impl=impl,
                                      donate=False).accumulate(params, plan)
        return (float(np.asarray(scal)[0]),
                float(np.asarray(global_norm(grads))))


def train_phase(argv: list[str], meter: CompileMeter, min_steps: int = 3,
                need_waves: bool = True) -> dict:
    from repro.launch import train
    t0 = time.perf_counter()
    res = train.main(argv)
    hist = res["history"]
    log(f"train: {len(hist)} steps in {time.perf_counter() - t0:.1f}s, "
        f"impl={res['args'].impl}; {meter.line()}; {peak_hbm()}")
    for h in hist:
        log(f"train step {h['step']}: loss {h['loss']:.6f} grad_norm "
            f"{h['grad_norm']:.6f} waves {h['waves']} {h['sec']:.3f}s")
    check(len(hist) >= min_steps,
          f"trainer ran {len(hist)} steps, wanted >= {min_steps}")
    check(all(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"])
              for h in hist), "non-finite loss or grad norm")
    check(not need_waves or any(h["waves"] for h in hist),
          "no step carried partition waves")
    return res


def kernel_in_step(res: dict) -> None:
    """The compiled packed step of the first plan holds the Pallas
    kernels (a persistent-cache hit: the trainer compiled it already)."""
    import jax
    import jax.numpy as jnp

    from repro import sharding as sh
    from repro.models.model import init_params
    from repro.train.engine import NUM_SCALARS, _packed_exec_fn
    cfg, plan, impl = res["cfg"], res["first_plan"], res["args"].impl
    check(plan.packed is not None, "first plan has no packed rows")
    has_waves = plan.partition is not None and bool(plan.partition.waves)
    with sh.use_mesh(res["mesh"], data_axes=res["data_axes"]):
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
        batch = dict(plan.packed.inputs)
        batch["num_trees"] = max(plan.num_trees, 1)
        scal = jax.ShapeDtypeStruct((NUM_SCALARS,), jnp.float32)
        acc = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params)
        args = (params, batch, acc, scal) if has_waves else \
            (params, batch, scal)
        fn = _packed_exec_fn(cfg, impl, True, with_acc=has_waves)
        text = fn.lower(*args).compile().as_text()
    n = text.count("tpu_custom_call")
    log(f"compiled step holds {n} tpu_custom_call ops")
    check(n > 0, "no Pallas kernel (tpu_custom_call) in the compiled step")


def compare(name: str, got: dict, want: dict, rtol: dict) -> None:
    for key, tol in rtol.items():
        err = rel_err(got[key], want[key])
        log(f"{name} {key}: {got[key]:.6f} vs {want[key]:.6f}, rel err "
            f"{err:.2e} (tolerance {tol:.0e})")
        check(err <= tol, f"{name} {key} differs by {err:.2e} > {tol:.0e}")


def ref_phase(res: dict, meter: CompileMeter) -> None:
    h0 = res["history"][0]
    loss, gnorm = first_plan_metrics(res["cfg"], res["first_plan"],
                                     res["mesh"], res["data_axes"], "ref",
                                     res["args"].seed)
    compare("kernel vs ref", h0, {"loss": loss, "grad_norm": gnorm},
            REF_RTOL)
    log(f"ref: {meter.line()}; {peak_hbm()}")


def rl_phase(argv: list[str], meter: CompileMeter) -> None:
    from repro.launch import rl_loop
    t0 = time.perf_counter()
    res = rl_loop.main(argv)
    hist = res["history"]
    for i, m in enumerate(hist):
        log(f"rl step {i}: loss {m['loss']:.6f} grad_norm "
            f"{m['grad_norm']:.6f} {m['sec']:.3f}s")
    log(f"rl: {len(hist)} steps in {time.perf_counter() - t0:.1f}s, "
        f"impl={res['args'].impl}, engine retraces "
        f"{res['engine'].retraces}; {meter.line()}; {peak_hbm()}")
    check(len(hist) == 2, f"rl loop ran {len(hist)} steps, wanted 2")
    check(all(math.isfinite(m["loss"]) for m in hist), "non-finite RL loss")


def four_chip_phase(argv: list[str], meter: CompileMeter) -> None:
    """Data-parallel trainer on the (4, 1) host mesh vs the same first
    plan on a one-device mesh."""
    import jax
    import numpy as np

    from repro.launch.mesh import host_descriptor
    res = train_phase(argv, meter, min_steps=1, need_waves=False)
    check(res["mesh"].shape["data"] == 4, f"mesh {res['mesh'].shape}")
    desc = host_descriptor(1)
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(desc.shape),
                            desc.axis_names, axis_types=desc.axis_types)
    loss, gnorm = first_plan_metrics(res["cfg"], res["first_plan"], one,
                                     ("data",), res["args"].impl,
                                     res["args"].seed)
    compare("4 chips vs 1", res["history"][0],
            {"loss": loss, "grad_norm": gnorm}, DP_RTOL)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: only the data-parallel trainer on a 4-chip "
                         "host, compared with one device")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("[smoke] FAIL: run from a checkout of the repository "
              "(src/repro not found)", file=sys.stderr)
        return 1
    sys.path.insert(0, src)
    import jax
    if jax.default_backend() != "tpu":
        print(f"[smoke] FAIL: needs a TPU, jax found "
              f"{jax.default_backend()}", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) != args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} but jax sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from repro.train.warmup import compile_cache_files, \
        configure_compile_cache
    cache = configure_compile_cache()
    files0 = compile_cache_files(cache)
    meter = CompileMeter()
    log(f"{len(devices)} x {devices[0].device_kind}; compile cache "
        f"{cache} ({files0} files)")
    try:
        if args.chips == 4:
            four_chip_phase(TRAIN_ARGS + ["--rows", "4", "--steps", "1"],
                            meter)
        else:
            res = train_phase(TRAIN_ARGS + ["--rows", "2", "--steps", "3"],
                              meter)
            kernel_in_step(res)
            ref_phase(res, meter)
            del res
            gc.collect()        # the trainer's state leaves the chip
            rl_phase(RL_ARGS, meter)
    except (SmokeFailure, AssertionError) as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    log(f"compile cache: {compile_cache_files(cache) - files0} new files; "
        f"{meter.line()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
