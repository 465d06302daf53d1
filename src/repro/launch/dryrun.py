"""Multi-pod dry-run: prove every (arch × input-shape × mesh) lowers and
compiles under the production sharding, and harvest roofline inputs.

MUST set the fake device count before ANY jax import (jax locks the device
count on first init) — hence the first two lines below.

Per combination we record to JSON: compile status/time,
``compiled.cost_analysis()`` (FLOPs/bytes), ``compiled.memory_analysis()``
(per-device bytes — proves it fits), and every collective op parsed from
the post-SPMD HLO with a while-loop trip-count multiplier (scan-over-
layers bodies are counted once by XLA; we re-multiply by the known trip
counts — see launch/roofline.py for the methodology notes).

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b                 # all shapes, both meshes
  python -m repro.launch.dryrun --arch all --shape train_4k --mesh single
  python -m repro.launch.dryrun --arch all                      # the full 40×2 matrix
"""
import os

os.environ["XLA_FLAGS"] = os.environ.get(
    "XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import sharding as sh                     # noqa: E402
from repro.analysis.hlo_comms import (loop_multiplier,  # noqa: E402
                                      parse_collectives)
from repro.configs import (ARCH_IDS, get_config,     # noqa: E402
                           long_context_variant, supports_shape)
from repro.configs.base import INPUT_SHAPES, ModelConfig  # noqa: E402
from repro.launch.mesh import data_axes, make_production_mesh  # noqa: E402
from repro.models.layers import logits_from_hidden   # noqa: E402
from repro.models.model import (has_router_loss,     # noqa: E402
                                loss_and_metrics, max_conv_taps,
                                needs_chunks)
from repro.models import transformer as tf           # noqa: E402
from repro.serve import decode as serve              # noqa: E402
from repro.train.optimizer import (OptimizerConfig,  # noqa: E402
                                   adamw_update, init_opt_state)

SDS = jax.ShapeDtypeStruct


def _dt(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins — no allocation)
# ---------------------------------------------------------------------------

def train_batch_specs(cfg: ModelConfig, B: int, S: int) -> dict:
    d = {
        "tokens": SDS((B, S), jnp.int32),
        "pos_ids": SDS((B, S), jnp.int32),
        "kv_last": SDS((B, S), jnp.int32),
        "weight": SDS((B, S), jnp.float32),
        "prev_idx": SDS((B, S), jnp.int32),
        "valid": SDS((B, S), jnp.bool_),
    }
    if has_router_loss(cfg):
        d["aux_weight"] = SDS((B, S), jnp.float32)
    if needs_chunks(cfg):
        d["chunk_parent"] = SDS((B, S // cfg.ssm.chunk_size), jnp.int32)
        d["prev_pows"] = SDS((B, S, max(1, max_conv_taps(cfg))), jnp.int32)
    if cfg.frontend is not None:
        d["extra_embeds"] = SDS((B, cfg.frontend_len, cfg.d_model), _dt(cfg))
    return d


def params_specs(cfg: ModelConfig):
    return jax.eval_shape(lambda k: tf.init_params(cfg, k),
                          jax.random.key(0))


def build_train_fn(cfg: ModelConfig, impl: str):
    opt_cfg = OptimizerConfig()
    micro = int(os.environ.get("DRYRUN_MICROBATCH", "1"))

    def grad_fn(params, batch):
        (loss, _m), grads = jax.value_and_grad(
            lambda p: loss_and_metrics(cfg, p, batch, impl),
            has_aux=True)(params)
        return loss, grads

    def step(params, opt_state, batch):
        if micro > 1:
            # gradient accumulation: scan over microbatches; per-device
            # activation temp shrinks by ~micro× at identical math
            def split(a):
                return a.reshape(micro, a.shape[0] // micro, *a.shape[1:])

            mb = jax.tree.map(split, batch)

            def body(acc, b):
                loss, grads = grad_fn(params, b)
                return jax.tree.map(
                    lambda x, g: x + g.astype(jnp.float32), acc, grads
                ), loss

            zero = jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            grads, losses = jax.lax.scan(body, zero, mb)
            loss = losses.sum()
        else:
            loss, grads = grad_fn(params, batch)
        params, opt_state, om = adamw_update(opt_cfg, params, grads,
                                             opt_state)
        return params, opt_state, loss, om["grad_norm"]

    return step


def build_prefill_fn(cfg: ModelConfig, impl: str):
    def prefill(params, tokens, extra=None):
        B, S = tokens.shape
        ar = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        batch = {
            "tokens": tokens,
            "pos_ids": ar,
            "kv_last": jnp.full((B, S), S - 1, jnp.int32),
            "prev_idx": ar - 1,
            "valid": jnp.ones((B, S), bool),
            "weight": jnp.zeros((B, S), jnp.float32),
        }
        if needs_chunks(cfg):
            C = S // cfg.ssm.chunk_size
            batch["chunk_parent"] = jnp.broadcast_to(
                jnp.arange(C, dtype=jnp.int32) - 1, (B, C))
            taps = max(1, max_conv_taps(cfg))
            batch["prev_pows"] = jnp.maximum(
                ar[..., None] - jnp.arange(1, taps + 1, dtype=jnp.int32),
                -1)
        if extra is not None:
            batch["extra_embeds"] = extra
        hidden, _ = tf.forward(cfg, params, batch, impl)
        logits = logits_from_hidden(params["embed"], params.get("lm_head"),
                                    hidden[:, -1:])
        return sh.shard_logits(logits)

    return prefill


def build_decode_fn(cfg: ModelConfig):
    def step(params, cache, tokens, pos, write_idx):
        return serve._decode_step(cfg, params, cache, tokens, pos, write_idx)

    return step


# cache_shardings moved to repro.sharding (shared with shardlint); the
# HLO collective parser moved to repro.analysis.hlo_comms — both kept as
# names here for the existing callers of this module.
cache_shardings = sh.cache_shardings


# ---------------------------------------------------------------------------
# One combo
# ---------------------------------------------------------------------------

def run_combo(arch: str, shape_name: str, multi_pod: bool, impl: str,
              outdir: str) -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    if not supports_shape(cfg, shape_name):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "family": cfg.family, "status": "skipped",
                "reason": "no long-decode semantics (see DESIGN.md)"}
    if shape_name == "long_500k":
        cfg = long_context_variant(cfg)
    if os.environ.get("DRYRUN_REMAT"):
        cfg = cfg.replace(remat=os.environ["DRYRUN_REMAT"])

    mesh_shape = None
    if os.environ.get("DRYRUN_MESH_SHAPE"):
        mesh_shape = tuple(int(x) for x in
                           os.environ["DRYRUN_MESH_SHAPE"].split("x"))
    mesh = make_production_mesh(multi_pod=multi_pod, shape=mesh_shape)
    daxes = data_axes(multi_pod)
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "multi" if multi_pod else "single",
                 "family": cfg.family,
                 "chips": int(np.prod(list(mesh.shape.values())))}
    t0 = time.time()
    seq_par = bool(os.environ.get("DRYRUN_SEQ_PARALLEL"))
    rec["seq_parallel"] = seq_par
    rec["remat"] = cfg.remat
    with sh.use_mesh(mesh, data_axes=daxes, seq_parallel=seq_par):
        pspecs = params_specs(cfg)
        pshard = sh.param_shardings(pspecs, mesh, fsdp_axis="data")
        if shape.kind == "train":
            fn = build_train_fn(cfg, impl)
            ospecs = jax.eval_shape(init_opt_state, pspecs)
            oshard = {"mu": sh.param_shardings(ospecs["mu"], mesh,
                                               fsdp_axis="data"),
                      "nu": sh.param_shardings(ospecs["nu"], mesh,
                                               fsdp_axis="data"),
                      "step": NamedSharding(mesh, P())}
            bspecs = train_batch_specs(cfg, shape.global_batch,
                                       shape.seq_len)
            bshard = sh.batch_shardings(bspecs, mesh, daxes)
            jf = jax.jit(fn, in_shardings=(pshard, oshard, bshard))
            lowered = jf.lower(pspecs, ospecs, bspecs)
        elif shape.kind == "prefill":
            fn = build_prefill_fn(cfg, impl)
            B, S = shape.global_batch, shape.seq_len
            args = [pspecs, SDS((B, S), jnp.int32)]
            shards = [pshard, sh.batch_shardings(args[1], mesh, daxes)]
            if cfg.frontend is not None:
                args.append(SDS((B, cfg.frontend_len, cfg.d_model),
                                _dt(cfg)))
                shards.append(sh.batch_shardings(args[2], mesh, daxes))
            jf = jax.jit(fn, in_shardings=tuple(shards))
            lowered = jf.lower(*args)
        else:  # decode
            fn = build_decode_fn(cfg)
            B, S = shape.global_batch, shape.seq_len
            enc_len = cfg.encdec.src_len if cfg.encdec else 0
            cspecs = jax.eval_shape(
                lambda: serve._init_cache(cfg, B, S, enc_len))
            cshard = cache_shardings(cspecs, mesh, daxes)
            args = (pspecs, cspecs, SDS((B, 1), jnp.int32),
                    SDS((B,), jnp.int32), SDS((), jnp.int32))
            shards = (pshard, cshard,
                      sh.batch_shardings(args[2], mesh, daxes),
                      sh.batch_shardings(args[3], mesh, daxes),
                      NamedSharding(mesh, P()))
            jf = jax.jit(fn, in_shardings=shards)
            lowered = jf.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        rec["status"] = "ok"

        try:
            ca = compiled.cost_analysis()
            rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                    if isinstance(v, (int, float))
                                    and k in ("flops", "bytes accessed",
                                              "transcendentals",
                                              "optimal_seconds")}
        except Exception as e:  # noqa: BLE001
            rec["cost_analysis"] = {"error": str(e)[:200]}
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                a: int(getattr(ma, a)) for a in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes")
                if hasattr(ma, a)}
        except Exception as e:  # noqa: BLE001
            rec["memory_analysis"] = {"error": str(e)[:200]}
        try:
            hlo = compiled.as_text()
            if os.environ.get("DRYRUN_DUMP_HLO"):
                tag = f"{arch}__{shape_name}__" \
                      f"{'multi' if multi_pod else 'single'}"
                with open(os.path.join(outdir, tag + ".hlo.txt"), "w") as f:
                    f.write(hlo)
            colls = parse_collectives(hlo)
            mult = loop_multiplier(cfg)
            chunks = (shape.seq_len // cfg.ssm.chunk_size
                      if needs_chunks(cfg) and shape.kind != "decode"
                      else 1)
            summary: dict[str, dict] = {}
            for c in colls:
                s = summary.setdefault(c["op"], {"count": 0, "bytes": 0,
                                                 "bytes_with_loops": 0})
                s["count"] += 1
                s["bytes"] += c["bytes"]
                m = 1
                if c["loop_depth"] == 1:
                    m = mult
                elif c["loop_depth"] >= 2:
                    m = mult * chunks
                s["bytes_with_loops"] += c["bytes"] * m
            rec["collectives"] = summary
            rec["loop_multiplier"] = mult
            rec["chunk_multiplier"] = chunks
            rec["hlo_bytes"] = len(hlo)
        except Exception as e:  # noqa: BLE001
            rec["collectives"] = {"error": str(e)[:200]}
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--impl", default="chunked")
    ap.add_argument("--out", default="dryrun_results")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS[:10] if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                print(f"[run ] {tag}", flush=True)
                try:
                    rec = run_combo(arch, shape, mp, args.impl, args.out)
                except Exception:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error",
                           "traceback": traceback.format_exc()[-4000:]}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"[done] {tag}: {rec.get('status')} "
                      f"({rec.get('total_s', '?')}s)", flush=True)


if __name__ == "__main__":
    main()
