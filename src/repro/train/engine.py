"""Unified tree-ingest training engine: one plan→execute loop.

The paper's systems claim is that the training engine must *natively
ingest tree-structured data* — and report its 6.2x speedup for both SFT
and the RL model-update phase.  Before this module the trainer was two
loops bolted together: a jitted step for the packed batch and a separate
host-driven wave driver for partitioned oversized trees, accumulating
gradients host-side with per-step ``float()`` syncs.

Here every step is an **ExecutionPlan** — an ordered list of uniform,
shape-bucketed microbatch executions:

  - the packed rows (tree- or baseline-packed) are a 1-element plan;
  - oversized trees contribute their partition waves via
    ``core/gateway.build_partition_plan`` (a plan *builder*, not a
    driver).

``TreeTrainEngine.step`` runs every execution through one jitted
forward/backward with a **donated fp32 gradient accumulator that never
leaves the device**; loss / token-CE / weight scalars accumulate in a
single on-device vector, and the step performs **exactly one host sync**
(the logging transfer, counted in ``engine.host_syncs``).

The loss is pluggable through the per-token weights threaded end-to-end
by the serializer: ``loss_mode="rl"`` multiplies λ_t by GRPO-style
per-branch advantages (see core/tree.serialize_tree), with advantage≡1
reducing bit-exactly to SFT — the same engine serves both scenarios.
"""
from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro import sharding as sh
from repro.configs.base import ModelConfig
from repro.core.gateway import (PartitionPlan, _cut_caps_view,
                                _embed_cut_cot, _embed_gw_row_cot,
                                _names_sig, _slice_gw_row, _stack_gw_rows,
                                _vjp1, _vjp2, assemble_child_gw,
                                route_child_cot)
from repro.core.plan_cost import packed_signature, wave_signature_of
from repro.models.model import loss_and_metrics
from repro.train.exec_cache import ExecutableCache, exec_key
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import jitted_update

logger = logging.getLogger(__name__)

# the on-device scalar accumulator: [loss, nll_sum, weight_sum,
# moe_held_rows]
NUM_SCALARS = 4


# ---------------------------------------------------------------------------
# Plan types
# ---------------------------------------------------------------------------

@dataclass
class PackedExec:
    """One uniform [B, S] microbatch execution (the packed rows)."""
    inputs: dict                 # jnp-ready model inputs (prepare_batch)
    tokens: int = 0              # host-side unique-token count (logging)
    cells: int = 0               # materialized row cells (B × S)
    attn_blocks_live: int = 0    # attention block pairs the kernels run
    attn_blocks_dense: int = 0   # all pairs of their dense grid


@dataclass
class ExecutionPlan:
    """Everything one optimizer step trains on, in execution order:
    the packed microbatch (if any) followed by the partition waves of the
    oversized trees (if any).  Built host-side by the plan-ahead
    scheduler (``train/planner``) — the engine only executes."""
    packed: Optional[PackedExec] = None
    partition: Optional[PartitionPlan] = None
    num_trees: int = 0           # packed + oversized (loss normalizer)
    dropped: int = 0             # trees lost this step (no auto-partition)
    versions: Optional[tuple] = None   # (min, max) weight_version of the
    #                              step's trees (async RL staleness; None
    #                              for offline/synthetic sources)

    @property
    def is_empty(self) -> bool:
        return self.packed is None and (
            self.partition is None or not self.partition.waves)

    @property
    def num_oversized(self) -> int:
        return 0 if self.partition is None else self.partition.num_trees

    @property
    def unique_tokens(self) -> int:
        n = 0 if self.packed is None else self.packed.tokens
        if self.partition is not None and self.partition.waves:
            n += self.partition.info["unique_tokens"]
        return n

    @property
    def padded_tokens(self) -> int:
        """Materialized row cells holding no unique token — the schedule
        overhead the plan-ahead cost model minimizes."""
        cells = 0 if self.packed is None else self.packed.cells
        if self.partition is not None and self.partition.waves:
            cells += self.partition.info.get("cells", 0)
        return cells - self.unique_tokens

    @property
    def num_executions(self) -> int:
        n = 0 if self.packed is None else 1
        if self.partition is not None:
            n += len(self.partition.waves)
        return n


# ---------------------------------------------------------------------------
# Cached jitted executions (shape-bucketed; donation recycles the
# accumulator buffers between microbatches)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=16)
def _packed_exec_fn(cfg: ModelConfig, impl: str, donate: bool,
                    with_acc: bool = True):
    """Packed microbatch: fused fwd+bwd, grads accumulated into the
    donated fp32 buffer, scalars into the donated scalar vector.

    ``with_acc=False`` is what the engine runs: the fp32 grads ARE the
    accumulator (the partition waves of the step add to it), so no
    separate param-sized zero buffer is ever materialized (``0 + g ≡ g``
    exactly, bit-for-bit).  ``with_acc=True`` is the form the static
    audits (``repro.analysis``) check."""
    def scal_add(scal, loss, metrics):
        return scal + jnp.stack(
            [loss.astype(jnp.float32),
             metrics["nll_sum"].astype(jnp.float32),
             metrics["weight_sum"].astype(jnp.float32),
             metrics["moe_held_rows"].astype(jnp.float32)])

    if with_acc:
        def f(params, batch, acc, scal):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_and_metrics(cfg, p, batch, impl),
                has_aux=True)(params)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                               acc, grads)
            return acc, scal_add(scal, loss, metrics)

        return jax.jit(f, donate_argnums=(2, 3) if donate else ())

    def f1(params, batch, scal):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_and_metrics(cfg, p, batch, impl),
            has_aux=True)(params)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return grads, scal_add(scal, loss, metrics)

    return jax.jit(f1, donate_argnums=(2,) if donate else ())


@lru_cache(maxsize=64)
def _wave_exec_fns(cfg: ModelConfig, names: tuple, impl: str,
                   has_gw: bool, donate: bool):
    """One partition wave: jitted forward (captures out, scalars
    accumulated on-device, loss pre-scaled by the tree normalizer) and
    jitted remat-backward (grads accumulated into the donated fp32
    buffer, gateway cotangents out for child→parent routing)."""
    from repro.models.transformer import partition_loss

    def fwd(params, batch, gw, capspecs, scal, scale):
        (loss, caps), metrics = partition_loss(
            cfg, params, batch, gw if has_gw else None, capspecs, impl)
        scal = scal + jnp.stack(
            [loss.astype(jnp.float32) * scale,
             metrics["nll_sum"].astype(jnp.float32),
             metrics["weight_sum"].astype(jnp.float32),
             metrics["moe_held_rows"].astype(jnp.float32)])
        return caps, scal

    def bwd(params, batch, gw, capspecs, cot, acc):
        if has_gw:
            g_params, g_gw = _vjp2(cfg, params, batch, gw, capspecs,
                                   impl, cot)
        else:
            g_params, g_gw = _vjp1(cfg, params, batch, capspecs, impl,
                                   cot)
        acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                           acc, g_params)
        return acc, g_gw

    return (jax.jit(fwd, donate_argnums=(4,) if donate else ()),
            jax.jit(bwd, donate_argnums=(5,) if donate else ()))


# ---------------------------------------------------------------------------
# Partition-plan executor (the runtime half of core/gateway's planner)
# ---------------------------------------------------------------------------

def run_partition_plan(
    cfg: ModelConfig,
    params: dict,
    plan: PartitionPlan,
    acc: Any,
    scal: jax.Array,
    *,
    impl: str = "ref",
    loss_scale: jax.Array,
    donate: bool = True,
    exec_lookup: Optional[Callable] = None,
    seq_len: Optional[int] = None,
) -> tuple[Any, jax.Array]:
    """Execute a PartitionPlan: forward sweep in wave order (assembling
    each fragment's gateway from its parent's runtime captures), backward
    sweep in reverse (routing gateway cotangents child→parent in fp32).

    ``loss_scale`` seeds every wave's backward cotangent — the engine
    passes 1/num_trees so the partitioned gradients land in the shared
    accumulator already normalized, with no extra scaling pass.  The loss
    scalar is scaled the same way; nll/weight sums stay raw.  Returns the
    updated ``(acc, scal)`` — no host sync happens here.

    ``exec_lookup(variant, sig, fn, args)`` (the engine's AOT
    executable-cache resolver) swaps each jitted wave fn for its
    precompiled executable; None dispatches the plain jit (every new
    shape bucket retraces inside jax)."""
    st: list[dict] = []
    S = seq_len
    if S is None and plan.waves:
        S = plan.waves[0].batch["tokens"].shape[1]

    def resolve(variant, wp, fn, args):
        if exec_lookup is None:
            return fn(*args)
        return exec_lookup(variant, wave_signature_of(wp, S), fn, args)

    # ---- forward sweep, wave order ---------------------------------------
    for wp in plan.waves:
        batch = {k: jnp.asarray(v) for k, v in wp.batch.items()}
        gw = None
        if wp.has_gw:
            rows_gw = []
            for ref in wp.parents:
                stp, pwp = st[ref.wave], plan.waves[ref.wave]
                cname = f"c{ref.cut}"
                p_gw_row = None if stp["gw"] is None else _slice_gw_row(
                    stp["gw"], ref.row, pwp.A_real[ref.row])
                caps_view = _cut_caps_view(cfg, stp["caps"], cname,
                                           ref.row, ref.path_len)
                rows_gw.append(
                    assemble_child_gw(cfg, p_gw_row, caps_view, cname))
            gw = _stack_gw_rows(rows_gw, wp.anc_A_max,
                                batch["tokens"].shape[0],
                                rows_idx=wp.slot_rows)
        fwd, _ = _wave_exec_fns(cfg, _names_sig(wp.capspecs), impl,
                                wp.has_gw, donate)
        caps, scal = resolve("wave.fwd", wp, fwd,
                             (params, batch, gw, wp.capspecs, scal,
                              loss_scale))
        st.append(dict(batch=batch, gw=gw, caps=caps, cot_gw=None,
                       cot_cut={}))

    # ---- backward sweep, reverse wave order ------------------------------
    for w in reversed(range(len(plan.waves))):
        wp, s = plan.waves[w], st[w]
        cot_caps = jax.tree.map(jnp.zeros_like, s["caps"])
        for cname, (r, cot_view) in s["cot_cut"].items():
            _embed_cut_cot(cot_caps, cot_view, cname, r)
        _, bwd = _wave_exec_fns(cfg, _names_sig(wp.capspecs), impl,
                                wp.has_gw, donate)
        acc, g_gw = resolve("wave.bwd", wp, bwd,
                            (params, s["batch"], s["gw"], wp.capspecs,
                             (loss_scale, cot_caps), acc))
        if not wp.has_gw:
            continue
        if s["cot_gw"] is not None:
            g_gw = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) + b, g_gw, s["cot_gw"])
        for si, ref in enumerate(wp.parents):
            row = wp.slot_rows[si]
            stp, pwp = st[ref.wave], plan.waves[ref.wave]
            cname = f"c{ref.cut}"
            cot_child_row = _slice_gw_row(g_gw, row, wp.A_real[row])
            p_gw_row = None if stp["gw"] is None else _slice_gw_row(
                stp["gw"], ref.row, pwp.A_real[ref.row])
            caps_view = _cut_caps_view(cfg, stp["caps"], cname, ref.row,
                                       ref.path_len)
            cot_gw_row = None if p_gw_row is None else jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), p_gw_row)
            cot_caps_row = jax.tree.map(jnp.zeros_like, caps_view)
            route_child_cot(cfg, p_gw_row, caps_view, cname,
                            cot_child_row, cot_gw_row, cot_caps_row)
            if cot_gw_row is not None:
                if stp["cot_gw"] is None:
                    stp["cot_gw"] = jax.tree.map(
                        lambda a: jnp.zeros(a.shape, jnp.float32),
                        stp["gw"])
                stp["cot_gw"] = _embed_gw_row_cot(stp["cot_gw"],
                                                  cot_gw_row, ref.row)
            stp["cot_cut"][cname] = (ref.row, cot_caps_row)
    return acc, scal


def _on_mesh(tree):
    """Commit the arrays no computation has placed yet (fresh weights, a
    fresh optimizer state) to the active mesh, replicated, as the
    update's outputs are: the first step then runs the programs every
    later step runs instead of compiling its own.  The buffers stay
    where they are."""
    mesh = sh.current_mesh()
    if mesh is None:
        return tree
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(
        lambda a: a if not isinstance(a, jax.Array) or a.committed
        else jax.device_put(a, rep), tree)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class TreeTrainEngine:
    """Plan→execute training engine: ``step(params, opt_state, plan)``
    runs every microbatch execution of the plan (packed rows first, then
    the partition waves), accumulates gradients in one donated fp32
    device buffer, applies the cached jitted AdamW update, and performs
    exactly ONE host sync to materialize the logging metrics.

    ``host_syncs`` counts every device→host transfer the engine issues —
    benchmarks assert it stays ≤ 1 per optimizer step.

    With an ``exec_cache`` (:class:`~repro.train.exec_cache
    .ExecutableCache`, filled by ``train/warmup.AOTWarmupService`` and
    the planner's pre-warm hook) every dispatch first resolves a
    precompiled AOT executable keyed by its planner-level signature —
    a hit bypasses jax's tracing machinery entirely.  A miss compiles
    synchronously (the honest slow path), counted in ``retraces`` with
    the stall seconds in ``compile_wait_s``; when a ``universe``
    (``analysis/signatures.SignatureUniverse``) is attached, an
    out-of-universe miss logs a warning naming why the planner escaped
    the enumerable bucket set."""

    METRIC_NAMES = ("loss", "nll_sum", "weight_sum", "moe_held_rows",
                     "grad_norm", "lr")

    def __init__(self, cfg: ModelConfig,
                 opt_cfg: Optional[OptimizerConfig] = None, *,
                 impl: str = "ref", donate: bool = True,
                 weight_store=None,
                 exec_cache: Optional[ExecutableCache] = None,
                 universe=None):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.impl = impl
        self.donate = donate
        self.host_syncs = 0
        self.steps_done = 0
        # async RL: publish updated weights (copied — ours get donated)
        # after every optimizer step, and audit the off-policy lag of
        # each consumed plan (trainer step − oldest tree's version)
        self.weight_store = weight_store
        self.max_lag_seen = 0
        # AOT executable cache: retraces counts cold-bucket stalls the
        # warmup/prewarm path failed to hide (0 on an in-universe
        # stream after warmup — asserted by benchmarks and rl_loop)
        self.exec_cache = exec_cache
        self.universe = universe
        self.retraces = 0
        self.compile_wait_s = 0.0

    # -- AOT executable resolution ----------------------------------------
    def _exec_lookup(self, variant: str, sig, fn, args: tuple):
        """Resolve one dispatch: cache hit → the AOT-compiled executable;
        miss → synchronous ``lower().compile()`` (counted as a retrace,
        its wall time as exposed compile wait), then cached so the bucket
        stalls at most once per run."""
        key = exec_key(variant, sig, args)
        compiled = self.exec_cache.get(key)
        if compiled is not None:
            return compiled(*args)
        t0 = time.perf_counter()
        compiled, _ = self.exec_cache.compile_once(key, fn, args)
        self.compile_wait_s += time.perf_counter() - t0
        self.retraces += 1
        if self.universe is not None and sig[0] in ("packed", "wave"):
            ok, why = self.universe.contains(sig)
            if not ok:
                logger.warning(
                    "out-of-universe signature %s (%s): compiled "
                    "synchronously on the slow path — the planner "
                    "escaped the enumerable bucket set", sig, why)
            else:
                logger.info(
                    "in-universe signature %s was not prewarmed: "
                    "compiled synchronously (%s)", sig, variant)
        return compiled(*args)

    def _run(self, variant: str, sig, fn, args: tuple):
        if self.exec_cache is None:
            return fn(*args)
        return self._exec_lookup(variant, sig, fn, args)

    # -- gradient accumulation (no optimizer, no host sync) ---------------
    def accumulate(self, params, plan: ExecutionPlan):
        """Run the plan's executions; returns ``(grads, scal)`` — the
        fp32 gradient sum (normalized per tree) and the on-device
        ``[loss, nll_sum, weight_sum, moe_held_rows]`` vector.  Loss
        semantics match the pre-engine two-branch loop: mean over the
        step's trees."""
        params = _on_mesh(params)
        scal = jnp.zeros((NUM_SCALARS,), jnp.float32)
        n = max(plan.num_trees, 1)
        has_waves = plan.partition is not None and plan.partition.waves
        if plan.packed is not None:
            batch = dict(plan.packed.inputs)
            batch["num_trees"] = n
            B, S = batch["tokens"].shape
            psig = packed_signature(B, S)
            # the grads ARE the accumulator, which the partition waves
            # (if any) add to — no param-sized zero buffer, and one
            # packed program whether or not the step has waves
            f = _packed_exec_fn(self.cfg, self.impl, self.donate,
                                with_acc=False)
            acc, scal = self._run("packed", psig, f, (params, batch, scal))
            if not has_waves:
                return acc, scal
        else:
            acc = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                               params)
        if has_waves:
            acc, scal = run_partition_plan(
                self.cfg, params, plan.partition, acc, scal,
                impl=self.impl,
                loss_scale=jnp.asarray(1.0 / n, jnp.float32),
                donate=self.donate,
                exec_lookup=(None if self.exec_cache is None
                             else self._exec_lookup))
        return acc, scal

    # -- one optimizer step ------------------------------------------------
    def step(self, params, opt_state, plan: ExecutionPlan):
        """Returns ``(params, opt_state, metrics)`` — metrics is a host
        dict (loss, nll, grad_norm, lr, …) pulled in a single transfer."""
        assert self.opt_cfg is not None, \
            "TreeTrainEngine.step needs an OptimizerConfig"
        with TraceAnnotation("repro.engine.dispatch"):
            params, opt_state = _on_mesh((params, opt_state))
            grads, scal = self.accumulate(params, plan)
            upd = jitted_update(self.opt_cfg, self.donate)
            params, opt_state, om = self._run("update", ("update",), upd,
                                              (params, grads, opt_state))
            vec = jnp.concatenate(
                [scal, jnp.stack([om["grad_norm"], om["lr"]]
                                 ).astype(jnp.float32)])
        host = self._sync(vec)
        metrics = dict(zip(self.METRIC_NAMES, host.tolist()))
        metrics["nll"] = metrics["nll_sum"] / max(metrics["weight_sum"],
                                                  1e-9)
        if plan.versions is not None:
            lag = self.steps_done - plan.versions[0]
            metrics["max_lag"] = lag
            self.max_lag_seen = max(self.max_lag_seen, lag)
        self.steps_done += 1
        if self.weight_store is not None:
            self.weight_store.publish(params, self.steps_done)
        return params, opt_state, metrics

    def warmup(self, params, opt_state, plan: ExecutionPlan):
        """Compile-warm every executable the plan exercises — the full
        accumulate + optimizer-update pipeline — WITHOUT the logging
        host sync: ``block_until_ready`` fences the compile+run but
        transfers nothing, so ``host_syncs`` stays 0 and the static
        auditor's one-host-sync proof (``repro.analysis``) covers warmup
        too.  Does not count as a step and publishes no weights; returns
        ``(params, opt_state)`` (donated inputs are consumed)."""
        assert self.opt_cfg is not None, \
            "TreeTrainEngine.warmup needs an OptimizerConfig"
        params, opt_state = _on_mesh((params, opt_state))
        grads, _scal = self.accumulate(params, plan)
        upd = jitted_update(self.opt_cfg, self.donate)
        params, opt_state, _om = self._run("update", ("update",), upd,
                                           (params, grads, opt_state))
        jax.block_until_ready(params)
        return params, opt_state

    def _sync(self, vec: jax.Array) -> np.ndarray:
        """THE host sync: every device→host read the engine performs
        funnels through here so the count is auditable."""
        self.host_syncs += 1
        with TraceAnnotation("repro.engine.sync"):
            return np.asarray(vec)
