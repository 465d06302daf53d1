"""AOT warmup engine: precompile the signature universe, persist the
compile cache, pre-warm the planner's upcoming shapes.

The runtime half of ROADMAP item 4.  ``analysis/signatures`` proved
(statically) that a planner run stays inside an enumerable pow2-bucket
:class:`SignatureUniverse`; this module inverts that proof into work:

  startup warmup     :class:`AOTWarmupService` enumerates the universe,
                     synthesizes abstract inputs per signature (the same
                     ``jax.eval_shape`` replay the jaxpr auditor uses —
                     ``abstract_wave_io`` is shared with
                     ``analysis/registry``) and AOT-compiles every
                     bucket on background threads, packed signature
                     first, then wave buckets by simulated hit frequency
                     (``CompileCacheSim.freq``) — MaxText's bucketed
                     executable-cache warmup idiom;
  planner pre-warm   ``prewarm(step=...)`` compiles a built
                     PlannedStep's *exact* executables from the plan's
                     real shapes; ``train/planner.plans(...,
                     warmup=svc)`` calls it on the pipeline's build
                     threads, so upcoming signatures compile while the
                     current step trains and ``TreeTrainEngine``'s
                     executable lookup never blocks on a cold bucket;
  persistence        :func:`configure_compile_cache` wires jax's
                     persistent compilation cache so a restarted run
                     compiles ~nothing (the AOT ``lower().compile()``
                     becomes a disk hit).

Run ``python -m repro.train.warmup --persist-probe DIR`` twice to
measure the restart story: each run prints JSON with the number of NEW
cache files it wrote (second run: 0) and its first-step latency —
``benchmarks/run.py``'s ``compile_warmup`` row drives exactly that.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import threading
import time
from contextlib import nullcontext
from typing import Any, Hashable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp

import repro.sharding as sh
from repro.analysis.signatures import SignatureUniverse
from repro.configs.base import ModelConfig
from repro.core.gateway import (_cut_caps_view, _names_sig, _slice_gw_row,
                                _stack_gw_rows, assemble_child_gw)
from repro.core.plan_cost import (CUT_FLOOR, CompileCacheSim,
                                  packed_signature, pow2,
                                  round_to_multiple, wave_path_floor,
                                  wave_signature, wave_signature_of)
from repro.data.loader import LoaderConfig
from repro.models.model import has_router_loss, max_conv_taps, needs_chunks
from repro.train.engine import (NUM_SCALARS, _packed_exec_fn,
                                _wave_exec_fns)
from repro.train.exec_cache import ExecutableCache, abstractify, exec_key
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.train_step import jitted_update

logger = logging.getLogger(__name__)

_sds = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------

# the checkout root (src/repro/train/warmup.py → three levels up)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir(cache_dir: Optional[str] = None) -> str:
    """Where the persistent compilation cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (nothing overrides it), else
    ``cache_dir``, else ``.jax_cache`` at the checkout root.  The path is
    part of what a cache hit needs, so it is fixed — never temporary,
    per-process or time-stamped."""
    d = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
         or os.path.join(_CHECKOUT, ".jax_cache"))
    return os.path.abspath(os.path.expanduser(d))


def configure_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Point jax's persistent compilation cache at
    :func:`compile_cache_dir` (created if missing) and drop the
    min-compile-time / min-entry-size floors — the defaults skip exactly
    the small, fast CPU modules this repo's shape buckets produce, which
    would leave a restarted run recompiling everything.  Idempotent; call
    before the first compile.  Returns the directory."""
    cache_dir = compile_cache_dir(cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def compile_cache_files(cache_dir: str) -> int:
    """Number of cache entries on disk — the restart metric: a warm
    restart adds 0 new files."""
    n = 0
    for _, _, names in os.walk(cache_dir):
        n += len(names)
    return n


# ---------------------------------------------------------------------------
# Abstract input synthesis (per signature, no real plan needed)
# ---------------------------------------------------------------------------

def _abstract_params(params) -> Any:
    """Params (concrete or abstract) → ShapeDtypeStructs carrying each
    leaf's sharding when present, so AOT lowering sees exactly the
    layouts the engine will dispatch with."""
    def one(leaf):
        shd = getattr(leaf, "sharding", None)
        return _sds(leaf.shape, leaf.dtype, sharding=shd)
    return jax.tree.map(one, params)


def abstract_packed_batch(cfg: ModelConfig, rows: int, seq_len: int
                          ) -> dict:
    """The abstract ``prepare_batch`` output for a [rows, seq_len] packed
    microbatch — field-for-field what ``PlannedStep.step_batch``
    materializes (``num_trees`` stays a python int: jit traces it as a
    weak scalar, so one executable serves every tree count)."""
    i32, f32 = jnp.int32, jnp.float32
    b: dict[str, Any] = {
        "tokens": _sds((rows, seq_len), i32),
        "pos_ids": _sds((rows, seq_len), i32),
        "kv_last": _sds((rows, seq_len), i32),
        "weight": _sds((rows, seq_len), f32),
        "prev_idx": _sds((rows, seq_len), i32),
        "valid": _sds((rows, seq_len), jnp.bool_),
        "num_trees": 1,
    }
    if has_router_loss(cfg):
        b["aux_weight"] = _sds((rows, seq_len), f32)
    if needs_chunks(cfg):
        chunk = cfg.ssm.chunk_size
        k = max(1, max_conv_taps(cfg))
        b["chunk_parent"] = _sds((rows, seq_len // chunk), i32)
        b["prev_pows"] = _sds((rows, seq_len, k), i32)
    if cfg.frontend is not None:
        # the planner materializes float32 frontend embeds (train/planner
        # PlannedStep.step_batch), not the bf16 stub path
        b["extra_embeds"] = _sds((rows, cfg.frontend_len, cfg.d_model),
                                 f32)
    return b


def _abstract_wave_batch(cfg: ModelConfig, rows: int, seq_len: int,
                         anc: int, n_extra: int) -> dict:
    """Abstract WavePlan batch columns for one wave bucket — mirrors
    ``core/gateway.build_partition_plan``'s batch construction."""
    i32, f32 = jnp.int32, jnp.float32
    b: dict[str, Any] = {
        "tokens": _sds((rows, seq_len), i32),
        "pos_ids": _sds((rows, seq_len), i32),
        "kv_last": _sds((rows, seq_len), i32),
        "weight": _sds((rows, seq_len), f32),
        "prev_idx": _sds((rows, seq_len), i32),
        "valid": _sds((rows, seq_len), jnp.bool_),
    }
    if has_router_loss(cfg):
        b["aux_weight"] = _sds((rows, seq_len), f32)
    if needs_chunks(cfg):
        chunk = cfg.ssm.chunk_size
        taps = max(1, max_conv_taps(cfg))
        b["chunk_parent"] = _sds((rows, seq_len // chunk), i32)
        b["prev_pows"] = _sds((rows, seq_len, taps), i32)
    if n_extra:
        b["extra_pos"] = _sds((rows, n_extra), i32)
        b["extra_label"] = _sds((rows, n_extra), i32)
        b["extra_weight"] = _sds((rows, n_extra), f32)
    if anc:
        b["anc_pos"] = _sds((rows, anc), i32)
        b["anc_valid"] = _sds((rows, anc), jnp.bool_)
    return b


def _abstract_capspecs(cfg: ModelConfig, ncut: int, plen: int) -> dict:
    """Abstract bucketed capture plans (``gateway._wave_capspecs``)."""
    i32 = jnp.int32
    taps = max(1, max_conv_taps(cfg))
    return {f"c{i}": {"path_idx": _sds((plen,), i32),
                      "cut_chunk": _sds((), i32),
                      "conv_pos": _sds((min(taps, plen),), i32),
                      "shift_pos": _sds((1,), i32)}
            for i in range(ncut)}


def abstract_wave_io(cfg: ModelConfig, partition, params_a, *,
                     impl: str = "ref", donate: bool = True):
    """Replay ``run_partition_plan``'s forward sweep entirely under
    ``jax.eval_shape`` over a REAL :class:`~repro.core.gateway
    .PartitionPlan` — each wave's gateway assembled abstractly from its
    parent's abstract captures, exactly like the runtime executor.

    Yields one dict per wave: ``{w, wp, fwd, bwd, fwd_args, bwd_args}``
    where the arg tuples are the abstract avals of the engine's actual
    dispatch (so AOT-compiling on them produces executables the engine's
    fingerprinted lookup hits).  Shared by the jaxpr auditor
    (``analysis/registry._wave_targets``) and the warmup service's
    pre-warm path — one replay, two consumers."""
    scal_a = _sds((NUM_SCALARS,), jnp.float32)
    scale_a = _sds((), jnp.float32)
    acc_a = jax.tree.map(lambda l: _sds(l.shape, jnp.float32), params_a)
    st: list[dict] = []
    for w, wp in enumerate(partition.waves):
        batch_a = abstractify(wp.batch)
        caps_a = abstractify(wp.capspecs)
        gw_a = None
        if wp.has_gw:
            def mk_gw(prev, _wp=wp, _ba=batch_a):
                rows_gw = []
                for ref in _wp.parents:
                    stp = prev[ref.wave]
                    pwp = partition.waves[ref.wave]
                    cname = f"c{ref.cut}"
                    p_gw_row = (None if stp["gw"] is None else
                                _slice_gw_row(stp["gw"], ref.row,
                                              pwp.A_real[ref.row]))
                    caps_view = _cut_caps_view(cfg, stp["caps"], cname,
                                               ref.row, ref.path_len)
                    rows_gw.append(
                        assemble_child_gw(cfg, p_gw_row, caps_view,
                                          cname))
                return _stack_gw_rows(rows_gw, _wp.anc_A_max,
                                      _ba["tokens"].shape[0],
                                      rows_idx=_wp.slot_rows)
            gw_a = jax.eval_shape(mk_gw, st)
        fwd, bwd = _wave_exec_fns(cfg, _names_sig(wp.capspecs), impl,
                                  wp.has_gw, donate)
        caps_out, _ = jax.eval_shape(fwd, params_a, batch_a, gw_a,
                                     caps_a, scal_a, scale_a)
        yield dict(w=w, wp=wp, fwd=fwd, bwd=bwd, caps_out=caps_out,
                   fwd_args=(params_a, batch_a, gw_a, caps_a, scal_a,
                             scale_a),
                   bwd_args=(params_a, batch_a, gw_a, caps_a,
                             (scale_a, caps_out), acc_a))
        st.append(dict(caps=caps_out, gw=gw_a))


def abstract_wave_exec(cfg: ModelConfig, sig: tuple, params_a, *,
                       impl: str = "ref", donate: bool = True) -> dict:
    """Synthesize one wave bucket's (fwd, bwd) executables and abstract
    args straight from its signature — no real plan.

    The gateway avals are derived the honest way: a minimal abstract
    *parent* wave (1 row, 1 cut) is forwarded under ``jax.eval_shape``
    for its capture structure, then one child row is cut out, assembled
    and stacked through the very gateway helpers the runtime executor
    uses (``_cut_caps_view`` → ``assemble_child_gw`` →
    ``_stack_gw_rows``), front-padded to the bucket's ancestor length.
    Fidelity is measured, not assumed: the retrace-count benchmarks
    assert the engine's fingerprinted lookup hits these executables on a
    real in-universe stream."""
    _, rows, S, anc, ncut, plen, n_extra = sig
    scal_a = _sds((NUM_SCALARS,), jnp.float32)
    scale_a = _sds((), jnp.float32)
    acc_a = jax.tree.map(lambda l: _sds(l.shape, jnp.float32), params_a)
    batch_a = _abstract_wave_batch(cfg, rows, S, anc, n_extra)
    caps_a = _abstract_capspecs(cfg, ncut, plen)
    has_gw = anc > 0
    gw_a = None
    if has_gw:
        taps = max(1, max_conv_taps(cfg))
        plen_p = pow2(taps)
        parent_batch = _abstract_wave_batch(cfg, 1, S, 0, 1)
        parent_caps = _abstract_capspecs(cfg, 1, plen_p)
        pfwd, _ = _wave_exec_fns(cfg, ("c0",), impl, False, donate)
        pcaps_out, _ = jax.eval_shape(pfwd, params_a, parent_batch, None,
                                      parent_caps, scal_a, scale_a)

        def mk_gw(caps):
            # true_len = taps ≤ anc (anc buckets start at 8): the conv
            # tail lands at its full tap count — the wave max on every
            # real gateway wave — while attention ancestors front-pad to
            # the bucket anyway, so the stacked avals match runtime
            view = _cut_caps_view(cfg, caps, "c0", 0, taps)
            row = assemble_child_gw(cfg, None, view, "c0")
            return _stack_gw_rows([row], anc, rows, rows_idx=[0])
        gw_a = jax.eval_shape(mk_gw, pcaps_out)
    names = tuple(sorted(f"c{i}" for i in range(ncut)))
    fwd, bwd = _wave_exec_fns(cfg, names, impl, has_gw, donate)
    caps_out, _ = jax.eval_shape(fwd, params_a, batch_a, gw_a, caps_a,
                                 scal_a, scale_a)
    return dict(fwd=fwd, bwd=bwd,
                fwd_args=(params_a, batch_a, gw_a, caps_a, scal_a,
                          scale_a),
                bwd_args=(params_a, batch_a, gw_a, caps_a,
                          (scale_a, caps_out), acc_a))


# ---------------------------------------------------------------------------
# Universe enumeration (independent of SignatureUniverse.enumerate_signatures
# — treelint cross-checks the two lists for equality)
# ---------------------------------------------------------------------------

DEFAULT_CAPS = (64, 8, 64, 8)     # (anc, ncut, plen, extra) fallbacks


def universe_signatures(lc: LoaderConfig, pc, caps: Sequence[int]
                        ) -> list[Hashable]:
    """Every live signature the planner can emit under (lc, pc), bounded
    by per-field ``caps = (anc, ncut, plen, extra)``.  Deliberately a
    second, independent implementation of
    ``SignatureUniverse.enumerate_signatures`` — the treelint warmup
    pass asserts the two agree, so neither can silently drift from what
    the engine actually keys."""
    S = lc.seq_len
    R = max(getattr(pc, "num_replicas", 1), 1)
    max_rows = pc.max_rows if pc.max_rows is not None else lc.batch_rows
    anc_cap, ncut_cap, plen_cap, extra_cap = caps
    plen_cap = min(plen_cap, pow2(lc.capacity or S))
    anc_lo = max(8, wave_path_floor(S))
    plen_lo = min(wave_path_floor(S), pow2(lc.capacity or S))
    sigs: list[Hashable] = [
        packed_signature(round_to_multiple(lc.batch_rows, R), S)]

    def pow2s(lo, cap):
        b = lo
        while b <= cap:
            yield b
            b *= 2

    for rows in pow2s(R, R * pow2(-(-max_rows // R))):
        # leaf waves: gateway in, no cuts → no capture paths, no extras
        for anc in pow2s(anc_lo, anc_cap):
            sigs.append(wave_signature(rows, S, anc, 0, 0, 0))
        for ncut in pow2s(CUT_FLOOR, ncut_cap):
            for plen in pow2s(plen_lo, plen_cap):
                for n_extra in pow2s(CUT_FLOOR, min(extra_cap, ncut)):
                    # root waves (anc=0) always cut: one-fragment trees
                    # take inert cuts
                    sigs.append(wave_signature(rows, S, 0, ncut, plen,
                                               n_extra))
                    for anc in pow2s(anc_lo, anc_cap):
                        sigs.append(wave_signature(rows, S, anc, ncut,
                                                   plen, n_extra))
    return sigs


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------

class AOTWarmupService:
    """Fills an :class:`ExecutableCache` ahead of the engine.

    Two producers:

      ``start()``/``warm_all()``  enumerate the signature universe and
          AOT-compile every bucket (packed first, then waves by
          ``CompileCacheSim.freq`` hit frequency, small buckets first on
          ties), on background threads or synchronously;
      ``prewarm(step=...)``       compile one built PlannedStep's exact
          executables — the planner pipeline calls this from its build
          workers the moment a window's plans exist, so upcoming
          signatures compile while the current step trains.

    Construct it with the same ``params``/``opt_cfg``/``impl``/
    ``donate`` the engine runs with (params may be concrete or abstract;
    shardings are carried into the lowering when present), then hand
    ``service.cache`` and ``service.universe`` to
    :class:`~repro.train.engine.TreeTrainEngine`."""

    def __init__(self, cfg: ModelConfig, lc: LoaderConfig, pc=None, *,
                 params, opt_cfg: Optional[OptimizerConfig] = None,
                 opt_state=None, cache: Optional[ExecutableCache] = None,
                 impl: str = "ref", donate: bool = True,
                 universe: Optional[SignatureUniverse] = None,
                 caps: Optional[Sequence[int]] = None,
                 sim: Optional[CompileCacheSim] = None,
                 max_compiles: Optional[int] = None):
        if pc is None:
            from repro.train.planner import PlannerConfig
            pc = PlannerConfig()
        self.cfg, self.lc, self.pc = cfg, lc, pc
        self.impl, self.donate = impl, donate
        self.cache = cache if cache is not None else ExecutableCache()
        self.sim = sim
        self.caps = tuple(caps) if caps is not None else DEFAULT_CAPS
        self.max_compiles = max_compiles
        self.universe = universe or SignatureUniverse(
            seq_len=lc.seq_len, batch_rows=lc.batch_rows,
            num_replicas=pc.num_replicas,
            max_rows=(pc.max_rows if pc.max_rows is not None
                      else lc.batch_rows),
            capacity=lc.capacity or lc.seq_len)
        self.params_a = _abstract_params(params)
        self.opt_cfg = opt_cfg
        self.opt_a = (abstractify(opt_state) if opt_state is not None
                      else (jax.eval_shape(init_opt_state, self.params_a)
                            if opt_cfg is not None else None))
        self.acc_a = jax.tree.map(
            lambda l: _sds(l.shape, jnp.float32), self.params_a)
        self.scal_a = _sds((NUM_SCALARS,), jnp.float32)
        # jax's mesh context is thread-local: capture the active mesh so
        # background compiles lower under the same layouts as dispatch
        self._mesh_args = None
        if sh.current_mesh() is not None:
            ctx = sh._CTX
            self._mesh_args = (ctx.mesh, ctx.data_axes, ctx.model_axis,
                               ctx.seq_parallel)
        self.errors: list[str] = []
        self.prewarmed = 0
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()

    # -- enumeration -------------------------------------------------------
    def signature_list(self) -> list[Hashable]:
        """The warmup compile list: the live universe under ``caps``,
        packed signature first, then wave buckets by descending
        simulated hit frequency (``sim.freq``), smallest bucket first on
        ties (small modules compile fastest — more of the universe is
        warm sooner)."""
        sigs = universe_signatures(self.lc, self.pc, self.caps)
        freq = self.sim.freq if self.sim is not None else {}

        def order(s):
            if s[0] == "packed":
                return (0, 0, ())
            return (1, -freq.get(s, 0), s[1:])
        return sorted(sigs, key=order)

    # -- per-signature compile --------------------------------------------
    def _mesh_scope(self):
        if self._mesh_args is None:
            return nullcontext()
        mesh, daxes, maxis, sp = self._mesh_args
        return sh.use_mesh(mesh, data_axes=daxes, model_axis=maxis,
                           seq_parallel=sp)

    def _variants_for(self, sig: Hashable) -> list[tuple]:
        """(variant, fn, abstract args) triples one signature compiles
        to — the exact keys ``TreeTrainEngine`` resolves."""
        if sig == ("update",):
            if self.opt_cfg is None:
                return []
            return [("update", jitted_update(self.opt_cfg, self.donate),
                     (self.params_a, self.acc_a, self.opt_a))]
        if sig[0] == "packed":
            _, rows, S = sig
            batch_a = abstract_packed_batch(self.cfg, rows, S)
            return [("packed",
                     _packed_exec_fn(self.cfg, self.impl, self.donate,
                                     with_acc=False),
                     (self.params_a, batch_a, self.scal_a))]
        io = abstract_wave_exec(self.cfg, sig, self.params_a,
                                impl=self.impl, donate=self.donate)
        return [("wave.fwd", io["fwd"], io["fwd_args"]),
                ("wave.bwd", io["bwd"], io["bwd_args"])]

    def warm_signature(self, sig: Hashable) -> int:
        """AOT-compile every executable variant of one signature into
        the cache; returns how many were new.  A synthesis or compile
        failure is recorded (and logged) but never raises — the engine's
        synchronous slow path stays the correctness backstop."""
        new = 0
        try:
            variants = self._variants_for(sig)
        except Exception as e:          # pragma: no cover - defensive
            self.errors.append(f"{sig}: synthesis failed: {e}")
            logger.warning("warmup synthesis failed for %s: %s", sig, e)
            return 0
        for variant, fn, args in variants:
            if self._stop.is_set():
                break
            try:
                with self._mesh_scope():
                    _, was_new = self.cache.compile_once(
                        exec_key(variant, sig, args), fn, args)
                new += was_new
            except Exception as e:      # pragma: no cover - defensive
                self.errors.append(f"{sig}/{variant}: {e}")
                logger.warning("warmup compile failed for %s/%s: %s",
                               sig, variant, e)
        return new

    # -- startup warmup ----------------------------------------------------
    def _budgeted(self, sigs: Iterable[Hashable]) -> Iterable[Hashable]:
        out = list(sigs)
        if self.max_compiles is not None:
            # 2 executables per signature (fwd+bwd / packed pair)
            keep = max(self.max_compiles // 2, 1)
            if len(out) > keep:
                logger.info(
                    "warmup budget: compiling %d of %d universe "
                    "signatures (hottest first)", keep, len(out))
                out = out[:keep]
        return out

    def warm_all(self) -> int:
        """Synchronously compile the update + the whole (budgeted)
        universe; returns the number of new executables."""
        new = self.warm_signature(("update",))
        for sig in self._budgeted(self.signature_list()):
            new += self.warm_signature(sig)
        return new

    def start(self, threads: int = 1) -> "AOTWarmupService":
        """Background startup warmup: the universe list is compiled on
        ``threads`` daemon workers in priority order.  Returns self."""
        work = list(self._budgeted(self.signature_list()))
        work.insert(0, ("update",))
        it = iter(work)
        lock = threading.Lock()

        def run():
            while not self._stop.is_set():
                with lock:
                    sig = next(it, None)
                if sig is None:
                    break
                self.warm_signature(sig)

        self._threads = [threading.Thread(target=run, daemon=True,
                                          name=f"aot-warmup-{i}")
                         for i in range(max(1, threads))]
        for t in self._threads:
            t.start()
        return self

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Join the background workers; True when all finished."""
        deadline = None if timeout is None else time.monotonic() + timeout
        for t in self._threads:
            t.join(None if deadline is None
                   else max(deadline - time.monotonic(), 0))
        return not any(t.is_alive() for t in self._threads)

    def stop(self) -> None:
        self._stop.set()

    # -- planner pre-warm --------------------------------------------------
    def prewarm(self, signatures: Optional[Iterable[Hashable]] = None,
                step=None) -> int:
        """Compile upcoming work before the engine consumes it; returns
        the number of new executables.

        ``step``: a built PlannedStep — its packed batch, every wave and
        the optimizer update compile from the plan's EXACT shapes (the
        ``abstract_wave_io`` replay), so the engine's fingerprinted
        lookup is guaranteed to hit.  ``signatures``: bare signatures,
        synthesized like the startup universe.  The planner pipeline
        calls this on its build threads (``plans(..., warmup=svc)``) —
        compile overlaps the current step's device work."""
        new = self.warm_signature(("update",))
        for sig in (signatures or ()):
            new += self.warm_signature(sig)
        if step is not None:
            plan = step.execution_plan()
            sigs: list[Hashable] = []
            if plan.packed is not None:
                # inputs already carry the python-int num_trees leaf
                batch_a = abstractify(dict(plan.packed.inputs))
                B, S = plan.packed.inputs["tokens"].shape
                sig = packed_signature(B, S)
                sigs.append(sig)
                variants = [("packed",
                             _packed_exec_fn(self.cfg, self.impl,
                                             self.donate, with_acc=False),
                             (self.params_a, batch_a, self.scal_a))]
                for variant, fn, args in variants:
                    try:
                        with self._mesh_scope():
                            _, was_new = self.cache.compile_once(
                                exec_key(variant, sig, args), fn, args)
                        new += was_new
                    except Exception as e:   # pragma: no cover
                        self.errors.append(f"{sig}/{variant}: {e}")
                        logger.warning("prewarm failed for %s/%s: %s",
                                       sig, variant, e)
            if plan.partition is not None and plan.partition.waves:
                seq_len = step.lc.seq_len
                try:
                    with self._mesh_scope():
                        for io in abstract_wave_io(
                                self.cfg, plan.partition, self.params_a,
                                impl=self.impl, donate=self.donate):
                            sig = wave_signature_of(io["wp"], seq_len)
                            sigs.append(sig)
                            for variant, fn, args in (
                                    ("wave.fwd", io["fwd"],
                                     io["fwd_args"]),
                                    ("wave.bwd", io["bwd"],
                                     io["bwd_args"])):
                                _, was_new = self.cache.compile_once(
                                    exec_key(variant, sig, args), fn,
                                    args)
                                new += was_new
                except Exception as e:       # pragma: no cover
                    self.errors.append(f"prewarm waves: {e}")
                    logger.warning("prewarm wave replay failed: %s", e)
            if self.sim is not None:
                self.sim.commit(sigs)
        self.prewarmed += new
        return new

    def raise_errors(self, who: str) -> None:
        """A compile this service could not do is a failed run, not a log
        line: exit non-zero naming the first failure."""
        if self.errors:
            raise SystemExit(f"[{who}] {len(self.errors)} warmup compile "
                             f"errors; first: {self.errors[0]}")

    def stats(self) -> dict:
        s = self.cache.stats()
        s.update(prewarmed=self.prewarmed, errors=len(self.errors))
        return s


# ---------------------------------------------------------------------------
# Persist probe (the restart story, measured from a fresh process)
# ---------------------------------------------------------------------------

def _probe_config() -> ModelConfig:
    from repro.configs.base import AttnCfg
    return ModelConfig(
        name="warmup-probe", family="dense", n_layers=2, d_model=32,
        d_ff=128, vocab_size=256,
        attn=AttnCfg(n_heads=4, n_kv_heads=2, head_dim=8, qk_norm=True),
        dtype="float32", vocab_pad_multiple=64)


def _persist_probe(cache_dir: str) -> dict:
    """One fresh-process probe: configure the persistent cache, pre-warm
    a tiny real plan stream (packed rows + partition waves), run one
    engine step, and report how many NEW cache files this process wrote.
    Run twice with the same dir: run 1 fills the disk cache, run 2 must
    report ``new_cache_files == 0`` (the warm-restart claim) and a much
    faster warmup."""
    from repro.models.transformer import init_params
    from repro.train.engine import TreeTrainEngine
    from repro.train.planner import PlannerConfig, plan_stream

    cache_dir = configure_compile_cache(cache_dir)
    files0 = compile_cache_files(cache_dir)
    t_start = time.perf_counter()

    cfg = _probe_config()
    lc = LoaderConfig(seq_len=64, batch_rows=2, trees_per_batch=2,
                      auto_partition=True, capacity=32, seed=5,
                      gen_kwargs=dict(num_turns=2,
                                      turn_len_range=(8, 20)))
    pc = PlannerConfig()
    steps = [ps for ps in plan_stream(cfg, lc, 1, pc)]
    params = init_params(cfg, jax.random.key(0))
    opt_cfg = OptimizerConfig()
    opt_state = init_opt_state(params)

    svc = AOTWarmupService(cfg, lc, pc, params=params, opt_cfg=opt_cfg,
                           opt_state=opt_state)
    t0 = time.perf_counter()
    for ps in steps:
        svc.prewarm(step=ps)
    warm_s = time.perf_counter() - t0

    eng = TreeTrainEngine(cfg, opt_cfg, exec_cache=svc.cache,
                          universe=svc.universe)
    t0 = time.perf_counter()
    params, opt_state, metrics = eng.step(params, opt_state,
                                          steps[0].execution_plan())
    step1_ms = (time.perf_counter() - t0) * 1e3

    return dict(cache_dir=cache_dir,
                new_cache_files=compile_cache_files(cache_dir) - files0,
                aot_executables=len(svc.cache),
                compile_s=round(svc.cache.compile_s, 3),
                prewarm_s=round(warm_s, 3),
                retraces=eng.retraces,
                step1_ms=round(step1_ms, 2),
                loss=float(metrics["loss"]),
                wall_s=round(time.perf_counter() - t_start, 3))


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="AOT warmup utilities (persist-probe mode)")
    ap.add_argument("--persist-probe", metavar="CACHE_DIR",
                    help="fill/verify the persistent compile cache from "
                         "a fresh process and print JSON stats")
    args = ap.parse_args(argv)
    if args.persist_probe:
        print(json.dumps(_persist_probe(args.persist_probe)))
        return 0
    ap.print_help()
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
