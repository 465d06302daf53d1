"""shardlint — treelint passes 4–5: abstract-mesh SPMD & collective audit.

Pass 4 (comms audit): lower the registered entrypoints under the
production mesh descriptors (``launch/mesh``) with zero real accelerators
(``--xla_force_host_platform_device_count`` fakes), walk the post-SPMD
HLO for collectives (``analysis/hlo_comms``), attribute per-axis/per-
dtype wire bytes, and check each entrypoint's declared
:class:`~repro.analysis.registry.CommContract`:

  * ``engine.packed+acc`` — exactly one fp32 grad psum over the data
    axes (reduced element count == grad element count), zero forward
    all-gathers materializing a parameter, and with ``seq_parallel=True``
    the block-boundary forward reduction lowers as a true reduce-scatter
    with strictly fewer forward wire bytes than the all-reduce baseline
    (total fwd+bwd boundary bytes are conserved — see ``hlo_comms``'s
    byte model — so the gate is the forward edge, which is exactly what
    the ``sharding.use_mesh`` docstring claims);
  * ``session.step`` — zero data-axis collectives: decode replicas own
    disjoint cache rows.

Pass 5 (sharding-propagation lint): every ≥2-D param must match a
``sharding._RULES`` entry and must not silently lower fully replicated
when a dim divides the model axis; ``shard_activation`` annotations must
survive into the lowered StableHLO (``sdy.sharding_constraint`` ops with
the expected tile factors); every requested non-replicated param sharding
must appear in the lowering.  Coverage is closed: every registered
entrypoint needs a ``CommContract`` or a ``COMM_ALLOWED`` reason.

Pass 6 (``analysis/lock_lint``) rides along under ``lint --comms``.

The boundary attribution trick: ``sharding.tp_out_proj`` owns a known
source-line range; collectives whose HLO metadata points into that range
are the block-boundary reduction, and backward ops are split off by the
``transpose(...)`` marker in ``op_name`` (the VJP inherits the forward's
source line).

Run as ``python -m repro.analysis.lint --comms [--fast]``, or
``python -m repro.analysis.comms_audit --sweep`` for the per-family
lowering sweep (the "can't run on one host" configs become statically
verified).
"""
from __future__ import annotations

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count"
                                 "=512")
# GSPMD's advisory "involuntary full rematerialization" messages log at
# ERROR level and flood the audit output; nothing here executes anyway
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import inspect       # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
from typing import Optional  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import sharding as sh                  # noqa: E402
from repro.analysis import hlo_comms              # noqa: E402
from repro.analysis.jaxpr_audit import Finding    # noqa: E402
from repro.analysis.registry import (_forest,     # noqa: E402
                                     audit_loader_config, build_targets,
                                     comm_coverage_findings,
                                     params_abstract)
from repro.configs import (ARCH_IDS,              # noqa: E402
                           SHARDLINT_SWEEP_ARCHS, get_config)
from repro.launch.mesh import (MeshDescriptor,    # noqa: E402
                               host_descriptor, production_descriptor)
from repro.models.model import needs_chunks       # noqa: E402

SDS = jax.ShapeDtypeStruct

# the dense config the acceptance invariants are proven on
DENSE_ARCH = "qwen1p5_0p5b"

# elements below this are metric scalars (loss/nll/weight sums), not
# grads; also the slack the grad-psum equality tolerates for them when
# XLA's all-reduce combiner folds scalars into a grad tuple-reduce
SCALAR_SLACK = 64


# ---------------------------------------------------------------------------
# Pure contract checks — unit-testable on synthetic collective tables.
# Each collective dict needs: op, dtype, elems, bytes, wire_bytes, axes,
# op_name (and optionally elems_eff / wire_eff with loop multipliers).
# ---------------------------------------------------------------------------

def _eff(c: dict, key: str, base: str) -> int:
    return c.get(key, c[base])


def check_grad_psum(colls: list[dict], data_axes: tuple[str, ...],
                    grad_elems: int,
                    grad_min: Optional[int] = None) -> list[str]:
    """Exactly-once fp32 grad reduction over the data axes.

    ``grad_elems`` is the per-device ledger (Σ local shard elements);
    ``grad_min`` relaxes the lower bound for replicated params, whose
    reduction XLA may legally re-associate across mesh axes (model-axis
    AR of partials + data-axis AR of a 1/msize slice — same sum, fewer
    data-axis elements).  Sharded params have no such freedom: their
    data-axis psum must appear in full."""
    da = set(data_axes)
    grad_min = grad_elems if grad_min is None else grad_min
    total = 0
    msgs: list[str] = []
    for c in colls:
        if c["op"] != "all-reduce" or not da.issubset(set(c["axes"])):
            continue
        if c["dtype"] == "f32":
            total += _eff(c, "elems_eff", "elems")
        elif _eff(c, "elems_eff", "elems") > SCALAR_SLACK:
            msgs.append(
                f"non-fp32 ({c['dtype']}) data-axis all-reduce of "
                f"{c['elems']} elements — the grad psum must run in fp32 "
                f"(dtype policy)")
    if total < grad_min:
        msgs.append(
            f"grad psum missing or short: {total} fp32 elements "
            f"all-reduced over the data axes, expected at least "
            f"{grad_min} (each grad shard reduced exactly once)")
    elif total > grad_elems + SCALAR_SLACK:
        msgs.append(
            f"grad over-reduction: {total} fp32 elements all-reduced "
            f"over the data axes vs {grad_elems} grad shard elements "
            f"(+{SCALAR_SLACK} scalar slack) — something reduces twice, "
            f"silently scaling the effective LR")
    return msgs


def check_no_param_allgather(colls: list[dict],
                             param_elems: set[int]) -> list[str]:
    """No forward all-gather materializes a full parameter."""
    msgs = []
    for c in colls:
        if (c["op"] == "all-gather" and hlo_comms.is_forward(c)
                and c["elems"] in param_elems):
            msgs.append(
                f"forward all-gather of {c['elems']} elements matches a "
                f"parameter's full size (axes {c['axes']}, "
                f"op_name '{c['op_name'][:80]}') — params must stay "
                f"resident on the packed forward, not be re-gathered per "
                f"microbatch")
    return msgs


def check_zero_data_axis(colls: list[dict],
                         data_axes: tuple[str, ...]) -> list[str]:
    """Decode-style entrypoints: no collective may span a data axis."""
    msgs = []
    for c in colls:
        hit = set(c["axes"]) & set(data_axes)
        if hit:
            msgs.append(
                f"{c['op']} of {c['elems']} elements spans data "
                f"ax{'es' if len(hit) > 1 else 'is'} {sorted(hit)} — "
                f"decode replicas own disjoint rows; this serializes "
                f"every serving step")
    return msgs


def check_seq_parallel_boundary(base_fwd: list[dict],
                                sp_fwd: list[dict]) -> list[str]:
    """SP must replace the boundary forward all-reduce with a true
    reduce-scatter and strictly shrink forward boundary wire bytes."""
    msgs = []
    base_wire = sum(_eff(c, "wire_eff", "wire_bytes") for c in base_fwd)
    sp_wire = sum(_eff(c, "wire_eff", "wire_bytes") for c in sp_fwd)
    if not any(c["op"] == "all-reduce" for c in base_fwd):
        msgs.append(
            "baseline boundary has no forward all-reduce — source-line "
            "attribution to sharding.tp_out_proj is broken (the check "
            "would be vacuous)")
    if not any(c["op"] == "reduce-scatter" for c in sp_fwd):
        msgs.append(
            "seq_parallel=True boundary carries no true reduce-scatter — "
            "GSPMD fell back to all-reduce + slice (the docstring claim "
            "does not hold)")
    if any(c["op"] == "all-reduce" for c in sp_fwd):
        msgs.append(
            "seq_parallel=True still all-reduces at the block boundary "
            "in the forward pass")
    if sp_wire >= base_wire:
        msgs.append(
            f"seq_parallel forward boundary wire bytes did not drop: "
            f"{sp_wire} (SP) >= {base_wire} (baseline)")
    return msgs


def boundary_collectives(colls: list[dict]) -> list[dict]:
    """Collectives attributed to ``sharding.tp_out_proj``'s source lines
    — the block-boundary TP reduction (fwd + bwd)."""
    lines, start = inspect.getsourcelines(sh.tp_out_proj)
    rng = range(start, start + len(lines))
    return [c for c in colls
            if c["source_file"].endswith("repro/sharding.py")
            and c["source_line"] in rng]


# ---------------------------------------------------------------------------
# Pass 5a — host-side rule lint (zero devices needed)
# ---------------------------------------------------------------------------

def rule_lint(cfg, msize: int = 16, rules=None) -> list[str]:
    """Every ≥2-D param matches a ``_RULES`` entry, and a matched rule's
    target dim may not fall back to replication when it IS divisible by
    the model axis (the silent-fallback bug class; the documented
    fallback is only for genuinely indivisible dims).  The target dims
    are found by probing the rule with an all-divisible shape.  Runs on
    the FULL config — this is where the 1T/340B layouts get verified
    without any devices."""
    rules = sh._RULES if rules is None else rules
    params = params_abstract(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    msgs: list[str] = []
    for path, leaf in flat:
        ps = sh._path_str(path)
        n_stack = 1 if ("layer_stacks" in ps or ps.startswith("encoder")) \
            else 0
        base = leaf.shape[n_stack:]
        rule = next(((pat, fn) for pat, fn in rules
                     if re.search(pat, ps)), None)
        if rule is None:
            if len(base) >= 2:
                msgs.append(
                    f"{cfg.name}: param {ps} {tuple(leaf.shape)} matches "
                    f"no sharding._RULES entry — add a rule (or it "
                    f"silently replicates onto every device)")
            continue
        pat, fn = rule
        actual = list(fn(base, msize))
        probe = list(fn(tuple(max(d, 1) * msize for d in base), msize))
        for i, want in enumerate(probe):
            if (want == "M" and i < len(actual) and actual[i] is None
                    and base[i] % msize == 0):
                msgs.append(
                    f"{cfg.name}: param {ps} {tuple(leaf.shape)} dim {i} "
                    f"({base[i]}) divides the {msize}-way model axis but "
                    f"rule '{pat}' left it replicated — silent "
                    f"replicated fallback")
    return msgs


# ---------------------------------------------------------------------------
# Pass 5b — annotation survival in the lowered StableHLO
# ---------------------------------------------------------------------------

# Shardy spells a sharding ``<@mesh, [{"data"}, {}, {"model"}]>``: one
# brace group of axis names per tensor dim
_SDY_SPEC_RE = re.compile(r"<@[\w]+, \[((?:\{[^}]*\}(?:, )?)*)\]")


def _sdy_tiles(spec: str, desc: MeshDescriptor) -> list[int]:
    """Per-dim tile counts of one Shardy dim list."""
    tiles = []
    for dim in re.findall(r"\{([^}]*)\}", spec):
        n = 1
        for axis in re.findall(r'"(\w+)"', dim):
            n *= desc.axis_size(axis)
        tiles.append(n)
    return tiles


def annotation_findings(stablehlo: str, desc: MeshDescriptor,
                        seq_parallel: bool,
                        n_sharded_params: int) -> list[str]:
    msgs = []
    tiled_dims = []
    n_got = 0
    for ln in stablehlo.splitlines():
        constraint = "sdy.sharding_constraint" in ln
        for spec in _SDY_SPEC_RE.findall(ln):
            tiles = _sdy_tiles(spec, desc)
            if not any(t > 1 for t in tiles):
                continue
            if constraint:
                tiled_dims.append(tiles)
            else:
                n_got += 1
    if not tiled_dims:
        msgs.append(
            "no tiled sharding constraint survived lowering — "
            "shard_activation/shard_logits silently no-opped (divisibility "
            "fallback?) and the whole activation path runs replicated")
    if seq_parallel:
        msize = desc.axis_size(desc.model_axis)
        if not any(len(d) >= 3 and d[1] == msize for d in tiled_dims):
            msgs.append(
                f"seq_parallel: no rank≥3 sharding constraint shards "
                f"dim 1 (sequence) {msize}-way over the model axis — the "
                f"S-sharded boundary activations fell back to replicated")
    if n_got < n_sharded_params:
        msgs.append(
            f"only {n_got} non-replicated sdy.sharding annotations in "
            f"the lowering but {n_sharded_params} params requested "
            f"non-replicated NamedShardings — propagation dropped some")
    return msgs


# ---------------------------------------------------------------------------
# Lowering drivers
# ---------------------------------------------------------------------------

def _with_shardings(tree, shard_tree, force_dtype=None):
    def one(leaf, s):
        dt = force_dtype or leaf.dtype
        return SDS(leaf.shape, dt, sharding=s)
    return jax.tree.map(one, tree, shard_tree)


def _attach_batch(batch, mesh, daxes):
    def one(leaf):
        if not hasattr(leaf, "shape"):
            return leaf                      # python ints trace as scalars
        s = sh.batch_shardings(leaf, mesh, daxes)
        return SDS(leaf.shape, leaf.dtype, sharding=s)
    return jax.tree.map(one, batch)


def demo_packed_plan(cfg, num_replicas: int):
    """A real host-side planner run sized to the mesh's data axis (rows
    must divide it or the batch replicates and every data-axis check goes
    vacuous).  Waves are not needed here — any packed plan does."""
    from repro.train.planner import PlannerConfig, plan_window
    lc = audit_loader_config(cfg)
    pc = PlannerConfig(lookahead=2, num_replicas=num_replicas)
    for seed in range(40):
        window = [_forest(1000 * seed + b, lc.trees_per_batch,
                          cfg.vocab_size) for b in range(pc.lookahead)]
        for ps in plan_window(cfg, lc, pc, window):
            if ps.is_empty:
                continue
            plan = ps.execution_plan()
            if plan.packed is not None:
                return plan
    raise RuntimeError(f"no packed demo plan for {cfg.name} at "
                       f"{num_replicas} replicas")


def _require_devices(desc: MeshDescriptor) -> None:
    if jax.device_count() < desc.device_count:
        raise RuntimeError(
            f"mesh {desc.name} needs {desc.device_count} (fake) devices "
            f"but jax sees {jax.device_count()} — run via "
            f"'python -m repro.analysis.lint --comms' which sets "
            f"--xla_force_host_platform_device_count before jax init")


def lower_packed(cfg, impl: str, desc: MeshDescriptor, *,
                 seq_parallel: bool, compile_: bool = True):
    """Lower (and optionally compile) the engine's packed train step under
    a mesh descriptor.  Returns (lowered, colls, aux); collectives carry
    axes + loop-multiplied ``elems_eff``/``wire_eff``.  The engine's jits
    are lru-cached and ``seq_parallel`` is read at trace time, so the jit
    caches are cleared first for a fresh trace per context."""
    from repro.train.engine import NUM_SCALARS, _packed_exec_fn
    _require_devices(desc)
    jax.clear_caches()
    mesh = desc.build()
    with sh.use_mesh(mesh, data_axes=desc.data_axes,
                     model_axis=desc.model_axis,
                     seq_parallel=seq_parallel):
        params_a = params_abstract(cfg)
        pshard = sh.param_shardings(params_a, mesh,
                                    model_axis=desc.model_axis)
        plan = demo_packed_plan(cfg, desc.data_axis_size)
        batch = dict(plan.packed.inputs)
        batch["num_trees"] = max(plan.num_trees, 1)
        args = (
            _with_shardings(params_a, pshard),
            _attach_batch(batch, mesh, desc.data_axes),
            _with_shardings(params_a, pshard, force_dtype=jnp.float32),
            SDS((NUM_SCALARS,), jnp.float32,
                sharding=NamedSharding(mesh, P())),
        )
        fn = _packed_exec_fn(cfg, impl, True, with_acc=True)
        lowered = fn.lower(*args)
        colls: list[dict] = []
        if compile_:
            hlo = lowered.compile().as_text()
            colls = hlo_comms.attach_axes(
                hlo_comms.parse_collectives(hlo), desc.shape,
                desc.axis_names)
    S = batch["tokens"].shape[1]
    mult = hlo_comms.loop_multiplier(cfg)
    chunks = S // cfg.ssm.chunk_size if needs_chunks(cfg) else 1
    for c in colls:
        m = hlo_comms._mult(c, mult, chunks)
        c["elems_eff"] = c["elems"] * m
        c["wire_eff"] = c["wire_bytes"] * m
    # post-SPMD AR results are per-device shards, so the exactly-once
    # grad-psum ledger counts each param's LOCAL shard elements; the
    # all-gather check matches FULL param sizes (an AG materializing a
    # param yields the whole tensor)
    grad_elems = 0
    grad_min = 0
    param_elems = set()
    msize = desc.axis_size(desc.model_axis)
    for leaf, ns in zip(jax.tree.leaves(params_a),
                        jax.tree.leaves(pshard)):
        n_local = 1
        for d in ns.shard_shape(leaf.shape):
            n_local *= d
        grad_elems += n_local
        # replicated params: XLA may re-associate the reduction over
        # (model, data), leaving only a 1/msize slice on the data axis
        grad_min += (n_local // msize if ns.is_fully_replicated
                     else n_local)
        n_full = 1
        for d in leaf.shape:
            n_full *= d
        if n_full >= 256:
            param_elems.add(n_full)
    n_sharded = sum(1 for s in jax.tree.leaves(pshard)
                    if not s.is_fully_replicated)
    aux = {"grad_elems": grad_elems, "grad_elems_min": grad_min,
           "param_elems": param_elems,
           "n_sharded_params": n_sharded, "loop_multiplier": mult,
           "rows": batch["tokens"].shape[0], "seq_len": S}
    return lowered, colls, aux


def lower_decode(cfg, desc: MeshDescriptor, *, buf_len: int = 64):
    """Lower + compile one ``DecodeSession.step`` with the cache batch
    sized to the data axis (the registry's K=4 would replicate on a
    16-way axis and make the zero-data-collectives check vacuous)."""
    from repro.serve.decode import _init_cache
    from repro.serve.session import _step_exec
    _require_devices(desc)
    jax.clear_caches()
    mesh = desc.build()
    B = desc.data_axis_size
    enc = cfg.encdec.src_len if cfg.encdec is not None else 0
    i32 = jnp.int32
    with sh.use_mesh(mesh, data_axes=desc.data_axes,
                     model_axis=desc.model_axis):
        params_a = params_abstract(cfg)
        pshard = sh.param_shardings(params_a, mesh,
                                    model_axis=desc.model_axis)
        cache_a = jax.eval_shape(lambda: _init_cache(cfg, B, buf_len, enc))
        cshard = sh.cache_shardings(cache_a, mesh, desc.data_axes,
                                    desc.model_axis)
        dspec = NamedSharding(mesh, P(desc.data_axes))
        args = (
            _with_shardings(params_a, pshard),
            _with_shardings(cache_a, cshard),
            SDS((B, 1), i32, sharding=NamedSharding(
                mesh, P(desc.data_axes, None))),
            SDS((B,), i32, sharding=dspec),
            SDS((), i32, sharding=NamedSharding(mesh, P())),
        )
        hlo = _step_exec(cfg, True).lower(*args).compile().as_text()
    return hlo_comms.attach_axes(hlo_comms.parse_collectives(hlo),
                                 desc.shape, desc.axis_names)


# ---------------------------------------------------------------------------
# The lint entrypoints
# ---------------------------------------------------------------------------

def _f(target: str, check: str, msgs: list[str]) -> list[Finding]:
    return [Finding(target, check, m) for m in msgs]


def audit_mesh(cfg, impl: str, desc: MeshDescriptor, *,
               check_sp: bool, verbose: bool = True
               ) -> tuple[list[Finding], dict]:
    """Pass 4 + 5b for one mesh descriptor on one config."""
    def say(msg):
        if verbose:
            print(f"[shardlint] {msg}", flush=True)

    findings: list[Finding] = []
    rep: dict = {"mesh": desc.name, "shape": list(desc.shape),
                 "data_axes": list(desc.data_axes),
                 "dci_axes": list(desc.dci_axes)}
    tag = f"{cfg.name}@{desc.name}"

    t0 = time.perf_counter()
    lowered, colls, aux = lower_packed(cfg, impl, desc,
                                       seq_parallel=False)
    rep["engine.packed"] = {
        "collectives": hlo_comms.summarize(colls),
        "per_axis_wire_bytes": hlo_comms.per_axis_wire_bytes(colls),
        **{k: aux[k] for k in ("grad_elems", "rows", "seq_len",
                               "loop_multiplier")}}
    findings += _f(f"{tag}:engine.packed+acc", "comms/grad-psum",
                   check_grad_psum(colls, desc.data_axes,
                                   aux["grad_elems"],
                                   aux["grad_elems_min"]))
    findings += _f(f"{tag}:engine.packed+acc", "comms/param-allgather",
                   check_no_param_allgather(colls, aux["param_elems"]))
    findings += _f(f"{tag}:engine.packed+acc", "sharding/annotations",
                   annotation_findings(lowered.as_text(), desc, False,
                                       aux["n_sharded_params"]))
    base_boundary_fwd = [c for c in boundary_collectives(colls)
                         if hlo_comms.is_forward(c)]
    say(f"{tag} engine.packed baseline: {sum(s['count'] for s in rep['engine.packed']['collectives'].values())} "
        f"collectives, grad_elems={aux['grad_elems']} "
        f"[{time.perf_counter() - t0:.1f}s]")

    if check_sp and desc.axis_size(desc.model_axis) > 1:
        t0 = time.perf_counter()
        sp_lowered, sp_colls, sp_aux = lower_packed(cfg, impl, desc,
                                                    seq_parallel=True)
        sp_boundary_fwd = [c for c in boundary_collectives(sp_colls)
                           if hlo_comms.is_forward(c)]
        findings += _f(f"{tag}:engine.packed+acc", "comms/seq-parallel",
                       check_seq_parallel_boundary(base_boundary_fwd,
                                                   sp_boundary_fwd))
        findings += _f(f"{tag}:engine.packed+acc",
                       "sharding/annotations-sp",
                       annotation_findings(sp_lowered.as_text(), desc,
                                           True,
                                           sp_aux["n_sharded_params"]))
        base_wire = sum(c["wire_eff"] for c in base_boundary_fwd)
        sp_wire = sum(c["wire_eff"] for c in sp_boundary_fwd)
        rep["seq_parallel"] = {
            "boundary_fwd_wire_bytes": {"all_reduce_baseline": base_wire,
                                        "seq_parallel": sp_wire},
            "collectives": hlo_comms.summarize(sp_colls),
            "per_axis_wire_bytes":
                hlo_comms.per_axis_wire_bytes(sp_colls)}
        say(f"{tag} seq_parallel boundary fwd wire bytes: "
            f"{sp_wire} (SP) vs {base_wire} (baseline) "
            f"[{time.perf_counter() - t0:.1f}s]")

    t0 = time.perf_counter()
    dcolls = lower_decode(cfg, desc)
    rep["session.step"] = {
        "collectives": hlo_comms.summarize(dcolls),
        "per_axis_wire_bytes": hlo_comms.per_axis_wire_bytes(dcolls)}
    findings += _f(f"{tag}:session.step", "comms/data-axis",
                   check_zero_data_axis(dcolls, desc.data_axes))
    say(f"{tag} session.step: "
        f"{sum(s['count'] for s in rep['session.step']['collectives'].values())} "
        f"collectives, 0 on data axes required "
        f"[{time.perf_counter() - t0:.1f}s]")
    return findings, rep


def run_comms_lint(*, fast: bool = False, impl: str = "ref",
                   verbose: bool = True) -> tuple[list[Finding], dict]:
    """Passes 4–6.  ``fast``: host-mesh (16,1) descriptor + dense config,
    rule lint on the two smoke archs — the <15 s CI gate.  Full: the
    production (16,16) and (2,16,16) descriptors with the seq-parallel
    A/B, rule lint over every arch's FULL config."""
    from repro.analysis.lock_lint import lock_findings

    def say(msg):
        if verbose:
            print(f"[shardlint] {msg}", flush=True)

    findings: list[Finding] = []
    report: dict = {"mode": "fast" if fast else "full", "meshes": {}}

    # pass 6 — lock discipline (pure AST)
    findings += _f("async-layers", "lock-discipline", lock_findings())
    say("lock discipline: PlanPipeline/WeightStore/AsyncTreeRLService "
        f"audited, {len(findings)} findings")

    # pass 5a — rule lint on FULL configs (host-side, zero devices)
    t0 = time.perf_counter()
    rule_archs = (DENSE_ARCH, "qwen3_30b_a3b") if fast else ARCH_IDS
    rl: list[str] = []
    for arch in rule_archs:
        rl += rule_lint(get_config(arch))
    findings += _f("sharding._RULES", "sharding/rule-coverage", rl)
    report["rule_lint"] = {"archs": list(rule_archs),
                           "findings": len(rl),
                           "seconds": round(time.perf_counter() - t0, 2)}
    say(f"rule lint: {len(rule_archs)} full configs, {len(rl)} findings "
        f"[{report['rule_lint']['seconds']}s]")

    # comm-contract closed coverage over the dense registry
    cfg = get_config(DENSE_ARCH, smoke=True)
    cov = comm_coverage_findings(build_targets(cfg, impl))
    findings += _f("registry", "comms/coverage", cov)
    say(f"comm-contract coverage: {len(cov)} undeclared entrypoints")

    # pass 4 — lower under the mesh descriptors
    descs = ([host_descriptor(min(16, jax.device_count()))] if fast else
             [production_descriptor(False), production_descriptor(True)])
    for desc in descs:
        mesh_f, mesh_rep = audit_mesh(cfg, impl, desc,
                                      check_sp=not fast, verbose=verbose)
        findings += mesh_f
        report["meshes"][desc.name] = mesh_rep

    report["findings"] = [
        {"target": f.target, "check": f.check, "message": f.message}
        for f in findings]
    return findings, report


# ---------------------------------------------------------------------------
# Per-family lowering sweep (nightly / slow tests)
# ---------------------------------------------------------------------------

def lower_sweep(archs=SHARDLINT_SWEEP_ARCHS, impl: str = "ref",
                verbose: bool = True) -> tuple[list[Finding], dict]:
    """Prove every family (and the production-scale configs) lowers
    cleanly under the production mesh: smoke dims for the trace (family
    structure is what lowering exercises), FULL dims for the rule lint."""
    desc = production_descriptor(False)
    findings: list[Finding] = []
    rep: dict = {}
    for arch in archs:
        t0 = time.perf_counter()
        entry: dict = {}
        try:
            cfg = get_config(arch, smoke=True)
            lowered, _, aux = lower_packed(cfg, impl, desc,
                                           seq_parallel=False,
                                           compile_=False)
            entry["lowered"] = True
            findings += _f(f"{arch}@{desc.name}", "sharding/annotations",
                           annotation_findings(lowered.as_text(), desc,
                                               False,
                                               aux["n_sharded_params"]))
        except Exception as e:  # noqa: BLE001 — a sweep must report, not die
            entry["lowered"] = False
            findings.append(Finding(f"{arch}@{desc.name}",
                                    "sharding/lowering",
                                    f"failed to lower under "
                                    f"{desc.shape}: {e!r}"[:400]))
        rl = rule_lint(get_config(arch))
        findings += _f(arch, "sharding/rule-coverage", rl)
        entry["rule_findings"] = len(rl)
        entry["seconds"] = round(time.perf_counter() - t0, 1)
        rep[arch] = entry
        if verbose:
            print(f"[shardlint] sweep {arch}: lowered="
                  f"{entry['lowered']} rule_findings={entry['rule_findings']} "
                  f"[{entry['seconds']}s]", flush=True)
    return findings, rep


def main(argv=None) -> int:
    import argparse
    import sys
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.comms_audit",
        description="shardlint per-family lowering sweep")
    ap.add_argument("--sweep", action="store_true",
                    help="lower every family + production-scale config "
                         "under the production mesh")
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--impl", default="ref", choices=("ref", "pallas"))
    args = ap.parse_args(argv)
    archs = args.arch or list(SHARDLINT_SWEEP_ARCHS)
    if not args.sweep and not args.arch:
        ap.error("pass --sweep (or --arch)")
    findings, _rep = lower_sweep(archs, args.impl)
    for f in findings:
        print(f"FINDING {f}", file=sys.stderr)
    print(f"[shardlint] sweep {'FAILED' if findings else 'OK'}: "
          f"{len(findings)} findings across {len(archs)} arch(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
