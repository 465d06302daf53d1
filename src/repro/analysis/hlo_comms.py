"""Post-SPMD HLO collective parsing + the wire-byte cost model.

Shared by the multi-pod dry-run tool (``launch/dryrun``) and shardlint
(``analysis/comms_audit``): one definition of what counts as a collective,
how its bytes are measured, which mesh axes it spans, and how while-loop
trip counts multiply it.

Byte model (per device, ring algorithms, the (n−1)/n factor dropped):

  all-reduce          2 × full tensor bytes (reduce-scatter + all-gather
                      halves of the ring; post-SPMD result is the full
                      replicated tensor, so 2 × result bytes)
  all-gather          1 × full tensor bytes (= result bytes: the result is
                      the gathered, group-replicated tensor)
  reduce-scatter      1 × full tensor bytes (= result bytes × group size:
                      the result is one scattered shard)
  collective-permute / all-to-all   1 × result bytes

so a ring all-reduce costs exactly reduce-scatter + all-gather — the
conservation law behind sequence parallelism: SP does not shrink fwd+bwd
boundary totals, it halves the *forward* edge (RS instead of AR) and pays
the other half as the backward's all-gather.  shardlint gates on the
forward edge for precisely this reason.

Pure string/regex code — no jax import, safe anywhere.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "f8": 1}

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

# `%name = TYPE op(...)` where TYPE is `f32[4,8]{1,0}` or a tuple
# `(f32[4]{0}, s32[8]{0})` (XLA combines per-tensor all-reduces).
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[\d,]*\]\S*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?[\s(]")
_TYPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_SOURCE_RE = re.compile(r'source_file="([^"]*)"\s+source_line=(\d+)')
_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


def _parse_replica_groups(line: str) -> Optional[list[list[int]]]:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ng, gs, rdims, perm = m.groups()
        rdims = [int(d) for d in rdims.split(",")]
        total = 1
        for d in rdims:
            total *= d
        ids = list(range(total))
        # reshape(rdims) → transpose(perm) → reshape(ng, gs)
        if perm:
            perm_t = [int(p) for p in perm.split(",")]
            strides = [1] * len(rdims)
            for i in range(len(rdims) - 2, -1, -1):
                strides[i] = strides[i + 1] * rdims[i + 1]
            out = []
            tdims = [rdims[p] for p in perm_t]
            tstrides = [strides[p] for p in perm_t]

            def emit(depth: int, off: int) -> None:
                if depth == len(tdims):
                    out.append(off)
                    return
                for j in range(tdims[depth]):
                    emit(depth + 1, off + j * tstrides[depth])

            emit(0, 0)
            ids = out
        ng, gs = int(ng), int(gs)
        return [ids[g * gs:(g + 1) * gs] for g in range(ng)]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return [[int(x) for x in grp.split(",") if x]
                for grp in re.findall(r"\{([^}]*)\}", m.group(1))]
    if "replica_groups={}" in line:
        return []                    # empty = one group of all devices
    return None


def _parse_pairs(line: str) -> Optional[list[tuple[int, int]]]:
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return [(int(a), int(b)) for a, b in
            re.findall(r"\{(\d+),(\d+)\}", m.group(1))]


def _frame_sources(hlo: str) -> dict[int, tuple[str, int]]:
    """stack_frame_id → (file, line) of the frame itself, from the
    module's ``FileNames`` / ``FileLocations`` / ``StackFrames`` tables
    (newer XLA keeps source locations there instead of inline
    ``source_file``/``source_line`` metadata)."""
    tables: dict[str, dict[int, str]] = {}
    section = None
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = tables.setdefault(line, {})
            continue
        m = re.match(r"(\d+) (.*)", line) if section is not None else None
        if m is None:
            section = None
            continue
        section[int(m.group(1))] = m.group(2)
    files = {i: v.strip('"') for i, v in tables.get("FileNames", {}).items()}
    locs = {}
    for i, v in tables.get("FileLocations", {}).items():
        f = re.search(r"file_name_id=(\d+)", v)
        ln = re.search(r"\bline=(\d+)", v)
        if f and ln:
            locs[i] = (files.get(int(f.group(1)), ""), int(ln.group(1)))
    out = {}
    for i, v in tables.get("StackFrames", {}).items():
        loc = re.search(r"file_location_id=(\d+)", v)
        if loc and int(loc.group(1)) in locs:
            out[i] = locs[int(loc.group(1))]
    return out


def parse_collectives(hlo: str) -> list[dict]:
    """Every collective op in a post-SPMD HLO dump, with result bytes,
    wire bytes (the module-docstring model), replica groups, source
    attribution (``op_name`` / ``source_file`` / ``source_line`` metadata)
    and loop attribution.

    Post-optimization HLO wraps ops into called computations, so lexical
    position says nothing about loops.  We build the computation call
    graph (to_apply / body / condition / branch edges) and mark a
    collective as in-loop when some while body transitively reaches its
    computation; the nesting depth (≥2 = inside the per-layer scan's inner
    chunk scan) is recorded for the trip-count multiplier.
    """
    frames = _frame_sources(hlo)
    comp = "entry"
    comp_of_line: list[tuple[str, str]] = []
    edges: dict[str, set] = {}
    while_bodies: set[str] = set()
    for line in hlo.splitlines():
        # computation headers sit at column 0: `%name (args...) -> ty {`
        # (args may nest parens — tuple types — so don't try to span them)
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(", line)
            if m:
                comp = m.group(1)
        comp_of_line.append((comp, line))
        for attr in re.findall(
                r"(?:to_apply|body|condition)=%?([\w\.\-]+)", line):
            edges.setdefault(comp, set()).add(attr)
        mb = re.search(r"body=%?([\w\.\-]+)", line)
        if mb and "while(" in line:
            while_bodies.add(mb.group(1))

    # loop depth per computation: BFS from each while body
    depth: dict[str, int] = {}

    def mark(c: str, d: int):
        if depth.get(c, 0) >= d:
            return
        depth[c] = d
        for nxt in edges.get(c, ()):  # descend; nested whiles add depth
            mark(nxt, d + 1 if nxt in while_bodies else d)

    for b in while_bodies:
        mark(b, 1)

    out = []
    for comp, line in comp_of_line:
        m = _COLL_RE.search(line)
        if not m:
            continue
        rtype, op, _start = m.groups()
        n_bytes = 0
        n_elems = 0
        dt = "f32"
        for dt_i, dims in _TYPE_RE.findall(rtype):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            n_elems += n
            n_bytes += n * _DTYPE_BYTES.get(dt_i, 4)
            dt = dt_i
        groups = _parse_replica_groups(line)
        pairs = _parse_pairs(line)
        group_size = len(groups[0]) if groups else None
        if op == "all-reduce":
            wire = 2 * n_bytes
        elif op == "reduce-scatter":
            wire = n_bytes * (group_size or 1)
        else:
            wire = n_bytes
        # primary loop signal: the op's own jax-level op_name metadata
        # ("jit(step)/jvp()/while/body/..."); nested scans repeat "while/".
        mo = _OPNAME_RE.search(line)
        op_name = mo.group(1) if mo else ""
        d_meta = op_name.count("while/")
        d_cg = depth.get(comp, 0)
        d_final = max(d_meta, d_cg)
        ms = _SOURCE_RE.search(line)
        src = (ms.group(1), int(ms.group(2))) if ms else ("", -1)
        mf = None if ms else _FRAME_RE.search(line)
        if mf:
            src = frames.get(int(mf.group(1)), src)
        out.append({"op": op, "dtype": dt,
                    "bytes": n_bytes,
                    "elems": n_elems,
                    "wire_bytes": wire,
                    "comp": comp,
                    "op_name": op_name,
                    "source_file": src[0],
                    "source_line": src[1],
                    "replica_groups": groups,
                    "source_target_pairs": pairs,
                    "loop_depth": d_final,
                    "in_loop": d_final >= 1})
    return out


# ---------------------------------------------------------------------------
# Mesh-axis attribution
# ---------------------------------------------------------------------------

def _coords(device_id: int, shape: Sequence[int]) -> tuple[int, ...]:
    """Row-major unravel — jax.make_mesh lays device ids out row-major
    over the mesh shape."""
    out = []
    for s in reversed(shape):
        out.append(device_id % s)
        device_id //= s
    return tuple(reversed(out))


def collective_axes(coll: dict, shape: Sequence[int],
                    axis_names: Sequence[str]) -> tuple[str, ...]:
    """Mesh axes a collective spans: axes along which some replica group
    (or permute pair) holds more than one distinct coordinate."""
    total = 1
    for s in shape:
        total *= s
    groups = coll.get("replica_groups")
    if groups == []:                         # empty = all devices
        groups = [list(range(total))]
    if not groups and coll.get("source_target_pairs"):
        groups = [list(p) for p in coll["source_target_pairs"]]
    if not groups:
        return tuple(axis_names)             # unknown: assume everything
    spanned = set()
    for grp in groups:
        cs = [_coords(d, shape) for d in grp if d < total]
        for ax in range(len(shape)):
            if len({c[ax] for c in cs}) > 1:
                spanned.add(axis_names[ax])
    return tuple(a for a in axis_names if a in spanned)


def attach_axes(colls: list[dict], shape: Sequence[int],
                axis_names: Sequence[str]) -> list[dict]:
    for c in colls:
        c["axes"] = collective_axes(c, shape, axis_names)
    return colls


def is_forward(coll: dict) -> bool:
    """Backward-pass ops carry ``transpose(...)`` in their jax op_name;
    ops inside the VJP inherit the forward's source line, so source-line
    attribution alone cannot split fwd from bwd — this can."""
    return "transpose(" not in coll.get("op_name", "")


# ---------------------------------------------------------------------------
# Summaries
# ---------------------------------------------------------------------------

def loop_multiplier(cfg) -> int:
    """Scan-over-layers trip count (dominant while loop)."""
    from repro.models.transformer import layer_groups
    groups = layer_groups(cfg)
    if cfg.family == "hybrid":
        return cfg.hybrid.attn_every
    return max(n for _, n in groups)


def _mult(coll: dict, loop_mult: int, chunk_mult: int) -> int:
    if coll["loop_depth"] >= 2:
        return loop_mult * chunk_mult
    if coll["loop_depth"] == 1:
        return loop_mult
    return 1


def summarize(colls: list[dict], loop_mult: int = 1,
              chunk_mult: int = 1) -> dict[str, dict]:
    """Per-op totals: count, result bytes, wire bytes, and the same with
    while-loop trip counts re-multiplied (scan bodies are in the HLO
    once)."""
    summary: dict[str, dict] = {}
    for c in colls:
        s = summary.setdefault(c["op"], {
            "count": 0, "bytes": 0, "bytes_with_loops": 0,
            "wire_bytes": 0, "wire_bytes_with_loops": 0})
        m = _mult(c, loop_mult, chunk_mult)
        s["count"] += 1
        s["bytes"] += c["bytes"]
        s["bytes_with_loops"] += c["bytes"] * m
        s["wire_bytes"] += c["wire_bytes"]
        s["wire_bytes_with_loops"] += c["wire_bytes"] * m
    return summary


def per_axis_wire_bytes(colls: list[dict], loop_mult: int = 1,
                        chunk_mult: int = 1) -> dict[str, int]:
    """Wire bytes attributed to each mesh axis a collective spans (a
    collective spanning k axes charges each; requires ``attach_axes``)."""
    out: dict[str, int] = {}
    for c in colls:
        m = _mult(c, loop_mult, chunk_mult)
        for a in c.get("axes", ()):
            out[a] = out.get(a, 0) + c["wire_bytes"] * m
    return out
