"""Compile-signature lint: the static front half of AOT warmup.

The engine retraces on shape, and the planner's job is to keep every
emitted shape inside a small, enumerable pow2-bucket universe
(``core/plan_cost.pow2`` for waves, fixed ``[rows, seq_len]`` for the
packed batch).  This pass:

  1. derives each planned step's jit signatures
     (``core/plan_cost.packed_signature`` / ``wave_signature``) exactly
     the way ``train/engine`` keys its retraces;
  2. checks every signature of a real planner run against the reachable
     universe — an out-of-universe signature means a silent mid-training
     recompile stall;
  3. enumerates (counts) the bounded universe: the list an AOT warmup
     pass would precompile (ROADMAP item 4's static front half).

Pure host code — no jax imports, safe in CI's fast gate.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Hashable, Iterable, Optional

from repro.core.plan_cost import (CUT_FLOOR, CompileCacheSim,
                                  packed_signature, pow2,
                                  round_to_multiple, wave_path_floor,
                                  wave_signature, wave_signature_of)

__all__ = ["wave_signature_of", "step_signatures", "SignatureUniverse",
           "lint_signatures", "synthetic_source", "template_source"]


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def step_signatures(ps) -> list[Hashable]:
    """All jit signatures one PlannedStep will dispatch (packed batch +
    every partition wave)."""
    sigs: list[Hashable] = []
    sb = ps.step_batch()
    if sb.tb is not None:
        B, S = sb.tb.tokens.shape
        sigs.append(packed_signature(B, S))
    plan = ps.execution_plan()
    if plan.partition is not None:
        sigs.extend(wave_signature_of(wp, ps.lc.seq_len)
                    for wp in plan.partition.waves)
    return sigs


@dataclass(frozen=True)
class SignatureUniverse:
    """The reachable jit-signature set for one (LoaderConfig,
    PlannerConfig) pair.  Membership is exact for the packed batch and
    pow2-bucket-shaped for waves; ``count`` bounds the enumeration an AOT
    warmup would precompile."""
    seq_len: int
    batch_rows: int
    num_replicas: int
    max_rows: int
    capacity: int

    @property
    def packed_rows(self) -> int:
        return round_to_multiple(self.batch_rows, self.num_replicas)

    @property
    def max_wave_rows(self) -> int:
        R = max(self.num_replicas, 1)
        return R * pow2(-(-self.max_rows // R))

    def contains(self, sig: Hashable) -> tuple[bool, str]:
        kind = sig[0]
        if kind == "packed":
            _, rows, S = sig
            if rows != self.packed_rows:
                return False, (f"packed rows {rows} != replica-rounded "
                               f"batch_rows {self.packed_rows}")
            if S != self.seq_len:
                return False, f"packed seq {S} != {self.seq_len}"
            return True, ""
        if kind == "wave":
            _, rows, S, anc, ncut, plen, n_extra = sig
            R = max(self.num_replicas, 1)
            if S != self.seq_len:
                return False, f"wave seq {S} != {self.seq_len}"
            if rows % R or not _is_pow2(rows // R):
                return False, (f"wave rows {rows} not a pow2 multiple of "
                               f"{R} replicas")
            if rows > self.max_wave_rows:
                return False, (f"wave rows {rows} exceed the max_rows "
                               f"bucket {self.max_wave_rows}")
            if anc and (not _is_pow2(anc) or anc < 8):
                return False, f"ancestor pad {anc} not a pow2 ≥ 8 bucket"
            if ncut and not _is_pow2(ncut):
                return False, f"cut count {ncut} not pow2-bucketed"
            if plen and (not _is_pow2(plen) or plen > pow2(self.capacity)):
                return False, f"path pad {plen} out of pow2 buckets"
            if n_extra and not _is_pow2(n_extra):
                return False, f"extra pad {n_extra} not pow2-bucketed"
            return True, ""
        return False, f"unknown signature kind {kind!r}"

    def count(self, anc_cap: int, ncut_cap: int, plen_cap: int,
              extra_cap: int) -> int:
        """Bounding-box size of the universe under observed per-field
        maxima: 1 packed + every wave bucket combination.  An upper bound
        on — and sanity check for — :meth:`enumerate_signatures`, which
        is the exact list the AOT warmup service compiles."""
        def nopts(cap: int, lo: int = 1) -> int:
            n, b = 1, lo                       # the 0 bucket
            while b <= cap:
                n, b = n + 1, b * 2
            return n
        R = max(self.num_replicas, 1)
        rows_opts = 0
        b = R
        while b <= self.max_wave_rows:
            rows_opts, b = rows_opts + 1, b * 2
        anc_lo, plen_lo = self._floors()
        return 1 + (rows_opts * nopts(anc_cap, anc_lo)
                    * nopts(ncut_cap, CUT_FLOOR) * nopts(plen_cap, plen_lo)
                    * nopts(extra_cap, CUT_FLOOR))

    def _floors(self) -> tuple[int, int]:
        """The least ancestor-pad and capture-path-pad buckets
        (``core/gateway.build_partition_plan``; cut and boundary-extra
        counts start at ``CUT_FLOOR``)."""
        fl = wave_path_floor(self.seq_len)
        return max(8, fl), min(fl, pow2(self.capacity))

    def _buckets(self, cap: int, lo: int = 1) -> list[int]:
        out, b = [0], lo
        while b <= cap:
            out.append(b)
            b *= 2
        return out

    def enumerate_signatures(self, anc_cap: int, ncut_cap: int,
                             plen_cap: int, extra_cap: int
                             ) -> list[Hashable]:
        """THE AOT compile list: every *live* signature in the universe,
        bounded by observed (or configured) per-field caps.  ``count``
        is the loose bounding-box upper bound; this enumeration drops the
        structurally dead corners a real planner run can never emit, so
        the warmup service compiles no dead bucket:

          - ``ncut == 0`` ⟺ ``plen == 0`` ⟺ ``n_extra == 0`` — the
            capture plans drive both the path pad and the boundary-extra
            columns, so the three vanish together (a leaf wave);
          - ``anc == 0 ⇒ ncut ≥ 1`` — a root wave's fragments cut to
            children, and a root wave of one-fragment trees takes inert
            cuts (``core/gateway.build_partition_plan``);
          - ``n_extra ≤ ncut`` — per-row boundary extras are bucketed
            from per-row cut counts, never exceeding the wave total.

        Every returned signature passes :meth:`contains`;
        ``len(result) ≤ count(same caps)``."""
        sigs: list[Hashable] = [packed_signature(self.packed_rows,
                                                 self.seq_len)]
        R = max(self.num_replicas, 1)
        rows_list, b = [], R
        while b <= self.max_wave_rows:
            rows_list.append(b)
            b *= 2
        plen_cap = min(plen_cap, pow2(self.capacity))
        anc_lo, plen_lo = self._floors()
        for rows in rows_list:
            for anc in self._buckets(anc_cap, lo=anc_lo):
                for ncut in self._buckets(ncut_cap, lo=CUT_FLOOR):
                    if ncut == 0:
                        if anc > 0:     # leaf wave: gateway in, no cuts
                            sigs.append(wave_signature(
                                rows, self.seq_len, anc, 0, 0, 0))
                        continue
                    for plen in self._buckets(plen_cap, lo=plen_lo)[1:]:
                        for n_extra in self._buckets(
                                min(extra_cap, ncut), lo=CUT_FLOOR)[1:]:
                            sigs.append(wave_signature(
                                rows, self.seq_len, anc, ncut, plen,
                                n_extra))
        return sigs


def lint_signatures(cfg, lc, pc, source,
                    universe: Optional[SignatureUniverse] = None
                    ) -> tuple[list, dict]:
    """Run the planner over ``source`` (host-side only) and check every
    emitted jit signature against the reachable universe.  Returns
    (findings, report) where the report carries the distinct signature
    set, the simulated compile-miss count, and the AOT-universe size."""
    from repro.analysis.jaxpr_audit import Finding
    from repro.train.planner import plan_stream

    universe = universe or SignatureUniverse(
        seq_len=lc.seq_len, batch_rows=lc.batch_rows,
        num_replicas=pc.num_replicas,
        max_rows=(pc.max_rows if pc.max_rows is not None
                  else lc.batch_rows),
        capacity=lc.capacity or lc.seq_len)
    sim = CompileCacheSim()
    findings: list = []
    all_sigs: list = []
    steps = 0
    for ps in plan_stream(cfg, lc, source, pc):
        steps += 1
        sigs = step_signatures(ps)
        all_sigs.extend(sigs)
        for sig in sigs:
            ok, why = universe.contains(sig)
            if not ok:
                findings.append(Finding(
                    f"{cfg.name}:planner", "signature",
                    f"step {steps}: out-of-universe jit signature "
                    f"{sig}: {why} — would recompile mid-training"))
        sim.commit(sigs)
    distinct = sorted(set(map(str, all_sigs)))
    waves = [s for s in all_sigs if s[0] == "wave"]
    caps = [max((s[i] for s in waves), default=0) for i in (3, 4, 5, 6)]
    report = {
        "steps": steps,
        "signatures_emitted": len(all_sigs),
        "signatures_distinct": len(distinct),
        "distinct": distinct,
        "compile_misses": len(sim.seen),
        "out_of_universe": len(findings),
        "observed_caps": list(caps),
        "aot_universe_size": universe.count(*caps),
        "aot_compile_list": len(universe.enumerate_signatures(*caps)),
    }
    return findings, report


def synthetic_source(cfg, n_batches: int, trees_per: int, seed: int = 0):
    """Deterministic forests sized to exercise both packed rows and
    partition waves under the audit LoaderConfig."""
    from repro.analysis.registry import _forest
    return [_forest(1000 * seed + b, trees_per, cfg.vocab_size)
            for b in range(n_batches)]


def template_source(cfg, lc, n_batches: int, trees_per: int,
                    seed: int = 0):
    """Template-heavy forests scaled to the audit LoaderConfig's unit —
    every tree opens with one of two verbatim system-prompt templates
    (``data.synthetic.template_tree``), so a graft-enabled planner replay
    actually merges trees and its grafted plans' signatures get checked
    against the same :class:`SignatureUniverse` as ungrafted ones."""
    from repro.data.synthetic import trees_for_batch
    unit = max(lc.seq_len // 8, 8)
    return [trees_for_batch(1000 * seed + b, n_trees=trees_per,
                            kind="template", vocab_size=cfg.vocab_size,
                            num_templates=2, template_len=2 * unit,
                            num_turns=2,
                            turn_len_range=(unit // 2, 2 * unit))
            for b in range(n_batches)]
