"""The program's named device work and host spans, from the profiler
trace that ``bench/run.py --trace 1`` records under ``.bench_trace/``.

The program names its device work with ``jax.named_scope``: the
tree-attention kernels (``tree_attn_fwd``, which covers the forward that
remat recomputes, ``tree_attn_dq``, ``tree_attn_dkv``; their Pallas calls
carry the same names), ``lm_head`` (the head, its log-sum-exp and the
NLL) and ``adamw`` (the update).  Its host layers write
``TraceAnnotation`` spans: ``repro.engine.dispatch``,
``repro.engine.sync``, ``repro.plan.wait`` and ``repro.plan.build``.

An op's name stack (``tf_op``) is kept in the trace's event metadata,
which ``ProfileData`` does not expose, so ``op_scopes`` reads it from the
file's bytes; every interval comes from ``ProfileData``.  Inside the
``bench.window`` span, ``reduce`` gives:

- ``scope_s``: device time of the ops under each name (an op's time less
  the ops nested in it), per chip averaged; ``kernel_s``: the part of it
  spent in Pallas calls;
- ``host_s``: host time inside each ``repro.*`` span, summed over threads;
- ``idle_s``: device idle time, each instant put down to the innermost
  ``repro.*`` span open then (the one that began last, on any thread),
  else to the innermost of the harness's own ``bench.*`` spans, per chip
  averaged; ``idle_total_s`` is all of it;
- ``steps``: the harness's ``bench.step`` spans that begin in the window.

    python -m bench.scopes <trace dir>

prints those per step for the newest trace under the directory: the
operator's view of the spans that no metric reads.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace import _kind, _overlap, _self_times, _union  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")    # where run.py traces
SCOPES = ("tree_attn_fwd", "tree_attn_dq", "tree_attn_dkv", "lm_head",
          "adamw")
SPANS = ("repro.engine.dispatch", "repro.engine.sync", "repro.plan.wait",
         "repro.plan.build")
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=$|[/):])")


def scope_of(tf_op: str):
    """The innermost of ``SCOPES`` in an op's name stack, or None."""
    found = _SCOPE.findall(tf_op)
    return found[-1] if found else None


# -- the event metadata: a walk over the protobuf wire format -------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entry: key
# 1, value 2), stat_metadata = 5 (the same); XEventMetadata: name = 2,
# stats = 5; XStatMetadata: name = 2; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7 (a string kept as a stat_metadata name).

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of each field of the message in buf[lo:hi];
    a length-delimited value is its (start, end)."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes(raw: bytes) -> dict:
    """{device plane: {op name: scope}} from a serialized XSpace: each op
    name whose ``tf_op`` holds one of ``SCOPES``.  A name that two ops of
    different scopes share is left out."""
    out: dict = {}
    for f, plane in _fields(raw, 0, len(raw)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(raw, *plane):
            if g == 2:
                name = _text(raw, v)
            elif g in (4, 5):
                entry = dict(_fields(raw, *v))
                if 2 not in entry:
                    continue
                if g == 4:
                    events.append(entry[2])
                else:
                    sm = dict(_fields(raw, *entry[2]))
                    stat_names[entry.get(1, 0)] = _text(raw, sm.get(2, (0, 0)))
        if not name.startswith("/device:"):
            continue
        tf_id = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        scopes: dict = {}
        for span in events:
            ev_name, tf_op = "", ""
            for g, v in _fields(raw, *span):
                if g == 2:
                    ev_name = _text(raw, v)
                elif g == 5:
                    st = dict(_fields(raw, *v))
                    if st.get(1) != tf_id:
                        continue
                    if 5 in st:
                        tf_op = _text(raw, st[5])
                    elif 7 in st:
                        tf_op = stat_names.get(st[7], "")
            sc = scope_of(tf_op)
            if ev_name in scopes and scopes[ev_name] != sc:
                sc = None
            scopes[ev_name] = sc
        out[name] = {n: s for n, s in scopes.items() if s is not None}
    return out


# -- intervals ---------------------------------------------------------------

def reduce_profile(pd, scopes: dict) -> dict:
    """ProfileData and ``op_scopes`` → {"device": {plane: [(t0, t1, scope,
    kind)]}, "host": [(name, t0, t1)]}, times in nanoseconds."""
    device: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            names = scopes.get(plane.name, {})
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                names.get(e.name),
                                _kind(e.name, dict(e.stats))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench.", "repro.")):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"device": device, "host": host}


def _innermost(spans: list, lo: float, hi: float) -> list:
    """[(t0, t1, span name)]: [lo, hi] cut where spans open or close, each
    piece under the ``repro.*`` span open there that began last, else
    under the harness's span that began last (None: no span)."""
    cuts = sorted({lo, hi} | {x for a, b, _ in spans for x in (a, b)
                              if lo < x < hi})
    out = []
    for x0, x1 in zip(cuts, cuts[1:]):
        open_ = [(n.startswith("repro."), a, -b, n) for a, b, n in spans
                 if a <= x0 and b >= x1]
        out.append((x0, x1, max(open_)[3] if open_ else None))
    return out


def reduce(tr: dict) -> dict:
    """Intervals → the named device time, host span time and idle time by
    span inside the ``bench.window`` span, in seconds."""
    wins = [(a, b) for n, a, b in tr["host"] if n == "bench.window"]
    if not wins or not tr["device"]:
        raise ValueError("trace holds no bench.window span or no TPU ops")
    lo, hi = wins[0]
    spans = [(max(a, lo), min(b, hi), n) for n, a, b in tr["host"]
             if n != "bench.window" and b > lo and a < hi]
    host_s: dict = {}
    for a, b, n in spans:
        if n.startswith("repro."):
            host_s[n] = host_s.get(n, 0.0) + b - a
    pieces = _innermost(spans, lo, hi)
    scope_s: dict = {}
    kernel_s: dict = {}
    idle_s: dict = {}
    idle_total = 0.0
    for evs in tr["device"].values():
        mine = [(max(a, lo), min(b, hi), (s, k), None) for a, b, s, k in evs
                if b > lo and a < hi]
        for (s, k), t in _self_times(mine):
            if s is not None:
                scope_s[s] = scope_s.get(s, 0.0) + t
                if k == "pallas":
                    kernel_s[s] = kernel_s.get(s, 0.0) + t
        busy = _union([(a, b) for a, b, _, _ in mine])
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        idle = [[g0, g1] for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        idle_total += sum(g1 - g0 for g0, g1 in idle)
        for x0, x1, n in pieces:
            if n is not None:
                idle_s[n] = idle_s.get(n, 0.0) + _overlap(idle, [[x0, x1]])
    chips = len(tr["device"])
    ns = 1e-9

    def per_chip(d):
        return {k: v / chips * ns for k, v in d.items()}

    steps = sum(1 for n, a, _ in tr["host"]
                if n == "bench.step" and lo <= a < hi)
    return {"window_s": (hi - lo) * ns, "chips": chips, "steps": steps,
            "scope_s": per_chip(scope_s), "kernel_s": per_chip(kernel_s),
            "host_s": {k: v * ns for k, v in host_s.items()},
            "idle_s": per_chip(idle_s),
            "idle_total_s": idle_total / chips * ns}


# -- the run's trace -----------------------------------------------------------

def newest(root: str):
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return reduce(reduce_profile(ProfileData.from_serialized_xspace(raw),
                                 op_scopes(raw)))


def load(path: str) -> dict:
    """``reduce`` of the trace at ``path``, read once."""
    return _load(path, os.path.getmtime(path))


def read(rec: dict):
    """The table of the run's own trace: the newest one under
    ``TRACE_DIR``, if its window is the run's traced window (within
    1 us); else None."""
    tr = rec.get("trace")
    path = newest(TRACE_DIR)
    if not tr or path is None:
        return None
    try:
        tab = load(path)
    except (OSError, RuntimeError, ValueError, IndexError):
        return None                 # unreadable: the metrics are absent
    return tab if abs(tab["window_s"] - tr["window_s"]) <= 1e-6 else None


def per_step_ms(rec: dict, part: str, name: str):
    """``read(rec)[part][name]`` in ms per step of the traced window, or
    None where the trace holds no such name."""
    tab = read(rec)
    steps = len(rec["trace"]["steps"]) if tab else 0
    if not steps or name not in tab[part]:
        return None
    return 1e3 * tab[part][name] / steps


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m bench.scopes <trace dir>", file=sys.stderr)
        return 2
    path = newest(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    tab = load(path)
    steps = max(tab["steps"], 1)
    ms = 1e3 / steps
    print(f"{path}\nwindow {tab['window_s']:.6f} s, {steps} steps, "
          f"{steps / tab['window_s']:.4f} steps/s, {tab['chips']} chip(s)")
    print("\ndevice, ms per step (Pallas part)")
    for n in SCOPES:
        if n in tab["scope_s"]:
            print(f"  {n:<24}{tab['scope_s'][n] * ms:10.3f}"
                  f"  ({tab['kernel_s'].get(n, 0.0) * ms:.3f})")
    named = sum(tab["scope_s"].values())
    print(f"  {'named, all':<24}{named * ms:10.3f}")
    print("\nhost span, ms per step: in the span / device idle under it")
    for n in SPANS:
        if n in tab["host_s"]:
            print(f"  {n:<24}{tab['host_s'][n] * ms:10.3f}"
                  f"{tab['idle_s'].get(n, 0.0) * ms:10.3f}")
    for n, t in sorted(tab["idle_s"].items()):
        if not n.startswith("repro."):
            print(f"  {n + ' (harness)':<24}{'':10}{t * ms:10.3f}")
    idle = tab["idle_total_s"]
    under = sum(t for n, t in tab["idle_s"].items() if n.startswith("repro."))
    print(f"  {'device idle, all':<24}{'':10}{idle * ms:10.3f}")
    print(f"\nidle under a repro.* span: {under / idle if idle else 0.0:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
