"""From a profiler trace (``.xplane.pb``) to intervals, and from
intervals to device time.

``load`` reads the newest trace under a directory with JAX's own
``ProfileData`` and keeps two things: every operation on each TPU's
``XLA Ops`` line (name, start, end, kind) and the benchmark's own host
spans (``bench.*`` ``TraceAnnotation``\\ s).  ``reduce`` turns those
into the numbers the readers use, inside the ``bench.window`` span:

- ``busy_s``: the union of the operations' intervals, per chip, averaged;
- ``pallas_s``: the device time of the Pallas kernels (Mosaic custom
  calls), summed over chips;
- ``collective_s`` / ``collective_exposed_s``: the device time of the
  collectives, and the part of it during which no other operation ran
  on that chip (per chip, averaged);
- ``top_ops`` and ``idle_gaps`` for the result's ``breakdown``.
"""
from __future__ import annotations

import glob
import os

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _kind(name: str, stats: dict) -> str:
    text = " ".join([name] + [str(v) for v in stats.values()]).lower()
    if "tpu_custom_call" in text or "mosaic" in text:
        return "pallas"
    if any(_short(name).startswith(c) for c in COLLECTIVES):
        return "collective"
    return "op"


def _short(name: str) -> str:
    """An op's event name is its HLO text; keep the instruction name."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_profile(pd) -> dict:
    """ProfileData → {"device": {plane: [(name, t0, t1, kind)]},
    "host": [(name, t0, t1)]}, times in nanoseconds."""
    device: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    evs.append((_short(e.name), e.start_ns,
                                e.start_ns + e.duration_ns,
                                _kind(e.name, stats)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"device": device, "host": host}


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return reduce_profile(ProfileData.from_file(max(files,
                                                    key=os.path.getmtime)))


def _union(iv: list) -> list:
    out: list = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _self_times(evs: list) -> list:
    """(name, self time) of each op: a loop's time less the ops nested
    inside it on the same line."""
    out = []
    stack: list = []
    for a, b, n, _ in sorted(evs, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= a:
            out.append(stack.pop()[2:])
        if stack and b <= stack[-1][1]:
            stack[-1][3] -= b - a
        stack.append([a, b, n, b - a])
    out.extend(s[2:] for s in stack)
    return out


def reduce(tr: dict, top: int = 10) -> dict:
    """Intervals → device time inside the ``bench.window`` span."""
    wins = [(a, b) for n, a, b in tr["host"] if n == "bench.window"]
    if not wins or not tr["device"]:
        raise ValueError("trace holds no bench.window span or no TPU ops")
    lo, hi = wins[0]
    spans = sorted((a, b, n) for n, a, b in tr["host"]
                   if n != "bench.window" and b > lo and a < hi)
    busy = pallas = coll = coll_exposed = 0.0
    by_name: dict = {}
    gaps: list = []
    for evs in tr["device"].values():
        mine = [(max(a, lo), min(b, hi), n, k) for n, a, b, k in evs
                if b > lo and a < hi]
        u = _union([(a, b) for a, b, _, _ in mine])
        busy += sum(b - a for a, b in u)
        for n, t in _self_times(mine):
            by_name[n] = by_name.get(n, 0.0) + t
        pallas += sum(b - a for a, b, _, k in mine if k == "pallas")
        cu = _union([(a, b) for a, b, _, k in mine if k == "collective"])
        ou = _union([(a, b) for a, b, _, k in mine if k != "collective"])
        coll += sum(b - a for a, b in cu)
        coll_exposed += sum(b - a for a, b in cu) - _overlap(cu, ou)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g0, g1))
    n = len(tr["device"])

    def host_label(g0, g1):
        best, name = 0.0, "other"
        for a, b, nm in spans:
            ov = min(b, g1) - max(a, g0)
            if ov > best:
                best, name = ov, nm
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy / n * ns,
        "pallas_s": pallas * ns,
        "collective_s": coll / n * ns,
        "collective_exposed_s": coll_exposed / n * ns,
        "chips": n,
        "top_ops": [[k, v * ns] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_label(a, b), (b - a) * ns]
                      for a, b in gaps[:top]],
    }
