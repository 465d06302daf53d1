"""bench/scopes.py and the readers of the named device scopes and host
spans, on a recorded trace: about 200 ms of qwen15_tree_sft on one TPU v5
lite around a step boundary (``scopes_fixture.json``, trimmed from a chip
run of ``bench/run.py --trace 1``), rebuilt as a serialized XSpace with
each op's name stack in its event metadata and read back through JAX's
``ProfileData``."""
import importlib
import json
import os
import re

import numpy as np
import pytest

import bench_smoke  # noqa: F401  (puts the repository on sys.path)
from bench import scopes, trace

HERE = os.path.dirname(__file__)
READERS = {"attn_fwd_ms": ("kernel", "tree_attn_fwd"),
           "attn_dq_ms": ("kernel", "tree_attn_dq"),
           "attn_dkv_ms": ("kernel", "tree_attn_dkv"),
           "lm_head_ms": ("scope", "lm_head"),
           "adamw_ms": ("scope", "adamw"),
           "dispatch_ms": ("host", "repro.engine.dispatch"),
           "plan_build_ms": ("host", "repro.plan.build")}


def _load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def _serialized(fx: dict) -> bytes:
    """The fixture as a serialized XSpace: host events on their lines,
    device ops on an ``XLA Ops`` line named as the TPU profiler names them
    (the instruction's HLO text), each op's ``tf_op`` in its metadata (as
    a string, or for every other op as a reference to a stat name)."""
    from jax.profiler import ProfileData
    tf_op = fx.get("tf_op", {})
    stat_ids = {"tf_op": 1}
    for t in tf_op.values():
        stat_ids.setdefault(t, len(stat_ids) + 1)

    def text(e):
        if e[3] == "pallas":
            return (f"%{e[0]} = bf16[8] custom-call(), "
                    f"custom_call_target=\"tpu_custom_call\"")
        return f"%{e[0]} = f32[8] op()"

    def plane(pid, name, lines, meta):
        out = f"planes {{ id: {pid} name: {json.dumps(name)}\n"
        for i, (line, evs) in enumerate(lines):
            out += f"lines {{ id: {i + 1} name: {json.dumps(line)} " \
                   "timestamp_ns: 0\n"
            out += "".join(
                f"events {{ metadata_id: {m} offset_ps: "
                f"{int(round(a * 1000))} duration_ps: "
                f"{int(round((b - a) * 1000))} }}\n" for m, a, b in evs)
            out += "}\n"
        return out + meta + "}\n"

    host_ids: dict = {}
    lines: dict = {}
    for h in fx["host"]:
        m = host_ids.setdefault(h[0], len(host_ids) + 1)
        lines.setdefault(h[3] if len(h) > 3 else 1, []).append(
            (m, h[1], h[2]))
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                   f"{json.dumps(n)} }} }}\n" for n, i in host_ids.items())
    out = plane(1, "/host:CPU", [("python3", lines[k])
                                 for k in sorted(lines)], meta)
    for p, (dev, evs) in enumerate(sorted(fx["device"].items())):
        ids: dict = {}
        for e in evs:
            ids.setdefault(text(e), (len(ids) + 1, e[0]))
        meta = ""
        for t, (i, op) in ids.items():
            st = ""
            if op in tf_op:
                st = (f"stats {{ metadata_id: 1 str_value: "
                      f"{json.dumps(tf_op[op])} }}" if i % 2 else
                      f"stats {{ metadata_id: 1 ref_value: "
                      f"{stat_ids[tf_op[op]]} }}")
            meta += (f"event_metadata {{ key: {i} value {{ id: {i} name: "
                     f"{json.dumps(t)} {st} }} }}\n")
        meta += "".join(f"stat_metadata {{ key: {i} value {{ id: {i} name: "
                        f"{json.dumps(n)} }} }}\n"
                        for n, i in stat_ids.items())
        out += plane(2 + p, dev, [("XLA Ops", [(ids[text(e)][0], e[1], e[2])
                                               for e in evs])], meta)
    return ProfileData.text_proto_to_serialized_xspace(out)


@pytest.fixture(scope="module")
def recorded():
    return _load("scopes_fixture.json")


@pytest.fixture(scope="module")
def raw(recorded):
    return _serialized(recorded)


def _window(fx):
    (lo, hi), = [h[1:3] for h in fx["host"] if h[0] == "bench.window"]
    return lo, hi


def _hand_scope(tf_op: str):
    """The last of the scope names among the name stack's parts."""
    parts = [p for p in re.split(r"[/()]", tf_op.rstrip(":")) if p]
    found = [p for p in parts if p in scopes.SCOPES]
    return found[-1] if found else None


def _rec(fx, tmp_path, monkeypatch, raw_bytes, window_s=None, steps=1):
    """A run's record whose traced window is the fixture's, and the
    fixture written where the readers look for the run's trace."""
    (tmp_path / "t.xplane.pb").write_bytes(raw_bytes)
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path))
    scopes._load.cache_clear()
    lo, hi = _window(fx)
    ws = (hi - lo) * 1e-9 if window_s is None else window_s
    return {"trace": {"window_s": ws, "steps": [{}] * steps}}


def test_op_scopes_from_metadata(recorded, raw):
    (plane, evs), = recorded["device"].items()
    got = scopes.op_scopes(raw)[plane]
    want = {}
    for e in evs:
        s = _hand_scope(recorded["tf_op"].get(e[0], ""))
        if s is not None:
            t = [k for k in got if k.startswith(f"%{e[0]} = ")]
            assert len(t) == 1
            want[t[0]] = s
    assert got == want
    assert set(want.values()) == set(scopes.SCOPES)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_matches_hand_count(recorded, raw, tmp_path, monkeypatch,
                                   metric):
    rec = _rec(recorded, tmp_path, monkeypatch, raw, steps=2)
    lo, hi = _window(recorded)
    part, name = READERS[metric]
    if part == "host":
        want = sum(min(b, hi) - max(a, lo) for n, a, b, *_ in
                   recorded["host"] if n == name and b > lo and a < hi)
    else:
        (evs,) = recorded["device"].values()
        mine = [e for e in evs if e[2] > lo and e[1] < hi
                and _hand_scope(recorded["tf_op"].get(e[0], "")) == name
                and (part == "scope" or e[3] == "pallas")]
        # none of these ops has another op nested in it, so each one's
        # device time is its whole (clipped) interval
        for _, a, b, _ in mine:
            assert not any(a <= x[1] and x[2] <= b and (x[1], x[2]) != (a, b)
                           for x in evs)
        want = sum(min(b, hi) - max(a, lo) for _, a, b, _ in mine)
    mod = importlib.import_module(f"bench.metrics.{metric}")
    got = mod.read(rec)
    assert want > 0
    assert got == pytest.approx(want * 1e-6 / 2, rel=1e-9)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_none_without_names(tmp_path, monkeypatch, metric):
    """A trace with no names and no program spans (the one recorded
    before the program had them) reads as absent, not as 0."""
    old = _load("trace_fixture.json")
    rec = _rec(old, tmp_path, monkeypatch, _serialized(old))
    assert scopes.read(rec) is not None
    assert importlib.import_module(f"bench.metrics.{metric}").read(rec) \
        is None


def test_read_needs_the_runs_own_window(recorded, raw, tmp_path,
                                        monkeypatch):
    lo, hi = _window(recorded)
    rec = _rec(recorded, tmp_path, monkeypatch, raw,
               window_s=(hi - lo) * 1e-9 + 2e-6)
    assert scopes.read(rec) is None
    assert importlib.import_module("bench.metrics.attn_fwd_ms").read(rec) \
        is None
    assert scopes.read({"trace": None}) is None


def test_named_time_within_busy(recorded, raw):
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(raw)
    tab = scopes.reduce(scopes.reduce_profile(pd, scopes.op_scopes(raw)))
    tr = trace.reduce(trace.reduce_profile(pd))
    assert 0 < sum(tab["scope_s"].values()) <= tr["busy_s"]
    # every Pallas call carries a kernel name: the kernels' time is the
    # Pallas time that attn_kernel_share reads
    assert sum(tab["kernel_s"].values()) == pytest.approx(tr["pallas_s"],
                                                          rel=1e-9)
    assert tab["idle_total_s"] == pytest.approx(
        tr["window_s"] - tr["busy_s"], rel=1e-9)
    assert tab["steps"] == 1 and tab["window_s"] == tr["window_s"]


def test_idle_by_innermost_span(recorded, raw):
    """Idle time by span, counted on a 1-ns grid over the window's idle
    instants: each goes to the program's span that began last among those
    open, else to the harness's."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(raw)
    tab = scopes.reduce(scopes.reduce_profile(pd, scopes.op_scopes(raw)))
    lo, hi = (int(round(x)) for x in _window(recorded))
    (evs,) = recorded["device"].values()
    merged: list = []
    for a, b in sorted((max(int(round(a)), lo), min(int(round(b)), hi))
                       for _, a, b, _ in evs):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        elif b > a:
            merged.append([a, b])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    idle = np.concatenate([np.arange(a, b, dtype=np.int64)
                           for a, b in zip(edges[::2], edges[1::2])
                           if b > a]) - lo            # idle instants
    label = np.full(idle.size, -1)
    # the harness's spans first, then the program's over them; within
    # each, later starts win
    spans = sorted((n.startswith("repro."), int(round(a)), -int(round(b)), n)
                   for n, a, b, *_ in recorded["host"] if n != "bench.window")
    names = sorted({n for *_, n in spans})
    for _, a, nb, n in spans:
        sel = (idle >= a - lo) & (idle < -nb - lo)
        label[sel] = names.index(n)
    assert tab["idle_total_s"] == pytest.approx(idle.size * 1e-9, rel=1e-6)
    assert any(n.startswith("repro.") for n in tab["idle_s"])
    for i, n in enumerate(names):
        got = tab["idle_s"].get(n, 0.0)
        assert got == pytest.approx((label == i).sum() * 1e-9, abs=2e-9), n
    assert sum(tab["idle_s"].values()) <= tab["idle_total_s"] + 1e-12


def test_cli_prints_the_table(raw, tmp_path, capsys):
    (tmp_path / "t.xplane.pb").write_bytes(raw)
    assert scopes.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    for n in scopes.SCOPES + scopes.SPANS:
        assert n in out
    assert "idle under a repro.* span" in out
    assert scopes.main([str(tmp_path / "none")]) == 1
