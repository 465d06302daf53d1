"""bench/trace.py on a small recorded trace: 30 ms of a qwen15_tree_sft step
on one TPU v5 lite (trimmed from a chip run of ``bench/run.py --trace
1``), rebuilt as an XSpace and read back through JAX's ``ProfileData``."""
import json
import os

import numpy as np
import pytest

import bench_smoke  # noqa: F401  (puts the repository on sys.path)
from bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "trace_fixture.json")


def _xspace(tr: dict) -> str:
    """The fixture's intervals as an XSpace text proto (names as the TPU
    profiler writes them: the instruction's HLO text)."""
    def plane(pid, name, line, events, text):
        names = sorted({text(e) for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[text(e)]} offset_ps: "
            f"{int(round(e[1] * 1000))} duration_ps: "
            f"{int(round((e[2] - e[1]) * 1000))} }}\n" for e in events)
        meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                       f"{json.dumps(n)} }} }}\n" for n, i in ids.items())
        return (f"planes {{ id: {pid} name: {json.dumps(name)}\n"
                f"lines {{ id: 1 name: {json.dumps(line)} timestamp_ns: 0\n"
                f"{evs}}}\n{meta}}}\n")

    def op_text(e):
        if e[3] == "pallas":
            return (f"%{e[0]} = bf16[8] custom-call(), "
                    f"custom_call_target=\"tpu_custom_call\"")
        return f"%{e[0]} = f32[8] op()"

    out = plane(1, "/host:CPU", "python3", tr["host"], lambda e: e[0])
    for i, (p, evs) in enumerate(sorted(tr["device"].items())):
        out += plane(2 + i, p, "XLA Ops", evs, op_text)
    return out


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_profile_round_trip(recorded):
    from jax.profiler import ProfileData
    got = trace.reduce_profile(ProfileData.from_text_proto(_xspace(recorded)))
    (plane,) = recorded["device"]
    want = sorted((n, a, b, k) for n, a, b, k in recorded["device"][plane])
    have = sorted(tuple(e) for e in got["device"][plane])
    assert [(n, k) for n, _, _, k in have] == [(n, k) for n, _, _, k in want]
    assert np.allclose([e[1:3] for e in have], [e[1:3] for e in want],
                       atol=1e-3)
    assert sorted(h[0] for h in got["host"]) == sorted(
        h[0] for h in recorded["host"])


def test_reduction_by_hand(recorded):
    r = trace.reduce(recorded)
    (lo, hi), = [(a, b) for n, a, b in recorded["host"] if n == "bench.window"]
    (evs,) = recorded["device"].values()
    # busy time by a 1 ns grid over the window
    grid = np.zeros(int(hi - lo) + 1, bool)
    for _, a, b, _ in evs:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int(round(a - lo)):int(round(b - lo))] = True
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-9, rel=1e-3)
    pallas = sum(min(b, hi) - max(a, lo) for _, a, b, k in evs
                 if k == "pallas" and b > lo and a < hi)
    assert pallas > 0 and r["pallas_s"] == pytest.approx(pallas * 1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(g for _, g in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
    assert r["collective_s"] == 0.0 and r["chips"] == 1


def test_self_times_nest():
    evs = [(0, 100, "while.1", "op"), (10, 30, "fusion.1", "op"),
           (40, 90, "custom-call.1", "pallas"), (50, 60, "fusion.2", "op")]
    got = dict(trace._self_times(evs))
    assert got == {"while.1": 30, "fusion.1": 20, "custom-call.1": 40,
                   "fusion.2": 10}
