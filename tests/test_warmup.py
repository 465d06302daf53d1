"""AOT warmup engine (train/warmup): the precompiled-executable story.

  - planner pre-warm on a real stream → the engine replays with ZERO
    retraces and grads BIT-IDENTICAL to the uncached dispatch path;
  - the pipeline compiles a step's signatures before the engine
    consumes the step (prewarm overlap);
  - out-of-universe signatures take the honest slow path: a logged
    warning and a synchronous compile, never a crash;
  - the warmup compile list is exactly the enumerable signature
    universe, ordered packed-first then by simulated hit frequency;
  - the persistent jax compilation cache round-trips across fresh
    processes: the second process writes 0 new cache modules;
  - satellite: the cost model charges wave signatures at
    ``wave_compile`` (not ``compile_miss``) and the planner's shared
    ``CompileCacheSim`` counts per-signature hit frequency.
"""
import json
import logging
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.analysis.signatures import SignatureUniverse, step_signatures
from repro.core.plan_cost import (CompileCacheSim, CostWeights,
                                  packed_signature, score_packing,
                                  wave_signature)
from repro.data.loader import LoaderConfig
from repro.models.model import init_params
from repro.train.engine import TreeTrainEngine
from repro.train.exec_cache import ExecutableCache, arg_fingerprint, exec_key
from repro.train.optimizer import OptimizerConfig
from repro.train.planner import PlannerConfig, plans
from repro.train.warmup import (AOTWarmupService, compile_cache_files,
                                universe_signatures)


def _lc(**kw):
    base = dict(seq_len=64, batch_rows=2, trees_per_batch=2, mode="tree",
                kind="agentic", seed=11, auto_partition=True, capacity=32,
                gen_kwargs=dict(turn_len_range=(6, 14), num_turns=2))
    base.update(kw)
    return LoaderConfig(**base)


# ---------------------------------------------------------------------------
# Host-only: enumeration, ordering, cost model (no compiles)
# ---------------------------------------------------------------------------

def test_universe_signatures_match_enumeration():
    """The warmup compile list and SignatureUniverse.enumerate_signatures
    are independent implementations — they must agree exactly, and every
    entry must pass the runtime ``contains`` check the engine applies on
    a cache miss (the treelint warmup pass proves the same invariant on
    the lint configs; this pins it at unit scope)."""
    lc = _lc()
    pc = PlannerConfig()
    caps = (16, 2, 16, 2)
    universe = SignatureUniverse(
        seq_len=lc.seq_len, batch_rows=lc.batch_rows,
        num_replicas=pc.num_replicas, max_rows=lc.batch_rows,
        capacity=lc.capacity)
    warm = universe_signatures(lc, pc, caps)
    enum = universe.enumerate_signatures(*caps)
    assert set(warm) == set(enum)
    assert len(warm) == len(set(warm)), "duplicate signatures in list"
    for sig in warm:
        ok, why = universe.contains(sig)
        assert ok, f"{sig}: {why}"
    # the enumeration is a strict subset of the loose bounding box
    assert len(enum) <= universe.count(*caps)


def test_wave_buckets_floor_at_a_sixteenth_of_the_row():
    """Ancestor runs and capture paths shorter than a sixteenth of the row
    share one bucket, so a stream's waves compile few programs; every
    observed signature stays in the enumerated universe."""
    from repro.core.plan_cost import wave_path_floor
    lc = _lc(seq_len=256, capacity=128, trees_per_batch=3,
             gen_kwargs=dict(turn_len_range=(20, 70), num_turns=3))
    assert wave_path_floor(256) == 16 and wave_path_floor(2048) == 128
    cfg = tiny_cfg("dense")
    sigs = {s for ps in plans(cfg, lc, 6) for s in step_signatures(ps)
            if s[0] == "wave"}
    assert sigs, "no partitioned step in this stream; adjust seeds"
    for _, _, _, anc, ncut, plen, _ in sigs:
        assert anc == 0 or anc >= 16
        assert plen == 0 or plen >= 16
    assert any(anc for _, _, _, anc, *_ in sigs)
    assert any(plen for *_, plen, _ in sigs)
    universe = SignatureUniverse(
        seq_len=lc.seq_len, batch_rows=lc.batch_rows, num_replicas=1,
        max_rows=lc.batch_rows, capacity=lc.capacity)
    caps = [max(s[i] for s in sigs) for i in (3, 4, 5, 6)]
    enum = set(universe.enumerate_signatures(*caps))
    assert enum == set(universe_signatures(lc, PlannerConfig(), caps))
    assert {s for s in sigs if s[4]} <= enum     # root and cut waves


def test_signature_list_priority():
    """Packed compiles first (every step needs it), then wave buckets in
    descending simulated-hit-frequency order — the hottest bucket is warm
    soonest when warmup runs on a background thread."""
    lc = _lc()
    cfg = tiny_cfg("dense")
    params = init_params(cfg, jax.random.key(0))
    sim = CompileCacheSim()
    hot = wave_signature(2, lc.seq_len, 8, 2, 8, 2)
    cold = wave_signature(2, lc.seq_len, 8, 2, 16, 2)
    for _ in range(5):
        sim.commit([hot])
    sim.commit([cold])
    svc = AOTWarmupService(cfg, lc, params=params, sim=sim,
                           caps=(16, 2, 16, 2))
    sigs = svc.signature_list()
    assert sigs[0][0] == "packed"
    waves = [s for s in sigs if s[0] == "wave"]
    assert waves.index(hot) < waves.index(cold)
    # budget keeps the hottest buckets: each signature costs two
    # executables (fwd+bwd), so max_compiles=4 admits two signatures
    svc.max_compiles = 4
    kept = list(svc._budgeted(sigs))
    assert len(kept) == 2 and sigs[0] in kept and hot in kept


def test_cost_model_charges_wave_compile():
    """score_packing bills a NEW wave signature at ``wave_compile`` and a
    new packed signature at ``compile_miss`` — a second scoring against
    a cache that has seen them charges neither."""
    w = CostWeights(pad=0.0, compile_miss=100.0, wave_compile=7.0,
                    live_block=0.0, comm_byte=0.0)
    psig = packed_signature(2, 64)
    wsig = wave_signature(2, 64, 8, 1, 8, 1)
    cache = CompileCacheSim()
    cost = score_packing([], 64, signatures=[psig, wsig], cache=cache,
                         weights=w)
    assert cost.total == pytest.approx(107.0)
    assert cost.new_signatures == 2
    cache.commit([psig, wsig])
    again = score_packing([], 64, signatures=[psig, wsig], cache=cache,
                          weights=w)
    assert again.total == pytest.approx(0.0)
    assert again.new_signatures == 0
    assert cache.freq[psig] == 1 and cache.freq[wsig] == 1
    cache.commit([wsig])
    assert cache.freq[wsig] == 2


def test_exec_key_fingerprints_shapes_not_values():
    """Python-int leaves fingerprint by TYPE (weak-typed scalars: one
    executable serves every value) while array leaves fingerprint by
    shape+dtype — a changed shape is a different executable."""
    sig = packed_signature(2, 64)
    a = {"tokens": np.zeros((2, 64), np.int32), "num_trees": 3}
    b = {"tokens": np.zeros((2, 64), np.int32), "num_trees": 7}
    c = {"tokens": np.zeros((2, 128), np.int32), "num_trees": 3}
    assert exec_key("packed", sig, (a,)) == exec_key("packed", sig, (b,))
    assert exec_key("packed", sig, (a,)) != exec_key("packed", sig, (c,))
    assert arg_fingerprint((a,)) == arg_fingerprint((b,))


# ---------------------------------------------------------------------------
# Compiled: prewarm overlap, zero retraces, bit-identical grads
# ---------------------------------------------------------------------------

def test_prewarm_stream_zero_retraces_bitident_grads():
    """Planner pipeline with ``warmup=svc``: every step's signatures are
    compiled BEFORE the engine consumes the step (prewarm overlap), the
    replay takes 0 retraces with 0 exposed compile wait, and the grads
    are bit-identical to an engine running the plain jit dispatch path
    (no executable cache) — AOT compilation is a pure latency move."""
    cfg = tiny_cfg("dense")
    lc = _lc()
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=2)
    params = init_params(cfg, jax.random.key(1))
    svc = AOTWarmupService(cfg, lc, params=params, opt_cfg=opt_cfg,
                           donate=False)
    eng = TreeTrainEngine(cfg, opt_cfg, donate=False,
                          exec_cache=svc.cache, universe=svc.universe)
    ref = TreeTrainEngine(cfg, opt_cfg, donate=False)
    steps = 0
    for ps in plans(cfg, lc, 2, warmup=svc):
        plan = ps.execution_plan()
        # prewarm overlap: the pipeline's build thread already compiled
        # this step's signatures before handing the plan over
        missing = set(step_signatures(ps)) - svc.cache.signatures()
        assert not missing, f"not prewarmed: {missing}"
        g_aot, s_aot = eng.accumulate(params, plan)
        g_ref, s_ref = ref.accumulate(params, plan)
        for a, b in zip(jax.tree.leaves(g_aot), jax.tree.leaves(g_ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(s_aot),
                                      np.asarray(s_ref))
        steps += 1
    assert steps == 2
    assert eng.retraces == 0, f"{eng.retraces} retraces after prewarm"
    assert eng.compile_wait_s == 0.0
    assert not svc.errors, svc.errors[:3]
    assert svc.prewarmed == len(svc.cache) > 0


def test_out_of_universe_sig_warns_not_crashes(caplog):
    """A signature outside the enumerable universe compiles on the
    honest synchronous slow path with a logged warning naming why the
    planner escaped — never an exception."""
    cfg = tiny_cfg("dense")
    # packed-only stream (no partitioning) keeps this to two compiles
    lc = _lc(auto_partition=False, capacity=None, mode="tree",
             gen_kwargs=dict(turn_len_range=(4, 8), num_turns=1))
    # a universe whose caps exclude the real batch: batch_rows=1 makes
    # the actual packed (2, 64) signature out-of-universe
    universe = SignatureUniverse(seq_len=lc.seq_len, batch_rows=1,
                                 num_replicas=1, max_rows=1, capacity=1)
    ok, _ = universe.contains(packed_signature(2, lc.seq_len))
    assert not ok
    eng = TreeTrainEngine(cfg, donate=False, exec_cache=ExecutableCache(),
                          universe=universe)
    params = init_params(cfg, jax.random.key(2))
    ps = next(iter(plans(cfg, lc, 1)))
    with caplog.at_level(logging.WARNING, logger="repro.train.engine"):
        grads, scal = eng.accumulate(params, ps.execution_plan())
    assert eng.retraces >= 1
    assert any("out-of-universe" in r.message for r in caplog.records)
    assert np.isfinite(float(np.asarray(scal)[0]))
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(grads))


# ---------------------------------------------------------------------------
# Persistent compilation cache across fresh processes
# ---------------------------------------------------------------------------

_PERSIST_SNIPPET = """
import json, sys
import jax, jax.numpy as jnp
from repro.train.warmup import configure_compile_cache, compile_cache_files
d = configure_compile_cache()
before = compile_cache_files(d)
f = jax.jit(lambda x: (x @ x.T).sum() + 1.0)
out = float(f(jnp.arange(48.0 * 16).reshape(48, 16)))
print(json.dumps({"new": compile_cache_files(d) - before, "out": out}))
"""


def test_persistent_cache_roundtrip(tmp_path):
    """configure_compile_cache wires jax's persistent compilation cache
    to $JAX_COMPILATION_CACHE_DIR: a second FRESH process compiling the
    same computation writes zero new cache modules and reproduces the
    same value."""
    cache_dir = str(tmp_path / "jax-cache")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    env.pop("XLA_FLAGS", None)
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _PERSIST_SNIPPET],
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert outs[0]["new"] > 0, "first process persisted nothing"
    assert outs[1]["new"] == 0, \
        f"warm restart recompiled {outs[1]['new']} modules"
    assert outs[1]["out"] == outs[0]["out"]
    assert compile_cache_files(cache_dir) == outs[0]["new"]


def test_compile_cache_dir_resolution(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins over any directory the code names;
    without it the cache sits at a fixed path inside the checkout."""
    from repro.train.warmup import compile_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert compile_cache_dir() == str(tmp_path / "env")
    assert compile_cache_dir(str(tmp_path / "arg")) == str(tmp_path / "env")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir(str(tmp_path / "arg")) == str(tmp_path / "arg")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache_dir() == os.path.join(root, ".jax_cache")


_FAILING_WARMUP = """
import importlib, sys
from repro.train import warmup

def broken(self, sig):
    self.errors.append(f"{sig}: injected failure")
    return 0

warmup.AOTWarmupService.warm_signature = broken
importlib.import_module("repro.launch." + sys.argv[1]).main(sys.argv[2:])
"""


@pytest.mark.parametrize("launcher,argv", [
    ("train", ["--arch", "qwen1.5-0.5b", "--smoke", "--steps", "1",
               "--seq-len", "512", "--aot-warmup"]),
    ("rl_loop", ["--arch", "qwen1.5-0.5b", "--smoke", "--steps", "1"]),
])
def test_launcher_fails_on_warmup_errors(tmp_path, launcher, argv):
    """A compile the warmup service could not do is a failed run: both
    launchers exit non-zero instead of printing the error count."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax-cache")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _FAILING_WARMUP, launcher,
                        *argv], env=env, capture_output=True, text=True,
                       timeout=300, cwd=root)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "warmup compile errors" in r.stderr, r.stderr[-2000:]
