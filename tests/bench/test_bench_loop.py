"""The harness drives the launcher's path: with the same arguments, the
same trees and the same weights, ``repro.launch.train.main`` and the
harness's checked steps give the same losses (qwen1.5-0.5b --smoke)."""
import pytest

import bench_smoke
from bench.adapter import init_weights


def test_harness_steps_match_launcher(monkeypatch):
    import repro.launch.train as train
    from repro.configs import get_config
    t = bench_smoke.TRAFFIC
    run = bench_smoke.make_run()
    # the bench's smoke sizes are the program's qwen1.5 smoke config
    ours = run.cfg.replace(name="x", source="")
    assert ours == get_config("qwen1.5-0.5b", smoke=True).replace(
        name="x", source="")
    prog = run.start(warm=False)
    run.free()

    real_plans = train.plans
    # one device, as the harness's cell asks, whatever this process holds
    monkeypatch.setattr(train, "make_host_mesh", lambda: run.mesh)
    monkeypatch.setattr(
        train, "init_params",
        lambda cfg, key: init_weights(bench_smoke.SMOKE_MHA, bench_smoke.SEED,
                                      program=True))
    monkeypatch.setattr(
        train, "plans",
        lambda cfg, lc, n, pc, warmup=None: real_plans(
            cfg, lc, iter(run.ptrees[:n]), pc, warmup=warmup))
    res = train.main([
        "--arch", "qwen1.5-0.5b", "--smoke", "--steps", "3",
        "--seq-len", str(t["seq_len"]), "--rows", "2",
        "--trees", str(t["trees_per_step"]), "--auto-partition",
        "--seed", str(bench_smoke.SEED), "--lr", str(t["optimizer"]["lr"])])
    losses = [h["loss"] for h in res["history"]]
    assert len(losses) == 3
    assert losses == pytest.approx(prog["loss"], rel=1e-6)
