"""The check catches a broken timed path, and the control.

Each test drives a run past the harness's look for a chip, with the
program broken underneath, and sees ``correct`` come out false under the
smoke limits, while the sound run comes out true.  The control (the
reference computed with float8 operands, the precision below the
configuration's) must fail too.  A one-chip cell has no exchange between
chips to leave out."""
import copy
import dataclasses

import numpy as np
import pytest

import bench_smoke
from bench import check


@pytest.fixture(scope="module")
def sound():
    """A sound run, freed, with the reference over its checked steps."""
    run = bench_smoke.make_run()
    run.start(warm=False)
    run.free()
    return run, run.reference()


@pytest.fixture(scope="module")
def reference(sound):
    return sound[1]


def _correct(prog, ref) -> bool:
    ok, _ = check.judge(check.gaps(prog, ref), bench_smoke.LIMITS)
    return ok and prog["ok"]


def _program(monkeypatch, fault=None):
    from repro.train.engine import TreeTrainEngine
    if fault is not None:
        fault(monkeypatch, TreeTrainEngine)
    run = bench_smoke.make_run()
    prog = run.start(warm=False)
    run.free()
    return prog


def unchanged_state(monkeypatch, engine):
    def step(self, params, opt_state, plan):
        _, scal = self.accumulate(params, plan)
        loss = float(np.asarray(scal)[0])
        return params, opt_state, {"loss": loss, "grad_norm": 1.0}
    monkeypatch.setattr(engine, "step", step)


def half_batch(monkeypatch, engine):
    real = engine.accumulate

    def accumulate(self, params, plan):
        p = copy.copy(plan)
        if p.packed is not None:
            inp = dict(p.packed.inputs)
            B = inp["weight"].shape[0]
            inp["weight"] = inp["weight"].at[B // 2:].set(0.0)
            p.packed = dataclasses.replace(p.packed, inputs=inp)
        p.partition = None
        p.num_trees = max(1, plan.num_trees // 2)
        return real(self, params, p)
    monkeypatch.setattr(engine, "accumulate", accumulate)


def altered_token(monkeypatch, engine):
    real = engine.accumulate

    def accumulate(self, params, plan):
        p = copy.copy(plan)
        if p.packed is not None:
            inp = dict(p.packed.inputs)
            inp["tokens"] = inp["tokens"].at[0, 5].add(1) % 512
            p.packed = dataclasses.replace(p.packed, inputs=inp)
        else:
            part = copy.copy(p.partition)
            wave = copy.copy(part.waves[0])
            batch = dict(wave.batch)
            batch["tokens"] = batch["tokens"].copy()
            batch["tokens"][0, 5] = (batch["tokens"][0, 5] + 1) % 512
            wave.batch = batch
            part.waves = [wave] + list(part.waves[1:])
            p.partition = part
        return real(self, params, p)
    monkeypatch.setattr(engine, "accumulate", accumulate)


def test_sound_run_is_correct(monkeypatch, reference):
    assert _correct(_program(monkeypatch), reference)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_token],
                         ids=lambda f: f.__name__)
def test_fault_is_caught(monkeypatch, reference, fault):
    assert not _correct(_program(monkeypatch, fault), reference)


def test_control_fails(sound):
    run, reference = sound
    control = run.reference("fp8")
    control["ok"] = True
    assert not _correct(control, reference)
