"""Family code is reached through ``bench.families`` alone.

- The seed's weights and the reference's readings on the smoke sizes are
  pinned in ``families_fixture.json``, recorded from the harness before
  its dense code moved into ``bench/families/dense.py``: the same seed
  has to give the same weights, losses, gradient and change.
- A configuration whose ``program.family`` names no module, or that the
  dense family would build as something it is not, is refused before the
  harness looks for a chip: exit code 2, no result line.
- A family that exists only as a new file is found by its name alone.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_smoke

FIXTURE = os.path.join(os.path.dirname(__file__), "families_fixture.json")
CASES = {"mha_untied": bench_smoke.SMOKE_MHA, "gqa_tied": bench_smoke.SMOKE_GQA}
PIN_STEPS = 2


def readings(c: dict) -> dict:
    """The seed's weight norms, and the reference over the first
    ``PIN_STEPS`` steps of the smoke traffic's pool."""
    from bench.adapter import entry_norms, flatten_norms, init_weights
    w = flatten_norms(entry_norms(init_weights(c, bench_smoke.SEED)))
    run = bench_smoke.make_run(c, dict(bench_smoke.TRAFFIC,
                                       ref_steps=PIN_STEPS))
    ref = run.reference(steps_trees=run.pool[:PIN_STEPS])
    return dict(weights=w, loss=ref["loss"], grad_norm=ref["grad_norm"],
                grad=ref["grad"], change=ref["change"])


@pytest.fixture(scope="module")
def pinned():
    with open(FIXTURE) as f:
        return json.load(f)


def _same(got, want, rel=1e-6):
    if isinstance(want, dict):
        assert sorted(got) == sorted(want)
        for k in want:
            _same(got[k], want[k], rel)
        return
    if isinstance(want, list):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            _same(g, w, rel)
        return
    assert abs(got - want) <= rel * abs(want), (got, want)


@pytest.mark.parametrize("case", sorted(CASES))
def test_readings_are_pinned(pinned, case):
    _same(readings(CASES[case]), pinned[case])


def _checkout(tmp_path, config: dict):
    """A checkout of the benchmark alone, whose one cell runs ``config``."""
    shutil.copytree(os.path.join(bench_smoke.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "configs" / "probe.json").write_text(json.dumps(config))
    with open(os.path.join(bench_smoke.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [dict(bench["workloads"][0], name="probe",
                               config="probe")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _published(change: dict) -> dict:
    """qwen1.5-0.5b's configuration with ``change`` (``program.<key>``
    for the program's settings)."""
    from bench.adapter import load_config
    c = load_config("qwen1.5-0.5b")
    for k, v in change.items():
        if k.startswith("program."):
            c["program"][k[len("program."):]] = v
        else:
            c[k] = v
    return c


@pytest.mark.parametrize("change,named", [
    ({"program.family": "nosuch"}, "bench/families/nosuch.py"),
    ({"num_experts": 128, "moe_intermediate_size": 768}, "num_experts")],
    ids=["unknown_family", "dense_with_experts"])
def test_refused_before_the_chip(tmp_path, change, named):
    """Refused by ``load_cell``, before the look for a TPU: exit code 2,
    no result line, and the reason names the key."""
    root = _checkout(tmp_path, _published(change))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "probe", "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert named in p.stderr and "needs a TPU" not in p.stderr, p.stderr


DENSE_REFUSES = {
    "num_experts": 128, "n_routed_experts": 64, "num_local_experts": 8,
    "moe_intermediate_size": 768, "layer_types": ["full_attention"] * 24,
    "use_sliding_window": True, "hidden_act": "gelu",
    "program.mlp_activation": "geglu"}


@pytest.mark.parametrize("key", list(DENSE_REFUSES))
def test_dense_refuses_what_it_would_ignore(key):
    """A configuration the dense family would build as a dense model
    without a word (experts, other kinds of layer, a sliding window, an
    MLP other than SwiGLU) is refused, naming its key."""
    from bench import families
    with pytest.raises(ValueError, match=key):
        families.load(_published({key: DENSE_REFUSES[key]}))


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(bench_smoke.ROOT, "bench",
                                            "configs"))))
def test_configurations_pass_their_family(name):
    from bench import families
    from bench.adapter import load_config
    c = load_config(name)
    assert families.load(c).__name__ == f"bench.families.{c['program']['family']}"


def test_a_new_family_file_is_found_by_its_name(tmp_path, monkeypatch):
    """A family that is only a new file (here the dense family under
    another name) drives the smoke traffic through ``Run`` and gives the
    dense family's numbers, program and reference alike."""
    import copy

    from bench import families
    (tmp_path / "dense_alias.py").write_text(
        "from bench.families.dense import *  # noqa: F403\n")
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(tmp_path)])
    alias = copy.deepcopy(bench_smoke.SMOKE_MHA)
    alias["program"]["family"] = "dense_alias"
    traffic = dict(bench_smoke.TRAFFIC, ref_steps=2)
    got = {}
    try:
        for c in (bench_smoke.SMOKE_MHA, alias):
            run = bench_smoke.make_run(c, traffic)
            prog = run.start(warm=False)
            run.free()
            got[c["program"]["family"]] = (run.fam.__name__, prog,
                                           run.reference())
    finally:
        sys.modules.pop("bench.families.dense_alias", None)
    assert got["dense_alias"][0] == "bench.families.dense_alias"
    assert got["dense_alias"][1:] == got["dense"][1:]
