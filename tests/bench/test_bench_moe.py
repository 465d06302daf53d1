"""The moe family (``bench/families/moe.py``), its configuration and the
expert readers.

- The plain reference equals the program (``models/moe.py`` through the
  engine) on seeded weights at a small size: the checked losses, the
  first gradient per entry and the change after the checked steps — on a
  share of the experts (4 of 8 held) and on all of them.
- ``qwen3-30b-a3b-ep8`` passes its family and builds the program's
  configuration; the family refuses what it would build as something
  else.
- ``expert_gmm_ms`` and ``expert_gmm_roofline`` read the Pallas time
  outside the tree-attention kernels.
"""
import copy
import math

import pytest

import bench_smoke
from bench import check

SMOKE_MOE = {
    "hidden_act": "silu", "hidden_size": 64, "initializer_range": 0.02,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "num_experts": 4, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "router_aux_loss_coef": 0.1,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "tie_word_embeddings": False,
    "vocab_size": 500, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "use_sliding_window": False, "attention_bias": False,
    "source": "smoke", "program": {
        "family": "moe", "router_experts": 8, "held_experts": [2, 6],
        "mlp_activation": "swiglu", "dtype": "float32",
        "vocab_pad_multiple": 8, "remat": "none"}}
SMOKE_ALL = copy.deepcopy(SMOKE_MOE)
SMOKE_ALL.update(num_experts=8, router_aux_loss_coef=0.001,
                 tie_word_embeddings=True)
SMOKE_ALL["program"]["held_experts"] = [0, 8]


@pytest.mark.parametrize("c", [SMOKE_MOE, SMOKE_ALL],
                         ids=["share_untied", "all_tied"])
def test_reference_matches_program(c):
    run = bench_smoke.make_run(c, dict(bench_smoke.TRAFFIC, ref_steps=2))
    prog = run.start(warm=False)
    run.free()
    ref = run.reference()
    g = check.gaps(prog, ref)
    # float32 both sides on the CPU
    assert g["loss_gap"] < 1e-5, g
    assert g["grad_gap"] < 1e-4, g
    assert g["change_gap"] < bench_smoke.LIMITS["change_gap"]["limit"], g


def test_router_loss_is_in_the_reference():
    """The reference's loss moves with the router-loss coefficient by the
    per-branch term, which is about K at an untrained router."""
    from bench.reference import run as ref_run
    run = bench_smoke.make_run(SMOKE_MOE)
    steps = run.pool[:1]
    R = bench_smoke.TRAFFIC["seq_len"] * 2
    off = dict(SMOKE_MOE, router_aux_loss_coef=0.0)
    a = ref_run(SMOKE_MOE, bench_smoke.SEED, steps, run.opt, R)["loss"][0]
    b = ref_run(off, bench_smoke.SEED, steps, run.opt, R)["loss"][0]
    # Σ over layers of coef · (≈ K) per branch, branches weighted to 1
    per_layer = (a - b) / (0.1 * SMOKE_MOE["num_hidden_layers"])
    assert 0.9 * SMOKE_MOE["num_experts_per_tok"] < per_layer < \
        1.5 * SMOKE_MOE["num_experts_per_tok"], per_layer


def test_weight_layout_is_the_programs():
    import jax

    from bench import families
    from bench.adapter import _init
    from repro.models.model import init_params
    for c in (SMOKE_MOE, SMOKE_ALL):
        fam = families.load(c)
        cfg = fam.program_config(c)
        want = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
        got = jax.eval_shape(
            lambda: fam.to_program(c, _init(c, jax.random.key(0))))
        assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
            jax.tree.map(lambda a: (a.shape, a.dtype), want)


def test_the_configuration_passes_its_family():
    from bench import families
    from bench.adapter import load_config
    c = load_config("qwen3-30b-a3b-ep8")
    fam = families.load(c)
    d = fam.dims(c)
    assert (d["L"], d["D"], d["F"], d["H"], d["Kh"], d["hd"]) == \
        (6, 2048, 768, 32, 4, 128)
    assert (d["E"], d["X"], d["K"], d["V"], d["Vp"]) == \
        (128, 16, 8, 18992, 19072)
    cfg = fam.program_config(c)
    assert cfg.moe.held == (0, 16) and cfg.moe.num_experts == 128
    assert cfg.moe.router_aux_weight == 0.001
    assert cfg.moe.router_z_weight == 0.0 and cfg.attn.qk_norm
    for k, (was, now) in c["reduced"].items():
        assert c[k] == now and was != now
    # 646 M parameters on the chip: 568 M in the 6 layers, 77.8 M in the
    # embedding and head slices
    sh = fam.shapes(c)
    shapes = [s for k, s in sh.items() if k != "layers"] \
        + list(sh["layers"].values())
    n = sum(math.prod(s) for s in shapes)
    assert 640e6 < n < 652e6, n


@pytest.mark.parametrize("key,value", [
    ("shared_expert_intermediate_size", 768), ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2), ("use_sliding_window", True),
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("norm_topk_prob", False)])
def test_refusals(key, value):
    from bench import families
    c = dict(copy.deepcopy(SMOKE_MOE), **{key: value})
    with pytest.raises(ValueError, match=key):
        families.load(c)


def test_held_experts_must_match_num_experts():
    from bench import families
    c = copy.deepcopy(SMOKE_MOE)
    c["program"]["held_experts"] = [2, 7]
    with pytest.raises(ValueError, match="held_experts"):
        families.load(c)


def test_step_work_counts_held_expert_rows():
    from bench import families
    from bench.gen import Node
    from bench.adapter import load_config
    c = load_config("qwen3-30b-a3b-ep8")
    fam = families.load(c)
    d = fam.dims(c)
    t = Node(100, [Node(20), Node(30)])
    w = fam.step_work([t], d)
    assert w["tokens"] == 150
    rows = 150 * 8 * 16 / 128 * 6
    assert w["expert_rows"] == rows
    assert w["expert_flops"] == 18 * 2048 * 768 * rows
    assert w["expert_bytes"] == 2 * (6 * 2 * 3 * 16 * 2048 * 768
                                     + rows * 2 * 2048)


def _rec(monkeypatch, kernel_s, pallas_s=0.5, steps=4):
    from bench.metrics import expert_gmm_ms
    monkeypatch.setattr(expert_gmm_ms, "scopes",
                        lambda rec: {"kernel_s": kernel_s})
    step = dict(expert_flops=1e12, expert_bytes=1e9)
    return {"trace": {"pallas_s": pallas_s, "chips": 1,
                      "steps": [step] * steps},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_expert_readers(monkeypatch):
    from bench.metrics import expert_gmm_ms, expert_gmm_roofline
    rec = _rec(monkeypatch, {"tree_attn_fwd": 0.1, "tree_attn_dq": 0.05,
                             "tree_attn_dkv": 0.05})
    assert expert_gmm_ms.read(rec) == pytest.approx(1e3 * 0.3 / 4)
    want = 100 * max(4e12 / 197e12, 4e9 / 819e9) / 0.3
    assert expert_gmm_roofline.read(rec) == pytest.approx(want)


def test_expert_readers_without_a_trace(monkeypatch):
    from bench.metrics import expert_gmm_ms, expert_gmm_roofline
    rec = _rec(monkeypatch, {})
    rec["trace"] = None
    assert expert_gmm_ms.read(rec) is None
    assert expert_gmm_roofline.read(rec) is None
    rec = _rec(monkeypatch, {"tree_attn_fwd": 0.5})      # no expert time
    assert expert_gmm_roofline.read(rec) is None
