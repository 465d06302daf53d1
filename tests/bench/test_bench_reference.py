"""The plain reference against the program (``models/transformer.py``
through the engine) on the smoke sizes of both configurations: MHA with
an untied head, and GQA (3 query heads per KV head) with a tied head."""
import pytest

import bench_smoke
from bench import check


@pytest.mark.parametrize("c,lookahead", [(bench_smoke.SMOKE_MHA, 1),
                                         (bench_smoke.SMOKE_GQA, 1),
                                         (bench_smoke.SMOKE_MHA, 2)],
                         ids=["mha_untied", "gqa_tied", "mha_lookahead2"])
def test_reference_matches_program(c, lookahead):
    """With a lookahead the planner may move trees between steps: the
    reference follows the trees each executed plan trained."""
    traffic = dict(bench_smoke.TRAFFIC, ref_steps=2, lookahead=lookahead)
    run = bench_smoke.make_run(c, traffic)
    prog = run.start(warm=False)
    run.free()
    ref = run.reference()
    g = check.gaps(prog, ref)
    # float32 both sides on the CPU
    assert g["loss_gap"] < 1e-5, g
    assert g["grad_gap"] < 1e-4, g
    assert g["change_gap"] < bench_smoke.LIMITS["change_gap"]["limit"], g


def test_rows_keep_paths_apart():
    """A path never sees another in its row: a row's loss is the sum of
    the losses of the same row holding one path at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.adapter import init_weights
    from bench.gen import Node
    from bench.reference import row_loss, step_rows
    c = bench_smoke.SMOKE_GQA
    rng = np.random.default_rng(1)
    trees = [Node(20, [Node(9), Node(14)]), Node(30)]
    for t in trees:
        for n in t.nodes():
            n.tokens = rng.integers(0, 512, n.length).astype(np.int32)
            n.trained = np.ones(n.length, bool)
    w = init_weights(c, 5)
    f = jax.jit(lambda w, r: row_loss(c, False, w, r))
    (row,) = step_rows(trees, 128)
    assert set(row["seg"]) == {-1, 0, 1, 2}
    whole = float(f(w, {k: jnp.asarray(v) for k, v in row.items()}))
    parts = 0.0
    for j in range(3):
        keep = row["seg"] == j
        one = dict(tokens=np.where(keep, row["tokens"], 0),
                   seg=np.where(keep, row["seg"], -1),
                   pos=np.where(keep, row["pos"], 0),
                   weight=np.where(keep, row["weight"], 0.0))
        parts += float(f(w, {k: jnp.asarray(v) for k, v in one.items()}))
    assert np.isfinite(whole) and whole == pytest.approx(parts, rel=1e-5)


@pytest.mark.parametrize("c", [bench_smoke.SMOKE_MHA, bench_smoke.SMOKE_GQA],
                         ids=["mha_untied", "gqa_tied"])
def test_weight_layout_is_the_programs(c):
    """The benchmark's weights fill exactly the program's parameter tree."""
    import jax

    from bench import families
    from bench.adapter import _init
    from repro.models.model import init_params
    fam = families.load(c)
    cfg = fam.program_config(c)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    got = jax.eval_shape(lambda: fam.to_program(c, _init(c, jax.random.key(0))))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)


@pytest.mark.parametrize("c", [bench_smoke.SMOKE_MHA, bench_smoke.SMOKE_GQA],
                         ids=["mha_untied", "gqa_tied"])
def test_reference_spans_the_published_vocabulary(c):
    """Rows that pad the head past the published vocabulary carry no
    probability in the reference: whatever they hold, the loss is the
    same, and their gradient is zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import families
    from bench.adapter import init_weights
    from bench.gen import Node
    from bench.reference import row_loss, step_rows
    c = dict(c, vocab_size=509)
    d = families.load(c).dims(c)
    V, Vp = d["V"], d["Vp"]
    assert Vp > V
    rng = np.random.default_rng(2)
    tree = Node(12, [Node(7), Node(9)])
    for n in tree.nodes():
        n.tokens = rng.integers(0, V, n.length).astype(np.int32)
        n.trained = np.ones(n.length, bool)
    (row,) = step_rows([tree], 64)
    row = {k: jnp.asarray(v) for k, v in row.items()}
    f = jax.jit(jax.value_and_grad(lambda w: row_loss(c, False, w, row)))
    w = init_weights(c, 5)
    w2 = dict(w, embed=w["embed"].at[V:].set(3.0))
    if "lm_head" in w:
        w2["lm_head"] = w["lm_head"].at[:, V:].set(3.0)
    (l1, g), (l2, _) = f(w), f(w2)
    assert np.isfinite(float(l1)) and float(l1) == float(l2)
    pad = g["lm_head"][:, V:] if "lm_head" in g else g["embed"][V:]
    assert not np.any(np.asarray(pad))
