"""bench/work.py, and the dense family's work count, against a hand
count on a three-leaf tree."""
import numpy as np

import bench_smoke  # noqa: F401  (puts the repository on sys.path)
from bench import families
from bench.gen import Node
from bench.work import tree_counts


def three_leaf_tree():
    #        root (3)
    #       /        \
    #     a (2)      b (4)
    #    /    \
    #  c (1)  d (2)
    a = Node(2, [Node(1), Node(2)])
    return Node(3, [a, Node(4)])


def test_tree_counts_by_hand():
    # root: 1+2+3 = 6 pairs; a: 2·3 + 1+2 = 9; c: 1·5 + 1 = 6;
    # d: 2·5 + 1+2 = 13; b: 4·3 + 1+2+3+4 = 22  →  56 pairs, 12 tokens
    assert tree_counts(three_leaf_tree()) == (12, 56)


def test_pairs_match_the_dense_mask():
    from bench.adapter import program_tree
    from repro.core.tree import serialize_tree, visibility_mask
    rng = np.random.default_rng(0)
    t = three_leaf_tree()
    for n in t.nodes():
        n.tokens = rng.integers(0, 50, n.length).astype(np.int32)
        n.trained = np.ones(n.length, bool)
    ser = serialize_tree(program_tree(t))
    assert int(visibility_mask(ser).sum()) == tree_counts(t)[1]


def test_step_work_formula():
    c = bench_smoke.SMOKE_GQA
    fam = families.load(c)
    d = fam.dims(c)
    w = fam.step_work([three_leaf_tree(), three_leaf_tree()], d)
    H, Kh, hd, L, D, F, V = (d[k] for k in ("H", "Kh", "hd", "L", "D", "F",
                                            "V"))
    n_mm = L * (D * (2 * H * hd + 2 * Kh * hd) + 3 * D * F) + D * V
    assert fam.matmul_params(d) == n_mm
    assert w["tokens"] == 24 and w["pairs"] == 112
    assert w["attn_flops"] == 12 * H * hd * L * 112
    assert w["flops"] == 6 * n_mm * 24 + w["attn_flops"]
    assert w["attn_bytes"] == 2 * 4 * (H + Kh) * hd * L * 24


def test_published_matmul_parameters():
    from bench.adapter import load_config

    def matmul_params(name):
        c = load_config(name)
        fam = families.load(c)
        return fam.matmul_params(fam.dims(c))
    # 24 layers of 12 845 056 plus the 155 582 464 head; 10 layers of
    # 46 792 704 plus the 233 373 696 tied head
    assert matmul_params("qwen1.5-0.5b") == 463_863_808
    assert matmul_params("qwen2-1.5b-depthcut") == 701_300_736
