"""Traffic generator: one general generator for every traffic file.

A traffic file (``bench/traffic/<name>.json``) names a tree ``kind`` and
its parameters.  The shapes of the trees (segment lengths and branching)
come from the file's fixed ``shape_seed``, so every run trains the same
multiset of trees; the run's ``--seed`` draws the token ids, which tokens
are trained, and the order in which the trees reach the steps.

The tree shapes follow the program's synthetic generators
(``repro.data.synthetic.agentic_tree`` and ``chain_tree``), copied here
so that a change to the program cannot change the yardstick.  Trees are
plain ``Node`` objects; ``bench.adapter`` turns them into the program's
own tree type.
"""
from __future__ import annotations

import numpy as np


class Node:
    """One tree node: a token segment, its trained mask, its children."""

    __slots__ = ("length", "tokens", "trained", "children")

    def __init__(self, length: int, children=None):
        self.length = int(length)
        self.tokens = None
        self.trained = None
        self.children = list(children or [])

    def nodes(self):
        stack = [self]
        while stack:
            n = stack.pop()
            yield n
            stack.extend(reversed(n.children))

    def paths(self):
        """Every root-to-leaf path, as a list of nodes, in DFS leaf order."""
        out = []

        def rec(n, prefix):
            prefix = prefix + [n]
            if not n.children:
                out.append(prefix)
            for c in n.children:
                rec(c, prefix)

        rec(self, [])
        return out

    def unique_tokens(self) -> int:
        return sum(n.length for n in self.nodes())


def _agentic_shape(rng, num_turns: int, turn_len_range, tool_branch_prob: float,
                   think_branch_prob: float, max_parallel_tools: int, **_) -> Node:
    lo, hi = turn_len_range

    def build(turn: int) -> Node:
        node = Node(rng.integers(lo, hi))
        if turn >= num_turns:
            return node
        r = rng.random()
        if r < tool_branch_prob:
            k = int(rng.integers(2, max_parallel_tools + 1))
            node.children = [build(turn + 1) for _ in range(k)]
        elif r < tool_branch_prob + think_branch_prob:
            node.children = [build(turn + 1), build(turn + 1)]
        else:
            node.children = [build(turn + 1)]
        return node

    return build(0)


def _chain_shape(rng, length: int, seg_len_range, **_) -> Node:
    lo, hi = seg_len_range
    root = cur = Node(rng.integers(lo, hi))
    for _ in range(length - 1):
        nxt = Node(rng.integers(lo, hi))
        cur.children = [nxt]
        cur = nxt
    return root


SHAPES = {"agentic": _agentic_shape, "chain": _chain_shape}


def seed_words(seed: int) -> list[int]:
    """A run's seed as unsigned 32-bit words (any whole number works)."""
    s = int(seed)
    words = [1 if s < 0 else 0]
    s = abs(s)
    while True:
        words.append(s & 0xFFFFFFFF)
        s >>= 32
        if not s:
            return words


def make_pool(traffic: dict, seed: int, vocab_size: int,
              chips: int = 1) -> list[list[Node]]:
    """The cell's stream: ``pool_steps`` lists of trees, one per step.

    Every seed gets the same multiset of tree shapes; the seed fills the
    tokens and trained masks and permutes the trees across steps."""
    shape = SHAPES[traffic["kind"]]
    per_step = traffic["trees_per_step"] * chips
    n = traffic["pool_steps"] * per_step
    srng = np.random.default_rng(traffic["shape_seed"])
    trees = [shape(srng, **traffic["gen"]) for _ in range(n)]
    rng = np.random.default_rng(seed_words(seed) + [traffic["shape_seed"]])
    trees = [trees[i] for i in rng.permutation(n)]
    frac = traffic["gen"].get("trained_frac", 1.0)
    for t in trees:
        for node in t.nodes():
            node.tokens = rng.integers(0, vocab_size, node.length).astype(np.int32)
            node.trained = (rng.random(node.length) < frac) if frac < 1.0 \
                else np.ones(node.length, bool)
    return [trees[i:i + per_step] for i in range(0, n, per_step)]
