"""The work a step needs, counted from its trees (never from the
program's layout): each family's ``step_work`` (``bench/families/``)
counts from ``tree_counts``.

Model FLOPs are 6 · (the matmul parameters a token runs through) per
unique token, plus the attention scores of the visible pairs: the
(query, key) pairs the trees' ancestor structure allows (each token sees
itself and the tokens above it on its path).  Padding,
rematerialization and the backward's recomputed scores are not counted,
so a share of the peak computed from this cannot pass 100 % unless the
time leaves out part of the work.

The attention kernels' useful work is the attention part of that count;
their useful bytes are q, k, v, o, dO, dq, dk and dv of the unique
tokens in bfloat16, once per layer."""
from __future__ import annotations


def tree_counts(root) -> tuple[int, int]:
    """(unique tokens, visible (query, key) pairs) of one tree."""
    tokens = pairs = 0
    stack = [(root, 0)]
    while stack:
        n, above = stack.pop()
        L = n.length
        tokens += L
        pairs += L * above + L * (L + 1) // 2
        stack.extend((ch, above + L) for ch in n.children)
    return tokens, pairs
