"""The work a step needs, counted from its trees (never from the
program's layout).

Model FLOPs = 6 · (matmul parameters of the layers and the LM head) ·
unique tokens + 12 · H · hd · layers · visible pairs, where the visible
pairs are the (query, key) pairs the trees' ancestor structure allows
(each token sees itself and the tokens above it on its path).  Padding,
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


def matmul_params(d: dict) -> int:
    q, kv = d["H"] * d["hd"], d["Kh"] * d["hd"]
    per_layer = d["D"] * (2 * q + 2 * kv) + 3 * d["D"] * d["F"]
    return d["L"] * per_layer + d["D"] * d["V"]


def step_work(trees, d: dict) -> dict:
    tokens = pairs = 0
    for t in trees:
        a, b = tree_counts(t)
        tokens += a
        pairs += b
    attn_flops = 12 * d["H"] * d["hd"] * d["L"] * pairs
    attn_bytes = 2 * 4 * (d["H"] + d["Kh"]) * d["hd"] * d["L"] * tokens
    return dict(tokens=tokens, pairs=pairs,
                flops=6 * matmul_params(d) * tokens + attn_flops,
                attn_flops=attn_flops, attn_bytes=attn_bytes)
