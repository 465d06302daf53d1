"""Trajectory trees and DFS serialization (paper §3.1–3.2).

A trajectory tree is a rooted tree whose nodes hold token segments; each
root-to-leaf path is one complete trajectory.  DFS serialization lays every
token out exactly once; per-token metadata arrays make the serialized
sequence *equivalent* to running every path independently:

  - ``kv_last[j]``  : DFS index of the last token in node(j)'s subtree.
    Token i may attend to token j  iff  ``j <= i and kv_last[j] >= i`` —
    this single int per key encodes causality + same-path visibility, and
    also separates multiple packed trees in one row for free.
  - ``pos_ids[t]``  : depth-based position (position the token would have in
    its standalone root-to-leaf sequence) — Eq. (9); makes RoPE exact.
  - ``weight[t]``   : λ_t = g_t / K  for trained tokens, 0 otherwise — Eq. (4).
  - ``prev_idx[t]`` : DFS index of the token whose *logits* predict token t
    (the preceding token on t's path).  Within a node this is t−1; at a node
    start it is the parent node's last token.  Several children of a
    branching node gather the same parent row — their losses (and gradients)
    accumulate there exactly as the per-branch baseline would.
  - ``node_id[t]``, ``chunk_parent`` : SSM chunk-grid metadata (§3.2 SSM).
  - ``aux_weight[t]`` : Σ of λ over the branches whose last token is t —
    where a branch's own per-branch terms (the MoE router loss) sit.

All host-side, numpy only; the jitted model consumes the arrays.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterator, Optional

import numpy as np


@dataclass
class TreeNode:
    """One node: a token segment plus children."""

    tokens: np.ndarray                      # int32 [len]
    trained: Optional[np.ndarray] = None    # bool  [len]; True = model output (gets loss)
    advantage: Optional[np.ndarray] = None  # f32   [len]; RL per-token advantage
    children: list["TreeNode"] = field(default_factory=list)
    # GRPO-style per-*branch* advantage: meaningful on leaves (a branch is
    # one root-to-leaf trajectory); None = 1.0.  Under loss_mode="rl" a
    # shared token's weight is Σ_{branches through it} A_b / K, which with
    # A≡1 reduces bit-exactly to sep_avg (g_t / K).
    branch_adv: Optional[float] = None

    def __post_init__(self) -> None:
        self.tokens = np.asarray(self.tokens, dtype=np.int32)
        if self.trained is None:
            self.trained = np.ones_like(self.tokens, dtype=bool)
        else:
            self.trained = np.asarray(self.trained, dtype=bool)
        if self.advantage is not None:
            self.advantage = np.asarray(self.advantage, dtype=np.float32)

    @property
    def size(self) -> int:
        return int(self.tokens.shape[0])


@dataclass
class TrajectoryTree:
    root: TreeNode

    # ---- basic structure ----------------------------------------------
    def nodes(self) -> Iterator[TreeNode]:
        stack = [self.root]
        while stack:
            n = stack.pop()
            yield n
            stack.extend(reversed(n.children))

    def num_unique_tokens(self) -> int:
        return sum(n.size for n in self.nodes())

    def num_leaves(self) -> int:
        return sum(1 for n in self.nodes() if not n.children)

    def num_nodes(self) -> int:
        return sum(1 for _ in self.nodes())

    def flat_tokens(self) -> int:
        """Token count of the baseline serialization X_base (every path,

        prefixes repeated) — Eq. (7) / denominator of POR (Eq. 12)."""
        total = 0
        for path in self.paths():
            total += sum(n.size for n in path)
        return total

    def max_path_tokens(self) -> int:
        def rec(n: TreeNode) -> int:
            return n.size + (max((rec(c) for c in n.children), default=0))
        return rec(self.root)

    def por(self) -> float:
        """Potential Overlap Ratio — Eq. (12)."""
        flat = self.flat_tokens()
        return 1.0 - self.num_unique_tokens() / flat if flat else 0.0

    def paths(self) -> list[list[TreeNode]]:
        """All root-to-leaf node paths (one per leaf), in DFS leaf order."""
        out: list[list[TreeNode]] = []

        def rec(n: TreeNode, prefix: list[TreeNode]) -> None:
            prefix = prefix + [n]
            if not n.children:
                out.append(prefix)
            for c in n.children:
                rec(c, prefix)

        rec(self.root, [])
        return out

    def linearize_paths(self) -> list[dict[str, np.ndarray]]:
        """Per-branch baseline: one linear sequence per root-to-leaf path.

        Each path dict also carries ``branch_adv`` — the leaf's per-branch
        RL advantage (1.0 when unset) — so baseline packers can reproduce
        the GRPO-weighted objective per replicated branch."""
        seqs = []
        for path in self.paths():
            toks = np.concatenate([n.tokens for n in path])
            trained = np.concatenate([n.trained for n in path])
            adv = (np.concatenate([
                n.advantage if n.advantage is not None
                else np.ones(n.size, np.float32) for n in path]))
            leaf = path[-1]
            seqs.append(dict(tokens=toks, trained=trained, advantage=adv,
                             pos_ids=np.arange(toks.shape[0],
                                               dtype=np.int32),
                             branch_adv=float(leaf.branch_adv)
                             if leaf.branch_adv is not None else 1.0))
        return seqs


@dataclass
class SerializedTree:
    """DFS serialization of one tree (paper Eq. (8)) + equivalence metadata."""

    tokens: np.ndarray        # i32 [N]
    pos_ids: np.ndarray       # i32 [N] depth-based positions (Eq. 9)
    kv_last: np.ndarray       # i32 [N] last DFS index visible-to bound
    weight: np.ndarray        # f32 [N] λ_t (Eq. 4), already ×advantage for RL
    prev_idx: np.ndarray      # i32 [N] logits row predicting token t (−1: none)
    valid: np.ndarray         # bool [N] False = chunk-alignment padding
    node_id: np.ndarray       # i32 [N] DFS node index per token
    node_parent: np.ndarray   # i32 [num_nodes] parent node index (−1 for root)
    node_start: np.ndarray    # i32 [num_nodes] DFS start offset of node segment
    node_end: np.ndarray      # i32 [num_nodes] end offset (exclusive, incl. pad)
    num_paths: int            # K
    aux_weight: Optional[np.ndarray] = None  # f32 [N] λ of branches ending at t

    @property
    def n(self) -> int:
        return int(self.tokens.shape[0])

    def chunk_parent_map(self, chunk_size: int) -> np.ndarray:
        """Per-chunk parent chunk index for tree SSM state routing (§3.2).

        Requires the serialization to be chunk-aligned (every node starts on
        a chunk boundary).  Chunk c's parent is the previous chunk of the
        same node, or the *last chunk of the parent node*; −1 = zero state.
        """
        assert self.n % chunk_size == 0, "serialization not chunk-aligned"
        num_chunks = self.n // chunk_size
        cp = np.full(num_chunks, -1, dtype=np.int32)
        for nid in range(len(self.node_parent)):
            s, e = int(self.node_start[nid]), int(self.node_end[nid])
            if s == e:
                continue
            assert s % chunk_size == 0, "node not chunk-aligned"
            c0 = s // chunk_size
            c1 = (e + chunk_size - 1) // chunk_size
            p = int(self.node_parent[nid])
            if p < 0:
                cp[c0] = -1
            else:
                # last chunk of the parent node
                pe = int(self.node_end[p])
                cp[c0] = (pe - 1) // chunk_size
            for c in range(c0 + 1, c1):
                cp[c] = c - 1
        return cp


def _leaf_counts(root: TreeNode) -> dict[int, int]:
    """g_n = number of root-to-leaf paths through node n (post-order)."""
    g: dict[int, int] = {}

    def rec(n: TreeNode) -> int:
        if not n.children:
            g[id(n)] = 1
            return 1
        tot = sum(rec(c) for c in n.children)
        g[id(n)] = tot
        return tot

    rec(root)
    return g


def _branch_adv_sums(root: TreeNode) -> dict[int, float]:
    """Σ of per-branch advantages over the leaves under each node.

    The RL analogue of ``_leaf_counts``: the GRPO objective
    (1/K) Σ_k A_k Σ_{t∈path k} nll_t gives a shared token the coefficient
    Σ_{branches through it} A_b / K.  A leaf with ``branch_adv=None``
    counts as 1.0, so a tree with no advantages sums to exactly g_n."""
    s: dict[int, float] = {}

    def rec(n: TreeNode) -> float:
        if not n.children:
            a = 1.0 if n.branch_adv is None else float(n.branch_adv)
            s[id(n)] = a
            return a
        tot = sum(rec(c) for c in n.children)
        s[id(n)] = tot
        return tot

    rec(root)
    return s


def tree_lam_map(root: TreeNode, loss_mode: str) -> dict[int, float]:
    """id(node) → λ for every node of the tree rooted at ``root``, under
    ``loss_mode`` — exactly the per-node weight ``serialize_tree`` would
    assign.  The single definition shared by the partitioner (pruned
    subtrees keep full-tree weights) and the cross-tree grafter
    (``core/forest``: unshared nodes keep their source tree's weights
    bit-exactly)."""
    g = _leaf_counts(root)
    K = g[id(root)]
    if loss_mode == "uniform":
        return {nid: 1.0 for nid in g}
    if loss_mode == "rl":
        return {nid: a / K for nid, a in _branch_adv_sums(root).items()}
    if loss_mode == "sep_avg":
        return {nid: gn / K for nid, gn in g.items()}
    raise ValueError(loss_mode)


def serialize_tree(
    tree: TrajectoryTree,
    *,
    chunk_size: Optional[int] = None,
    loss_mode: str = "sep_avg",
    lam_map: Optional[dict[int, float]] = None,
    depth_pos0: int = 0,
    root_prev: int = -1,
    leaf_ids: Optional[set] = None,
) -> SerializedTree:
    """DFS-serialize ``tree``; every token appears exactly once (Eq. 8).

    chunk_size: if given, each node segment is padded to a multiple of
      chunk_size so SSM chunk boundaries coincide with node boundaries
      (pad tokens are ``valid=False`` and inert everywhere).
    loss_mode: 'sep_avg' → λ_t = g_t/K (Eq. 4); 'uniform' → λ_t = 1 for
      every unique trained token (§3.1's alternative objective);
      'rl' → λ_t = Σ_{branches through t} A_b / K — the GRPO model-update
      objective with per-branch advantages (``TreeNode.branch_adv`` on
      leaves).  With A≡1 the branch sum equals g_t exactly, so 'rl'
      reduces bit-for-bit to 'sep_avg'.

    Partition-mode extras (core/partition.py):
      lam_map    : id(node) → λ computed on the *full* tree (a pruned
                   partition subtree must keep full-tree weights);
      depth_pos0 : depth position of the first token (= #ancestor tokens);
      root_prev  : prev_idx of the very first token; −2 means "gateway
                   context slot 0" (the immediate ancestor relayed through
                   the partition boundary — see models/layers.gather_prev);
      leaf_ids   : ids of the nodes that end a branch (a partition's pruned
                   subtree keeps only its true leaves); default: the nodes
                   without children.
    """
    g = _leaf_counts(tree.root)
    K = g[id(tree.root)]
    adv_sum = _branch_adv_sums(tree.root) if loss_mode == "rl" else None

    toks: list[np.ndarray] = []
    pos: list[np.ndarray] = []
    kvl: list[np.ndarray] = []
    wgt: list[np.ndarray] = []
    prv: list[np.ndarray] = []
    vld: list[np.ndarray] = []
    nid: list[np.ndarray] = []
    node_parent: list[int] = []
    node_start: list[int] = []
    node_end: list[int] = []
    ends: list[tuple[int, float]] = []   # (last token, λ) of each branch

    cursor = 0  # DFS token offset

    def pad_len(n_tokens: int) -> int:
        if chunk_size is None:
            return 0
        rem = n_tokens % chunk_size
        return 0 if rem == 0 else chunk_size - rem

    def rec(node: TreeNode, depth_pos: int, parent_nid: int,
            parent_last_tok: int) -> int:
        """Returns the DFS index one past the last token of node's subtree
        (including padding)."""
        nonlocal cursor
        my_nid = len(node_parent)
        node_parent.append(parent_nid)
        L = node.size
        P = pad_len(L)
        start = cursor
        node_start.append(start)
        node_end.append(start + L + P)

        toks.append(np.concatenate([node.tokens,
                                    np.zeros(P, np.int32)]))
        pos.append(np.concatenate([
            np.arange(depth_pos, depth_pos + L, dtype=np.int32),
            np.zeros(P, np.int32)]))
        if lam_map is not None:
            lam = lam_map[id(node)]
        elif loss_mode == "sep_avg":
            lam = g[id(node)] / K
        elif loss_mode == "uniform":
            lam = 1.0
        elif loss_mode == "rl":
            lam = adv_sum[id(node)] / K
        else:
            raise ValueError(loss_mode)
        adv = (node.advantage if node.advantage is not None
               else np.ones(L, np.float32))
        w = np.where(node.trained, lam * adv, 0.0).astype(np.float32)
        wgt.append(np.concatenate([w, np.zeros(P, np.float32)]))
        # prev index: within node = previous DFS slot; first token looks at
        # the parent node's last *real* token.  Empty nodes (L=0, e.g. the
        # empty leaf of a duplicated/prefix rollout branch) contribute no
        # tokens but still count as a leaf for λ.
        p = np.arange(start - 1, start + L - 1, dtype=np.int32)
        if L > 0:
            p[0] = parent_last_tok
        prv.append(np.concatenate([p, np.full(P, -1, np.int32)]))
        vld.append(np.concatenate([np.ones(L, bool), np.zeros(P, bool)]))
        nid.append(np.full(L + P, my_nid, np.int32))
        cursor += L + P

        my_last_tok = start + L - 1 if L > 0 else parent_last_tok
        if (not node.children) if leaf_ids is None else id(node) in leaf_ids:
            ends.append((my_last_tok, lam))
        for c in node.children:
            rec(c, depth_pos + L, my_nid, my_last_tok)
        subtree_end = cursor
        # kv_last for this node's tokens = last index of its subtree (pads
        # are invisible: kv_last = −1 so no query can ever see them).
        k = np.full(L + P, -1, np.int32)
        k[:L] = subtree_end - 1
        kvl.append(k)
        return subtree_end

    rec(tree.root, depth_pos0, -1, root_prev)

    # kv_last lists were appended post-order; rebuild in DFS token order.
    # Easier: recompute from node table.
    n_total = cursor
    kv_last = np.full(n_total, -1, np.int32)
    node_sub_end = np.zeros(len(node_parent), np.int64)
    # subtree end per node: max of node_end over descendants — compute by
    # iterating nodes in reverse DFS order (children appear after parents).
    for i in range(len(node_parent) - 1, -1, -1):
        node_sub_end[i] = max(node_sub_end[i], node_end[i])
        p = node_parent[i]
        if p >= 0:
            node_sub_end[p] = max(node_sub_end[p], node_sub_end[i])
    node_id_arr = np.concatenate(nid) if nid else np.zeros(0, np.int32)
    valid_arr = np.concatenate(vld) if vld else np.zeros(0, bool)
    for i in range(len(node_parent)):
        s, e = node_start[i], node_end[i]
        seg = slice(s, e)
        k = np.full(e - s, -1, np.int32)
        real = valid_arr[seg]
        k[real] = node_sub_end[i] - 1
        kv_last[seg] = k

    aux_weight = np.zeros(n_total, np.float32)
    for t, lam in ends:
        if t >= 0:
            aux_weight[t] += lam
    return SerializedTree(
        tokens=np.concatenate(toks),
        pos_ids=np.concatenate(pos),
        kv_last=kv_last,
        weight=np.concatenate(wgt),
        prev_idx=np.concatenate(prv),
        valid=valid_arr,
        node_id=node_id_arr,
        node_parent=np.asarray(node_parent, np.int32),
        node_start=np.asarray(node_start, np.int32),
        node_end=np.asarray(node_end, np.int32),
        num_paths=K,
        aux_weight=aux_weight,
    )


def visibility_mask(ser: SerializedTree) -> np.ndarray:
    """Dense [N, N] boolean tree-attention mask (test oracle; Fig. 3)."""
    n = ser.n
    i = np.arange(n)[:, None]
    j = np.arange(n)[None, :]
    return (j <= i) & (ser.kv_last[None, :] >= i) & ser.valid[None, :] \
        & ser.valid[:, None]


def subtree_token_count(tree: TrajectoryTree) -> dict[int, int]:
    """id(node) → token count of its subtree (used by the partitioner)."""
    out: dict[int, int] = {}

    def rec(n: TreeNode) -> int:
        t = n.size + sum(rec(c) for c in n.children)
        out[id(n)] = t
        return t

    rec(tree.root)
    return out
