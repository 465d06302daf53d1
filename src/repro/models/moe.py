"""Mixture-of-Experts layer: a full-width router, dropless dispatch to the
experts this device holds, grouped matrix products, and the router loss
per branch.

Expert parallelism: ``MoECfg.held = (lo, hi)`` names the experts whose
weights this layer carries (all of them by default).  The router always
scores every expert — softmax, top-k, renormalised over the k — so
routing is what the whole model would route; a
token's slot whose expert is held is computed here, a slot whose expert
is not held is another device's share and contributes nothing here.
The shares of all devices add up to the whole layer.

No token is dropped: every held slot is sorted by expert into one buffer
of N·k rows (the worst case, every slot held), and the held experts run
over it as grouped matrix products whose work follows the rows actually
routed to them (``kernels/grouped_matmul`` on a TPU; ``lax.ragged_dot``
elsewhere — the platform chooses, as it does for attention).  Results go
back to token order and are summed, each weighted by its slot's routing
probability.  The held slots sort first, so the rows the experts compute
are the buffer's prefix ``[0, live)``: on a TPU the permutations are
Pallas kernels that move only those rows (``kernels/moe_permute``) and
the products leave the rest of the buffer undefined; ``lax.ragged_dot``
needs a dense buffer, and there XLA gathers every row.

Router loss (``router_loss``): per-branch training computes, for each
root-to-leaf branch b of T_b tokens, the load-balance term
E · Σ_e (c_be / T_b) · (P_be / T_b) — c_be the branch's top-k slots routed
to expert e, P_be its summed router probability of e — and the z term
Σ_t lse_t² / T_b.  Both are sums along the branch's own path, so tree
training keeps per-node quantities and gathers them along each path: the
counts by path sums over the token chain (``prev_idx``), and the
gradient of Σ_b u_b · P_b (u_b the branch's coefficient) as the sum of
u over the branch ends in each token's subtree, which in DFS order is
the token's own range ``[t, kv_last[t]]``.  Each branch is weighted by
its leaf's λ (``core/tree.serialize_tree``'s ``aux_weight``), the weight
its own tokens' loss carries, so a prefix shared by k branches counts k
times, as in per-branch training.  Across a partition boundary the path
sums travel in the gateway like attention's ancestor KV.
"""
from __future__ import annotations

from functools import partial, reduce
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoECfg
from repro.kernels.grouped_matmul import expert_gmm, gmm_grads
from repro.kernels.moe_permute import moe_combine, moe_gather
from repro.models.layers import _dense_init


def held_range(cfg: MoECfg) -> tuple[int, int]:
    return cfg.held if cfg.held is not None else (0, cfg.num_experts)


def init_moe(key, cfg: MoECfg, d_model: int, activation: str,
             dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 6)
    E, F = cfg.num_experts, cfg.d_expert
    lo, hi = held_range(cfg)
    H = hi - lo
    p = {
        "router": _dense_init(ks[0], (d_model, E), scale=0.02,
                              dtype=jnp.float32),
        "wo": _dense_init(ks[3], (H, F, d_model), dtype=dtype),
    }
    if activation == "swiglu":
        p["wi_gate"] = _dense_init(ks[1], (H, d_model, F), dtype=dtype)
    p["wi_up"] = _dense_init(ks[2], (H, d_model, F), dtype=dtype)
    if cfg.num_shared_experts:
        Fs = F * cfg.num_shared_experts
        if activation == "swiglu":
            p["shared_wi_gate"] = _dense_init(ks[4], (d_model, Fs),
                                              dtype=dtype)
        p["shared_wi_up"] = _dense_init(ks[4], (d_model, Fs), dtype=dtype)
        p["shared_wo"] = _dense_init(ks[5], (Fs, d_model), dtype=dtype)
    return p


def _act(h_gate, h_up, activation: str):
    if activation == "swiglu":
        return jax.nn.silu(h_gate) * h_up
    if activation == "squared_relu":
        r = jax.nn.relu(h_up)
        return r * r
    return jax.nn.relu(h_up)


def platform_matmul() -> str:
    """The path this platform runs: the Pallas permutations and grouped
    products on one TPU; ``lax.ragged_dot`` over the whole buffer
    elsewhere and on a mesh of several devices, which XLA partitions (it
    cannot partition a Pallas call)."""
    from repro import sharding as sh
    mesh = sh.current_mesh()
    if jax.default_backend() == "tpu" and (mesh is None or mesh.size == 1):
        return "pallas"
    return "ragged"


# ---------------------------------------------------------------------------
# Row permutations whose transposes are gathers by the inverse
# permutation, not scatter-adds with repeated indices
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k: int):
    """x [N, D] → [N·k, D]: row r is the token of slot ``order[r]``."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return _dispatch(x, order, inv, k), (order, inv)


def _dispatch_bwd(k, res, g):
    order, inv = res
    n = g.shape[0] // k
    return g[inv].reshape(n, k, -1).sum(1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(a, idx, inv):
    """a[idx], ``idx`` a permutation whose inverse is ``inv``."""
    return a[idx]


def _permute_fwd(a, idx, inv):
    return a[idx], (idx, inv)


def _permute_bwd(res, g):
    idx, inv = res
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


# ---------------------------------------------------------------------------
# The Pallas path: the held rows only.  A permutation and the products next
# to it are one op, so that the N·k-row buffers between them stay in the
# layout the row copies need (``moe_permute.rows_view``) and no cotangent
# of the whole buffer is relaid out.
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _held_in(x, wi, order, inv, live, sizes, interpret: bool):
    """x [N, D] → each ``xs @ wi[g]`` [N·k, F] over the held slots, xs
    row r < live the token of slot ``order[r]``; rows past ``live``
    undefined."""
    return _held_in_fwd(x, wi, order, inv, live, sizes, interpret)[0]


def _held_in_fwd(x, wi, order, inv, live, sizes, interpret):
    with jax.named_scope("moe_route"):
        xs = moe_gather(x, order // inv.shape[1], live, interpret=interpret)
    with jax.named_scope("moe_experts"):
        hs = tuple(expert_gmm(xs, w, sizes, interpret=interpret,
                              zero_tail=False) for w in wi)
    return hs, (xs, wi, inv, live, sizes)


def _held_in_bwd(interpret, res, dhs):
    xs, wi, inv, live, sizes = res
    with jax.named_scope("moe_experts"):
        grads = [gmm_grads(xs, w, sizes, dh, interpret, zero_tail=False,
                           rows_out=True) for w, dh in zip(wi, dhs)]
        dxs = reduce(jnp.add, (d for d, _ in grads))
    with jax.named_scope("moe_route"):
        dx = moe_combine(dxs, inv, live, interpret=interpret)
    return dx, tuple(dw for _, dw in grads), None, None, None, None


_held_in.defvjp(_held_in_fwd, _held_in_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def _held_out(h, wo, w, order, inv, live, sizes, interpret: bool):
    """h [N·k, F] → [N, D]: token t's ``Σ_j w[t, j] · (h @ wo)[inv[t, j]]``
    over its held slots (``inv < live``)."""
    return _held_out_fwd(h, wo, w, order, inv, live, sizes, interpret)[0]


def _held_out_fwd(h, wo, w, order, inv, live, sizes, interpret):
    with jax.named_scope("moe_experts"):
        ys = expert_gmm(h, wo, sizes, interpret=interpret, zero_tail=False,
                        rows_out=True)
    with jax.named_scope("moe_route"):
        y = moe_combine(ys, inv, live, w, interpret=interpret)
    return y, (h, wo, ys, w, order, inv, live, sizes)


def _held_out_bwd(interpret, res, dy):
    h, wo, ys, w, order, inv, live, sizes = res
    N, k = inv.shape
    with jax.named_scope("moe_route"):
        dys, dots = moe_gather(dy.astype(ys.dtype), order // k, live,
                               scale=w.reshape(N * k)[order], dot=ys,
                               interpret=interpret)
        dw = jnp.where(inv < live, dots[inv], 0.0).astype(w.dtype)
    with jax.named_scope("moe_experts"):
        dh, dwo = gmm_grads(h, wo, sizes, dys, interpret, zero_tail=False)
    return dh, dwo, dw, None, None, None, None


_held_out.defvjp(_held_out_fwd, _held_out_bwd)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def moe(params: dict, cfg: MoECfg, x: jax.Array, valid: jax.Array,
        activation: str) -> tuple[jax.Array, dict]:
    """x: [B, S, D]; valid: [B, S] bool.  Returns ``(y, route)``: the
    held experts' share of the layer, and per token the router's
    quantities that ``router_loss`` takes (``cnt`` [B, S, E]: the token's
    top-k slots per expert, over every expert; ``feat`` [B, S, E+1]: its
    router probabilities and squared log-sum-exp; zero on invalid
    tokens) plus ``held_rows``, the slots this layer computed."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    lo, hi = held_range(cfg)
    H = hi - lo
    N = B * S
    xf = x.reshape(N, D)
    vmask = valid.reshape(N)

    with jax.named_scope("moe_route"):
        # float32 at full precision: which experts a token reaches must
        # not turn on the activations' rounding
        logits = jnp.dot(xf.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)   # [N, E]
        lse = jax.nn.logsumexp(logits, axis=-1)
        probs = jnp.exp(logits - lse[:, None])
        top_p, top_e = jax.lax.top_k(probs, K)                  # [N, K]
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-20)
        held = (top_e >= lo) & (top_e < hi) & vmask[:, None]    # [N, K]
        gid = jnp.where(held, top_e - lo, H).reshape(N * K)
        order = jnp.argsort(gid, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * K, dtype=jnp.int32), unique_indices=True)
        sizes = jax.nn.one_hot(gid, H, dtype=jnp.int32).sum(0)
        live = jnp.sum(sizes)

    kind = platform_matmul()
    gated = "wi_gate" in params
    if kind == "ragged":
        mm = jax.lax.ragged_dot
        with jax.named_scope("moe_route"):
            xs = _dispatch(xf, order, inv, K)                   # [N·K, D]
        with jax.named_scope("moe_experts"):
            gate = mm(xs, params["wi_gate"], sizes) if gated else None
            h = _act(gate, mm(xs, params["wi_up"], sizes), activation)
            ys = mm(h.astype(xs.dtype), params["wo"], sizes)    # [N·K, D]
        with jax.named_scope("moe_route"):
            w = jnp.where(held, top_p, 0.0).astype(x.dtype)
            y = (_permute(ys, inv, order).reshape(N, K, D)
                 * w[..., None]).sum(axis=1)
    else:
        interpret = kind == "interpret"
        wi = tuple(params[n] for n in ("wi_gate", "wi_up") if n in params)
        slots = inv.reshape(N, K)           # each token's slots' rows
        hs = _held_in(xf, wi, order, slots, live, sizes, interpret)
        with jax.named_scope("moe_experts"):
            h = _act(hs[0] if gated else None, hs[-1], activation)
        y = _held_out(h.astype(x.dtype), params["wo"],
                      jnp.where(held, top_p, 0.0), order, slots, live,
                      sizes, interpret)

    if "shared_wi_up" in params:
        sg = xf @ params["shared_wi_gate"] \
            if "shared_wi_gate" in params else None
        y = y + _act(sg, xf @ params["shared_wi_up"], activation) \
            @ params["shared_wo"]

    vf = vmask.astype(jnp.float32)[:, None]
    cnt = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(1) * vf
    feat = jnp.concatenate([probs, (lse * lse)[:, None]], -1) * vf
    route = {"cnt": jax.lax.stop_gradient(cnt).reshape(B, S, E),
             "feat": feat.reshape(B, S, E + 1),
             "held_rows": live}
    return y.reshape(B, S, D), route


# ---------------------------------------------------------------------------
# Router loss per branch
# ---------------------------------------------------------------------------

def path_sums(x: jax.Array, prev_idx: jax.Array) -> jax.Array:
    """x [B, S, C] → Σ over each token's path (itself and every token
    before it on its branch, following ``prev_idx``; a negative index
    ends the path), by pointer doubling: log2 S rounds of gathers."""
    S = x.shape[1]
    ptr = prev_idx
    for _ in range(max(1, (S - 1).bit_length())):
        ok = ptr >= 0
        safe = jnp.maximum(ptr, 0)
        x = x + jnp.where(ok[..., None],
                          jnp.take_along_axis(x, safe[..., None], axis=1),
                          0.0)
        ptr = jnp.where(ok, jnp.take_along_axis(ptr, safe, axis=1), -1)
    return x


def router_loss(cfg: MoECfg, route: dict, meta: dict,
                gw: Optional[dict] = None,
                cap_idx: Optional[dict] = None
                ) -> tuple[jax.Array, dict]:
    """The layer's router loss summed over the branches that end in this
    batch, each weighted by ``meta["aux_weight"]`` at its last token, and
    the path sums at each cut's last path token (``cap_idx``: cut name →
    that partition's path token indices) for the child partitions.

    ``gw`` (a partition's gateway): ``cnt`` [B, E] and ``feat`` [B, E+1]
    summed over the ancestors in earlier partitions; every token of a
    gateway row descends from them."""
    cnt, feat = route["cnt"], route["feat"]
    B, S, E = cnt.shape
    prev, kv_last = meta["prev_idx"], meta["kv_last"]
    valid = meta["valid"]
    aw = meta["aux_weight"].astype(jnp.float32)
    gcnt = None if gw is None else gw["cnt"].astype(jnp.float32)
    gfeat = None if gw is None else gw["feat"]

    # counts along each path (integers: exact in any order); a gateway
    # row's root token takes its ancestors' counts
    base = cnt
    if gcnt is not None:
        base = base + jnp.where((prev == -2)[..., None], gcnt[:, None], 0.0)
    c_path = jax.lax.stop_gradient(path_sums(base, prev))
    T = (meta["pos_ids"] + 1).astype(jnp.float32)[..., None]
    # each branch end's coefficient on its path's summed features
    u = aw[..., None] * jnp.concatenate(
        [cfg.router_aux_weight * E * c_path / (T * T),
         jnp.broadcast_to(cfg.router_z_weight / T, (B, S, 1))], -1)
    # Σ of u over the ends in each token's subtree [t, kv_last[t]]
    U = jnp.cumsum(u, axis=1)
    t = jnp.arange(S)
    hi = jnp.take_along_axis(U, jnp.maximum(kv_last, 0)[..., None], axis=1)
    lo = jnp.pad(U, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    live = (valid & (kv_last >= t[None]))[..., None]
    G = jax.lax.stop_gradient(jnp.where(live, hi - lo, 0.0))
    aux = jnp.sum(feat * G)
    if gfeat is not None:
        aux = aux + jnp.sum(gfeat * jax.lax.stop_gradient(u.sum(1)))

    caps: dict = {}
    for name, idx in (cap_idx or {}).items():
        c = idx[-1]
        on_path = (t <= c)[None] & (kv_last >= c) & valid      # [B, S]
        fc = jnp.einsum("bs,bsc->bc", on_path.astype(jnp.float32), feat,
                        precision=jax.lax.Precision.HIGHEST)
        if gfeat is not None:
            fc = fc + gfeat
        caps[name] = {"state": {"cnt": c_path[:, c], "feat": fc}}
    return aux, caps
