"""The expert layer (models/moe.py) and its grouped products
(kernels/grouped_matmul.py).

- Dropless: under a router skewed so that most tokens reach the same two
  experts, every routed slot is computed — the layer equals a dense
  per-token, per-expert reference.
- Share-sum: the layer over 8 experts equals the sum of its 4 shares of 2
  (expert parallelism: each device holds a share, routes over all).
- The Pallas grouped products (interpret mode) equal ``lax.ragged_dot``,
  forward and gradient, with empty groups and rows past the last group.
- The layer's Pallas path (permutations over the held rows only, products
  that leave the rest undefined) equals the plain path, and moves no
  buffer row by an XLA gather.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoECfg
from repro.kernels.grouped_matmul import expert_gmm, expert_tgmm, gmm
from repro.models import moe as moe_mod
from repro.models.moe import init_moe, moe

D, F = 32, 24


def _cfg(**kw):
    base = dict(num_experts=8, top_k=3, d_expert=F)
    base.update(kw)
    return MoECfg(**base)


def _inputs(seed=0, B=2, S=24):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    valid = jnp.asarray(rng.random((B, S)) < 0.9)
    return x, valid


def _dense_reference(p, cfg, x, valid):
    """Each token: Σ over its top-k slots whose expert is held of the
    slot's probability times that expert's SwiGLU — one token and one
    expert at a time."""
    lo, hi = cfg.held or (0, cfg.num_experts)
    probs = jax.nn.softmax(x @ p["router"], -1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    out = np.zeros(x.shape, np.float32)
    B, S, _ = x.shape
    for b in range(B):
        for s in range(S):
            if not valid[b, s]:
                continue
            for k in range(cfg.top_k):
                e = int(top_e[b, s, k])
                if not lo <= e < hi:
                    continue
                h = x[b, s]
                a = jax.nn.silu(h @ p["wi_gate"][e - lo]) \
                    * (h @ p["wi_up"][e - lo])
                out[b, s] += np.asarray(top_p[b, s, k] * (a @ p["wo"][e - lo]))
    return out


def test_dropless_under_a_skewed_router():
    cfg = _cfg()
    p = init_moe(jax.random.key(1), cfg, D, "swiglu")
    # experts 2 and 5 win every token's first two slots
    p["router"] = p["router"].at[:, 2].add(3.0).at[:, 5].add(2.5)
    x, valid = _inputs()
    x = jnp.abs(x)
    y, route = moe(p, cfg, x, valid, "swiglu")
    top = jax.lax.top_k(x @ p["router"], 2)[1]
    assert float(jnp.mean(jnp.sort(top, -1) == jnp.array([2, 5]))) > 0.9
    np.testing.assert_allclose(np.asarray(y),
                               _dense_reference(p, cfg, x, valid),
                               rtol=2e-5, atol=2e-5)
    assert int(route["held_rows"]) == cfg.top_k * int(valid.sum())


def test_shares_sum_to_the_layer():
    cfg = _cfg()
    p = init_moe(jax.random.key(2), cfg, D, "swiglu")
    x, valid = _inputs(1)
    whole, _ = moe(p, cfg, x, valid, "swiglu")
    total, rows = 0.0, 0
    for lo in range(0, 8, 2):
        share = dataclasses.replace(cfg, held=(lo, lo + 2))
        ps = {"router": p["router"],
              **{k: p[k][lo:lo + 2] for k in ("wi_gate", "wi_up", "wo")}}
        y, route = moe(ps, share, x, valid, "swiglu")
        np.testing.assert_allclose(np.asarray(y),
                                   _dense_reference(ps, share, x, valid),
                                   rtol=2e-5, atol=2e-5)
        total = total + y
        rows += int(route["held_rows"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    assert rows == cfg.top_k * int(valid.sum())


def _few_reach(p, x):
    """Expert 7 wins only the tokens whose inputs were flipped negative:
    every other expert's logit is raised by the token's input sum."""
    p = {**p, "router": p["router"].at[:, :7].add(1.0).at[:, 7].set(0.0)}
    x = jnp.abs(x).at[0, :2].multiply(-1.0)
    return p, x


# name → (held experts, tokens (B, S), how the inputs are set up)
PALLAS_CASES = {
    "share": ((3, 7), (2, 24), None),
    "every_slot": ((0, 8), (2, 24), None),
    "few_reach": ((7, 8), (2, 24), _few_reach),
    "wave": ((2, 6), (2, 320), None),
    "decode": ((3, 7), (3, 1), None),
}


@pytest.mark.parametrize("case", sorted(PALLAS_CASES))
def test_pallas_path_matches_plain_path(monkeypatch, case):
    """The layer through the Pallas permutations and grouped products
    (interpret mode) equals the CPU's ``lax.ragged_dot`` path, value and
    gradient, and computes the same held rows: every slot held, a held
    range few tokens reach, a partition wave's rows, and decode's few
    tokens (below one row tile)."""
    held, (B, S), setup = PALLAS_CASES[case]
    cfg = _cfg(held=held)
    p = init_moe(jax.random.key(3), cfg, D, "swiglu")
    x, valid = _inputs(2, B, S)
    if setup is not None:
        p, x = setup(p, x)

    def loss(p, x):
        y, route = moe(p, cfg, x, valid, "swiglu")
        return jnp.sum(y * jnp.sin(y)), route["held_rows"]

    grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    (want, want_rows), want_g = grad(p, x)
    assert moe_mod.platform_matmul() == "ragged"
    monkeypatch.setattr(moe_mod, "platform_matmul", lambda: "interpret")
    (got, got_rows), got_g = grad(p, x)
    assert int(got_rows) == int(want_rows)
    if case == "every_slot":
        assert int(got_rows) == cfg.top_k * int(valid.sum())
    if case == "few_reach":
        assert 0 < int(got_rows) <= 2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(want_g), jax.tree.leaves(got_g)):
        # the router's gradient sums every token's term: with many tokens
        # float32 summation order reads against the leaf's scale
        scale = 1.0 if case == "share" else max(1.0, float(jnp.abs(a).max()))
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6 * scale)


def _gathers(jaxpr, out=None):
    """Every ``gather`` in a jaxpr and the jaxprs inside it, except those
    inside a Pallas kernel."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "gather":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _gathers(sub, out)
    return out


def test_pallas_path_gathers_no_buffer_rows(monkeypatch):
    """On the Pallas path no XLA gather moves rows of the N·k-slot buffer,
    in the forward or the backward; the plain path's do."""
    cfg = _cfg(held=(3, 7))
    p = init_moe(jax.random.key(3), cfg, D, "swiglu")
    x, valid = _inputs(2)
    NK = x.shape[0] * x.shape[1] * cfg.top_k

    def buffer_gathers():
        f = jax.value_and_grad(
            lambda p, x: jnp.sum(moe(p, cfg, x, valid, "swiglu")[0]),
            argnums=(0, 1))
        return [e for e in _gathers(jax.make_jaxpr(f)(p, x).jaxpr)
                if e.outvars[0].aval.ndim > 1
                and e.outvars[0].aval.shape[0] == NK]

    assert buffer_gathers(), "the plain path's row gathers are not seen"
    monkeypatch.setattr(moe_mod, "platform_matmul", lambda: "interpret")
    assert not buffer_gathers()


@pytest.mark.parametrize("sizes", [[3, 0, 10, 5], [0, 0, 0, 0], [40, 0, 0, 1],
                                   [7, 9, 0, 24]])
def test_grouped_products_match_ragged_dot(sizes):
    """Groups empty, short of a row tile or past it, and rows past the
    last group (the slots no held expert takes)."""
    rng = np.random.default_rng(sum(sizes))
    M, K, N, G = 48, 16, 24, 4
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(G, K, N)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    np.testing.assert_allclose(np.asarray(expert_gmm(x, w, gs,
                                                     interpret=True)),
                               np.asarray(jax.lax.ragged_dot(x, w, gs)),
                               rtol=1e-5, atol=1e-5)
    g = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    want_w = jax.vjp(lambda w: jax.lax.ragged_dot(x, w, gs), w)[1](g)[0]
    np.testing.assert_allclose(
        np.asarray(expert_tgmm(x, g, gs, num_groups=G, interpret=True)),
        np.asarray(want_w), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda x, w: jnp.sum(gmm(x, w, gs, True) * g),
                   argnums=(0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(jax.lax.ragged_dot(x, w, gs) * g),
                    argnums=(0, 1))(x, w)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-5)
