"""The plain reference: per-branch training of the configuration.

Each tree is taken as its root-to-leaf paths, and each path is trained as
a causal sequence of its own (positions from 0), which is the per-branch
training the paper proves tree training equal to: token t of a path is
predicted from the path's token t-1 and weighted ``trained / (K · T)``,
K the tree's paths and T the step's trees.  Paths are laid into rows of
``R`` tokens only to keep a fixed shape; a path never sees another.

The forward is plain float32 ``jax.numpy`` of the published description:
the embedding, the family's layers (``body`` of
``bench/families/<family>.py``), the final RMSNorm and the LM head (tied
or not) over the published vocabulary, whatever rows the weights are
padded to.  Every matrix product runs at ``Precision.HIGHEST`` through
``ein``, which the families' layers use too.  The optimizer is
AdamW with global-norm clipping and the configuration's learning-rate
schedule; weights are kept in bfloat16 between steps, as the
configuration states, and decay applies to the family's ``DECAY``
entries (its matrices).

``precision="fp8"`` is the control: the same computation with the
operands of every matrix product rounded to float8 (e4m3 forward, e5m2
gradients, one scale per tensor), the step below the configuration's
bfloat16.  This module imports nothing of the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import families
from bench.adapter import entry_norms, flatten_norms, init_weights

HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Rows of paths (host side)
# ---------------------------------------------------------------------------

def step_rows(trees, R: int) -> list[dict]:
    """One step's trees → rows of whole paths, first-fit decreasing."""
    T = len(trees)
    segs = []
    for t in trees:
        paths = t.paths()
        K = len(paths)
        for p in paths:
            toks = np.concatenate([n.tokens for n in p]).astype(np.int32)
            w = np.concatenate([n.trained for n in p]).astype(np.float64)
            w = w / (K * T)
            w[0] = 0.0
            if toks.size > R:
                raise ValueError(f"path of {toks.size} tokens > row {R}")
            segs.append((toks, w))
    segs.sort(key=lambda s: -s[0].size)
    rows: list[list] = []
    used: list[int] = []
    for s in segs:
        for i, u in enumerate(used):
            if u + s[0].size <= R:
                rows[i].append(s)
                used[i] += s[0].size
                break
        else:
            rows.append([s])
            used.append(s[0].size)
    out = []
    for r in rows:
        tok = np.zeros(R, np.int32)
        seg = np.full(R, -1, np.int32)
        pos = np.zeros(R, np.int32)
        wt = np.zeros(R, np.float32)
        o = 0
        for j, (t, w) in enumerate(r):
            n = t.size
            tok[o:o + n], seg[o:o + n] = t, j
            pos[o:o + n] = np.arange(n)
            wt[o:o + n] = w
            o += n
        out.append(dict(tokens=tok, seg=seg, pos=pos, weight=wt))
    return out


# ---------------------------------------------------------------------------
# Matrix products: float32 at HIGHEST, or the float8 control
# ---------------------------------------------------------------------------

def _q(x, dt):
    """Round to float8 with one scale for the whole tensor."""
    amax = jnp.max(jnp.abs(x))
    s = jax.lax.stop_gradient(
        jnp.where(amax > 0, float(jnp.finfo(dt).max) / amax, 1.0))
    return (x * s).astype(dt).astype(jnp.float32) / s


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein8(spec, a, b):
    return jnp.einsum(spec, _q(a, jnp.float8_e4m3fn), _q(b, jnp.float8_e4m3fn),
                      precision=HI)


def _ein8_fwd(spec, a, b):
    return _ein8(spec, a, b), (a, b)


def _ein8_bwd(spec, res, g):
    a, b = res
    qa, qb = _q(a, jnp.float8_e4m3fn), _q(b, jnp.float8_e4m3fn)
    qg = _q(g, jnp.float8_e5m2)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HI),
                     qa, qb)
    return vjp(qg)


_ein8.defvjp(_ein8_fwd, _ein8_bwd)


def ein(spec, a, b, fp8: bool):
    if fp8:
        return _ein8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HI)


# ---------------------------------------------------------------------------
# Forward and loss of one row
# ---------------------------------------------------------------------------

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def visible(row):
    """[R, R]: query i sees key j of its own path at or before it; padding
    (seg -1) sees only itself, so that no row is empty."""
    seg = row["seg"]
    i = jnp.arange(seg.shape[0])
    return (((seg[:, None] == seg[None, :]) & (i[None, :] <= i[:, None])
             & (seg[None, :] >= 0)) | (i[:, None] == i[None, :]))


def rope(x, pos, theta):
    """x [R, heads, hd]; rotate_half rotary embedding."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_loss(c: dict, fp8: bool, w: dict, row: dict, chunk: int = 512):
    fam = families.load(c)
    d = fam.dims(c)
    tok = row["tokens"]
    R = tok.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    x, aux = fam.body(c, fp8, w, f32(w["embed"])[tok], row)
    x = rms(x, f32(w["final_norm"]), d["eps"])
    # the published vocabulary only: rows the weights carry past it
    # (padding to a multiple) are no tokens, so no probability falls on them
    head = f32(w["embed"]).T if d["tied"] else f32(w["lm_head"])
    head = head[:, :d["V"]]

    # token j+1 is predicted from position j; chunked so that the logits
    # of a whole row are never held at once
    h_prev = jnp.concatenate([x[:-1], jnp.zeros_like(x[:1])])
    tgt = jnp.concatenate([tok[1:], tok[:1]])
    wt = jnp.concatenate([row["weight"][1:], jnp.zeros((1,), jnp.float32)])

    @jax.checkpoint
    def piece(hc, tc, wc):
        lg = ein("rd,dv->rv", hc, head, fp8)
        lse = jax.nn.logsumexp(lg, axis=-1)
        lab = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(wc * (lse - lab))

    chunk = min(chunk, R)
    n = R // chunk
    parts = jax.lax.map(lambda a: piece(*a), (
        h_prev.reshape(n, chunk, -1), tgt.reshape(n, chunk),
        wt.reshape(n, chunk)))
    return jnp.sum(parts) + aux


# ---------------------------------------------------------------------------
# Gradient accumulation and AdamW (jitted once per configuration)
# ---------------------------------------------------------------------------

_FNS: dict = {}


def _fns(c: dict, fp8: bool):
    key = (id(c), fp8)
    if key in _FNS:
        return _FNS[key]

    def acc_grad(w, acc, loss, row):
        lo, g = jax.value_and_grad(partial(row_loss, c, fp8))(w, row)
        return jax.tree.map(jnp.add, acc, g), loss + lo

    def zeros(w):
        return jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)

    fns = dict(acc_grad=jax.jit(acc_grad, donate_argnums=(1, 2)),
               zeros=jax.jit(zeros),
               norms=jax.jit(entry_norms),
               diff_norms=jax.jit(lambda a, b: entry_norms(jax.tree.map(
                   lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                   a, b))))
    _FNS[key] = fns
    return fns


def lr_at(opt: dict, step: int) -> float:
    lr, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return lr * (step + 1) / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    floor = opt.get("min_lr_ratio", 0.1)
    return lr * (floor + (1 - floor) * 0.5 * (1 + np.cos(np.pi * prog)))


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def _adamw(hyper, w, g, mu, nu, lr, step):
    b1, b2, eps, wd, clip, decay = hyper
    leaves = jax.tree.leaves(g)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
    c1, c2 = 1 - b1 ** (step + 1), 1 - b2 ** (step + 1)

    def one(path, p, gg, m, v):
        name = jax.tree_util.keystr(path[-1:]).strip("[]'\"")
        gg = gg * scale
        m = b1 * m + (1 - b1) * gg
        v = b2 * v + (1 - b2) * gg * gg
        delta = (m / c1) / (jnp.sqrt(v / c2) + eps)
        pf = p.astype(jnp.float32)
        if name in decay:
            delta = delta + wd * pf
        return (pf - lr * delta).astype(p.dtype), m, v

    out = jax.tree_util.tree_map_with_path(one, w, g, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2), gn


def run(c: dict, seed: int, steps_trees: list, opt: dict, R: int,
        precision: str = "f32", weights=None) -> dict:
    """Train the seed's weights through ``steps_trees`` (one list of
    ``bench.gen.Node`` trees per step).  Returns each step's loss, the
    first step's gradient norm per entry, and the norm per entry of the
    weights' change over all the steps."""
    fp8 = precision == "fp8"
    f = _fns(c, fp8)
    w0 = init_weights(c, seed) if weights is None else weights
    w, mu, nu = w0, f["zeros"](w0), f["zeros"](w0)
    hyper = (opt.get("b1", 0.9), opt.get("b2", 0.95), opt.get("eps", 1e-8),
             opt.get("weight_decay", 0.1), opt.get("grad_clip", 1.0),
             families.load(c).DECAY)
    losses, gnorms, g0 = [], [], None
    for s, trees in enumerate(steps_trees):
        acc, loss = f["zeros"](w0), jnp.zeros((), jnp.float32)
        for row in step_rows(trees, R):
            acc, loss = f["acc_grad"](w, acc, loss,
                                      {k: jnp.asarray(v) for k, v in row.items()})
        losses.append(float(loss))
        if s == 0:
            g0 = flatten_norms(f["norms"](acc))
        w, mu, nu, gn = _adamw(hyper, w, acc, mu, nu,
                               jnp.float32(lr_at(opt, s)), jnp.int32(s))
        gnorms.append(float(gn))
        del acc
    change = flatten_norms(f["diff_norms"](w, w0))
    return dict(loss=losses, grad_norm=gnorms, grad=g0, change=change)
