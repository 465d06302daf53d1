"""Sharding rules: mesh context + per-tensor PartitionSpecs.

Megatron-style layout on a (data…, model) mesh:
  - batch dims of activations      → data axes ("pod","data" when multi-pod)
  - attention head / ffn / vocab / expert dims of weights → "model"
  - tensors whose sharded dim is not divisible by the model-axis size fall
    back to replication (e.g. qwen2-1.5b's 12 heads on a 16-way axis) —
    the rules are per-tensor, so the rest of the layer still shards.

Activation constraints are applied through ``shard_activation`` /
``shard_logits`` which no-op when no mesh is active (unit tests, CPU runs).
"""
from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class _Ctx:
    mesh: Optional[Mesh] = None
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    seq_parallel: bool = False


_CTX = _Ctx()


@contextmanager
def use_mesh(mesh: Mesh, data_axes=("data",), model_axis="model",
             seq_parallel: bool = False):
    """seq_parallel: additionally shard the sequence dim of inter-block
    activations over the model axis (Megatron sequence parallelism).  The
    *forward* TP reduction after each block's output projection is issued
    as a true reduce-scatter (``tp_out_proj``) instead of an all-reduce —
    half the forward wire bytes on that edge — and its backward re-gather
    is an all-gather.  Total fwd+bwd boundary bytes are conserved (ring
    all-reduce ≡ reduce-scatter + all-gather); the win is the halved
    forward path plus boundary activations living S/tp-sharded.  shardlint
    (``analysis/comms_audit``) proves the forward-path drop statically —
    this docstring is a lint invariant, not a hope."""
    old = (_CTX.mesh, _CTX.data_axes, _CTX.model_axis, _CTX.seq_parallel)
    _CTX.mesh, _CTX.data_axes, _CTX.model_axis, _CTX.seq_parallel = \
        mesh, tuple(data_axes), model_axis, seq_parallel
    try:
        with mesh:
            yield
    finally:
        (_CTX.mesh, _CTX.data_axes, _CTX.model_axis,
         _CTX.seq_parallel) = old


def current_mesh() -> Optional[Mesh]:
    """The active mesh, if any.  jax's ``with mesh:`` context is
    thread-local — a worker thread that dispatches jitted computations
    must re-enter it or it will trace (and compile) against no mesh."""
    return _CTX.mesh


def _ns(spec: P) -> Optional[NamedSharding]:
    if _CTX.mesh is None:
        return None
    return NamedSharding(_CTX.mesh, spec)


def shard_activation(x: jax.Array) -> jax.Array:
    """[B, S, D] (or [B, S]) activations: batch over data axes; with
    sequence parallelism also S over the model axis."""
    if _CTX.mesh is None:
        return x
    seq = None
    if (_CTX.seq_parallel and x.ndim >= 2
            and x.shape[1] % _CTX.mesh.shape[_CTX.model_axis] == 0):
        seq = _CTX.model_axis
    s = _ns(P(_CTX.data_axes, seq, *([None] * (x.ndim - 2))))
    return x if s is None else jax.lax.with_sharding_constraint(x, s)


def seq_sharded(S: int) -> bool:
    """True when sequence parallelism is active and a length-``S`` sequence
    dim divides the model axis (the condition under which
    ``shard_activation`` shards S and ``tp_out_proj`` scatters)."""
    return (_CTX.mesh is not None and _CTX.seq_parallel
            and S % _CTX.mesh.shape[_CTX.model_axis] == 0)


def tp_out_proj(h: jax.Array, w: jax.Array) -> jax.Array:
    """TP output projection ``h @ w`` (h: [B, S, F] feature-sharded over the
    model axis, w: [F, D] row-sharded).

    Without sequence parallelism this is a plain matmul — GSPMD inserts the
    usual all-reduce of the partial products.  With ``seq_parallel=True``
    the reduction is issued explicitly as ``psum_scatter`` inside a
    ``shard_map``, so the lowered HLO carries a true reduce-scatter (result
    [B, S, D] sharded S-over-model) instead of all-reduce + slice: half the
    wire bytes on the boundary, and the backward of the scatter is an
    all-gather rather than another all-reduce.  Falls back to the plain
    matmul whenever any dim doesn't divide its axis."""
    mesh, m = _CTX.mesh, _CTX.model_axis
    if (not seq_sharded(h.shape[1]) or h.ndim != 3
            or h.shape[-1] % mesh.shape[m] != 0
            or w.shape[0] % mesh.shape[m] != 0):
        return h @ w
    daxes = _CTX.data_axes
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    bspec = daxes if h.shape[0] % dsize == 0 else None

    def local(hl, wl):
        return jax.lax.psum_scatter(hl @ wl, m, scatter_dimension=1,
                                    tiled=True)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(bspec, None, m), P(m, None)),
                         out_specs=P(bspec, m, None),
                         check_vma=False)(h, w)


def shard_spec(x: jax.Array, *axes) -> jax.Array:
    """Constrain arbitrary dims: axes entries are None, 'data' (the data
    axes tuple), or 'model'.  No-op without an active mesh or when a
    requested dim is not divisible by its axis size."""
    if _CTX.mesh is None:
        return x
    parts = []
    for i, a in enumerate(axes):
        if a == "data":
            size = 1
            for ax in _CTX.data_axes:
                size *= _CTX.mesh.shape[ax]
            parts.append(_CTX.data_axes if x.shape[i] % size == 0 else None)
        elif a == "model":
            m = _CTX.model_axis
            parts.append(m if x.shape[i] % _CTX.mesh.shape[m] == 0
                         else None)
        else:
            parts.append(None)
    return jax.lax.with_sharding_constraint(x, _ns(P(*parts)))


def shard_logits(x: jax.Array) -> jax.Array:
    """[B, S, V]: batch over data axes, vocab over model."""
    if _CTX.mesh is None:
        return x
    V = x.shape[-1]
    m = _CTX.model_axis
    msize = _CTX.mesh.shape[m]
    spec = P(_CTX.data_axes, None, m if V % msize == 0 else None)
    return jax.lax.with_sharding_constraint(x, _ns(spec))


# ---------------------------------------------------------------------------
# Parameter shardings
# ---------------------------------------------------------------------------

# (path regex, spec builder given (shape, model_size)); first match wins.
# Specs are for the UNSTACKED tensor; a leading layer-stack dim is handled
# by rank offset (None prepended for each extra leading dim).
_RULES: list[tuple[str, Any]] = [
    # embeddings / lm head: vocab dim sharded
    (r"embed/table$",      lambda s, m: P("M" if s[0] % m == 0 else None, None)),
    (r"lm_head/w$",        lambda s, m: P(None, "M" if s[1] % m == 0 else None)),
    # attention
    (r"attn/wq$|xattn/wq$", lambda s, m: P(None, "M" if s[1] % m == 0 else None)),
    (r"attn/wk$|attn/wv$|xattn/wk$|xattn/wv$",
     lambda s, m: P(None, "M" if s[1] % m == 0 else None)),
    (r"attn/wo$|xattn/wo$", lambda s, m: P("M" if s[0] % m == 0 else None, None)),
    (r"attn/b[qkv]$",      lambda s, m: P("M" if s[0] % m == 0 else None)),
    # MoE: expert-parallel over the expert dim
    (r"moe/router$",       lambda s, m: P(None, None)),
    (r"moe/wi_gate$|moe/wi_up$|moe/wo$",
     lambda s, m: P("M" if s[0] % m == 0 else None, None, None)),
    (r"moe/shared_wi_gate$|moe/shared_wi_up$",
     lambda s, m: P(None, "M" if s[1] % m == 0 else None)),
    (r"moe/shared_wo$",    lambda s, m: P("M" if s[0] % m == 0 else None, None)),
    # dense MLP
    (r"mlp/wi_gate$|mlp/wi_up$|cm/wk$",
     lambda s, m: P(None, "M" if s[1] % m == 0 else None)),
    (r"mlp/wo$|cm/wv$",    lambda s, m: P("M" if s[0] % m == 0 else None, None)),
    (r"mlp/bi$",           lambda s, m: P("M" if s[0] % m == 0 else None)),
    # SSM projections: z/x (d_inner) shard on model; B/C/dt stay replicated
    # on their tiny output dims (see mamba2.init_mamba2 docstring)
    (r"ssm/in_[zx]$|ssm/in_proj$|tm/w[rkvg]$|ssm/w[qkvz]$",
     lambda s, m: P(None, "M" if s[1] % m == 0 else None)),
    (r"ssm/in_[BC]$|ssm/in_dt$|ssm/w[ab]$",
     lambda s, m: P(None, None)),
    # intentionally replicated ≥2-D tensors (explicit so shardlint's
    # closed-coverage rule lint proves intent, not fall-through):
    # depthwise conv taps follow the locally-resident d_inner slice; the
    # rwkv6 mix interpolants, per-head bonus, decay LoRA and channelmix
    # gate are small and stay off the collective hot path (see the
    # test_sharding replicate-allowlist and per-module init docstrings)
    (r"ssm/conv_w$|tm/mix$|cm/mix$|cm/wr$|tm/u$|tm/w_lora_[ab]$",
     lambda s, m: P(None, None)),
    (r"ssm/out_proj$|tm/wo$",
     lambda s, m: P("M" if s[0] % m == 0 else None, None)),
    (r"shared_in$",        lambda s, m: P(None, None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(path_str: str, shape: tuple[int, ...], model_size: int,
               model_axis: str, n_stack_dims: int = 0,
               fsdp_axis: Optional[str] = None,
               fsdp_size: int = 1) -> P:
    base_shape = shape[n_stack_dims:]
    for pat, fn in _RULES:
        if re.search(pat, path_str):
            spec = fn(base_shape, model_size)
            parts = [model_axis if a == "M" else a for a in spec]
            # FSDP (ZeRO-3): shard one non-model dim over the data axis —
            # the weight all-gather appears at use, exactly like MaxText's
            # fsdp axis.  Only ≥2-D tensors; pick the largest eligible dim.
            if fsdp_axis is not None and len(base_shape) >= 2:
                cand = [i for i, a in enumerate(parts)
                        if a is None and base_shape[i] % fsdp_size == 0]
                if cand:
                    best = max(cand, key=lambda i: base_shape[i])
                    parts[best] = fsdp_axis
            return P(*([None] * n_stack_dims), *parts)
    return P()  # replicate


def param_shardings(params_shape: Any, mesh: Mesh,
                    model_axis: str = "model",
                    fsdp_axis: Optional[str] = None) -> Any:
    """Pytree of NamedShardings for a (possibly layer-stacked) param tree.

    Stacked tensors are recognized by path: anything under ``layer_stacks``
    or ``encoder`` has one leading layer dim.  fsdp_axis: additionally
    shard weights over that (data) axis — required for the 340B/1T archs
    where 16-way tensor parallelism alone cannot hold the weights.
    """
    msize = mesh.shape[model_axis]
    fsize = mesh.shape[fsdp_axis] if fsdp_axis else 1
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    out = []
    for path, leaf in flat:
        ps = _path_str(path)
        n_stack = 1 if ("layer_stacks" in ps or ps.startswith("encoder")) \
            else 0
        spec = param_spec(ps, leaf.shape, msize, model_axis, n_stack,
                          fsdp_axis, fsize)
        if len(spec) > len(leaf.shape):
            spec = P(*list(spec)[:len(leaf.shape)])
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shardings(batch_shape: Any, mesh: Mesh,
                    data_axes=("data",)) -> Any:
    """Batch arrays: first dim over data axes, rest replicated."""
    def one(leaf):
        total = 1
        for a in data_axes:
            total *= mesh.shape[a]
        lead = data_axes if leaf.shape and leaf.shape[0] % total == 0 else None
        return NamedSharding(mesh, P(lead, *([None] * (len(leaf.shape) - 1)))
                             if leaf.shape else P())
    return jax.tree_util.tree_map(one, batch_shape)


def cache_shardings(cache_shapes: Any, mesh: Mesh, daxes=("data",),
                    model_axis: str = "model") -> Any:
    """Decode-cache layout: batch dim over the data axes; KV sequence dim
    (flash-decode style) / SSM heads / conv channels over the model axis.
    Shared by the dry-run tool and shardlint's DecodeSession audit."""
    msize = mesh.shape[model_axis]
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]

    def rule(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        shape = leaf.shape
        spec = [None] * len(shape)
        # batch dim: attn caches [L,B,...] / ssm [L,B,...] / cross valid [B,F]
        bdim = 1 if len(shape) >= 2 and name != "valid" else 0
        if shape[bdim] % dsize == 0 and shape[bdim] >= dsize:
            spec[bdim] = daxes
        if name in ("k", "v", "pos") and len(shape) >= 3:
            # shard the cache sequence dim over model (flash-decode style)
            if shape[2] % msize == 0:
                spec[2] = model_axis
        elif name in ("h", "S") and len(shape) >= 3:
            if shape[2] % msize == 0:          # heads
                spec[2] = model_axis
        elif name == "conv" and len(shape) == 4:
            if shape[3] % msize == 0:
                spec[3] = model_axis
        elif name in ("x_tm", "x_cm"):
            pass
        return NamedSharding(mesh, P(*spec))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [rule(p, l) for p, l in flat])
