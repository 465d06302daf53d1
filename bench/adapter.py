"""What the benchmark needs to drive the program, and nothing more.

- ``load_config``: a configuration file (``bench/configs/<name>.json``,
  the published ``config.json`` keys plus the program's own settings);
- ``init_weights``: the weights, made on the device from the seed in one
  jitted call, in bfloat16 (the type they are trained in), in the
  benchmark's own layout, which the reference reads as well;
- ``entry_norms``: the norm of each entry of that layout (one per layer
  and tensor), which is what the check compares;
- ``program_tree``: a generated tree as the program's tree type.

What depends on the model's layers (its sizes, the program's
``ModelConfig``, the layout and its mapping to the program's parameter
tree) is the family's: ``bench/families/``.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench import families

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return load_json("bench", "configs", f"{name}.json")


def seed_key(seed: int):
    import jax
    from bench.gen import seed_words
    words = seed_words(seed)
    key = jax.random.key(words[0])
    for w in words[1:]:
        key = jax.random.fold_in(key, w)
    return key


def _init(c: dict, key):
    """Benchmark-layout weights: normal(0, std) matrices and biases,
    RMSNorm scales 1 + normal(0, std), in the configuration's type."""
    import jax
    import jax.numpy as jnp
    fam = families.load(c)
    std = fam.dims(c)["std"]
    dt = jnp.bfloat16 if c["program"]["dtype"] == "bfloat16" else jnp.float32
    shapes = fam.shapes(c)
    flat = []
    for name, shp in shapes.items():
        if name == "layers":
            flat += [(k, s) for k, s in shp.items()]
        else:
            flat.append((name, shp))
    keys = jax.random.split(key, len(flat))
    vals = {}
    for (name, shp), k in zip(flat, keys):
        x = jax.random.normal(k, shp, jnp.float32) * std
        if name in fam.SCALES or name == "final_norm":
            x = x + 1.0
        vals[name] = x.astype(dt)
    w = {k: vals[k] for k in ("embed", "final_norm", "lm_head") if k in vals}
    w["layers"] = {k: vals[k] for k in shapes["layers"]}
    return w


def to_program(c: dict, w: dict) -> dict:
    """Benchmark layout → the program's parameter tree (no copies)."""
    ly = w["layers"]
    p = {"embed": {"table": w["embed"]},
         "final_norm": {"scale": w["final_norm"]},
         "layer_stacks": [{
             "ln1": {"scale": ly["ln1"]},
             "attn": {k: ly[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk",
                                         "bv")},
             "ln2": {"scale": ly["ln2"]},
             "mlp": {"wi_gate": ly["wg"], "wi_up": ly["wu"],
                     "wo": ly["wd"]}}]}
    if "lm_head" in w:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


def from_program(p: dict) -> dict:
    """The program's parameter-shaped tree (weights, moments, changes)
    → the benchmark layout."""
    s = p["layer_stacks"][0]
    w = {"embed": p["embed"]["table"], "final_norm": p["final_norm"]["scale"],
         "layers": {"ln1": s["ln1"]["scale"], "ln2": s["ln2"]["scale"],
                    "wg": s["mlp"]["wi_gate"], "wu": s["mlp"]["wi_up"],
                    "wd": s["mlp"]["wo"], **s["attn"]}}
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]["w"]
    return w


_INIT: dict = {}


def init_weights(c: dict, seed: int, program: bool = False, mesh=None):
    """The seed's weights on the device, made in one jitted call
    (replicated over the mesh's devices when it has several)."""
    import jax
    key = (json.dumps(c, sort_keys=True), program, mesh)
    if key not in _INIT:
        fam = families.load(c)
        out = None
        if mesh is not None and mesh.size > 1:
            out = jax.sharding.NamedSharding(mesh,
                                             jax.sharding.PartitionSpec())
        _INIT[key] = jax.jit(
            lambda k: fam.to_program(c, _init(c, k)) if program
            else _init(c, k), out_shardings=out)
    return _INIT[key](seed_key(seed))


def entry_norms(w: dict) -> dict:
    """Benchmark-layout tree → {entry: norm}: one entry per layer for the
    stacked layer tensors, one for each other tensor (float32)."""
    import jax.numpy as jnp
    out = {}
    for k in ("embed", "final_norm", "lm_head"):
        if k in w:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(w[k].astype(jnp.float32))))
    for k, a in w["layers"].items():
        a = a.astype(jnp.float32)
        out[k] = jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(
            range(1, a.ndim))))
    return out


def flatten_norms(norms: dict) -> dict:
    """{entry: scalar or per-layer array} → {"wq.3": float, ...}."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[k] = float(v)
        else:
            out.update({f"{k}.{i}": float(x) for i, x in enumerate(v)})
    return out


def program_tree(node):
    """A generated ``bench.gen.Node`` tree as the program's tree type."""
    from repro.core.tree import TrajectoryTree, TreeNode

    def rec(n):
        return TreeNode(tokens=n.tokens, trained=n.trained,
                        children=[rec(ch) for ch in n.children])

    return TrajectoryTree(root=rec(node))
