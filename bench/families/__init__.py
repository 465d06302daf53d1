"""Model code per family: ``bench/families/<family>.py``, found by the
configuration's ``program.family`` (the program's own family key).

Whatever the harness needs to know about a model's layers sits in that
one module; the harness (``bench/run.py``, ``bench/adapter.py``,
``bench/reference.py``) reaches it only through ``load``.  A family
joins by a new module alone.  It defines:

- ``check(c)``: raise ``ValueError``, naming the key, for a configuration
  this family would build as something else than it is;
- ``dims(c)``: the sizes; the shared code reads ``V`` (the published
  vocabulary), ``Vp`` (the rows the weights carry), ``tied``, ``eps``
  and ``std`` (the initializer's deviation);
- ``program_config(c, name)``: the program's ``ModelConfig``;
- ``shapes(c)``: ``{name: shape}`` of ``embed``, ``final_norm``,
  ``layers`` (``{name: shape}``, each stacked over the layers) and, for
  an untied head, ``lm_head``; ``bench.adapter.init_weights`` draws them
  in that order;
- ``SCALES``: the layer entries that are RMSNorm scales (drawn around 1);
- ``DECAY``: the entries that take weight decay;
- ``to_program(c, w)`` / ``from_program(p)``: the benchmark layout as the
  program's parameter tree and back;
- ``body(c, fp8, w, x, row)``: the reference's layers, from the embedded
  row ``x`` [R, D] (float32) to the hidden state before the final norm,
  with every matrix product through ``bench.reference.ein``; returns
  ``(hidden, aux)``, ``aux`` a float32 scalar added to the row's loss;
- ``step_work(trees, d)``: a step's ``tokens``, ``pairs``, model
  ``flops``, ``attn_flops`` and ``attn_bytes`` (what the metric readers
  take), counted from the generated trees.
"""
from __future__ import annotations

import importlib


def load(c: dict):
    """The module of ``c``'s family, once it has checked ``c``.  Raises
    ``ValueError`` for a family that has no module, or a configuration
    its module refuses."""
    name = c["program"]["family"]
    if not (isinstance(name, str) and name.isidentifier()):
        raise ValueError(f"program.family {name!r} is no module name")
    mod = f"{__name__}.{name}"
    try:
        fam = importlib.import_module(mod)
    except ModuleNotFoundError as e:
        if e.name != mod:
            raise
        raise ValueError(f"program.family {name!r}: no module "
                         f"bench/families/{name}.py") from None
    fam.check(c)
    return fam
