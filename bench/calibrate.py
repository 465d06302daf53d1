"""Readings for a cell's correctness limits, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --faults 3 --out <file.json>

For each seed the program takes the cell's checked steps (the benchmark's
own set-up, without its window) and the reference follows them: the
three numbers of ``bench/check.py`` are the sound runs' readings, whose
largest is a limit's lower reading.  On the first ``--faults`` seeds the
reference also stands in for the program in three broken forms, compared
with the sound reference:

- ``control``: every matrix product with float8 operands, the precision
  below the configuration's bfloat16;
- ``half_batch``: each step's second half of trees left out, the mean
  taken over the rest;
- ``altered_token``: one token of each step's first tree changed.

A step that returns its state unchanged reads 1 on ``change_gap`` and
needs no run.  The benchmark's own runs never run this.

With ``--write-limits`` the readings set the cell's limits
(``bench/limits/<cell>.json``) by ``limits`` below.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import check  # noqa: E402
from bench.run import Run, load_cell, log  # noqa: E402


def half_batch(steps):
    return [s[:max(1, len(s) // 2)] for s in steps]


def altered_token(steps, vocab):
    out = []
    for s in steps:
        s = [copy.deepcopy(t) if i == 0 else t for i, t in enumerate(s)]
        s[0].tokens = s[0].tokens.copy()
        s[0].tokens[len(s[0].tokens) // 2] = (
            s[0].tokens[len(s[0].tokens) // 2] + 1) % vocab
        out.append(s)
    return out


ABOUT = ("readings from bench/calibrate.py on the chip: lower = the "
         "largest gap over the sound seeds; upper = the least of the control"
         " and each planted fault that reads at least 3x (control, unchanged"
         " state) or 10x (other faults) the lower; limit = lower^(1/3) * "
         "upper^(2/3), to two digits")
FAULTS = ("control", "half_batch", "altered_token")


def limits(readings: dict) -> dict:
    """Each number's limit from the sound runs and the faults, by the
    rule in ``ABOUT``; None where nothing separates from the sound runs."""
    out = {"_about": ABOUT}
    for n in check.NAMES:
        sound = [s[n] for s in readings["sound"] if s["ok"]]
        lower = max(sound)
        got = {k: sorted(f[n] for f in readings["faults"] if f["fault"] == k)
               for k in FAULTS}
        cands = [(v[0], k) for k, v in got.items()
                 if v and v[0] >= (3 if k == "control" else 10) * lower]
        if n == "change_gap" and 1.0 >= 3 * lower:
            cands.append((1.0, "unchanged_state"))
        upper, frm = min(cands) if cands else (None, None)
        lim = None if upper is None else \
            float(f"{lower ** (1 / 3) * upper ** (2 / 3):.2g}")
        out[n] = {"limit": lim, "lower": lower, "upper": upper,
                  "upper_from": frm, "sound_seeds": len(sound),
                  "faults": got}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", required=True)
    ap.add_argument("--write-limits", action="store_true")
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        log("refused: needs a TPU")
        return 2
    from repro.train.warmup import configure_compile_cache
    configure_compile_cache()
    cell = load_cell(args.workload)
    out = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           "sound": [], "faults": []}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        run = Run(cell["c"], cell["traffic"], seed, cell["wl"]["chips"],
                  name=cell["wl"]["config"])
        prog = run.start(warm=False)
        run.free()
        ref = run.reference()
        g = check.gaps(prog, ref)
        g.update(seed=seed, ok=prog["ok"], loss=prog["loss"],
                 ref_loss=ref["loss"])
        out["sound"].append(g)
        log(f"seed {seed}: loss {g['loss_gap']:.3e} grad {g['grad_gap']:.3e}"
            f" ({g['grad_worst']}) change {g['change_gap']:.3e} "
            f"({g['change_worst']}) {time.perf_counter() - t0:.1f}s")
        if i < args.faults:
            steps = run.checked
            for name, kw in (
                    ("control", dict(precision="fp8")),
                    ("half_batch", dict(steps_trees=half_batch(steps))),
                    ("altered_token", dict(steps_trees=altered_token(
                        steps, run.d["V"])))):
                bad = run.reference(**kw)
                f = check.gaps(bad, ref)
                f.update(seed=seed, fault=name)
                out["faults"].append(f)
                log(f"  {name}: loss {f['loss_gap']:.3e} grad "
                    f"{f['grad_gap']:.3e} change {f['change_gap']:.3e}")
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
    for n in check.NAMES:
        log(f"{n}: sound max {max(s[n] for s in out['sound']):.3e}; "
            + ", ".join(f"{k} min {min(f[n] for f in out['faults'] if f['fault'] == k):.3e}"
                        for k in FAULTS if out["faults"]))
    if args.write_limits:
        lim = limits(out)
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "limits", f"{args.workload}.json")
        with open(path, "w") as fh:
            json.dump(lim, fh, indent=1)
        for n in check.NAMES:
            log(f"limit {n}: {lim[n]['limit']} (lower {lim[n]['lower']:.3e}, "
                f"upper {lim[n]['upper']} from {lim[n]['upper_from']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
