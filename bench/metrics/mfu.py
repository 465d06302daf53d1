"""Model FLOPs of the window's trees (``bench/work.py``: each unique
token once, no padding, no recomputation) over window × chips × the
chip's bf16 peak, in %."""


def read(rec):
    if not rec["steps"] or rec["window_s"] <= 0:
        return None
    flops = sum(s["flops"] for s in rec["steps"])
    return 100.0 * flops / (rec["window_s"] * rec["chips"]
                            * rec["peaks"]["bf16_flops"])
