"""The grouped expert products' share of their roofline over the traced
steps, in %: the least time the chip could take for the expert work of
those steps' trees (``expert_flops`` over the bf16 peak or
``expert_bytes`` over the HBM bandwidth, whichever is larger; the
families' ``step_work``, from the expected rows a held expert gets) over
the products' device time (``bench/metrics/expert_gmm_ms.py``)."""
from bench.metrics.expert_gmm_ms import expert_s


def read(rec):
    t = expert_s(rec)
    steps = (rec.get("trace") or {}).get("steps") or []
    if not t or not steps or "expert_flops" not in steps[0]:
        return None
    pk = rec["peaks"]
    t_min = max(sum(s["expert_flops"] for s in steps) / pk["bf16_flops"],
                sum(s["expert_bytes"] for s in steps)
                / pk["hbm_bytes_per_s"])
    return 100.0 * t_min / (t * rec["trace"]["chips"])
