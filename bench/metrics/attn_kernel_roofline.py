"""The tree-attention kernels' share of their roofline over the traced
steps, in %: the least time the chips could take for the attention work
of those steps' trees (the larger of its FLOPs over the bf16 peak and
its bytes over the HBM bandwidth), over the device time of every Pallas
call.  Every Pallas call in this program is tree attention."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["pallas_s"] <= 0 or not tr["steps"]:
        return None
    pk = rec["peaks"]
    t_min = max(sum(s["attn_flops"] for s in tr["steps"]) / pk["bf16_flops"],
                sum(s["attn_bytes"] for s in tr["steps"])
                / pk["hbm_bytes_per_s"])
    return 100.0 * t_min / tr["pallas_s"]
