"""Device time per step of the grouped expert products (``expert_gmm``,
``expert_tgmm``), in the traced window: the time of every Pallas call
(``trace.pallas_s`` over the chips) less the tree-attention kernels'
named time (``bench.scopes`` ``kernel_s`` of the ``tree_attn_*``
names).  In this program the Pallas calls outside those names are the
expert products; a configuration without experts reads about 0."""
from bench.scopes import read as scopes


def expert_s(rec):
    """Seconds of the traced window's expert products per chip, or None
    where the trace cannot say."""
    tr = rec.get("trace")
    tab = scopes(rec)
    if not tr or tab is None or not tr["steps"]:
        return None
    attn = sum(v for k, v in tab["kernel_s"].items()
               if k.startswith("tree_attn_"))
    return max(tr["pallas_s"] / tr["chips"] - attn, 0.0)


def read(rec):
    t = expert_s(rec)
    return None if t is None else 1e3 * t / len(rec["trace"]["steps"])
