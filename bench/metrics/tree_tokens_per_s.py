"""Unique tree tokens of every step completed in the window, over the
window's wall time: a shared prefix counts once, padding never."""


def read(rec):
    if not rec["steps"] or rec["window_s"] <= 0:
        return None
    return sum(s["tokens"] for s in rec["steps"]) / rec["window_s"]
