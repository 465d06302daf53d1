"""90th percentile of the window's step times; a step runs from the end
of the previous step's host sync to the end of its own, so the wait for
its plan and its dispatch are inside it."""
import numpy as np


def read(rec):
    if not rec["steps"]:
        return None
    return float(np.percentile([s["dt"] for s in rec["steps"]], 90))
