"""Share of the window's row cells (packed rows and wave rows) that hold
no unique token, from the program's ``ExecutionPlan`` counts."""


def read(rec):
    cells = sum(s["cells"] for s in rec["steps"])
    if not cells:
        return None
    return sum(s["padded"] for s in rec["steps"]) / cells
