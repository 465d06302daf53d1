"""Milliseconds per step that the loop waited on the planner: the growth
of the program's ``PlanPipeline.exposed_s`` over the window, per step."""


def read(rec):
    if not rec["steps"]:
        return None
    return 1e3 * rec["plan_wait_s"] / len(rec["steps"])
