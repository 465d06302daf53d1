"""Host time per step inside ``repro.plan.build`` (plan builds, on every
thread), in the traced window."""
from bench.scopes import per_step_ms


def read(rec):
    return per_step_ms(rec, "host_s", "repro.plan.build")
