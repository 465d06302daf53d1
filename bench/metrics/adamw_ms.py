"""Device time per step of the ops under ``adamw`` (the optimizer
update), in the traced window."""
from bench.scopes import per_step_ms


def read(rec):
    return per_step_ms(rec, "scope_s", "adamw")
