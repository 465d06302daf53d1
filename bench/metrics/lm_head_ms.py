"""Device time per step of the ops under ``lm_head`` (the head, its log-
sum-exp and the NLL, forward and backward), in the traced window."""
from bench.scopes import per_step_ms


def read(rec):
    return per_step_ms(rec, "scope_s", "lm_head")
