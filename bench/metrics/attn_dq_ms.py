"""Device time per step of the tree-attention dq kernel
(``tree_attn_dq``), in the traced window."""
from bench.scopes import per_step_ms


def read(rec):
    return per_step_ms(rec, "kernel_s", "tree_attn_dq")
