"""Device time per step of the tree-attention dk/dv kernel
(``tree_attn_dkv``), in the traced window."""
from bench.scopes import per_step_ms


def read(rec):
    return per_step_ms(rec, "kernel_s", "tree_attn_dkv")
