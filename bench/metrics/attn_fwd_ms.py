"""Device time per step of the forward tree-attention kernel
(``tree_attn_fwd``): the forward and the forward that remat recomputes,
in the traced window."""
from bench.scopes import per_step_ms


def read(rec):
    return per_step_ms(rec, "kernel_s", "tree_attn_fwd")
