"""Small configurations and a small traffic mix for the benchmark's CPU
tests (the benchmark itself refuses to run without a TPU; these tests
drive its parts directly)."""
from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the program's qwen1.5 and qwen2 smoke sizes, in config.json keys
SMOKE_MHA = {
    "hidden_act": "silu", "hidden_size": 64, "initializer_range": 0.02,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "tie_word_embeddings": False, "vocab_size": 512,
    "source": "smoke", "program": {
        "family": "dense", "qkv_bias": True, "mlp_activation": "swiglu",
        "dtype": "float32", "vocab_pad_multiple": 8, "remat": "none"}}
SMOKE_GQA = dict(copy.deepcopy(SMOKE_MHA), hidden_size=96,
                 intermediate_size=192, num_attention_heads=6,
                 num_key_value_heads=2, tie_word_embeddings=True)

TRAFFIC = {
    "kind": "agentic",
    "gen": {"num_turns": 3, "turn_len_range": [8, 48],
            "tool_branch_prob": 0.4, "think_branch_prob": 0.3,
            "max_parallel_tools": 4, "trained_frac": 0.6},
    "shape_seed": 3, "pool_steps": 4, "trees_per_step": 2, "seq_len": 256,
    "rows_per_chip": 2, "auto_partition": True, "capacity": "auto",
    "loss_mode": "sep_avg", "lookahead": 1, "plan_workers": 1,
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                  "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 2,
                  "total_steps": 3, "min_lr_ratio": 0.1},
    "ref_steps": 3}

# float32 on the CPU: sound runs read about 1e-7 on the loss and the
# gradient; the weight change reads up to 0.7 % because the program
# decays its stacked norm scales and biases, which the reference does not
LIMITS = {"loss_gap": {"limit": 1e-4}, "grad_gap": {"limit": 1e-3},
          "change_gap": {"limit": 0.05}}
SEED = 2 ** 31 + 7


def make_run(c=None, traffic=None, seed: int = SEED):
    from bench.run import Run
    return Run(c or SMOKE_MHA, traffic or TRAFFIC, seed, 1, name="smoke")
