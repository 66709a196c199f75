"""Shared by tests/test_nemotron_h.py: the small Nemotron-H description
the tests serve (the head of the published pattern and one period, at
tiny widths) and the benchmark's plain reference loaded beside it —
`benchmark/reference/ssm_gqa_moe_block.py` IS the reference the tests
hold the program to (token-by-token scan, float32, no kernels); nothing
here restates an equation."""
from __future__ import annotations

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCH = {
    "model_type": "nemotron_h", "hidden_size": 64,
    "hybrid_override_pattern": "MEMEM*EMEMEM*E", "num_hidden_layers": 14,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16, "expand": 2,
    "intermediate_size": 32, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "mamba_proj_bias": False, "mlp_bias": False,
    "use_bias": False, "attention_bias": False,
    "tie_word_embeddings": False, "norm_eps": 1e-5,
    "layer_norm_epsilon": 1e-5, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4, "rope_theta": 10000,
    "partial_rotary_factor": 1, "sliding_window": None,
    "max_position_embeddings": 4096, "vocab_size": 512,
}
SHARE = {"layers": 13, "dense_layers": 0, "experts": [0, 8],
         "vocab": [0, 512]}


def reference():
    """benchmark/reference/ssm_gqa_moe_block.py as a module."""
    spec = importlib.util.spec_from_file_location(
        "bench_reference_ssm", os.path.join(
            REPO, "benchmark", "reference", "ssm_gqa_moe_block.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def describe(tmp_path, seed=5, arch=None, share=None) -> str:
    """Write a description file; returns its path."""
    path = os.path.join(str(tmp_path), "model.json")
    with open(path, "w") as f:
        json.dump({"architecture": {**ARCH, **(arch or {})},
                   "share": {**SHARE, **(share or {})}, "seed": seed}, f)
    return path
