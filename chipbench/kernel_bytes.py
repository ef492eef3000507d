"""Bytes a kernel must move from HBM for one call, from the configuration's
published shapes and what the program counted: the numerator of a kernel's
roofline share (``readers/kernel_roofline.py``).  Only what cannot be avoided
is counted (the matrices of the experts some token chose; the values of the
rows a step attends over), so a share over 100% is a counting error."""

from typing import Dict


def moe_expert_matmul(config: Dict, traffic: Dict, experts_touched: float) -> float:
    """One expert layer of one decode step: gate, up and down matrices of
    every expert a token chose, at the weights' 2 bytes.  Activations (a few
    rows a matrix) are left out."""
    return experts_touched * 3 * config["hidden_size"] * config["moe_intermediate_size"] * 2


def mla_decode_attn(config: Dict, traffic: Dict, live_row_share: float) -> float:
    """One layer of one decode step: the cached values (c_kv and RoPE(k_r),
    without the lane padding) of every position an active slot attends over,
    at the pool's 2 bytes.  ``live_row_share`` is of slots x positions a slot."""
    rows = live_row_share * traffic["slots"] * traffic["positions_per_slot"]
    return rows * (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * 2
