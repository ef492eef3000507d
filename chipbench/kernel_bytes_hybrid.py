"""Bytes a kernel of the hybrid linear-attention configuration must move from
HBM for one call (``readers/kernel_roofline_of.py`` names this module), from
the configuration's published shapes and what the program counted.  Only what
cannot be avoided is counted, so a share over 100% is a counting error.  The
held experts' matmul is counted by ``kernel_bytes.moe_expert_matmul``: an
expert's three matrices are the same bytes held or not."""

from typing import Dict


def kda_decode(config: Dict, traffic: Dict, live_slots: float) -> float:
    """One KDA layer of one decode step: the float32 state of every slot that
    holds live state, heads x d_v x d_k, read once and written once.  The
    step's q, k, v, g (a few KB a head) are left out."""
    lin = config["linear_attn_config"]
    return live_slots * lin["num_heads"] * lin["head_dim"] * lin["head_dim"] * 4 * 2

