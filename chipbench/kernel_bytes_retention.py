"""Bytes a kernel of the power-retention configuration must move from HBM for
one call (``readers/kernel_roofline_of.py`` names this module), from the
configuration's published shapes and what the program counted.  Only what
cannot be avoided is counted, so a share over 100% is a counting error."""

from typing import Dict


def retention_decode(config: Dict, traffic: Dict, live_slots: float) -> float:
    """One retention layer of one decode step: the float32 state of every slot
    that holds live state, K/V heads x the symmetric square's d (d + 1) / 2
    distinct products x d_v, read once and written once.  What the kernel's
    layout adds to it (8,320 places for 8,256 products), the normaliser (a
    128th) and the step's q, k, v (a few KB a head) are left out."""
    d = config["head_dim"]
    return live_slots * config["num_key_value_heads"] * (d * (d + 1) // 2) * d * 4 * 2
