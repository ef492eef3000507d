"""Bytes the Mamba-2 decode kernel must move from HBM for one call
(``readers/kernel_roofline_of.py`` names this module), from the
configuration's published shapes and what the program counted.  Only what
cannot be avoided is counted, so a share over 100% is a counting error."""

from typing import Dict


def ssd_decode(config: Dict, traffic: Dict, live_slots: float) -> float:
    """One Mamba-2 layer of one decode step: the float32 state of every slot
    that holds live state, heads x d_head x d_state, read once and written
    once: what ANY kernel must move.  What a slot brings to the step and takes
    away (decays, inputs, B, C, y: 0.1 MB beside 8.4) is left out, and so is
    the convolution's tail, which the kernel does not move."""
    state = config["mamba_n_heads"] * config["mamba_d_head"] * config["mamba_d_state"]
    return live_slots * state * 4 * 2
