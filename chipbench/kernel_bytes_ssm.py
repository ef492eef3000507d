"""Bytes the selective-scan kernels of the Mamba configuration must move from
HBM for one call (``readers/kernel_roofline_of.py`` and
``readers/kernel_bytes_roofline.py`` name this module), from the
configuration's published shapes, the matched call's own shape and what the
program counted.  Only what cannot be avoided is counted, so a share over 100%
is a counting error."""

from typing import Dict


def ssm_decode(config: Dict, traffic: Dict, live_slots: float) -> float:
    """One Mamba layer of one decode step: the float32 state of every slot
    that holds live state, d_state x d_inner, read once and written once.  The
    step's dt, u and y (20 KB each a slot) are left out, and so is the
    convolution's tail, which the kernel does not move."""
    d_inner = config["mamba_expand"] * config["hidden_size"]
    return live_slots * config["mamba_d_state"] * d_inner * 4 * 2


def ssm_prefill(config: Dict, positions: float, channels: int) -> float:
    """One Mamba layer of one prompt of ``positions`` REAL positions (the
    metric hands the traced window's mean prompt length, not the bucket the
    call's shape holds: the padding is no work any scan must do): u, dt and z
    read and y written, float32 [positions, channels]; B and C [positions,
    d_state]; the final state written once.  What ANY scan over the prompt
    must move, whatever it keeps on the chip in between."""
    states = config["mamba_d_state"]
    return 4.0 * (4 * positions * channels + 2 * positions * states + states * channels)
