"""Operations the chunked form of the Mamba-2 recurrence needs for a prompt,
from the configuration's published shapes (beside ``flops.py``;
``readers/kernel_flops_of.py`` names this module).  A multiply-add counts as
two operations; only the prompt's REAL positions count, at the PUBLISHED chunk,
whatever chunk a kernel picks and whatever it spends on a bucket's padding, so
a share over 100% is a counting error."""

from typing import Dict


def ssd_prefill(config: Dict, positions: float) -> float:
    """One Mamba-2 layer over ``positions`` real positions, in chunks of Q =
    ``mamba_chunk_size`` (256): a chunk is ``G = C B^T`` once for every head (2
    Q Q N) and, a head, ``(L o G)(dt x)`` (2 Q Q P), the carried state's
    read-out ``C S^T`` and the state's update ``(dt x)^T B`` (2 Q N P each).
    Linear in ``positions``: a mean over prompts gives their sum."""
    Q, N = config["mamba_chunk_size"], config["mamba_d_state"]
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    return positions / Q * (2.0 * Q * Q * N + H * (2.0 * Q * Q * P + 4.0 * Q * N * P))
