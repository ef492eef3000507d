"""Operations a windowed causal attention needs, from its shapes (beside
``flops.py``; ``readers/kernel_flops_roofline.py`` names this module).  A
multiply-add counts as two operations; only the (query, key) pairs inside the
window and the causal mask count, so what a kernel spends on the masked part of
a block is its loss, and a share over 100% is a counting error."""

from typing import Dict


def window_pairs(length: int, window: int) -> int:
    """(query, key) pairs of a causal attention over ``length`` positions in
    which a query sees itself and the ``window - 1`` keys before it."""
    full = min(length, window)
    return full * (full + 1) // 2 + (length - full) * window


def windowed_attention(config: Dict, heads: int, length: int) -> float:
    """One call over ``length`` positions with ``heads`` query heads: QK^T and
    the weighted sum of V, ``head_dim`` multiply-adds a pair each."""
    return 4.0 * config["head_dim"] * heads * window_pairs(length, config["sliding_window"])
