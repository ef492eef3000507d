"""Operations a routed expert layer's grouped matmuls need, from the
configuration's published shapes and what the program counted (beside
``flops.py``; ``readers/kernel_flops_of.py`` names this module).  A
multiply-add counts as two operations; only the LIVE (token, expert) pairs
count (a prompt's real tokens times the experts a token, as the program's
``serve_moe_prefill_pairs`` has them), so what the kernel spends on a bucket's
padding, on a row tile past a group's end or on a tile two groups share is its
loss, and a share over 100% is a counting error."""

from typing import Dict


def expert_layer(config: Dict, pairs: float) -> float:
    """One expert layer over ``pairs`` (token, expert) pairs: gate and up
    (hidden x 2 x expert width) and down (expert width x hidden), a
    multiply-add an entry a pair."""
    D, F = config["hidden_size"], config["moe_intermediate_size"]
    return 2.0 * pairs * (D * 2 * F + F * D)


def expert_matmul_call(config: Dict, pairs: float) -> float:
    """The MEAN over a layer's two grouped-matmul calls (gate|up, then down):
    ``kernel_flops_of`` multiplies it by the matched events, two a layer, so
    that their sum is :func:`expert_layer`'s."""
    return expert_layer(config, pairs) / 2.0
