"""Operations the algorithm needs, computed from a configuration's shapes.

Kept with the benchmark so that no PR that claims a gain can change how its
utilisation is counted.  A multiply-add counts as two operations.
"""

from __future__ import annotations

from typing import Dict


def gpt_forward_flops_per_token(config: Dict, n_layer: int, seq_len: int) -> float:
    """Forward operations per token of a GPT-2-shaped decoder at ``seq_len``.

    Per block: QKV (3 d^2), output projection (d^2) and the FFN (2 d n_inner)
    as multiply-adds, plus causal attention: a token attends to (T + 1) / 2
    positions on average, QK^T and AV each cost d multiply-adds a position.
    The head costs d * vocab.  Embedding lookups, LayerNorm, GELU and softmax
    are not counted (they are not matrix operations, and the peak they would
    be held to is not theirs).  Operations that the masked half of a dense
    attention spends, or that recomputation repeats, do not count either.
    """
    d = config["n_embd"]
    inner = config["n_inner"]
    matmul = 4 * d * d + 2 * d * inner
    attention = 2 * d * (seq_len + 1) / 2
    return 2.0 * (n_layer * (matmul + attention) + d * config["vocab_size"])


def gpt_train_flops_per_token(config: Dict, n_layer: int, seq_len: int) -> float:
    """Forward and backward: the backward pass costs twice the forward."""
    return 3.0 * gpt_forward_flops_per_token(config, n_layer, seq_len)
