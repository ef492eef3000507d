"""Operations the algorithm needs, computed from a configuration's shapes.

Kept with the benchmark so that no PR that claims a gain can change how its
utilisation is counted.  A multiply-add counts as two operations.
"""

from __future__ import annotations

from typing import Dict


def gpt_block_macs_per_row(config: Dict, attended: float) -> float:
    """Multiply-adds one row costs in one block of a GPT-2-shaped decoder when
    it attends to ``attended`` positions: QKV (3 d^2), output projection
    (d^2), the FFN (2 d n_inner), and QK^T and AV at d a position each.  THE
    definition of a block's operations: the train step's count and a serving
    step's both stand on it."""
    d = config["n_embd"]
    return 4 * d * d + 2 * d * config["n_inner"] + 2 * d * attended


def gpt_forward_flops_per_token(config: Dict, n_layer: int, seq_len: int) -> float:
    """Forward operations per token of a GPT-2-shaped decoder at ``seq_len``.

    Per block ``gpt_block_macs_per_row`` under causal attention: a token
    attends to (T + 1) / 2 positions on average.
    The head costs d * vocab.  Embedding lookups, LayerNorm, GELU and softmax
    are not counted (they are not matrix operations, and the peak they would
    be held to is not theirs).  Operations that the masked half of a dense
    attention spends, or that recomputation repeats, do not count either.
    """
    block = gpt_block_macs_per_row(config, (seq_len + 1) / 2)
    return 2.0 * (n_layer * block + config["n_embd"] * config["vocab_size"])


def gpt_train_flops_per_token(config: Dict, n_layer: int, seq_len: int) -> float:
    """Forward and backward: the backward pass costs twice the forward."""
    return 3.0 * gpt_forward_flops_per_token(config, n_layer, seq_len)


def gpt_admit_step_flops(config: Dict, n_layer: int, tokens: int, rows: int) -> float:
    """Operations a decode step that carries one admission WAS ASKED for
    (``engine_admit_step``; ``tokens`` and ``rows`` are what its dispatching
    span says): the prompt's ``tokens`` REAL positions through every block,
    causal among themselves, as the forward count above has them; the
    ``rows`` decode rows through every block's products; the head over
    ``rows + 1`` rows (each decode row's next token and the prompt's first).
    The positions that pad the prompt to its bucket count for NOTHING: what
    the program computes for them is utilisation lost, and a program that
    skips them gains it.  Left out: the decode rows' attention over their
    slots' K/V (the span does not say their lengths; it is bound by bytes
    and a percent of the step's operations), and as above everything that
    is no matrix product."""
    prompt = tokens * gpt_block_macs_per_row(config, (tokens + 1) / 2)
    decode = rows * gpt_block_macs_per_row(config, 0)
    head = (rows + 1) * config["n_embd"] * config["vocab_size"]
    return 2.0 * (n_layer * (prompt + decode) + head)
