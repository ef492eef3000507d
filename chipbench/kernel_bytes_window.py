"""Bytes the decode attention of a sliding-window layer must move from HBM for
one call (``readers/kernel_roofline_of.py`` names this module), from the
configuration's published shapes and what the program counted.  Only what
cannot be avoided is counted (the rows inside the window, K and V, once each,
whatever kernel reads them and however it lays them out), so a share over
100% is a counting error."""

from typing import Dict

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def swa_decode_attention(config: Dict, traffic: Dict, rows_read: float) -> float:
    """One sliding layer of one decode step: ``rows_read`` rows (the sum over
    the active slots of min(position + 1, window), the program's
    ``serve_engine_ring_rows_read``), each K/V heads x head size wide, K and
    V.  The step's q and its output (a few KB a head) are left out."""
    row = config["num_key_value_heads"] * config["head_dim"]
    return rows_read * row * _ITEMSIZE[config["precision"]["serve"]["kv_ring"]] * 2
