"""Bytes the paged decode-attention kernel must move from HBM for one call
(``readers/kernel_roofline_of.py`` names this module), from the
configuration's published shapes, the traffic file's pool geometry and what
the program counted.  Only what cannot be avoided is counted (the live
blocks' K and V, once each, whatever kernel reads them), so a share over
100% is a counting error."""

from typing import Dict

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def paged_attention(config: Dict, traffic: Dict, live_share: float) -> float:
    """One paged layer of one decode step: every K/V block that holds a
    position the step attends over (``serve_engine_kv_live_share`` of
    slots x blocks a slot), K and V, whole (a block is one copy: the unused
    tail of a slot's last block rides with it).  The step's q and its output
    (a few KB a head) and the block tables are left out."""
    blocks_a_slot = -(-traffic["positions_per_slot"] // traffic["block_size"])
    block = (traffic["block_size"] * config["num_key_value_heads"] * config["head_dim"]
             * _ITEMSIZE[config["precision"]["serve"]["kv_pool"]])
    return live_share * traffic["slots"] * blocks_a_slot * block * 2
