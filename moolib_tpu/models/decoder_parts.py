"""What the decoders built from a published configuration file share
(``latent_moe``, ``hybrid_kda``, ``retention_lm``): everything that is not a
mixer, as plain functions.  Nothing here knows the serving engine: ``engine/``
imports ``models/``, never the other way.  Matmul inputs are in the model's
``dtype`` with float32 accumulation; RMSNorm, RoPE and the logits float32.
"""

from __future__ import annotations

import json
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class SlotCache(NamedTuple):
    """The device cache of a model that keeps both kinds of leaf, one pytree
    in the engine's donated chain (``engine/engine.py``, "What a model offers").

    blocks: the paged pools, block axis first (``model.cache_spec``).
    slots: what a decode SLOT owns, slot axis first (``model.state_spec``).  It
        needs no allocator: the slot's id is its address, a join overwrites
        the row whole, a retire leaves it.
    """

    blocks: Any
    slots: Any


def load_config(config, overrides):
    """A configuration (a dict, or the path of its JSON file) with
    ``overrides`` laid over it (a test's depth, the engine's ``max_len``).
    Returns (the merged dict, the ``dtype`` popped from them: bfloat16 without one)."""
    if not isinstance(config, dict):
        with open(config) as f:
            config = json.load(f)
    dtype = overrides.pop("dtype", jnp.bfloat16)
    return {**config, **overrides}, dtype


def refuse(name: str, refused) -> None:
    """Raise one ``ValueError`` naming every key of ``refused`` whose value is
    true: what the file asks for and the model ``name`` cannot honour."""
    bad = sorted(k for k, v in refused.items() if v)
    if bad:
        raise ValueError(f"{name} does not implement the file's {', '.join(bad)}")


def weight_drawer(key, count: int, dtype):
    """``init``'s random draws: (an iterator over ``jax.random.split(key,
    count)``, ``w``).  ``w`` takes the iterator's next key and draws normal
    with deviation ``scale * fan_in ** -0.5`` in float32, cast to ``dtype``
    (None: the model's); ``init`` takes keys from the iterator itself for a
    bias or a decay.  A tree's values follow from the ORDER of the draws."""
    keys = iter(jax.random.split(key, count))
    model_dtype = dtype

    def w(shape, fan_in, dtype=None, scale=1.0):
        def draw(key, shape):
            x = jax.random.normal(key, shape, jnp.float32) * (scale * fan_in ** -0.5)
            return x.astype(dtype or model_dtype)

        if len(shape) < 3:
            return draw(next(keys), shape)
        # A slice of the leading axis at a time: the float32 draw of a
        # whole stack of experts would not fit beside the weights.
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(next(keys), shape[0]))

    return keys, w


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope_half_split(x, pos, theta):
    """Rotary embedding, half-split pairs (i, i + r/2); x [..., r] with
    ``pos`` broadcastable against its leading axes."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def dot(x, w, dtype):
    return jnp.dot(x.astype(dtype), w, preferred_element_type=jnp.float32)


def head_logits(h, final_norm, head, eps, dtype):
    """The final norm and the head over h [..., D]: float32 logits [..., V]."""
    return dot(rms_norm(h, final_norm, eps), head, dtype)


def rows_to_blocks(x, block_size: int, axis: int):
    """A prompt's rows along ``axis`` (its bucket's length Lb) as ``ceil(Lb /
    block_size)`` blocks: the axis padded to whole blocks and split in two."""
    nbw = -(-x.shape[axis] // block_size)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, nbw * block_size - x.shape[axis])
    return jnp.pad(x, pad).reshape(
        x.shape[:axis] + (nbw, block_size) + x.shape[axis + 1:])


def write_slot_rows(leaves, rows, slot):
    """A join's ``write_state``: the slot's row of every slot-axis leaf
    becomes the prefill's, whole, whatever the slot's last holder left."""
    return jax.tree.map(
        lambda leaf, new: jax.lax.dynamic_update_index_in_dim(
            leaf, new.astype(leaf.dtype), slot, 0),
        leaves, rows)
