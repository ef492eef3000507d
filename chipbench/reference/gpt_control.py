"""The precision control of the dense serving cells: ``gpt.py``'s forward
pass put in the program's place and computed in the precision BELOW the one
``configs/cerebras-gpt-1.3b.json`` states for serving (bfloat16 products over
a bfloat16 K/V pool): every operand of every product (the activations, the
weights, q, k, v and the attention's weights) rounded to ``mantissa_bits`` bits
of mantissa, 3 for float8 e4m3.  ``reduce_precision`` keeps bfloat16's exponent
range, so this is float8 with a perfect scale a tensor: the mildest form of the
step that would tempt a later PR, and inside a jitted program XLA does not
elide it as it elides a pair of converts.

What ``correct`` compares in these cells is, for every token a request emits,
how far the float32 reference's logit of that token lies under the reference's
best, in standard deviations of the row.  The control does not decode: at each
position of the same prompt and served tokens it names the token the lower
precision puts first (:func:`first_tokens`), and the runner's own
``gap_sigma`` and ``compare_gaps`` read and judge it as they do the program's.
On the chip at the cell's own size: ``tools/gap_control.py``; at a size a test
run holds: ``tests/test_runners.py``.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import gpt


def _rounder(mantissa_bits: int):
    return lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=mantissa_bits)


def _dense(x, p, r):
    return r(x) @ r(p["kernel"].astype(jnp.float32)) + p["bias"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_head", "mantissa_bits"))
def block(x, p, n_head: int, mantissa_bits: int):
    """``gpt.block`` with every product's operands rounded."""
    r = _rounder(mantissa_bits)
    T, d = x.shape
    hd = d // n_head
    qkv = _dense(gpt._layer_norm(x, p["LayerNorm_0"]), p["qkv"], r).reshape(T, 3 * n_head, hd)
    q, k, v = r(qkv[:, :n_head]), r(qkv[:, n_head:2 * n_head]), r(qkv[:, 2 * n_head:])
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,khd->qhd", r(probs), v).reshape(T, d)
    x = x + _dense(att, p["proj"], r)
    y = _dense(gpt._layer_norm(x, p["LayerNorm_1"]), p["Dense_0"], r)
    return x + _dense(gpt._gelu_tanh(y), p["Dense_1"], r)


def logits(params, tokens, n_layer: int, n_head: int, rows, mantissa_bits: int):
    """``gpt.logits`` of ``rows`` in the lower precision (the head too)."""
    p = params["params"]
    x = gpt._embed(tokens, p["embed"], p["pos"])
    for i in range(n_layer):
        x = block(x, p[f"block{i}"], n_head, mantissa_bits)
    return _dense(gpt._layer_norm(x[rows], p["ln_f"]), p["lm_head"], _rounder(mantissa_bits))


def first_tokens(params, prompt, emitted, n_layer: int, n_head: int, mantissa_bits: int):
    """At every position where the request chose a token: the token the lower
    precision puts first, given the same prompt and the tokens served so far."""
    seq = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    rows = jnp.arange(len(prompt) - 1, len(seq) - 1)
    return np.asarray(logits(params, jnp.asarray(seq[:-1]), n_layer, n_head, rows, mantissa_bits)).argmax(-1)
