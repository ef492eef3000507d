"""Plain reference of the GPT-2-shaped decoder the LM cells run.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no batching
tricks, and no import from ``moolib_tpu``.  It follows the published
architecture (Cerebras-GPT, arXiv:2304.03208: pre-LayerNorm blocks, fused QKV
multi-head attention, a GELU FFN of 4d, learned positions) with the departures
the configuration file lists under ``assumed``: an untied head with a bias,
the tanh form of GELU, LayerNorm epsilon 1e-6.

Weights come in as the nested dict the program's ``TransformerLM`` holds
(``embed/embedding``, ``pos/embedding``, ``block<i>/{LayerNorm_0, qkv, proj,
LayerNorm_1, Dense_0, Dense_1}``, ``ln_f``, ``lm_head``): the layout is the
one thing the reference takes from the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_EPS = 1e-6


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + _EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head",))
def block(x, p, n_head: int):
    """One pre-LN block on ``x`` [T, d]."""
    with jax.default_matmul_precision("highest"):
        T, d = x.shape
        hd = d // n_head
        qkv = _dense(_layer_norm(x, p["LayerNorm_0"]), p["qkv"]).reshape(T, 3 * n_head, hd)
        q, k, v = qkv[:, :n_head], qkv[:, n_head:2 * n_head], qkv[:, 2 * n_head:]
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((T, T), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(T, d)
        x = x + _dense(att, p["proj"])
        y = _dense(_layer_norm(x, p["LayerNorm_1"]), p["Dense_0"])
        return x + _dense(_gelu_tanh(y), p["Dense_1"])


@jax.jit
def _embed(tokens, embed, pos):
    T = tokens.shape[0]
    return embed["embedding"].astype(jnp.float32)[tokens] + pos["embedding"].astype(jnp.float32)[:T]


@jax.jit
def _head(x, ln_f, lm_head):
    with jax.default_matmul_precision("highest"):
        return _dense(_layer_norm(x, ln_f), lm_head)


def features(params, tokens, n_layer: int, n_head: int):
    """Final hidden states [T, d] of one sequence ``tokens`` [T]."""
    p = params["params"]
    x = _embed(tokens, p["embed"], p["pos"])
    for i in range(n_layer):
        x = block(x, p[f"block{i}"], n_head)
    return x


def logits(params, tokens, n_layer: int, n_head: int, rows=None):
    """Logits [T, vocab] of one sequence, or of its ``rows`` only."""
    x = features(params, tokens, n_layer, n_head)
    if rows is not None:
        x = x[rows]
    p = params["params"]
    return _head(x, p["ln_f"], p["lm_head"])


def _sequence_loss(params, seq, n_layer: int, n_head: int):
    """Next-token cross-entropy over the repeated half of one sequence:
    positions T/2 - 1 .. T - 2 predict the tokens T/2 .. T - 1."""
    p = params["params"]
    half = seq.shape[0] // 2
    x = _embed(seq, p["embed"], p["pos"])
    for i in range(n_layer):
        # one block's activations at a time: the backward pass recomputes them
        x = jax.checkpoint(block, static_argnums=(2,))(x, p[f"block{i}"], n_head)
    logp = jax.nn.log_softmax(_head(x[half - 1:-1], p["ln_f"], p["lm_head"]), axis=-1)
    return -jnp.take_along_axis(logp, seq[half:, None], axis=-1).mean()


@functools.partial(jax.jit, static_argnames=("n_layer", "n_head"))
def _sequence_loss_and_grad(params, seq, n_layer: int, n_head: int):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_sequence_loss)(params, seq, n_layer, n_head)


@functools.partial(jax.jit, static_argnames=("n_layer", "n_head"))
def _sequence_loss_only(params, seq, n_layer: int, n_head: int):
    with jax.default_matmul_precision("highest"):
        return _sequence_loss(params, seq, n_layer, n_head)


_tree_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=0)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grad_sum, mu, nu, t, n, lr, b1, b2, eps, weight_decay):
    """One step of AdamW (Loshchilov & Hutter, arXiv:1711.05101, algorithm 2
    with the decay scaled by the learning rate) on the mean of ``n`` summed
    per-sequence gradients."""
    def leaf(p, g, m, v):
        g = g / n
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        step = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
        return p - lr * (step + weight_decay * p), m, v
    out = jax.tree_util.tree_map(leaf, params, grad_sum, mu, nu)
    pick = lambda k: jax.tree_util.tree_map(lambda _p, o: o[k], params, out)
    return pick(0), pick(1), pick(2)


def copy_task_losses(params, batches, n_layer: int, n_head: int, adamw: dict) -> list:
    """The copy task's loss (mean over the batch) on each of ``batches`` in
    turn, with one AdamW step on each batch but the last in between: entry k
    is the loss of batch k under the weights after k steps.  One sequence at a
    time, in float32 throughout.  ``params`` is consumed by the first step."""
    params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for k, batch in enumerate(batches):
        last = k + 1 == len(batches)
        total, grad_sum = 0.0, None
        for seq in batch:
            if last:
                loss = _sequence_loss_only(params, seq, n_layer, n_head)
            else:
                loss, grad = _sequence_loss_and_grad(params, seq, n_layer, n_head)
                grad_sum = grad if grad_sum is None else _tree_add(grad_sum, grad)
            total += float(loss)
        losses.append(total / len(batch))
        if not last:
            params, mu, nu = _adamw(
                params, grad_sum, mu, nu, float(k + 1), float(len(batch)), adamw["learning_rate"],
                adamw["b1"], adamw["b2"], adamw["eps"], adamw["weight_decay"])
    return losses
