"""Plain reference of the ``granitemoehybrid`` decoder (granite-4.0-h-small),
as published and as the configuration's ``assumed`` completes it: float32
``jax.numpy`` at the highest matmul precision, no cache, no kernels, no chunks,
no sorting, independent of ``moolib_tpu``.

x [T, D] the residual stream, ``e`` a token's row of the table, no positions
anywhere, no biases but the convolution's, RMSNorm eps 1e-5::

    h = embedding_multiplier e                                   (12)
    per layer:  h = h + residual_multiplier Mixer(RMSNorm(h; w_mixer))          (0.22)
                h = h + residual_multiplier Experts(RMSNorm(h; w_ffn))
    logits = (RMSNorm(h; w_final) E^T) / logits_scaling          (16; E the table, tied)

    Mamba-2:    [z | xBC | dt] = x W_in
                xBC_t <- silu(b_c + sum_{i=0..3} w_c[i] xBC_{t-3+i})     (zeros before the prompt)
                [x | B | C] = xBC          x: heads of d_head channels; B, C: d_state, ONE group
                dt = softplus(dt + dt_bias);   A = -exp(A_log)        one a head each
                S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T          S [heads, d_head, d_state], S_{-1} = 0
                y_t = S_t C_t + D x_t
                out = RMSNorm(y * silu(z); w_gate, over ALL channels) W_out      the gate BEFORE the norm
    attention:  q = x W_q (32 heads of 128);  [k | v] = x W_kv (8 heads)
                out = W_o concat_h softmax_causal(attention_multiplier q_h k^T) v     (1 / 128)
    experts:    s = x W_r over the router's whole width; the ``num_experts_per_tok`` largest;
                g = softmax over THOSE logits.  This chip holds experts ``held_from ..
                held_from + G - 1`` (G the matrices given): sum over the chosen experts THAT
                ARE HELD of g_e SwiGLU_e(x), plus the shared expert, ungated.  What the absent
                experts would add is left out; the weights are normalised over all the chosen.

The recurrence runs a token at a time over the whole sequence and attention
through a full [T, T] matrix a head; every held expert is computed for every
token and masked by its weight: nothing here shares a trick with the program.
:func:`expert_shares` gives the expert layer one share at a time, for the test
that the shares add up to the uncut layer.

Weights are the program's pytree (``w_in`` is z | x B C | dt, ``w_kv`` W_k |
W_v, ``*_gu`` W_gate | W_up side by side; ``mamba`` is a tuple of the runs of
Mamba layers between attention layers, each stacked on a leading axis, ``attn``
the attention layers stacked, ``experts_*`` every layer's held experts
``[L, G, ..]``), in any dtype: each layer is widened to float32 as its turn
comes, an expert and a block of the table's rows at a time, so that at the
published widths ``logits`` fits beside a serving engine that holds 12.5 GB.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_KEYS = ("num_attention_heads", "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
         "mamba_d_state", "rms_norm_eps", "num_experts_per_tok", "attention_multiplier",
         "residual_multiplier")
_HEAD_BLOCKS = 8  # the table is widened an eighth of the vocabulary at a time
_ROWS = 512  # rows of logits a program: they go to the host a block of this many at a time


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _f32(x):
    return x.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _swiglu(x, w_gate_up, w_down):
    gu = _mm(x, w_gate_up)
    F = gu.shape[1] // 2
    return _mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], w_down)


def mamba(p, x, cfg):
    """The Mamba-2 mixer over one sequence x [T, D] (already normed)."""
    T = x.shape[0]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    Ci = H * P
    wide = _mm(x, p["w_in"])
    z, xbc, dt = wide[:, :Ci], wide[:, Ci:2 * Ci + 2 * N], wide[:, 2 * Ci + 2 * N:]
    taps = _f32(p["conv"])  # [4, channels]: tap i weighs the input 3 - i positions back
    before = jnp.concatenate([jnp.zeros((taps.shape[0] - 1, xbc.shape[1]), jnp.float32), xbc])
    xbc = jax.nn.silu(_f32(p["conv_bias"]) + sum(
        taps[i] * before[i:i + T] for i in range(taps.shape[0])))
    xs, B, C = xbc[:, :Ci].reshape(T, H, P), xbc[:, Ci:Ci + N], xbc[:, Ci + N:]
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))  # [T, H]
    A = -jnp.exp(_f32(p["a_log"]))  # [H]

    def token(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        return S, jnp.sum(S * C_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32), (xs, dt, B, C))
    y = (y + _f32(p["d"])[:, None] * xs).reshape(T, Ci) * jax.nn.silu(z)
    return _mm(_rms(y, p["gate_norm"], cfg["rms_norm_eps"]), p["w_out"])


def attention(p, x, cfg):
    """The grouped-query mixer over one sequence x [T, D] (already normed): a
    full [T, T] softmax a query head, against its group's K/V head; scores
    times ``attention_multiplier``, no positions."""
    T, H, Hk = x.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = _mm(x, p["w_q"]).reshape(T, H, -1)
    d = q.shape[-1]
    kv = _mm(x, p["w_kv"]).reshape(T, 2 * Hk, d)
    k, v = kv[:, :Hk], kv[:, Hk:]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def head(h):
        g = h // (H // Hk)
        s = jnp.where(causal, _mm(q[:, h], k[:, g].T) * cfg["attention_multiplier"], -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), v[:, g])

    o = jax.lax.map(head, jnp.arange(H)).transpose(1, 0, 2).reshape(T, H * d)
    return _mm(o, p["w_o"])


def route(p, x, cfg):
    """[T, E] float32 over the router's whole width: an expert's weight for a
    token, 0 where not chosen: the softmax over the chosen logits alone."""
    s = _mm(x, p["router"])
    top, chosen = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    g = jax.nn.softmax(top, axis=-1)
    return jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32) * g[..., None], axis=1)


def routed(p, x, cfg, experts_gu, experts_down, held_from, layer=None):
    """The held experts' part of the layer: every expert given, for every
    token, masked by its weight.  ``layer`` (an index, traced or not): the
    matrices are every layer's, stacked [L, G, ...], and one is read out of
    the stack at a time."""
    weights = route(p, x, cfg)
    pick = (lambda w, e: w[e]) if layer is None else (lambda w, e: w[layer, e])

    def one(e, acc):
        y = _swiglu(x, pick(experts_gu, e), pick(experts_down, e))
        return acc + jnp.take(weights, held_from + e, axis=1)[:, None] * y

    return jax.lax.fori_loop(0, experts_gu.shape[-3], one, jnp.zeros_like(x))


def shared(p, x):
    return _swiglu(x, p["shared_gu"], p["shared_down"])


def expert_shares(p, x, cfg, experts_gu, experts_down, shares: int):
    """The whole expert layer as ``shares`` chips would compute it: the routed
    part of each share (its experts alone, the router whole), and the shared
    expert once.  Their sum is the uncut layer."""
    G = experts_gu.shape[0] // shares
    parts = [routed(p, x, cfg, experts_gu[i * G:(i + 1) * G], experts_down[i * G:(i + 1) * G],
                    i * G) for i in range(shares)]
    return parts, shared(p, x)


@functools.partial(jax.jit, static_argnames=("cfg", "mixer"))
def _mixer_jit(p, h, cfg, mixer):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, p["mixer_norm"], cfg["rms_norm_eps"])
        return h + cfg["residual_multiplier"] * mixer(p, x, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "held_from"))
def _experts_jit(p, experts_gu, experts_down, h, cfg, held_from, layer):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, p["ffn_norm"], cfg["rms_norm_eps"])
        y = routed(p, x, cfg, experts_gu, experts_down, held_from, layer) + shared(p, x)
        return h + cfg["residual_multiplier"] * y


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_jit(h, scale, table, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, scale, eps)
        n = table.shape[0] // _HEAD_BLOCKS if table.shape[0] % _HEAD_BLOCKS == 0 else table.shape[0]
        out = jax.lax.map(
            lambda c: _mm(x, jax.lax.dynamic_slice_in_dim(table, c * n, n, axis=0).T),
            jnp.arange(table.shape[0] // n))
        return out.transpose(1, 0, 2).reshape(h.shape[0], -1)


@jax.jit
def _take(stack, i):
    return jax.tree.map(lambda x: x[i], stack)


def layers(params: Dict, config: Dict):
    """(the mixer, the layer's weights) of every layer in order, from the
    file's ``layer_types`` (its first ``num_hidden_layers`` entries), the runs
    of Mamba layers and the stacked attention layers; a layer's weights are cut
    out of their stack as its turn comes, so one layer's copy is held at a time."""
    mamba_at = ((run, i) for run in params["mamba"]
                for i in range(jax.tree.leaves(run)[0].shape[0]))
    attn_at = iter(range(config["num_hidden_layers"]))
    for kind in config["layer_types"][:config["num_hidden_layers"]]:
        if kind == "attention":
            yield attention, _take(params["attn"], next(attn_at))
        else:
            run, i = next(mamba_at)
            yield mamba, _take(run, i)


def logits(params: Dict, tokens, config: Dict, rows: Optional[jax.Array] = None):
    """Teacher-forced logits of one sequence ``tokens`` [T] -> [T, V], or the
    given ``rows`` of it.  ``config`` holds the published keys and
    ``held_from``."""
    cfg = _Frozen({k: config[k] for k in _KEYS})
    held_from = int(config.get("held_from", 0))
    h = _f32(params["embed"][tokens]) * config["embedding_multiplier"]
    for layer, (mixer, p) in enumerate(layers(params, config)):
        h = _mixer_jit(p, h, cfg, mixer)
        h = _experts_jit(p, params["experts_gu"], params["experts_down"], h, cfg, held_from, layer)
    if rows is not None:
        h = h[rows]

    def head(part):  # always _ROWS rows (the last block padded): ONE program, whatever was asked for
        logits = _head_jit(jnp.pad(part, ((0, _ROWS - part.shape[0]), (0, 0))), params["final_norm"],
                           params["embed"], cfg["rms_norm_eps"])
        return np.asarray(logits[:part.shape[0]]) / config["logits_scaling"]

    return np.concatenate([head(h[i:i + _ROWS]) for i in range(0, h.shape[0], _ROWS)])
