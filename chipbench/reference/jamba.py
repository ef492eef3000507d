"""Plain reference of the ``jamba`` decoder (AI21-Jamba2-3B), as published and
as the configuration's ``assumed`` completes it: float32 ``jax.numpy`` at the
highest matmul precision, no cache, no kernels, independent of ``moolib_tpu``.

Layer ``l`` is attention where ``l % attn_layer_period == attn_layer_offset``
and Mamba elsewhere; x [T, D] the residual stream, no positions anywhere::

    xn = RMSNorm(x; w_mixer)
    Mamba:      [u | z] = xn W_in
                u_t <- silu(b_c + sum_{i=0..3} w_c[i] u_{t-3+i})            (zeros before the prompt)
                [dt_r | B | C] = u W_x, each RMSNorm'ed with its own scale
                dt = softplus(dt_r W_dt + b_dt);   A = -exp(A_log)
                h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t^T     h [d_inner, 16], h_{-1} = 0
                y_t = h_t C_t + D u_t;   x = x + (y * silu(z)) W_out
    attention:  q_h = W_q,h xn (20 heads of 128);  k = W_k xn, v = W_v xn (ONE head)
                x = x + W_o concat_h softmax_causal(q_h k^T / sqrt(128)) v
    x = x + W_down(silu(W_gate xn') * (W_up xn')),  xn' = RMSNorm(x; w_ffn)
    logits = RMSNorm(x; w_final) E^T                 (the embedding table, tied)

The recurrence runs a token at a time over the whole sequence and the
attention through a full [T, T] matrix a head: nothing of the program's
chunks, layouts, state leaves or pools appears here; the two share the
equations above and nothing else.

Weights are the program's pytree (``w_in`` is u | z, ``w_x`` dt_r | B | C,
``w_kv`` W_k | W_v, ``w_gu`` W_gate | W_up side by side; ``a_log`` is held
[16, d_inner]; ``mamba`` is a tuple of the runs of Mamba layers between
attention layers, each stacked on a leading axis, ``attn`` the attention
layers stacked), in any dtype: each layer is widened to float32 as its turn
comes and runs one program, the head a block of the vocabulary at a time, so
that the whole model fits beside a serving engine that holds 10 GB.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_KEYS = ("num_attention_heads", "mamba_d_state", "mamba_dt_rank", "rms_norm_eps")
_HEAD_BLOCKS = 8  # the table is widened an eighth of the vocabulary at a time
_ROWS = 512  # more rows of logits than this go to the host a block at a time


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _f32(x):
    return x.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def mamba(p, x, cfg):
    """The Mamba mixer over one sequence x [T, D] (already normed)."""
    T = x.shape[0]
    N, R, eps = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["rms_norm_eps"]
    uz = _mm(x, p["w_in"])
    Ci = uz.shape[1] // 2
    u, z = uz[:, :Ci], uz[:, Ci:]
    taps = _f32(p["conv"])  # [4, d_inner]: tap i weighs the input 3 - i positions back
    before = jnp.concatenate([jnp.zeros((taps.shape[0] - 1, Ci), jnp.float32), u], axis=0)
    u = jax.nn.silu(_f32(p["conv_bias"]) + sum(
        taps[i] * before[i:i + T] for i in range(taps.shape[0])))
    x_dbl = _mm(u, p["w_x"])
    dt_r = _rms(x_dbl[:, :R], p["dt_norm"], eps)
    B = _rms(x_dbl[:, R:R + N], p["b_norm"], eps)
    C = _rms(x_dbl[:, R + N:], p["c_norm"], eps)
    dt = jax.nn.softplus(_mm(dt_r, p["w_dt"]) + _f32(p["dt_bias"]))
    A = -jnp.exp(_f32(p["a_log"])).T  # [d_inner, 16]

    def token(h, xs):
        u_t, dt_t, B_t, C_t = xs
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * u_t)[:, None] * B_t[None, :]
        return h, jnp.sum(h * C_t[None, :], axis=-1)

    # unrolled 16 tokens a turn of the loop: the same tokens in the same order
    _, y = jax.lax.scan(token, jnp.zeros((Ci, N), jnp.float32), (u, dt, B, C), unroll=16)
    y = (y + _f32(p["d"]) * u) * jax.nn.silu(z)
    return _mm(y, p["w_out"])


def attention(p, x, cfg):
    """The multi-query mixer over one sequence x [T, D] (already normed): a
    full [T, T] softmax a query head, every head against the one K/V head."""
    T, H = x.shape[0], cfg["num_attention_heads"]
    q = _mm(x, p["w_q"]).reshape(T, H, -1)
    d = q.shape[-1]
    kv = _mm(x, p["w_kv"])
    k, v = kv[:, :d], kv[:, d:]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def head(qh):
        s = jnp.where(causal, _mm(qh, k.T) / jnp.sqrt(jnp.float32(d)), -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(head, q.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(T, H * d)
    return _mm(o, p["w_o"])


def feed_forward(p, x):
    """SwiGLU over x [T, D] (already normed)."""
    gu = _mm(x, p["w_gu"])
    F = gu.shape[1] // 2
    return _mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], p["w_down"])


@functools.partial(jax.jit, static_argnames=("cfg", "mixer"))
def _layer_jit(p, h, cfg, mixer):
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = h + mixer(p, _rms(h, p["mixer_norm"], eps), cfg)
        return h + feed_forward(p, _rms(h, p["ffn_norm"], eps))


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
    runs of Mamba layers and the stacked attention layers; a layer's weights
    are cut out of their stack as its turn comes, so one layer's copy is held
    at a time."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    mamba_at = ((run, i) for run in params["mamba"]
                for i in range(jax.tree.leaves(run)[0].shape[0]))
    for l in range(config["num_hidden_layers"]):
        if l % period == offset:
            yield attention, _take(params["attn"], l // period)
        else:
            run, i = next(mamba_at)
            yield mamba, _take(run, i)


def logits(params: Dict, tokens, config: Dict, rows: Optional[jax.Array] = None):
    """Teacher-forced logits of one sequence ``tokens`` [T] -> [T, V], or the
    given ``rows`` of it.  ``config`` holds the published keys."""
    cfg = _Frozen({k: config[k] for k in _KEYS})
    h = _f32(params["embed"][tokens])
    for mixer, p in layers(params, config):
        h = _layer_jit(p, h, cfg, mixer)
    if rows is not None:
        h = h[rows]
    head = lambda part: _head_jit(part, params["final_norm"], params["embed"], cfg["rms_norm_eps"])
    if h.shape[0] <= _ROWS:
        return head(h)
    return np.concatenate([np.asarray(head(h[i:i + _ROWS])) for i in range(0, h.shape[0], _ROWS)])
