"""Plain reference of the ``brumby`` decoder (Brumby-14B-Base), as published
and as the configuration's ``assumed`` completes it: float32 ``jax.numpy`` at
the highest matmul precision, no state, no cache, no kernels, independent of
``moolib_tpu``.

Per layer, x [T, D] the residual stream, no biases but the gate's::

    xn   = RMSNorm(x; w_in)
    q_h  = RoPE_t(RMSNorm_128(W_q,h xn; w_qn))     40 heads
    k_g  = RoPE_t(RMSNorm_128(W_k,g xn; w_kn))      8 heads;   v_g = W_v,g xn
    lam_g,t = logsigmoid(w_gate,g . xn + b_gate,g)              (<= 0)
    a_h(t, j) = exp(sum_{l=j+1..t} lam_g,l) (q_h,t . k_g,j / sqrt(128))^2    j <= t, g = h // 5
    o_h,t = sum_j a_h(t, j) v_g,j / (sum_j a_h(t, j) + 1e-6)
    x = x + W_o concat_h(o_h,t);   x = x + W_down(silu(W_gate xn') * (W_up xn')),  xn' = RMSNorm(x; w_post)

Power retention (arXiv:2507.04239) at degree 2 in its ATTENTION form: every
position sums over all earlier ones, a query head at a time, through a full
[T, T] matrix of weights.  The symmetric square, the recurrent state and its
normaliser, which are all the program keeps, never appear here: the two
share the equations above and nothing else.

Weights are the program's pytree (``w_kv`` is W_k | W_v side by side, ``w_gu``
W_gate | W_up; the layers stacked on a leading axis), in any dtype: each
matrix is widened to float32 as its turn comes, the wide ones (the
feed-forward's, the head's) a block of columns at a time, and the layers run
one program each, so that six layers at 5,120 fit beside a serving engine
that holds 12 GB.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta")
_WIDE = 4096  # a matrix wider than this is widened a block of columns at a time
_ROWS = 512  # more rows of logits than this go to the host a block at a time


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _f32(x):
    return x.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _blocks(width: int) -> int:
    """How many blocks of columns a matrix ``width`` wide is taken in."""
    for n in (8, 4, 2):
        if width > _WIDE and width % n == 0:
            return n
    return 1


def rope(x, theta):
    """x [T, heads, d] at positions 0 .. T - 1: the pair (i, i + d/2) turns by
    t * theta ** (-2 i / d)."""
    T, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]  # [T, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + turned * sin


def retention(p, x, cfg):
    """The mixer over one sequence x [T, D] (already normed): the quadratic
    sum itself, a query head at a time."""
    T = x.shape[0]
    H, G, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = _mm(x, _f32(p["w_q"])).reshape(T, H, d)
    kv = _mm(x, _f32(p["w_kv"])).reshape(T, 2 * G, d)
    k, v = kv[:, :G], kv[:, G:]
    q, k = rope(_rms(q, p["q_norm"], eps), theta), rope(_rms(k, p["k_norm"], eps), theta)
    lam = jax.nn.log_sigmoid(_mm(x, _f32(p["w_gate"])) + _f32(p["b_gate"]))  # [T, G]
    since = jnp.cumsum(lam, axis=0)  # the log-decay from the start, inclusive
    earlier = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def head(h):
        g = h // (H // G)
        kh, vh, c = jnp.take(k, g, axis=1), jnp.take(v, g, axis=1), jnp.take(since, g, axis=1)
        power = (_mm(jnp.take(q, h, axis=1), kh.T) / jnp.sqrt(jnp.float32(d))) ** 2
        kept = jnp.exp(jnp.where(earlier, c[:, None] - c[None, :], 0.0))
        a = jnp.where(earlier, kept * power, 0.0)
        return _mm(a, vh) / (jnp.sum(a, axis=-1, keepdims=True) + 1e-6)

    o = jax.lax.map(head, jnp.arange(H)).transpose(1, 0, 2).reshape(T, H * d)
    return _mm(o, _f32(p["w_o"]))


def feed_forward(p, x):
    """SwiGLU over x [T, D] (already normed), a block of the hidden width at
    a time."""
    F = p["w_down"].shape[0]
    n = F // _blocks(F)

    def block(c, acc):
        cols = lambda w, start: _f32(jax.lax.dynamic_slice_in_dim(w, start, n, axis=1))
        gate, up = _mm(x, cols(p["w_gu"], c * n)), _mm(x, cols(p["w_gu"], F + c * n))
        down = _f32(jax.lax.dynamic_slice_in_dim(p["w_down"], c * n, n, axis=0))
        return acc + _mm(jax.nn.silu(gate) * up, down)

    return jax.lax.fori_loop(0, F // n, block, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _layer_jit(p, h, cfg):
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = h + retention(p, _rms(h, p["attn_norm"], eps), cfg)
        return h + feed_forward(p, _rms(h, p["ffn_norm"], eps))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_jit(h, scale, head, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, scale, eps)
        n = head.shape[1] // _blocks(head.shape[1])
        out = jax.lax.map(
            lambda c: _mm(x, _f32(jax.lax.dynamic_slice_in_dim(head, c * n, n, axis=1))),
            jnp.arange(head.shape[1] // n))
        return out.transpose(1, 0, 2).reshape(h.shape[0], -1)


def logits(params: Dict, tokens, config: Dict, rows: Optional[jax.Array] = None):
    """Teacher-forced logits of one sequence ``tokens`` [T] -> [T, V], or the
    given ``rows`` of it.  ``config`` holds the published keys; the depth is
    the leading axis of ``params["layers"]``."""
    cfg = _Frozen({k: config[k] for k in _KEYS})
    h = _f32(params["embed"][tokens])
    for layer in range(params["layers"]["w_q"].shape[0]):
        h = _layer_jit(jax.tree.map(lambda x: x[layer], params["layers"]), h, cfg)
    if rows is not None:
        h = h[rows]
    head = lambda part: _head_jit(part, params["final_norm"], params["head"], cfg["rms_norm_eps"])
    if h.shape[0] <= _ROWS:
        return head(h)
    return np.concatenate([np.asarray(head(h[i:i + _ROWS])) for i in range(0, h.shape[0], _ROWS)])
