"""Plain reference of the ``laguna`` decoder (Laguna-S-2.1), as published and
as the configuration's ``assumed`` completes it: float32 ``jax.numpy`` at the
highest matmul precision, no cache, no ring, no kernels, no sorting,
independent of ``moolib_tpu``.

Per layer l, x [T, D], no biases, RMSNorm eps 1e-6:
h = x + Mixer_l(RMSNorm(x));  y = h + FeedForward_l(RMSNorm(h)).

Mixer: ``layer_types[l]`` is ``full_attention`` (l = 0, 4, 8, ...) or
``sliding_attention``; H_l = ``num_attention_heads_per_layer[l]`` query heads
(48 full, 72 sliding) over 8 K/V heads of 128::

    q = x W_q;  [k | v] = x W_kv;  q, k rotated (below)
    scores = q . k / sqrt(128), causal; on a sliding layer a query at t sees
    keys t - 511 .. t (``sliding_window`` 512, itself included); softmax
    y = ((softmax v) * sigmoid(x W_g)[head]) W_o       one gate a head

Rotation, half-split pairs (i, i + r/2) over the first r entries of a head:
sliding layers r = 128 (the whole head), frequencies ``10000 ** (-2i / 128)``;
full layers r = 64 (``partial_rotary_factor`` 0.5, the other 64 unrotated),
YaRN: with ``f_i = 500000 ** (-2i / 64)``, the correction dimensions
``c(b) = 64 ln(8192 / (2 pi b)) / (2 ln 500000)``, low = floor(c(32)) = 9 and
high = ceil(c(1)) = 18, and the ramp ``g_i = clip((i - low) / (high - low), 0,
1)``, pair i turns at ``f_i (1 - g_i) + f_i / 128 g_i``, whatever the length;
cos and sin times ``attention_factor`` 1.4852030263919618.

Feed-forward: layer 0 a dense SwiGLU of 12,288; every other layer
``p = softmax(x W_r)`` over the router's whole width, the 10 largest of p + b
chosen (b the selection bias, zero unless the file's ``assumed`` says it was
balanced), ``w = p[chosen] / sum(p[chosen]) x 2.5``.  This chip holds experts
``held_from .. held_from + G - 1`` (G the matrices given): y = sum over the
chosen experts THAT ARE HELD of w_e SwiGLU_e(x), plus the shared expert,
ungated.  What the absent experts would add is left out; the weights are
normalised over all ten chosen.  :func:`expert_shares` gives the same layer
one share at a time, for the test that the shares add up to the uncut layer.

Every held expert is computed for every token and masked by its weight,
attention is a full masked softmax a head: nothing here shares a trick with
the program.  Weights are the program's values (bfloat16) widened to float32
a layer at a time, the dense layer's and the head's a block of columns at a
time, so that at the published widths ``logits`` fits beside the engine.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_WIDE = 4096  # a matrix wider than this is widened a block of columns at a time
_KEYS = ("num_key_value_heads", "head_dim", "rms_norm_eps", "sliding_window",
         "num_experts_per_tok", "moe_routed_scaling_factor")


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _swiglu(x, w_gate_up, w_down):
    """SwiGLU with W_gate | W_up side by side, the hidden width a block at a
    time where it is wide (the matrices in any dtype, widened as read)."""
    F = w_down.shape[0]
    n = F // max(1, -(-F // _WIDE))
    while F % n:
        n -= 1

    def block(c, acc):
        cols = lambda start: jax.lax.dynamic_slice_in_dim(
            w_gate_up, start, n, axis=1).astype(jnp.float32)
        mid = jax.nn.silu(_mm(x, cols(c * n))) * _mm(x, cols(F + c * n))
        rows = jax.lax.dynamic_slice_in_dim(w_down, c * n, n, axis=0).astype(jnp.float32)
        return acc + _mm(mid, rows)

    return jax.lax.fori_loop(0, F // n, block, jnp.zeros_like(x))


def inv_freq(rope: Dict, head_dim: int):
    """(the rotated width r, the r / 2 inverse frequencies, the factor on cos
    and sin) of one of ``rope_parameters``' two groups."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    plain = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    if rope.get("rope_type", "default") == "default":
        return r, jnp.asarray(plain, jnp.float32), 1.0
    original = rope["original_max_position_embeddings"]
    c = lambda turns: r * math.log(original / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), r - 1)
    ramp = [min(max((i - low) / max(high - low, 0.001), 0.0), 1.0) for i in range(r // 2)]
    blended = [f * (1 - g) + f / rope["factor"] * g for f, g in zip(plain, ramp)]
    return r, jnp.asarray(blended, jnp.float32), float(rope["attention_factor"])


def rotate(x, rope: Dict):
    """x [T, heads, hd] at positions 0 .. T - 1."""
    r, freq, factor = inv_freq(rope, x.shape[-1])
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]], axis=-1)


def attention(p, x, cfg, rope: Dict, window: Optional[int]):
    """A gated grouped-query mixer over one sequence x [T, D] (already
    normed), a query head at a time; ``window``: the keys a query sees, itself
    included (None: every key before it)."""
    T = x.shape[0]
    Hk, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    H = p["w_gate"].shape[1]
    q = rotate(_mm(x, p["w_q"]).reshape(T, H, hd), rope)
    kv = _mm(x, p["w_kv"]).reshape(T, 2 * Hk, hd)
    k, v = rotate(kv[:, :Hk], rope), kv[:, Hk:]
    t = jnp.arange(T)
    seen = t[:, None] >= t[None, :]
    if window is not None:
        seen &= t[:, None] - t[None, :] < window

    def head(h):
        kh = jnp.take(k, h // (H // Hk), axis=1)
        vh = jnp.take(v, h // (H // Hk), axis=1)
        scores = _mm(jnp.take(q, h, axis=1), kh.T) / jnp.sqrt(jnp.float32(hd))
        return _mm(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), vh)

    out = jax.lax.map(head, jnp.arange(H)).transpose(1, 0, 2)  # [T, H, hd]
    gate = jax.nn.sigmoid(_mm(x, p["w_gate"]))  # [T, H]
    return _mm((out * gate[..., None]).reshape(T, H * hd), p["w_o"])


def route(p, x, cfg):
    """[T, E] float32 over the router's whole width: an expert's weight for
    a token, 0 where not chosen."""
    s = jax.nn.softmax(_mm(x, p["router"]), axis=-1)
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32), axis=1)
    picked = s * mask
    return picked / jnp.sum(picked, axis=-1, keepdims=True) * cfg["moe_routed_scaling_factor"]


def routed(p, x, cfg, experts_gu, experts_down, held_from, layer=None):
    """The held experts' part of the layer: every expert given, for every
    token, masked by its weight.  ``layer`` (an index, traced or not): the
    matrices are every expert layer's, stacked [L, G, ...], and one is read
    out of the stack at a time."""
    weights = route(_f32({k: p[k] for k in ("router", "router_bias")}), x, cfg)
    pick = (lambda w, e: w[e]) if layer is None else (lambda w, e: w[layer, e])

    def one(e, acc):
        y = _swiglu(x, pick(experts_gu, e), pick(experts_down, e))
        return acc + jnp.take(weights, held_from + e, axis=1)[:, None] * y

    return jax.lax.fori_loop(0, experts_gu.shape[-3], one, jnp.zeros_like(x))


def shared(p, x):
    return _swiglu(x, p["shared_gu"], p["shared_down"])


def expert_shares(p, x, cfg, experts_gu, experts_down, shares: int):
    """The whole expert layer as ``shares`` chips would compute it: the
    routed part of each share (its experts alone, the router whole), and the
    shared expert once.  Their sum is the uncut layer."""
    G = experts_gu.shape[0] // shares
    parts = [routed(p, x, cfg, experts_gu[i * G:(i + 1) * G], experts_down[i * G:(i + 1) * G],
                    i * G) for i in range(shares)]
    return parts, shared(p, x)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


_MIXER = ("attn_norm", "w_q", "w_kv", "w_gate", "w_o")


@functools.partial(jax.jit, static_argnames=("cfg", "rope", "window"))
def _mixer_jit(p, h, cfg, rope, window):
    with jax.default_matmul_precision("highest"):
        m = _f32({k: p[k] for k in _MIXER})
        return h + attention(m, _rms(h, m["attn_norm"], cfg["rms_norm_eps"]), cfg, rope, window)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _dense_jit(p, h, cfg):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, p["ffn_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
        return h + _swiglu(x, p["dense_gu"], p["dense_down"])


@functools.partial(jax.jit, static_argnames=("cfg", "held_from"))
def _experts_jit(p, experts_gu, experts_down, h, cfg, held_from, layer):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, p["ffn_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
        return h + routed(p, x, cfg, experts_gu, experts_down, held_from, layer) + shared(p, x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_jit(h, scale, head, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, scale.astype(jnp.float32), eps)
        n = head.shape[1] // max(1, -(-head.shape[1] // _WIDE))
        while head.shape[1] % n:
            n -= 1
        blocks = jax.lax.map(
            lambda c: _mm(x, jax.lax.dynamic_slice_in_dim(head, c * n, n, axis=1).astype(
                jnp.float32)), jnp.arange(head.shape[1] // n))
        return blocks.transpose(1, 0, 2).reshape(x.shape[0], -1)


def logits(params: Dict, tokens, config: Dict, rows: Optional[jax.Array] = None):
    """Teacher-forced logits of one sequence ``tokens`` [T] -> [T, V], or the
    given ``rows`` of it.  ``config`` holds the published keys and
    ``held_from``; the depth and the pattern are read from ``params`` (a
    leading full layer, then a tuple of periods: the sliding layers stacked,
    and the full layer behind them)."""
    cfg = _Frozen({k: config[k] for k in _KEYS})
    rope = {k: _Frozen(v) for k, v in config["rope_parameters"].items()}
    held_from = int(config.get("held_from", 0))
    full = functools.partial(_mixer_jit, cfg=cfg, rope=rope["full_attention"], window=None)
    sliding = functools.partial(_mixer_jit, cfg=cfg, rope=rope["sliding_attention"],
                                window=config["sliding_window"])
    experts = lambda p, h, layer: _experts_jit(
        p, params["experts_gu"], params["experts_down"], h, cfg, held_from, layer)
    h = params["embed"][tokens].astype(jnp.float32)
    h = _dense_jit(params["lead"], full(params["lead"], h), cfg)
    layer = 0  # of the expert layers
    for stacked, p_full in zip(params["swa"], params["full"]):
        for i in range(stacked["w_gate"].shape[0]):
            p = jax.tree.map(lambda x: x[i], stacked)
            h = experts(p, sliding(p, h), layer)
            layer += 1
        h = experts(p_full, full(p_full, h), layer)
        layer += 1
    if rows is not None:
        h = h[rows]
    return _head_jit(h, params["final_norm"], params["head"], cfg["rms_norm_eps"])
