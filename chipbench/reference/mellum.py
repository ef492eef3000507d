"""Plain reference of the ``mellum`` decoder (Mellum2-12B-A2.5B-Instruct), as
its published configuration describes it and as the configuration file's
``assumed`` completes it: float32 ``jax.numpy`` at the highest matmul
precision, no cache, no ring, no kernel, no sorting, no batching; it imports
neither ``moolib_tpu`` nor another reference.

``T`` positions, residual stream ``h`` float32, ``eps`` 1e-6, no bias anywhere::

    h0 = E[tokens]
    for l in 0 .. L-1, kind_l = layer_types[l]           (s s s f, repeated)
      x  = rmsnorm(h; g1_l)
      q  = x Wq_l -> [T, 32, 128];  k = x Wk_l -> [T, 4, 128];  v = x Wv_l -> [T, 4, 128]
      q  = rmsnorm(q; gq_l), k = rmsnorm(k; gk_l)        over the 128 entries of a head
      q, k rotated: all 128 entries, pairs (i, i + 64)
             sliding: inv_freq_i = 500000 ** (-2i / 128)
             full:    the YaRN table (below), cos and sin x attention_factor
      s_ij = q_i . k_j / sqrt(128), query head n reads K/V head n // 8
             allowed: j <= i, and on a sliding layer i - j < sliding_window
      o  = softmax_j(s) v
      h  = h + concat_heads(o) Wo_l
      y  = rmsnorm(h; g2_l)
      p  = softmax(y Wr_l) over the 64 experts
      S  = the 8 largest of p;  w_e = p_e / sum_{e' in S} p_e'       (norm_topk_prob)
      h  = h + sum_{e in S} w_e (silu(y Wg_l^e) * (y Wu_l^e)) Wd_l^e
    logits = rmsnorm(h_L; gf) W_head                      not tied to E

No dense layer, no shared expert, no gate on the attention's output, no scale
on the routed sum.

The YaRN table, as ``transformers`` computes it (``dim`` 128, ``theta`` 500000,
``factor`` 16, ``original`` 8192): with ``f_i = theta ** (-2i / dim)`` and the
correction dimensions ``c(b) = dim ln(original / (2 pi b)) / (2 ln theta)``,
``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))`` and the ramp
``g_i = clip((i - low) / (high - low), 0, 1)``, pair i turns at
``f_i (1 - g_i) + f_i / factor g_i``, whatever the length.

DEPARTURE from the published description, the one assumed size: the per-head
RMSNorm on q and k has no key in the published config; the configuration
file's ``assumed.qk_norm`` argues it from the lineage of the config's keys and
carries ``"qk_norm": true``, which :func:`logits` reads (false: the two norms
are left out).

The weights are the program's own values (its parameter tree: ``swa`` the
stacks of the runs of sliding layers, ``full`` the full layers, the experts
stacked over layers, ``w_kv`` W_k | W_v side by side and an expert's W_g | W_u
side by side: the same numbers under other names), widened to float32 as they
are read: a layer, an expert, a block of the head's columns at a time, and
attention a query head at a time by an explicit [T, T] mask, so that 4,096
positions at the published widths fit beside a serving engine.  Every expert
is computed for every position and weighed (by 0 where it was not chosen).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_HEAD_BLOCK = 4096  # columns of the head widened at a time


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mm(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def inv_freq(rope: Dict, dim: int):
    """(the ``dim / 2`` inverse frequencies, the factor on cos and sin) of one
    of ``rope_parameters``' two groups."""
    theta = float(rope["rope_theta"])
    plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if rope.get("rope_type", "default") == "default":
        return jnp.asarray(plain, jnp.float32), 1.0
    original = rope["original_max_position_embeddings"]
    c = lambda turns: dim * math.log(original / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    ramp = [min(max((i - low) / max(high - low, 0.001), 0.0), 1.0) for i in range(dim // 2)]
    table = [f * (1 - g) + f / rope["factor"] * g for f, g in zip(plain, ramp)]
    return jnp.asarray(table, jnp.float32), float(rope["attention_factor"])


def rotate(x, rope: Dict):
    """x [T, heads, hd] at positions 0 .. T - 1: every entry of a head, pairs
    (i, i + hd / 2)."""
    half = x.shape[-1] // 2
    freq, factor = inv_freq(rope, x.shape[-1])
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, x, rope: Dict, window: Optional[int], kv_heads: int, head_dim: int,
              eps: float, qk_norm: bool):
    """A grouped-query mixer over one sequence x [T, D] (already normed), a
    query head at a time; ``window``: the keys a query sees, itself included
    (None: every key up to it)."""
    T = x.shape[0]
    heads = p["w_q"].shape[-1] // head_dim
    q = _mm(x, p["w_q"]).reshape(T, heads, head_dim)
    kv = _mm(x, p["w_kv"]).reshape(T, 2 * kv_heads, head_dim)
    k, v = kv[:, :kv_heads], kv[:, kv_heads:]
    if qk_norm:
        q = _rms(q, p["q_norm"].astype(jnp.float32), eps)
        k = _rms(k, p["k_norm"].astype(jnp.float32), eps)
    q, k = rotate(q, rope), rotate(k, rope)
    t = jnp.arange(T)
    allowed = t[:, None] >= t[None, :]
    if window is not None:
        allowed &= t[:, None] - t[None, :] < window

    def head(n):
        kn = jnp.take(k, n // (heads // kv_heads), axis=1)
        vn = jnp.take(v, n // (heads // kv_heads), axis=1)
        s = _mm(jnp.take(q, n, axis=1), kn.T) / jnp.sqrt(jnp.float32(head_dim))
        return _mm(jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1), vn)

    o = jax.lax.map(head, jnp.arange(heads)).transpose(1, 0, 2)  # [T, heads, hd]
    return _mm(o.reshape(T, heads * head_dim), p["w_o"])


def route(router, y, top_k: int):
    """[T, E] float32: an expert's weight for a position, 0 where it is not
    among the position's ``top_k`` largest probabilities; the chosen ones'
    renormalised to sum to 1."""
    p = jax.nn.softmax(_mm(y, router), axis=-1)
    _, chosen = jax.lax.top_k(p, top_k)
    picked = p * jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=jnp.float32), axis=1)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def experts(router, experts_gu, experts_down, layer, y, top_k: int):
    """The expert layer: every expert of layer ``layer`` of the stacks
    ``experts_gu`` [L, E, D, 2F] (W_g | W_u) and ``experts_down`` [L, E, F, D]
    for every position, weighed."""
    weights = route(router, y, top_k)
    F = experts_down.shape[-2]

    def one(e, acc):
        gu = _mm(y, experts_gu[layer, e])
        out = _mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], experts_down[layer, e])
        return acc + jnp.take(weights, e, axis=1)[:, None] * out

    return jax.lax.fori_loop(0, experts_gu.shape[1], one, jnp.zeros_like(y))


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@functools.partial(jax.jit, static_argnames=(
    "rope", "window", "kv_heads", "head_dim", "eps", "qk_norm", "top_k"))
def _layer(p, experts_gu, experts_down, layer, h, *, rope, window, kv_heads, head_dim, eps,
           qk_norm, top_k):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, p["attn_norm"].astype(jnp.float32), eps)
        h = h + attention(p, x, rope, window, kv_heads, head_dim, eps, qk_norm)
        y = _rms(h, p["ffn_norm"].astype(jnp.float32), eps)
        return h + experts(p["router"], experts_gu, experts_down, layer, y, top_k)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, scale, head, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, scale.astype(jnp.float32), eps)
        V = head.shape[1]
        n = next(n for n in range(min(_HEAD_BLOCK, V), 0, -1) if V % n == 0)
        blocks = jax.lax.map(
            lambda c: _mm(x, jax.lax.dynamic_slice_in_dim(head, c * n, n, axis=1)),
            jnp.arange(V // n))
        return blocks.transpose(1, 0, 2).reshape(x.shape[0], V)


def logits(params: Dict, tokens, config: Dict, rows: Optional[jax.Array] = None):
    """Teacher-forced logits of one sequence ``tokens`` [T] -> [T, V], or the
    given ``rows`` of it.  ``config`` holds the published keys (and
    ``qk_norm``); the depth is the parameters': their layers are walked in the
    order of ``config["layer_types"]``, a sliding layer the next of the
    stacks' (``params["swa"]``), a full layer the next of ``params["full"]``."""
    sliding = iter([jax.tree.map(lambda x: x[i], stack) for stack in params["swa"]
                    for i in range(stack["w_q"].shape[0])])
    full = iter(params["full"])
    depth = params["experts_gu"].shape[0]
    shared = dict(kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
                  eps=config["rms_norm_eps"], qk_norm=bool(config.get("qk_norm", False)),
                  top_k=config["num_experts_per_tok"])
    rope = {k: _Frozen(v) for k, v in config["rope_parameters"].items()}
    h = params["embed"][tokens].astype(jnp.float32)
    for layer, kind in enumerate(config["layer_types"][:depth]):
        if kind == "sliding_attention":
            p, window = next(sliding), config["sliding_window"]
        else:
            p, window = next(full), None
        h = _layer(p, params["experts_gu"], params["experts_down"], layer, h,
                   rope=rope[kind], window=window, **shared)
    if rows is not None:
        h = h[rows]
    return _head(h, params["final_norm"], params["head"], config["rms_norm_eps"])
