"""Plain reference of the ``solar_open2`` decoder (Solar-Open2-250B), as
published and as the configuration's ``assumed`` completes it: float32
``jax.numpy`` at the highest matmul precision, no cache, no kernels, no
chunking, no sorting, independent of ``moolib_tpu``.

Per layer, x [T, D], no biases:  h = x + Mixer(RMSNorm(x));  y = h + Experts(RMSNorm(h)).

Mixer, layers 0, 4, 8, ... (``gqa_layers``): softmax attention WITHOUT
positions, 64 query heads over 8 K/V heads of 128, gated::

    q = x W_q;  [k | v] = x W_kv;  scores = q . k / sqrt(128), causal softmax
    y = ((softmax v) * sigmoid(x W_gate)) W_o

Mixer, every other layer: Kimi delta attention (arXiv:2510.26692), per head h
of 64 with d_k = d_v = 128, a token at a time::

    [q | k | v] = silu(conv4(x W_qkv))      causal, depthwise, one filter a channel
    q = l2norm(q) / sqrt(128);  k = l2norm(k)
    g = -exp(A_h) softplus(x W_f1 W_f2 + b_dt)        one log-decay a key channel
    beta = 2 sigmoid(x W_beta)                        one a head, in [0, 2]
    S_t = (I - beta k k^T) diag(exp(g)) S_{t-1} + beta k v^T;   o = S_t^T q
    y = (RMSNorm_head(o) * sigmoid(x W_g1 W_g2)) W_o

Experts, every layer: s = sigmoid(x W_r) over the router's whole width; the 8
largest of s + b are chosen; w = s[chosen] / sum(s[chosen]) (times the
scaling factor, 1).  This chip holds experts ``held_from .. held_from + G - 1``
(G the matrices given):  y = sum over the chosen experts THAT ARE HELD of
w_e SwiGLU_e(x), plus the shared expert.  What the absent experts would add
is left out; the weights are normalised over all eight chosen.
:func:`expert_shares` gives the same layer one share at a time, for the test
that the shares add up to the uncut layer.

Every held expert is computed for every token and masked by its weight, the
recurrence walks the sequence under a ``scan``, attention is a full softmax a
head: nothing here shares a trick with the program.  Weights are the
program's values (bfloat16) widened to float32, a layer at a time, so that at
the published widths ``logits`` fits beside the engine.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_PERIOD_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
                "num_experts_per_tok", "routed_scaling_factor")


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _swiglu(x, w_gate_up, w_down):
    gu = _mm(x, w_gate_up)
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_down)


def gqa(p, x, cfg):
    """Gated softmax attention without positions over one sequence x [T, D]
    (already normed), a query head at a time."""
    T = x.shape[0]
    H, Hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = _mm(x, p["w_q"]).reshape(T, H, hd)
    kv = _mm(x, p["w_kv"]).reshape(T, 2 * Hk, hd)
    k, v = kv[:, :Hk], kv[:, Hk:]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def head(h):
        kh = jnp.take(k, h // (H // Hk), axis=1)
        vh = jnp.take(v, h // (H // Hk), axis=1)
        scores = _mm(jnp.take(q, h, axis=1), kh.T) / jnp.sqrt(jnp.float32(hd))
        return _mm(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1), vh)

    out = jax.lax.map(head, jnp.arange(H)).transpose(1, 0, 2).reshape(T, H * hd)
    return _mm(out * jax.nn.sigmoid(_mm(x, p["w_gate"])), p["w_o"])


def kda(p, x, cfg):
    """Kimi delta attention over one sequence x [T, D] (already normed): the
    recurrence itself, one token after another."""
    T = x.shape[0]
    H, d = p["a_log"].shape[0], p["o_norm"].shape[0]
    raw = jnp.pad(_mm(x, p["w_qkv"]), ((3, 0), (0, 0)))
    qkv = jax.nn.silu(sum(raw[i:i + T] * p["conv"][i] for i in range(4)))
    q, k, v = (a.reshape(T, H, d) for a in jnp.split(qkv, 3, axis=-1))
    q, k = _l2norm(q) / jnp.sqrt(jnp.float32(d)), _l2norm(k)
    f = _mm(_mm(x, p["w_f1"]), p["w_f2"]) + p["dt_bias"]
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f).reshape(T, H, d)
    beta = 2.0 * jax.nn.sigmoid(_mm(x, p["w_beta"]))

    def token(S, t):
        q, k, v, g, beta = t
        S = S * jnp.exp(g)[:, :, None]  # [H, d_k, d_v]
        S = S + beta[:, None, None] * k[:, :, None] * (
            v - jnp.sum(k[:, :, None] * S, axis=1))[:, None, :]
        return S, jnp.sum(q[:, :, None] * S, axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32), (q, k, v, g, beta))
    gate = jax.nn.sigmoid(_mm(_mm(x, p["w_g1"]), p["w_g2"]))
    o = _rms(o, p["o_norm"], cfg["rms_norm_eps"]).reshape(T, H * d)
    return _mm(o * gate, p["w_o"])


def route(p, x, cfg):
    """[T, E] float32 over the router's whole width: an expert's weight for
    a token, 0 where not chosen."""
    s = jax.nn.sigmoid(_mm(x, p["router"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32), axis=1)
    picked = s * mask
    return picked / jnp.sum(picked, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]


def routed(p, x, cfg, experts_gu, experts_down, held_from, layer=None):
    """The held experts' part of the layer: every expert given, for every
    token, masked by its weight.  The matrices [G, ...] may be in any dtype:
    each is widened as its turn comes.  ``layer`` (an index, traced or not):
    they are every layer's, stacked [L, G, ...], and one matrix is read out of
    the stack at a time (a layer's slice of the stack would be a copy of 1.26
    GB, and a static index a program a layer)."""
    weights = route(_f32({k: p[k] for k in ("router", "router_bias")}), x, cfg)
    pick = (lambda w, e: w[e]) if layer is None else (lambda w, e: w[layer, e])

    def one(e, acc):
        y = _swiglu(x, pick(experts_gu, e).astype(jnp.float32),
                    pick(experts_down, e).astype(jnp.float32))
        return acc + jnp.take(weights, held_from + e, axis=1)[:, None] * y

    return jax.lax.fori_loop(0, experts_gu.shape[-3], one, jnp.zeros_like(x))


def shared(p, x):
    return _swiglu(x, p["shared_gu"].astype(jnp.float32), p["shared_down"].astype(jnp.float32))


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


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "held_from"))
def _layer_jit(p, experts_gu, experts_down, h, cfg, kind, held_from, layer):
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        ffn = {"router", "router_bias", "shared_gu", "shared_down", "ffn_norm"}
        m = _f32({k: v for k, v in p.items() if k not in ffn})
        mixer = gqa if kind == "gqa" else kda
        h = h + mixer(m, _rms(h, m["attn_norm"], eps), cfg)
        x = _rms(h, p["ffn_norm"].astype(jnp.float32), eps)
        return h + routed(p, x, cfg, experts_gu, experts_down, held_from, layer) + shared(p, x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_jit(h, scale, head, eps):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(h, scale.astype(jnp.float32), eps), head.astype(jnp.float32))


def logits(params: Dict, tokens, config: Dict, rows: Optional[jax.Array] = None):
    """Teacher-forced logits of one sequence ``tokens`` [T] -> [T, V], or the
    given ``rows`` of it.  ``config`` holds the published keys and
    ``held_from``; the depth and the pattern are read from ``params`` (periods
    of one GQA layer and the KDA layers stacked behind it)."""
    cfg = _Frozen({k: config[k] for k in _PERIOD_KEYS})
    held_from = int(config.get("held_from", 0))
    h = params["embed"][tokens].astype(jnp.float32)
    periods, per = params["kda"]["a_log"].shape[:2]
    layer = 0
    for period in range(periods):
        kinds = [("gqa", jax.tree.map(lambda x: x[period], params["gqa"]))]
        kinds += [("kda", jax.tree.map(lambda x: x[period, i], params["kda"]))
                  for i in range(per)]
        for kind, p in kinds:
            h = _layer_jit(p, params["experts_gu"], params["experts_down"],
                           h, cfg, kind, held_from, layer)
            layer += 1
    if rows is not None:
        h = h[rows]
    return _head_jit(h, params["final_norm"], params["head"], cfg["rms_norm_eps"])
