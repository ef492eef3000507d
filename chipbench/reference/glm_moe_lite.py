"""Plain reference of the ``glm4_moe_lite`` decoder (GLM-4.7-Flash), as
published: float32 ``jax.numpy`` at the highest matmul precision, no cache,
no kernels, no sorting, independent of ``moolib_tpu``.

Per layer, x [T, D], no biases:

    h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MLA:  c_q = RMSNorm(x W_dq);  q = c_q W_uq -> H heads of nope + rope
          [c_kv | k_r] = x W_dkv;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r)
          k_nope = c_kv W_uk;  v = c_kv W_uv            (a head: nope, v)
          scores = (q_nope . k_nope + RoPE(q_rope) . k_r) / sqrt(nope + rope)
          causal softmax, then [H x v] W_o
    FFN, layer 0:  (silu(x W_g) * (x W_u)) W_d
    FFN, later:    s = sigmoid(x W_r);  the top k of s + b are chosen;
                   w = s[chosen] / sum(s[chosen]) * scale
                   y = sum_i w_i SwiGLU_i(x) + SwiGLU_shared(x)

The attention is the **decompressed** one only (per-head keys and values are
made), and every expert is computed for every token and masked by its
weight: nothing here shares a trick with the program.  Departures from the
published model, as the configuration's ``assumed`` lists them: RoPE pairs
are (i, i + rope/2); ``kv_b_proj`` is held as its halves ``w_uk`` / ``w_uv``
and gate | up side by side in one matrix, which is a naming of the same
numbers.  Weights are the program's values (bfloat16) widened to float32.

``logits`` works layer by layer, a jit a layer kind with the layer's weights
widened inside it and the experts in a loop, so that at the published widths
it fits beside the engine (2.5 GB for a layer, 1.3 GB for the head).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _swiglu(x, w_gate_up, w_down):
    gu = _mm(x, w_gate_up)
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_down)


def attention(p, x, cfg):
    """MLA over one sequence x [T, D] (already normed), decompressed."""
    T = x.shape[0]
    H = cfg["num_attention_heads"]
    c, r, nope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(T)
    q = _mm(_rms(_mm(x, p["w_dq"]), p["q_norm"], eps), p["w_uq"]).reshape(T, H, nope + r)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos[:, None], theta)
    ckv = _mm(x, p["w_dkv"])
    lat = _rms(ckv[:, :c], p["kv_norm"], eps)
    k_r = _rope(ckv[:, c:], pos, theta)
    k_nope = jnp.einsum("tc,hcn->thn", lat, p["w_uk"], precision=HIGHEST)
    v = jnp.einsum("tc,hcv->thv", lat, p["w_uv"], precision=HIGHEST)
    scores = (jnp.einsum("thn,shn->hts", q_nope, k_nope, precision=HIGHEST)
              + jnp.einsum("thr,sr->hts", q_rope, k_r, precision=HIGHEST))
    scores = scores / jnp.sqrt(jnp.float32(nope + r))
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    out = jnp.einsum("hts,shv->thv", jax.nn.softmax(scores, axis=-1), v,
                     precision=HIGHEST)
    return _mm(out.reshape(T, -1), p["w_o"])


def route(p, x, cfg):
    """[T, E] float32: an expert's weight for a token, 0 where not chosen."""
    s = jax.nn.sigmoid(_mm(x, p["router"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32), axis=1)
    picked = s * mask
    return picked / jnp.sum(picked, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]


def experts(p, x, cfg):
    """Every expert for every token, masked by its weight, plus the shared
    expert.  ``p``'s expert matrices may be in any dtype: each is widened as
    its turn comes."""
    weights = route(_f32({k: p[k] for k in ("router", "router_bias")}), x, cfg)

    def one(e, acc):
        y = _swiglu(x, p["experts_gu"][e].astype(jnp.float32),
                    p["experts_down"][e].astype(jnp.float32))
        return acc + weights[:, e, None] * y

    routed = jax.lax.fori_loop(0, weights.shape[-1], one, jnp.zeros_like(x))
    return routed + _swiglu(x, p["shared_gu"].astype(jnp.float32),
                            p["shared_down"].astype(jnp.float32))


_ATTN = ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk", "w_uv",
         "w_o", "ffn_norm")


def _layer(p, h, cfg, moe):
    a = _f32({k: p[k] for k in _ATTN})
    eps = cfg["rms_norm_eps"]
    h = h + attention(a, _rms(h, a["attn_norm"], eps), cfg)
    x = _rms(h, a["ffn_norm"], eps)
    if moe:
        return h + experts(p, x, cfg)
    return h + _swiglu(x, p["w_gate_up"].astype(jnp.float32),
                       p["w_down"].astype(jnp.float32))


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@functools.partial(jax.jit, static_argnames=("cfg", "moe"))
def _layer_jit(p, h, cfg, moe):
    with jax.default_matmul_precision("highest"):
        return _layer(p, h, cfg, moe)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_jit(h, scale, head, eps):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(h, scale.astype(jnp.float32), eps), head.astype(jnp.float32))


def logits(params: Dict, tokens, config: Dict, rows: Optional[jax.Array] = None):
    """Teacher-forced logits of one sequence ``tokens`` [T] -> [T, V], or the
    given ``rows`` of it.  ``config`` holds the published keys; the depth is
    read from ``params`` (one dense layer, then the stacked expert layers)."""
    cfg = _Frozen({k: config[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
        "rms_norm_eps", "rope_theta", "num_experts_per_tok", "routed_scaling_factor")})
    h = params["embed"][tokens].astype(jnp.float32)
    h = _layer_jit(params["dense"], h, cfg, False)
    for l in range(params["moe"]["router"].shape[0]):
        h = _layer_jit(jax.tree.map(lambda x: x[l], params["moe"]), h, cfg, True)
    if rows is not None:
        h = h[rows]
    return _head_jit(h, params["final_norm"], params["head"], cfg["rms_norm_eps"])
