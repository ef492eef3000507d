"""A decoder built from a published configuration file: latent attention
(MLA) with decoupled RoPE, RMSNorm, SwiGLU, a dense first layer and dropless
sigmoid-routed expert layers with a shared expert after it (``model_type``
``glm4_moe_lite`` and its relatives).

Plain functions over a parameter pytree, not a flax module: the identical
expert layers are stacked on a leading axis and run under ``jax.lax.scan``,
so that compile time does not grow with depth.  The model offers the serving
engine the three things it asks of any model (``engine/engine.py``):

- :meth:`LatentMoELM.cache_spec`: ONE pool ``[num_blocks, layers,
  block_size, row_width]`` holding, for a token and a layer, the row
  ``[RMSNorm(c_kv) | RoPE(k_r) | 0-padding to the 128 lanes]``;
- :meth:`LatentMoELM.prefill`: the whole prompt, attention **decompressed**
  (per-head keys ``[k_nope | k_r]`` and values of the published head sizes
  through ``ops.flash_attention``), the head over the last position only;
- :meth:`LatentMoELM.decode`: one token a slot, attention **absorbed**
  (``q_nope W_uk^T`` against the shared row through
  ``ops.paged_attention.latent_paged_attention``, then ``W_uv``): per-head K
  and V are never made.

Precision: weights and matmul inputs in ``dtype`` (bfloat16), products
accumulated in float32; the residual stream, RMSNorm, the router, the softmax
and the logits in float32.  Parameter names follow the equations
(``docs/DESIGN.md`` section 6c), with ``kv_b_proj`` kept as its two halves
``w_uk`` [H, c, nope] and ``w_uv`` [H, c, v].
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .. import telemetry
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import (
    PagedState,
    latent_kv_write,
    latent_paged_attention,
)
from ..parallel.moe import dropless_moe, swiglu
from . import decoder_parts as parts

_LANES = 128

_REG = telemetry.get_registry()
_M_EXPERTS_TOUCHED = _REG.histogram(
    "serve_engine_experts_touched",
    "per decode step and expert layer: routed experts that at least one "
    "active slot's token chose (the expert matrices the step reads)",
    buckets=(1, 2, 4, 8, 16, 24, 32, 40, 48, 56, 64, 128, 256),
)
_M_PREFILL_LOAD = _REG.histogram(
    "serve_engine_prefill_expert_load",
    "per prefill and expert layer: the fullest expert's tokens over the mean "
    "(prompt tokens x experts a token / experts)",
    buckets=(1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0, 16.0),
)


@dataclasses.dataclass(frozen=True)
class LatentMoELM:
    """Sizes under their published names (``from_config`` reads them)."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    first_k_dense_replace: int = 1
    n_shared_experts: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_len: int = 8192  # positions the engine may ask for
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_config(cls, config, **overrides) -> "LatentMoELM":
        """Build from a configuration (a dict, or the path of its JSON file)
        that holds the published keys; ``overrides`` replace single sizes
        (a test's depth, the engine's ``max_len``).  A key the model cannot
        honour is refused by name."""
        config, dtype = parts.load_config(config, overrides)
        parts.refuse(cls.__name__, {
            "n_group": config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1,
            "rope_scaling": config.get("rope_scaling") is not None,
            "n_shared_experts": config.get("n_shared_experts", 1) != 1,
            "first_k_dense_replace": config.get("first_k_dense_replace", 1) != 1,
        })
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in names and k != "dtype"}
        kw.setdefault("max_len", min(config.get("max_position_embeddings", 8192), 8192))
        return cls(dtype=dtype, **kw)

    # ------------------------------------------------------------ geometry
    @property
    def row_values(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """The cached row, padded to whole 128-lane tiles (a Mosaic copy of a
        block out of a lane-padded pool is refused)."""
        return -(-self.row_values // _LANES) * _LANES

    @property
    def step_counters(self) -> int:
        """int32 counters a decode step hands back: experts touched, by
        expert layer."""
        return self.num_hidden_layers - 1

    @property
    def prefill_counters(self) -> int:
        """int32 counters a prefill hands back: the fullest expert's tokens,
        by expert layer (the mean is prompt x top-k / experts)."""
        return self.num_hidden_layers - 1

    def observe_step(self, counters) -> None:
        """A decode step's counters, back on the host (the engine fetched
        them with the step's packet)."""
        for touched in counters:
            _M_EXPERTS_TOUCHED.observe(int(touched))

    def observe_prefill(self, counters, prompt_len: int) -> None:
        mean = prompt_len * self.num_experts_per_tok / self.n_routed_experts
        for fullest in counters:
            _M_PREFILL_LOAD.observe(float(fullest) / mean)

    def cache_spec(self, num_blocks: int, block_size: int):
        return jax.ShapeDtypeStruct(
            (num_blocks, self.num_hidden_layers, block_size, self.row_width),
            self.dtype)

    def write_rows(self, pool, rows, block_ids):
        return pool.at[block_ids].set(rows.astype(pool.dtype))

    # -------------------------------------------------------------- weights
    def init(self, key) -> Dict:
        """Random weights from ``key``: normal with standard deviation
        fan_in ** -0.5 (embedding 1.0), norms 1, and a small non-zero
        selection bias so that choosing (score + bias) and weighing (score)
        differ.  Jit it: the weights are made on the device."""
        D, H = self.hidden_size, self.num_attention_heads
        c, r = self.kv_lora_rank, self.qk_rope_head_dim
        nope, vd, ql = self.qk_nope_head_dim, self.v_head_dim, self.q_lora_rank
        F, Fd, E = self.moe_intermediate_size, self.intermediate_size, self.n_routed_experts
        Lm = self.num_hidden_layers - 1
        keys, w = parts.weight_drawer(key, 64, self.dtype)

        def attn(lead):
            return {
                "attn_norm": jnp.ones(lead + (D,), jnp.float32),
                "w_dq": w(lead + (D, ql), D),
                "q_norm": jnp.ones(lead + (ql,), jnp.float32),
                "w_uq": w(lead + (ql, H * (nope + r)), ql),
                "w_dkv": w(lead + (D, c + r), D),
                "kv_norm": jnp.ones(lead + (c,), jnp.float32),
                "w_uk": w(lead + (H, c, nope), c),
                "w_uv": w(lead + (H, c, vd), c),
                "w_o": w(lead + (H * vd, D), H * vd),
                "ffn_norm": jnp.ones(lead + (D,), jnp.float32),
            }

        dense = attn(())
        dense.update(w_gate_up=w((D, 2 * Fd), D), w_down=w((Fd, D), Fd))
        moe = attn((Lm,))
        moe.update(
            router=w((Lm, D, E), D, jnp.float32),
            router_bias=jax.random.uniform(next(keys), (Lm, E), jnp.float32, -0.1, 0.1),
            experts_gu=w((Lm, E, D, 2 * F), D),
            experts_down=w((Lm, E, F, D), F),
            shared_gu=w((Lm, D, 2 * F), D),
            shared_down=w((Lm, F, D), F),
        )
        return {
            "embed": w((self.vocab_size, D), 1.0),
            "dense": dense,
            "moe": moe,
            "final_norm": jnp.ones((D,), jnp.float32),
            "head": w((D, self.vocab_size), D),
        }

    # ------------------------------------------------------------- pieces
    def _norm(self, x, scale):
        return parts.rms_norm(x, scale, self.rms_norm_eps)

    def _dot(self, x, w):
        return parts.dot(x, w, self.dtype)

    def _head(self, params, h):
        return parts.head_logits(
            h, params["final_norm"], params["head"], self.rms_norm_eps, self.dtype)

    def _latent(self, p, xn, pos):
        """Queries and this layer's cache rows for normed inputs xn [T, D] at
        positions pos [T]: q_nope [T, H, nope], RoPE(q_rope) [T, H, r] (both
        float32) and rows [T, row_width] in ``dtype``."""
        H, c, r = self.num_attention_heads, self.kv_lora_rank, self.qk_rope_head_dim
        T = xn.shape[0]
        cq = self._norm(self._dot(xn, p["w_dq"]), p["q_norm"])
        q = self._dot(cq, p["w_uq"]).reshape(T, H, self.qk_nope_head_dim + r)
        q_nope, q_rope = q[..., : self.qk_nope_head_dim], q[..., self.qk_nope_head_dim:]
        q_rope = parts.rope_half_split(q_rope, pos[:, None], self.rope_theta)
        ckv = self._dot(xn, p["w_dkv"])
        rows = jnp.concatenate(
            [self._norm(ckv[:, :c], p["kv_norm"]),
             parts.rope_half_split(ckv[:, c:], pos, self.rope_theta),
             jnp.zeros((T, self.row_width - c - r), jnp.float32)], axis=-1)
        return q_nope, q_rope, rows.astype(self.dtype)

    def _ffn(self, p, xn, expert_layer=None, valid=None):
        """``expert_layer`` None: the dense SwiGLU.  Else the index of this
        layer among the expert layers: ``p`` then holds the experts' stacked
        matrices whole (module docstring of ``parallel.moe``)."""
        if expert_layer is None:
            return swiglu(xn.astype(self.dtype), p["w_gate_up"], p["w_down"]), None
        return dropless_moe(
            xn, p, top_k=self.num_experts_per_tok,
            scale=self.routed_scaling_factor, valid=valid, layer=expert_layer)

    @staticmethod
    def _split(moe):
        """The stacked expert layers as (what a scan slices a layer at a
        time, the experts' matrices: left whole and indexed by the kernel)."""
        whole = {k: moe[k] for k in ("experts_gu", "experts_down")}
        return {k: v for k, v in moe.items() if k not in whole}, whole

    def _expert_layers(self):
        return jnp.arange(self.num_hidden_layers - 1, dtype=jnp.int32)

    # ------------------------------------------------------------- prefill
    def _prefill_layer(self, p, h, pos, valid, expert_layer=None):
        H, c = self.num_attention_heads, self.kv_lora_rank
        T = h.shape[0]
        q_nope, q_rope, rows = self._latent(p, self._norm(h, p["attn_norm"]), pos)
        # Decompress from the rows as cached: per-head keys and values.
        ckv, k_r = rows[:, :c], rows[:, c:self.row_values]
        k_nope = jnp.einsum("tc,hcn->thn", ckv, p["w_uk"],
                            preferred_element_type=jnp.float32)
        v = jnp.einsum("tc,hcv->thv", ckv, p["w_uv"],
                       preferred_element_type=jnp.float32)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, None].astype(jnp.float32),
                                      (T, H, k_r.shape[-1]))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if v.shape[-1] != q.shape[-1]:
            raise ValueError("prefill attention wants v_head_dim == qk head size")
        att = flash_attention(
            q[None].astype(self.dtype), k[None].astype(self.dtype),
            v[None].astype(self.dtype), causal=True)[0]
        h = h + self._dot(att.reshape(T, -1), p["w_o"])
        y, load = self._ffn(p, self._norm(h, p["ffn_norm"]), expert_layer, valid)
        return h + y, rows, load

    def prefill(self, params, toks, tp, block_size: int):
        """toks [1, Lb] (the prompt padded to its bucket), tp the true
        length.  Returns (cache rows [nbw, layers, block_size, row_width],
        logits [V] float32 at position tp - 1, counters [layers - 1] int32:
        the fullest expert's tokens by expert layer, pad tokens not
        counted)."""
        Lb = toks.shape[1]
        pos = jnp.arange(Lb)
        valid = pos < tp
        h = params["embed"][toks[0]].astype(jnp.float32)
        h, rows0, _ = self._prefill_layer(params["dense"], h, pos, valid)
        sliced, whole = self._split(params["moe"])

        def body(h, xs):
            p, l = xs
            h, rows, load = self._prefill_layer({**p, **whole}, h, pos, valid, l)
            return h, (rows, jnp.max(load))

        h, (rows, fullest) = jax.lax.scan(body, h, (sliced, self._expert_layers()))
        rows = parts.rows_to_blocks(  # [L, Lb, W] -> [L, nbw, block_size, W]
            jnp.concatenate([rows0[None], rows]), block_size, axis=1)
        logits = self._head(params, jnp.take(h, tp - 1, axis=0))
        return rows.transpose(1, 0, 2, 3), logits, fullest.astype(jnp.int32)

    # -------------------------------------------------------------- decode
    def _decode_layer(self, p, h, pool, layer, paged: PagedState, expert_layer=None):
        c = self.kv_lora_rank
        S = h.shape[0]
        q_nope, q_rope, rows = self._latent(
            p, self._norm(h, p["attn_norm"]), paged.lengths)
        pool = latent_kv_write(
            pool, rows, layer, paged.block_tables, paged.lengths, paged.active)
        # Absorb W_uk into the query: a head's score against the shared row.
        q_abs = jnp.einsum("shn,hcn->shc", q_nope.astype(self.dtype), p["w_uk"],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate(
            [q_abs, q_rope,
             jnp.zeros(q_abs.shape[:2] + (self.row_width - self.row_values,),
                       jnp.float32)], axis=-1)
        o_lat = latent_paged_attention(
            q, pool, layer, paged.block_tables, paged.lengths, paged.active,
            value_width=c,
            scale=(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5)
        att = jnp.einsum("shc,hcv->shv", o_lat.astype(self.dtype), p["w_uv"],
                         preferred_element_type=jnp.float32)
        h = h + self._dot(att.reshape(S, -1), p["w_o"])
        y, load = self._ffn(p, self._norm(h, p["ffn_norm"]), expert_layer, paged.active)
        return h + y, pool, load

    def decode(self, params, pool, tokens, paged: PagedState, mesh=None):
        """One token a slot.  tokens [S]; returns (logits [S, V] float32, the
        pool with this step's rows written, counters [layers - 1] int32:
        experts that an active slot's token chose, by expert layer)."""
        if mesh is not None:
            raise ValueError("the latent decoder runs on one device")
        h = params["embed"][tokens].astype(jnp.float32)
        h, pool, _ = self._decode_layer(params["dense"], h, pool, jnp.int32(0), paged)
        sliced, whole = self._split(params["moe"])

        def body(carry, xs):
            h, pool = carry
            p, l = xs
            h, pool, load = self._decode_layer({**p, **whole}, h, pool, l + 1, paged, l)
            return (h, pool), jnp.sum(load > 0)

        (h, pool), touched = jax.lax.scan(
            body, (h, pool), (sliced, self._expert_layers()))
        return self._head(params, h), pool, touched.astype(jnp.int32)

    # ---------------------------------------------------- the whole forward
    def logits(self, params, toks):
        """Teacher-forced logits [T, V] of one sequence toks [T] through the
        prefill path (tests)."""
        T = toks.shape[0]
        pos = jnp.arange(T)
        h = params["embed"][toks].astype(jnp.float32)
        h, _, _ = self._prefill_layer(params["dense"], h, pos, None)
        sliced, whole = self._split(params["moe"])
        h, _ = jax.lax.scan(
            lambda h, xs: (self._prefill_layer({**xs[0], **whole}, h, pos, None, xs[1])[0], None),
            h, (sliced, self._expert_layers()))
        return self._head(params, h)


def tiny_config() -> Dict:
    """The published ratios at a size the CPU tests and ``chip_smoke.py``
    run: every width a multiple of the 128 lanes where the kernels need it."""
    return {
        "model_type": "glm4_moe_lite", "vocab_size": 512, "hidden_size": 256,
        "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 128,
        "kv_lora_rank": 128, "qk_nope_head_dim": 96, "qk_rope_head_dim": 32,
        "v_head_dim": 128, "intermediate_size": 512, "moe_intermediate_size": 128,
        "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "first_k_dense_replace": 1, "n_group": 1,
        "topk_group": 1, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
        "rope_scaling": None, "max_position_embeddings": 1024,
    }
