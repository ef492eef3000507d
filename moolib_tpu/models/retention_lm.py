"""A dense decoder built from a published configuration file whose every
mixer is power retention (``model_type`` ``brumby``; ``ops.retention``): a
linear attention at degree 2 under a scalar forget gate a K/V head, on the
projections of the Qwen3 lineage (grouped K/V heads, per-head RMSNorm on q and
k, half-split RoPE), each layer followed by a SwiGLU feed-forward.

Plain functions over a parameter pytree; the identical layers are stacked and
run under one ``jax.lax.scan``.  A sequence holds a fixed state and nothing
that grows, so the model offers the serving engine (``engine/engine.py``)
``state_spec`` and NO ``cache_spec``: no paged pool, no block table.

- :meth:`state_spec`: what a SLOT owns, slot axis first: the state of every
  layer ``[slots, layers, kv_heads, d/2 + 1, d_v, d]`` float32 (the symmetric
  square in cyclic diagonals, one ``[d_v, d]`` tile a diagonal: the layout the
  decode kernel reads) and its normaliser ``[slots, layers, kv_heads, 72, d]``
  (the d/2 + 1 rows in whole tiles of 8);
- :meth:`prefill` hands back the state and the normaliser after position
  ``tp - 1`` and does no work for its bucket's padding: the true length,
  which the program receives as data, bounds the position-wise work to the
  row tiles that hold a real position (``decoder_parts.over_live_rows``) and
  the two retention kernels to the blocks below it, so the padding moves
  neither and costs a part of one tile; :meth:`write_state` overwrites the
  slot's row with them, whatever the slot's last holder left there;
- :meth:`decode`: one token a slot; the state and the normaliser of ACTIVE
  slots advance in place, the others' are left as they are.  ``paged.lengths``
  are the positions (RoPE); ``paged.block_tables`` is None.

Precision: weights and matmul inputs in ``dtype`` (bfloat16), products
accumulated in float32; the residual stream, RMSNorm, the gate, RoPE, the
state, its normaliser and the logits in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops import retention
from ..ops.paged_attention import PagedState
from ..parallel.moe import swiglu
from . import decoder_parts as parts

# Rows a pass of a prefill's position-wise work takes (``over_live_rows``): a
# layer's 0.66 GB of weights are read again for every tile, which 512 rows of
# products outlast on a v5e (PERF.md section 6, PR 54, has the chip's readings).
_ROW_TILE = 512


@dataclasses.dataclass(frozen=True)
class PowerRetentionLM:
    """Sizes under their published names (``from_config`` reads them)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_len: int = 8192  # positions the engine may ask for
    dtype: Any = jnp.bfloat16

    step_counters = 1  # slots holding live state
    prefill_counters = 1  # bucket rows whose position-wise work the program ran

    @classmethod
    def from_config(cls, config, **overrides) -> "PowerRetentionLM":
        """Build from a configuration (a dict, or the path of its JSON file)
        that holds the published keys; ``overrides`` replace single sizes (a
        test's depth, the engine's ``max_len``).  A key the model cannot
        honour is refused by name."""
        config, dtype = parts.load_config(config, overrides)
        parts.refuse(cls.__name__, {
            "attention_bias": config.get("attention_bias", False) is not False,
            "hidden_act": config.get("hidden_act", "silu") != "silu",
            "rope_scaling": config.get("rope_scaling") is not None,
            "use_sliding_window": config.get("use_sliding_window", False) is not False,
            "tie_word_embeddings": config.get("tie_word_embeddings", False) is not False,
            "num_key_value_heads": config["num_attention_heads"] % config["num_key_value_heads"] != 0,
            "max_len": config.get("max_len", 0) > config.get("max_position_embeddings", 1 << 30),
        })
        return cls(
            vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"],
            num_hidden_layers=config["num_hidden_layers"],
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
            rms_norm_eps=config.get("rms_norm_eps", 1e-6),
            rope_theta=config.get("rope_theta", 1e6),
            max_len=config.get("max_len", min(config.get("max_position_embeddings", 8192), 8192)),
            dtype=dtype,
        )

    # ------------------------------------------------------- what the engine asks
    def observe_step(self, counters) -> None:
        """A decode step's counters, back on the host (the engine fetched
        them with the step's packet)."""
        parts.observe_state_live(counters[0])

    def observe_prefill(self, counters, prompt_len: int) -> None:
        """A prefill's counters, back on the host with its first token."""
        parts.observe_prefill_rows(counters[0])

    def state_spec(self, slots: int):
        lead, d = (slots, self.num_hidden_layers, self.num_key_value_heads), self.head_dim
        return {
            "state": jax.ShapeDtypeStruct(lead + (retention.diagonals(d), d, d), jnp.float32),
            "norm": jax.ShapeDtypeStruct(lead + (retention.norm_rows(d), d), jnp.float32),
        }

    def write_state(self, cache, rows, slot):
        """The join: the slot's row of every leaf becomes the prefill's, whole."""
        return parts.write_slot_rows(cache, rows, slot)

    # -------------------------------------------------------------- weights
    def init(self, key) -> Dict:
        """Random weights from ``key``: normal with standard deviation
        fan_in ** -0.5 (embedding 1.0), norms 1; the gate's projection at a
        quarter of that and its bias the logit of a retention ``1 - f`` with
        the forgetting ``f`` log-uniform in 0.001 .. 0.1, so that a token
        keeps 0.9 .. 0.999 of the state (a gate of one half would forget a
        prompt in three tokens).  Jit it: the weights are made on the device."""
        D, F, L = self.hidden_size, self.intermediate_size, self.num_hidden_layers
        H, G, hd = self.num_attention_heads, self.num_key_value_heads, self.head_dim
        keys, w = parts.weight_drawer(key, 16, self.dtype)

        forget = jnp.exp(jax.random.uniform(
            next(keys), (L, G), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        layers = {
            "attn_norm": jnp.ones((L, D), jnp.float32),
            "w_q": w((L, D, H * hd), D),
            "w_kv": w((L, D, 2 * G * hd), D),
            "q_norm": jnp.ones((L, hd), jnp.float32),
            "k_norm": jnp.ones((L, hd), jnp.float32),
            "w_gate": w((L, D, G), D, jnp.float32, scale=0.25),
            "b_gate": jnp.log1p(-forget) - jnp.log(forget),
            "w_o": w((L, H * hd, D), H * hd),
            "ffn_norm": jnp.ones((L, D), jnp.float32),
            "w_gu": w((L, D, 2 * F), D),
            "w_down": w((L, F, D), F),
        }
        return {
            "embed": w((self.vocab_size, D), 1.0),
            "layers": layers,
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

    def _mixer_inputs(self, p, xn, pos):
        """What retention takes, from normed inputs xn [T, D] at positions pos
        [T]: q [T, H, d], k and v [T, G, d], the gate's log lam [T, G] <= 0,
        all float32."""
        T, H, G, hd = xn.shape[0], self.num_attention_heads, self.num_key_value_heads, self.head_dim
        # The barrier keeps the products as [T, heads x hd]: left to itself XLA
        # folds the head-major reshapes into the dots and transposes the weights.
        q, kv = jax.lax.optimization_barrier((self._dot(xn, p["w_q"]), self._dot(xn, p["w_kv"])))
        k, v = jnp.split(kv.reshape(T, 2 * G, hd), 2, axis=1)
        q = parts.rope_half_split(
            self._norm(q.reshape(T, H, hd), p["q_norm"]), pos[:, None], self.rope_theta)
        k = parts.rope_half_split(self._norm(k, p["k_norm"]), pos[:, None], self.rope_theta)
        gate = jnp.dot(xn, p["w_gate"], precision=jax.lax.Precision.HIGHEST) + p["b_gate"]
        return q, k, v, jax.nn.log_sigmoid(gate)

    def _close(self, p, h, o):
        """A layer's other half: the output projection of o [T, H, d] into the
        residual stream h [T, D], then the feed-forward."""
        h = h + self._dot(o.reshape(h.shape[0], -1), p["w_o"])
        xn = self._norm(h, p["ffn_norm"]).astype(self.dtype)
        return h + swiglu(xn, p["w_gu"], p["w_down"])

    # ------------------------------------------------------------- prefill
    def _forward(self, params, toks, tp):
        """The whole prompt toks [T] of which the first ``tp`` are real (None:
        all).  Returns (h [T, D], every layer's state [L, G, D', d, d] and
        normaliser [L, G, D', d] after position tp - 1).  Where the bucket is
        more than one row tile, only the tiles that hold a real position are
        computed, a tile a pass; the rows of ``h`` past them are 0 and those
        between ``tp`` and the last live tile's end mean nothing: nothing
        reads a row past ``tp - 1``, whatever token ids lie there."""
        pos = jnp.arange(toks.shape[0])
        h = params["embed"][toks].astype(jnp.float32)

        # A layer's weights are taken out of the stacks where the rows are:
        # inside a tile's pass they stay operands of the products (sliced in
        # front of the loop over the tiles, 0.66 GB would be copied a layer).
        layer = lambda l: jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, l, keepdims=False), params["layers"])

        def mixer_inputs(rows, l):
            p, (h, pos) = layer(l), rows
            return self._mixer_inputs(p, self._norm(h, p["attn_norm"]), pos)

        def close(rows, l):
            return self._close(layer(l), *rows)

        def body(h, l):
            q, k, v, lam = parts.over_live_rows(mixer_inputs, (h, pos), tp, _ROW_TILE, (l,))
            # with a length, the padding's k, v and lam are not read: it moves
            # neither state nor normaliser
            o, state, norm = retention.retention_prefill(
                q, k, v, lam, length=tp, dtype=self.dtype)
            h = parts.over_live_rows(close, (h, o), tp, _ROW_TILE, (l,))
            return h, (state, norm)

        return jax.lax.scan(body, h, jnp.arange(self.num_hidden_layers))

    def prefill(self, params, toks, tp, block_size: int):
        """toks [1, Lb] (the prompt padded to its bucket), tp the true
        length; ``block_size`` is the engine's and is not read (nothing here
        is paged).  Returns (the rows :meth:`write_state` takes, logits [V]
        float32 at position tp - 1, counters: the bucket rows whose
        position-wise work ran, ``ceil(tp / tile)`` tiles, or the whole
        bucket where it is at most one)."""
        h, (state, norm) = self._forward(params, toks[0], tp)
        logits = self._head(params, jnp.take(h, tp - 1, axis=0))
        rows = parts.rows_over_live_tiles(toks.shape[1], tp, _ROW_TILE)
        return {"state": state, "norm": norm}, logits, jnp.asarray(rows, jnp.int32)[None]

    # -------------------------------------------------------------- decode
    def decode(self, params, cache, tokens, paged: PagedState, mesh=None):
        """One token a slot.  tokens [S]; returns (logits [S, V] float32, the
        cache with the active slots' state and normaliser advanced, counters:
        the slots holding live state)."""
        if mesh is not None:
            raise ValueError("the retention decoder runs on one device")
        active = paged.active
        h = params["embed"][tokens].astype(jnp.float32)

        def body(carry, xs):
            h, state, norm = carry
            p, layer = xs
            q, k, v, lam = self._mixer_inputs(p, self._norm(h, p["attn_norm"]), paged.lengths)
            o, state, norm = retention.retention_decode(
                q, k, v, lam, state, norm, layer, active)
            return (self._close(p, h, o), state, norm), None

        layers = jnp.arange(self.num_hidden_layers, dtype=jnp.int32)
        (h, state, norm), _ = jax.lax.scan(
            body, (h, cache["state"], cache["norm"]), (params["layers"], layers))
        return (self._head(params, h), {"state": state, "norm": norm},
                jnp.sum(active, dtype=jnp.int32)[None])

    # ---------------------------------------------------- the whole forward
    def logits(self, params, toks):
        """Teacher-forced logits [T, V] of one sequence toks [T] (a power of
        two, or a multiple of 256) through the prefill path (tests)."""
        return self._head(params, self._forward(params, toks, None)[0])


def tiny_config() -> Dict:
    """The published ratios at a size the CPU tests run: heads of 128 (the
    kernels' lanes), two query heads a K/V head."""
    return {
        "model_type": "brumby", "vocab_size": 384, "hidden_size": 256,
        "intermediate_size": 512, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128, "hidden_act": "silu",
        "attention_bias": False, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "rope_scaling": None, "sliding_window": None, "use_sliding_window": False,
        "max_window_layers": 2, "tie_word_embeddings": False,
        "max_position_embeddings": 1024,
    }
