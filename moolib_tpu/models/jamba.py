"""A dense decoder built from a published configuration file whose layers are
of two kinds (``model_type`` ``jamba``): a Mamba-1 mixer (``ops.selective_scan``:
a short causal convolution, a selective state-space scan over ``d_inner``
channels of ``mamba_d_state`` states each, a gate), with the Jamba family's
RMSNorm on the step's low-rank input and on ``B`` and ``C``; and, in one
layer of ``attn_layer_period``, a softmax attention without positions whose
query heads all share ONE K/V head (multi-query).  Every layer ends in a
SwiGLU feed-forward (``num_experts`` 1: the family's expert layers are the
same dense one); the head is the embedding table, tied.

Plain functions over a parameter pytree.  The Mamba layers between two
attention layers are a **run**: the layers before the first attention layer
(``attn_layer_offset``), those between two, those after the last.  Each run's
weights are stacked in arrays of its own and run under one ``jax.lax.scan`` (a
tuple over the runs, not a leading axis: a run's slice of one stacked array
would be a copy of its weights in every step), and the attention layers are
a Python loop, so that each layer's K/V pools are operands of their own.  The
model offers the serving engine both kinds of cache leaf (``engine/engine.py``):

- :meth:`cache_spec`: the paged pools, block axis first: K and V of each
  attention layer, ``[num_blocks, block_size, 1, head_dim]`` bfloat16;
- :meth:`state_spec`: what a SLOT owns, slot axis first: the scan's state of
  every Mamba layer ``[slots, mamba_layers, d_state, d_inner]`` float32 (the
  states on sublanes, the channels on lanes: as ``[.., d_inner, 16]`` the leaf
  would be padded eightfold in HBM) and the convolution's tail, the last
  three inputs, ``[slots, mamba_layers, 3 d_inner / 128, 128]``: tap t in rows
  ``t d_inner / 128`` onwards, so that a slot's tail of a layer is one
  contiguous block of whole tiles (``ops.selective_scan.conv_tail_write``);
- :meth:`prefill` hands back the K/V rows with the state after position
  ``tp - 1`` and the tail there (a bucket's padding has the step ``dt``
  zeroed, so it moves nothing); :meth:`write_rows` scatters the former by
  block and :meth:`write_state` overwrites the slot's row with the latter,
  whatever the slot's last holder left there;
- :meth:`decode`: one token a ROW; the state and the tail of ACTIVE rows'
  slots advance in place, the others' are left as they are.  The rows are the
  slots, or fewer (:data:`JambaLM.decodes_rows`): ``paged.slots`` then names
  each row's slot, the tails of those slots alone are gathered and written
  back (the active rows' alone, by ``conv_tail_write``), and the scan kernel
  reads a row's state at its slot.

Precision: weights and matmul inputs in ``dtype`` (bfloat16), products
accumulated in float32; the residual stream, every RMSNorm, the convolution
and its weights, the softplus, ``A``, ``D``, the step's bias, the scan, the
softmax and the logits in float32; the scan's state and the convolution's
tail float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import selective_scan as ssm
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import PagedState
from ..parallel.moe import swiglu
from . import decoder_parts as parts

_CONV = parts.CONV_TAPS  # the convolution's taps; the tail is the last _CONV - 1 inputs


@dataclasses.dataclass(frozen=True)
class JambaLM:
    """Sizes under their published names (``from_config`` reads them)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    attn_layer_period: int
    attn_layer_offset: int
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_expand: float = 2
    rms_norm_eps: float = 1e-6
    max_len: int = 4096  # positions the engine may ask for
    dtype: Any = jnp.bfloat16

    step_counters = 1  # slots holding live state
    prefill_counters = 1  # the real positions the prefill's scan ran over
    decodes_rows = True  # decode addresses its slot-axis leaves through paged.slots

    @classmethod
    def from_config(cls, config, **overrides) -> "JambaLM":
        """Build from a configuration (a dict, or the path of its JSON file)
        that holds the published keys; ``overrides`` replace single sizes (a
        test's depth, the engine's ``max_len``).  A key the model cannot
        honour is refused by name."""
        config, dtype = parts.load_config(config, overrides)
        depth, period = config["num_hidden_layers"], config["attn_layer_period"]
        offset = config["attn_layer_offset"]
        positions = config.get("max_position_embeddings", 1 << 30)
        parts.refuse(cls.__name__, {
            "num_experts": config.get("num_experts", 1) != 1,
            "num_key_value_heads": config["num_key_value_heads"] != 1,
            "mamba_proj_bias": config.get("mamba_proj_bias", False) is not False,
            "mamba_conv_bias": config.get("mamba_conv_bias", True) is not True,
            "mamba_d_conv": config.get("mamba_d_conv", _CONV) != _CONV,
            "hidden_act": config.get("hidden_act", "silu") != "silu",
            "sliding_window": config.get("sliding_window") is not None,
            "tie_word_embeddings": config.get("tie_word_embeddings", True) is not True,
            "num_hidden_layers": depth % period != 0,  # whole periods only
            # a run of Mamba layers on either side of every attention layer
            "attn_layer_offset": not 0 < offset < period - 1,
            "num_attention_heads": config["hidden_size"] % config["num_attention_heads"] != 0,
            "max_len": config.get("max_len", 0) > positions,
        })
        return cls(
            vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"], num_hidden_layers=depth,
            num_attention_heads=config["num_attention_heads"],
            attn_layer_period=period, attn_layer_offset=offset,
            mamba_d_state=config["mamba_d_state"], mamba_dt_rank=config["mamba_dt_rank"],
            mamba_expand=config["mamba_expand"],
            rms_norm_eps=config.get("rms_norm_eps", 1e-6),
            max_len=config.get("max_len", positions), dtype=dtype,
        )

    # ------------------------------------------------------------ geometry
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return int(self.mamba_expand * self.hidden_size)

    @property
    def attn_layers(self) -> int:
        return self.num_hidden_layers // self.attn_layer_period

    @property
    def runs(self) -> Tuple[int, ...]:
        """Mamba layers before the first attention layer, between two, and
        after the last."""
        return parts.runs_between(
            ["attention" if l % self.attn_layer_period == self.attn_layer_offset else "mamba"
             for l in range(self.num_hidden_layers)], "attention")

    @property
    def mamba_layers(self) -> int:
        return sum(self.runs)

    # ------------------------------------------------------- what the engine asks
    def observe_step(self, counters) -> None:
        """A decode step's counters, back on the host (the engine fetched
        them with the step's packet)."""
        parts.observe_state_live(counters[0])

    def observe_prefill(self, counters, prompt_len: int) -> None:
        """A prefill's counter, back on the host beside its first token."""
        parts.observe_scan_positions(counters[0])

    def cache_spec(self, num_blocks: int, block_size: int):
        pool = jax.ShapeDtypeStruct((num_blocks, block_size, 1, self.head_dim), self.dtype)
        return {"k": (pool,) * self.attn_layers, "v": (pool,) * self.attn_layers}

    def state_spec(self, slots: int):
        lead = (slots, self.mamba_layers)
        return {
            "ssm": jax.ShapeDtypeStruct(lead + (self.mamba_d_state, self.d_inner), jnp.float32),
            "conv": jax.ShapeDtypeStruct(
                lead + ((_CONV - 1) * self.d_inner // ssm.LANES, ssm.LANES), jnp.float32),
        }

    def write_rows(self, cache: parts.SlotCache, rows, block_ids) -> parts.SlotCache:
        return parts.write_cache_rows(cache, rows, block_ids)

    def write_state(self, cache: parts.SlotCache, rows, slot) -> parts.SlotCache:
        """The join's other half: the slot's row of every slot-axis leaf
        becomes the prefill's, whole."""
        return parts.write_cache_state(cache, rows, slot)

    # -------------------------------------------------------------- weights
    def init(self, key) -> Dict:
        """Random weights from ``key``: normal with standard deviation
        fan_in ** -0.5 (the embedding as the head it also is: hidden ** -0.5,
        so that logits are of order 1), norms and ``D`` 1, and what decides
        how long the state remembers as Mamba-1's initialiser draws it:
        ``A = -(1 .. d_state)`` for every channel, the step's bias the inverse
        softplus of a step log-uniform in 0.001 .. 0.1.  Jit it: the weights
        are made on the device."""
        D, F, Ci = self.hidden_size, self.intermediate_size, self.d_inner
        N, R, H, hd = self.mamba_d_state, self.mamba_dt_rank, self.num_attention_heads, self.head_dim
        keys, w = parts.weight_drawer(key, 16 * (len(self.runs) + 1), self.dtype)

        def ffn(lead):
            return {"ffn_norm": jnp.ones(lead + (D,), jnp.float32),
                    "w_gu": w(lead + (D, 2 * F), D), "w_down": w(lead + (F, D), F)}

        def mamba(lead):
            dt_bias = parts.step_bias(next(keys), lead + (Ci,))
            return {
                "mixer_norm": jnp.ones(lead + (D,), jnp.float32),
                "w_in": w(lead + (D, 2 * Ci), D),  # u | z
                "conv": w(lead + (_CONV, Ci), _CONV, jnp.float32),
                "conv_bias": w(lead + (Ci,), _CONV, jnp.float32),
                "w_x": w(lead + (Ci, R + 2 * N), Ci),  # the step's low-rank input | B | C
                "dt_norm": jnp.ones(lead + (R,), jnp.float32),
                "b_norm": jnp.ones(lead + (N,), jnp.float32),
                "c_norm": jnp.ones(lead + (N,), jnp.float32),
                "w_dt": w(lead + (R, Ci), R),
                "dt_bias": dt_bias,
                # transposed, as the state is held: [d_state, d_inner]
                "a_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], lead + (N, Ci)),
                "d": jnp.ones(lead + (Ci,), jnp.float32),
                "w_out": w(lead + (Ci, D), Ci),
                **ffn(lead),
            }

        P = self.attn_layers
        return {
            "embed": w((self.vocab_size, D), D),
            "mamba": tuple(mamba((n,)) for n in self.runs),
            "attn": {
                "mixer_norm": jnp.ones((P, D), jnp.float32),
                "w_q": w((P, D, H * hd), D),
                "w_kv": w((P, D, 2 * hd), D),
                "w_o": w((P, H * hd, D), H * hd),
                **ffn((P,)),
            },
            "final_norm": jnp.ones((D,), jnp.float32),
        }

    # ------------------------------------------------------------- pieces
    def _norm(self, x, scale):
        return parts.rms_norm(x, scale, self.rms_norm_eps)

    def _dot(self, x, w):
        return parts.dot(x, w, self.dtype)

    def _head(self, params, h):
        """The tied head: the contraction runs over the table's second axis
        where it lies."""
        return parts.head_logits(h, params["final_norm"], params["embed"],
                                 self.rms_norm_eps, self.dtype, tied=True)

    def _ffn(self, p, h):
        return h + swiglu(self._norm(h, p["ffn_norm"]).astype(self.dtype), p["w_gu"], p["w_down"])

    def _scan_inputs(self, p, u):
        """What the scan takes beside the convolution's output u [T, d_inner]
        (after its silu): the step dt [T, d_inner], B and C [T, d_state], the
        decay's rate A [d_state, d_inner], float32."""
        R, N = self.mamba_dt_rank, self.mamba_d_state
        x = self._dot(u, p["w_x"])
        dt = self._norm(x[:, :R], p["dt_norm"])
        B = self._norm(x[:, R:R + N], p["b_norm"])
        C = self._norm(x[:, R + N:], p["c_norm"])
        dt = jax.nn.softplus(self._dot(dt, p["w_dt"]) + p["dt_bias"])
        return dt, B, C, -jnp.exp(p["a_log"])

    def _qkv(self, p, xn):
        return parts.gqa_qkv(xn, p["w_q"], p["w_kv"], self.num_attention_heads, 1,
                             self.head_dim, self.dtype)

    # ------------------------------------------------------------- prefill
    def _attn_prefill(self, p, h):
        """A multi-query layer's mixer over a whole prompt h [T, D]: (h + y,
        K and V [T, 1, hd] in the pools' dtype)."""
        xn = self._norm(h, p["mixer_norm"])
        q, k, v = self._qkv(p, xn)
        k, v = k.astype(self.dtype), v.astype(self.dtype)
        with jax.named_scope("mqa_prefill"):
            att = flash_attention(q[None].astype(self.dtype), k[None], v[None], causal=True)[0]
        return h + self._dot(att.reshape(h.shape[0], -1), p["w_o"]), k, v

    def _mamba_prefill(self, p, h, last):
        """A Mamba layer's mixer over a whole prompt h [T, D] whose first
        ``last`` positions are real: (h + y, the state [d_state, d_inner] and
        the convolution's tail [3, d_inner] after position last - 1).  The
        kernel holds the state still over the padding and runs no chunk that
        lies wholly in it."""
        Ci = self.d_inner
        uz = self._dot(self._norm(h, p["mixer_norm"]), p["w_in"])
        u, tail = parts.conv_prefill(uz[:, :Ci], p["conv"], p["conv_bias"], last)
        dt, B, C, A = self._scan_inputs(p, u)
        y, state = ssm.ssm_prefill(u, dt, uz[:, Ci:], A, B, C, p["d"], length=last)
        return h + self._dot(y, p["w_out"]), state, tail

    def _forward(self, params, toks, tp):
        """The whole prompt toks [T] of which the first ``tp`` are real (None:
        all).  Returns (h [T, D], K and V by attention layer [T, 1, hd], the
        Mamba layers' states [mamba_layers, d_state, d_inner] and convolution
        tails [mamba_layers, 3, d_inner] after position tp - 1)."""
        last = toks.shape[0] if tp is None else tp
        h = params["embed"][toks].astype(jnp.float32)
        ks, vs, states, tails = [], [], [], []

        def body(h, p):
            h, state, tail = self._mamba_prefill(p, h, last)
            return self._ffn(p, h), (state, tail)

        for i, run in enumerate(params["mamba"]):
            h, (state, tail) = jax.lax.scan(body, h, run)
            states.append(state), tails.append(tail)
            if i < self.attn_layers:
                p = jax.tree.map(lambda x: x[i], params["attn"])
                h, k, v = self._attn_prefill(p, h)
                h = self._ffn(p, h)
                ks.append(k), vs.append(v)
        return h, ks, vs, jnp.concatenate(states), jnp.concatenate(tails)

    def prefill(self, params, toks, tp, block_size: int):
        """toks [1, Lb] (the prompt padded to its bucket), tp the true
        length.  Returns (rows for :meth:`write_rows` and :meth:`write_state`,
        logits [V] float32 at position tp - 1, counters: the length the scan
        was told)."""
        h, ks, vs, state, tail = self._forward(params, toks[0], tp)

        def blocks(xs):  # [Lb, 1, hd] -> [nbw, block_size, 1, hd], by attention layer
            return tuple(parts.rows_to_blocks(x, block_size, axis=0) for x in xs)

        rows = {"blocks": {"k": blocks(ks), "v": blocks(vs)},
                "slots": {"ssm": state, "conv": tail.reshape(tail.shape[0], -1, ssm.LANES)}}
        return (rows, self._head(params, jnp.take(h, tp - 1, axis=0)),
                jnp.asarray(tp, jnp.int32).reshape(1))

    # -------------------------------------------------------------- decode
    def _mamba_decode(self, p, h, state, conv, layer, paged):
        """One token a row through a Mamba layer's mixer: h [R, D], the state
        and tail leaves whole, ``layer`` this layer's index into them,
        ``paged.active`` [R] the rows that step and ``paged.slots`` [R] each
        row's slot in the leaves (None: row i is slot i).  (The step's
        ``PagedState`` whole, not its two fields: a subclass that wraps this
        method hands it on unread.)"""
        Ci = self.d_inner
        active, slots = paged.active, paged.slots
        uz = self._dot(self._norm(h, p["mixer_norm"]), p["w_in"])
        u, conv = parts.conv_step(conv, uz, 0, p["conv"], p["conv_bias"], layer, active, slots)
        dt, B, C, A = self._scan_inputs(p, u)
        y, state = ssm.ssm_decode(u, dt, A, B, C, state, layer, active, slots)
        y = (y + p["d"] * u) * jax.nn.silu(uz[:, Ci:])
        return h + self._dot(y, p["w_out"]), state, conv

    def decode(self, params, cache: parts.SlotCache, tokens, paged: PagedState, mesh=None):
        """One token a row.  tokens [R]; returns (logits [R, V] float32, the
        cache with this step's K/V written and the active rows' state and
        tail advanced at their slots, counters: the slots holding live state)."""
        if mesh is not None:
            raise ValueError("the Mamba decoder runs on one device")
        active = paged.active
        h = params["embed"][tokens].astype(jnp.float32)
        pools_k, pools_v = list(cache.blocks["k"]), list(cache.blocks["v"])
        state, conv = cache.slots["ssm"], cache.slots["conv"]

        def body(carry, xs):
            h, state, conv = carry
            p, layer = xs
            h, state, conv = self._mamba_decode(p, h, state, conv, layer, paged)
            return (self._ffn(p, h), state, conv), None

        first = 0
        for i, (run, n) in enumerate(zip(params["mamba"], self.runs)):
            (h, state, conv), _ = jax.lax.scan(
                body, (h, state, conv), (run, first + jnp.arange(n, dtype=jnp.int32)))
            first += n
            if i < self.attn_layers:
                p = jax.tree.map(lambda x: x[i], params["attn"])
                xn = self._norm(h, p["mixer_norm"])
                q, k, v = self._qkv(p, xn)
                with jax.named_scope("mqa_decode"):
                    att, pools_k[i], pools_v[i] = parts.paged_gqa_decode(
                        pools_k[i], pools_v[i], q, k, v, paged)
                h = self._ffn(p, h + self._dot(att.reshape(h.shape[0], -1), p["w_o"]))
        cache = parts.SlotCache(
            blocks={"k": tuple(pools_k), "v": tuple(pools_v)},
            slots={"ssm": state, "conv": conv})
        return self._head(params, h), cache, jnp.sum(active, dtype=jnp.int32)[None]

    # ---------------------------------------------------- the whole forward
    def logits(self, params, toks):
        """Teacher-forced logits [T, V] of one sequence toks [T] through the
        prefill path (tests)."""
        return self._head(params, self._forward(params, toks, None)[0])


def tiny_config() -> Dict:
    """The published shape at a size the CPU tests run: two periods of four
    layers with the attention layer second, one K/V head of 128 under four
    query heads, 256 channels of 16 states, a tied head."""
    return {
        "model_type": "jamba", "vocab_size": 384, "hidden_size": 512,
        "intermediate_size": 256, "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 1, "attn_layer_period": 4, "attn_layer_offset": 1,
        "expert_layer_period": 2, "expert_layer_offset": 1, "num_experts": 1,
        "num_experts_per_tok": 1, "hidden_act": "silu", "mamba_d_state": 16,
        "mamba_d_conv": 4, "mamba_expand": 0.5, "mamba_dt_rank": 8, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "rms_norm_eps": 1e-6, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True, "num_logits_to_keep": 1,
        "max_position_embeddings": 1024,
    }
