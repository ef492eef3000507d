"""A decoder built from a published configuration file whose layers are of two
kinds, each followed by routed experts (``model_type`` ``granitemoehybrid``):
a Mamba-2 mixer (``ops.ssd``: a short causal convolution over ``x | B | C``,
a state-space recurrence with a SCALAR decay a head over ``mamba_n_heads``
heads of ``mamba_d_head`` channels and ``mamba_d_state`` states, a gate in
front of an RMSNorm over all channels) and, where ``layer_types`` says
``attention``, grouped-query softmax attention WITHOUT positions.  EVERY layer
ends in dropless softmax-routed experts beside a shared expert, of which this
chip may hold a share (``parallel.moe.dropless_moe``'s ``held_from``).  The
family's four multipliers: the embedding times ``embedding_multiplier``, each
branch into the residual stream times ``residual_multiplier``, scores times
``attention_multiplier`` (not ``head_dim ** -0.5``), logits over
``logits_scaling``; the head is the embedding table, tied.

Plain functions over a parameter pytree.  The Mamba layers between two
attention layers are a **run** (``decoder_parts.runs_between``): each run's
weights are stacked in arrays of its own and run under one ``jax.lax.scan`` (a
tuple over the runs, not a leading axis: a run's slice of one stacked array
would be a copy of its weights in every step), the attention layers are a
Python loop, so that each layer's K/V pools are operands of their own, and
the held experts' matrices are ONE stack over all layers that goes to
``dropless_moe`` whole, the layer an index.  The model offers the serving
engine both kinds of cache leaf (``engine/engine.py``):

- :meth:`cache_spec`: the paged pools, block axis first: K and V of each
  attention layer, ``[num_blocks, block_size, kv_heads, head_dim]`` bfloat16;
- :meth:`state_spec`: what a SLOT owns, slot axis first: the recurrence's
  state of every Mamba layer ``[slots, mamba_layers, heads, d_head, d_state]``
  float32 (a head's channels on sublanes, the states on lanes: 4 MB a layer a
  slot as published) and the convolution's tail, the last three inputs of all
  ``d_inner + 2 d_state`` channels, ``[slots, mamba_layers, 3 channels / 128,
  128]`` with the rows rounded up to whole tiles, 198 -> 200 as published
  (``decoder_parts.conv_tail_spec``; ``ops.selective_scan.conv_tail_write``'s
  layout);
- :meth:`prefill` hands back the K/V rows with the state after position ``tp
  - 1`` and the tail there (a bucket's padding has the step ``dt`` zeroed, so
  it moves nothing, and chunks wholly in it do not run); :meth:`write_rows`
  scatters the former by block and :meth:`write_state` overwrites the slot's
  row with the latter, whatever the slot's last holder left there;
- :meth:`decode`: one token a slot; the state and the tail of ACTIVE slots
  advance in place (``ssd_decode``, ``conv_tail_write``), the others' are left
  as they are, bit for bit.

Precision: weights and matmul inputs in ``dtype`` (bfloat16), products
accumulated in float32; the residual stream, every RMSNorm (the gated one
too), the convolution with its taps and bias, ``dt_bias``, the softplus,
``A``, ``D``, ``exp(dt A)``, the recurrence (the prefill's chunk products are
float32 at the highest precision), the router, the softmax and the logits in
float32; the K/V pools in ``dtype``; the recurrent state and the tails float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssd
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import PagedState
from ..parallel.moe import dropless_moe, softmax_topk_route
from . import decoder_parts as parts

_CONV = parts.CONV_TAPS
# ``init`` draws the tied table at this many times hidden ** -0.5 OVER
# ``embedding_multiplier``: a constant of the program that serves the CHECK,
# not the model (no checkpoint is loaded).  Why: the table is the head too.
# Drawn at hidden ** -0.5, a token's row enters the stream 12 times as large
# and meets ITSELF in the head: that token's logit stands 12 deviations above
# every other, whatever the twenty branches (0.22 each) add, so a greedy decode
# repeats its last prompt token for ever, the reference agrees, and no fault in
# any mixer, state or expert moves a single token (tests/test_ssd_moe.py: a
# join that writes no state emitted the sound run's tokens, 8 of 8).  Over the
# multiplier the row enters at the branches' own size and its own logit gains
# one deviation of 25,088.
_TABLE_SCALE = 1.0


@dataclasses.dataclass(frozen=True)
class SsdGqaMoELM:
    """Sizes under their published names (``from_config`` reads them).
    ``num_local_experts`` counts the experts HELD here, ids ``held_from ..``;
    ``router_experts`` (a key of the file under that name; without it the held
    count) is the router's width, the published count.  ``layer_types`` is the
    kinds of the ``num_hidden_layers`` layers that run."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int  # ONE routed expert's width
    shared_intermediate_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    num_local_experts: int
    router_experts: int
    num_experts_per_tok: int
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0  # 0: head_dim ** -0.5
    logits_scaling: float = 1.0
    held_from: int = 0
    rms_norm_eps: float = 1e-5
    max_len: int = 4096  # positions the engine may ask for
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_config(cls, config, **overrides) -> "SsdGqaMoELM":
        """Build from a configuration (a dict, or the path of its JSON file)
        that holds the published keys; ``overrides`` replace single sizes (a
        test's depth, the engine's ``max_len``).  ``layer_types`` may be longer
        than the depth (the published list, under a cut): its first
        ``num_hidden_layers`` entries are read.  A key the model cannot honour
        is refused by name."""
        config, dtype = parts.load_config(config, overrides)
        depth, D = config["num_hidden_layers"], config["hidden_size"]
        kinds = tuple(config["layer_types"][:depth])
        heads = config["num_attention_heads"]
        held = config["num_local_experts"]
        router = config.get("router_experts", held)
        positions = config.get("max_position_embeddings", 1 << 30)
        inner = config["mamba_n_heads"] * config["mamba_d_head"]
        parts.refuse(cls.__name__, {
            "mamba_n_groups": config.get("mamba_n_groups", 1) != 1,
            "mamba_d_conv": config.get("mamba_d_conv", _CONV) != _CONV,
            "mamba_conv_bias": config.get("mamba_conv_bias", True) is not True,
            "mamba_proj_bias": config.get("mamba_proj_bias", False) is not False,
            "attention_bias": config.get("attention_bias", False) is not False,
            "position_embedding_type": config.get("position_embedding_type", "nope") != "nope",
            "rope_scaling": config.get("rope_scaling") is not None,
            "hidden_act": config.get("hidden_act", "silu") != "silu",
            "normalization_function": config.get("normalization_function", "rmsnorm") != "rmsnorm",
            "tie_word_embeddings": config.get("tie_word_embeddings", True) is not True,
            "mamba_expand": inner != config["mamba_expand"] * D,
            # whole runs: Mamba layers on either side of every attention layer
            "layer_types": len(kinds) != depth or set(kinds) - {"mamba", "attention"} != set()
            or 0 in parts.runs_between(kinds, "attention"),
            "held_from": not 0 <= config.get("held_from", 0) <= router - held,
            "num_attention_heads": heads % config["num_key_value_heads"] != 0,
            # the tails' leaf is whole rows of 128 lanes
            "mamba_d_state": (inner + 2 * config["mamba_d_state"]) % 128 != 0,
            "max_len": config.get("max_len", 0) > positions,
        })
        return cls(
            vocab_size=config["vocab_size"], hidden_size=D,
            intermediate_size=config["intermediate_size"],
            shared_intermediate_size=config["shared_intermediate_size"],
            num_hidden_layers=depth, layer_types=kinds, num_attention_heads=heads,
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim") or D // heads,
            mamba_n_heads=config["mamba_n_heads"], mamba_d_head=config["mamba_d_head"],
            mamba_d_state=config["mamba_d_state"], num_local_experts=held,
            router_experts=router, num_experts_per_tok=config["num_experts_per_tok"],
            embedding_multiplier=config.get("embedding_multiplier", 1.0),
            residual_multiplier=config.get("residual_multiplier", 1.0),
            attention_multiplier=config.get("attention_multiplier", 0.0),
            logits_scaling=config.get("logits_scaling", 1.0),
            held_from=config.get("held_from", 0),
            rms_norm_eps=config.get("rms_norm_eps", 1e-5),
            max_len=config.get("max_len", min(positions, 4096)), dtype=dtype,
        )

    # ------------------------------------------------------------ geometry
    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        """x | B | C: what the convolution runs over."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def runs(self) -> Tuple[int, ...]:
        """Mamba layers before the first attention layer, between two, and
        after the last."""
        return parts.runs_between(self.layer_types, "attention")

    @property
    def attn_layers(self) -> int:
        return len(self.runs) - 1

    @property
    def mamba_layers(self) -> int:
        return sum(self.runs)

    @property
    def q_scale(self) -> float:
        """What q is multiplied by so that the attention kernels' own
        ``head_dim ** -0.5`` makes scores times ``attention_multiplier``."""
        return (self.attention_multiplier or self.head_dim ** -0.5) * self.head_dim ** 0.5

    # ------------------------------------------------------- what the engine asks
    @property
    def step_counters(self) -> int:
        """int32 counters a decode step hands back: the slots holding live
        state, then by layer the held (token, expert) pairs and the held
        experts touched."""
        return 1 + 2 * self.num_hidden_layers

    @property
    def prefill_counters(self) -> int:
        """The real positions the prefill's scan ran over, then the fullest
        held expert's tokens by layer."""
        return 1 + self.num_hidden_layers

    def observe_step(self, counters) -> None:
        """A decode step's counters, back on the host (the engine fetched
        them with the step's packet)."""
        live = int(counters[0])
        parts.observe_state_live(live)
        parts.observe_held_step(counters[1:], live, self.num_experts_per_tok)

    def observe_prefill(self, counters, prompt_len: int) -> None:
        """A prefill's counters, back on the host beside its first token."""
        parts.observe_scan_positions(counters[0])
        parts.observe_held_prefill(
            counters[1:], prompt_len, self.num_experts_per_tok, self.router_experts)

    def cache_spec(self, num_blocks: int, block_size: int):
        pool = jax.ShapeDtypeStruct(
            (num_blocks, block_size, self.num_key_value_heads, self.head_dim), self.dtype)
        return {"k": (pool,) * self.attn_layers, "v": (pool,) * self.attn_layers}

    def state_spec(self, slots: int):
        return {
            "ssd": jax.ShapeDtypeStruct(
                (slots, self.mamba_layers, self.mamba_n_heads, self.mamba_d_head,
                 self.mamba_d_state), jnp.float32),
            "conv": parts.conv_tail_spec(slots, self.mamba_layers, self.conv_channels),
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
        fan_in ** -0.5 (the tied table at hidden ** -0.5 over
        ``embedding_multiplier``: ``_TABLE_SCALE``), norms and ``D`` 1, and what
        decides how long
        the state remembers as Mamba-2's initialiser draws it: ``A`` uniform
        in -(1 .. 16) a head, the step's bias the inverse softplus of a step
        log-uniform in 0.001 .. 0.1.  Jit it: the weights are made on the
        device."""
        D, Ci, Cc = self.hidden_size, self.d_inner, self.conv_channels
        Hm, H, Hk, hd = self.mamba_n_heads, self.num_attention_heads, \
            self.num_key_value_heads, self.head_dim
        F, Fs, E, G = self.intermediate_size, self.shared_intermediate_size, \
            self.router_experts, self.num_local_experts
        keys, w = parts.weight_drawer(key, 16 * (len(self.runs) + 2), self.dtype)

        def ffn(lead):
            return {"ffn_norm": jnp.ones(lead + (D,), jnp.float32),
                    "router": w(lead + (D, E), D, jnp.float32),
                    "shared_gu": w(lead + (D, 2 * Fs), D),
                    "shared_down": w(lead + (Fs, D), Fs)}

        def mamba(lead):
            a = jax.random.uniform(next(keys), lead + (Hm,), jnp.float32, 1.0, 16.0)
            return {
                "mixer_norm": jnp.ones(lead + (D,), jnp.float32),
                "w_in": w(lead + (D, 2 * Ci + 2 * self.mamba_d_state + Hm), D),  # z | x B C | dt
                "conv": w(lead + (_CONV, Cc), _CONV, jnp.float32),
                "conv_bias": w(lead + (Cc,), _CONV, jnp.float32),
                "dt_bias": parts.step_bias(next(keys), lead + (Hm,)),
                "a_log": jnp.log(a),
                "d": jnp.ones(lead + (Hm,), jnp.float32),
                "gate_norm": jnp.ones(lead + (Ci,), jnp.float32),
                "w_out": w(lead + (Ci, D), Ci),
                **ffn(lead),
            }

        P, L = self.attn_layers, self.num_hidden_layers
        return {
            "embed": w((self.vocab_size, D), D, scale=_TABLE_SCALE / self.embedding_multiplier),
            "mamba": tuple(mamba((n,)) for n in self.runs),
            "attn": {
                "mixer_norm": jnp.ones((P, D), jnp.float32),
                "w_q": w((P, D, H * hd), D),
                "w_kv": w((P, D, 2 * Hk * hd), D),
                "w_o": w((P, H * hd, D), H * hd),
                **ffn((P,)),
            },
            "experts_gu": w((L, G, D, 2 * F), D),
            "experts_down": w((L, G, F, D), F),
            "final_norm": jnp.ones((D,), jnp.float32),
        }

    # ------------------------------------------------------------- pieces
    def _norm(self, x, scale):
        return parts.rms_norm(x, scale, self.rms_norm_eps)

    def _dot(self, x, w):
        return parts.dot(x, w, self.dtype)

    def _embed(self, params, tokens):
        return params["embed"][tokens].astype(jnp.float32) * self.embedding_multiplier

    def _head(self, params, h):
        """The tied head: the contraction runs over the table's second axis
        where it lies."""
        return parts.head_logits(h, params["final_norm"], params["embed"], self.rms_norm_eps,
                                 self.dtype, tied=True) / self.logits_scaling

    def _branch(self, h, y):
        return h + self.residual_multiplier * y

    def _ffn(self, p, experts, h, layer, valid):
        """The expert layer behind a mixer; ``layer`` indexes the experts'
        stacked matrices.  Returns (h + 0.22 y, tokens a held expert)."""
        # the family's router has no selection bias: the choice is the scores'
        bias = jnp.zeros((self.router_experts,), jnp.float32)
        y, load = dropless_moe(
            self._norm(h, p["ffn_norm"]), {**p, **experts, "router_bias": bias},
            top_k=self.num_experts_per_tok, scale=1.0, valid=valid, layer=layer,
            held_from=self.held_from, route=softmax_topk_route)
        return self._branch(h, y), load

    def _split(self, wide):
        """The in-projection's output [T, z | x B C | dt]: (z, dt's columns),
        the convolution's lie between them."""
        Ci = self.d_inner
        return wide[:, :Ci], wide[:, Ci + self.conv_channels:]

    def _scan_inputs(self, p, u, dt):
        """What the recurrence takes, from the convolution's output u [T, x |
        B | C] (after its silu) and the step's columns dt [T, heads]: x [T,
        heads, d_head], the step after its softplus, the decay's rate A
        [heads], B and C [T, d_state], float32."""
        Ci, N = self.d_inner, self.mamba_d_state
        x = u[:, :Ci].reshape(u.shape[0], self.mamba_n_heads, self.mamba_d_head)
        return (x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]),
                u[:, Ci:Ci + N], u[:, Ci + N:])

    def _gated_out(self, p, y, x, z):
        """From the recurrence's read-out y [T, heads, d_head]: the ``D x``
        term, the gate, THEN the norm over all channels, the out-projection."""
        y = (y + p["d"][:, None] * x).reshape(z.shape) * jax.nn.silu(z)
        return self._dot(self._norm(y, p["gate_norm"]), p["w_out"])

    def _qkv(self, p, xn):
        q, k, v = parts.gqa_qkv(xn, p["w_q"], p["w_kv"], self.num_attention_heads,
                                self.num_key_value_heads, self.head_dim, self.dtype)
        return q * self.q_scale, k, v  # in float32, before q's cast

    # ------------------------------------------------------------- prefill
    def _attn_prefill(self, p, h):
        """A grouped-query layer's mixer over a whole prompt h [T, D]: (h +
        0.22 y, K and V [T, Hk, hd] in the pools' dtype)."""
        xn = self._norm(h, p["mixer_norm"])
        q, k, v = self._qkv(p, xn)
        k, v = k.astype(self.dtype), v.astype(self.dtype)
        group = self.num_attention_heads // self.num_key_value_heads
        with jax.named_scope("nope_prefill"):
            att = flash_attention(
                q[None].astype(self.dtype), jnp.repeat(k, group, axis=1)[None],
                jnp.repeat(v, group, axis=1)[None], causal=True)[0]
        return self._branch(h, self._dot(att.reshape(h.shape[0], -1), p["w_o"])), k, v

    def _scan_prefill(self, x, dt, A, B, C, last):
        """The recurrence over a bucket (a planted fault's seam)."""
        return ssd.ssd_prefill(x, dt, A, B, C, length=last)

    def _mamba_prefill(self, p, h, last):
        """A Mamba-2 layer's mixer over a whole prompt h [T, D] whose first
        ``last`` positions are real: (h + 0.22 y, the state [heads, d_head,
        d_state] and the convolution's tail [3, channels] after position last
        - 1)."""
        wide = self._dot(self._norm(h, p["mixer_norm"]), p["w_in"])
        z, dt = self._split(wide)
        u, tail = parts.conv_prefill(
            wide[:, self.d_inner:self.d_inner + self.conv_channels],
            p["conv"], p["conv_bias"], last)
        x, dt, A, B, C = self._scan_inputs(p, u, dt)
        y, state = self._scan_prefill(x, dt, A, B, C, last)
        return self._branch(h, self._gated_out(p, y, x, z)), state, tail

    def _forward(self, params, toks, tp):
        """The whole prompt toks [T] of which the first ``tp`` are real (None:
        all).  Returns (h [T, D], K and V by attention layer [T, Hk, hd], the
        Mamba layers' states [mamba_layers, heads, d_head, d_state] and
        convolution tails [mamba_layers, 3, channels] after position tp - 1,
        tokens a held expert by layer [L, G])."""
        T = toks.shape[0]
        last = T if tp is None else tp
        valid = None if tp is None else jnp.arange(T) < tp
        h = self._embed(params, toks)
        experts = parts.held_experts(params)
        ks, vs, states, tails, loads = [], [], [], [], []

        def body(h, xs):
            p, layer = xs
            h, state, tail = self._mamba_prefill(p, h, last)
            h, load = self._ffn(p, experts, h, layer, valid)
            return h, (state, tail, load)

        first = 0
        for i, (run, n) in enumerate(zip(params["mamba"], self.runs)):
            h, (state, tail, load) = jax.lax.scan(
                body, h, (run, first + jnp.arange(n, dtype=jnp.int32)))
            first += n
            states.append(state), tails.append(tail), loads.append(load)
            if i < self.attn_layers:
                p = jax.tree.map(lambda x: x[i], params["attn"])
                h, k, v = self._attn_prefill(p, h)
                h, load = self._ffn(p, experts, h, first, valid)
                first += 1
                ks.append(k), vs.append(v), loads.append(load[None])
        cat = jnp.concatenate
        return h, ks, vs, cat(states), cat(tails), cat(loads)

    def prefill(self, params, toks, tp, block_size: int):
        """toks [1, Lb] (the prompt padded to its bucket), tp the true
        length.  Returns (rows for :meth:`write_rows` and :meth:`write_state`,
        logits [V] float32 at position tp - 1, counters: the length the scan
        was told, then the fullest held expert's tokens by layer)."""
        h, ks, vs, state, tail, load = self._forward(params, toks[0], tp)

        def blocks(xs):  # [Lb, Hk, hd] -> [nbw, block_size, Hk, hd], by attention layer
            return tuple(parts.rows_to_blocks(x, block_size, axis=0) for x in xs)

        rows = {"blocks": {"k": blocks(ks), "v": blocks(vs)},
                "slots": {"ssd": state, "conv": parts.tail_rows(tail)}}
        counters = jnp.concatenate([jnp.asarray(tp, jnp.int32).reshape(1),
                                    jnp.max(load, axis=-1).astype(jnp.int32)])
        return rows, self._head(params, jnp.take(h, tp - 1, axis=0)), counters

    # -------------------------------------------------------------- decode
    def _mamba_decode(self, p, h, state, conv, layer, paged):
        """One token a slot through a Mamba-2 layer's mixer: h [S, D], the
        state and tail leaves whole, ``layer`` this layer's index into them,
        ``paged.active`` [S] the slots that step."""
        wide = self._dot(self._norm(h, p["mixer_norm"]), p["w_in"])
        z, dt = self._split(wide)
        u, conv = parts.conv_step(
            conv, wide, self.d_inner, p["conv"], p["conv_bias"], layer, paged.active)
        x, dt, A, B, C = self._scan_inputs(p, u, dt)
        y, state = ssd.ssd_decode(x, dt, A, B, C, state, layer, paged.active)
        return self._branch(h, self._gated_out(p, y, x, z)), state, conv

    def decode(self, params, cache: parts.SlotCache, tokens, paged: PagedState, mesh=None):
        """One token a slot.  tokens [S]; returns (logits [S, V] float32, the
        cache with this step's K/V written and the active slots' state and
        tail advanced, counters: :attr:`step_counters`)."""
        if mesh is not None:
            raise ValueError("the Mamba-2 decoder runs on one device")
        active = paged.active
        h = self._embed(params, tokens)
        experts = parts.held_experts(params)
        pools_k, pools_v = list(cache.blocks["k"]), list(cache.blocks["v"])
        state, conv = cache.slots["ssd"], cache.slots["conv"]
        loads = []

        def body(carry, xs):
            h, state, conv = carry
            p, layer, mamba_layer = xs
            h, state, conv = self._mamba_decode(p, h, state, conv, mamba_layer, paged)
            h, load = self._ffn(p, experts, h, layer, active)
            return (h, state, conv), load

        first = done = 0
        for i, (run, n) in enumerate(zip(params["mamba"], self.runs)):
            steps = jnp.arange(n, dtype=jnp.int32)
            (h, state, conv), load = jax.lax.scan(
                body, (h, state, conv), (run, first + steps, done + steps))
            first, done = first + n, done + n
            loads.append(load)
            if i < self.attn_layers:
                p = jax.tree.map(lambda x: x[i], params["attn"])
                q, k, v = self._qkv(p, self._norm(h, p["mixer_norm"]))
                with jax.named_scope("nope_decode"):
                    att, pools_k[i], pools_v[i] = parts.paged_gqa_decode(
                        pools_k[i], pools_v[i], q, k, v, paged)
                h = self._branch(h, self._dot(att.reshape(h.shape[0], -1), p["w_o"]))
                h, load = self._ffn(p, experts, h, first, active)
                first += 1
                loads.append(load[None])
        counters = jnp.concatenate([
            jnp.sum(active, dtype=jnp.int32)[None],
            parts.held_step_counters(jnp.concatenate(loads, axis=0))])
        cache = parts.SlotCache(
            blocks={"k": tuple(pools_k), "v": tuple(pools_v)},
            slots={"ssd": state, "conv": conv})
        return self._head(params, h), cache, counters

    # ---------------------------------------------------- the whole forward
    def logits(self, params, toks):
        """Teacher-forced logits [T, V] of one sequence toks [T] through the
        prefill path (tests)."""
        return self._head(params, self._forward(params, toks, None)[0])


def tiny_config() -> Dict:
    """The published SHAPE at a size the CPU tests run: two periods of five
    layers with the attention layer third, four Mamba-2 heads of 64 channels
    and 64 states (384 convolution channels: three whole rows of lanes), two
    K/V heads of 128 under four query heads, 4 of 8 experts held, 3 a token,
    beside a shared expert; a tied head; all four multipliers other than 1."""
    period = ["mamba", "mamba", "attention", "mamba", "mamba"]
    return {
        "model_type": "granitemoehybrid", "vocab_size": 384, "hidden_size": 128,
        "intermediate_size": 64, "shared_intermediate_size": 128, "num_hidden_layers": 10,
        "layer_types": period * 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 128, "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 16,
        "hidden_act": "silu", "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 64, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 4, "mamba_proj_bias": False,
        "max_position_embeddings": 1024, "normalization_function": "rmsnorm",
        "num_experts_per_tok": 3, "num_local_experts": 4, "router_experts": 8, "held_from": 0,
        "position_embedding_type": "nope", "rms_norm_eps": 1e-5, "rope_scaling": None,
        "rope_theta": 10000, "tie_word_embeddings": True,
    }
