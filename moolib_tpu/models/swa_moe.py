"""A decoder built from a published configuration file whose attention layers
are of two kinds: sliding-window grouped-query attention (a query sees itself
and the ``sliding_window - 1`` keys before it) on a RING a slot, and full causal
attention on the paged pools, each kind rotated by its own group of the file's
``rope_parameters`` (the sliding layers at one base, the full layers by a YaRN
table); behind every mixer dropless softmax-routed experts
(``parallel.moe.dropless_moe``), of which this chip may hold a share
(``held_from``) or all.  Two published files build it today (``model_type``
``laguna`` and ``mellum``), and the layer plan is the FILE's:

- ``mlp_layer_types``: the leading ``dense`` layers (none, or one full-attention
  layer with a dense SwiGLU), every layer behind them ``sparse``;
- ``layer_types``: behind the leading layers, whole **periods** (the shortest
  repeat of the published list), each with at least one full layer wherever in
  the period it stands (``s s s f`` in both files).  The RUNS of sliding layers
  between full layers (``decoder_parts.runs_between``) are each a stack of
  weights under one ``jax.lax.scan``; the full layers are a Python loop between
  them, so that each full layer's K/V pools are operands of their own;
- one head count for both kinds (``num_attention_heads``) or one a kind
  (``num_attention_heads_per_layer``); a sigmoid gate a head on the attention's
  output where the file says ``gating`` ``per-head``, none without the key; a
  shared expert where ``shared_expert_intermediate_size`` is there; a learned
  RMSNorm over each head of q and k before the rotation where ``qk_norm`` is
  true; part of a head rotated where a rope group has ``partial_rotary_factor``;
  the routed sum scaled where ``moe_routed_scaling_factor`` is there.

What the module cannot honour is refused by the key's name (``from_config``).

Plain functions over a parameter pytree.  The model offers the serving engine
both kinds of cache leaf (``engine/engine.py``), and BOTH hold keys and values:

- :meth:`cache_spec`: the paged pools of the FULL layers, block axis first:
  K and V of each, ``[num_blocks, block_size, kv_heads, head_dim]``;
- :meth:`state_spec`: what a SLOT owns, slot axis first: K and V **rings** of
  the SLIDING layers, ``[slots, sliding_layers, window, kv_heads, head_dim]``:
  ``window`` rows a layer whatever the length.  Keys are rotated before they are
  written, so order inside a ring is free: the token at position t lies in row
  ``t % window``, and a step attends over the ``min(t + 1, window)`` rows
  written so far.  The ring is read by ``ops.paged_attention`` itself: the
  whole leaf, reshaped ``[slots x sliding_layers x window / 128, 128, kv_heads,
  head_dim]`` (a bitcast), is a pool whose block table is arithmetic, built
  inside the jit: ``(slot x sliding_layers + layer) x window / 128 + j``.  The
  LAYER is chosen by the table, under the scan too, so no layer of the leaf is
  ever sliced out;
- :meth:`prefill` hands back the full layers' K/V rows as blocks, with the
  last ``min(tp, window)`` rows of each sliding layer placed where their
  positions put them in a ring; :meth:`write_rows` scatters the former by
  block and :meth:`write_state` overwrites the slot's rings whole;
- :meth:`decode`: one token a slot; the rings of ACTIVE slots take the token's
  row, the others' stay as they are, bit for bit (a ring is NOT written with
  ``paged_kv_write``: that sends an inactive slot's row to block 0, which here
  is slot 0's ring).

Precision: weights and matmul inputs in ``dtype`` (bfloat16), products
accumulated in float32; the residual stream, RMSNorm (the q/k norm too),
rotation, router, softmax and logits in float32; pools and rings in ``dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry
from ..ops.flash_attention import flash_attention, window_key_blocks
from ..ops.paged_attention import PagedState, paged_attention
from ..parallel.moe import dropless_moe, softmax_topk_route, swiglu
from . import decoder_parts as parts

_M_RING_ROWS = telemetry.get_registry().histogram(
    "serve_engine_ring_live_rows",
    "per decode step: rows of a sliding layer's ring that an active slot "
    "attends over, min(position + 1, window), mean over the active slots",
    buckets=(1, 8, 32, 64, 128, 192, 256, 320, 384, 448, 512, 640, 768, 896, 1024,
             1536, 2048, 4096),
)

_M_RING_READ = telemetry.get_registry().histogram(
    "serve_engine_ring_rows_read",
    "per decode step: rows of ONE sliding layer's rings that the step attends "
    "over, the sum over the active slots of min(position + 1, window): what the "
    "kernel must read of K, and of V, a layer",
    buckets=(64, 256, 1024, 2048, 4096, 8192, 12288, 16384, 24576, 32768, 65536),
)

_M_WINDOW_BLOCKS = telemetry.get_registry().counter(
    "serve_engine_window_key_blocks",
    "per prefill, by sliding layer: (query block, key block) pairs of the "
    "windowed flash forward over the prompt's bucket; blocks=visited the pairs "
    "it copies and multiplies, blocks=skipped the further pairs a causal forward "
    "of the same length and blocks would visit (visited + skipped: the causal "
    "count).  From the bucket, the window and the kernel's block sizes, on the "
    "host; a bucket that takes no kernel counts nothing",
    labelnames=("blocks",),
)

_RING_BLOCK = 128  # rows of a ring that the paged kernel copies at once
# ``init`` draws W_q at this many times the fan-in deviation, so that scores
# have this deviation and a query's weight lies on a few keys, as a trained
# model's does.  At 1 the softmax over hundreds of random keys is nearly
# uniform, every mixer's output is a mean of values (a 25th of the residual
# stream's deviation), and a fault in WHICH keys a query sees moves no token:
# the cell's check read a prefill without its window mask as sound (PERF.md).
# Under a q/k norm the scale sits on the q norm's weights instead (the norm
# would undo it on W_q).
_Q_SCALE = 4.0
_FULL, _SLIDING = "full_attention", "sliding_attention"


def _list_period(kinds) -> int:
    """The shortest repeat of a per-layer list (its own length where it does
    not repeat)."""
    return next(p for p in range(1, len(kinds) + 1)
                if all(a == b for a, b in zip(kinds, kinds[p:])))


@dataclasses.dataclass(frozen=True)
class SlidingGqaMoELM:
    """Sizes under their published names (``from_config`` reads them).
    ``num_experts`` counts the experts HELD here, ids ``held_from ..``;
    ``router_experts`` (a key of the file under that name; without it the
    held count) is the router's width, the published count.  ``full_rope`` and
    ``sliding_rope`` are ``rope_parameters``' two groups as sorted items.  The
    plan: ``lead_layers`` leading full-attention layers with a dense
    feed-forward (0 or 1), then ``runs``: the sliding layers before the first
    full layer behind them, between two, and after the last.  ``gated``: a
    sigmoid gate a head; ``qk_norm``: an RMSNorm a head on q and k;
    ``shared_expert_intermediate_size`` 0: no shared expert.  ``embed_scale``
    (the file's own key ``embed_init_scale``) is ``init``'s alone."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    full_heads: int
    sliding_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int
    router_experts: int
    num_experts_per_tok: int
    full_rope: Tuple
    sliding_rope: Tuple
    moe_routed_scaling_factor: float = 1.0
    lead_layers: int = 1
    runs: Tuple[int, ...] = (3, 0)
    gated: bool = True
    qk_norm: bool = False
    held_from: int = 0
    embed_scale: float = 1.0
    rms_norm_eps: float = 1e-6
    max_len: int = 8192  # positions the engine may ask for
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_config(cls, config, **overrides) -> "SlidingGqaMoELM":
        """Build from a configuration (a dict, or the path of its JSON file)
        that holds the published keys; ``overrides`` replace single sizes (a
        test's depth, the engine's ``max_len``).  The per-layer lists may be
        longer than the depth (the published ones, under a cut): their first
        ``num_hidden_layers`` entries are read.  A key the model cannot honour
        is refused by name."""
        config, dtype = parts.load_config(config, overrides)
        depth = config["num_hidden_layers"]
        kinds = list(config["layer_types"])
        ffns = list(config["mlp_layer_types"][:depth])
        lead = next((i for i, f in enumerate(ffns) if f != "dense"), len(ffns))
        # The period is the published list's, behind the leading layers; the
        # depth's share of it must hold whole periods with a full layer each.
        period = _list_period(kinds[lead:]) if len(kinds) > lead else 0
        body = kinds[lead:depth]
        heads = list(config.get("num_attention_heads_per_layer",
                                [config["num_attention_heads"]] * len(kinds))[:depth])
        by_kind = {k: {h for kind, h in zip(kinds, heads) if kind == k}
                   for k in (_FULL, _SLIDING)}
        rope = config["rope_parameters"]
        gating = config.get("gating")
        refused = {
            "layer_types": not set(kinds[:depth]) <= {_FULL, _SLIDING}
            or _FULL not in kinds[lead:lead + period] or _SLIDING not in body
            or any(k != _FULL for k in kinds[:lead]),
            # whole periods behind the leading layers
            "num_hidden_layers": depth <= lead or (depth - lead) % max(period, 1) != 0,
            "num_attention_heads_per_layer": any(len(h) > 1 for h in by_kind.values()),
            "mlp_layer_types": lead > 1 or any(f != "sparse" for f in ffns[lead:]),
            "mlp_only_layers": list(config.get("mlp_only_layers", range(lead)))
            != list(range(lead)),
            "decoder_sparse_step": config.get("decoder_sparse_step", 1) != 1,
            "gating": gating not in (None, "per-head"),
            "gating_types": any(g != "per_head" for g in config.get("gating_types", [])[:depth]),
            "moe_router_logit_softcapping": config.get("moe_router_logit_softcapping", 0) != 0,
            "moe_apply_router_weight_on_input":
                config.get("moe_apply_router_weight_on_input", False) is not False,
            "norm_topk_prob": config.get("norm_topk_prob", True) is not True,
            "attention_bias": config.get("attention_bias", False) is not False,
            "tie_word_embeddings": config.get("tie_word_embeddings", False) is not False,
            "hidden_act": config.get("hidden_act", "silu") != "silu",
            "rope_parameters.full_attention.rope_type":
                rope["full_attention"].get("rope_type") != "yarn",
            "rope_parameters.sliding_attention.rope_type":
                rope["sliding_attention"].get("rope_type", "default") != "default",
            "sliding_window": config["sliding_window"] % min(
                _RING_BLOCK, config["sliding_window"]) != 0,
        }
        parts.refuse(cls.__name__, refused)
        return cls(
            vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"], num_hidden_layers=depth,
            full_heads=by_kind[_FULL].pop(), sliding_heads=by_kind[_SLIDING].pop(),
            num_key_value_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
            sliding_window=config["sliding_window"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_expert_intermediate_size=config.get("shared_expert_intermediate_size", 0),
            num_experts=config["num_experts"],
            router_experts=config.get("router_experts", config["num_experts"]),
            num_experts_per_tok=config["num_experts_per_tok"],
            full_rope=tuple(sorted(rope["full_attention"].items())),
            sliding_rope=tuple(sorted(rope["sliding_attention"].items())),
            moe_routed_scaling_factor=config.get("moe_routed_scaling_factor", 1.0),
            lead_layers=lead, runs=parts.runs_between(body, _FULL),
            gated=gating == "per-head", qk_norm=bool(config.get("qk_norm", False)),
            held_from=config.get("held_from", 0),
            embed_scale=config.get("embed_init_scale", 1.0),
            rms_norm_eps=config.get("rms_norm_eps", 1e-6),
            max_len=config.get("max_len", min(config.get("max_position_embeddings", 8192), 8192)),
            dtype=dtype,
        )

    # ------------------------------------------------------------ geometry
    @property
    def sliding_layers(self) -> int:
        return sum(self.runs)

    @property
    def full_layers(self) -> int:
        return self.lead_layers + len(self.runs) - 1

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.lead_layers

    @property
    def ring_block(self) -> int:
        return min(_RING_BLOCK, self.sliding_window)

    @property
    def step_counters(self) -> int:
        """int32 counters a decode step hands back: the active slots, the sum
        over them of the ring rows attended, then by expert layer the held
        (token, expert) pairs and the held experts touched."""
        return 2 + 2 * self.expert_layers

    @property
    def prefill_counters(self) -> int:
        """By expert layer the fullest held expert's tokens, then the held
        (token, expert) pairs, then the held experts that hold rows; last the
        prompt's bucket."""
        return 3 * self.expert_layers + 1

    def observe_step(self, counters) -> None:
        live = int(counters[0])
        _M_RING_ROWS.observe(int(counters[1]) / max(1, live))
        _M_RING_READ.observe(int(counters[1]))
        parts.observe_held_step(counters[2:], live, self.num_experts_per_tok)

    def observe_prefill(self, counters, prompt_len: int) -> None:
        L = self.expert_layers
        parts.observe_held_prefill(
            counters[:L], prompt_len, self.num_experts_per_tok, self.router_experts)
        parts.observe_prefill_rows_per_expert(counters[L:2 * L], counters[2 * L:3 * L])
        visited, causal = window_key_blocks(int(counters[3 * L]), self.sliding_window)
        _M_WINDOW_BLOCKS.inc(visited * self.sliding_layers, blocks="visited")
        _M_WINDOW_BLOCKS.inc((causal - visited) * self.sliding_layers, blocks="skipped")

    def cache_spec(self, num_blocks: int, block_size: int):
        pool = jax.ShapeDtypeStruct(
            (num_blocks, block_size, self.num_key_value_heads, self.head_dim), self.dtype)
        return {"k": (pool,) * self.full_layers, "v": (pool,) * self.full_layers}

    def state_spec(self, slots: int):
        ring = jax.ShapeDtypeStruct(
            (slots, self.sliding_layers, self.sliding_window, self.num_key_value_heads,
             self.head_dim), self.dtype)
        return {"k": ring, "v": ring}

    def write_rows(self, cache: parts.SlotCache, rows, block_ids) -> parts.SlotCache:
        return parts.write_cache_rows(cache, rows, block_ids)

    def write_state(self, cache: parts.SlotCache, rows, slot) -> parts.SlotCache:
        """The join's other half: the slot's rings become the prefill's, whole."""
        return parts.write_cache_state(cache, rows, slot)

    # -------------------------------------------------------------- weights
    def init(self, key) -> Dict:
        """Random weights from ``key``: normal with standard deviation
        fan_in ** -0.5 (embedding ``embed_scale``; W_q ``_Q_SCALE`` times it, or
        the q norm's weights where there is one), norms 1, a zero selection bias.
        Jit it: the weights are made on the device.  ``lead`` is there with a
        leading dense layer; ``swa`` holds one stack a run of sliding layers
        (an empty run has none) and ``full`` the full layers behind the
        leading ones."""
        D, hd, Hk = self.hidden_size, self.head_dim, self.num_key_value_heads
        F, Fs, E = self.moe_intermediate_size, self.shared_expert_intermediate_size, \
            self.router_experts
        keys, w = parts.weight_drawer(key, 64, self.dtype)

        def mixer(lead, H):
            p = {
                "attn_norm": jnp.ones(lead + (D,), jnp.float32),
                "w_q": w(lead + (D, H * hd), D, scale=1.0 if self.qk_norm else _Q_SCALE),
                "w_kv": w(lead + (D, 2 * Hk * hd), D),
                "w_o": w(lead + (H * hd, D), H * hd),
                "ffn_norm": jnp.ones(lead + (D,), jnp.float32),
            }
            if self.gated:
                p["w_gate"] = w(lead + (D, H), D)
            if self.qk_norm:
                p["q_norm"] = jnp.full(lead + (hd,), _Q_SCALE, jnp.float32)
                p["k_norm"] = jnp.ones(lead + (hd,), jnp.float32)
            return p

        def ffn(lead):
            p = {"router": w(lead + (D, E), D, jnp.float32),
                 "router_bias": jnp.zeros(lead + (E,), jnp.float32)}
            if Fs:
                p["shared_gu"] = w(lead + (D, 2 * Fs), D)
                p["shared_down"] = w(lead + (Fs, D), Fs)
            return p

        G = self.num_experts
        params = {"embed": w((self.vocab_size, D), 1.0, scale=self.embed_scale)}
        if self.lead_layers:
            params["lead"] = {
                **mixer((), self.full_heads),
                "dense_gu": w((D, 2 * self.intermediate_size), D),
                "dense_down": w((self.intermediate_size, D), self.intermediate_size)}
        return {
            **params,
            # A tuple over the runs, not a leading axis: a run's slice of
            # one stacked array would be a copy of its weights in every step.
            "swa": tuple({**mixer((K,), self.sliding_heads), **ffn((K,))}
                         for K in self.runs if K),
            "full": tuple({**mixer((), self.full_heads), **ffn(())}
                          for _ in self.runs[1:]),
            "experts_gu": w((self.expert_layers, G, D, 2 * F), D),
            "experts_down": w((self.expert_layers, G, F, D), F),
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

    def _rotate_full(self, x, pos):
        """A full layer's rotation: the first ``partial_rotary_factor`` of a
        head (all of it without the key) by the YaRN table, cos and sin times
        ``attention_factor``."""
        r = dict(self.full_rope)
        rotated = int(self.head_dim * r.get("partial_rotary_factor", 1.0))
        table = parts.yarn_inv_freq(
            rotated, r["rope_theta"], r["factor"], r["original_max_position_embeddings"],
            r.get("beta_fast", 32), r.get("beta_slow", 1))
        return parts.rope_table(x, pos, table, r["attention_factor"])

    def _rotate_sliding(self, x, pos):
        r = dict(self.sliding_rope)
        rotated = int(self.head_dim * r.get("partial_rotary_factor", 1.0))
        table = r["rope_theta"] ** (-jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated)
        return parts.rope_table(x, pos, table)

    def _qkv(self, p, xn, pos, heads, rotate):
        """q [T, heads, hd] and k, v [T, Hk, hd], q and k normed a head where
        the layer has the scales, then rotated at ``pos`` [T] (which
        broadcasts over their heads), float32."""
        q, k, v = parts.gqa_qkv(xn, p["w_q"], p["w_kv"], heads,
                                self.num_key_value_heads, self.head_dim, self.dtype,
                                p.get("q_norm"), p.get("k_norm"), self.rms_norm_eps)
        return rotate(q, pos[:, None]), rotate(k, pos[:, None]), v

    def _output(self, p, xn, att):
        """att [T, H, hd], under its gate (one sigmoid a head) where the
        layer has one, through W_o."""
        if "w_gate" in p:
            gate = jax.nn.sigmoid(self._dot(xn, p["w_gate"]))  # [T, H]
            att = att.astype(jnp.float32) * gate[..., None]
        return self._dot(att.reshape(xn.shape[0], -1), p["w_o"])

    def _dense(self, p, h):
        x = self._norm(h, p["ffn_norm"]).astype(self.dtype)
        return h + swiglu(x, p["dense_gu"], p["dense_down"])

    def _ffn(self, p, experts, h, layer, valid):
        """An expert layer; ``layer`` indexes the experts' stacked matrices.
        Returns (h + y, tokens a held expert)."""
        y, load = dropless_moe(
            self._norm(h, p["ffn_norm"]), {**p, **experts},
            top_k=self.num_experts_per_tok, scale=self.moe_routed_scaling_factor,
            valid=valid, layer=layer, held_from=self.held_from, route=softmax_topk_route)
        return h + y, load

    def _plan(self, params):
        """The layers behind the leading ones, in order: ("swa", the run's
        stack, its expert layers' indices, its ring layers' indices) for a run
        of sliding layers, ("full", the layer's weights, its expert layer, its
        pool) for a full layer."""
        plan, stacks = [], iter(params["swa"])
        layer = ring = 0
        for i, run in enumerate(self.runs):
            if run:
                steps = jnp.arange(run, dtype=jnp.int32)
                plan.append(("swa", next(stacks), layer + steps, ring + steps))
                layer, ring = layer + run, ring + run
            if i < len(self.runs) - 1:
                plan.append(("full", params["full"][i], layer, self.lead_layers + i))
                layer += 1
        return plan

    # ------------------------------------------------------------- prefill
    def _attend_prompt(self, p, h, pos, heads, rotate, window):
        """A mixer over a whole prompt h [T, D]: (h + y, K and V [T, Hk, hd]
        in the cache's dtype, K rotated)."""
        xn = self._norm(h, p["attn_norm"])
        q, k, v = self._qkv(p, xn, pos, heads, rotate)
        k, v = k.astype(self.dtype), v.astype(self.dtype)
        group = heads // self.num_key_value_heads
        att = flash_attention(
            q[None].astype(self.dtype), jnp.repeat(k, group, axis=1)[None],
            jnp.repeat(v, group, axis=1)[None], causal=True, window=window)[0]
        return h + self._output(p, xn, att), k, v

    def _full_prefill(self, p, h, pos):
        with jax.named_scope("full_prefill"):
            return self._attend_prompt(p, h, pos, self.full_heads, self._rotate_full, None)

    def _swa_prefill(self, p, h, pos):
        with jax.named_scope("swa_prefill"):
            return self._attend_prompt(
                p, h, pos, self.sliding_heads, self._rotate_sliding, self.sliding_window)

    def _ffn_prefill(self, p, experts, h, layer, valid):
        with jax.named_scope("moe_prefill"):
            return self._ffn(p, experts, h, layer, valid)

    def _forward(self, params, toks, tp):
        """The whole prompt toks [T] of which the first ``tp`` are real (None:
        all).  Returns (h [T, D], K and V of the full layers [T, Hk, hd] a
        layer, K and V of the sliding layers [sliding_layers, T, Hk, hd],
        tokens a held expert by expert layer [expert layers, G])."""
        T = toks.shape[0]
        pos = jnp.arange(T)
        valid = None if tp is None else pos < tp
        h = params["embed"][toks].astype(jnp.float32)
        experts = parts.held_experts(params)
        ks, vs, ring_k, ring_v, loads = [], [], [], [], []
        if self.lead_layers:
            h, k, v = self._full_prefill(params["lead"], h, pos)
            h = self._dense(params["lead"], h)
            ks.append(k), vs.append(v)
        for kind, p, layer, _cache in self._plan(params):
            if kind == "swa":
                def body(h, xs):
                    p, layer = xs
                    h, k, v = self._swa_prefill(p, h, pos)
                    h, load = self._ffn_prefill(p, experts, h, layer, valid)
                    return h, (k, v, load)

                h, (k, v, load) = jax.lax.scan(body, h, (p, layer))
                ring_k.append(k), ring_v.append(v), loads.append(load)
            else:
                h, k, v = self._full_prefill(p, h, pos)
                h, load = self._ffn_prefill(p, experts, h, layer, valid)
                ks.append(k), vs.append(v), loads.append(load[None])
        cat = lambda xs: jnp.concatenate(xs, axis=0)
        return h, ks, vs, cat(ring_k), cat(ring_v), cat(loads)

    def prefill(self, params, toks, tp, block_size: int):
        """toks [1, Lb] (the prompt padded to its bucket), tp the true
        length.  Returns (rows for :meth:`write_rows` and :meth:`write_state`,
        logits [V] float32 at position tp - 1, counters int32
        (:attr:`prefill_counters`; pad tokens not counted))."""
        h, ks, vs, ring_k, ring_v, load = self._forward(params, toks[0], tp)
        # Row r of a ring holds the last position before tp that is r modulo
        # the window (rows past tp, where the prompt is shorter than the
        # window, are not attended until a decode step has written them).
        W = self.sliding_window
        r = jnp.arange(W)
        at = jnp.clip(tp - 1 - (tp - 1 - r) % W, 0, toks.shape[1] - 1)

        def blocks(xs):  # [Lb, Hk, hd] -> [nbw, block_size, Hk, hd], by full layer
            return tuple(parts.rows_to_blocks(x, block_size, axis=0) for x in xs)

        rows = {"blocks": {"k": blocks(ks), "v": blocks(vs)},
                "slots": {"k": jnp.take(ring_k, at, axis=1), "v": jnp.take(ring_v, at, axis=1)}}
        logits = self._head(params, jnp.take(h, tp - 1, axis=0))
        counters = jnp.concatenate([
            jnp.max(load, axis=-1).astype(jnp.int32), parts.held_step_counters(load),
            jnp.full((1,), toks.shape[1], jnp.int32)])
        return rows, logits, counters

    # -------------------------------------------------------------- decode
    def _ring_write(self, ring, x, layer, row, active):
        """x [S, Hk, hd] into row ``row`` [S] of layer ``layer`` of each ACTIVE
        slot's ring [S, layers, W, Hk, hd]; an inactive slot's index lies past
        the ring and its update is dropped."""
        S, W = ring.shape[0], ring.shape[2]
        return ring.at[jnp.arange(S), layer, jnp.where(active, row, W)].set(
            x.astype(ring.dtype), mode="drop")

    def _ring_row(self, position):
        """The row of a ring that holds position ``position``."""
        return position % self.sliding_window

    def _ring_attend(self, q, ring_k, ring_v, layer, position, active):
        """q [S, H, hd] against layer ``layer`` of the rings through the paged
        kernel: the whole leaf as a pool of ``ring_block``-row blocks, the
        slot's and the layer's blocks named by the table."""
        S, layers, W, Hk, hd = ring_k.shape
        nb = W // self.ring_block
        pool = lambda ring: ring.reshape(S * layers * nb, self.ring_block, Hk, hd)
        tables = ((jnp.arange(S, dtype=jnp.int32)[:, None] * layers + layer) * nb
                  + jnp.arange(nb, dtype=jnp.int32)[None, :])
        return paged_attention(
            q[:, None].astype(ring_k.dtype), pool(ring_k), pool(ring_v), tables,
            jnp.minimum(position, W - 1), active)[:, 0]

    def _full_decode(self, p, h, pool_k, pool_v, paged):
        xn = self._norm(h, p["attn_norm"])
        q, k, v = self._qkv(p, xn, paged.lengths, self.full_heads, self._rotate_full)
        with jax.named_scope("full_decode"):
            att, pool_k, pool_v = parts.paged_gqa_decode(pool_k, pool_v, q, k, v, paged)
        return h + self._output(p, xn, att), pool_k, pool_v

    def decode(self, params, cache: parts.SlotCache, tokens, paged: PagedState, mesh=None):
        """One token a slot.  tokens [S]; returns (logits [S, V] float32, the
        cache with this step's K/V written (the full layers' into the pools,
        the sliding layers' into the active slots' rings), counters:
        :attr:`step_counters`)."""
        if mesh is not None:
            raise ValueError("the sliding-window decoder runs on one device")
        active, position = paged.active, paged.lengths
        W = self.sliding_window
        h = params["embed"][tokens].astype(jnp.float32)
        experts = parts.held_experts(params)
        pools_k, pools_v = list(cache.blocks["k"]), list(cache.blocks["v"])
        ring_k, ring_v = cache.slots["k"], cache.slots["v"]
        if self.lead_layers:
            h, pools_k[0], pools_v[0] = self._full_decode(
                params["lead"], h, pools_k[0], pools_v[0], paged)
            h = self._dense(params["lead"], h)
        loads = []
        for kind, p, layer, at in self._plan(params):
            if kind == "swa":
                def body(carry, xs):
                    h, ring_k, ring_v = carry
                    p, layer, ring_layer = xs
                    xn = self._norm(h, p["attn_norm"])
                    q, k, v = self._qkv(
                        p, xn, position, self.sliding_heads, self._rotate_sliding)
                    with jax.named_scope("swa_decode"):
                        row = self._ring_row(position)
                        ring_k = self._ring_write(ring_k, k, ring_layer, row, active)
                        ring_v = self._ring_write(ring_v, v, ring_layer, row, active)
                        att = self._ring_attend(q, ring_k, ring_v, ring_layer, position, active)
                    h = h + self._output(p, xn, att)
                    h, load = self._ffn(p, experts, h, layer, active)
                    return (h, ring_k, ring_v), load

                (h, ring_k, ring_v), load = jax.lax.scan(
                    body, (h, ring_k, ring_v), (p, layer, at))
                loads.append(load)
            else:
                h, pools_k[at], pools_v[at] = self._full_decode(
                    p, h, pools_k[at], pools_v[at], paged)
                h, load = self._ffn(p, experts, h, layer, active)
                loads.append(load[None])
        counters = jnp.concatenate([
            jnp.sum(active, dtype=jnp.int32)[None],
            jnp.sum(jnp.where(active, jnp.minimum(position + 1, W), 0), dtype=jnp.int32)[None],
            parts.held_step_counters(jnp.concatenate(loads, axis=0))])
        cache = parts.SlotCache(
            blocks={"k": tuple(pools_k), "v": tuple(pools_v)},
            slots={"k": ring_k, "v": ring_v})
        return self._head(params, h), cache, counters

    # ---------------------------------------------------- the whole forward
    def logits(self, params, toks):
        """Teacher-forced logits [T, V] of one sequence toks [T] through the
        prefill path (tests)."""
        return self._head(params, self._forward(params, toks, None)[0])


def tiny_config() -> Dict:
    """``laguna``'s published SHAPE at a size the CPU tests run: a leading dense full
    layer and one period of three sliding layers and a full one, two head
    counts (6 sliding, 4 full) over 2 K/V heads of 128 (the kernels' lanes),
    full layers half rotated by a YaRN table, a window of 8, 8 of 16 experts
    held, 3 a token."""
    period = [_SLIDING] * 3 + [_FULL]
    return {
        "model_type": "laguna", "vocab_size": 384, "hidden_size": 256,
        "intermediate_size": 512, "num_hidden_layers": 5, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128, "max_position_embeddings": 1024,
        "attention_bias": False, "rms_norm_eps": 1e-6, "num_experts": 8, "router_experts": 16,
        "held_from": 0, "num_experts_per_tok": 3, "moe_intermediate_size": 128,
        "shared_expert_intermediate_size": 128, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0], "tie_word_embeddings": False,
        "gating": "per-head", "sliding_window": 8,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 64, "beta_slow": 1, "beta_fast": 32,
                "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
        # two periods' worth: a test may run a depth of 9
        "layer_types": [_FULL] + period * 2,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 8,
        "gating_types": ["per_head"] * 9, "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [4] + [6, 6, 6, 4] * 2,
        "moe_router_logit_softcapping": 0,
    }


def tiny_mellum_config() -> Dict:
    """``mellum``'s published SHAPE at a size the CPU tests run: two periods of
    three sliding layers and a full one with nothing in front, every layer
    sparse, ONE head count (4 over 2 K/V heads of 128), whole heads rotated
    (the full layers by a YaRN table), an RMSNorm a head on q and k, no gate,
    no shared expert, no scale on the routed sum, a window of 8, all 8 experts
    held, 2 a token."""
    period = [_SLIDING] * 3 + [_FULL]
    return {
        "model_type": "mellum", "vocab_size": 384, "hidden_size": 256,
        "intermediate_size": 512, "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128, "max_position_embeddings": 1024,
        "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
        "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 128,
        "norm_topk_prob": True, "tie_word_embeddings": False, "sliding_window": 8,
        "max_window_layers": 0, "use_sliding_window": True, "qk_norm": True,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 16,
                "original_max_position_embeddings": 64, "beta_slow": 1, "beta_fast": 32,
                "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
        # three periods' worth: a test may run a depth of 4 or 12
        "layer_types": period * 3, "mlp_layer_types": ["sparse"] * 12,
    }
