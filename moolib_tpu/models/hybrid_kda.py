"""A decoder built from a published configuration file whose layers are of two
kinds (``model_type`` ``solar_open2``): Kimi delta attention (``ops.kda``),
a linear attention with one fixed-size recurrent state a sequence, and in
one layer of four a softmax attention without positions over grouped K/V
heads with an output gate; every layer followed by dropless sigmoid-routed
experts with a shared expert, of which this chip may hold a share
(``parallel.moe.dropless_moe``'s ``held_from``).

Plain functions over a parameter pytree.  The pattern comes from the file's
``gqa_layers``: a **period** is one gated GQA layer and the KDA layers that
follow it up to the next (three, as published); the KDA layers of a period
are stacked and run under one ``jax.lax.scan``, and the periods are a Python
loop, so that each period's K/V pools are operands of their own and a deeper
cut repeats the period.  The model offers the serving engine both kinds of
cache leaf (``engine/engine.py``):

- :meth:`cache_spec`: the paged pools, block axis first: K and V of each GQA
  layer, ``[num_blocks, block_size, kv_heads, head_dim]`` bfloat16;
- :meth:`state_spec`: what a SLOT owns, slot axis first: the recurrent state
  of every KDA layer ``[slots, kda_layers, heads, d_v, d_k]`` float32 (held
  transposed, as the decode kernel reads it) and the short convolution's tail,
  the last three inputs of q | k | v, ``[slots, kda_layers, 3, 3 H d]``;
- :meth:`prefill` hands back the K/V rows with the state after position
  ``tp - 1`` and the tail there (a bucket's padding has ``beta`` and the
  log-decay zeroed, so it moves neither); :meth:`write_rows` scatters the
  former by block and :meth:`write_state` overwrites the slot's row with the
  latter, whatever the slot's last holder left there;
- :meth:`decode`: one token a slot; the state and the tail of ACTIVE slots
  advance in place, the others' are left as they are.

Precision: weights and matmul inputs in ``dtype`` (bfloat16), products
accumulated in float32; the residual stream, RMSNorm, l2norm, the router,
decay, softmax and logits in float32; the recurrent state and the
convolution tail float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops import kda
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import PagedState
from ..parallel.moe import dropless_moe
from . import decoder_parts as parts

_CONV = 4  # the short convolution's taps; the tail is the last _CONV - 1 inputs


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@dataclasses.dataclass(frozen=True)
class HybridKdaMoELM:
    """Sizes under their published names (``from_config`` reads them).
    ``n_routed_experts`` counts the experts HELD here, ids ``held_from ..``;
    ``router_experts`` (a key of the file under that name; without it the
    held count) is the router's width, the published count."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int
    router_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float = 1.0
    kda_heads: int = 64
    kda_head_dim: int = 128
    gate_rank: int = 128
    period: int = 4
    held_from: int = 0
    rms_norm_eps: float = 1e-5
    max_len: int = 8192  # positions the engine may ask for
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_config(cls, config, **overrides) -> "HybridKdaMoELM":
        """Build from a configuration (a dict, or the path of its JSON file)
        that holds the published keys; ``overrides`` replace single sizes (a
        test's depth, the engine's ``max_len``).  A key the model cannot
        honour is refused by name."""
        config, dtype = parts.load_config(config, overrides)
        lin = config["linear_attn_config"]
        refused = {
            "use_rope": config.get("use_rope", False) is not False,
            "use_gqa_gate": config.get("use_gqa_gate", True) is not True,
            "kda_use_full_proj": config.get("kda_use_full_proj", False) is not False,
            "kda_allow_neg_eigval": config.get("kda_allow_neg_eigval", True) is not True,
            "first_k_dense_replace": config.get("first_k_dense_replace", 0) != 0,
            "n_shared_experts": config.get("n_shared_experts", 1) != 1,
            "norm_topk_prob": config.get("norm_topk_prob", True) is not True,
            "n_group": config.get("n_group", 1) != 1,
            "linear_attn_config.short_conv_kernel_size": lin["short_conv_kernel_size"] != _CONV,
            "linear_attn_config.num_kv_heads": lin.get("num_kv_heads") is not None,
        }
        depth = config["num_hidden_layers"]
        gqa = [l for l in config["gqa_layers"] if l < depth]
        period = config.get("gqa_interval", 3) + 1
        refused["gqa_layers"] = gqa != list(range(0, depth, period))
        refused["num_hidden_layers"] = depth % period != 0  # whole periods only
        parts.refuse(cls.__name__, refused)
        return cls(
            vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
            num_hidden_layers=depth, num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
            moe_intermediate_size=config["moe_intermediate_size"],
            n_routed_experts=config["n_routed_experts"],
            router_experts=config.get("router_experts", config["n_routed_experts"]),
            num_experts_per_tok=config["num_experts_per_tok"],
            routed_scaling_factor=config.get("routed_scaling_factor", 1.0),
            kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
            gate_rank=config.get("kda_gate_rank", lin["head_dim"]), period=period,
            held_from=config.get("held_from", 0),
            rms_norm_eps=config.get("rms_norm_eps", 1e-5),
            max_len=config.get("max_len", min(config.get("max_position_embeddings", 8192), 8192)),
            dtype=dtype,
        )

    # ------------------------------------------------------------ geometry
    @property
    def periods(self) -> int:
        return self.num_hidden_layers // self.period

    @property
    def kda_layers(self) -> int:
        return self.periods * (self.period - 1)

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def step_counters(self) -> int:
        """int32 counters a decode step hands back: slots holding live state,
        then by layer the held (token, expert) pairs and the held experts
        touched."""
        return 1 + 2 * self.num_hidden_layers

    @property
    def prefill_counters(self) -> int:
        """The fullest held expert's tokens, by layer."""
        return self.num_hidden_layers

    def observe_step(self, counters) -> None:
        """A decode step's counters, back on the host (the engine fetched
        them with the step's packet)."""
        live = int(counters[0])
        parts.observe_state_live(live)
        parts.observe_held_step(counters[1:], live, self.num_experts_per_tok)

    def observe_prefill(self, counters, prompt_len: int) -> None:
        parts.observe_held_prefill(
            counters, prompt_len, self.num_experts_per_tok, self.router_experts)

    def cache_spec(self, num_blocks: int, block_size: int):
        pool = jax.ShapeDtypeStruct(
            (num_blocks, block_size, self.num_key_value_heads, self.head_dim), self.dtype)
        return {"k": (pool,) * self.periods, "v": (pool,) * self.periods}

    def state_spec(self, slots: int):
        return {
            "kda": jax.ShapeDtypeStruct(
                (slots, self.kda_layers, self.kda_heads, self.kda_head_dim,
                 self.kda_head_dim), jnp.float32),
            "conv": jax.ShapeDtypeStruct(
                (slots, self.kda_layers, _CONV - 1, 3 * self.kda_width), jnp.float32),
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
        fan_in ** -0.5 (embedding 1.0), norms 1, the selection bias that
        balances the experts' load (below), and the decay's ``a_log`` and ``dt_bias`` drawn as the family's
        initialiser draws them (log U(1, 16); the inverse softplus of a step
        log-uniform in 0.001 .. 0.1).  Jit it: the weights are made on the
        device."""
        D, F = self.hidden_size, self.moe_intermediate_size
        H, hd, Hk = self.num_attention_heads, self.head_dim, self.num_key_value_heads
        W, r, E = self.kda_width, self.gate_rank, self.router_experts
        P, K = self.periods, self.period - 1
        keys, w = parts.weight_drawer(key, 64, self.dtype)

        def ffn(lead):
            return {
                "ffn_norm": jnp.ones(lead + (D,), jnp.float32),
                "router": w(lead + (D, E), D, jnp.float32),
                "router_bias": jnp.zeros(lead + (E,), jnp.float32),  # balanced below
                "shared_gu": w(lead + (D, 2 * F), D),
                "shared_down": w(lead + (F, D), F),
            }

        gqa = {
            "attn_norm": jnp.ones((P, D), jnp.float32),
            "w_q": w((P, D, H * hd), D),
            "w_kv": w((P, D, 2 * Hk * hd), D),
            "w_gate": w((P, D, H * hd), D),
            "w_o": w((P, H * hd, D), H * hd),
            **ffn((P,)),
        }
        lead = (P, K)
        dt_bias = parts.step_bias(next(keys), lead + (W,))  # drawn first: the order is the tree's
        kda_p = {
            "attn_norm": jnp.ones(lead + (D,), jnp.float32),
            "w_qkv": w(lead + (D, 3 * W), D),
            "conv": w(lead + (_CONV, 3 * W), _CONV, jnp.float32),
            "w_f1": w(lead + (D, r), D),
            "w_f2": w(lead + (r, W), r),
            "dt_bias": dt_bias,
            "a_log": jnp.log(jax.random.uniform(
                next(keys), lead + (self.kda_heads,), jnp.float32, 1.0, 16.0)),
            "w_beta": w(lead + (D, self.kda_heads), D),
            "w_g1": w(lead + (D, r), D),
            "w_g2": w(lead + (r, W), r),
            "o_norm": jnp.ones(lead + (self.kda_head_dim,), jnp.float32),
            "w_o": w(lead + (W, D), W),
            **ffn(lead),
        }
        G = self.n_routed_experts
        params = {
            "embed": w((self.vocab_size, D), 1.0),
            "gqa": gqa,
            "kda": kda_p,
            "experts_gu": w((self.num_hidden_layers, G, D, 2 * F), D),
            "experts_down": w((self.num_hidden_layers, G, F, D), F),
            "final_norm": jnp.ones((D,), jnp.float32),
            "head": w((D, self.vocab_size), D),
        }
        # The selection bias, as the load-balancing rule that trains it leaves
        # it: every expert of the router equally likely to be chosen, on a
        # sample of tokens from the key.  Random scores alone load the experts unevenly (a
        # linear attention's output is much the same from token to token, and
        # its projection on a router's column shifts that expert's score for
        # all of them), and a chip's share of the load then follows the seed.
        n = min(4096, -(-100 * E // self.num_experts_per_tok // 128) * 128)
        sample = jax.random.randint(next(keys), (n,), 0, self.vocab_size)
        bias = self._balanced_bias(params, sample).reshape(P, self.period, E)
        params["gqa"]["router_bias"], params["kda"]["router_bias"] = bias[:, 0], bias[:, 1:]
        return params

    def _balanced_bias(self, params, toks):
        """:meth:`init` alone: the selection bias of every layer [L, E] under
        which the tokens ``toks`` [T] (T a multiple of the chunk) load the
        router's experts evenly.  The layers one after another, each expert
        layer run with the bias it was just given, since the next layer's
        scores follow from its output."""
        T = toks.shape[0]
        h = params["embed"][toks].astype(jnp.float32)
        experts = parts.held_experts(params)
        tail = jax.scipy.stats.norm.ppf(1.0 - self.num_experts_per_tok / self.router_experts)
        biases = []
        for layer in range(self.num_hidden_layers):
            period, k = divmod(layer, self.period)
            if k == 0:
                p = jax.tree.map(lambda x: x[period], params["gqa"])
                h = self._gqa_prefill(p, h)[0]
            else:
                p = jax.tree.map(lambda x: x[period, k - 1], params["kda"])
                h = self._kda_prefill(p, h, T)[0]
            # An expert's score before the sigmoid is a sum over the hidden
            # size, near enough normal over tokens: its (1 - k/E) quantile from
            # the mean and deviation of ALL the sample, not from the few
            # scores that lie above it.
            z = jnp.dot(self._norm(h, p["ffn_norm"]), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
            bias = -jax.nn.sigmoid(jnp.mean(z, axis=0) + tail * jnp.std(z, axis=0))
            biases.append(bias - jnp.mean(bias))
            if layer + 1 < self.num_hidden_layers:
                h = self._ffn({**p, "router_bias": biases[-1]}, experts, h, layer, None)[0]
        return jnp.stack(biases)

    # ------------------------------------------------------------- pieces
    def _norm(self, x, scale):
        return parts.rms_norm(x, scale, self.rms_norm_eps)

    def _dot(self, x, w):
        return parts.dot(x, w, self.dtype)

    def _head(self, params, h):
        return parts.head_logits(
            h, params["final_norm"], params["head"], self.rms_norm_eps, self.dtype)

    def _ffn(self, p, experts, h, layer, valid):
        """The expert layer every layer ends in; ``layer`` indexes the
        experts' stacked matrices.  Returns (h + y, tokens a held expert)."""
        y, load = dropless_moe(
            self._norm(h, p["ffn_norm"]), {**p, **experts},
            top_k=self.num_experts_per_tok, scale=self.routed_scaling_factor,
            valid=valid, layer=layer, held_from=self.held_from)
        return h + y, load

    def _kda_inputs(self, p, xn, qkv):
        """What the delta rule takes, from normed inputs xn [T, D] and the
        convolution's output qkv [T, 3 W] (before its silu): q, k, v, g [T, H,
        d] and beta [T, H], float32."""
        T, H, d = xn.shape[0], self.kda_heads, self.kda_head_dim
        q, k, v = (x.reshape(T, H, d) for x in jnp.split(jax.nn.silu(qkv), 3, axis=-1))
        q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
        f = self._dot(self._dot(xn, p["w_f1"]), p["w_f2"]) + p["dt_bias"]
        g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f).reshape(T, H, d)
        beta = 2.0 * jax.nn.sigmoid(self._dot(xn, p["w_beta"]))
        return q, k, v, g, beta

    def _kda_output(self, p, xn, o):
        """o [T, H, d] -> [T, D]: the head-wise RMSNorm, the low-rank output
        gate and the output projection."""
        T = xn.shape[0]
        gate = jax.nn.sigmoid(self._dot(self._dot(xn, p["w_g1"]), p["w_g2"]))
        o = self._norm(o, p["o_norm"]).reshape(T, -1) * gate
        return self._dot(o, p["w_o"])

    def _gqa_qkv(self, p, xn):
        return parts.gqa_qkv(xn, p["w_q"], p["w_kv"], self.num_attention_heads,
                             self.num_key_value_heads, self.head_dim, self.dtype)

    def _gqa_output(self, p, xn, att):
        gate = jax.nn.sigmoid(self._dot(xn, p["w_gate"]))
        return self._dot(att.reshape(xn.shape[0], -1) * gate, p["w_o"])

    # ------------------------------------------------------------- prefill
    def _gqa_prefill(self, p, h):
        """A gated GQA layer's mixer over a whole prompt h [T, D]: (h + y, K
        and V [T, Hk, hd] in the pools' dtype)."""
        xn = self._norm(h, p["attn_norm"])
        q, k, v = self._gqa_qkv(p, xn)
        k, v = k.astype(self.dtype), v.astype(self.dtype)
        group = self.num_attention_heads // self.num_key_value_heads
        att = flash_attention(
            q[None].astype(self.dtype), jnp.repeat(k, group, axis=1)[None],
            jnp.repeat(v, group, axis=1)[None], causal=True)[0]
        return h + self._gqa_output(p, xn, att), k, v

    def _kda_prefill(self, p, h, last):
        """A KDA layer's mixer over a whole prompt h [T, D] (T a multiple of
        the chunk) whose first ``last`` positions are real: (h + y, the state
        [H, d_v, d_k] and the convolution's tail [3, 3 W] after position
        last - 1).  The kernel holds the state still over the padding and
        runs no chunk that lies wholly in it."""
        T = h.shape[0]
        xn = self._norm(h, p["attn_norm"])
        raw = jnp.pad(self._dot(xn, p["w_qkv"]), ((_CONV - 1, 0), (0, 0)))
        qkv = sum(raw[i:i + T] * p["conv"][i] for i in range(_CONV))
        tail = jax.lax.dynamic_slice_in_dim(raw, last, _CONV - 1, axis=0)
        q, k, v, g, beta = self._kda_inputs(p, xn, qkv)
        o, state = kda.chunked_kda(q, k, v, g, beta, length=last)
        return h + self._kda_output(p, xn, o), state.transpose(0, 2, 1), tail

    def _forward(self, params, toks, tp):
        """The whole prompt toks [T] of which the first ``tp`` are real (None:
        all).  Returns (h [T, D], K and V by period [T, Hk, hd], the KDA
        layers' final states [kda_layers, H, d_v, d_k] and convolution tails
        [kda_layers, 3, 3 W] after position tp - 1, tokens a held expert by
        layer [L, G])."""
        T = toks.shape[0]
        pad = -T % kda.CHUNK
        pos = jnp.arange(T + pad)
        valid = None if tp is None and not pad else pos < (T if tp is None else tp)
        last = T if tp is None else tp
        h = params["embed"][jnp.pad(toks, (0, pad))].astype(jnp.float32)
        experts = parts.held_experts(params)
        ks, vs, states, tails, loads = [], [], [], [], []
        for period in range(self.periods):
            p = jax.tree.map(lambda x: x[period], params["gqa"])
            h, k, v = self._gqa_prefill(p, h)
            h, load = self._ffn(p, experts, h, period * self.period, valid)
            ks.append(k[:T]), vs.append(v[:T]), loads.append(load[None])

            def body(h, xs):
                p, layer = xs
                h, state, tail = self._kda_prefill(p, h, last)
                h, load = self._ffn(p, experts, h, layer, valid)
                return h, (state, tail, load)

            first = period * self.period + 1
            h, (state, tail, load) = jax.lax.scan(
                body, h, (jax.tree.map(lambda x: x[period], params["kda"]),
                          first + jnp.arange(self.period - 1, dtype=jnp.int32)))
            states.append(state), tails.append(tail), loads.append(load)
        cat = lambda xs: jnp.concatenate(xs, axis=0)
        return h[:T], ks, vs, cat(states), cat(tails), cat(loads)

    def prefill(self, params, toks, tp, block_size: int):
        """toks [1, Lb] (the prompt padded to its bucket), tp the true
        length.  Returns (rows for :meth:`write_rows` and :meth:`write_state`,
        logits [V] float32 at position tp - 1, counters [layers] int32: the
        fullest held expert's tokens by layer, pad tokens not counted)."""
        h, ks, vs, state, tail, load = self._forward(params, toks[0], tp)

        def blocks(xs):  # [Lb, Hk, hd] -> [nbw, block_size, Hk, hd], by period
            return tuple(parts.rows_to_blocks(x, block_size, axis=0) for x in xs)

        rows = {"blocks": {"k": blocks(ks), "v": blocks(vs)},
                "slots": {"kda": state, "conv": tail}}
        logits = self._head(params, jnp.take(h, tp - 1, axis=0))
        return rows, logits, jnp.max(load, axis=-1).astype(jnp.int32)

    # -------------------------------------------------------------- decode
    def decode(self, params, cache: parts.SlotCache, tokens, paged: PagedState, mesh=None):
        """One token a slot.  tokens [S]; returns (logits [S, V] float32, the
        cache with this step's K/V written and the active slots' state and
        tail advanced, counters: :attr:`step_counters`)."""
        if mesh is not None:
            raise ValueError("the hybrid decoder runs on one device")
        S = tokens.shape[0]
        active = paged.active
        h = params["embed"][tokens].astype(jnp.float32)
        experts = parts.held_experts(params)
        pools_k, pools_v = list(cache.blocks["k"]), list(cache.blocks["v"])
        state, conv = cache.slots["kda"], cache.slots["conv"]
        loads = []
        for period in range(self.periods):
            p = jax.tree.map(lambda x: x[period], params["gqa"])
            xn = self._norm(h, p["attn_norm"])
            q, k, v = self._gqa_qkv(p, xn)
            att, pools_k[period], pools_v[period] = parts.paged_gqa_decode(
                pools_k[period], pools_v[period], q, k, v, paged)
            h = h + self._gqa_output(p, xn, att)
            h, load = self._ffn(p, experts, h, period * self.period, active)
            loads.append(load[None])

            def body(carry, xs):
                h, state, conv = carry
                p, layer, slot_layer = xs
                xn = self._norm(h, p["attn_norm"])
                tail = jax.lax.dynamic_index_in_dim(conv, slot_layer, 1, keepdims=False)
                window = jnp.concatenate(
                    [tail, self._dot(xn, p["w_qkv"])[:, None]], axis=1)  # [S, 4, 3 W]
                qkv = jnp.sum(window * p["conv"], axis=1)
                tail = jnp.where(active[:, None, None], window[:, 1:], tail)
                conv = jax.lax.dynamic_update_index_in_dim(conv, tail, slot_layer, 1)
                q, k, v, g, beta = self._kda_inputs(p, xn, qkv)
                o, state = kda.kda_decode(q, k, v, g, beta, state, slot_layer, active)
                h = h + self._kda_output(p, xn, o)
                h, load = self._ffn(p, experts, h, layer, active)
                return (h, state, conv), load

            K = self.period - 1
            steps = jnp.arange(K, dtype=jnp.int32)
            (h, state, conv), load = jax.lax.scan(
                body, (h, state, conv),
                (jax.tree.map(lambda x: x[period], params["kda"]),
                 period * self.period + 1 + steps, period * K + steps))
            loads.append(load)
        load = jnp.concatenate(loads, axis=0)  # [L, G]
        counters = jnp.concatenate([
            jnp.sum(active, dtype=jnp.int32)[None], parts.held_step_counters(load)])
        cache = parts.SlotCache(
            blocks={"k": tuple(pools_k), "v": tuple(pools_v)},
            slots={"kda": state, "conv": conv})
        return self._head(params, h), cache, counters

    # ---------------------------------------------------- the whole forward
    def logits(self, params, toks):
        """Teacher-forced logits [T, V] of one sequence toks [T] through the
        prefill path (tests)."""
        return self._head(params, self._forward(params, toks, None)[0])


def tiny_config() -> Dict:
    """The published ratios at a size the CPU tests run: two periods of one
    gated GQA layer and three KDA layers, heads of 128 (the kernels' lanes),
    8 of 16 experts held."""
    return {
        "model_type": "solar_open2", "vocab_size": 384, "hidden_size": 256,
        "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 128, "moe_intermediate_size": 128, "n_routed_experts": 8,
        "router_experts": 16, "held_from": 0,
        "num_experts_per_tok": 4, "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "first_k_dense_replace": 0, "use_rope": False,
        "gqa_interval": 3, "gqa_layers": [0, 4, 8, 12], "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "kda_gate_rank": 32,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 2, "num_kv_heads": None},
        "rms_norm_eps": 1e-5, "max_position_embeddings": 1024,
    }
