"""Faults planted in the sliding-window model, and the controls that lower ONE
stated precision each, as subclasses that a configuration's ``"model"`` can
name (``chipbench.tests.planted_faults_window:<class>``).  The four faults are
what the cell's ``correct`` has to refuse; the controls (``Fp8Ring``,
``Fp8Pool``, ``Bf16Router``, ``Bf16Residual``) are read to find which lowered
precision the limit tells apart from the stated one: the file's ``tolerance``
has every reading.  The tests run them through ``run.main`` at a tiny size;
``tools/variant.py --config model=...`` runs them through ``run.py`` on the
chip at the cell's own size."""

import jax
import jax.numpy as jnp

from moolib_tpu.models import decoder_parts as parts
from moolib_tpu.models.swa_moe import SlidingGqaMoELM
from moolib_tpu.parallel.moe import dropless_moe


class NoWindowMask(SlidingGqaMoELM):
    """The sliding layers' prefill attends over the whole prompt: no window."""

    def _swa_prefill(self, p, h, pos):
        return self._attend_prompt(p, h, pos, self.sliding_heads, self._rotate_sliding, None)


class NoRingWrite(SlidingGqaMoELM):
    """A join leaves the slot's rings as the slot's last holder left them
    (zeros, in a fresh engine)."""

    def write_state(self, cache, rows, slot):
        return cache


class ClippedRing(SlidingGqaMoELM):
    """The ring written at the position clipped to its last row, not wrapped:
    past the window every token lands on row ``window - 1``."""

    def _ring_row(self, position):
        return jnp.minimum(position, self.sliding_window - 1)


class PlainFullRope(SlidingGqaMoELM):
    """The full layers rotated at base 10,000 without YaRN: the sliding
    layers' table over the full layers' rotated width, cos and sin unscaled."""

    def _rotate_full(self, x, pos):
        rotated = int(self.head_dim * dict(self.full_rope).get("partial_rotary_factor", 1.0))
        table = 10000.0 ** (-jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated)
        return parts.rope_table(x, pos, table)


# The precision next below a stated one (``reduce_precision``: inside a jitted
# step XLA elides a pair of converts on the chip).
def _fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


class Fp8Ring(SlidingGqaMoELM):
    """The precision next below the stated one for the sliding layers' cache:
    the rings rounded to float8 e4m3 at the join and after every decode step."""

    def _rounded(self, cache):
        return cache._replace(slots=jax.tree.map(_fp8, cache.slots))

    def write_state(self, cache, rows, slot):
        return self._rounded(super().write_state(cache, rows, slot))

    def decode(self, params, cache, tokens, paged, mesh=None):
        logits, cache, counters = super().decode(params, cache, tokens, paged, mesh)
        return logits, self._rounded(cache), counters


class Fp8Pool(SlidingGqaMoELM):
    """The precision next below the stated one for the full layers' cache: the
    K and V rows rounded to float8 e4m3 as they are written into the pools, by
    the join and by every decode step."""

    def write_rows(self, cache, rows, block_ids):
        return super().write_rows(
            cache, {**rows, "blocks": jax.tree.map(_fp8, rows["blocks"])}, block_ids)

    def _full_decode(self, p, h, pool_k, pool_v, paged):
        xn = self._norm(h, p["attn_norm"])
        q, k, v = self._qkv(p, xn, paged.lengths, self.full_heads, self._rotate_full)
        att, pool_k, pool_v = parts.paged_gqa_decode(pool_k, pool_v, q, _fp8(k), _fp8(v), paged)
        return h + self._output(p, xn, att), pool_k, pool_v


def _bf16_softmax_topk_route(x32, w_router, bias, top_k, scale):
    """``parallel.moe.softmax_topk_route`` with its product in bfloat16."""
    s = jax.nn.softmax(jnp.dot(
        x32.astype(jnp.bfloat16), w_router.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32), axis=-1)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), picked / jnp.sum(picked, axis=-1, keepdims=True) * scale


class Bf16Router(SlidingGqaMoELM):
    """The router, stated as float32 at the highest matmul precision, with
    bfloat16 inputs to its product."""

    def _ffn(self, p, experts, h, layer, valid):
        y, load = dropless_moe(
            self._norm(h, p["ffn_norm"]), {**p, **experts},
            top_k=self.num_experts_per_tok, scale=self.moe_routed_scaling_factor,
            valid=valid, layer=layer, held_from=self.held_from, route=_bf16_softmax_topk_route)
        return h + y, load


class Bf16Residual(SlidingGqaMoELM):
    """The residual stream, stated as float32, held in bfloat16: rounded where
    a feed-forward reads it (every mixer's sum) and where it writes it."""

    def _dense(self, p, h):
        return _bf16(super()._dense(p, _bf16(h)))

    def _ffn(self, p, experts, h, layer, valid):
        h, load = super()._ffn(p, experts, _bf16(h), layer, valid)
        return _bf16(h), load
