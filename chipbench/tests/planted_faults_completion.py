"""Faults planted in the sliding-window model as ``mellum2-12b-a2.5b-instruct``'s
file builds it, and one control that lowers a stated precision, as subclasses
that a configuration's ``"model"`` can name
(``chipbench.tests.planted_faults_completion:<class>``).  The five faults are
what ``mellum_serve_completion``'s ``correct`` has to refuse; the control
(``Fp8Ring``) is the reading the limit's upper side is set from: the file's
``tolerance`` has every reading.  ``NoWindowMask``, ``ClippedRing`` and
``Fp8Ring`` are ``planted_faults_window``'s own classes (they name nothing of
Laguna's shapes); the three below are this file's mechanisms.  The tests run
them through ``run.main`` at a tiny size; ``tools/variant.py --config
model=...`` runs them through ``run.py`` on the chip at the cell's own size."""

import jax
import jax.numpy as jnp

from chipbench.tests.planted_faults_window import (  # noqa: F401 - named by a file's "model"
    ClippedRing, Fp8Ring, NoWindowMask)
from moolib_tpu.models import decoder_parts as parts
from moolib_tpu.models.swa_moe import SlidingGqaMoELM
from moolib_tpu.parallel.moe import dropless_moe


class PlainFullRope(SlidingGqaMoELM):
    """The full layers rotated without the YaRN table: the plain table at the
    group's own base (no pair slowed by ``factor``), cos and sin unscaled."""

    def _rotate_full(self, x, pos):
        r = dict(self.full_rope)
        rotated = int(self.head_dim * r.get("partial_rotary_factor", 1.0))
        table = r["rope_theta"] ** (-jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated)
        return parts.rope_table(x, pos, table)


def _softmax_topk_route_unnormalised(x32, w_router, bias, top_k, scale):
    """``parallel.moe.softmax_topk_route`` WITHOUT ``norm_topk_prob``: the
    chosen experts weighed by their probabilities over all the router's width
    as they are."""
    s = jax.nn.softmax(jnp.dot(
        x32.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    return chosen.astype(jnp.int32), jnp.take_along_axis(s, chosen, axis=-1) * scale


class NoTopkRenorm(SlidingGqaMoELM):
    """The top-8 weights NOT renormalised over the chosen: the routed sum is
    scaled by the chosen experts' share of the softmax, a token at a time."""

    def _ffn(self, p, experts, h, layer, valid):
        y, load = dropless_moe(
            self._norm(h, p["ffn_norm"]), {**p, **experts},
            top_k=self.num_experts_per_tok, scale=self.moe_routed_scaling_factor,
            valid=valid, layer=layer, held_from=self.held_from,
            route=_softmax_topk_route_unnormalised)
        return h + y, load


class NoQkNorm(SlidingGqaMoELM):
    """The q/k norm left out: q and k go to the rotation as the projections
    made them (the learned scales are in the tree and are not read)."""

    def _qkv(self, p, xn, pos, heads, rotate):
        q, k, v = parts.gqa_qkv(xn, p["w_q"], p["w_kv"], heads,
                                self.num_key_value_heads, self.head_dim, self.dtype)
        return rotate(q, pos[:, None]), rotate(k, pos[:, None]), v
