"""The config-built sliding-window decoder (``models/swa_moe.py``: window layers
on a ring a slot beside full layers on the paged pool, softmax-routed experts
of which a share or all are held) built from BOTH published layer plans
(``laguna``: a leading dense full layer, two head counts, a gate a head, a
half-rotated YaRN table, a shared expert, a share of the experts; ``mellum``:
nothing in front, one head count, a q/k norm, no gate, no shared expert, every
expert) and what it forced in the ops (``flash_attention(window=)``, the ring
read through ``paged_attention``, ``softmax_topk_route``), each against its own
plain reference (``chipbench/reference/laguna.py``, ``reference/mellum.py``),
on the CPU at a tiny size of the published SHAPE, seeded random weights, logits
not tokens.

Tolerances.  The model runs in float32 here (``dtype=float32``), its kernels
in Pallas interpret mode, so what separates program and reference is the order
of float32 sums: logits of magnitude ~1 agree to ``TOL`` = 2e-4 (measured: at
most 1e-5 over these seeds).  A ring written at the wrong row, a join that
leaves the last holder's ring, or a window that is not masked moves a logit by
tenths.
"""

import json
import os
import re
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import laguna as ref  # noqa: E402
from chipbench.reference import mellum as ref_mellum  # noqa: E402
from moolib_tpu import telemetry  # noqa: E402
from moolib_tpu.engine import ContinuousBatchingEngine  # noqa: E402
from moolib_tpu.models import decoder_parts as parts  # noqa: E402
from moolib_tpu.models.decoder_parts import SlotCache  # noqa: E402
from moolib_tpu.models.swa_moe import (  # noqa: E402
    SlidingGqaMoELM, tiny_config, tiny_mellum_config)
from moolib_tpu.ops.flash_attention import (  # noqa: E402
    _blockwise_attention, flash_attention, window_key_blocks)
from moolib_tpu.ops.paged_attention import PagedState, gathered_decode_attention  # noqa: E402
from moolib_tpu.parallel import moe as moe_mod  # noqa: E402

TOL = 2e-4
CFG = tiny_config()  # full + dense, then (sliding, sliding, sliding, full); window 8
MELLUM = tiny_mellum_config()  # (sliding, sliding, sliding, full) twice, nothing in front
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The two tiny files, each with its reference, the published file it is the
# shape of, and the depth that is two whole periods.
FILES = {
    "laguna": (CFG, ref, "laguna-s-2.1.json", 9),
    "mellum": (MELLUM, ref_mellum, "mellum2-12b-a2.5b-instruct.json", 8),
}


class Family:
    """One tiny file: the model built from it in float32, seeded weights, its
    reference."""

    def __init__(self, name):
        self.name = name
        self.cfg, self.ref, self.published, self.two_periods = FILES[name]
        self.model = SlidingGqaMoELM.from_config(self.cfg, dtype=jnp.float32, max_len=256)
        self.params = jax.jit(self.model.init)(jax.random.key(7))

    def want(self, params, toks, cfg=None):
        return _highest(self.ref.logits, params, toks, cfg or self.cfg)


@pytest.fixture(scope="module", params=sorted(FILES))
def family(request):
    return Family(request.param)


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, CFG["vocab_size"], n), jnp.int32)


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _published(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name)) as f:
        return json.load(f)


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# ------------------------------------------------------------------ the file
def test_builds_from_the_published_keys_and_the_plan(family):
    """The plan is the FILE's: the leading layers from ``mlp_layer_types``, the
    runs of sliding layers between full ones from ``layer_types``; the gate,
    the shared expert, the second head count, the q/k norm where the file has
    them.  The published file, cut, builds the cell's geometry, and its bytes
    by ``eval_shape`` are those its ``reduced`` states."""
    m = family.model
    if family.name == "laguna":
        assert (m.lead_layers, m.runs, m.sliding_layers, m.full_layers) == (1, (3, 0), 3, 2)
        assert (m.full_heads, m.sliding_heads, m.sliding_window) == (4, 6, 8)
        assert (m.num_experts, m.router_experts, m.expert_layers) == (8, 16, 4)
        assert (m.gated, m.qk_norm, m.shared_expert_intermediate_size) == (True, False, 128)
        cut = SlidingGqaMoELM.from_config(_published(family.published), max_len=6144)
        assert (cut.lead_layers, cut.runs, cut.sliding_layers, cut.full_layers) == (
            1, (3, 3, 0), 6, 3)
        assert (cut.full_heads, cut.sliding_heads, cut.sliding_window, cut.ring_block) == (
            48, 72, 512, 128)
        ring = cut.state_spec(64)["k"]
        assert ring.shape == (64, 6, 512, 8, 128) and ring.dtype == jnp.bfloat16
        pools = cut.cache_spec(3073, 128)
        assert len(pools["k"]) == 3 and pools["v"][0].shape == (3073, 128, 8, 128)
        # the issue's count of the cut, by eval_shape: 3.20 B parameters, 6.40 GB
        assert 6.40e9 < _nbytes(jax.eval_shape(cut.init, jax.random.key(0))) < 6.42e9
        return
    assert (m.lead_layers, m.runs, m.sliding_layers, m.full_layers) == (0, (3, 3, 0), 6, 2)
    assert (m.full_heads, m.sliding_heads, m.sliding_window) == (4, 4, 8)
    assert (m.num_experts, m.router_experts, m.expert_layers) == (8, 8, 8)
    assert (m.gated, m.qk_norm, m.shared_expert_intermediate_size) == (False, True, 0)
    assert m.moe_routed_scaling_factor == 1.0
    shapes = jax.eval_shape(m.init, jax.random.key(0))
    assert "lead" not in shapes and len(shapes["swa"]) == len(shapes["full"]) == 2
    assert {"q_norm", "k_norm"} <= set(shapes["full"][0]) and not {
        "w_gate", "shared_gu", "dense_gu"} & set(shapes["full"][0])
    file = _published(family.published)
    cut = SlidingGqaMoELM.from_config(file, max_len=4224, **file["uses"]["serve"])
    assert (cut.lead_layers, cut.runs, cut.sliding_layers, cut.full_layers) == (0, (3, 3, 0), 6, 2)
    assert (cut.full_heads, cut.sliding_heads, cut.num_key_value_heads) == (32, 32, 4)
    assert (cut.sliding_window, cut.ring_block, cut.vocab_size) == (1024, 128, 98304)
    # every expert of a layer and the whole router: nothing of a layer is elsewhere
    assert (cut.num_experts, cut.router_experts, cut.held_from, cut.num_experts_per_tok) == (
        64, 64, 0, 8)
    ring = cut.state_spec(32)["k"]
    assert ring.shape == (32, 6, 1024, 4, 128) and ring.dtype == jnp.bfloat16
    pools = cut.cache_spec(1 + 32 * 33, 128)
    assert len(pools["k"]) == 2 and pools["v"][0].shape == (1057, 128, 4, 128)
    # the bytes its ``reduced`` states, by eval_shape: 3.79 B parameters, 7.59 GB
    shapes = jax.eval_shape(cut.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3794969344
    assert _nbytes(shapes) == 7592381440
    assert "3,794,969,344" in file["reduced"]["num_hidden_layers"]
    assert "7,592,381,440" in file["reduced"]["num_hidden_layers"]
    layer = _nbytes(shapes["full"][0]) + _nbytes(shapes["experts_gu"]) // 8 + _nbytes(
        shapes["experts_down"]) // 8
    assert 0.835e9 < layer < 0.837e9  # the issue's 0.836 GB a layer
    assert _nbytes(cut.cache_spec(1 + 32 * 33, 128)) == 554172416  # pools, 0.55 GB
    assert _nbytes(cut.state_spec(32)) == 402653184  # rings, 0.40 GB


_S, _F = "sliding_attention", "full_attention"


@pytest.mark.parametrize("name,key,value", [
    ("laguna", "gating", "per-channel"),
    ("laguna", "moe_router_logit_softcapping", 30.0),
    ("laguna", "moe_apply_router_weight_on_input", True),
    ("laguna", "num_hidden_layers", 7),  # not whole periods behind the leading layer
    ("laguna", "layer_types", [_F] + [_S] * 8),  # a period without a full layer
    ("laguna", "mlp_only_layers", [0, 1]),
    ("laguna", "norm_topk_prob", False),
    ("laguna", "mlp_layer_types", ["dense", "sparse", "dense"] + ["sparse"] * 6),
    ("laguna", "num_attention_heads_per_layer", [4] + [6, 6, 5, 4] * 2),
    ("mellum", "layer_types", [_S] * 12),  # a period without a full layer
    ("mellum", "layer_types", [_S, "linear_attention", _S, _F] * 3),  # a kind it has not
    ("mellum", "mlp_layer_types", ["sparse", "dense"] + ["sparse"] * 10),  # dense past the lead
    ("mellum", "mlp_layer_types", ["dense", "dense"] + ["sparse"] * 10),  # two leading layers
    ("mellum", "num_hidden_layers", 6),  # a depth that is not whole periods
    ("mellum", "num_hidden_layers", 0),
    ("mellum", "gating", "per-channel"),
    ("mellum", "attention_bias", True),
    ("mellum", "hidden_act", "gelu"),
    ("mellum", "tie_word_embeddings", True),
    ("mellum", "sliding_window", 200),  # a ring that is not whole blocks of 128
])
def test_a_key_the_model_cannot_honour_is_refused_by_name(name, key, value):
    with pytest.raises(ValueError, match=key):
        SlidingGqaMoELM.from_config({**FILES[name][0], key: value})


@pytest.mark.parametrize("kinds,depth,lead,runs", [
    ([_S, _S, _S, _F] * 2, 8, 0, (3, 3, 0)),   # mellum's
    ([_F, _S, _S] * 2, 6, 0, (0, 2, 2)),       # the full layer FIRST in its period
    ([_S, _F, _S, _S] * 3, 8, 0, (1, 3, 2)),   # in the middle: a run wraps the period's edge
    ([_S, _F] * 4, 4, 0, (1, 1, 0)),           # a period of two
])
def test_the_plan_is_read_wherever_in_the_period_the_full_layer_stands(kinds, depth, lead, runs):
    cfg = {**MELLUM, "layer_types": kinds, "mlp_layer_types": ["sparse"] * len(kinds),
           "num_hidden_layers": depth}
    m = SlidingGqaMoELM.from_config(cfg, dtype=jnp.float32, max_len=64)
    assert (m.lead_layers, m.runs) == (lead, runs)
    assert (m.sliding_layers, m.full_layers) == (sum(runs), len(runs) - 1)
    p = jax.jit(m.init)(jax.random.key(1))
    assert [x["w_q"].shape[0] for x in p["swa"]] == [r for r in runs if r]
    toks = _tokens(21, seed=depth)
    got = _highest(jax.jit(m.logits), p, toks)
    np.testing.assert_allclose(got, _highest(ref_mellum.logits, p, toks, cfg), atol=TOL)


def test_embed_init_scale_is_the_initialisers_alone(family):
    """The file's own key ``embed_init_scale`` multiplies the embedding's
    draws and nothing else: every other leaf is the same draw, and the forward
    reads the weights it is handed."""
    m = SlidingGqaMoELM.from_config({**family.cfg, "embed_init_scale": 3.0},
                                    dtype=jnp.float32, max_len=256)
    assert (m.embed_scale, family.model.embed_scale) == (3.0, 1.0)
    p = jax.jit(m.init)(jax.random.key(7))
    np.testing.assert_allclose(p["embed"], 3.0 * family.params["embed"], rtol=1e-6)
    rest = lambda tree: jax.tree.leaves({k: v for k, v in tree.items() if k != "embed"})
    assert all(np.array_equal(a, b) for a, b in zip(rest(p), rest(family.params)))
    toks = _tokens(21, seed=3)
    np.testing.assert_allclose(_highest(jax.jit(m.logits), p, toks), family.want(p, toks), atol=TOL)


# -------------------------------------------------- the window in the kernel
@pytest.mark.parametrize("T,window,block_q,block_k", [
    (512, 128, 128, 128),   # the window is one block
    (512, 200, 128, 256),   # it divides neither block
    (1024, 512, 512, 512),  # the cell's window at the kernel's own blocks
    (384, 130, 128, 128),   # one past a block's edge
    (512, 8, None, None),   # a window far inside one block, blocks chosen
])
def test_flash_window_equals_the_oracle_and_a_dense_mask(T, window, block_q, block_k):
    q, k, v = (jax.random.normal(kk, (1, T, 2, 128), jnp.float32)
               for kk in jax.random.split(jax.random.key(T + window), 3))
    got = flash_attention(q, k, v, window=window, block_q=block_q, block_k=block_k)
    oracle = _blockwise_attention(q, k, v, True, 128, 128, window=window)
    pos = np.arange(T)
    seen = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < window)
    scores = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) / np.sqrt(128)
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    dense = np.einsum("bhqk,bkhd->bqhd", np.asarray(weights), np.asarray(v))
    np.testing.assert_allclose(got, oracle, atol=2e-6)
    np.testing.assert_allclose(got, dense, atol=5e-6)


def test_flash_window_visits_only_the_key_blocks_a_window_touches():
    """The skip, not a mask: the windowed call's grid has the key blocks ONE
    query block's windows touch for its key axis (2 of 8 here), where the
    causal call's has them all."""
    q = jnp.zeros((1, 1024, 1, 128), jnp.float32)
    grid = lambda **kw: re.search(r"grid=\(([\d, ]+)\)", str(jax.make_jaxpr(
        lambda q: flash_attention(q, q, q, block_q=128, block_k=128, **kw))(q))).group(1)
    assert grid() == "1, 8, 8" and grid(window=128) == "1, 8, 2"
    assert grid(window=129) == "1, 8, 2"  # a block's first query still starts one block back
    assert grid(window=130) == "1, 8, 3"  # one key further: a third block


@pytest.mark.parametrize("T,window,visited,causal", [
    (512, 1024, 1, 1),     # no longer than the window: the causal call, one block
    (1024, 1024, 2, 2),    # blocks of 512 x 1,024: two query blocks over one key block
    (2048, 1024, 6, 6),    # a key block as wide as the window: nothing of the causal triangle is outside it
    (4096, 1024, 14, 20),  # query blocks 4-7 leave 1, 1, 2, 2 key blocks behind
    (4096, 512, 15, 36),   # laguna's window: key blocks of 512
    (40, 8, 0, 0),         # no kernel at this length
])
def test_window_key_blocks_counts_the_pairs_the_windowed_grid_visits(T, window, visited, causal):
    assert window_key_blocks(T, window) == (visited, causal)


def test_flash_window_none_is_the_default_and_the_parents_program():
    q, k, v = (jax.random.normal(kk, (1, 256, 2, 128), jnp.float32)
               for kk in jax.random.split(jax.random.key(0), 3))
    lowered = lambda fn: jax.jit(fn).lower(q, k, v).as_text()
    assert lowered(lambda q, k, v: flash_attention(q, k, v)) == lowered(
        lambda q, k, v: flash_attention(q, k, v, window=None))
    # a window no shorter than the sequence is no window: the same program
    assert lowered(lambda q, k, v: flash_attention(q, k, v)) == lowered(
        lambda q, k, v: flash_attention(q, k, v, window=256))
    np.testing.assert_array_equal(flash_attention(q, k, v), flash_attention(q, k, v, window=300))
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q: flash_attention(q, k, v, window=100).sum())(q)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=100)


def test_a_windowed_length_that_does_not_tile_takes_the_oracle():
    q, k, v = (jax.random.normal(kk, (1, 40, 2, 128), jnp.float32)
               for kk in jax.random.split(jax.random.key(1), 3))
    got = flash_attention(q, k, v, window=8)
    want = _blockwise_attention(q, k, v, True, 8, 8, window=8)
    np.testing.assert_allclose(got, want, atol=2e-6)


# ------------------------------------------------------ rotation and routing
def test_yarn_table_is_the_references_and_the_plain_table_is_rope_half_split():
    full = CFG["rope_parameters"]["full_attention"]
    published = {**full, "original_max_position_embeddings": 8192}
    for rope in (full, published):
        r, want, _factor = ref.inv_freq(rope, 128)
        got = parts.yarn_inv_freq(r, rope["rope_theta"], rope["factor"],
                                  rope["original_max_position_embeddings"],
                                  rope["beta_fast"], rope["beta_slow"])
        np.testing.assert_allclose(got, want, rtol=1e-6)
    # published: pairs 0-9 turn as written, 18-31 at 1/128 of it, a ramp between
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[18:], plain[18:] / 128, rtol=1e-6)
    assert np.all(np.diff(np.asarray(got / plain)[9:19]) < 0)
    x = jax.random.normal(jax.random.key(0), (5, 3, 128), jnp.float32)
    pos = jnp.asarray([0, 3, 100, 511, 6000])[:, None]
    table = 10000.0 ** (-jnp.arange(0, 128, 2, dtype=jnp.float32) / 128)
    np.testing.assert_allclose(parts.rope_table(x, pos, table),
                               parts.rope_half_split(x, pos, 10000.0), atol=1e-5)
    half = parts.rope_table(x, pos, table[:32], 1.5)
    np.testing.assert_array_equal(half[..., 64:], x[..., 64:])  # the rest unrotated


def _layer(key, T=48, D=256, E=16, F=128):
    ks = jax.random.split(key, 8)
    w = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5
    return {
        "router": w(ks[0], (D, E), D), "router_bias": jnp.zeros((E,), jnp.float32),
        "experts_gu": w(ks[2], (E, D, 2 * F), D), "experts_down": w(ks[3], (E, F, D), F),
        "shared_gu": w(ks[4], (D, 2 * F), D), "shared_down": w(ks[5], (F, D), F),
    }, jax.random.normal(ks[6], (T, D), jnp.float32)


ROUTE = {"num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5}


def test_softmax_route_is_the_references_and_the_default_stays_sigmoid():
    p, x = _layer(jax.random.key(3))
    chosen, weights = moe_mod.softmax_topk_route(x, p["router"], p["router_bias"], 3, 2.5)
    want = np.asarray(_highest(ref.route, p, x, ROUTE))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(chosen), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)  # renormalised, then scaled
    y_default, _ = _highest(lambda: moe_mod.dropless_moe(x, p, top_k=3, scale=2.5))
    y_sigmoid, _ = _highest(lambda: moe_mod.dropless_moe(
        x, p, top_k=3, scale=2.5, route=moe_mod.sigmoid_topk_route))
    np.testing.assert_array_equal(y_default, y_sigmoid)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips, two of the sixteen experts each: the routed parts of all
    the shares, and the shared expert counted once, are the whole layer, in
    the program (``held_from``) and in the reference alike."""
    p, x = _layer(jax.random.key(1))
    moe = lambda p, **kw: moe_mod.dropless_moe(
        x, p, top_k=3, scale=2.5, route=moe_mod.softmax_topk_route, **kw)
    whole, load = _highest(lambda: moe(p))
    uncut = _highest(lambda: ref.routed(p, x, ROUTE, p["experts_gu"], p["experts_down"], 0)
                     + ref.shared(p, x))
    np.testing.assert_allclose(whole, uncut, atol=TOL)
    shared = _highest(ref.shared, p, x)
    shares, _ = _highest(ref.expert_shares, p, x, ROUTE, p["experts_gu"], p["experts_down"], 8)
    total, pairs = shared, 0
    for i in range(8):
        held = {**p, "experts_gu": p["experts_gu"][2 * i:2 * i + 2],
                "experts_down": p["experts_down"][2 * i:2 * i + 2]}
        y, held_load = _highest(lambda: moe(held, held_from=2 * i))
        np.testing.assert_allclose(y - shared, shares[i], atol=TOL)
        np.testing.assert_array_equal(held_load, load[2 * i:2 * i + 2])
        total, pairs = total + (y - shared), pairs + int(held_load.sum())
    np.testing.assert_allclose(total, uncut, atol=TOL)
    assert pairs == 48 * 3  # every pair is held by exactly one share


# ------------------------------------------------------------------ the ring
def _ring_model(window=256):
    return SlidingGqaMoELM.from_config({**CFG, "sliding_window": window}, dtype=jnp.float32)


@pytest.mark.parametrize("positions", [(5, 130, 255), (256, 300, 1000)])
def test_the_ring_read_through_the_paged_kernel_is_attention_over_the_last_window(positions):
    """Three slots, three layers, a ring of 256 in two blocks of 128: the
    whole leaf as a pool, the layer chosen by the table, against
    ``gathered_decode_attention`` over the last 256 rows of a plain history."""
    m, W, layer = _ring_model(), 256, 1
    S, Hk, hd, H = len(positions), 2, 128, 6
    keys = jax.random.split(jax.random.key(sum(positions)), 4)
    T = max(positions) + 1
    hist_k, hist_v = (jax.random.normal(kk, (S, T, Hk, hd), jnp.float32) for kk in keys[:2])
    q = jax.random.normal(keys[2], (S, H, hd), jnp.float32)
    ring_k = jax.random.normal(keys[3], (S, 3, W, Hk, hd), jnp.float32)  # other layers: noise
    ring_v = ring_k + 1.0
    for s, t in enumerate(positions):  # row p % W holds position p, the newest wins
        for p in range(max(0, t - W + 1), t + 1):
            ring_k = ring_k.at[s, layer, p % W].set(hist_k[s, p])
            ring_v = ring_v.at[s, layer, p % W].set(hist_v[s, p])
    pos = jnp.asarray(positions, jnp.int32)
    got = m._ring_attend(q, ring_k, ring_v, jnp.int32(layer), pos, jnp.ones((S,), bool))
    for s, t in enumerate(positions):
        lo = max(0, t - W + 1)
        want = gathered_decode_attention(
            q[s][None, None], hist_k[s, lo:t + 1][None], hist_v[s, lo:t + 1][None], t - lo)
        np.testing.assert_allclose(got[s], want[0, 0], atol=2e-5)


def test_a_step_leaves_inactive_slots_rings_and_slot_0s_bit_for_bit(family):
    model, params = family.model, family.params
    S, bs, MB = 4, 16, 4
    rng = np.random.default_rng(5)
    fill = lambda spec: jax.tree.map(
        lambda s: jnp.asarray(rng.standard_normal(s.shape), s.dtype), spec)
    cache = SlotCache(fill(model.cache_spec(1 + S * MB, bs)), fill(model.state_spec(S)))
    tables = jnp.arange(1, 1 + S * MB, dtype=jnp.int32).reshape(S, MB)
    active = jnp.asarray([False, True, False, True])
    paged = PagedState(tables, jnp.asarray([3, 9, 0, 20], jnp.int32), active)
    _logits, after, counters = jax.jit(model.decode)(params, cache, _tokens(S), paged)
    for name in ("k", "v"):
        before, now = np.asarray(cache.slots[name]), np.asarray(after.slots[name])
        np.testing.assert_array_equal(now[[0, 2]], before[[0, 2]])  # slot 0 is nobody's null block
        changed = np.argwhere((now != before).any(axis=(-1, -2)))
        # an active slot: ONE row a sliding layer, at position % window
        assert sorted(map(tuple, changed)) == sorted(
            (s, l, p % 8) for s, p in ((1, 9), (3, 20)) for l in range(model.sliding_layers))
    assert int(counters[0]) == 2 and int(counters[1]) == 8 + 8  # min(position + 1, 8) each


# ------------------------------------------------ prefill, decode, the engine
def test_prefill_path_matches_the_reference_over_two_periods(family):
    cfg = {**family.cfg, "num_hidden_layers": family.two_periods}
    m = SlidingGqaMoELM.from_config(cfg, dtype=jnp.float32, max_len=128)
    assert m.runs == (3, 3, 0)
    p = jax.jit(m.init)(jax.random.key(2))
    toks = _tokens(45, seed=4)
    got = _highest(jax.jit(m.logits), p, toks)
    np.testing.assert_allclose(got, family.want(p, toks, cfg), atol=TOL)


def _decode_against_reference(family, lengths=(5, 20, 33), steps=20, hook=None):
    """Teacher-forced: prefill ``lengths[s]`` tokens of sequence s in its
    bucket (shorter than the window of 8, longer than it, past a bucket's
    edge), then decode ``steps`` tokens through the pools and the rings: 20
    steps wrap a ring of 8 twice.  Returns the largest |decode logit -
    reference logit| over all steps and slots."""
    model, params = family.model, family.params
    bs, S, L = 16, len(lengths), model.expert_layers
    bucket = lambda n: max(16, 1 << (n - 1).bit_length())
    MB = -(-(max(lengths) + steps) // bs)
    zeros = lambda spec: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    cache = SlotCache(zeros(model.cache_spec(1 + S * MB, bs)), zeros(model.state_spec(S)))
    tables = np.arange(1, 1 + S * MB, dtype=np.int32).reshape(S, MB)
    seqs = [_tokens(n + steps, seed=s) for s, n in enumerate(lengths)]
    prefill = jax.jit(model.prefill, static_argnums=3)
    for s, n in enumerate(lengths):
        lb = bucket(n)
        rows, _logits, counted = _highest(
            prefill, params, jnp.pad(seqs[s][:n], (0, lb - n))[None], jnp.int32(n), bs)
        # by expert layer the fullest held expert, the held pairs, the held
        # experts with rows; then the bucket.  Pad tokens are not counted.
        assert counted.shape == (model.prefill_counters,) == (3 * L + 1,)
        assert int(counted[-1]) == lb
        pairs, touched = np.asarray(counted[L:2 * L]), np.asarray(counted[2 * L:3 * L])
        assert np.all(pairs <= n * model.num_experts_per_tok) and np.all(touched >= 1)
        if model.num_experts == model.router_experts:  # every pair's expert is here
            assert np.all(pairs == n * model.num_experts_per_tok)
        assert np.all(np.asarray(counted[:L]) * touched >= pairs)  # the fullest holds the mean
        cache = model.write_rows(cache, rows, tables[s, : -(-lb // bs)])
        cache = model.write_state(cache, rows, s)
    want = [family.want(params, seq) for seq in seqs]
    decode = jax.jit(model.decode)
    worst = 0.0
    for t in range(steps):
        lens = jnp.asarray([n + t for n in lengths], jnp.int32)
        tok = jnp.stack([seqs[s][n + t] for s, n in enumerate(lengths)])
        paged = PagedState(jnp.asarray(tables), lens, jnp.ones((S,), bool))
        got, cache, counters = _highest(decode, params, cache, tok, paged)
        assert counters.shape == (2 + 2 * L,) and int(counters[0]) == S
        assert int(counters[1]) == sum(min(n + t + 1, 8) for n in lengths)
        if hook is not None:
            cache = hook(cache, t)
        for s, n in enumerate(lengths):
            worst = max(worst, float(jnp.max(jnp.abs(got[s] - want[s][n + t]))))
    return worst


def test_prefill_then_decode_on_ring_and_pool_matches_the_reference(family):
    assert _decode_against_reference(family) < TOL


def test_a_ring_row_lost_fails_the_tolerance(family):
    """What the tolerance has to tell apart: one slot's ring of one layer
    zeroed after the third step (a write that went to the wrong place)."""
    def lost(cache, t):
        if t != 2:
            return cache
        return cache._replace(slots={**cache.slots, "k": cache.slots["k"].at[1, 2].set(0.0)})

    assert _decode_against_reference(family, hook=lost) > 50 * TOL


def _engine(model, params, slots=3, **kw):
    return ContinuousBatchingEngine(
        model, params, slots=slots, block_size=16, max_seq_len=256, max_prompt_len=64,
        min_prompt_len=5, **kw)


def _run(eng, requests):
    live, out = {}, {}
    for i, (prompt, budget) in enumerate(requests):
        slot, _emitted = eng.submit(prompt, budget)
        live[slot] = i
    while live:
        _emissions, finished = eng.step()
        for slot in finished:
            out[live.pop(slot)] = eng.retire(slot)
    return out


def _gaps(params, prompt, emitted, reference=ref, cfg=CFG):
    seq = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    want = np.asarray(_highest(reference.logits, params, jnp.asarray(seq[:-1]), cfg))
    want = want[len(prompt) - 1:]
    return want.max(-1) - want[np.arange(len(emitted)), emitted]


def test_engine_submit_step_retire_matches_the_reference_and_slots_are_reused(family):
    """Every slot in use, prompts on both sides of the window, decodes that
    wrap the ring; then the freed slots take new requests, whose rings must
    be their own prompts' (a join overwrites the slot's rings whole)."""
    model, params = family.model, family.params
    registry = telemetry.get_registry()
    before = registry.snapshot()
    with jax.default_matmul_precision("highest"):
        eng = _engine(model, params)
        assert eng.state_bytes == 2 * 3 * model.sliding_layers * 8 * 2 * 128 * 4
        for wave in (((5, 20), (20, 12), (60, 9)), ((33, 18), (7, 25))):
            requests = [(np.asarray(_tokens(n, seed=20 + n)), b) for n, b in wave]
            out = _run(eng, requests)
            for i, (prompt, budget) in enumerate(requests):
                assert len(out[i]) == budget
                # every emitted token is the reference's argmax, up to a near tie
                assert _gaps(params, prompt, out[i], family.ref, family.cfg).max() < TOL
        assert eng._step_jit._cache_size() == 1
        assert eng.pool.available() == eng.pool.num_blocks - 1
    snapshot = registry.snapshot()
    rows = snapshot["serve_engine_ring_live_rows"]["series"][0]["value"]
    assert rows["count"] > 0 and 1 <= rows["sum"] / rows["count"] <= 8
    assert snapshot["serve_engine_held_pair_share"]["series"][0]["value"]["count"] > 0
    assert snapshot["serve_engine_state_bytes"]["series"][0]["value"] == eng.state_bytes
    # what a prefill says of its expert layers and of its windowed forward
    count = lambda shot, name: sum(x["value"]["count"] for x in shot.get(name, {"series": []})["series"])
    rise = lambda name: count(snapshot, name) - count(before, name)
    assert rise("serve_moe_prefill_rows_per_expert") == 5 * model.expert_layers
    assert rise("serve_moe_prefill_pairs") == 5 * model.expert_layers
    blocks = {tuple(x["labels"].items()): x["value"]
              for x in snapshot["serve_engine_window_key_blocks"]["series"]}
    # (buckets of 64 positions and under take no flash kernel: nothing to count here;
    # test_window_key_blocks_counts_the_pairs_the_windowed_grid_visits has the counts)
    assert set(blocks) == {(("blocks", "visited"),), (("blocks", "skipped"),)}


@pytest.mark.parametrize("cell,M,K,N,G,plan", [
    # (rows of the largest prefill and of a decode step) x (gate|up, down), by
    # routed configuration: hidden, 2 x expert width, experts held
    ("glm", 16384, 2048, 3072, 64, (256, 3072, 127, 39845888)),
    ("glm", 16384, 1536, 2048, 64, (256, 2048, 127, 22544384)),
    ("glm", 128, 2048, 3072, 64, (128, 3072, 64, 32505856)),
    ("solar", 32768, 4096, 2560, 40, (256, 1280, 334, 30408704)),
    ("solar", 32768, 1280, 4096, 40, (256, 4096, 167, 39059456)),
    ("solar", 512, 4096, 2560, 40, (256, 1280, 82, 30408704)),
    ("laguna", 40960, 3072, 2048, 32, (256, 2048, 191, 36700160)),
    ("laguna", 40960, 1024, 3072, 32, (256, 3072, 191, 26214400)),
    ("laguna", 640, 3072, 2048, 32, (256, 2048, 34, 36700160)),
    ("granite", 40960, 4096, 1536, 18, (256, 1536, 177, 35651584)),
    ("granite", 40960, 768, 4096, 18, (256, 4096, 177, 30146560)),
    ("granite", 1280, 4096, 1536, 18, (256, 1536, 22, 35651584)),
    ("mellum", 32768, 2304, 1792, 64, (256, 1792, 191, 26214400)),
    ("mellum", 32768, 896, 2304, 64, (256, 2304, 191, 18612224)),
    ("mellum", 256, 2304, 1792, 64, (256, 1792, 64, 26214400)),
    ("mellum", 256, 896, 2304, 64, (256, 2304, 64, 18612224)),
])
def test_grouped_matmul_plans_are_pinned_for_the_routed_configurations(cell, M, K, N, G, plan):
    """The blocking every routed cell's grouped matmuls take today: a group's
    whole matrix a grid step wherever VMEM holds it twice (``tn`` = N, also at
    mellum's 1,792 = 14 x 128 and 2,304 = 18 x 128, which are no powers of two;
    solar's 4,096 x 2,560 alone is cut, into two strips of 1,280).  A change of
    the plan for one model moves the others."""
    got = moe_mod.grouped_matmul_plan(M, K, N, G, 2)
    assert tuple(got) == plan
    assert N % got.tn == 0 and got.tn % 128 == 0
    assert got.vmem_bytes <= moe_mod._GMM_VMEM_LIMIT


def test_lm_serve_engine_config_builds_the_model_and_answers_a_request(tmp_path):
    """The normal entry point, not only the benchmark's runner: ``lm_serve
    --engine --config <file>`` builds the class the file's ``"model"`` names
    and answers one request whose tokens are the reference's argmax."""
    from moolib_tpu.rpc import Rpc
    from moolib_tpu.serving import ServeClient

    config = {**CFG, "model": "moolib_tpu.models.swa_moe:SlidingGqaMoELM"}
    path = tmp_path / "laguna_tiny.json"
    path.write_text(json.dumps(config))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        address = f"127.0.0.1:{s.getsockname()[1]}"
    log = open(tmp_path / "replica.log", "w")
    replica = subprocess.Popen(
        [sys.executable, "-m", "moolib_tpu.examples.lm_serve", "--listen", address,
         "--name", "swa_replica", "--engine", "--config", str(path), "--slots", "2",
         "--seq_len", "32", "--max_new_tokens", "12", "--seed", "0"],
        stdout=log, stderr=subprocess.STDOUT, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    rpc = Rpc()
    try:
        rpc.set_name("swa_client")
        rpc.connect(address)
        client = ServeClient(rpc, fn="generate", replicas=["swa_replica"], deadline_s=240.0,
                             attempt_timeout=240.0, max_attempts=1, metadata=True)
        prompt = np.asarray(_tokens(20, seed=9))
        end = time.monotonic() + 240
        while "serving" not in open(tmp_path / "replica.log").read():
            assert replica.poll() is None, open(tmp_path / "replica.log").read()[-2000:]
            assert time.monotonic() < end, "the replica did not come up"
            time.sleep(0.5)
        out = np.asarray(client.submit(prompt, 12).result(240.0))
        client.close()
    finally:
        rpc.close()
        replica.terminate()
        try:
            replica.wait(timeout=20)
        except subprocess.TimeoutExpired:
            replica.kill()
            replica.wait()
        log.close()
    emitted = out[len(prompt):]
    assert len(emitted) == 12
    model = SlidingGqaMoELM.from_config(config, dtype=jnp.float32, max_len=44)
    params = jax.jit(model.init)(jax.random.key(0))
    assert _gaps(params, prompt, emitted).max() < 1e-3  # default matmul precision there
