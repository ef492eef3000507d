"""The config-built hybrid decoder (``models/hybrid_kda.py``: Kimi delta
attention, one gated GQA layer in four, a share of the experts) and its ops
against the plain reference (``chipbench/reference/solar_open2.py``), on the
CPU at a tiny size with the published ratios, seeded random weights, logits
not tokens.

Tolerances.  The model runs in float32 here (``dtype=float32``), its kernels
in Pallas interpret mode, so what separates program and reference is the order
of float32 sums (the chunked delta rule against the token-by-token one, the
sorted grouped matmul against the masked loop): logits of magnitude ~1 agree
to ``TOL`` = 2e-4 (measured: at most 8e-6 over these seeds).  A recurrent
state rounded to bfloat16 loses 2**-9 of every entry a step and is shown to
break ``TOL`` below, so a lower precision than the configuration states
cannot pass; so is a freed slot's state that a join did not overwrite.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import solar_open2 as ref  # noqa: E402
from moolib_tpu.engine import ContinuousBatchingEngine  # noqa: E402
from moolib_tpu.models.decoder_parts import SlotCache  # noqa: E402
from moolib_tpu.models.hybrid_kda import HybridKdaMoELM, tiny_config  # noqa: E402
from moolib_tpu.ops import kda  # noqa: E402
from moolib_tpu.ops.paged_attention import PagedState  # noqa: E402
from moolib_tpu.parallel import moe as moe_mod  # noqa: E402

TOL = 2e-4
CFG = {**tiny_config(), "num_hidden_layers": 4}  # one period: GQA, KDA, KDA, KDA


@pytest.fixture(scope="module")
def model():
    return HybridKdaMoELM.from_config(CFG, dtype=jnp.float32, max_len=512)


@pytest.fixture(scope="module")
def params(model):
    return jax.jit(model.init)(jax.random.key(7))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, CFG["vocab_size"], n), jnp.int32)


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


# ------------------------------------------------------------------ the file
def test_builds_from_the_published_keys_and_the_pattern(model):
    assert (model.periods, model.kda_layers, model.period) == (1, 3, 4)
    assert (model.n_routed_experts, model.router_experts, model.held_from) == (8, 16, 0)
    assert model.step_counters == 9 and model.prefill_counters == 4
    two = HybridKdaMoELM.from_config(tiny_config())
    assert (two.periods, two.kda_layers) == (2, 6)


@pytest.mark.parametrize("key,value", [
    ("use_rope", True), ("use_gqa_gate", False), ("kda_use_full_proj", True),
    ("kda_allow_neg_eigval", False), ("first_k_dense_replace", 1), ("n_shared_experts", 2),
    ("norm_topk_prob", False), ("gqa_layers", [0, 3, 6]), ("num_hidden_layers", 6),
])
def test_a_key_the_model_cannot_honour_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        HybridKdaMoELM.from_config({**CFG, key: value})


# ------------------------------------------------------------------- the ops
def _kda_inputs(T, H=2, d=128, seed=0, spread=2.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (T, H, d)))
    v = jax.random.normal(ks[2], (T, H, d))
    # log-decays from -1e-4 to several hundred a step: a channel that forgets
    # at once would overflow a chunk-wide exp(-G)
    g = -jnp.exp(jax.random.normal(ks[3], (T, H, d)) * spread - 1.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("T", [150, 64, 1])
def test_chunked_prefill_equals_the_token_by_token_recurrence(T):
    """At a length that is not whole chunks: the tail is padded with beta 0
    and log-decay 0, which must leave the state where position T - 1 put it."""
    q, k, v, g, beta = _kda_inputs(T)
    zeros = jnp.zeros((2, 128, 128))
    want_o, want_S = _highest(kda.recurrent_kda, q, k, v, g, beta, zeros)
    pad = lambda x: jnp.pad(x, ((0, -T % kda.CHUNK),) + ((0, 0),) * (x.ndim - 1))
    got_o, got_S = _highest(jax.jit(kda.chunked_kda), *map(pad, (q, k, v, g, beta)))
    assert float(g.min()) < -50.0
    np.testing.assert_allclose(got_o[:T], want_o, atol=2e-6)
    np.testing.assert_allclose(got_S, want_S, atol=2e-5)


# T, the real positions of it (None: all), a state to start from, the spread of
# the log-decays (2.0: below -50 a step)
@pytest.mark.parametrize("T,length,start,spread", [
    (128, None, True, 1.0),    # a state to start from
    (256, 128, False, 1.0),    # shorter by whole chunks
    (256, 150, True, 1.0),     # ... and by part of one
    (192, 1, False, 1.0),      # one real position
    (128, 0, True, 1.0),       # none: the state comes back as it went in
    (256, None, False, 1.0),   # 4 chunks
    (2048, 1990, False, 1.0),  # 32 chunks
    (256, 170, True, 2.0),     # log-decays below -50 a step, a length and a state
])
def test_prefill_kernel_takes_a_length_and_a_state(T, length, start, spread):
    """Positions from ``length`` on are whatever the bucket holds (here: as
    lively as the real ones, unmasked): the state is what position length - 1
    left, the rows of ``o`` below it are the recurrence's, and the chunks
    wholly past it were not run, so their rows are 0."""
    q, k, v, g, beta = _kda_inputs(T, seed=11, spread=spread)
    S0 = (jax.random.normal(jax.random.key(12), (2, 128, 128)) if start
          else jnp.zeros((2, 128, 128)))
    n = T if length is None else length
    want_o, want_S = _highest(kda.recurrent_kda, q[:n], k[:n], v[:n], g[:n], beta[:n], S0)
    got_o, got_S = kda.chunked_kda(
        q, k, v, g, beta, length=None if length is None else jnp.int32(length),
        state=S0 if start else None)
    assert spread < 2.0 or float(g[:n].min()) < -50.0
    np.testing.assert_allclose(got_o[:n], want_o, atol=2e-6)
    np.testing.assert_allclose(got_S, want_S, atol=2e-5)
    skipped = -(-n // kda.CHUNK) * kda.CHUNK
    np.testing.assert_array_equal(np.asarray(got_o[skipped:]), 0.0)


def test_prefill_in_two_calls_is_the_prefill_in_one():
    """The second call starts from the state the first one left: what a
    prompt prefilled in pieces between decode steps will do."""
    q, k, v, g, beta = _kda_inputs(256, seed=13, spread=1.0)
    whole_o, whole_S = kda.chunked_kda(q, k, v, g, beta)
    first_o, S = kda.chunked_kda(*(x[:128] for x in (q, k, v, g, beta)))
    second_o, S = kda.chunked_kda(*(x[128:] for x in (q, k, v, g, beta)), state=S)
    np.testing.assert_allclose(jnp.concatenate([first_o, second_o]), whole_o, atol=1e-6)
    np.testing.assert_allclose(S, whole_S, atol=2e-6)


@pytest.mark.parametrize("active", [(True, False, True, True), (False,) * 4, (True,) * 4])
def test_decode_kernel_in_interpret_mode_equals_the_jnp_step(active):
    q, k, v, g, beta = _kda_inputs(4, seed=3, spread=1.0)
    state = jax.random.normal(jax.random.key(5), (4, 3, 2, 128, 128))
    active = jnp.asarray(active)
    want_o, want_s = kda.kda_step(q, k, v, g, beta, state, 1, active)
    got_o, got_s = kda.kda_decode(q, k, v, g, beta, state, jnp.int32(1), active)
    np.testing.assert_allclose(got_o, jnp.where(active[:, None, None], want_o, 0.0), atol=1e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    # other layers and the slots nobody holds are bit for bit what they were
    idle = np.nonzero(~np.asarray(active))[0]
    np.testing.assert_array_equal(np.asarray(got_s)[idle], np.asarray(state)[idle])
    np.testing.assert_array_equal(np.asarray(got_s)[:, [0, 2]], np.asarray(state)[:, [0, 2]])


def test_the_jnp_step_is_the_recurrence_transposed():
    q, k, v, g, beta = _kda_inputs(5, seed=4, spread=1.0)
    S0 = jax.random.normal(jax.random.key(6), (2, 128, 128))
    want_o, want_S = _highest(kda.recurrent_kda, q, k, v, g, beta, S0)
    state = jnp.swapaxes(S0, -1, -2)[None, None]  # one slot, one layer, transposed
    for t in range(5):
        o, state = kda.kda_step(q[t:t + 1], k[t:t + 1], v[t:t + 1], g[t:t + 1], beta[t:t + 1],
                                state, 0, jnp.ones((1,), bool))
        np.testing.assert_allclose(o[0], want_o[t], atol=1e-6)
    np.testing.assert_allclose(jnp.swapaxes(state[0, 0], -1, -2), want_S, atol=2e-6)


# ------------------------------------------------------------ the expert layer
def _layer(key, T=48, D=256, E=16, F=128):
    ks = jax.random.split(key, 8)
    w = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5
    return {
        "router": w(ks[0], (D, E), D),
        "router_bias": jax.random.uniform(ks[1], (E,), jnp.float32, -0.1, 0.1),
        "experts_gu": w(ks[2], (E, D, 2 * F), D), "experts_down": w(ks[3], (E, F, D), F),
        "shared_gu": w(ks[4], (D, 2 * F), D), "shared_down": w(ks[5], (F, D), F),
    }, jax.random.normal(ks[6], (T, D), jnp.float32)


ROUTE = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.0}


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips, two of the sixteen experts each: the routed parts of all
    the shares, and the shared expert counted once, are the whole layer, in
    the program (``held_from``) and in the reference alike."""
    p, x = _layer(jax.random.key(1))
    whole, load = _highest(lambda: moe_mod.dropless_moe(x, p, top_k=4, scale=1.0))
    uncut = _highest(lambda: ref.routed(p, x, ROUTE, p["experts_gu"], p["experts_down"], 0)
                     + ref.shared(p, x))
    np.testing.assert_allclose(whole, uncut, atol=TOL)
    shared = _highest(ref.shared, p, x)
    parts, _ = _highest(ref.expert_shares, p, x, ROUTE, p["experts_gu"], p["experts_down"], 8)
    total, pairs = shared, 0
    for i in range(8):
        held = {**p, "experts_gu": p["experts_gu"][2 * i:2 * i + 2],
                "experts_down": p["experts_down"][2 * i:2 * i + 2]}
        y, held_load = _highest(lambda: moe_mod.dropless_moe(
            x, held, top_k=4, scale=1.0, held_from=2 * i))
        np.testing.assert_allclose(y - shared, parts[i], atol=TOL)
        np.testing.assert_array_equal(held_load, load[2 * i:2 * i + 2])
        total, pairs = total + (y - shared), pairs + int(held_load.sum())
    np.testing.assert_allclose(total, uncut, atol=TOL)
    assert pairs == 48 * 4  # every pair is held by exactly one share


def test_a_share_leaves_out_pad_tokens_and_absent_experts_alike():
    p, x = _layer(jax.random.key(2))
    held = {**p, "experts_gu": p["experts_gu"][4:8], "experts_down": p["experts_down"][4:8]}
    valid = jnp.arange(48) < 30
    y, load = _highest(lambda: moe_mod.dropless_moe(
        x, held, top_k=4, scale=1.0, held_from=4, valid=valid))
    want = _highest(lambda: ref.routed(p, x, ROUTE, held["experts_gu"], held["experts_down"], 4))
    shared = _highest(ref.shared, p, x)
    np.testing.assert_allclose((y - shared)[:30], want[:30], atol=TOL)
    np.testing.assert_allclose((y - shared)[30:], 0.0, atol=1e-6)  # the shared expert alone
    chosen, _ = moe_mod.sigmoid_topk_route(x, p["router"], p["router_bias"], 4, 1.0)
    chosen = np.asarray(chosen)[:30]
    np.testing.assert_array_equal(load, [(chosen == e).sum() for e in range(4, 8)])
    with pytest.raises(ValueError, match="held"):
        moe_mod.dropless_moe(x, held, top_k=4, scale=1.0)


def _parents_dropless_moe(x32, p, *, top_k, scale, valid=None, layer=None, interpret=None):
    """``dropless_moe`` as the parent commit had it, line for line."""
    T, D = x32.shape
    E = p["router"].shape[-1]
    dtype = p["experts_gu"].dtype
    experts, weights = moe_mod.sigmoid_topk_route(
        x32, p["router"], p["router_bias"], top_k, scale)
    flat = experts.reshape(-1)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, top_k), flat, E)
    order = jnp.argsort(flat, stable=True)
    load = jnp.bincount(flat, length=E + 1)[:E].astype(jnp.int32)
    x = x32.astype(dtype)
    rows = x[order // top_k]
    gu = moe_mod.grouped_matmul(rows, p["experts_gu"], load, layer, interpret=interpret)
    down = moe_mod.grouped_matmul(moe_mod._silu_gate(gu, dtype), p["experts_down"], load, layer,
                                  interpret=interpret)
    back = jnp.argsort(order)
    pairs = down[back].reshape(T, top_k, D).astype(jnp.float32)
    if valid is not None:
        pairs = jnp.where(valid[:, None, None], pairs, 0.0)
    routed = jnp.sum(pairs * weights[..., None], axis=1)
    return routed + moe_mod.swiglu(x, p["shared_gu"], p["shared_down"]), load


@pytest.mark.parametrize("with_valid", [False, True])
def test_dropless_moe_without_a_share_is_the_parents_bit_for_bit(with_valid):
    """The same program (the lowered text, line for line) and the same bits."""
    p, x = _layer(jax.random.key(3))
    valid = (jnp.arange(48) % 5 != 0) if with_valid else None
    new = jax.jit(lambda x, p: moe_mod.dropless_moe(x, p, top_k=4, scale=1.8, valid=valid))
    old = jax.jit(lambda x, p: _parents_dropless_moe(x, p, top_k=4, scale=1.8, valid=valid))
    strip = lambda text: [ln.split(" loc(")[0] for ln in text.splitlines()]
    assert strip(new.lower(x, p).as_text()) == strip(old.lower(x, p).as_text())
    for got, want in zip(new(x, p), old(x, p)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------ the model, whole
def test_prefill_path_matches_the_reference_over_two_periods():
    cfg = tiny_config()
    model = HybridKdaMoELM.from_config(cfg, dtype=jnp.float32)
    params = jax.jit(model.init)(jax.random.key(11))
    toks = _tokens(150, seed=1)  # not whole chunks, not whole flash blocks
    got = _highest(jax.jit(model.logits), params, toks)
    want = _highest(ref.logits, params, toks, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def _decode_against_reference(model, params, lengths=(127, 129, 40), steps=6, hook=None):
    """Teacher-forced: prefill ``lengths[s]`` tokens of sequence s in its
    bucket (127 and 129 lie one short of and one past the edge of the bucket
    of 128: the padding must move neither state nor tail), then decode
    ``steps`` tokens through the pools and the slot state.  Returns the
    largest |decode logit - reference logit| over all steps and slots.
    ``hook(cache) -> cache`` runs between steps (a planted fault)."""
    bs, S = 16, len(lengths)
    bucket = lambda n: max(64, 1 << (n - 1).bit_length())
    MB = max(bucket(n) for n in lengths) // bs  # a join allocates the bucket's blocks
    zeros = lambda spec: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    cache = SlotCache(zeros(model.cache_spec(1 + S * MB, bs)), zeros(model.state_spec(S)))
    tables = np.arange(1, 1 + S * MB, dtype=np.int32).reshape(S, MB)
    seqs = [_tokens(n + steps, seed=s) for s, n in enumerate(lengths)]
    prefill = jax.jit(model.prefill, static_argnums=3)
    for s, n in enumerate(lengths):
        lb = bucket(n)
        rows, _logits, fullest = _highest(
            prefill, params, jnp.pad(seqs[s][:n], (0, lb - n))[None], jnp.int32(n), bs)
        assert fullest.shape == (4,)
        cache = model.write_rows(cache, rows, tables[s, : lb // bs])
        cache = model.write_state(cache, rows, s)
    want = [_highest(ref.logits, params, seq, CFG) for seq in seqs]
    decode = jax.jit(model.decode)
    worst = 0.0
    for t in range(steps):
        lens = jnp.asarray([n + t for n in lengths], jnp.int32)
        tok = jnp.stack([seqs[s][n + t] for s, n in enumerate(lengths)])
        paged = PagedState(jnp.asarray(tables), lens, jnp.ones((S,), bool))
        got, cache, counters = _highest(decode, params, cache, tok, paged)
        assert counters.shape == (9,) and int(counters[0]) == S
        assert int(counters[1:5].max()) <= S * 4 and int(counters[5:].max()) <= 8
        if hook is not None:
            cache = hook(cache)
        for s, n in enumerate(lengths):
            worst = max(worst, float(jnp.max(jnp.abs(got[s] - want[s][n + t]))))
    return worst


def test_prefill_then_paged_decode_matches_the_reference_at_a_buckets_edges(model, params):
    assert _decode_against_reference(model, params) < TOL


def test_a_bfloat16_state_fails_the_tolerance(model, params):
    """The nearest precision below the one the configuration states for the
    recurrent state: rounded to bfloat16 after every step."""
    def rounded(cache):
        state = cache.slots["kda"].astype(jnp.bfloat16).astype(jnp.float32)
        return cache._replace(slots={**cache.slots, "kda": state})

    assert _decode_against_reference(model, params, hook=rounded) > 10 * TOL


# -------------------------------------------------------- through the engine
def _engine(model, params, slots=3, **kw):
    return ContinuousBatchingEngine(
        model, params, slots=slots, block_size=16, max_seq_len=256, max_prompt_len=128,
        min_prompt_len=33, **kw)


def _run(eng, requests):
    """Submit all, then step to the end.  Returns {index: emitted}."""
    live, out = {}, {}
    for i, (prompt, budget) in enumerate(requests):
        slot, emitted = eng.submit(prompt, budget)
        live[slot] = i
    while live:
        _emissions, finished = eng.step()
        for slot in finished:
            out[live.pop(slot)] = eng.retire(slot)
    return out


def _gaps(params, prompt, emitted):
    seq = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    want = np.asarray(_highest(ref.logits, params, jnp.asarray(seq[:-1]), CFG))[len(prompt) - 1:]
    return want.max(-1) - want[np.arange(len(emitted)), emitted]


def test_engine_submit_step_retire_matches_the_reference_all_slots_in_use(model, params):
    with jax.default_matmul_precision("highest"):
        eng = _engine(model, params)
        assert eng.warmup() == 2 * 2 + 1  # buckets 64, 128, their joins, the step
        requests = [(np.asarray(_tokens(n, seed=20 + n)), b)
                    for n, b in ((63, 7), (65, 5), (120, 9))]
        out = _run(eng, requests)
        assert eng._step_jit._cache_size() == 1
        assert eng.pool.available() == eng.pool.num_blocks - 1
    for i, (prompt, _b) in enumerate(requests):
        # Every emitted token is the reference's argmax, up to a near tie.
        assert _gaps(params, prompt, out[i]).max() < TOL


def test_a_prompt_shorter_than_a_chunk_prefills_in_its_own_bucket(model, params):
    """``lm_serve`` warms every bucket from 1 up: a bucket below the chunk of
    64 is padded inside the model and its K/V rows cut back to the bucket."""
    with jax.default_matmul_precision("highest"):
        eng = ContinuousBatchingEngine(model, params, slots=2, block_size=16, max_seq_len=64,
                                       max_prompt_len=32)
        requests = [(np.asarray(_tokens(5, seed=61)), 4), (np.asarray(_tokens(17, seed=62)), 3)]
        out = _run(eng, requests)
    for i, (prompt, _b) in enumerate(requests):
        assert _gaps(params, prompt, out[i]).max() < TOL


def test_a_freed_slot_joined_again_starts_from_the_new_requests_state(model, params, monkeypatch):
    """One slot, two requests one after the other: the second must see its
    own prefill's state and tail, not what the first left in the slot's row.
    With the state write taken out of the join it does not."""
    first = (np.asarray(_tokens(100, seed=31)), 6)
    second = (np.asarray(_tokens(50, seed=32)), 8)

    def both():
        eng = _engine(model, params, slots=1)
        _run(eng, [first])
        return _run(eng, [second])[0], eng

    with jax.default_matmul_precision("highest"):
        emitted, eng = both()
        assert _gaps(params, second[0], emitted).max() < TOL
        assert eng._step_jit._cache_size() == 1 and eng.stats()["joins"] == 2
        # the planted fault: a join that leaves the slot's row as it is
        monkeypatch.setattr(HybridKdaMoELM, "write_state", lambda self, cache, rows, slot: cache)
        stale, _ = both()
    assert _gaps(params, second[0], stale).max() > 10 * TOL


def test_a_join_behind_a_step_in_flight_lands_behind_it(model, params):
    """The join's state write rides the donated chain: dispatched while a
    decode step is in flight it neither disturbs that step's slots nor is
    overwritten by it."""
    a = (np.asarray(_tokens(70, seed=41)), 9)
    b = (np.asarray(_tokens(90, seed=42)), 6)
    with jax.default_matmul_precision("highest"):
        eng = _engine(model, params, slots=2)
        slot_a, _ = eng.submit(*a)
        eng.step()
        assert bool(eng._flights)  # a step is ahead, unfetched
        slot_b, _ = eng.submit(*b)
        live, out = {slot_a: 0, slot_b: 1}, {}
        while live:
            _e, finished = eng.step()
            for slot in finished:
                out[live.pop(slot)] = eng.retire(slot)
        assert eng._step_jit._cache_size() == 1
    assert _gaps(params, a[0], out[0]).max() < TOL
    assert _gaps(params, b[0], out[1]).max() < TOL


def test_one_step_program_under_slot_churn_and_counters_ride_the_packet(model, params):
    from moolib_tpu import telemetry

    reg = telemetry.get_registry()
    count = lambda name: sum(s["value"]["count"] for s in reg.snapshot()[name]["series"])
    before = {n: count(n) for n in (
        "serve_engine_state_live_slots", "serve_engine_held_pair_share",
        "serve_engine_held_experts_touched", "serve_engine_held_prefill_expert_load")}
    telemetry.get_tracer().clear()
    eng = _engine(model, params, slots=2)
    for round_ in range(3):  # six requests through two slots
        _run(eng, [(np.asarray(_tokens(40 + 9 * round_ + i, seed=50 + i)), 3 + i)
                   for i in range(2)])
    assert eng._step_jit._cache_size() == 1
    steps = eng.stats()["steps"] - eng.stats()["empty_steps"]
    assert count("serve_engine_state_live_slots") - before["serve_engine_state_live_slots"] == steps
    assert count("serve_engine_held_pair_share") - before["serve_engine_held_pair_share"] == 4 * steps
    assert (count("serve_engine_held_experts_touched")
            - before["serve_engine_held_experts_touched"]) == 4 * steps
    assert (count("serve_engine_held_prefill_expert_load")
            - before["serve_engine_held_prefill_expert_load"]) == 4 * 6
    spans = telemetry.get_tracer().spans()
    writes = [s for s in spans if s.name == "engine.state_write"]
    joins = [s for s in spans if s.name == "engine.join"]
    assert len(writes) == len(joins) == 6  # a span of its own under every join
    for w, j in zip(writes, joins):
        assert j.start_ns <= w.start_ns and w.start_ns + w.dur_ns <= j.start_ns + j.dur_ns


def test_a_model_without_slot_state_gets_the_pools_alone():
    from moolib_tpu.models.latent_moe import LatentMoELM
    from moolib_tpu.models.latent_moe import tiny_config as latent_tiny

    latent = LatentMoELM.from_config(latent_tiny(), dtype=jnp.float32)
    eng = ContinuousBatchingEngine(
        latent, jax.jit(latent.init)(jax.random.key(0)), slots=2, block_size=8,
        max_seq_len=32, max_prompt_len=16)
    assert not isinstance(eng._cache, SlotCache) and eng._cache.ndim == 4
    hybrid = HybridKdaMoELM.from_config(CFG, dtype=jnp.float32, max_len=64)
    eng = ContinuousBatchingEngine(
        hybrid, jax.jit(hybrid.init)(jax.random.key(0)), slots=2, block_size=16)
    assert isinstance(eng._cache, SlotCache)
    assert eng._cache.slots["kda"].shape == (2, 3, 2, 128, 128)
    assert eng._cache.slots["conv"].shape == (2, 3, 3, 3 * 256)
    assert eng._cache.blocks["k"][0].shape == (1 + 2 * 4, 16, 2, 128)
