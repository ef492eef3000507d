"""Power retention (``ops/retention.py``) and the config-built decoder whose
every mixer it is (``models/retention_lm.py``) against the plain reference
(``chipbench/reference/brumby.py``), on the CPU at a tiny size with the
published ratios, seeded random weights, logits not tokens.

Tolerances.  The model runs in float32 here (``dtype=float32``), its kernels
in Pallas interpret mode, so what separates program and reference is the order
of float32 sums (the recurrence over a state of symmetric squares against the
quadratic sum over all earlier positions): logits of magnitude ~1 agree to
``TOL`` = 2e-4 (measured: at most 1e-5 over these seeds).  A state rounded to
bfloat16 loses 2**-9 of every entry a step and is shown to break ``TOL``
below, so a lower precision than the configuration states cannot pass; so is
a freed slot's state that a join did not overwrite.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import brumby as ref  # noqa: E402
from moolib_tpu.engine import ContinuousBatchingEngine  # noqa: E402
from moolib_tpu.models.retention_lm import PowerRetentionLM, tiny_config  # noqa: E402
from moolib_tpu.ops import retention  # noqa: E402
from moolib_tpu.ops.paged_attention import PagedState  # noqa: E402

TOL = 2e-4
CFG = tiny_config()


@pytest.fixture(scope="module")
def model():
    return PowerRetentionLM.from_config(CFG, dtype=jnp.float32, max_len=512)


@pytest.fixture(scope="module")
def params(model):
    return jax.jit(model.init)(jax.random.key(7))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, CFG["vocab_size"], n), jnp.int32)


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


# ------------------------------------------------------------------ the file
def test_builds_from_the_published_keys(model):
    assert (model.num_attention_heads, model.num_key_value_heads, model.head_dim) == (4, 2, 128)
    assert model.step_counters == 1 and model.prefill_counters == 1
    assert not hasattr(model, "cache_spec") and not hasattr(model, "write_rows")
    spec = model.state_spec(3)
    assert spec["state"].shape == (3, 2, 2, 65, 128, 128) and spec["state"].dtype == jnp.float32
    assert spec["norm"].shape == (3, 2, 2, 72, 128) and spec["norm"].dtype == jnp.float32
    assert PowerRetentionLM.from_config(CFG).max_len == 1024  # max_position_embeddings


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("hidden_act", "gelu"), ("rope_scaling", {"type": "yarn"}),
    ("use_sliding_window", True), ("tie_word_embeddings", True), ("num_key_value_heads", 3),
    ("max_len", 2048),
])
def test_a_key_the_model_cannot_honour_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        PowerRetentionLM.from_config({**CFG, key: value})


# ------------------------------------------------------------------- the ops
def _inputs(T, H=4, G=2, d=128, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(key, (T, heads, d))
               for key, heads in zip(ks, (H, G, G)))
    # gates from 0.5 (forgets in a few tokens) to 0.9999
    lam = jax.nn.log_sigmoid(jax.random.uniform(ks[3], (T, G), minval=0.0, maxval=9.0))
    return q, k, v, lam


def _empty(G=2, d=128):
    return (jnp.zeros((G, retention.diagonals(d), d, d)),
            jnp.zeros((G, retention.norm_rows(d), d)))


@pytest.mark.parametrize("d", [128, 16, 2])
def test_phi_is_the_symmetric_square(d):
    q, k = jax.random.normal(jax.random.key(d), (2, 7, d))
    fq, fk = retention.phi(q), retention.phi(k)
    assert fq.shape == (7, d // 2 + 1, d)  # 8,320 places for 8,256 products at 128
    f64 = lambda x: np.asarray(x, np.float64)  # 8,320 terms of either sign: summed in float64
    want = np.sum(f64(q) * f64(k), axis=-1) ** 2
    np.testing.assert_allclose(np.sum(f64(fq) * f64(fk), axis=(-1, -2)), want, rtol=1e-6, atol=1e-4)


def _quadratic(q, k, v, lam):
    """The first form: every position over all earlier ones."""
    T, H, d = q.shape
    group = H // k.shape[1]
    kk, vv = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    since = jnp.repeat(jnp.cumsum(lam, axis=0), group, axis=1).T  # [H, T]
    power = jnp.einsum("thd,jhd->htj", q, kk, precision="highest") ** 2 / d
    earlier = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = jnp.where(earlier, power * jnp.exp(
        jnp.where(earlier, since[:, :, None] - since[:, None, :], 0.0)), 0.0)
    return jnp.einsum("htj,jhd->thd", a, vv, precision="highest") / (
        jnp.sum(a, axis=-1).T[..., None] + retention.EPS)


def test_the_recurrence_equals_the_quadratic_form():
    q, k, v, lam = _inputs(96)
    got, _S, _z = retention.recurrent_retention(q, k, v, lam, *_empty())
    np.testing.assert_allclose(got, _quadratic(q, k, v, lam), atol=1e-4)  # 8,320 float32 terms a read-out
    assert float(jnp.exp(lam).min()) < 0.6 and float(jnp.exp(lam).max()) > 0.999


@pytest.mark.parametrize("T,real", [(64, 64), (64, 41), (512, 300), (16, 5)])
def test_prefill_equals_the_recurrence_and_padding_moves_nothing(T, real):
    """The blocked quadratic sum and the state after the last REAL position: a
    bucket's padding has its keys and log-decay zeroed."""
    q, k, v, lam = _inputs(T, seed=T + real)
    valid = jnp.arange(T) < real
    k, lam = jnp.where(valid[:, None, None], k, 0.0), jnp.where(valid[:, None], lam, 0.0)
    want_o, want_S, want_z = retention.recurrent_retention(
        q[:real], k[:real], v[:real], lam[:real], *_empty())
    got_o, got_S, got_z = retention.retention_prefill(q, k, v, lam, dtype=jnp.float32)
    # float32 on both sides: a read-out of the recurrence is 8,320 terms of either
    # sign, 1e-5 of their size 128 apart, under a weight that may be 0.01
    np.testing.assert_allclose(got_o[:real], want_o, atol=2e-3)
    np.testing.assert_allclose(got_S, want_S, rtol=1e-4, atol=1e-4 * float(jnp.abs(want_S).max()))
    np.testing.assert_allclose(got_z, want_z, rtol=1e-4, atol=1e-4 * float(jnp.abs(want_z).max()))
    assert not np.asarray(got_z)[:, retention.diagonals(128):].any()  # the tiles' spare rows


# a block edge of the kernels (256) from below, at and above; the whole bucket
@pytest.mark.parametrize("T,real", [
    (512, 255), (512, 256), (512, 257), (512, 512),
    (1024, 511), (1024, 512), (1024, 513), (1024, 769), (1024, 1024)])
def test_prefill_with_a_length_neither_reads_nor_counts_what_lies_past_it(T, real):
    """``length`` in place of zeroed keys and log-decay: the rows past it are
    NaN in q, k and v and reach neither the live rows of ``o`` nor state nor
    normaliser; the blocks wholly past it are not visited at all."""
    q, k, v, lam = _inputs(T, seed=T + real)
    valid = jnp.arange(T) < real
    _o, want_S, want_z = retention.recurrent_retention(
        q[:real], k[:real], v[:real], lam[:real], *_empty())
    # the read-outs against the first form itself: the recurrence's are 8,320 terms of
    # either sign, a hundredth off where a position's weights sum to 1e-4
    want_o = _quadratic(q[:real], k[:real], v[:real], lam[:real])
    masked_o, masked_S, masked_z = retention.retention_prefill(  # the call of before
        q, jnp.where(valid[:, None, None], k, 0.0), v, jnp.where(valid[:, None], lam, 0.0),
        dtype=jnp.float32)
    poison = lambda x: jnp.where(valid[:, None, None], x, jnp.nan)
    got_o, got_S, got_z = retention.retention_prefill(
        poison(q), poison(k), poison(v), lam, length=jnp.int32(real), dtype=jnp.float32)
    np.testing.assert_allclose(got_o[:real], want_o, atol=1e-4)
    np.testing.assert_allclose(got_S, want_S, rtol=1e-4, atol=1e-4 * float(jnp.abs(want_S).max()))
    np.testing.assert_allclose(got_z, want_z, rtol=1e-4, atol=1e-4 * float(jnp.abs(want_z).max()))
    # the same blocks in the same order: the masked call's sums, to the last bit
    np.testing.assert_array_equal(got_o[:real], masked_o[:real])
    np.testing.assert_array_equal(got_S, masked_S)
    np.testing.assert_array_equal(got_z, masked_z)


@pytest.mark.parametrize("active", [(True, False, True, True), (False,) * 4, (True,) * 4])
def test_decode_kernel_in_interpret_mode_equals_the_jnp_step(active):
    q, k, v, lam = _inputs(4, seed=3)
    state = jax.random.normal(jax.random.key(5), (4, 3, 2, 65, 128, 128))
    norm = jnp.abs(jax.random.normal(jax.random.key(6), (4, 3, 2, 72, 128))) * 50.0
    active = jnp.asarray(active)
    want = retention.retention_step(q, k, v, lam, state, norm, 1, active)
    got = retention.retention_decode(q, k, v, lam, state, norm, jnp.int32(1), active)
    for g, w, tol in zip(got, want, (1e-5, 1e-5, 1e-4)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=tol)
    assert not np.asarray(got[0])[~np.asarray(active)].any()
    # other layers and the slots nobody holds are bit for bit what they were
    idle = np.nonzero(~np.asarray(active))[0]
    for new, old in ((got[1], state), (got[2], norm)):
        np.testing.assert_array_equal(np.asarray(new)[idle], np.asarray(old)[idle])
        np.testing.assert_array_equal(np.asarray(new)[:, [0, 2]], np.asarray(old)[:, [0, 2]])


def test_the_jnp_step_is_the_recurrence():
    q, k, v, lam = _inputs(5, seed=4)
    S0 = jax.random.normal(jax.random.key(6), (2, 65, 128, 128))
    z0 = jnp.abs(jax.random.normal(jax.random.key(8), (2, 72, 128))).at[:, 65:].set(0.0) * 50.0
    want_o, want_S, want_z = retention.recurrent_retention(q, k, v, lam, S0, z0)
    state, norm = S0[None, None], z0[None, None]  # one slot, one layer
    for t in range(5):
        o, state, norm = retention.retention_step(
            q[t:t + 1], k[t:t + 1], v[t:t + 1], lam[t:t + 1], state, norm, 0, jnp.ones((1,), bool))
        np.testing.assert_allclose(o[0], want_o[t], atol=1e-5)
    np.testing.assert_allclose(state[0, 0], want_S, atol=1e-4)
    np.testing.assert_allclose(norm[0, 0], want_z, atol=1e-4)


# ------------------------------------------------------ the model, whole
def test_prefill_path_matches_the_reference(model, params):
    toks = _tokens(512, seed=1)  # two blocks of the quadratic kernel
    got = _highest(jax.jit(model.logits), params, toks)
    want = _highest(ref.logits, params, toks, CFG)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def _decode_against_reference(model, params, lengths=(127, 129, 40), steps=6, hook=None):
    """Teacher-forced: prefill ``lengths[s]`` tokens of sequence s in its
    bucket (127 and 129 lie one short of and one past the edge of the bucket
    of 128: the padding must move neither state nor normaliser), then decode
    ``steps`` tokens through the state a slot.  Returns the largest |decode
    logit - reference logit| over all steps and slots.  ``hook(cache) ->
    cache`` runs between steps (a planted fault)."""
    S = len(lengths)
    bucket = lambda n: max(64, 1 << (n - 1).bit_length())
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), model.state_spec(S))
    seqs = [_tokens(n + steps, seed=s) for s, n in enumerate(lengths)]
    prefill = jax.jit(model.prefill, static_argnums=3)
    for s, n in enumerate(lengths):
        rows, _logits, counters = _highest(
            prefill, params, jnp.pad(seqs[s][:n], (0, bucket(n) - n))[None], jnp.int32(n), 16)
        assert counters.shape == (1,) and int(counters[0]) == bucket(n)  # one row tile or less
        cache = model.write_state(cache, rows, s)
    want = [_highest(ref.logits, params, seq, CFG) for seq in seqs]
    decode = jax.jit(model.decode)
    worst = 0.0
    for t in range(steps):
        lens = jnp.asarray([n + t for n in lengths], jnp.int32)
        tok = jnp.stack([seqs[s][n + t] for s, n in enumerate(lengths)])
        got, cache, counters = _highest(
            decode, params, cache, tok, PagedState(None, lens, jnp.ones((S,), bool)))
        assert counters.shape == (1,) and int(counters[0]) == S
        if hook is not None:
            cache = hook(cache)
        for s, n in enumerate(lengths):
            worst = max(worst, float(jnp.max(jnp.abs(got[s] - want[s][n + t]))))
    return worst


def test_prefill_then_decode_matches_the_reference_at_a_buckets_edges(model, params):
    assert _decode_against_reference(model, params) < TOL


def test_a_bfloat16_state_fails_the_tolerance(model, params):
    """The nearest precision below the one the configuration states for the
    state: rounded to bfloat16 after every step."""
    rounded = lambda cache: {**cache, "state": cache["state"].astype(jnp.bfloat16).astype(jnp.float32)}
    assert _decode_against_reference(model, params, hook=rounded) > 10 * TOL


# ------------------------------------------- a prefill without its padding
@pytest.mark.parametrize("bucket,lengths", [
    (128, (1, 63, 64, 65, 128)),                           # two row tiles of 64
    (256, (64, 65, 128, 129, 192, 193, 255, 256)),         # four
])
def test_prefill_over_live_row_tiles_equals_the_one_call_path(model, params, monkeypatch, bucket, lengths):
    """The position-wise work a tile of 64 rows at a time, up to the prompt's
    length: state, normaliser and logits are the whole bucket's in one call
    (the module's own tile holds it), whatever token ids lie past ``tp``."""
    from moolib_tpu.models import retention_lm

    whole, tiled = (jax.jit(lambda p, toks, tp: model.prefill(p, toks, tp, 16)) for _ in range(2))

    def call(fn, tile, toks, tp):
        monkeypatch.setattr(retention_lm, "_ROW_TILE", tile)  # read where the call is traced
        return _highest(fn, params, toks[None], jnp.int32(tp))

    seq = _tokens(bucket, seed=bucket)
    for tp in lengths:
        pad = jnp.arange(bucket) >= tp
        want_rows, want_logits, want_count = call(whole, 512, jnp.where(pad, 0, seq), tp)
        got_rows, got_logits, got_count = call(tiled, 64, jnp.where(pad, 383 - seq, seq), tp)
        assert int(want_count[0]) == bucket and int(got_count[0]) == -(-tp // 64) * 64
        assert float(jnp.max(jnp.abs(got_logits - want_logits))) < TOL
        for name in ("state", "norm"):
            scale = float(jnp.abs(want_rows[name]).max())
            np.testing.assert_allclose(got_rows[name], want_rows[name], rtol=1e-5, atol=1e-5 * scale)
    assert whole._cache_size() == 1 and tiled._cache_size() == 1  # the length is data


# -------------------------------------------------------- through the engine
def _engine(model, params, slots=3, **kw):
    return ContinuousBatchingEngine(
        model, params, slots=slots, block_size=16, max_seq_len=256, max_prompt_len=128,
        min_prompt_len=33, **kw)


def _run(eng, requests):
    """Submit all, then step to the end.  Returns {index: emitted}."""
    live, out = {}, {}
    for i, (prompt, budget) in enumerate(requests):
        slot, _emitted = eng.submit(prompt, budget)
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
        assert eng.warmup() == 2 + 1 + 1  # buckets 64 and 128, ONE join, the step
        requests = [(np.asarray(_tokens(n, seed=20 + n)), b)
                    for n, b in ((63, 7), (65, 5), (120, 9))]
        out = _run(eng, requests)
        assert eng._step_jit._cache_size() == 1 and eng._join_jit._cache_size() == 1
    for i, (prompt, _b) in enumerate(requests):
        # Every emitted token is the reference's argmax, up to a near tie.
        assert len(out[i]) == requests[i][1] and _gaps(params, prompt, out[i]).max() < TOL


def test_a_slot_is_reused_and_a_join_that_writes_no_state_is_seen(model, params):
    """Two requests through ONE slot: the second decodes from its own prompt's
    state.  With the join's ``write_state`` planted out it decodes from the
    first one's, and the reference's gap shows it."""
    requests = [(np.asarray(_tokens(70, seed=31)), 6), (np.asarray(_tokens(50, seed=32)), 6)]

    class NoStateWrite(PowerRetentionLM):
        def write_state(self, cache, rows, slot):
            return cache

    worst = {}
    for name, m in (("sound", model), ("planted", NoStateWrite.from_config(
            CFG, dtype=jnp.float32, max_len=512))):
        with jax.default_matmul_precision("highest"):
            eng = _engine(m, params, slots=1)
            first = _run(eng, requests[:1])[0]
            second = _run(eng, requests[1:])[0]
        worst[name] = max(_gaps(params, requests[0][0], first).max(),
                          _gaps(params, requests[1][0], second).max())
    assert worst["sound"] < TOL < 100 * TOL < worst["planted"]


def test_the_engine_hands_the_rows_computed_to_observe_prefill(params, monkeypatch):
    """``prefill_counters`` = 1: whole row tiles up to the prompt, riding the
    first token's vector home, into ``serve_prefill_rows_computed_total``."""
    from moolib_tpu import telemetry
    from moolib_tpu.models import retention_lm

    monkeypatch.setattr(retention_lm, "_ROW_TILE", 32)  # buckets of 64 and 128: two and four tiles
    seen = []

    class Spy(PowerRetentionLM):
        def observe_prefill(self, counters, prompt_len):
            seen.append((int(counters[0]), prompt_len))
            super().observe_prefill(counters, prompt_len)

    rows = lambda: telemetry.get_registry().counter_values().get(
        "serve_prefill_rows_computed_total", 0)
    before = rows()
    with jax.default_matmul_precision("highest"):
        eng = _engine(Spy.from_config(CFG, dtype=jnp.float32, max_len=512), params)
        requests = [(np.asarray(_tokens(n, seed=40 + n)), b)
                    for n, b in ((63, 3), (65, 2), (97, 2), (20, 2))]
        out = _run(eng, requests[:3]) | {3: _run(eng, requests[3:])[0]}
    assert sorted(seen) == [(32, 20), (64, 63), (96, 65), (128, 97)]
    assert rows() - before == 32 + 64 + 96 + 128
    stats = eng.stats()
    assert stats["prefill_tokens"] + stats["prefill_pad_tokens"] == 64 + 128 + 128 + 64
    for i, (prompt, budget) in enumerate(requests):
        assert len(out[i]) == budget and _gaps(params, prompt, out[i]).max() < TOL
