"""An admission that rides a decode step (moolib_tpu/engine/engine.py) — ISSUE 57.

A model that offers ``decode_with_prompt`` (``PagedTransformerLM``) has its
joins carried by the next step: ``engine_admit_step`` is a decode step over
the slots already active AND the prefill and join of one prompt, so a weight
matrix is read once where ``engine_prefill`` and ``engine_decode`` read it
twice.  What must hold:

- the model's form equals ``decode`` and ``prefill`` run apart: the decode
  rows' logits, the prompt's logits at ``tp - 1``, the pools after
  ``write_rows``;
- the SAME requests through an engine whose model offers the form and one
  whose model hides it (``conftest.own_programs``) emit identical tokens,
  whatever is in flight; under the form every join rides a step, a budget of
  1 too, and no first token is booked more than one ``step()`` later than
  programs of its own would have had it, however many arrive in one pass;
- after ``warmup()`` nothing compiles, whatever bucket arrives;
- the service observes a first token at the ``step()`` that books it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import own_programs
from moolib_tpu import telemetry
from moolib_tpu.models import transformer
from moolib_tpu.engine import ContinuousBatchingEngine, EngineService
from moolib_tpu.engine.engine import NoFreeSlot
from moolib_tpu.engine.kv_pool import PoolExhausted
from moolib_tpu.models.transformer import PagedTransformerLM, TransformerLM
from moolib_tpu.ops.paged_attention import PagedState

V = 64


def _lm(pos="rotary", attention="dense", kv_heads=2, d_model=32, heads=4, max_len=64):
    model = TransformerLM(vocab_size=V, d_model=d_model, num_heads=heads, num_kv_heads=kv_heads,
                          num_layers=2, max_len=max_len, attention=attention,
                          dtype=jnp.float32, pos_embedding=pos)
    return model, model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def lm():
    return _lm()


# ------------------------------------------------------------------ the model
def _three_slots(paged_lm, bucket, block):
    """Three slots, one of them inactive, at their own lengths in shuffled
    blocks of a pool that holds noise; a prompt's ``bucket`` tokens and the
    blocks they are written to."""
    slots, per = 3, max(16, bucket // block)
    cache = jax.tree.map(lambda leaf: jax.random.normal(jax.random.key(2), leaf.shape, leaf.dtype),
                         paged_lm.cache_spec(1 + slots * per, block))
    rng = np.random.default_rng(0)
    tables = jnp.asarray(1 + rng.permutation(slots * per).reshape(slots, per), jnp.int32)
    paged = PagedState(tables, jnp.asarray([5, 0, 9], jnp.int32),
                       jnp.asarray([True, False, True]))
    toks = jnp.asarray(rng.integers(1, V, (1, bucket)), jnp.int32)
    written = jnp.asarray(slots * per - np.arange(bucket // block), jnp.int32)
    return cache, paged, jnp.asarray([3, 7, 9], jnp.int32), toks, written


@pytest.mark.parametrize("pos,attention,kv_heads,tp,bucket,heads", [
    ("learned", "dense", None, 11, 16, 4),  # learned positions, every head its own K/V, a padded bucket
    ("learned", "dense", 2, 16, 16, 4),     # Hk < H, a full bucket
    ("rotary", "dense", 2, 11, 16, 4),
    ("rotary", "dense", None, 1, 16, 4),    # a prompt of one token
    ("learned", "flash", 2, 11, 16, 4),
    ("rotary", "flash", 2, 16, 16, 4),
    # Buckets that ride the flash kernel under a model built with
    # attention="dense", as the cells build theirs (the adapter's threshold set
    # to the kernel's own 128 rows for all but the last): heads of 8 through
    # head-major copies, heads of 128 (d_model 256) where the operands lie
    # (learned positions: the packed projection itself); 192 does not tile.
    ("learned", "dense", None, 100, 128, 4),
    ("learned", "dense", 2, 128, 128, 4),    # tp equal to the bucket
    ("rotary", "dense", 2, 150, 192, 4),
    ("rotary", "dense", None, 192, 192, 4),
    ("learned", "dense", 1, 37, 192, 2),     # in place, packed
    ("rotary", "dense", 2, 130, 192, 2),     # in place, three operands
    ("learned", "dense", 2, 1, 128, 2),
    ("learned", "dense", 2, 700, 1104, 4),   # the threshold as shipped: more than 1,024 rows
])
def test_decode_with_prompt_equals_decode_and_prefill_run_apart(
        monkeypatch, pos, attention, kv_heads, tp, bucket, heads):
    """A prompt of ``tp`` tokens in a bucket of ``bucket`` beside three slots:
    one pass returns what ``decode`` and ``prefill`` return run apart, and
    the prompt's logits are the model's own (``model.attention``'s dense
    scores) over the ``tp`` real tokens alone."""
    if bucket <= 1024:
        monkeypatch.setattr(transformer, "_DENSE_PROMPT_ROWS", 127)
    model, params = _lm(pos, attention, kv_heads, d_model=32 if heads == 4 else 256,
                        heads=heads, max_len=max(256, bucket))
    paged_lm = PagedTransformerLM(model)
    assert paged_lm.prompt_attention_kernel(bucket) == ("dense" if bucket == 16 else "flash")
    block = 4 if bucket == 16 else 16
    cache, paged, tokens, toks, written = _three_slots(paged_lm, bucket, block)

    logits, stepped, _ = paged_lm.decode(params, cache, tokens, paged)
    rows, at_last, _ = paged_lm.prefill(params, toks, jnp.int32(tp), block)
    both = paged_lm.decode_with_prompt(params, cache, tokens, paged, toks, jnp.int32(tp), block)
    close = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(both[0], logits, **close)
    np.testing.assert_allclose(both[1], at_last, **close)
    for got, want in zip(jax.tree.leaves(paged_lm.write_rows(both[2], both[3], written)),
                         jax.tree.leaves(paged_lm.write_rows(stepped, rows, written))):
        np.testing.assert_allclose(got, want, **close)
    assert int(jnp.argmax(both[1])) == int(jnp.argmax(at_last))
    own = model.apply(params, toks[:, :tp])[0, tp - 1]
    np.testing.assert_allclose(both[1], own, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("form", ["decode_with_prompt", "prefill"])
@pytest.mark.parametrize("pos", ["learned", "rotary"])
def test_the_rows_written_for_a_buckets_padding_are_finite(monkeypatch, form, pos):
    """The flash kernel writes the padding's rows as zeros, so what flows on
    from them (the projection, the feed-forward, the next layer's K/V, which
    ``write_rows`` puts into the pool's blocks) is finite at every position
    of the bucket, and the real positions' K/V are what a prompt in a bucket
    of its own length gives."""
    monkeypatch.setattr(transformer, "_DENSE_PROMPT_ROWS", 127)
    model, params = _lm(pos, "dense", 2, max_len=256)
    paged_lm = PagedTransformerLM(model)
    cache, paged, tokens, toks, _ = _three_slots(paged_lm, 192, 16)
    tp = 37
    if form == "prefill":
        rows = paged_lm.prefill(params, toks, jnp.int32(tp), 16)[0]
    else:
        rows = paged_lm.decode_with_prompt(params, cache, tokens, paged, toks, jnp.int32(tp), 16)[3]
    tight = paged_lm.prefill(params, toks[:, :tp], jnp.int32(tp), 1)[0]
    for got, want in zip(rows, tight):  # K, then V: [layers, blocks, block, Hk, hd]
        got = np.asarray(got).reshape(2, 192, 2, 8)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got[:, :tp], np.asarray(want)[:, :, 0], rtol=1e-5, atol=1e-5)


def test_the_forms_mode_is_a_mode_of_paged_decode():
    model, params = _lm()
    twin = TransformerLM(vocab_size=V, d_model=32, num_heads=4, num_kv_heads=2, num_layers=2, max_len=64,
                         attention="dense", dtype=jnp.float32, pos_embedding="rotary",
                         prompt_rows=8)
    with pytest.raises(ValueError, match="prompt_rows is a mode of paged decode"):
        twin.apply({"params": params["params"]}, jnp.zeros((1, 8), jnp.int32))


# ----------------------------------------------------------------- the engine
def _engine(lm, form, slots=3, max_prompt_len=16, **kw):
    model, params = lm
    return ContinuousBatchingEngine(model if form else own_programs(model), params, slots=slots,
                                    block_size=4, max_seq_len=64, max_prompt_len=max_prompt_len,
                                    **kw)


def _play(eng, reqs, passes, retire_early=()):
    """``passes``: for each ``step()``, the requests (indices) that arrive
    before it, submitted in order as slots come free; then steps until
    everything has finished.  A request in ``retire_early`` is retired right
    after its ``submit``; one the pool cannot hold is dropped.  Returns each
    request's emitted tokens, and for each request how many ``step()`` calls
    after its ``submit`` its first token was booked (1: the next; 0:
    ``submit`` or ``retire`` did)."""
    outs, live, first_at, refused = {}, {}, {}, []
    waiting, queue = {}, []
    for n in range(200):
        queue += passes[n] if n < len(passes) else ()
        while queue:
            i = queue[0]
            try:
                slot, emitted = eng.submit(*reqs[i])
            except NoFreeSlot:
                break
            except PoolExhausted:
                refused.append(queue.pop(0))
                continue
            queue.pop(0)
            if slot is None:
                outs[i], first_at[i] = list(emitted), 0
            elif i in retire_early:
                outs[i], first_at[i] = eng.retire(slot), 0
            else:
                live[slot], waiting[i] = i, (emitted, n)
        if n >= len(passes) and not live and not queue:
            break
        _, finished = eng.step()
        for i in [i for i, (emitted, _) in waiting.items() if emitted]:
            first_at[i] = n + 1 - waiting.pop(i)[1]
        for slot in finished:
            outs[live.pop(slot)] = eng.retire(slot)
    assert not live, "engine never drained"
    return outs, first_at, refused


def _requests(shapes, seed=11):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, V, size=n).astype(np.int32), budget) for n, budget in shapes]


# name -> (prompt length, budget) a request; the requests of each pass
SCENARIOS = {
    "behind_steps_in_flight": ([(5, 9), (9, 4), (3, 6), (12, 3)],
                               [[0], [], [1], [], [2], [], [], [], [3]]),
    "into_an_empty_engine": ([(7, 5)], [[0]]),
    "two_in_one_pass": ([(5, 9), (9, 4), (3, 6)], [[0], [], [1, 2]]),
    "three_in_one_pass": ([(4, 8), (7, 4), (13, 6)], [[0, 1, 2]]),
    "three_in_one_pass_behind_a_step": ([(5, 9), (4, 8), (7, 4), (13, 6)], [[0], [], [1, 2, 3]]),
    "a_budget_of_one": ([(5, 6), (12, 1), (9, 3)], [[0], [1, 2]]),
    "budgets_of_one_alone": ([(5, 1), (12, 1)], [[0], [], [1]]),
    "every_length": ([(1, 3), (2, 3), (3, 4), (5, 3), (9, 3), (16, 2)],
                     [[0], [1], [2], [3], [4], [5]]),
}


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_the_same_requests_emit_the_same_tokens_on_either_path(lm, name):
    shapes, passes = SCENARIOS[name]
    reqs = _requests(shapes)
    eng, plain = _engine(lm, True, slots=4), _engine(lm, False, slots=4)
    outs, first_at, _ = _play(eng, reqs, passes)
    want, want_first_at, _ = _play(plain, reqs, passes)
    assert outs == want and sorted(outs) == list(range(len(reqs)))
    assert all(len(outs[i]) == budget for i, (_, budget) in enumerate(reqs))
    stats = eng.stats()
    # every join rode a step, a budget of 1 too (which joins nothing otherwise)
    assert stats["admissions_by_path"] == {"step": len(reqs), "own": 0}
    assert len(reqs) == stats["joins"] == stats["joins_ahead"]
    assert eng._prefill_jit.seq == eng._join_jit.seq == 0
    ones = sum(budget == 1 for _, budget in reqs)
    assert plain.stats()["admissions_by_path"] == {"step": 0, "own": len(reqs) - ones}
    # No first token comes more than one step later than its own programs
    # would have it.
    assert all(first_at[i] - want_first_at[i] in (0, 1) for i in first_at), (first_at, want_first_at)
    assert stats["row_overflows"] == 0 and eng._flights == [] and eng._admission is None
    eng.pool.check_invariants()
    assert eng.pool.available() == eng.pool.num_blocks - 1


@pytest.mark.parametrize("in_flight", [False, True])
def test_a_first_token_that_is_eos_finishes_the_slot_once(lm, in_flight):
    """The step that carries the admission leaves the slot dark on the
    device; the ``step()`` that books its packet reports the slot finished,
    once, and the slot never decodes."""
    model, params = lm
    prompt = np.asarray([42, 4, 61, 36, 57, 18], np.int32)
    other = np.asarray([62, 4, 18, 25], np.int32)
    probe = _engine(lm, False)
    slot, _ = probe.submit(prompt, 2)
    eos = probe.retire(slot)[0]
    reqs = ([(other, 9)] if in_flight else []) + [(prompt, 12)]
    passes = [[0], [1]] if in_flight else [[0]]
    eng = _engine(lm, True, eos_id=eos)
    outs, _, _ = _play(eng, reqs, passes)
    want, _, _ = _play(_engine(lm, False, eos_id=eos), reqs, passes)
    assert outs == want and outs[len(reqs) - 1] == [eos]
    stats = eng.stats()
    assert stats["admissions_by_path"]["step"] == stats["retires"] == len(reqs)
    assert stats["decode_tokens"] == (len(outs[0]) - 1 if in_flight else 0)
    eng.pool.check_invariants()


@pytest.mark.parametrize("when", ["recorded", "in_flight", "beside_a_neighbour"])
def test_retire_before_any_step_returns_the_first_token(lm, when):
    """Recorded and never carried, the admission's step goes out at the
    ``retire``; carried by a step in flight, its token is read from that
    step's packet, which is then booked without it."""
    reqs = _requests([(9, 4), (5, 7)])
    plain = _engine(lm, False)
    slot, _ = plain.submit(*reqs[0])
    want = plain.retire(slot)
    eng = _engine(lm, True)
    if when == "recorded":
        outs, _, _ = _play(eng, reqs, [[0]], retire_early={0})
        assert eng.stats()["admissions_by_path"] == {"step": 1, "own": 0}
    else:
        live = {}
        if when == "beside_a_neighbour":
            neighbour, _ = eng.submit(*reqs[1])
            eng.step()
            eng.step()
            slot, emitted = eng.submit(*reqs[0])
            eng.step()  # books the step that was in flight, dispatches the one that carries
        else:
            slot, emitted = eng.submit(*reqs[0])
            eng._dispatch(eng._expected())  # as a step() into an empty engine, booking nothing
        assert eng._flights[-1][3] == slot and emitted == []
        outs = {0: eng.retire(slot)}
        assert eng._flights[-1][3] is None and outs[0] is emitted
        eng._active_host[slot] = False  # the caller dropped the request
        if when == "beside_a_neighbour":
            live[neighbour] = 1
            while live:
                # (the device still decodes the slot dropped: the neighbour's
                # tokens must not care)
                for s in eng.step()[1]:
                    if s in live:
                        outs[live.pop(s)] = eng.retire(s)
            ref, _, _ = _play(_engine(lm, False), reqs, [[1]])
            assert outs[1] == ref[1]
    assert outs[0] == want and len(want) == 1
    eng.close()
    eng.pool.check_invariants()


@pytest.mark.parametrize("form", [True, False])
def test_pool_exhaustion_at_submit_launches_nothing_and_leaves_the_slot_free(lm, form):
    model, params = lm
    eng = ContinuousBatchingEngine(model if form else own_programs(model), params, slots=3,
                                   block_size=4, num_blocks=12, max_seq_len=64, max_prompt_len=16)
    reqs = _requests([(5, 6), (5, 40), (3, 5)])
    free = list(eng._free_slots)
    outs, _, refused = _play(eng, reqs, [[0], [1], [2]])
    assert refused == [1] and sorted(outs) == [0, 2]
    want, _, _ = _play(_engine(lm, False), [reqs[0], reqs[2]], [[0], [], [1]])
    assert [outs[0], outs[2]] == [want[0], want[1]]
    assert sorted(eng._free_slots) == sorted(free) and eng._admission is None
    assert eng.stats()["joins"] == 2 and eng._prefill_jit.seq == (0 if form else 2)
    eng.pool.check_invariants()


class _RowsLM:
    """A paged transformer whose class says it decodes rows (as in
    ``tests/test_engine_ahead.py``), with or without the form."""

    step_counters = 0
    prefill_counters = 0
    decodes_rows = True

    def __init__(self, model, form):
        self._inner = PagedTransformerLM(model) if form else own_programs(model)
        self.max_len = model.max_len

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_an_admission_rides_a_step_of_fewer_rows_than_slots(lm):
    """256 slots over a model that decodes rows: the step that carries an
    admission runs over 128 rows gathered on the device like any other, and
    the tokens are those of the engine without the form."""
    model, params = lm
    reqs = _requests([(n % 7 + 2, 3 + n % 4) for n in range(9)], seed=5)
    passes = [[0, 1, 2], [3], [], [4, 5], [6], [7], [], [8]]
    engines = [ContinuousBatchingEngine(_RowsLM(model, form), params, slots=256, block_size=4,
                                        max_seq_len=32, max_prompt_len=8) for form in (True, False)]
    (outs, _, _), (want, _, _) = (_play(eng, reqs, passes) for eng in engines)
    assert outs == want
    stats = engines[0].stats()
    assert stats["steps_by_rows"][256] == 0 and stats["steps_by_rows"][128] == stats["steps"]
    assert stats["admissions_by_path"] == {"step": 9, "own": 0} and stats["row_overflows"] == 0


def test_the_dispatch_span_says_program_seq_rows_bucket_tokens_and_slot(lm):
    eng = _engine(lm, True, max_prompt_len=32)
    reqs = _requests([(5, 4), (23, 3)])
    telemetry.get_tracer().clear()
    _play(eng, reqs, [[0], [], [1]])
    spans = telemetry.get_tracer().spans()
    carried = [s.args for s in spans if s.name == "engine.admit_step_dispatch"]
    assert [{k: a[k] for k in ("program", "seq", "rows", "bucket", "tokens")} for a in carried] == [
        {"program": "engine_admit_step", "seq": 0, "rows": 3, "bucket": 16, "tokens": 5},
        {"program": "engine_admit_step", "seq": 1, "rows": 3, "bucket": 32, "tokens": 23}]
    assert all(a["slot"] in range(3) for a in carried)
    names = {s.name for s in spans}
    assert not names & {"engine.prefill_dispatch", "engine.join", "engine.first_token_fetch"}
    steps = [s.args["seq"] for s in spans if s.name == "engine.step_dispatch"]
    assert steps == list(range(len(steps))) and eng._step_jit.seq == len(steps)


# ------------------------------------------------------------------- warm-up
def _compiles():
    family = telemetry.get_registry().snapshot().get("jit_compiles_total", {"series": []})
    return {s["labels"]["fn"]: s["value"] for s in family["series"]}


def test_after_warmup_no_program_compiles_whatever_bucket_arrives(lm):
    """A prompt carried by a step fills a bucket of at least 16 rows, and no
    admission takes another program: warm-up builds the step that carries
    one, a bucket (16, 32), and the decode step, and no prefill and no join."""
    eng = _engine(lm, True, slots=8, max_prompt_len=32)
    assert eng.warmup() == 2 + 1
    jits = (eng._step_jit, eng._prefill_jit, eng._join_jit, eng._admit_jit)
    sizes = [j._cache_size() for j in jits]
    assert sizes == [1, 0, 0, 2]
    compiled = _compiles()
    assert eng._flights == [] and eng.active_count() == 0
    reqs = _requests([(n, 1 + n % 4) for n in range(1, 33)], seed=3)
    # one a pass, then pairs and triples: every length, every budget from 1
    passes = ([[i] for i in range(12)] + [[12, 13], [], [14, 15, 16], [], [17, 18, 19]]
              + [[i] for i in range(20, 32)])
    outs, _, _ = _play(eng, reqs, passes)
    assert [j._cache_size() for j in jits] == sizes and _compiles() == compiled
    want, _, _ = _play(_engine(lm, False, slots=8, max_prompt_len=32), reqs, passes)
    assert outs == want
    stats = eng.stats()
    assert stats["row_overflows"] == 0
    assert stats["admissions_by_path"] == {"step": 32, "own": 0} and stats["joins"] == 32
    eng.pool.check_invariants()


def test_the_counter_family_has_one_series_a_path(lm):
    def series():
        family = telemetry.get_registry().snapshot()["serve_engine_admissions_total"]["series"]
        return {s["labels"]["path"]: s["value"] for s in family}

    before = series() if "serve_engine_admissions_total" in telemetry.get_registry().snapshot() else {}
    reqs = _requests([(4, 3), (6, 3), (5, 3)])
    _play(_engine(lm, True), reqs, [[0, 1, 2]])
    _play(_engine(lm, False), reqs[:2], [[0, 1]])
    after = series()
    assert set(after) == {"step", "own"}
    assert after["step"] - before.get("step", 0) == 3 and after["own"] - before.get("own", 0) == 2


@pytest.mark.parametrize("form", [True, False])
def test_prompt_rows_are_counted_under_the_kernel_their_bucket_takes(monkeypatch, form):
    """``serve_prompt_attention_rows_total{kernel}``: each prompt's REAL
    tokens, under ``flash`` where its bucket has the rows from which the
    adapter takes the kernel (more than 1,024 as shipped: 128, the least the
    kernel takes, here) and ``dense`` below, whichever program carried the
    admission."""
    kernel = PagedTransformerLM.prompt_attention_kernel
    assert [kernel(b) for b in (16, 128, 512, 1024, 1104, 1984, 2048)] == [
        "dense", "dense", "dense", "dense", "flash", "flash", "flash"]
    monkeypatch.setattr(transformer, "_DENSE_PROMPT_ROWS", 127)
    def series():
        family = telemetry.get_registry().snapshot().get(
            "serve_prompt_attention_rows_total", {"series": []})
        return {s["labels"]["kernel"]: s["value"] for s in family["series"]}

    model, params = _lm(max_len=256)
    eng = ContinuousBatchingEngine(model if form else own_programs(model), params, slots=3,
                                   block_size=16, max_seq_len=256, max_prompt_len=192)
    before = series()
    rng = np.random.default_rng(5)
    # buckets of 16, 128 and 192 (the cap: it does not tile) rows
    outs = _play(eng, [(rng.integers(1, V, n), 2) for n in (5, 100, 150)], [[0, 1, 2]])[0]
    assert [len(outs[i]) for i in range(3)] == [2, 2, 2]
    after = series()
    assert after["dense"] - before.get("dense", 0) == 5
    assert after["flash"] - before.get("flash", 0) == 250


# ---------------------------------------------------------------- the service
def _first_tokens():
    family = telemetry.get_registry().snapshot().get("serve_phase_seconds", {"series": []})
    return sum(s["value"]["count"] for s in family["series"]
               if s["labels"]["phase"] == "first_token")


@pytest.mark.parametrize("form", [True, False])
def test_the_service_observes_a_first_token_at_the_step_that_books_it(lm, form):
    from test_program_spans import _Ret, _Rpc

    service = EngineService(_Rpc(), _engine(lm, form), default_max_new=4)
    rets = [_Ret(), _Ret()]
    reqs = _requests([(5, 8), (7, 3)])
    service._on_request(rets[0], reqs[0][0], reqs[0][1])
    assert service._admit_joins() == (1, 0)
    service._decode_and_reply()
    service._decode_and_reply()
    assert service._first_due == []  # a step is in flight, the first request decoding
    service._on_request(rets[1], reqs[1][0], reqs[1][1])
    seen = _first_tokens()
    assert service._admit_joins() == (1, 0)
    (_, emitted), = service._first_due
    service._decode_and_reply()
    if form:
        # The step booked was in flight at the submit; the one dispatched now
        # carries the admission, and its packet the token.
        assert emitted == [] and _first_tokens() == seen and len(service._first_due) == 1
        service._decode_and_reply()
        assert len(emitted) == 1
    else:
        assert len(emitted) == 1
    assert _first_tokens() == seen + 1 and service._first_due == []
    for _ in range(12):
        service._decode_and_reply()
    assert [len(r.answers) for r in rets] == [1, 1]
    want, _, _ = _play(_engine(lm, False), reqs, [[0], [], [1]])
    for ret, (prompt, _), i in zip(rets, reqs, (0, 1)):
        kind, value = ret.answers[0]
        assert kind == "ok" and list(np.asarray(value)[len(prompt):]) == want[i]


def test_closing_a_service_with_an_admission_recorded_answers_it_once(lm):
    from test_program_spans import _Ret, _Rpc

    service = EngineService(_Rpc(), _engine(lm, True), default_max_new=4)
    rets = [_Ret(), _Ret()]
    for ret, (prompt, budget) in zip(rets, _requests([(5, 8), (7, 3)])):
        service._on_request(ret, prompt, budget)
    assert service._admit_joins() == (2, 0)
    # the second; the step that carries the first went out at its submit
    assert service._engine._admission is not None and len(service._engine._flights) == 1
    service.close()
    assert service._engine._admission is None and service._engine._flights == []
    assert service._first_due == [] and service._slot_req == {}
    assert [[kind for kind, _ in r.answers] for r in rets] == [["error"], ["error"]]
