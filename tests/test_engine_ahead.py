"""The decode loop one step ahead (moolib_tpu/engine/engine.py) — ISSUE 30,
and the admission the host does not wait for — ISSUE 42.

``step()`` dispatches step N+1 before it waits for step N's packet.  What
must hold whatever is in flight: every call books exactly one step, tokens
equal ``generate()``'s, a join lands behind the step in flight, a finish by
EOS that the host could not foresee costs one empty step and nothing else,
and new weights apply from the next dispatch.  (Closing a service with a step
in flight: ``tests/test_program_spans.py``, beside the other close test.)

``submit`` dispatches the prefill and the join back to back: the join takes
the first token from the prefill's device output, and the host reads it in
the next ``step()``, once a step is queued behind the join.  Until then the
slot's ``emitted`` list is empty; a first token that is EOS leaves the slot dark on
the device and comes back as that step's ``finished``; a budget of 1 joins no
slot and is answered at once.

The step's rows (ISSUE 52): an engine of more than 128 slots over a model that
says ``decodes_rows`` compiles the step once a row count and picks, a
dispatch, the smallest that holds the slots its mirrors expect to advance;
the last section holds it to an engine pinned to every slot, token for token.

Every engine here is built over ``conftest.own_programs``: its model does not
offer the form that lets an admission ride a decode step (ISSUE 57), so each
join takes ``engine_prefill`` and ``engine_join``, as under the six decoders
without the form; ``tests/test_engine_admit_step.py`` holds the other path to
this one, token for token.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import own_programs
from moolib_tpu import telemetry
from moolib_tpu.engine import ContinuousBatchingEngine
from moolib_tpu.models.transformer import TransformerLM, generate

V = 64


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=V, d_model=32, num_heads=4,
                          num_kv_heads=2, num_layers=2, max_len=64,
                          attention="dense", dtype=jnp.float32,
                          pos_embedding="rotary")
    return model, model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))


def _engine(lm, **kw):
    model, params = lm
    return ContinuousBatchingEngine(own_programs(model), params, slots=3, block_size=4,
                                    max_seq_len=64, max_prompt_len=16, **kw)


def _reference(lm, prompt, budget):
    model, params = lm
    return np.asarray(generate(model, params, jnp.asarray(prompt[None]), budget))[0]


def _counter(name):
    series = telemetry.get_registry().snapshot()[name]["series"]
    return series[0]["value"] if series else 0


def _histogram(name):
    return _counter(name) or {"count": 0, "sum": 0.0}


@pytest.mark.parametrize("budget", [2, 3, 7])
def test_a_lone_request_runs_one_step_ahead_and_leaves_none_in_flight(lm, budget):
    """Budget k: the prefill's token and k-1 decode steps.  All but the first
    are dispatched while their predecessor is unfetched; the last is not
    followed by another, since the mirrors foresee a finish by budget."""
    eng = _engine(lm)
    before = eng.stats()
    ahead0 = _counter("serve_engine_steps_ahead_total")
    empty0 = _counter("serve_engine_empty_steps_total")
    prompt = np.arange(1, 6, dtype=np.int32)
    slot, _ = eng.submit(prompt, budget)
    finished = []
    for i in range(budget - 1):
        emissions, finished = eng.step()
        assert list(emissions) == [slot]
        # One step stays in flight between calls, until the last.
        assert (bool(eng._flights)) == (i < budget - 2)
    assert finished == [slot]
    assert eng.step() == ({}, [])  # nothing active, nothing in flight
    after = eng.stats()
    assert after["steps"] - before["steps"] == budget - 1
    assert after["steps_ahead"] - before["steps_ahead"] == budget - 2
    assert after["empty_steps"] == before["empty_steps"]
    assert _counter("serve_engine_steps_ahead_total") - ahead0 == budget - 2
    assert _counter("serve_engine_empty_steps_total") == empty0
    out = np.concatenate([prompt, np.asarray(eng.retire(slot), np.int32)])
    np.testing.assert_array_equal(out, _reference(lm, prompt, budget))


def test_a_join_behind_the_step_in_flight_equals_generate(lm):
    """Requests submitted between two ``step()`` calls, while a step is in
    flight, are first decoded by the step after it; they and their
    neighbours reproduce ``generate()`` token for token, and the one decode
    program is never compiled again."""
    eng = _engine(lm)
    eng.warmup()
    assert not eng._flights and eng._step_jit._cache_size() == 1
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(1, V, size=n).astype(np.int32), mn)
            for n, mn in ((5, 9), (9, 4), (3, 6), (12, 3), (7, 5))]
    slot_of, outs = {}, {}

    def submit(i):
        slot, _ = eng.submit(*reqs[i])
        slot_of[slot] = i

    def step():
        _, finished = eng.step()
        for s in finished:
            i = slot_of.pop(s)
            outs[i] = np.concatenate([reqs[i][0], np.asarray(eng.retire(s), np.int32)])

    submit(0)
    step()
    assert bool(eng._flights)
    submit(1)  # behind the step in flight
    step()
    assert bool(eng._flights)
    submit(2)
    pending, behind_a_flight = [3, 4], 2
    for _ in range(40):
        step()
        if pending and len(slot_of) < 3:
            # A slot freed at this fetch is rejoined while the next step flies.
            behind_a_flight += bool(eng._flights)
            submit(pending.pop(0))
        if len(outs) == len(reqs):
            break
    assert sorted(outs) == list(range(len(reqs))) and behind_a_flight >= 3
    for i, (prompt, budget) in enumerate(reqs):
        np.testing.assert_array_equal(outs[i], _reference(lm, prompt, budget),
                                      err_msg=f"request {i}")
    assert not eng._flights
    assert eng._step_jit._cache_size() == 1
    eng.pool.check_invariants()
    assert eng.pool.available() == eng.pool.num_blocks - 1
    st = eng.stats()
    assert st["empty_steps"] == 0  # budgets only: every finish is foreseen
    assert st["steps_ahead"] >= st["steps"] - 2


def test_an_unforeseen_eos_costs_one_empty_step_and_the_slot_is_reused(lm):
    """With ``eos_id`` the host dispatches past a finish it cannot foresee:
    the slot emits nothing after its EOS, the step that was in flight is
    booked as empty, and the freed slot and blocks serve a new request that
    again equals ``generate()``."""
    prompt = np.asarray([42, 4, 61, 36, 57, 18], np.int32)
    ref = _reference(lm, prompt, 12)
    emitted_ref = ref[len(prompt):]
    eos = int(emitted_ref[3])  # the fourth token: the third decode step's
    assert eos not in emitted_ref[:3]
    eng = _engine(lm, eos_id=eos)
    slot, emitted = eng.submit(prompt, 12)
    assert emitted == []  # not read yet: the join took it on the device
    blocks = list(eng._slot_blocks[slot])
    emissions, finished = eng.step()
    # The first step() brought the first token home, ahead of the step's own.
    assert emitted == [int(t) for t in emitted_ref[:2]] and not finished
    while not finished:
        emissions, finished = eng.step()
    assert emissions == {slot: eos} and finished == [slot]
    assert bool(eng._flights)  # dispatched before the EOS was known
    assert eng.active_count() == 0
    empty0 = eng.stats()["empty_steps"]
    counter0 = _counter("serve_engine_empty_steps_total")
    assert eng.retire(slot) == [int(t) for t in emitted_ref[:4]]
    eng.pool.check_invariants()

    # The freed slot and blocks go to a new request behind the empty step.
    prompt2 = np.asarray([62, 4, 18, 25], np.int32)
    emitted2 = _reference(lm, prompt2, 5)[len(prompt2):]
    assert eos not in emitted2
    slot2, _ = eng.submit(prompt2, 5)
    assert slot2 == slot and set(blocks) & set(eng._slot_blocks[slot2])
    assert eng.step() == ({}, [])  # the step that was in flight at the EOS
    assert eng.stats()["empty_steps"] == empty0 + 1
    assert _counter("serve_engine_empty_steps_total") == counter0 + 1
    finished = []
    for _ in range(10):
        _, finished = eng.step()
        if finished:
            break
    assert finished == [slot2]
    out2 = np.asarray(eng.retire(slot2), np.int32)
    np.testing.assert_array_equal(out2, emitted2)
    eng.pool.check_invariants()
    assert eng.pool.available() == eng.pool.num_blocks - 1


def test_eos_in_one_slot_leaves_its_neighbour_untouched(lm):
    """A step in flight in which ONE slot is already inactive: the packet's
    was-active row keeps the host from booking a token for it."""
    prompt = np.asarray([56, 12, 5, 24], np.int32)
    other = np.asarray([62, 4, 18, 25], np.int32)
    emitted_ref = _reference(lm, prompt, 12)[len(prompt):]
    other_ref = _reference(lm, other, 9)[len(other):]
    eos = int(emitted_ref[2])
    assert eos not in emitted_ref[:2] and eos not in other_ref
    eng = _engine(lm, eos_id=eos)
    a, _ = eng.submit(prompt, 12)
    b, _ = eng.submit(other, 9)
    got = {}
    for _ in range(12):
        emissions, finished = eng.step()
        assert a not in emissions or a not in got  # nothing after its EOS
        for s in finished:
            got[s] = eng.retire(s)
        if len(got) == 2:
            break
    assert got[a] == [int(t) for t in emitted_ref[:3]]
    assert got[b] == [int(t) for t in other_ref]
    assert eng.stats()["empty_steps"] == 0 and not eng._flights
    eng.pool.check_invariants()


def test_new_weights_apply_from_the_next_dispatch(lm):
    """``set_params`` with a step in flight: that step finishes under the
    old weights, the next dispatch runs the new.  All-zero weights make every
    logit equal, so a step under them emits token 0."""
    model, params = lm
    prompt = np.asarray([42, 4, 61, 36, 57, 18], np.int32)
    old = _reference(lm, prompt, 3)[len(prompt):]
    assert old[2] != 0  # else the in-flight step could not be told apart
    eng = _engine(lm)
    slot, _ = eng.submit(prompt, 6)
    emissions, _ = eng.step()  # books step 1; step 2 flies under the old
    assert bool(eng._flights)
    eng.set_params(jax.tree.map(jnp.zeros_like, params))
    tokens = [int(old[0]), emissions[slot]]
    finished = []
    while not finished:
        emissions, finished = eng.step()
        tokens.append(emissions[slot])
    assert tokens == [int(old[0]), int(old[1]), int(old[2]), 0, 0, 0]
    assert eng.retire(slot) == tokens and not eng._flights


# ------------------------------------------- an admission nobody waits for
def _drain(eng, live, outs):
    """Step until every slot of ``live`` (slot -> prompt) has finished."""
    for _ in range(64):
        if not live:
            return
        _, finished = eng.step()
        for s in finished:
            prompt = live.pop(s)
            outs.append(np.concatenate([prompt, np.asarray(eng.retire(s), np.int32)]))
    raise AssertionError("engine never drained")


@pytest.mark.parametrize("in_flight", [False, True])
def test_a_join_reads_its_first_token_in_the_next_step(lm, in_flight):
    """With a step in flight the join lands behind it: the next ``step()``
    books that step, which did not advance the slot, and reads the slot's
    first token after dispatching the step that will; with none in flight it
    dispatches and books the slot's first step, the first token ahead of the
    step's own.  Either way ``submit`` reads nothing and the tokens are
    ``generate()``'s."""
    eng = _engine(lm)
    eng.warmup()
    compiled = eng._join_jit._cache_size(), eng._prefill_jit._cache_size()
    first0 = _counter("serve_engine_joins_ahead_total")
    rng = np.random.default_rng(5)
    a = rng.integers(1, V, size=6).astype(np.int32)
    b = rng.integers(1, V, size=11).astype(np.int32)
    ref_b = _reference(lm, b, 5)
    live, outs = {}, []
    if in_flight:
        slot_a, _ = eng.submit(a, 9)
        live[slot_a] = a
        eng.step()
        assert bool(eng._flights)
    telemetry.get_tracer().clear()
    slot, emitted = eng.submit(b, 5)
    assert [s.name for s in telemetry.get_tracer().spans()
            if s.name == "engine.first_token_fetch"] == []
    assert emitted == [] and emitted is eng._emitted[slot]
    live[slot] = b
    emissions, _ = eng.step()
    if in_flight:
        # The step booked was dispatched before the join: not this slot's.
        assert slot not in emissions and emitted == [int(ref_b[len(b)])]
        emissions, _ = eng.step()
    assert emitted == [int(ref_b[len(b)]), int(ref_b[len(b) + 1])]
    assert emissions[slot] == emitted[1]
    _drain(eng, live, outs)
    want = {tuple(_reference(lm, a, 9)), tuple(ref_b)} if in_flight else {tuple(ref_b)}
    assert {tuple(o) for o in outs} == want
    st = eng.stats()
    assert st["joins_ahead"] == st["joins"] == len(outs)
    assert _counter("serve_engine_joins_ahead_total") - first0 == len(outs)
    # Warm-up called the join as ``submit`` does: nothing compiled since.
    assert (eng._join_jit._cache_size(), eng._prefill_jit._cache_size()) == compiled
    eng.pool.check_invariants()


def test_two_admissions_in_one_pass_are_read_in_one_step(lm):
    """Prefill, join, prefill, join behind the step in flight, then one
    ``step()``: it dispatches the step that advances both and reads both
    first tokens, in the order joined."""
    eng = _engine(lm)
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(1, V, size=n).astype(np.int32), mn)
            for n, mn in ((4, 8), (7, 4), (13, 6))]
    refs = [_reference(lm, p, mn) for p, mn in reqs]
    slot0, _ = eng.submit(*reqs[0])
    eng.step()
    telemetry.get_tracer().clear()
    slot1, em1 = eng.submit(*reqs[1])
    slot2, em2 = eng.submit(*reqs[2])
    assert em1 == [] and em2 == [] and list(eng._first) == [slot1, slot2]
    emissions, _ = eng.step()  # books the step in flight: neither slot's
    assert set(emissions) == {slot0} and not eng._first
    assert em1 == [int(refs[1][7])] and em2 == [int(refs[2][13])]
    spans = telemetry.get_tracer().spans()
    fetches = [s for s in spans if s.name == "engine.first_token_fetch"]
    step, = [s for s in spans if s.name == "engine.step"]
    ahead, = [s for s in spans if s.name == "engine.step_dispatch"]
    assert len(fetches) == 2 and all(
        ahead.start_ns + ahead.dur_ns <= f.start_ns
        and f.start_ns + f.dur_ns <= step.start_ns + step.dur_ns for f in fetches)
    eng.step()
    assert em1 == [int(t) for t in refs[1][7:9]]
    assert em2 == [int(t) for t in refs[2][13:15]]
    live = {slot0: reqs[0][0], slot1: reqs[1][0], slot2: reqs[2][0]}
    outs = []
    _drain(eng, live, outs)
    assert {tuple(o) for o in outs} == {tuple(r) for r in refs}
    assert eng.stats()["joins_ahead"] == eng.stats()["joins"] == 3


def test_a_budget_of_one_joins_no_slot_and_is_answered_at_once(lm):
    eng = _engine(lm, eos_id=1)
    prompt = np.arange(2, 9, dtype=np.int32)
    before = eng.stats()
    slot, emitted = eng.submit(prompt, 1)
    assert slot is None
    assert emitted == [int(_reference(lm, prompt, 1)[-1])]
    after = eng.stats()
    assert after["joins"] == before["joins"]
    assert after["joins_ahead"] == before["joins_ahead"]
    assert eng.pool.available() == eng.pool.num_blocks - 1 and eng.active_count() == 0
    assert eng.step() == ({}, [])


@pytest.mark.parametrize("in_flight", [False, True])
def test_a_first_token_that_is_eos_finishes_the_slot_once(lm, in_flight):
    """The host cannot know it when it dispatches the join: the device leaves
    the slot dark, the ``step()`` that reads the token reports the slot finished,
    once, and ``retire`` gives back the blocks and the slot."""
    prompt = np.asarray([42, 4, 61, 36, 57, 18], np.int32)
    other = np.asarray([62, 4, 18, 25], np.int32)
    eos = int(_reference(lm, prompt, 2)[len(prompt)])
    other_ref = _reference(lm, other, 9)
    assert eos not in other_ref[len(other):]
    eng = _engine(lm, eos_id=eos)
    free0 = eng.pool.available()
    got = {}
    if in_flight:
        neighbour, _ = eng.submit(other, 9)
        eng.step()
    slot, emitted = eng.submit(prompt, 12)
    assert slot is not None and emitted == [] and eng.active_count() == 1 + in_flight
    reported = []
    for _ in range(16):
        emissions, finished = eng.step()
        assert slot not in emissions  # it never decodes
        reported += [s for s in finished if s == slot]
        for s in finished:
            got[s] = eng.retire(s)
        if not eng.active_count() and not eng._flights:
            break
    assert reported == [slot] and got[slot] == [eos]
    if in_flight:
        assert got[neighbour] == [int(t) for t in other_ref[len(other):]]
    eng.pool.check_invariants()
    assert eng.pool.available() == free0
    # The freed slot serves a request whose first token is no EOS.
    again = {eng.submit(other, 9)[0]: other for _ in range(eng.slots)}
    assert slot in again and len(again) == eng.slots
    outs = []
    _drain(eng, again, outs)
    for out in outs:
        np.testing.assert_array_equal(out, other_ref)
    st = eng.stats()
    assert st["joins_ahead"] == st["joins"] == st["retires"] == 1 + in_flight + eng.slots


def test_retire_before_any_step_returns_the_first_token(lm):
    """A caller that retires a slot no step has advanced still gets the
    first token: ``retire`` reads it if no booking has."""
    eng = _engine(lm)
    prompt = np.arange(3, 12, dtype=np.int32)
    slot, emitted = eng.submit(prompt, 4)
    assert emitted == []
    toks = eng.retire(slot)
    assert toks == [int(_reference(lm, prompt, 1)[-1])] and toks is emitted
    eng.close()
    eng.pool.check_invariants()


class _CountingLM:
    """A paged transformer that reports two prefill counters: the prompt's
    length as the device saw it, and a constant."""

    step_counters = 0
    prefill_counters = 2

    def __init__(self, model):
        self._inner = own_programs(model)
        self.max_len = model.max_len
        self.seen = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def prefill(self, params, toks, tp, block_size):
        rows, logits, _ = self._inner.prefill(params, toks, tp, block_size)
        return rows, logits, jnp.stack([tp, jnp.int32(7)])

    def observe_prefill(self, counters, prompt_len):
        self.seen.append(([int(c) for c in counters], prompt_len))


def test_prefill_counters_reach_the_model_once_a_request(lm):
    """They ride the first token's vector, so they arrive where it is read:
    in the next ``step()`` for a joined slot, inside ``submit`` for a budget
    of 1."""
    model, params = lm
    counting = _CountingLM(model)
    eng = ContinuousBatchingEngine(counting, params, slots=3, block_size=4,
                                   max_seq_len=64, max_prompt_len=16)
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(1, V, size=n).astype(np.int32), mn)
            for n, mn in ((5, 4), (12, 1), (9, 3))]
    live, outs = {}, []
    for prompt, mn in reqs:
        slot, emitted = eng.submit(prompt, mn)
        if slot is None:
            outs.append(np.concatenate([prompt, np.asarray(emitted, np.int32)]))
            assert counting.seen == [([12, 7], 12)]  # the one that waited
        else:
            live[slot] = prompt
    assert len(counting.seen) == 1
    _drain(eng, live, outs)
    assert sorted(counting.seen) == [([5, 7], 5), ([9, 7], 9), ([12, 7], 12)]
    assert {tuple(o) for o in outs} == {tuple(_reference(lm, p, mn)) for p, mn in reqs}
    st = eng.stats()
    assert st["joins"] == st["joins_ahead"] == 2  # the budget of 1 joined nothing


# ------------------------------------------ rows follow the occupied slots
class _RowsLM:
    """A paged transformer whose class says it decodes rows.  It holds pools
    alone, so a row's block table is all it needs: the engine's half of the
    row counts shows without a slot-axis leaf (``tests/test_jamba.py`` has
    the model with one)."""

    step_counters = 0
    prefill_counters = 0
    decodes_rows = True

    def __init__(self, model):
        self._inner = own_programs(model)
        self.max_len = model.max_len

    def __getattr__(self, name):
        return getattr(self._inner, name)


SLOTS = 256


def _rows_engine(lm, pinned=False, **kw):
    model, params = lm
    eng = ContinuousBatchingEngine(_RowsLM(model), params, slots=SLOTS, block_size=4,
                                   max_seq_len=32, max_prompt_len=8, **kw)
    if pinned:  # every step at the full row count, as before there was another
        eng._rows_for = lambda stepping: eng.slots
    return eng


def _watch(eng):
    """Check, at every dispatch, what the choice of a row count rests on:
    the mirrors' set holds the device's, so the rows chosen hold its count.
    Returns the list the row counts are appended to."""
    chosen, launch = [], eng._launch

    def checked(rows):
        device = np.asarray(eng._active)
        stepping = eng._active_host if not eng._flights else None
        assert int(device.sum()) <= rows, (int(device.sum()), rows)
        if stepping is not None:
            assert not (device & ~stepping).any()
        chosen.append(rows)
        return launch(rows)

    eng._launch = checked
    return chosen


def _waves(eng, waves, until):
    """Submit each wave of (prompt, budget) once fewer than ``until`` of the
    slots are lit, a step in flight from the second wave on; step to the end.
    Returns the emitted tokens by request."""
    live, outs, n = {}, {}, 0
    waves = list(waves)
    for _ in range(400):
        if waves and eng.active_count() < until:
            for prompt, budget in waves.pop(0):
                slot, _ = eng.submit(prompt, budget)
                live[slot] = n
                n += 1
        if not live:
            break
        _, finished = eng.step()
        for s in finished:
            outs[live.pop(s)] = eng.retire(s)
    assert not live and not waves
    return outs


def _requests(n, seed, budgets):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, V, size=int(rng.integers(1, 9))).astype(np.int32),
             int(budgets[i % len(budgets)])) for i in range(n)]


@pytest.mark.parametrize("eos", [None, 22])
def test_the_rows_follow_the_occupied_slots_and_no_token_changes(lm, eos):
    """256 slots; 150 requests of short and long budgets, 120 more once 50
    are left (joins behind a step in flight, between a step of 128 rows and
    one of 256), then the drain: the row count goes 256, 128, 256, 128, and
    every request emits what an engine pinned to 256 rows emits.  With an EOS
    id slots finish on the device a step before the host knows: the mirrors
    stay a superset, so a step may run 256 rows over 128 live slots, never
    the reverse."""
    waves = [_requests(150, 1, (3, 3, 12)), _requests(120, 2, (4, 9))]
    pinned = _rows_engine(lm, pinned=True, eos_id=eos)
    want = _waves(pinned, waves, until=51)
    assert pinned._step_jit._cache_size() == 1
    assert pinned.stats()["steps_by_rows"] == {128: 0, 256: pinned.stats()["steps"]}

    eng = _rows_engine(lm, eos_id=eos)
    # warm-up compiles both row counts, and its return counts them
    assert eng.warmup() == (eng._prefill_jit._cache_size() + eng._join_jit._cache_size()
                            + eng._step_jit._cache_size())
    assert eng._step_jit._cache_size() == 2 and not eng._flights
    before = _histogram("serve_engine_decode_rows")
    chosen = _watch(eng)
    got = _waves(eng, waves, until=51)
    assert got == want
    crossings = [r for i, r in enumerate(chosen) if i == 0 or r != chosen[i - 1]]
    assert crossings == [256, 128, 256, 128]
    st = eng.stats()
    assert st["row_overflows"] == 0 and _counter("serve_engine_row_overflows_total") == 0
    assert st["steps_by_rows"] == {r: chosen.count(r) for r in (128, 256)}
    # every step dispatched is booked, but one still in flight behind an EOS
    assert sum(st["steps_by_rows"].values()) == st["steps"] + (bool(eng._flights))
    after = _histogram("serve_engine_decode_rows")
    assert after["count"] - before["count"] == len(chosen)
    assert after["sum"] - before["sum"] == sum(chosen)
    assert eng._step_jit._cache_size() == 2  # nothing compiled since the warm-up
    if eos is not None:
        assert any(out[-1] == eos for out in got.values())
        assert st["empty_steps"] == pinned.stats()["empty_steps"]
    for i in (0, 7, 151):  # and what they emit is generate()'s
        prompt, budget = (waves[0] + waves[1])[i]
        if eos is None:
            np.testing.assert_array_equal(
                np.concatenate([prompt, got[i]]), _reference(lm, prompt, budget))
    eng.pool.check_invariants()
    assert eng.pool.available() == eng.pool.num_blocks - 1


def test_a_step_with_too_few_rows_is_late_and_counted_never_wrong(lm):
    """The invariant broken by hand: 150 lit slots and a step of 128 rows.
    The 22 slots past the rows do not step, their packet says so, the next
    steps bring them up; every request's tokens are the pinned engine's and
    the counter that must read 0 does not."""
    wave = [_requests(150, 3, (5, 7))]
    want = _waves(_rows_engine(lm, pinned=True), wave, until=1)
    eng = _rows_engine(lm)
    eng._rows_for = lambda stepping: 128
    zero = _counter("serve_engine_row_overflows_total")
    got = _waves(eng, wave, until=1)
    assert got == want
    st = eng.stats()
    assert st["row_overflows"] > 0 and st["steps_by_rows"][256] == 0
    assert _counter("serve_engine_row_overflows_total") - zero == st["row_overflows"]
    # no step late, the longest request's tokens after its first are the steps
    assert st["steps"] > max(len(o) for o in want.values()) - 1


@pytest.mark.parametrize("slots,decodes,counts", [
    (3, True, (3,)), (128, True, (128,)), (129, True, (128, 129)),
    (256, True, (128, 256)), (300, True, (128, 256, 300)), (256, False, (256,)),
])
def test_row_counts_by_slots_and_by_what_the_model_says(lm, slots, decodes, counts):
    """Multiples of 128 below the slots, then the slots; one count, the
    slots, for a model that does not say it decodes rows (its packet is then
    the three rows of before)."""
    model, params = lm
    wrapped = _RowsLM(model) if decodes else model
    eng = ContinuousBatchingEngine(wrapped, params, slots=slots, block_size=4,
                                   max_seq_len=16, max_prompt_len=8)
    assert eng._row_counts == counts
    lit = np.zeros(slots, bool)
    for n in (1, 127, 128, 129, 256, 257, 300):
        if n <= slots:
            lit[:n] = True
            assert eng._rows_for(lit) == next(c for c in counts if c >= n)
    assert eng._n_packet_counters == (len(counts) > 1)
