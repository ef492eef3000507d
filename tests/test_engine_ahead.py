"""The decode loop one step ahead (moolib_tpu/engine/engine.py) — ISSUE 30.

``step()`` dispatches step N+1 before it waits for step N's packet.  What
must hold whatever is in flight: every call books exactly one step, tokens
equal ``generate()``'s, a join lands behind the step in flight, a finish by
EOS that the host could not foresee costs one empty step and nothing else,
and new weights apply from the next dispatch.  (Closing a service with a step
in flight: ``tests/test_program_spans.py``, beside the other close test.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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
    return ContinuousBatchingEngine(model, params, slots=3, block_size=4,
                                    max_seq_len=64, max_prompt_len=16, **kw)


def _reference(lm, prompt, budget):
    model, params = lm
    return np.asarray(generate(model, params, jnp.asarray(prompt[None]), budget))[0]


def _counter(name):
    series = telemetry.get_registry().snapshot()[name]["series"]
    return series[0]["value"] if series else 0


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
        assert (eng._flight is not None) == (i < budget - 2)
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
    assert eng._flight is None and eng._step_jit._cache_size() == 1
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
    assert eng._flight is not None
    submit(1)  # behind the step in flight
    step()
    assert eng._flight is not None
    submit(2)
    pending, behind_a_flight = [3, 4], 2
    for _ in range(40):
        step()
        if pending and len(slot_of) < 3:
            # A slot freed at this fetch is rejoined while the next step flies.
            behind_a_flight += eng._flight is not None
            submit(pending.pop(0))
        if len(outs) == len(reqs):
            break
    assert sorted(outs) == list(range(len(reqs))) and behind_a_flight >= 3
    for i, (prompt, budget) in enumerate(reqs):
        np.testing.assert_array_equal(outs[i], _reference(lm, prompt, budget),
                                      err_msg=f"request {i}")
    assert eng._flight is None
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
    slot, first = eng.submit(prompt, 12)
    assert first == [int(emitted_ref[0])]
    blocks = list(eng._slot_blocks[slot])
    finished = []
    while not finished:
        emissions, finished = eng.step()
    assert emissions == {slot: eos} and finished == [slot]
    assert eng._flight is not None  # dispatched before the EOS was known
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
    assert eng.stats()["empty_steps"] == 0 and eng._flight is None
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
    assert eng._flight is not None
    eng.set_params(jax.tree.map(jnp.zeros_like, params))
    tokens = [int(old[0]), emissions[slot]]
    finished = []
    while not finished:
        emissions, finished = eng.step()
        tokens.append(emissions[slot])
    assert tokens == [int(old[0]), int(old[1]), int(old[2]), 0, 0, 0]
    assert eng.retire(slot) == tokens and eng._flight is None
