"""The program's own spans and counters (docs/TELEMETRY.md "Program spans"):
what one engine iteration and one train step record, that the tracer mirrors
them into the profiler wherever jax is imported, that the recompile detector
stays off the per-call path, the named scopes in the compiled HLO,
``EngineService.close`` from a second thread, the service loop's three states
(busy, empty, blocked), and that every span and registry series a file of
``chipbench/metrics/`` names is still emitted under that name."""

import asyncio
import gc
import glob
import json
import os
import sys
import threading
import time
import types

import numpy as np
import pytest

from conftest import own_programs
from moolib_tpu import telemetry
from moolib_tpu.telemetry import devmon, tracing

ITERATION_TREE = {
    "serve.iteration": None,
    "serve.admit": "serve.iteration",
    "engine.submit": "serve.admit",
    "engine.prefill_dispatch": "engine.submit",
    "engine.join": "engine.submit",
    "engine.step": "serve.iteration",
    "engine.step_dispatch": "engine.step",
    "engine.decode_fetch": "engine.step",
    # Read in the next step(), behind its dispatch: ``submit`` waits for nothing.
    "engine.first_token_fetch": "engine.step",
    "engine.step_host": "engine.step",
    "serve.reply": "serve.iteration",
}


# ------------------------------------------------------------ tracer -> jax
class _FakeAnnotation:
    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.mark.parametrize("jax_imported", [True, False])
def test_spans_are_mirrored_where_jax_is_imported(monkeypatch, jax_imported):
    log = []
    if jax_imported:
        fake = types.SimpleNamespace(profiler=types.SimpleNamespace(
            TraceAnnotation=lambda name: _FakeAnnotation(log, name)))
        monkeypatch.setitem(sys.modules, "jax", fake)
    else:
        monkeypatch.delitem(sys.modules, "jax", raising=False)
    tracer = tracing.Tracer()  # the default: nobody switched anything on
    with tracer.span("outer"):
        with tracer.span("inner"):
            pass
    assert [s.name for s in tracer.spans()] == ["inner", "outer"]
    if jax_imported:
        assert log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("exit", "outer")]
    else:
        assert log == [] and "jax" not in sys.modules  # never imported from here


def _fetch_an_action():
    import jax.numpy as jnp

    from moolib_tpu import rollout

    rollout.PendingAction(jnp.arange(3)).realize()


def _redistribute_a_leaf():
    import jax
    import jax.numpy as jnp

    from moolib_tpu.parallel import collectives

    collectives.redistribute(
        {"w": jnp.ones(4)}, jax.sharding.SingleDeviceSharding(jax.devices()[1]))


@pytest.mark.parametrize("name,call", [
    ("rollout.act_fetch", _fetch_an_action),
    ("parallel.redistribute", _redistribute_a_leaf)])
def test_call_site_records_its_tracer_span(name, call):
    telemetry.get_tracer().clear()
    call()
    assert [s.name for s in telemetry.get_tracer().spans()].count(name) == 1


# ------------------------------------------------------- recompile detector
def test_instrumented_jit_computes_no_signature_on_a_repeat_call(monkeypatch):
    import jax
    import jax.numpy as jnp

    devmon.reset_for_tests()
    telemetry.get_flight_recorder().clear()
    calls = []
    real = devmon._signature
    monkeypatch.setattr(devmon, "_signature",
                        lambda a, k: calls.append(1) or real(a, k))
    f = devmon.instrument_jit(jax.jit(lambda x: x * 2, donate_argnums=(0,)), "t.o1")
    f(jnp.ones((4, 4)))
    assert len(calls) == 1  # the first call compiled
    for _ in range(3):
        f(jnp.ones((4, 4)))
    assert len(calls) == 1  # a repeat call: two reads of the jit's cache size
    f(jnp.ones((8, 4)))
    assert len(calls) == 2  # the cache grew: signature, diff, event
    events = [a for _t, n, a in telemetry.get_flight_recorder().events()
              if n == "devmon.recompile"]
    assert len(events) == 1 and events[0]["fn"] == "t.o1"
    assert "(4, 4)/float32 -> (8, 4)/float32" in events[0]["diff"]
    f(jnp.ones((4, 4)))  # back to a seen signature: served from the cache
    assert len(calls) == 2
    devmon.reset_for_tests()


def test_a_callable_without_a_cache_still_gets_its_signature_every_call(monkeypatch):
    devmon.reset_for_tests()
    calls = []
    real = devmon._signature
    monkeypatch.setattr(devmon, "_signature",
                        lambda a, k: calls.append(1) or real(a, k))
    f = devmon.instrument_jit(lambda x: x, "t.closure2")
    f(np.ones(3))
    f(np.ones(3))
    assert len(calls) == 2
    devmon.reset_for_tests()


# --------------------------------------------------------- engine iteration
class _Ret:
    """What the RPC layer hands a deferred handler: call it with the value,
    or ``.error`` with a message.  Counts answers."""

    def __init__(self):
        self.answers = []

    def __call__(self, value):
        self.answers.append(("ok", value))

    def error(self, message):
        self.answers.append(("error", message))


class _Rpc:
    def define_deferred(self, name, fn):
        pass

    define = define_deferred

    def undefine(self, name):
        pass


def _service(slots=3):
    import jax
    import jax.numpy as jnp

    from moolib_tpu.engine import ContinuousBatchingEngine, EngineService
    from moolib_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=64, d_model=32, num_heads=4, num_kv_heads=2,
                          num_layers=2, max_len=64, attention="dense",
                          dtype=jnp.float32, pos_embedding="rotary")
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    # Every join by programs of its own (conftest.own_programs): the tree and
    # the counts below are that path's; tests/test_engine_admit_step.py has
    # the admission that rides a step.
    engine = ContinuousBatchingEngine(own_programs(model), params, slots=slots, block_size=4,
                                      max_seq_len=64, max_prompt_len=8)
    return EngineService(_Rpc(), engine, default_max_new=4)


def _phase_counts(snapshot):
    family = snapshot.get("serve_phase_seconds", {"series": []})
    return {s["labels"]["phase"]: s["value"]["count"] for s in family["series"]}


def _loop_seconds(snapshot):
    family = snapshot.get("serve_loop_seconds_total", {"series": []})
    return {s["labels"]["state"]: s["value"] for s in family["series"]}


@pytest.fixture(scope="module")
def one_busy_spell():
    """An empty spell of two idle ticks and a collection, then two requests
    (budgets 3 and 5) in one arrival: one pass admits both, then four decode
    steps, with the host monitor on as ``loop`` starts it; the tracer's spans,
    the phase counts, every ``serve_phase_seconds`` observation in order, and
    the loop's lifetime by the caller's clock."""
    service = _service()
    rets = [_Ret(), _Ret()]
    prompt = np.arange(1, 6, dtype=np.int32)
    from moolib_tpu.engine import service as service_mod

    observed = []
    real = service_mod._M_PHASE.observe
    service_mod._M_PHASE.observe = lambda v, **kw: observed.append((kw["phase"], v)) or real(v, **kw)

    async def spell():
        async def arrive():
            await asyncio.sleep(0.12)
            gc.collect()
            for ret, budget in zip(rets, (3, 5)):
                service._on_request(ret, prompt, budget)

        arrival = asyncio.ensure_future(arrive())
        iterations = await asyncio.wait_for(service.loop(total=2), 120)
        await arrival
        return iterations

    telemetry.get_tracer().clear()
    registry_before = telemetry.get_registry().snapshot()
    t0 = time.monotonic()
    try:
        iterations = asyncio.run(spell())
    finally:
        del service_mod._M_PHASE.observe
    lifetime = time.monotonic() - t0
    registry_after = telemetry.get_registry().snapshot()
    before, after = _phase_counts(registry_before), _phase_counts(registry_after)
    return {"iterations": iterations, "rets": rets, "observed": observed,
            "spans": telemetry.get_tracer().spans(), "lifetime": lifetime,
            "registry": (registry_before, registry_after),
            "counts": {k: v - before.get(k, 0) for k, v in after.items()}}


@pytest.fixture(scope="module")
def one_expert_spell():
    """A model with routed experts through the same engine (the tiny preset
    of ``models/latent_moe.py``): one request, prefilled and decoded to its
    budget; the registry before and after.  Its counters (experts touched a
    step, the fullest expert of a prefill) exist for no other model."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.engine import ContinuousBatchingEngine
    from moolib_tpu.models.latent_moe import LatentMoELM, tiny_config

    model = LatentMoELM.from_config(tiny_config(), dtype=jnp.float32)
    params = jax.jit(model.init)(jax.random.key(0))
    engine = ContinuousBatchingEngine(model, params, slots=2, block_size=8,
                                      max_seq_len=32, max_prompt_len=16)
    before = telemetry.get_registry().snapshot()
    slot, _ = engine.submit(np.arange(2, 9, dtype=np.int32), 3)
    while not engine.step()[1]:
        pass
    engine.retire(slot)
    return {"registry": (before, telemetry.get_registry().snapshot())}


@pytest.fixture(scope="module")
def one_state_spell():
    """The tiny hybrid decoder (``models/hybrid_kda.py``: a recurrent state a
    slot, a share of the experts) through the engine: one request to its
    budget; the registry before and after.  Its counters (slots holding live
    state, held pairs, held experts touched) exist for no other model."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.engine import ContinuousBatchingEngine
    from moolib_tpu.models.hybrid_kda import HybridKdaMoELM, tiny_config

    model = HybridKdaMoELM.from_config(
        {**tiny_config(), "num_hidden_layers": 4}, dtype=jnp.float32, max_len=128)
    params = jax.jit(model.init)(jax.random.key(0))
    engine = ContinuousBatchingEngine(model, params, slots=2, block_size=16,
                                      max_seq_len=128, max_prompt_len=64)
    before = telemetry.get_registry().snapshot()
    telemetry.get_tracer().clear()
    slot, _ = engine.submit(np.arange(2, 40, dtype=np.int32), 3)
    while not engine.step()[1]:
        pass
    engine.retire(slot)
    return {"registry": (before, telemetry.get_registry().snapshot()),
            "spans": telemetry.get_tracer().spans()}


@pytest.fixture(scope="module")
def one_ring_spell():
    """The tiny sliding-window decoder (``models/swa_moe.py``: rings a slot
    beside paged pools) through the engine: one request to its budget; the
    registry before and after.  Its ring counters exist for no other model."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.engine import ContinuousBatchingEngine
    from moolib_tpu.models.swa_moe import SlidingGqaMoELM, tiny_config

    model = SlidingGqaMoELM.from_config(tiny_config(), dtype=jnp.float32, max_len=160)
    params = jax.jit(model.init)(jax.random.key(0))
    engine = ContinuousBatchingEngine(model, params, slots=2, block_size=16,
                                      max_seq_len=160, max_prompt_len=128)
    before = telemetry.get_registry().snapshot()
    # a bucket of 128 positions: the shortest that takes the flash kernel, so
    # that the windowed forward's key blocks are counted
    slot, _ = engine.submit(np.arange(2, 100, dtype=np.int32), 3)
    while not engine.step()[1]:
        pass
    engine.retire(slot)
    return {"registry": (before, telemetry.get_registry().snapshot())}


def _parent(span, spans):
    """The innermost span of the same thread that contains this one."""
    around = [p for p in spans if p is not span and p.tid == span.tid
              and p.start_ns <= span.start_ns
              and p.start_ns + p.dur_ns >= span.start_ns + span.dur_ns]
    return max(around, key=lambda p: p.start_ns).name if around else None


def test_one_engine_iteration_yields_the_span_tree(one_busy_spell):
    spans = [s for s in one_busy_spell["spans"] if s.name in ITERATION_TREE]
    assert one_busy_spell["iterations"] == 4
    assert all(len(r.answers) == 1 and r.answers[0][0] == "ok"
               for r in one_busy_spell["rets"])
    assert {s.name for s in spans} == set(ITERATION_TREE)
    for s in spans:
        assert _parent(s, spans) == ITERATION_TREE[s.name], s.name
    count = lambda name: sum(s.name == name for s in spans)
    assert count("serve.iteration") == count("engine.step") == 4
    assert count("engine.submit") == 2 and count("serve.reply") == 2
    first = min((s for s in spans if s.name == "serve.iteration"), key=lambda s: s.start_ns)
    assert first.args == {"active": 2, "joined": 2, "finished": 0}
    # The pass that joined both slots read their first tokens in its step(),
    # after the dispatch and the packet's wait, never inside a submit.
    fetches = [s for s in spans if s.name == "engine.first_token_fetch"]
    assert len(fetches) == 2 and all(
        first.start_ns <= s.start_ns <= first.start_ns + first.dur_ns for s in fetches)
    assert not [s for s in fetches if _parent(s, spans) == "engine.submit"]
    packet = min((s for s in spans if s.name == "engine.decode_fetch"), key=lambda s: s.start_ns)
    assert all(s.start_ns >= packet.start_ns + packet.dur_ns for s in fetches)


def test_new_serve_phases_count_per_iteration_request_and_step(one_busy_spell):
    counts = one_busy_spell["counts"]
    assert counts["dispatch"] == counts["fetch"] == counts["device"] == 4  # per step
    assert counts["first_token"] == counts["prefill"] == counts["queue"] == 2  # per request
    # per gap between two passes that decoded while a slot still waited
    assert counts["iteration"] == 3


def test_first_token_is_never_under_queue_wait(one_busy_spell):
    by_phase = lambda p: [v for phase, v in one_busy_spell["observed"] if phase == p]
    queue, first = by_phase("queue"), by_phase("first_token")
    assert len(queue) == len(first) == 2
    assert all(f >= q for q, f in zip(queue, first))


def test_three_states_sum_to_the_loops_lifetime(one_busy_spell):
    """One ``inc`` a pass, start of pass to start of the next: busy + empty +
    blocked is the loop's lifetime, and every pass lies under its span."""
    before, after = (_loop_seconds(r) for r in one_busy_spell["registry"])
    rose = {state: after[state] - before.get(state, 0.0) for state in after}
    assert set(rose) == {"busy", "empty", "blocked"}
    assert rose["empty"] >= 0.1 and rose["busy"] > 0 and rose["blocked"] == 0
    assert sum(rose.values()) == pytest.approx(one_busy_spell["lifetime"], rel=0.01)
    spans = one_busy_spell["spans"]
    empty = [s for s in spans if s.name == "serve.empty"]
    assert len(empty) >= 2 and not [s for s in spans if s.name == "serve.blocked"]
    assert sum(s.dur_ns for s in empty) / 1e9 == pytest.approx(rose["empty"], rel=0.05)
    first = min(s.start_ns for s in spans if s.name == "serve.iteration")
    assert all(s.start_ns + s.dur_ns <= first for s in empty)  # one state at a time


@pytest.fixture(scope="module")
def one_blocked_spell():
    """A queued request the engine cannot take (``can_accept`` says no, as it
    does of a request the pool cannot hold yet) and no slot active, until the
    service is closed 0.12 s on."""
    service = _service(slots=1)
    ret = _Ret()
    service._on_request(ret, np.arange(1, 6, dtype=np.int32), 3)
    service._engine.can_accept = lambda tp, mn: False
    before = _loop_seconds(telemetry.get_registry().snapshot())
    telemetry.get_tracer().clear()

    async def spell():
        asyncio.get_event_loop().call_later(0.12, service.close)
        await asyncio.wait_for(service.loop(), 60)

    asyncio.run(spell())
    return {"ret": ret, "spans": telemetry.get_tracer().spans(),
            "seconds": (before, _loop_seconds(telemetry.get_registry().snapshot()))}


def test_a_request_the_pool_cannot_hold_is_a_blocked_spell(one_blocked_spell):
    before, after = one_blocked_spell["seconds"]
    blocked = [s for s in one_blocked_spell["spans"] if s.name == "serve.blocked"]
    assert len(blocked) >= 2
    assert after["blocked"] - before.get("blocked", 0.0) == pytest.approx(
        sum(s.dur_ns for s in blocked) / 1e9, rel=0.05)
    assert after["empty"] == before.get("empty", 0.0)
    answers = one_blocked_spell["ret"].answers
    assert answers and answers[0][0] == "error" and "closed" in answers[0][1]


def test_close_from_a_second_thread_mid_loop():
    """``close`` runs to its end on a second thread while the loop's thread
    is inside an iteration whose step finishes slots (at PR 23 the loop then
    died of a ``KeyError`` in ``_slot_req.pop``).  The slot table is the
    loop's own: it answers the finished requests, then the rest "closed"."""
    service = _service(slots=3)
    rets = [_Ret() for _ in range(12)]
    prompt = np.arange(1, 5, dtype=np.int32)
    for ret in rets:
        service._on_request(ret, prompt, 2)  # one decode step each: every step finishes slots
    engine_step = service._engine.step

    def step_then_close():
        out = engine_step()
        if service._stats["iterations"] == 1:  # the second iteration is in flight
            closer = threading.Thread(target=service.close)
            closer.start()
            closer.join(timeout=30)
            assert not closer.is_alive()
        return out

    service._engine.step = step_then_close
    asyncio.run(asyncio.wait_for(service.loop(), 120))
    assert service._slot_req == {}
    assert all(len(r.answers) == 1 for r in rets)
    kinds = [r.answers[0][0] for r in rets]
    assert kinds[:6] == ["ok"] * 6  # two iterations of three slots were served
    assert all(k == "error" and "closed" in v for k, v in (r.answers[0] for r in rets[6:]))
    late = _Ret()
    service._on_request(late, prompt, 2)
    assert late.answers == [("error", "serve generate: closed")]
    # With no loop running, close itself answers what is in flight.
    idle = _service(slots=3)
    waiting = _Ret()
    idle._on_request(waiting, prompt, 4)
    idle._admit_joins()
    idle.close()
    assert waiting.answers == [("error", "serve generate: closed")] and idle._slot_req == {}


def test_close_with_a_step_in_flight_answers_every_request_once():
    """One request finishes in the iteration that sees the close, two are in
    the step in flight, one is queued.  The engine drops that step unbooked,
    then every request has exactly one answer."""
    service = _service(slots=3)
    eng = service._engine
    rets = [_Ret() for _ in range(4)]
    prompt = np.arange(1, 5, dtype=np.int32)
    for ret, budget in zip(rets, (2, 9, 9, 9)):
        service._on_request(ret, prompt, budget)
    engine_step = eng.step

    def step_then_close():
        out = engine_step()
        assert bool(eng._flights)
        service.close()  # the loop runs: it answers on its way out
        return out

    eng.step = step_then_close
    asyncio.run(asyncio.wait_for(service.loop(), 120))
    assert not eng._flights and service._slot_req == {}
    assert [len(r.answers) for r in rets] == [1, 1, 1, 1]
    kind, out = rets[0].answers[0]
    assert kind == "ok" and len(out) == len(prompt) + 2
    assert all(r.answers[0][0] == "error" and "closed" in r.answers[0][1]
               for r in rets[1:])


# ------------------------------------------------------- train loop, scopes
@pytest.fixture(scope="module")
def tiny_train():
    """Two steps of ``lm.train`` at a toy size, with its jitted step and the
    arguments of its first call kept for lowering."""
    from moolib_tpu.examples import lm

    kept = {}
    real = devmon.instrument_jit

    def keep(fn, name):
        wrapped = real(fn, name)
        if name != "lm_train_step":
            return wrapped

        class Call:
            lower = staticmethod(fn.lower)  # devmon.step_cost lowers through the wrapper
            seq = property(lambda self: wrapped.seq)  # the train_step span reads it

            def __call__(self, *args):
                kept.setdefault("args", args)
                return wrapped(*args)

        kept["jit"] = fn
        return Call()

    flags = lm.make_flags([
        "--vocab", "32", "--d_model", "32", "--heads", "2", "--layers", "1",
        "--seq_len", "16", "--batch_size", "2", "--attention", "flash",
        "--steps", "2", "--log_interval", "1", "--quiet"])
    telemetry.get_tracer().clear()
    devmon.instrument_jit = keep
    try:
        lm.train(flags)
    finally:
        devmon.instrument_jit = real
    kept["spans"] = [s.name for s in telemetry.get_tracer().spans()]
    return kept


def test_train_loop_records_its_three_sections(tiny_train):
    for name in ("make_batch", "train_step", "fetch_loss"):
        assert tiny_train["spans"].count(name) == 2, name
    family = telemetry.get_registry().snapshot()["loop_section_seconds"]
    sections = {s["labels"]["section"] for s in family["series"]}
    assert {"make_batch", "train_step", "fetch_loss"} <= sections


def _op_names(text):
    return [line.split('op_name="', 1)[1].split('"', 1)[0]
            for line in text.splitlines() if 'op_name="' in line]


def _paged_text():
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops import paged_attention as pa

    def step(pool, x, q, tables, lengths, active):
        pool = pa.paged_kv_write(pool, x, tables, lengths, active)
        return pa.paged_attention(q, pool, pool, tables, lengths)

    pool = jnp.zeros((5, 4, 2, 8))
    args = (pool, jnp.ones((2, 2, 8)), jnp.ones((2, 1, 4, 8)),
            jnp.array([[1, 2], [3, 4]], jnp.int32), jnp.array([3, 5], jnp.int32),
            jnp.array([True, True]))
    return jax.jit(step).lower(*args).compile().as_text()


def _flash_text():
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 128, 1, 128), jnp.float32)
    loss = lambda q, k, v: flash_attention(q, k, v, causal=True).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile().as_text()


@pytest.mark.parametrize("scope,backward", [
    ("paged_attention", False), ("flash_attention", True),
    ("lm_loss", True), ("optimizer", False)])
def test_named_scope_is_in_the_compiled_op_names(scope, backward, request):
    if scope == "paged_attention":
        text = _paged_text()
    elif scope == "flash_attention":
        text = _flash_text()
    else:
        kept = request.getfixturevalue("tiny_train")
        text = kept["jit"].lower(*kept["args"]).compile().as_text()
    names = [n for n in _op_names(text) if scope in n]
    assert names, f"no operation of the compiled program is named under {scope!r}"
    if backward:
        assert any("transpose(" not in n for n in names)
        assert any("transpose(" in n and scope in n.split("transpose(", 1)[1] for n in names)


# ------------------------------------------- what the benchmark reads by name
def _metric_files(*readers):
    """The benchmark's metric files of those readers, by metric name.  Read,
    never written: a later metric is held to the same contract unasked."""
    root = os.path.join(os.path.dirname(__file__), "..", "chipbench", "metrics")
    specs = {}
    for path in sorted(glob.glob(os.path.join(root, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("reader") in readers:
            specs[os.path.basename(path)[:-len(".json")]] = spec
    return specs


_SPAN_NAMES = sorted({name for spec in _metric_files("span_time", "span_tail").values()
                      for key in ("spans", "among", "dispatch", "witness")
                      for name in spec.get(key, [])})
_REGISTRY_METRICS = _metric_files("histogram_mean", "gauge_mean", "registry_delta")


@pytest.mark.parametrize("name", _SPAN_NAMES)
def test_every_span_the_benchmark_selects_is_recorded(
        name, one_busy_spell, one_blocked_spell, one_state_spell, tiny_train):
    """``span_time`` gives ``None`` for a name no span has, and the harness
    then leaves the metric out of the line in silence."""
    recorded = ({s.name for s in one_busy_spell["spans"] + one_blocked_spell["spans"]
                 + one_state_spell["spans"]} | set(tiny_train["spans"]))
    assert name in recorded


@pytest.mark.parametrize("metric", sorted(_REGISTRY_METRICS))
def test_every_registry_series_the_benchmark_reads_was_observed(
        metric, one_busy_spell, one_expert_spell, one_state_spell, one_ring_spell):
    """Through the benchmark's own readers, the way its serving runner feeds
    them: the registry before and after the window, and the gauges sampled
    from a snapshot inside it.  A series that only a model with experts, one
    with a state a slot, or one with rings observes is looked for in that
    model's spell."""
    from chipbench.readers import gauge_mean, histogram_mean, registry_delta

    spec = _REGISTRY_METRICS[metric]
    before, after = one_busy_spell["registry"]
    if spec["reader"] in ("histogram_mean", "registry_delta"):
        reader = histogram_mean if spec["reader"] == "histogram_mean" else registry_delta
        for before, after in (one_busy_spell["registry"], one_expert_spell["registry"],
                              one_state_spell["registry"], one_ring_spell["registry"]):
            measured = types.SimpleNamespace(counters_before=before, counters_after=after)
            value = reader.read(spec, {"measured": measured})
            if value is not None:
                break
    else:
        family = after.get(spec["gauge"])
        samples = [family["series"][0]["value"]] if family and family["series"] else []
        value = gauge_mean.read(spec, {"measured": types.SimpleNamespace(
            samples={spec["gauge"]: samples})})
    assert value is not None and value >= 0
