"""Every run of a device program has a name, a cause and arguments (ISSUE 53;
docs/TELEMETRY.md "Device programs").

One string a program (``devmon.jit_program``): the compiled module's name,
``jit_compiles_total{fn}`` and the ``program`` of the dispatching span.  A
span's arguments at OPEN time reach the profiler's annotation, ``set()``'s stay
in the ring.  ``seq`` counts a program's dispatches.  And on recorded chip
traces the program's own summary (``profiling.summarize``) and the benchmark's
reader (``chipbench/readers/program_time.py``) tie each run to the span that
dispatched it and agree to the nanosecond, though neither imports the other.
"""

import gzip
import os
import shutil
import sys
import threading
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import own_programs
from moolib_tpu import telemetry
from moolib_tpu.engine import ContinuousBatchingEngine
from moolib_tpu.models.transformer import TransformerLM
from moolib_tpu.telemetry import devmon, profiling

DATA = os.path.join(os.path.dirname(__file__), "..", "chipbench", "tests", "data")
# program -> dispatch span: the trace of before PR 53 has the private methods'
# names and spans without arguments; the one made with PR 53 has the programs'
NAMED = {"engine_prefill": "engine.prefill_dispatch", "engine_join": "engine.join",
         "engine_decode": "engine.step_dispatch", "lm_train_step": "train_step"}
OLD = {"_prefill_impl": "engine.prefill_dispatch", "_join_impl": "engine.join",
       "_step_impl": "engine.step_dispatch"}
TRACES = {"program_spans": OLD, "program_runs": NAMED}


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=64, d_model=32, num_heads=4, num_kv_heads=2,
                          num_layers=2, max_len=64, attention="dense",
                          dtype=jnp.float32, pos_embedding="rotary")
    return model, model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))


def _engine(lm):
    model, params = lm
    # every join by programs of its own: the spans and counts here are theirs
    return ContinuousBatchingEngine(own_programs(model), params, slots=3, block_size=4,
                                    max_seq_len=64, max_prompt_len=16)


# ------------------------------------------------------------ one name a program
def _engine_call(eng, program):
    """The jit of ``program`` and one call's arguments, as ``warmup`` makes it."""
    state = (eng._cache, eng._tables, eng._lengths, eng._active, eng._tokens, eng._remaining)
    if program == "engine_decode":
        return eng._step_jit, (eng._params, *state, eng.slots)
    toks = np.zeros((1, 16), np.int32)
    if program == "engine_prefill":
        return eng._prefill_jit, (eng._params, toks, np.int32(16))
    rows, first = jax.eval_shape(eng._prefill_jit, eng._params, toks, np.int32(16))
    return eng._join_jit, (*state, np.int32(0), np.zeros(eng.max_blocks_per_seq, np.int32),
                           np.int32(0), first, np.int32(0), rows, np.zeros(4, np.int32))


@pytest.mark.parametrize("program", ["engine_prefill", "engine_join", "engine_decode"])
def test_an_engine_program_compiles_under_its_one_name(lm, program):
    jitted, args = _engine_call(_engine(lm), program)
    assert jitted.name == program
    assert jitted.lower(*args).compile().as_text().startswith(f"HloModule jit_{program},")


@pytest.mark.parametrize("mesh_spec", ["", "dp=4"])
def test_the_train_step_compiles_under_its_one_name(mesh_spec):
    import optax

    from moolib_tpu import parallel
    from moolib_tpu.examples import lm as lm_example

    flags = lm_example.make_flags([
        "--vocab", "32", "--d_model", "32", "--heads", "2", "--layers", "1", "--seq_len", "16",
        "--batch_size", "4", "--attention", "dense", "--mesh", mesh_spec, "--quiet"])
    mesh = parallel.parse_mesh_spec(flags.mesh)
    model, opt = lm_example.make_model(flags), optax.adamw(flags.learning_rate)
    tokens = jax.ShapeDtypeStruct((flags.batch_size, flags.seq_len), jnp.int32)
    params = jax.eval_shape(lambda t: model.init(
        jax.random.key(0), t, **lm_example._apply_kwargs(flags, mesh)), tokens)
    opt_state = jax.eval_shape(opt.init, params)
    _, step = lm_example.make_step(flags, model, opt, mesh)
    jstep, _put = lm_example.jit_step(step, params, opt_state, flags, mesh)
    assert jstep.name == lm_example.STEP_PROGRAM == "lm_train_step" and jstep.seq == 0
    text = jstep.lower(params, opt_state, tokens).compile().as_text()
    assert text.startswith("HloModule jit_lm_train_step,")


def test_jit_program_names_counts_and_labels_one_string():
    devmon.reset_for_tests()

    class Holder:
        def _private_impl(self, x, k):
            return x * k

    f = devmon.jit_program(Holder()._private_impl, "t_program", static_argnums=(1,))
    assert (f.name, f.seq) == ("t_program", 0)
    assert f.lower(jnp.ones(4), 3).compile().as_text().startswith("HloModule jit_t_program,")
    assert float(f(jnp.ones(4), 3)[0]) == 3.0 and f.seq == 1
    f(jnp.ones(4), 3)
    f(jnp.ones(4), 2)  # static argument: a second program under the same name
    assert f.seq == 3 and f._cache_size() == 2
    fams = telemetry.get_registry().snapshot()["jit_compiles_total"]["series"]
    assert {s["labels"]["fn"]: s["value"] for s in fams}["t_program"] >= 1
    assert devmon.instrument_jit(f, "other") is f


def test_the_engines_labels_are_the_programs_names(lm):
    devmon.reset_for_tests()
    eng = _engine(lm)
    eng.warmup()
    fams = telemetry.get_registry().snapshot()["jit_compiles_total"]["series"]
    labels = {s["labels"]["fn"] for s in fams if s["value"]}
    assert {"engine_prefill", "engine_join", "engine_decode"} <= labels
    assert not labels & {"engine.prefill", "engine.join", "engine.step", "lm.step"}


def test_no_metric_file_holds_a_private_methods_name():
    root = os.path.join(os.path.dirname(__file__), "..", "chipbench", "metrics")
    for name in os.listdir(root):
        with open(os.path.join(root, name)) as f:
            text = f.read()
        assert not any(p in text for p in OLD), name


# --------------------------------------------------- a span's arguments, at open
class _Annotation:
    log = []
    enabled = True
    thread = None

    def __init__(self, name, **kwargs):
        # the test's own spans: a host monitor that an earlier test of this
        # process started ticks under a span too, on its own thread
        if threading.get_ident() == self.thread:
            self.log.append((name, kwargs))

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    _Annotation.log, _Annotation.enabled = [], True
    _Annotation.thread = threading.get_ident()
    monkeypatch.setitem(sys.modules, "jax", types.SimpleNamespace(
        profiler=types.SimpleNamespace(TraceAnnotation=_Annotation)))
    return _Annotation


def test_args_given_at_open_reach_the_annotation_and_sets_do_not(annotations):
    tracer = telemetry.Tracer()
    with tracer.span("engine.join", program="engine_join", seq=7, slot=3) as sp:
        sp.set(blocks=12)
    assert annotations.log == [("engine.join", {"program": "engine_join", "seq": 7, "slot": 3})]
    recorded, = tracer.spans()
    assert recorded.args == {"program": "engine_join", "seq": 7, "slot": 3, "blocks": 12}


def test_only_scalars_reach_the_annotation(annotations):
    tracer = telemetry.Tracer()
    with tracer.span("x", rows=128, share=0.5, live=True, kind="a", slots=[1, 2], table={"a": 1}):
        pass
    assert annotations.log == [("x", {"rows": 128, "share": 0.5, "live": True, "kind": "a"})]


def test_a_closed_profiler_is_handed_no_argument(annotations):
    annotations.enabled = False
    tracer = telemetry.Tracer()
    with tracer.span("engine.step_dispatch", program="engine_decode", seq=0, rows=128):
        pass
    with telemetry.span("bare"):
        pass
    assert annotations.log == [("engine.step_dispatch", {}), ("bare", {})]
    assert tracer.spans()[0].args == {"program": "engine_decode", "seq": 0, "rows": 128}


def test_the_tracer_has_no_switch_for_annotations():
    assert not hasattr(telemetry.Tracer(), "enable_jax_annotations")


def test_a_step_timers_section_passes_its_args_to_the_span():
    from moolib_tpu.utils.profiling import StepTimer

    tracer = telemetry.Tracer()
    timer = StepTimer(registry=telemetry.Registry(), tracer=tracer)
    with timer.section("train_step", program="lm_train_step", seq=4):
        pass
    span, = tracer.spans()
    assert (span.name, span.args) == ("train_step", {"program": "lm_train_step", "seq": 4})


# ------------------------------------------------------------- seq, slot, rows
def _spans(name):
    return [s for s in telemetry.get_tracer().spans() if s.name == name]


def test_seq_counts_a_programs_dispatches(lm):
    """Two ``step()``s of a busy spell: the first dispatches the step it books
    and the one after, the second one more; each fetch books the oldest."""
    eng = _engine(lm)
    telemetry.get_tracer().clear()
    slot, _ = eng.submit(np.arange(1, 6, dtype=np.int32), 8)
    eng.step()
    eng.step()
    dispatches = _spans("engine.step_dispatch")
    assert [s.args["seq"] for s in dispatches] == [0, 1, 2]
    assert all(s.args["program"] == "engine_decode" and s.args["rows"] == eng.slots
               for s in dispatches)
    assert [s.args["seq"] for s in _spans("engine.decode_fetch")] == [0, 1]
    assert eng._step_jit.seq == 3


def test_an_admissions_spans_say_program_seq_bucket_and_slot(lm):
    eng = _engine(lm)
    telemetry.get_tracer().clear()
    slots = [eng.submit(np.arange(1, n, dtype=np.int32), 4)[0] for n in (6, 12)]
    eng.step()
    prefills, joins = _spans("engine.prefill_dispatch"), _spans("engine.join")
    assert [s.args for s in prefills] == [
        {"program": "engine_prefill", "seq": 0, "bucket": 8, "tokens": 5},
        {"program": "engine_prefill", "seq": 1, "bucket": 16, "tokens": 11}]
    assert [s.args for s in joins] == [
        {"program": "engine_join", "seq": i, "slot": slot} for i, slot in enumerate(slots)]
    # the slot is the request's identifier inside the engine: join -> first token
    assert [s.args["slot"] for s in _spans("engine.first_token_fetch")] == slots


def test_a_join_that_finds_no_blocks_leaves_the_slot_free(lm):
    from moolib_tpu.engine.kv_pool import PoolExhausted

    model, params = lm
    eng = ContinuousBatchingEngine(own_programs(model), params, slots=3, block_size=4, num_blocks=6,
                                   max_seq_len=64, max_prompt_len=16)
    free = list(eng._free_slots)
    telemetry.get_tracer().clear()
    with pytest.raises(PoolExhausted):
        eng.submit(np.arange(1, 6, dtype=np.int32), 40)
    # the blocks are asked for before anything is launched: no program, no span
    assert eng._free_slots == free and eng._join_jit.seq == eng._prefill_jit.seq == 0
    assert not _spans("engine.join") and not _spans("engine.prefill_dispatch")


def test_a_budget_of_one_reads_its_token_for_no_slot(lm):
    eng = _engine(lm)
    telemetry.get_tracer().clear()
    slot, emitted = eng.submit(np.arange(1, 6, dtype=np.int32), 1)
    assert slot is None and len(emitted) == 1
    fetch, = _spans("engine.first_token_fetch")
    assert fetch.args == {"slot": -1} and not _spans("engine.join")


# ------------------------------------- recorded chip traces: summary and reader
@pytest.fixture(scope="module")
def logdirs(tmp_path_factory):
    """Each recorded trace unpacked where the profiler would have written it."""
    out = {}
    for name in TRACES:
        logdir = tmp_path_factory.mktemp(name)
        where = logdir / "plugins" / "profile" / "recorded"
        where.mkdir(parents=True)
        with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as f, \
                open(where / "chip.xplane.pb", "wb") as g:
            shutil.copyfileobj(f, g)
        out[name] = str(logdir)
    return out


@pytest.fixture(scope="module")
def read_by_reader():
    """``name -> (runs, host, busy_s)`` as the benchmark's reader extracts it."""
    from chipbench import trace_reduce as tr
    from chipbench.readers import program_time

    out = {}
    for name, programs in TRACES.items():
        data = tr.load(os.path.join(DATA, name + ".xplane.pb.gz"))
        busy_s = tr.reduce(tr.extract(data), 1)["busy_s"]
        out[name] = (*program_time.extract(data, set(programs.values())), busy_s)
    return out


def _figure(read_by_reader, name, **spec):
    from chipbench.readers import program_time

    runs, host, busy_s = read_by_reader[name]
    return program_time.figure({"programs": TRACES[name], **spec}, runs, host, busy_s, 1)


def test_the_old_trace_holds_two_prefills_two_joins_three_steps(logdirs, read_by_reader):
    summary = profiling.summarize(logdirs["program_spans"], OLD)
    got = {p: (row["runs"], round(row["mean_ms"] * 1e3, 2)) for p, row in summary["programs"].items()}
    assert got == {"_prefill_impl": (2, 7.32), "_join_impl": (2, 13.39), "_step_impl": (3, 18.79)}
    assert summary["matched_share"] == 100.0
    assert _figure(read_by_reader, "program_spans", figure="matched_share") == 100.0


@pytest.mark.parametrize("name,program", [
    (name, program) for name, programs in TRACES.items() for program in programs
    if program != "lm_train_step"])
def test_summary_and_reader_agree_to_the_nanosecond(logdirs, read_by_reader, name, program):
    """All of these traces' runs lie inside their window, so the reader's
    clipped figures are the summary's whole ones."""
    args = (TRACES[name],) if name == "program_spans" else ()  # the new trace's spans say it
    row = profiling.summarize(logdirs[name], *args)["programs"][program]
    ns = lambda ms: round(ms * 1e6)
    mean = _figure(read_by_reader, name, figure="mean_ms", program=program)
    delay = _figure(read_by_reader, name, figure="queue_delay_mean_ms", program=program)
    assert ns(row["mean_ms"]) == ns(mean) > 0
    assert ns(row["queue_delay_mean_ms"]) == ns(delay)
    assert row["matched"] == row["runs"]
    share = _figure(read_by_reader, name, figure="device_share", program=program)
    # the reader's share is of the operations' union, the summary's of the runs'
    assert share > row["busy_share"] > 0


@pytest.mark.parametrize("name", list(TRACES))
def test_the_clock_lead_is_the_same_and_read_at_every_dispatch(logdirs, read_by_reader, name):
    args = (TRACES[name],) if name == "program_spans" else ()
    summary = profiling.summarize(logdirs[name], *args)
    lead = _figure(read_by_reader, name, figure="clock_lead_ms")
    assert round(summary["clock_lead_ms"] * 1e6) == round(lead * 1e6) > 0
    # every run of the window counts, not only a wake-up from an empty engine
    assert sum(row["matched"] for row in summary["programs"].values()) >= 7


def test_the_new_traces_spans_name_program_seq_and_rows(logdirs, read_by_reader):
    from chipbench.readers import program_time

    runs, host, _busy = read_by_reader["program_runs"]
    pairs = program_time.matches(runs, host, NAMED)
    assert len(pairs) >= 11 and all(span is not None for _run, span in pairs)
    for run, span in pairs:
        assert span[3]["program"] == run["program"] and span[2] == NAMED[run["program"]]
    by = lambda program: [s[3] for r, s in pairs if r["program"] == program]
    assert all({"bucket", "tokens", "seq"} <= set(a) for a in by("engine_prefill"))
    assert sorted(a["slot"] for a in by("engine_join")) == [0, 1, 2]
    seqs = [a["seq"] for a in by("engine_decode")]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    rows = profiling.summarize(logdirs["program_runs"])["programs"]["engine_decode"]["by_rows"]
    assert list(rows) == [4] and rows[4]["runs"] == len(seqs)


def test_an_unmatched_run_is_reported_not_guessed(read_by_reader):
    """A run whose launch the trace does not hold (dispatched before the
    profiler started) has spans all around it in time, and gets none."""
    from chipbench.readers import program_time

    runs, host, busy_s = read_by_reader["program_runs"]
    producers, consumers, spans, window = host
    victim = next(r for r in runs[0] if r["program"] == "engine_decode")
    cut = {flow: at for flow, at in producers.items() if flow != victim["flow"]}
    pairs = program_time.matches(runs, (cut, consumers, spans, window), NAMED)
    assert [r["run_id"] for r, s in pairs if s is None] == [victim["run_id"]]
    spec = {"programs": NAMED, "figure": "matched_share"}
    share = program_time.figure(spec, runs, (cut, consumers, spans, window), busy_s, 1)
    assert share == pytest.approx(100.0 * (len(pairs) - 1) / len(pairs))
    # 13 of 14 is under 95%: what needs the match reads nothing
    for fig in ("clock_lead_ms", "queue_delay_mean_ms"):
        spec = {"programs": NAMED, "figure": fig, "program": "engine_prefill"}
        assert program_time.figure(spec, runs, (cut, consumers, spans, window), busy_s, 1) is None
    # the figures that need no match still read
    spec = {"programs": NAMED, "figure": "mean_ms", "program": "engine_decode"}
    assert program_time.figure(spec, runs, (cut, consumers, spans, window), busy_s, 1) > 0


def test_a_span_of_another_program_or_a_stale_seq_is_no_match(read_by_reader):
    from chipbench.readers import program_time

    runs, host, _busy = read_by_reader["program_runs"]
    swapped = dict(NAMED, engine_prefill="engine.join", engine_join="engine.prefill_dispatch")
    pairs = program_time.matches(runs, host, swapped)
    assert all((s is None) == (r["program"] != "engine_decode") for r, s in pairs)
    # the same runs twice on one chip: the second pass's seq does not rise
    twice = {0: runs[0] + runs[0]}
    pairs = program_time.matches(twice, host, NAMED)
    assert sum(s is not None for _r, s in pairs) == len(runs[0])


@pytest.mark.parametrize("name", list(TRACES))
def test_the_summary_of_a_trace_without_its_launches_matches_nothing(logdirs, name, monkeypatch):
    """The program's own summary, the same property: with the launch's flow
    events gone every run is unmatched and the lead and the delays read None."""
    from jax.profiler import ProfileData

    real = ProfileData.from_file

    class Event:
        def __init__(self, ev):
            self.name, self.start_ns, self.duration_ns = ev.name, ev.start_ns, ev.duration_ns
            self.stats = [(k, v) for k, v in ev.stats if k not in ("_p", "_c")]

    def stripped(path):
        data = real(path)
        planes = [types.SimpleNamespace(name=p.name, lines=[
            types.SimpleNamespace(name=l.name, events=[Event(e) for e in l.events])
            for l in p.lines]) for p in data.planes]
        return types.SimpleNamespace(planes=planes)

    monkeypatch.setattr(ProfileData, "from_file", staticmethod(stripped))
    args = (TRACES[name],) if name == "program_spans" else ()
    summary = profiling.summarize(logdirs[name], *args)
    assert summary["matched_share"] == 0.0 and summary["clock_lead_ms"] is None
    assert all(row["matched"] == 0 and row["queue_delay_mean_ms"] is None and row["mean_ms"] > 0
               for row in summary["programs"].values())


# --------------------------------------------------------- the operator's window
def test_a_trace_with_no_program_line_gives_an_empty_summary(tmp_path):
    """The CPU backend's trace: host planes alone.  The window still closes
    with ``ok``, the summary is empty, nothing fails."""
    logdir = str(tmp_path / "cpu")
    assert profiling.start_device_trace(logdir)["ok"]
    with telemetry.span("engine.step_dispatch", program="engine_decode", seq=0, rows=4):
        jnp.ones(4).block_until_ready()
    telemetry.get_flight_recorder().clear()
    reply = profiling.stop_device_trace()
    assert reply["ok"] and reply["summary"]["programs"] == {} and reply["summary"]["window_s"] > 0
    events = [(n, a) for _t, n, a in telemetry.get_flight_recorder().events()]
    assert [a["programs"] for n, a in events if n == "profile.summary"] == [{}]
    span = [s for s in telemetry.get_tracer().spans() if s.name == "device_profile"][-1]
    assert span.args["summary"]["programs"] == {}
    assert profiling.summarize(str(tmp_path / "nothing_here")) == {"programs": {}}


def test_the_rpc_stop_replies_with_the_summary(logdirs, monkeypatch):
    """``__telemetry_profile`` ``stop``: the reply carries what the window's
    device ran (here the recorded chip trace stands for the one just closed)."""
    monkeypatch.setattr(jax.profiler, "start_trace", lambda logdir: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    assert profiling.handle_command("start", logdir=logdirs["program_runs"])["ok"]
    reply = profiling.handle_command("stop")
    programs = reply["summary"]["programs"]
    assert set(programs) == {"engine_prefill", "engine_join", "engine_decode"}
    assert all(row["runs"] == row["matched"] and row["queue_delay_max_ms"] is not None
               for row in programs.values())
    assert reply["summary"]["busy_s"] > 0 and reply["duration_s"] == reply["summary"]["window_s"]
