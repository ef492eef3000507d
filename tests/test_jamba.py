"""The config-built Mamba-1 / multi-query decoder (``models/jamba.py``)
against the plain reference (``chipbench/reference/jamba.py``), on the CPU at
a tiny size of the published SHAPE (two periods of four layers with the
attention layer second, one K/V head of 128 under four query heads, 256
channels of 16 states, a tied head), seeded random weights, logits not tokens.

Tolerances.  The model runs in float32 here (``dtype=float32``), its kernels
in Pallas interpret mode, so what separates program and reference is the order
of float32 sums (the chunked scan with the states on sublanes against the
token-by-token one, flash attention against a full softmax): logits of
magnitude ~1 agree to ``TOL`` = 2e-4 (measured: at most 8e-6 here).  A scan
state rounded to bfloat16 loses 2**-9 of every entry a step and is shown to
break ``TOL`` below, so a lower precision than the configuration states
cannot pass; so is a freed slot's state that a join did not overwrite.  The
prefill kernel's chunk is 16 here (128 in ``tests/test_selective_scan.py``
and on the chip): in interpret mode a chunk is unrolled into the program.
"""

import json
import os
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench.reference import jamba as ref  # noqa: E402
from moolib_tpu import telemetry  # noqa: E402
from moolib_tpu.engine import ContinuousBatchingEngine  # noqa: E402
from moolib_tpu.models.jamba import JambaLM, tiny_config  # noqa: E402
from moolib_tpu.ops import selective_scan as ssm  # noqa: E402

TOL = 2e-4
CFG = tiny_config()


@pytest.fixture(autouse=True)
def _small_chunk(monkeypatch):
    monkeypatch.setattr(ssm, "CHUNK", 16)


@pytest.fixture(scope="module")
def model():
    return JambaLM.from_config(CFG, dtype=jnp.float32, max_len=512)


@pytest.fixture(scope="module")
def params(model):
    return jax.jit(model.init)(jax.random.key(7))


def _tokens(n, seed=0):
    return np.asarray(np.random.default_rng(seed).integers(0, CFG["vocab_size"], n), np.int32)


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


# ------------------------------------------------------------------ the file
def test_builds_from_the_published_keys_and_the_order_rule(model):
    assert model.runs == (1, 3, 2) and model.mamba_layers == 6 and model.attn_layers == 2
    assert (model.head_dim, model.d_inner, model.step_counters) == (128, 256, 1)
    spec = model.state_spec(5)
    assert spec["ssm"].shape == (5, 6, 16, 256) and spec["ssm"].dtype == jnp.float32
    assert spec["conv"].shape == (5, 6, 6, 128)  # three taps of 256 channels, as whole tiles of 128 lanes
    pools = model.cache_spec(9, 16)
    assert len(pools["k"]) == 2 and pools["k"][0].shape == (9, 16, 1, 128)
    # the published file: attention at layers 7 and 21 of 28, 5,120 channels, 3.03 B parameters
    with open(os.path.join(ROOT, "chipbench", "configs", "ai21-jamba2-3b.json")) as f:
        config = json.load(f)
    whole = JambaLM.from_config(config, max_len=4096, **config["uses"]["serve"])
    assert whole.runs == (7, 13, 6) and (whole.d_inner, whole.head_dim) == (5120, 128)
    shapes = jax.eval_shape(whole.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_029_337_472
    assert "head" not in shapes  # tied


@pytest.mark.parametrize("key,value", [
    ("num_experts", 4), ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("mamba_d_conv", 3), ("sliding_window", 512), ("num_hidden_layers", 6),
    ("tie_word_embeddings", False), ("num_key_value_heads", 2), ("attn_layer_offset", 0),
    ("max_len", 2048),
])
def test_a_key_the_model_cannot_honour_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        JambaLM.from_config({**CFG, key: value})


# ------------------------------------------------------ the model, whole
def test_prefill_path_matches_the_reference(model, params):
    toks = jnp.asarray(_tokens(150, seed=1))  # not whole chunks, not whole flash blocks
    got = _highest(jax.jit(model.logits), params, toks)
    want = _highest(ref.logits, params, toks, CFG)
    assert float(jnp.std(want)) > 0.5  # logits of order 1: the tolerance means something
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_the_tied_head_contracts_against_the_table_where_it_lies(model, params):
    """No transpose of the [vocab, hidden] table in the lowered step."""
    h = jnp.ones((3, CFG["hidden_size"]), jnp.float32)
    text = jax.jit(model._head).lower(params, h).as_text()
    assert "transpose" not in text
    assert "contracting_dims = [1] x [1]" in text


# -------------------------------------------------------- through the engine
def _engine(model, params, slots=3, **kw):
    kw = {"block_size": 16, "max_seq_len": 512, "max_prompt_len": 128, **kw}
    return ContinuousBatchingEngine(model, params, slots=slots, **kw)


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
    """Reference's largest logit - its logit of the emitted token, a token."""
    seq = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    want = np.asarray(_highest(ref.logits, params, jnp.asarray(seq[:-1]), CFG))[len(prompt) - 1:]
    return want.max(-1) - want[np.arange(len(emitted)), emitted]


@pytest.fixture(scope="module")
def engine(model, params):
    """One engine for the tests that only read it: every bucket from 1 to 128
    is compiled once."""
    ssm.CHUNK, was = 16, ssm.CHUNK
    try:
        with jax.default_matmul_precision("highest"):
            eng = _engine(model, params)
            eng.warmup()
        yield eng
    finally:
        ssm.CHUNK = was


# prompts shorter than the convolution; a bucket's edge and one past it; a
# prompt whose padding is longer than its content (the state is the one at
# tp - 1, not at the bucket's end)
@pytest.mark.parametrize("lengths", [(1, 2, 3), (64, 65, 127), (33, 17, 128)])
def test_engine_submit_step_retire_matches_the_reference(engine, params, lengths):
    requests = [(_tokens(n, seed=20 + n), 4 + i) for i, n in enumerate(lengths)]
    with jax.default_matmul_precision("highest"):
        out = _run(engine, requests)
    assert engine._step_jit._cache_size() == 1
    assert engine.pool.available() == engine.pool.num_blocks - 1
    for i, (prompt, budget) in enumerate(requests):
        assert len(out[i]) == budget
        # every emitted token is the reference's argmax, up to a near tie
        assert _gaps(params, prompt, out[i]).max() < TOL


def _waves(eng, waves, until):
    """Submit each wave of (prompt, budget) once fewer than ``until`` slots
    are lit (a step in flight from the second wave on); step to the end.
    Returns ({request: emitted}, the row count of every step dispatched)."""
    live, outs, n, chosen, launch = {}, {}, 0, [], eng._launch

    def watched(rows):
        # what the choice rests on: the rows hold the device's active slots
        assert int(np.asarray(eng._active).sum()) <= rows
        chosen.append(rows)
        return launch(rows)

    eng._launch = watched
    waves = list(waves)
    while waves or live:
        if waves and eng.active_count() < until:
            for prompt, budget in waves.pop(0):
                slot, _ = eng.submit(prompt, budget)
                live[slot], n = n, n + 1
        _, finished = eng.step()
        for slot in finished:
            outs[live.pop(slot)] = eng.retire(slot)
    return outs, chosen


def test_the_rows_of_a_step_are_the_occupied_slots_and_no_token_changes(model, params):
    """256 slots.  140 requests (prompts of 1 to 20 tokens: shorter than the
    convolution, and longer), 110 more once 40 are left, joined behind a step
    in flight between a step of 128 rows and one of 256, then the drain: the
    row count goes 256, 128, 256, 128, and every request emits, token for
    token, what an engine pinned to 256 rows emits: a row's tail and state
    are its slot's, whichever row of the step it is, and a padded row's
    write-back (its own tail and state, unchanged) touches no live slot's."""
    rng = np.random.default_rng(5)

    def requests(n, budgets):
        return [(_tokens(int(rng.integers(1, 21)), seed=int(rng.integers(1 << 30))),
                 budgets[i % len(budgets)]) for i in range(n)]

    waves = [requests(140, (3, 3, 3, 10)), requests(110, (4, 8))]
    kw = {"slots": 256, "max_seq_len": 32, "max_prompt_len": 32, "min_prompt_len": 17}
    pinned = _engine(model, params, **kw)
    pinned._rows_for = lambda stepping: pinned.slots
    want, rows = _waves(pinned, waves, until=41)
    assert set(rows) == {256} and pinned._step_jit._cache_size() == 1

    eng = _engine(model, params, **kw)
    assert eng.warmup() == 1 + 1 + 2  # the one bucket, its join, two row counts
    assert eng._step_jit._cache_size() == 2
    got, rows = _waves(eng, waves, until=41)
    assert [r for i, r in enumerate(rows) if i == 0 or r != rows[i - 1]] == [256, 128, 256, 128]
    assert got == want
    stats = eng.stats()
    assert stats["row_overflows"] == 0 and eng._step_jit._cache_size() == 2
    assert stats["steps_by_rows"] == {128: rows.count(128), 256: rows.count(256)}
    assert eng.pool.available() == eng.pool.num_blocks - 1
    # and what they emit is the reference's argmax, up to a near tie
    for i in (0, 139, 249):
        assert _gaps(params, (waves[0] + waves[1])[i][0], got[i]).max() < TOL


def _teacher_forced(model, params, lengths, steps, hook=None, active=None):
    """Prefill ``lengths[s]`` tokens of sequence s in its bucket, then decode
    ``steps`` tokens through the pools and the slot state, teacher-forced.
    Returns (the largest |decode logit - reference logit| over all steps and
    active slots, the cache).  ``hook(cache) -> cache`` runs between steps (a
    planted fault); ``active`` [S] bool: the slots that step."""
    from moolib_tpu.models.decoder_parts import SlotCache
    from moolib_tpu.ops.paged_attention import PagedState

    bs, S = 16, len(lengths)
    bucket = lambda n: max(16, 1 << (n - 1).bit_length())
    MB = -(-(max(lengths) + steps) // bs)
    zeros = lambda spec: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    cache = SlotCache(zeros(model.cache_spec(1 + S * MB, bs)), zeros(model.state_spec(S)))
    tables = np.arange(1, 1 + S * MB, dtype=np.int32).reshape(S, MB)
    seqs = [_tokens(n + steps, seed=40 + s) for s, n in enumerate(lengths)]
    prefill = jax.jit(model.prefill, static_argnums=3)
    for s, n in enumerate(lengths):
        lb = bucket(n)
        rows, _logits, counters = _highest(
            prefill, params, jnp.pad(jnp.asarray(seqs[s][:n]), (0, lb - n))[None], jnp.int32(n), bs)
        assert counters.tolist() == [n]  # the length the scan was told, not its bucket
        cache = model.write_rows(cache, rows, tables[s, : lb // bs])
        cache = model.write_state(cache, rows, s)
    want = [np.asarray(_highest(ref.logits, params, jnp.asarray(seq), CFG)) for seq in seqs]
    decode = jax.jit(model.decode)
    active = jnp.ones((S,), bool) if active is None else jnp.asarray(active)
    worst = 0.0
    for t in range(steps):
        lens = jnp.asarray([n + t for n in lengths], jnp.int32)
        tok = jnp.asarray([seqs[s][n + t] for s, n in enumerate(lengths)])
        got, cache, counters = _highest(
            decode, params, cache, tok, PagedState(jnp.asarray(tables), lens, active))
        assert counters.shape == (1,) and int(counters[0]) == int(active.sum())
        if hook is not None:
            cache = hook(cache)
        for s, n in enumerate(lengths):
            if bool(active[s]):
                worst = max(worst, float(np.abs(np.asarray(got[s]) - want[s][n + t]).max()))
    return worst, cache


def test_a_decode_of_200_steps_holds_the_logits_and_a_bfloat16_state_does_not(model, params):
    """Logits in float32 against the reference's full forward, 200 steps
    after a prompt of 40 (measured: 4e-6); with the scan's state rounded to
    bfloat16 after every step, the nearest precision below the stated one,
    the same run is off by 7e-3, 36 times ``TOL``."""
    sound, _ = _teacher_forced(model, params, (40, 9), 200)
    assert sound < TOL

    def rounded(cache):
        state = cache.slots["ssm"].astype(jnp.bfloat16).astype(jnp.float32)
        return cache._replace(slots={**cache.slots, "ssm": state})

    lossy, _ = _teacher_forced(model, params, (40, 9), 200, hook=rounded)
    assert lossy > 5 * TOL


def test_a_step_leaves_inactive_slots_state_and_tail_bit_for_bit(model, params):
    before = _teacher_forced(model, params, (20, 30, 25), 0)[1]
    worst, after = _teacher_forced(model, params, (20, 30, 25), 3, active=(True, False, True))
    assert worst < TOL
    for leaf in ("ssm", "conv"):
        np.testing.assert_array_equal(
            np.asarray(after.slots[leaf])[1], np.asarray(before.slots[leaf])[1])
        assert not np.array_equal(np.asarray(after.slots[leaf])[0], np.asarray(before.slots[leaf])[0])


def test_a_freed_slot_joined_again_starts_from_the_new_requests_state(model, params, monkeypatch):
    """One slot, two requests one after the other: the second must see its
    own prefill's state and tail, not what the first left in the slot's row.
    With the state write taken out of the join it does not."""
    first, second = (_tokens(100, seed=31), 6), (_tokens(50, seed=32), 8)

    def both():
        eng = _engine(model, params, slots=1, min_prompt_len=33)
        _run(eng, [first])
        return _run(eng, [second])[0], eng

    registry = telemetry.get_registry()

    def series(snap, name="serve_engine_state_live_slots"):
        found = snap.get(name, {"series": []})["series"]  # none until its first observation
        return found[0]["value"] if found else {"count": 0, "sum": 0.0}

    before = series(registry.snapshot())
    told = series(registry.snapshot(), "serve_engine_scan_prefill_positions")
    with jax.default_matmul_precision("highest"):
        emitted, eng = both()
        assert _gaps(params, second[0], emitted).max() < TOL
        assert eng._step_jit._cache_size() == 1 and eng.stats()["joins"] == 2
        snapshot = registry.snapshot()
        live = series(snapshot)
        steps = live["count"] - before["count"]  # one observation a booked step, one slot live
        assert steps > 0 and live["sum"] - before["sum"] <= steps
        # a prefill hands back the length its scan was told: the prompts', not their buckets'
        scanned = series(snapshot, "serve_engine_scan_prefill_positions")
        assert (scanned["count"] - told["count"], scanned["sum"] - told["sum"]) == (2, 150.0)
        assert snapshot["serve_engine_state_bytes"]["series"][0]["value"] == eng.state_bytes
        assert eng.state_bytes == 6 * (16 + 3) * 256 * 4
        assert snapshot["serve_engine_kv_live_share"]["series"][0]["value"]["count"] > 0
        # the planted fault: a join that leaves the slot's row as it is
        monkeypatch.setattr(JambaLM, "write_state", lambda self, cache, rows, slot: cache)
        stale, _ = both()
    assert _gaps(params, second[0], stale).max() > 10 * TOL


def test_lm_serve_engine_config_builds_the_model_and_answers_a_request(tmp_path):
    """The normal entry point, not only the benchmark's runner: ``lm_serve
    --engine --config <file>`` builds the class the file's ``"model"`` names
    and answers one request whose tokens are the reference's argmax."""
    from moolib_tpu.rpc import Rpc
    from moolib_tpu.serving import ServeClient

    config = {**CFG, "model": "moolib_tpu.models.jamba:JambaLM"}
    path = tmp_path / "jamba_tiny.json"
    path.write_text(json.dumps(config))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        address = f"127.0.0.1:{s.getsockname()[1]}"
    log = open(tmp_path / "replica.log", "w")
    replica = subprocess.Popen(
        [sys.executable, "-m", "moolib_tpu.examples.lm_serve", "--listen", address,
         "--name", "jamba_replica", "--engine", "--config", str(path), "--slots", "2",
         "--seq_len", "32", "--max_new_tokens", "12", "--seed", "0"],
        stdout=log, stderr=subprocess.STDOUT, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    rpc = Rpc()
    try:
        rpc.set_name("jamba_client")
        rpc.connect(address)
        client = ServeClient(rpc, fn="generate", replicas=["jamba_replica"], deadline_s=240.0,
                             attempt_timeout=240.0, max_attempts=1, metadata=True)
        prompt = _tokens(20, seed=9)
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
    model = JambaLM.from_config(config, dtype=jnp.float32, max_len=44)
    params = jax.jit(model.init)(jax.random.key(0))
    assert _gaps(params, prompt, emitted).max() < 1e-3  # default matmul precision there
