"""The config-built Mamba-2 / NoPE grouped-query decoder with experts behind
every layer (``models/ssd_moe.py``) against the plain reference
(``chipbench/reference/granite_hybrid.py``), on the CPU at a tiny size of the
published SHAPE (two periods of five layers with the attention layer third,
four Mamba-2 heads of 64 channels and 64 states, two K/V heads of 128 under
four query heads, 8 experts top-3 with a shared expert, a tied head, all four
multipliers other than 1), seeded random weights, logits not tokens.

Tolerances.  The model runs in float32 here (``dtype=float32``), its kernels
in Pallas interpret mode, so what separates program and reference is the order
of float32 sums (the chunked recurrence's products against the token-by-token
one, flash attention against a full softmax, the sorted grouped products
against every expert for every token): logits IN UNITS OF THEIR OWN DEVIATION
(they are divided by ``logits_scaling`` 16 and the tied table is drawn over
``embedding_multiplier`` 12, so a logit is of order 0.005: the tolerance is of
logits of order 1, as the other decoders' tests have it) agree to ``TOL`` =
2e-4 (measured: at most 1e-5 here).  Four faults are shown to break it, each by several times: a
recurrent state rounded to bfloat16 after every step (the nearest precision
below the stated one), ``residual_multiplier`` 1, scores over ``sqrt(d)`` in
place of ``attention_multiplier``, and logits not divided by
``logits_scaling``.  The prefill kernel's chunk is 16 here (128 on the chip),
so that short prompts cross chunks.
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

from chipbench.reference import granite_hybrid as ref  # noqa: E402
from moolib_tpu import telemetry  # noqa: E402
from moolib_tpu.engine import ContinuousBatchingEngine  # noqa: E402
from moolib_tpu.models.ssd_moe import SsdGqaMoELM, tiny_config  # noqa: E402
from moolib_tpu.ops import ssd  # noqa: E402

TOL = 2e-4
CFG = tiny_config()
FILE = os.path.join(ROOT, "chipbench", "configs", "granite-4.0-h-small.json")


@pytest.fixture(autouse=True)
def _small_chunk(monkeypatch):
    monkeypatch.setattr(ssd, "CHUNK", 16)


@pytest.fixture(scope="module")
def model():
    return SsdGqaMoELM.from_config(CFG, dtype=jnp.float32, max_len=512)


@pytest.fixture(scope="module")
def params(model):
    return jax.jit(model.init)(jax.random.key(7))


def _tokens(n, seed=0):
    return np.asarray(np.random.default_rng(seed).integers(0, CFG["vocab_size"], n), np.int32)


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _off(got, want):
    """The largest |logit - reference| in units of the reference's deviation."""
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))) / np.std(np.asarray(want)))


# ------------------------------------------------------------------ the file
def test_builds_from_the_published_keys_and_the_layer_types(model):
    assert model.runs == (2, 4, 2) and model.mamba_layers == 8 and model.attn_layers == 2
    assert (model.d_inner, model.conv_channels, model.head_dim) == (256, 384, 128)
    assert (model.step_counters, model.prefill_counters) == (21, 11)
    spec = model.state_spec(5)
    assert spec["ssd"].shape == (5, 8, 4, 64, 64) and spec["ssd"].dtype == jnp.float32
    assert spec["conv"].shape == (5, 8, 16, 128)  # three taps of 384 channels: 9 rows of 128 lanes, in whole tiles of 8
    pools = model.cache_spec(9, 16)
    assert len(pools["k"]) == 2 and pools["k"][0].shape == (9, 16, 2, 128)
    # q times attention_multiplier x sqrt(head_dim): the kernels' own head_dim ** -0.5 makes 1/64
    assert model.q_scale == pytest.approx(128 ** 0.5 / 64)


def test_the_parameter_count_at_the_published_widths():
    """The cut file (one period, 18 of 72 experts, a quarter of the
    vocabulary): 2.96 B; its keys uncut (40 layers, 72 experts, 100,352 rows):
    32.2 B, the family's "32B"; ten of 72 experts active: 8.8 B, its "A9B"."""
    with open(FILE) as f:
        config = json.load(f)
    cut = SsdGqaMoELM.from_config(config, max_len=3072, **config["uses"]["serve"])
    assert cut.runs == (5, 4) and (cut.d_inner, cut.conv_channels, cut.head_dim) == (8192, 8448, 128)
    count = lambda m: sum(x.size for x in jax.tree.leaves(jax.eval_shape(m.init, jax.random.key(0))))
    mamba, attn = 102_286_976, 41_943_040  # a mixer of either kind
    beside = 2 * 4096 + 4096 * 72 + 18_874_368  # two norms, the router, the shared expert
    expert = 9_437_184
    assert count(cut) == (9 * mamba + attn + 10 * (beside + 18 * expert)
                          + 25_088 * 4096 + 4096) == 2_955_758_208
    whole = SsdGqaMoELM.from_config({**config, **config["published"], "router_experts": 72})
    assert whole.runs == (5, 9, 9, 9, 4)
    total = (36 * mamba + 4 * attn + 40 * (beside + 72 * expert) + 100_352 * 4096 + 4096)
    assert count(whole) == total and 32.1e9 < total < 32.3e9
    assert 8.7e9 < total - 40 * 62 * expert < 8.9e9
    assert "head" not in jax.eval_shape(cut.init, jax.random.key(0))  # tied


@pytest.mark.parametrize("key,value", [
    ("mamba_n_groups", 8), ("mamba_d_conv", 3), ("mamba_conv_bias", False),
    ("mamba_proj_bias", True), ("attention_bias", True), ("position_embedding_type", "rope"),
    ("rope_scaling", {"type": "yarn"}), ("hidden_act", "gelu"),
    ("normalization_function", "layernorm"), ("tie_word_embeddings", False),
    ("mamba_expand", 4), ("num_hidden_layers", 8), ("held_from", 6), ("max_len", 2048),
    ("layer_types", ["attention"] + ["mamba"] * 9),
])
def test_a_key_the_model_cannot_honour_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key if key != "num_hidden_layers" else "layer_types"):
        SsdGqaMoELM.from_config({**CFG, key: value})


# ------------------------------------------------------ the model, whole
def test_prefill_path_matches_the_reference(model, params):
    toks = jnp.asarray(_tokens(150, seed=1))  # not whole chunks, not whole flash blocks
    got = _highest(jax.jit(model.logits), params, toks)
    want = _highest(ref.logits, params, toks, CFG)
    assert _off(got, want) < TOL


@pytest.mark.parametrize("fault,least", [
    ({"residual_multiplier": 1.0}, 100), ({"attention_multiplier": 128 ** -0.5}, 100),
    ({"logits_scaling": 1.0}, 100),
])
def test_a_multiplier_left_out_breaks_the_tolerance(params, fault, least):
    """Readings (the largest |logit - reference| in deviations, over ``TOL``;
    the sound run reads 0.03): ``residual_multiplier`` 1 for 0.22: 19,900
    times; scores over ``sqrt(128)`` for the file's 1 / 64: 6,900 times;
    ``logits_scaling`` dropped: 353,000 times."""
    toks = jnp.asarray(_tokens(150, seed=1))
    wrong = SsdGqaMoELM.from_config({**CFG, **fault}, dtype=jnp.float32, max_len=512)
    got = _highest(jax.jit(wrong.logits), params, toks)
    want = _highest(ref.logits, params, toks, CFG)
    assert _off(got, want) > least * TOL


def test_the_tied_head_contracts_against_the_table_where_it_lies(model, params):
    """No transpose of the [vocab, hidden] table in the lowered step."""
    h = jnp.ones((3, CFG["hidden_size"]), jnp.float32)
    text = jax.jit(model._head).lower(params, h).as_text()
    assert "transpose" not in text
    assert "contracting_dims = [1] x [1]" in text


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(params):
    """The share ties to the model: the routed parts that chips holding
    experts 0-1, 2-3, 4-5 and 6-7 compute (``held_from`` 0, 2, 4, 6: the
    program's ``dropless_moe``, the router whole, weights normalised over all
    three chosen), plus the shared expert ONCE, equal the uncut reference's
    expert layer; so do the reference's own shares."""
    from moolib_tpu.parallel.moe import dropless_moe, softmax_topk_route, swiglu

    D, F = CFG["hidden_size"], CFG["intermediate_size"]
    keys = jax.random.split(jax.random.key(3), 4)
    draw = lambda k, shape, fan_in: jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
    layer = jax.tree.map(lambda x: x[1], params["mamba"][1])  # a router, a shared expert
    gu, down = draw(keys[0], (8, D, 2 * F), D), draw(keys[1], (8, F, D), F)
    x = jax.random.normal(keys[2], (40, D), jnp.float32)
    cfg = {"num_experts_per_tok": 3}
    with jax.default_matmul_precision("highest"):
        want = ref.routed(layer, x, cfg, gu, down, 0) + ref.shared(layer, x)
        theirs, once = ref.expert_shares(layer, x, cfg, gu, down, 4)
        np.testing.assert_allclose(sum(theirs) + once, want, atol=1e-5)
        shared = swiglu(x, layer["shared_gu"], layer["shared_down"])
        total = 0.0
        for share in range(4):
            p = {**layer, "router_bias": jnp.zeros((8,)), "experts_gu": gu[2 * share:2 * share + 2],
                 "experts_down": down[2 * share:2 * share + 2]}
            y, load = dropless_moe(x, p, top_k=3, scale=1.0, held_from=2 * share,
                                   route=softmax_topk_route)
            assert load.shape == (2,)
            total = total + (y - shared)  # the routed part alone
            np.testing.assert_allclose(y - shared, theirs[share], atol=1e-5)
        np.testing.assert_allclose(total + shared, want, atol=2e-5)


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


def _gaps(params, prompt, emitted, config=CFG):
    """Reference's largest logit - its logit of the emitted token, a token,
    in units of the logits' deviation."""
    seq = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    want = np.asarray(_highest(ref.logits, params, jnp.asarray(seq[:-1]), config))[len(prompt) - 1:]
    return (want.max(-1) - want[np.arange(len(emitted)), emitted]) / want.std(-1)


@pytest.fixture(scope="module")
def engine(model, params):
    """One engine for the tests that only read it: every bucket from 1 to 128
    is compiled once."""
    ssd.CHUNK, was = 16, ssd.CHUNK
    try:
        with jax.default_matmul_precision("highest"):
            eng = _engine(model, params)
            eng.warmup()
        yield eng
    finally:
        ssd.CHUNK = was


# prompts shorter than the convolution; a bucket's edge and one past it; a
# prompt whose padding is longer than its content (the state is the one at
# tp - 1, not at the bucket's end)
@pytest.mark.parametrize("lengths", [(1, 2, 3), (64, 65, 127), (33, 17, 128)])
def test_engine_submit_step_retire_matches_the_reference(engine, model, params, lengths):
    assert not getattr(model, "decodes_rows", False)  # the rows of a step are the slots
    requests = [(_tokens(n, seed=20 + n), 4 + i) for i, n in enumerate(lengths)]
    with jax.default_matmul_precision("highest"):
        out = _run(engine, requests)
    assert engine._step_jit._cache_size() == 1
    assert engine.pool.available() == engine.pool.num_blocks - 1
    for i, (prompt, budget) in enumerate(requests):
        assert len(out[i]) == budget
        # every emitted token is the reference's argmax, up to a near tie
        assert _gaps(params, prompt, out[i]).max() < TOL


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
        assert counters.shape == (model.prefill_counters,)
        assert int(counters[0]) == n  # the length the scan was told, not its bucket
        assert int(counters[1:].max()) <= n  # a held expert's tokens: pad tokens are not counted
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
        live = int(active.sum())
        assert counters.shape == (model.step_counters,) and int(counters[0]) == live
        pairs, touched = counters[1:11], counters[11:]
        assert int(pairs.max()) <= 3 * live and int(touched.max()) <= 4
        if hook is not None:
            cache = hook(cache)
        for s, n in enumerate(lengths):
            if bool(active[s]):
                worst = max(worst, _off(got[s], want[s][n + t]))
    return worst, cache


def test_a_decode_of_200_steps_holds_the_logits_and_a_bfloat16_state_does_not(model, params):
    """Logits in float32 against the reference's full forward, 200 steps
    after a prompt of 40 (measured: 9e-6 deviations); with the recurrence's
    state rounded to bfloat16 after every step, the nearest precision below
    the stated one, the same run is off by 0.24, 1,200 times ``TOL``."""
    sound, _ = _teacher_forced(model, params, (40, 9), 200)
    assert sound < TOL

    def rounded(cache):
        state = cache.slots["ssd"].astype(jnp.bfloat16).astype(jnp.float32)
        return cache._replace(slots={**cache.slots, "ssd": state})

    lossy, _ = _teacher_forced(model, params, (40, 9), 200, hook=rounded)
    assert lossy > 100 * TOL


def test_a_step_leaves_inactive_slots_state_and_tail_bit_for_bit(model, params):
    before = _teacher_forced(model, params, (20, 30, 25), 0)[1]
    worst, after = _teacher_forced(model, params, (20, 30, 25), 3, active=(True, False, True))
    assert worst < TOL
    for leaf in ("ssd", "conv"):
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

    def series(snap, name):
        found = snap.get(name, {"series": []})["series"]  # none until its first observation
        return found[0]["value"] if found else {"count": 0, "sum": 0.0}

    names = ("serve_engine_state_live_slots", "serve_engine_scan_prefill_positions",
             "serve_engine_held_experts_touched")
    before = {n: series(registry.snapshot(), n) for n in names}
    with jax.default_matmul_precision("highest"):
        emitted, eng = both()
        assert _gaps(params, second[0], emitted).max() < TOL
        assert eng._step_jit._cache_size() == 1 and eng.stats()["joins"] == 2
        snapshot = registry.snapshot()
        delta = {n: {k: series(snapshot, n)[k] - before[n][k] for k in ("count", "sum")}
                 for n in names}
        steps = delta[names[0]]["count"]  # one observation a booked step, one slot live
        assert steps > 0 and delta[names[0]]["sum"] <= steps
        # a prefill hands back the length its scan was told: the prompts', not their buckets'
        assert (delta[names[1]]["count"], delta[names[1]]["sum"]) == (2, 150.0)
        # the held experts' counters came home in the same packet: ten layers a step
        assert delta[names[2]]["count"] == 10 * steps
        assert snapshot["serve_engine_state_bytes"]["series"][0]["value"] == eng.state_bytes
        assert eng.state_bytes == 8 * (4 * 64 * 64 + 16 * 128) * 4
        # the planted fault: a join that leaves the slot's row as it is
        monkeypatch.setattr(SsdGqaMoELM, "write_state", lambda self, cache, rows, slot: cache)
        stale, _ = both()
    assert _gaps(params, second[0], stale).max() > 10 * TOL


def test_a_step_over_128_slots_reads_the_active_slots_alone(model, params):
    """The cell's slot count at the tiny widths: three requests in slots of
    128, every other slot's state and tail bit for bit what they were."""
    eng = _engine(model, params, slots=128, max_seq_len=64, max_prompt_len=32, min_prompt_len=17)
    requests = [(_tokens(20 + i, seed=60 + i), 5) for i in range(3)]
    with jax.default_matmul_precision("highest"):
        before = jax.tree.map(np.asarray, eng._cache.slots)
        out = _run(eng, requests)
        after = jax.tree.map(np.asarray, eng._cache.slots)
    for i, (prompt, _budget) in enumerate(requests):
        assert _gaps(params, prompt, out[i]).max() < TOL
    for leaf in ("ssd", "conv"):
        changed = {int(s) for s in np.flatnonzero(
            (after[leaf] != before[leaf]).reshape(128, -1).any(axis=1))}
        assert len(changed) == 3


def test_lm_serve_engine_config_builds_the_model_and_answers_a_request(tmp_path):
    """The normal entry point, not only the benchmark's runner: ``lm_serve
    --engine --config <file>`` builds the class the file's ``"model"`` names
    and answers one request whose tokens are the reference's argmax."""
    from moolib_tpu.rpc import Rpc
    from moolib_tpu.serving import ServeClient

    with open(FILE) as f:
        named = json.load(f)["model"]
    config = {**CFG, "model": named}
    path = tmp_path / "granite_tiny.json"
    path.write_text(json.dumps(config))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        address = f"127.0.0.1:{s.getsockname()[1]}"
    log = open(tmp_path / "replica.log", "w")
    replica = subprocess.Popen(
        [sys.executable, "-m", "moolib_tpu.examples.lm_serve", "--listen", address,
         "--name", "granite_replica", "--engine", "--config", str(path), "--slots", "2",
         "--seq_len", "32", "--max_new_tokens", "12", "--seed", "0"],
        stdout=log, stderr=subprocess.STDOUT, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    rpc = Rpc()
    try:
        rpc.set_name("granite_client")
        rpc.connect(address)
        client = ServeClient(rpc, fn="generate", replicas=["granite_replica"], deadline_s=240.0,
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
    model = SsdGqaMoELM.from_config(config, dtype=jnp.float32, max_len=44)
    params = jax.jit(model.init)(jax.random.key(0))
    assert _gaps(params, prompt, emitted).max() < 1e-3  # default matmul precision there
