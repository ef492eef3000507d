"""The config-built latent-attention + dropless-experts decoder
(``models/latent_moe.py``) against the plain reference
(``chipbench/reference/glm_moe_lite.py``), on the CPU at a tiny size with the
published ratios, seeded random weights, logits not tokens.

Tolerances.  The model runs in float32 here (``dtype=float32``), its kernels
in Pallas interpret mode, so what separates program and reference is the order
of float32 sums: logits of magnitude ~1 agree to ``TOL`` = 2e-4 (measured:
at most 3e-5 over these seeds).  An 8-bit cache row rounds to 2**-4 of a value
and a bfloat16 router moves a score by 2**-9 of it: both are shown to break
``TOL`` below, so a lower precision than the configuration states cannot
pass.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import glm_moe_lite as ref  # noqa: E402
from moolib_tpu.engine import ContinuousBatchingEngine  # noqa: E402
from moolib_tpu.models.latent_moe import LatentMoELM, tiny_config  # noqa: E402
from moolib_tpu.ops.paged_attention import PagedState  # noqa: E402
from moolib_tpu.parallel import moe as moe_mod  # noqa: E402

TOL = 2e-4
CFG = tiny_config()


@pytest.fixture(scope="module")
def model():
    return LatentMoELM.from_config(CFG, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return jax.jit(model.init)(jax.random.key(7))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, CFG["vocab_size"], n), jnp.int32)


def test_builds_from_the_published_keys(model):
    assert model.row_values == CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"] == 160
    assert model.row_width == 256  # padded to whole 128-lane tiles
    spec = model.cache_spec(9, 8)
    assert spec.shape == (9, CFG["num_hidden_layers"], 8, 256)
    with pytest.raises(ValueError, match="LatentMoELM does not implement the file's n_group$"):
        LatentMoELM.from_config({**CFG, "n_group": 2})


def test_mla_prefill_matches_the_reference(model, params):
    toks = _tokens(37)
    got = jax.jit(model.logits)(params, toks)
    want = ref.logits(params, toks, CFG)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def _decode_against_reference(model, params, pool_dtype=None, lengths=(7, 8, 17), steps=10):
    """Teacher-forced: prefill ``lengths[s]`` tokens of sequence s, then decode
    ``steps`` tokens through the pool (blocks of 8: the lengths start below,
    at and above a block boundary and cross two more).  Returns the largest
    |decode logit - reference logit| over all steps and slots."""
    bs, S = 8, len(lengths)
    MB = -(-(max(lengths) + steps) // bs)
    pool = jnp.zeros(model.cache_spec(1 + S * MB, bs).shape, jnp.float32)
    tables = np.arange(1, 1 + S * MB, dtype=np.int32).reshape(S, MB)
    seqs = [_tokens(n + steps, seed=s) for s, n in enumerate(lengths)]
    for s, n in enumerate(lengths):
        lb = -(-n // bs) * bs
        toks = jnp.pad(seqs[s][:n], (0, lb - n))[None]
        rows, _logits, fullest = model.prefill(params, toks, jnp.int32(n), bs)
        assert fullest.shape == (CFG["num_hidden_layers"] - 1,)
        pool = pool.at[tables[s, : lb // bs]].set(rows)
    if pool_dtype is not None:
        pool = pool.astype(pool_dtype).astype(jnp.float32)
    want = [ref.logits(params, seq, CFG) for seq in seqs]
    decode = jax.jit(model.decode)
    worst = 0.0
    for t in range(steps):
        lens = jnp.asarray([n + t for n in lengths], jnp.int32)
        tok = jnp.stack([seqs[s][n + t] for s, n in enumerate(lengths)])
        paged = PagedState(jnp.asarray(tables), lens, jnp.ones((S,), bool))
        got, pool, touched = decode(params, pool, tok, paged)
        assert touched.shape == (CFG["num_hidden_layers"] - 1,)
        assert 1 <= int(touched.min()) and int(touched.max()) <= min(
            CFG["n_routed_experts"], S * CFG["num_experts_per_tok"])
        for s, n in enumerate(lengths):
            worst = max(worst, float(jnp.max(jnp.abs(got[s] - want[s][n + t]))))
    return worst


def test_absorbed_paged_decode_matches_the_reference_across_blocks(model, params):
    assert _decode_against_reference(model, params) < TOL


def test_an_8_bit_pool_fails_the_tolerance(model, params):
    assert _decode_against_reference(model, params, jnp.float8_e4m3fn, steps=3) > 10 * TOL


def _skewed_layer(key, T=48, D=256, E=8, F=128):
    """One expert layer whose router sends most tokens to expert 2 and none
    to experts 5-7 by its scores, with a bias that lifts expert 6 into the
    selection: choosing by s + b and weighing by s then differ."""
    ks = jax.random.split(key, 8)
    w = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5
    router = w(ks[0], (D, E), D)
    x = jax.random.normal(ks[1], (T, D), jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 2.0, 0.0, 0.0, -3.0, 0.9, -3.0], jnp.float32)
    p = {"router": router, "router_bias": bias,
         "experts_gu": w(ks[2], (E, D, 2 * F), D), "experts_down": w(ks[3], (E, F, D), F),
         "shared_gu": w(ks[4], (D, 2 * F), D), "shared_down": w(ks[5], (F, D), F)}
    return p, x


def test_dropless_experts_match_the_dense_masked_reference_under_skew():
    p, x = _skewed_layer(jax.random.key(3))
    cfg = {"num_experts_per_tok": 2, "routed_scaling_factor": 1.8}
    got, load = jax.jit(lambda p, x: moe_mod.dropless_moe(x, p, top_k=2, scale=1.8))(p, x)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(p, x, cfg)
        weights = ref.route(p, x, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    load = np.asarray(load)
    assert load.sum() == 2 * x.shape[0]  # nothing dropped at any load
    assert load[2] == x.shape[0] and load[5] == 0 and load[7] == 0
    assert load[6] > 0  # chosen only through its bias ...
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision="highest"))
    picked = np.asarray(weights) > 0
    np.testing.assert_array_equal(picked.sum(-1), 2)
    # ... and weighed by its score alone: weights are s over the chosen, x 1.8.
    expect = np.where(picked, np.asarray(s), 0.0)
    expect = expect / expect.sum(-1, keepdims=True) * 1.8
    np.testing.assert_allclose(np.asarray(weights), expect, rtol=1e-5)
    by_bias_too = np.where(picked, np.asarray(s) + np.asarray(p["router_bias"]), 0.0)
    by_bias_too = by_bias_too / by_bias_too.sum(-1, keepdims=True) * 1.8
    assert np.abs(by_bias_too - expect).max() > 0.1


def test_a_bfloat16_router_fails_the_tolerance():
    p, x = _skewed_layer(jax.random.key(4), T=256)
    p = {**p, "router_bias": jnp.zeros_like(p["router_bias"])}
    run = jax.jit(lambda p, x: moe_mod.dropless_moe(x, p, top_k=2, scale=1.8)[0])
    exact = run(p, x)
    rounded = run({**p, "router": p["router"].astype(jnp.bfloat16)}, x.astype(jnp.bfloat16))
    assert float(jnp.max(jnp.abs(rounded - exact))) > 10 * TOL


@pytest.mark.parametrize("n_valid", [5, 1, 0])
def test_a_pad_token_opens_no_expert(n_valid):
    """Experts that only pad tokens chose are poisoned: they are not read,
    the valid tokens' outputs are bit for bit those of the layer without the
    mask, and a pad token gets the shared expert alone."""
    T, k = 32, 2
    p, x = _skewed_layer(jax.random.key(5), T=T)
    p = {**p, "router_bias": jnp.zeros_like(p["router_bias"])}  # spread the tokens
    valid = jnp.arange(T) < n_valid
    run = jax.jit(lambda p, x, valid: moe_mod.dropless_moe(x, p, top_k=k, scale=1.8,
                                                           valid=valid))
    chosen, _ = moe_mod.sigmoid_topk_route(x, p["router"], p["router_bias"], k, 1.8)
    by_valid = np.zeros(8, bool)
    by_valid[np.asarray(chosen)[:n_valid].reshape(-1)] = True
    assert 0 < (~by_valid).sum()  # some expert has pad tokens alone
    poisoned = {**p, **{name: p[name].at[~by_valid].set(jnp.nan)
                        for name in ("experts_gu", "experts_down")}}
    got, load = run(poisoned, x, valid)
    every, _ = jax.jit(lambda p, x: moe_mod.dropless_moe(x, p, top_k=k, scale=1.8))(p, x)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_array_equal(np.asarray(got)[:n_valid], np.asarray(every)[:n_valid])
    shared = moe_mod.swiglu(x, p["shared_gu"], p["shared_down"])
    np.testing.assert_allclose(np.asarray(got)[n_valid:], np.asarray(shared)[n_valid:],
                               atol=1e-6)
    assert int(load.sum()) == k * n_valid and not np.asarray(load)[~by_valid].any()


# The last three span several row tiles under either blocking (the default
# row tile is 256: prefill's shape class), with experts that straddle a tile's
# edge, one that fills a tile exactly and empty ones between them.
_GROUP_SIZES = [[0, 17, 0, 3, 0, 20, 0, 0], [40, 0, 0, 0, 0, 0, 0, 0],
                [5, 5, 5, 5, 5, 5, 5, 5],
                [0, 300, 0, 100, 0, 150, 50, 0], [256, 0, 255, 0, 0, 2, 0, 87],
                [0, 0, 0, 0, 0, 0, 1, 700]]
_BLOCKINGS = {"tm16_tn128": {"tm": 16, "tn": 128}, "plan": {}}


def _grouped_case(sizes, dtype):
    x = jax.random.normal(jax.random.key(0), (sum(sizes), 128), dtype)
    w = jax.random.normal(jax.random.key(1), (8, 128, 256), dtype)
    # An expert no row chose is never read: poison it.
    w = w.at[np.asarray(sizes) == 0].set(jnp.nan)
    return x, w, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("blocking", list(_BLOCKINGS))
@pytest.mark.parametrize("sizes", _GROUP_SIZES)
def test_grouped_matmul_visits_only_groups_with_rows(sizes, blocking):
    x, w, group_sizes = _grouped_case(sizes, jnp.float32)
    out = moe_mod.grouped_matmul(x, w, group_sizes, **_BLOCKINGS[blocking])
    gid = np.repeat(np.arange(8), sizes)
    want = np.einsum("mk,mkn->mn", np.asarray(x), np.nan_to_num(np.asarray(w))[gid])
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)


@pytest.mark.parametrize("sizes", _GROUP_SIZES)
def test_grouped_matmul_blocking_does_not_change_a_bfloat16_result(sizes):
    """Blocking N reorders no sum over K: the plan's one block an expert and
    strips of 128 columns give the same bits."""
    x, w, group_sizes = _grouped_case(sizes, jnp.bfloat16)
    whole = moe_mod.grouped_matmul(x, w, group_sizes)
    strips = moe_mod.grouped_matmul(x, w, group_sizes, tn=128)
    assert whole.dtype == jnp.bfloat16 and not np.isnan(np.asarray(whole, np.float32)).any()
    np.testing.assert_array_equal(np.asarray(whole, np.float32), np.asarray(strips, np.float32))


@pytest.mark.parametrize("shape,tm,tn,steps", [
    ((128, 2048, 3072, 64, 2), 128, 3072, 64),   # decode, gate | up: one block an expert
    ((128, 1536, 2048, 64, 2), 128, 2048, 64),   # decode, down
    ((8192, 2048, 3072, 64, 2), 256, 3072, 95),  # prefill of a 2,048-token bucket
    ((128, 4096, 8192, 64, 2), 128, 2048, 256),  # wider than VMEM: the fewest strips
    ((24, 64, 96, 8, 4), 32, 96, 8),             # the tiny preset: N off the 128 lanes
])
def test_grouped_matmul_plan_is_one_block_an_expert_where_vmem_holds_it(shape, tm, tn, steps):
    plan = moe_mod.grouped_matmul_plan(*shape)
    assert plan[:3] == (tm, tn, steps)
    assert shape[2] % plan.tn == 0 and plan.vmem_bytes <= moe_mod._GMM_VMEM_LIMIT
    # an explicit choice is planned as given: the strips of 512 before PR 38
    M, K, N, G, itemsize = shape
    old = moe_mod.grouped_matmul_plan(*shape, tn=min(512, N))
    assert old.tn == min(512, N) and old.steps == steps // (N // tn) * (N // old.tn)


def test_latent_kernel_matches_its_gathered_oracle(monkeypatch):
    """Windows of two blocks, so that a slot spans several and the copies
    chain from slot to slot; an inactive slot between active ones; NaN in
    every block no live position is in and in the tail of a last block (a
    select must zero it: 0 x NaN is NaN)."""
    from moolib_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_LATENT_WINDOW_TOKENS", 16)
    S, H, W, V, bs, MB, L, layer = 4, 5, 256, 128, 8, 6, 3, 1
    rng = np.random.default_rng(0)
    tables = rng.permutation(np.arange(1, 1 + S * MB)).reshape(S, MB).astype(np.int32)
    lengths = np.asarray([0, 7, 8, 47], np.int32)
    active = np.asarray([True, True, False, True])
    pool = np.full((1 + S * MB, L, bs, W), np.nan, np.float32)
    for s in range(S):
        for t in range(lengths[s] + 1):
            pool[tables[s, t // bs], layer, t % bs] = rng.standard_normal(W)
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    got = pa.latent_paged_attention(
        q, jnp.asarray(pool), jnp.int32(layer), jnp.asarray(tables), jnp.asarray(lengths),
        jnp.asarray(active), value_width=V, scale=0.1)
    want = pa.latent_gathered_attention(
        q, jnp.asarray(np.nan_to_num(pool)), layer, jnp.asarray(tables),
        jnp.asarray(lengths), value_width=V, scale=0.1)
    assert got.shape == (S, H, V) and not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got)[active], np.asarray(want)[active], atol=2e-5)
    assert not np.asarray(got)[~active].any()  # read nothing, gave 0


def _engine(model, params, **kw):
    return ContinuousBatchingEngine(
        model, params, slots=4, block_size=8, max_seq_len=64, max_prompt_len=32,
        min_prompt_len=5, **kw)


def test_engine_submit_step_retire_matches_the_reference_all_slots_in_use(model, params):
    eng = _engine(model, params)
    assert eng.warmup() == 2 * 3 + 1  # buckets 8, 16, 32 (none below 5's), their joins, the step
    prompts = [np.asarray(_tokens(n, seed=10 + n)) for n in (5, 8, 19, 30)]
    live, done = {}, []
    for prompt, budget in zip(prompts, (12, 9, 14, 6)):
        slot, emitted = eng.submit(prompt, budget)
        live[slot] = prompt
    assert eng.active_count() == 4
    while live:
        _emissions, finished = eng.step()
        for slot in finished:
            done.append((live.pop(slot), eng.retire(slot)))
    assert eng._step_jit._cache_size() == 1  # one compile for the engine's lifetime
    assert eng.pool.available() == eng.pool.num_blocks - 1
    for prompt, emitted in done:
        seq = np.concatenate([prompt, np.asarray(emitted, np.int32)])
        want = np.asarray(ref.logits(params, jnp.asarray(seq[:-1]), CFG))[len(prompt) - 1:]
        # Every emitted token is the reference's argmax, up to a near tie.
        gap = want.max(-1) - want[np.arange(len(emitted)), emitted]
        assert gap.max() < TOL
    snap = eng.stats()
    assert snap["joins"] == 4 and snap["retires"] == 4


def test_engine_counters_ride_the_packet(model, params):
    from moolib_tpu import telemetry

    reg = telemetry.get_registry()

    def count(name):
        fam = reg.snapshot().get(name)
        return fam["series"][0]["value"]["count"] if fam and fam["series"] else 0

    before = {n: count(n) for n in ("serve_engine_experts_touched",
                                    "serve_engine_prefill_expert_load",
                                    "serve_engine_live_row_share")}
    eng = _engine(model, params)
    slot, _ = eng.submit(np.asarray(_tokens(9, seed=1)), 4)
    steps = 0
    while True:
        _e, finished = eng.step()
        steps += 1
        if finished:
            break
    eng.retire(slot)
    layers = CFG["num_hidden_layers"] - 1
    assert count("serve_engine_experts_touched") - before["serve_engine_experts_touched"] == steps * layers
    assert count("serve_engine_prefill_expert_load") - before["serve_engine_prefill_expert_load"] == layers
    assert count("serve_engine_live_row_share") - before["serve_engine_live_row_share"] == steps


def test_transformer_lm_through_the_reshaped_engine_is_bit_equal():
    """``lm_serve_steady``'s kind of model (MHA, learned positions): what the
    engine's adapter computes is, to the bit, what the engine computed before
    it took its cache layout from the model (the twins applied directly, K/V
    stacked and cut into blocks)."""
    from moolib_tpu.models.transformer import PagedTransformerLM, TransformerLM

    lm = TransformerLM(vocab_size=97, d_model=64, num_heads=2, num_layers=3, max_len=64,
                       attention="dense", dtype=jnp.float32, pos_embedding="learned")
    p = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    paged_lm = PagedTransformerLM(lm)
    bs, nb = 8, 9
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 97, (1, 16)), jnp.int32)
    rows, logits, counters = jax.jit(
        lambda p, t: paged_lm.prefill(p, t, jnp.int32(11), bs))(p, toks)
    assert counters is None
    pre = TransformerLM(vocab_size=97, d_model=64, num_heads=2, num_layers=3, max_len=64,
                        attention="dense", dtype=jnp.float32, pos_embedding="learned",
                        collect_kv=True)
    old_logits, col = jax.jit(lambda p, t: pre.apply({"params": p["params"]}, t, mutable=["kv"]))(p, toks)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(old_logits[0, 10]))
    for i in range(3):
        old_k = np.asarray(col["kv"][f"block{i}"]["k"][0][0]).reshape(2, bs, 2, 32)
        np.testing.assert_array_equal(np.asarray(rows[0][i]), old_k)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), paged_lm.cache_spec(nb, bs))
    cache = paged_lm.write_rows(cache, rows, jnp.asarray([1, 2]))
    np.testing.assert_array_equal(
        np.asarray(cache["block2"]["pool_v"][1:3]), np.asarray(rows[1][2]))
    paged = PagedState(jnp.asarray([[1, 2, 3, 0]], jnp.int32), jnp.asarray([11], jnp.int32),
                       jnp.asarray([True]))
    got, new_cache, _ = jax.jit(paged_lm.decode)(p, cache, jnp.asarray([5], jnp.int32), paged)
    dec = TransformerLM(vocab_size=97, d_model=64, num_heads=2, num_layers=3, max_len=64,
                        attention="dense", dtype=jnp.float32, pos_embedding="learned",
                        decode=True, kv_num_blocks=nb, kv_block_size=bs)
    old, upd = jax.jit(lambda p, c, t: dec.apply(
        {"params": p["params"], "cache": c}, t[:, None], paged=paged, mutable=["cache"]))(
            p, cache, jnp.asarray([5], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old[:, 0]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 new_cache, upd["cache"])
    with pytest.raises(ValueError, match="SwitchMoE"):
        ContinuousBatchingEngine(
            TransformerLM(vocab_size=97, d_model=64, num_heads=2, num_layers=2,
                          moe_num_experts=4), p)
