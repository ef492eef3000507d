"""An engine for a model with no paged cache (moolib_tpu/engine/engine.py) —
ISSUE 44.  A model that offers ``state_spec`` and no ``cache_spec`` gets no
``BlockPool``, no block table among its programs' arguments and no
``write_rows`` call: admission is by free slots alone, ``max_len`` bounds
positions and not memory, a retire frees nothing and a freed slot's row is
never read again.  The three models that have pools get the engine they had.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from moolib_tpu import telemetry
from moolib_tpu.engine import ContinuousBatchingEngine, NoFreeSlot
from moolib_tpu.models.retention_lm import PowerRetentionLM, tiny_config

CFG = tiny_config()


@pytest.fixture(scope="module")
def model():
    return PowerRetentionLM.from_config(CFG, dtype=jnp.float32, max_len=96)


@pytest.fixture(scope="module")
def params(model):
    return jax.jit(model.init)(jax.random.key(3))


def _engine(model, params, slots=2, **kw):
    # block_size and num_blocks are passed as a runner passes them, and unread
    return ContinuousBatchingEngine(model, params, slots=slots, block_size=16, num_blocks=3,
                                    max_prompt_len=64, min_prompt_len=16, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG["vocab_size"], n).astype(np.int32)


def _drain(eng, live):
    out = {}
    while live:
        _emissions, finished = eng.step()
        for slot in finished:
            out[live.pop(slot)] = eng.retire(slot)
    return out


def test_no_pool_no_table_and_stats_without_either(model, params):
    eng = _engine(model, params)
    assert eng.pool is None and eng._tables is None
    stats = eng.stats()
    assert not {"num_blocks", "free", "in_use", "utilization", "block_size"} & set(stats)
    want = sum(s.size * 4 for s in jax.tree.leaves(model.state_spec(2)))
    assert stats["state_bytes"] == eng.state_bytes == want
    gauge = telemetry.get_registry().snapshot()["serve_engine_state_bytes"]["series"][0]["value"]
    assert gauge == want
    # the cache is the state pytree itself, slot axis first in every leaf
    assert set(eng._cache) == {"state", "norm"}
    assert all(leaf.shape[0] == 2 for leaf in jax.tree.leaves(eng._cache))


def test_admission_is_by_slots_alone(model, params):
    """``num_blocks=3`` would hold 48 positions: a paged engine could not take
    one such request, and this one takes two at once."""
    eng = _engine(model, params)
    assert eng.can_accept(60, 30) and eng.can_accept(1, 1)
    a, _ = eng.submit(_prompt(60), 30)
    assert eng.can_accept(60, 30)
    b, _ = eng.submit(_prompt(60, 1), 30)
    assert not eng.can_accept(1, 2)
    with pytest.raises(NoFreeSlot):
        eng.submit(_prompt(5), 3)
    out = _drain(eng, {a: "a", b: "b"})
    assert len(out["a"]) == len(out["b"]) == 30 and eng.can_accept(60, 30)
    assert eng.stats()["retires"] == 2 and eng._step_jit._cache_size() == 1


def test_positions_past_max_len_are_refused_by_submit(model, params):
    eng = _engine(model, params)
    assert eng.seq_capacity == model.max_len == 96
    with pytest.raises(ValueError, match="sequence capacity"):
        eng.submit(_prompt(60), 37)
    slot, _ = eng.submit(_prompt(60), 36)  # 96 positions: the last one RoPE can address
    assert len(_drain(eng, {slot: 0})[0]) == 36
    with pytest.raises(ValueError, match="max_len"):
        ContinuousBatchingEngine(model, params, max_seq_len=97)


def test_a_freed_slots_row_is_never_read_after_the_next_join(model, params):
    """One slot, two requests.  Between them the freed row is poisoned with
    NaN: the second request's tokens are those of a fresh engine, so the join
    overwrote the row whole before anything read it."""
    first, second = (_prompt(40, 5), 6), (_prompt(23, 6), 9)
    fresh = _engine(model, params, slots=1)
    slot, _ = fresh.submit(*second)
    want = _drain(fresh, {slot: 0})[0]
    eng = _engine(model, params, slots=1)
    slot, _ = eng.submit(*first)
    _drain(eng, {slot: 0})
    assert float(jnp.abs(eng._cache["state"]).max()) > 0  # the retire left the row as it was
    eng._cache = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), eng._cache)
    slot, _ = eng.submit(*second)
    assert _drain(eng, {slot: 0})[0] == want
    assert eng._join_jit._cache_size() == 1  # one join program, whatever the bucket


def test_warmup_compiles_one_join_and_leaves_no_slot_lit(model, params):
    eng = _engine(model, params)
    assert eng.warmup() == 3 + 1 + 1  # buckets 16, 32, 64; one join; the step
    assert eng.active_count() == 0 and eng.step() == ({}, [])
    assert not np.asarray(eng._active).any()


def test_joins_ahead_hold_a_bounded_share_of_state_rows(model, params):
    """Admissions wait for nothing, and each holds a row of state until its
    join has run: with a state of 17 MB a slot the bound is 62 admissions
    away, and with one of a GB a slot it is one prefill in flight."""
    eng = _engine(model, params, slots=3)
    assert eng._joins_unread_max == (1 << 30) * 3 // eng.state_bytes == 62
    eng._joins_unread_max = 1
    slots = [eng.submit(_prompt(20, s), 4)[0] for s in range(3)]  # each waits for the one before
    out = _drain(eng, {s: i for i, s in enumerate(slots)})
    assert [len(out[i]) for i in range(3)] == [4, 4, 4]


def _chain_leaves(eng):
    """Array arguments of the step's donated chain, and of a join."""
    chain = (eng._cache, eng._tables, eng._lengths, eng._active, eng._tokens, eng._remaining)
    return len(jax.tree.leaves(chain))


def test_models_with_pools_keep_their_tables_and_their_argument_counts(model, params):
    """jit argument counts: a paged model's chain is its cache leaves, the
    block table and the four slot vectors; the pool-less model's has no table."""
    from moolib_tpu.models import hybrid_kda, latent_moe
    from moolib_tpu.models.transformer import PagedTransformerLM, TransformerLM

    lm = TransformerLM(vocab_size=64, d_model=32, num_heads=4, num_kv_heads=2, num_layers=2,
                       max_len=64, attention="dense", dtype=jnp.float32, pos_embedding="rotary")
    latent = latent_moe.LatentMoELM.from_config(
        latent_moe.tiny_config(), dtype=jnp.float32, max_len=64)
    hybrid = hybrid_kda.HybridKdaMoELM.from_config(
        {**hybrid_kda.tiny_config(), "num_hidden_layers": 4}, dtype=jnp.float32, max_len=64)
    cache_leaves = {"paged": 4, "latent": 1, "hybrid": 2 + 2}  # K, V a layer; rows; K, V + state, tail
    for name, m in (("paged", lm), ("latent", latent), ("hybrid", hybrid)):
        eng = ContinuousBatchingEngine(m, None, slots=2, block_size=16, max_seq_len=64)
        assert eng.pool is not None and eng._tables.shape == (2, 4), name
        assert eng.pool.num_blocks == 1 + 2 * 4, name
        assert _chain_leaves(eng) == cache_leaves[name] + 1 + 4, name
        assert ("state_bytes" in eng.stats()) == (name == "hybrid")
        assert isinstance(eng.model, PagedTransformerLM) == (name == "paged")
        assert eng._joins_unread_max > 2  # never reached with two slots
    assert _chain_leaves(_engine(model, params)) == 2 + 4
