"""Paged KV decode (moolib_tpu/ops/paged_attention.py + engine/) — ISSUES 12, 25.

``gathered_decode_attention`` is the one definition of the decode-attention
mathematics: the dense ``decode=True`` cache path calls it, and the fused
paged kernel (run here in Pallas interpret mode) is held to it over a gathered
context — to rounding, since the kernel folds an online softmax block by block
and so sums in another order: float32 models agree to 1e-5 and pick the same
tokens; bfloat16 outputs differ by at most one unit in the last place.  Dead
blocks, the null block and the stale tail of a last live block are filled with
NaN so that a stray read shows.  On top of the kernel, the block pool's
free-list invariants and the engine's slot join/retire schedule are pinned
against ``generate()`` greedy decoding under a seeded arrival order.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from moolib_tpu.engine import (
    BlockPool,
    ContinuousBatchingEngine,
    EngineService,
    PoolExhausted,
)
from moolib_tpu.models.transformer import TransformerLM, generate
from moolib_tpu.ops.paged_attention import (
    PagedState,
    gathered_decode_attention,
    paged_attention,
    paged_gather,
)
from moolib_tpu.rpc import Rpc
from moolib_tpu.serving import AdmissionController, ServeClient


# ------------------------------------------- the kernel against the gather
def _kernel_case(kv_heads, block_size, dtype, seed=0, heads=4, hd=8,
                 max_blocks=5):
    """A pool in shuffled order and one slot for each length worth a case: 0,
    1, a block boundary and either side of it, full capacity, a mid value;
    and two inactive slots whose stale rows point at dead blocks.  Returns
    (q, clean pools, poisoned pools, tables, lengths, active)."""
    bs, mb = block_size, max_blocks
    cap = bs * mb
    lengths = np.asarray(
        [0, 1, bs - 2, bs - 1, bs, 2 * bs - 1, 2 * bs, cap // 2 + 1, cap - 2,
         cap - 1, cap // 3, 7], np.int32) % cap
    active = np.ones(len(lengths), bool)
    active[[-2, -1]] = False
    S = len(lengths)
    rng = np.random.default_rng(seed)
    nb = 1 + S * mb
    ids = np.arange(1, nb)
    rng.shuffle(ids)
    tables = ids.reshape(S, mb).astype(np.int32)
    pk = rng.standard_normal((nb, bs, kv_heads, hd)).astype(np.float32)
    pv = rng.standard_normal((nb, bs, kv_heads, hd)).astype(np.float32)
    q = rng.standard_normal((S, 1, heads, hd)).astype(np.float32)
    live = np.zeros((nb, bs), bool)  # positions some active slot attends over
    for slot in np.nonzero(active)[0]:
        for pos in range(lengths[slot] + 1):
            live[tables[slot, pos // bs], pos % bs] = True
    pkn, pvn = pk.copy(), pv.copy()
    pkn[~live] = np.nan  # dead blocks, the null block, last blocks' tails
    pvn[~live] = np.nan
    cast = lambda x: jnp.asarray(x, dtype)
    return (cast(q), (cast(pk), cast(pv)), (cast(pkn), cast(pvn)),
            jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(active))


def _traced_anew(*args):
    """``paged_attention`` under a jit of its own: ``jax.jit`` of the one
    function would answer from the trace an earlier test left, made under
    whatever window and chunk sizes that test had patched in."""
    return jax.jit(lambda *a: paged_attention(*a))(*args)


def _gathered(q, pools, tables, lengths):
    """The oracle: the gathered mathematics over the whole table."""
    return gathered_decode_attention(
        q, paged_gather(pools[0], tables), paged_gather(pools[1], tables),
        lengths)


def _gathered_as_stated(q, pools, tables, lengths):
    """The oracle of the kernel AT ITS STATED PRECISION: the
    gathered mathematics with the products of the stored values summed in
    float32, the softmax statistics in float32, and the softmax weights
    rounded to the pool's dtype before they meet V (their sum is the
    unrounded one).  Returns (the attention in q's dtype, float32
    ``sum_i w_i |v_i|`` over the normalised weights: what a relative error
    of the weights is relative to)."""
    k, v = (np.asarray(paged_gather(p, tables), np.float32) for p in pools)
    S, T_ctx, Hk, hd = k.shape
    H = q.shape[2]
    qg = np.asarray(q, np.float32).reshape(S, Hk, H // Hk, hd)
    sc = np.einsum("shgd,sthd->shgt", qg, k) * np.float32(hd ** -0.5)
    live = np.arange(T_ctx)[None, :] <= np.asarray(lengths)[:, None]
    sc = np.where(live[:, None, None, :], sc, np.float32(-1e30))
    p_att = np.exp(sc - sc.max(-1, keepdims=True))
    total = p_att.sum(-1, keepdims=True)
    rounded = np.asarray(jnp.asarray(p_att).astype(pools[1].dtype), np.float32)
    out = np.einsum("shgt,sthd->shgd", rounded, v) / total
    spread = np.einsum("shgt,sthd->shgd", p_att, np.abs(v)) / total
    shape = (S, 1, H, hd)
    return jnp.asarray(out.reshape(shape)).astype(q.dtype), spread.reshape(shape)


def _assert_close(out, ref, dtype, spread=None):
    """float32: 1e-5 (two float32 sums in different orders, values of order
    1).  bfloat16: both sides round to 8 bits of mantissa a float32 value
    that agrees to 1e-5, so they differ by at most one unit in the last
    place, 2**-7 of the value's binade.  With ``spread`` (the kernel
    against :func:`_gathered_as_stated`): the kernel rounds a weight to
    bfloat16 as ``exp(score - the running maximum)``, when its chunk is
    reduced, the oracle as ``exp(score - the final maximum)``: the same
    weight under another factor, so each side holds it to a relative 2**-9
    (half a unit of 8 bits) and the two weighted sums differ by at most
    2 x 2**-9 x sum_i w_i |v_i| = 2**-8 x spread before the one unit of the
    output's own rounding."""
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    if dtype == jnp.float32:
        np.testing.assert_allclose(out, ref, rtol=0, atol=1e-5)
        assert np.array_equal(out.argmax(-1), ref.argmax(-1))
    else:
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref), 2.0 ** -20))) - 7)
        slack = 0.0 if spread is None else 2.0 ** -8 * spread
        assert (np.abs(out - ref) <= ulp + slack + 1e-5).all(), np.abs(out - ref).max()


def _check_kernel(case):
    """The kernel over the poisoned pools of ``case`` against the oracle of
    its precision over the clean ones, active slots only: the gathered
    mathematics for a float32 pool, the stated precision for a bfloat16
    one."""
    q, clean, poisoned, tables, lengths, active = case
    dtype = q.dtype
    if dtype == jnp.bfloat16:
        ref, spread = _gathered_as_stated(q, clean, tables, lengths)
    else:
        ref, spread = _gathered(q, clean, tables, lengths), None
    out = _traced_anew(q, *poisoned, tables, lengths, active)
    assert out.shape == q.shape and out.dtype == q.dtype
    on = np.asarray(active)
    _assert_close(np.asarray(out, np.float32)[on], np.asarray(ref, np.float32)[on],
                  dtype, None if spread is None else spread[on])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block_size", [4, 16])
@pytest.mark.parametrize("kv_heads", [4, 2, 1], ids=["mha", "gqa", "mqa"])
def test_kernel_matches_gathered_attention(kv_heads, block_size, dtype):
    """The fused kernel over a NaN-poisoned pool against the gathered
    mathematics over the clean one: lengths of 0, 1, a block boundary and
    its neighbours and full capacity, shuffled tables, inactive slots.
    In bfloat16 against the oracle at the kernel's stated precision.  At
    blocks of 4 tokens the pages of ``gqa`` in bfloat16 and of ``mqa`` in
    both dtypes (8 and 4 rows) leave a sublane tile part empty, and are
    padded in the window."""
    _check_kernel(_kernel_case(kv_heads, block_size, dtype))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_grouped_kernel_at_the_long_generation_cells_page_shape(dtype):
    """8 query heads a K/V head over blocks of 128 tokens x 8 K/V heads of
    128 (``solar_serve_longgen``'s page: 1,024 rows, 256 KB in bfloat16), a
    few slots, the NaN-poisoned pool: lengths of 0, a block edge and its
    neighbours, full capacity, inactive slots; six blocks a slot are more
    than one VMEM window (four pages in bfloat16, two in float32), so the
    double-buffered copies wrap inside a slot and a window's last chunk
    holds pages that were never copied."""
    _check_kernel(_kernel_case(8, 128, dtype, heads=64, hd=128, max_blocks=6))


@pytest.mark.parametrize("kv_heads,hd,path", [(4, 128, "mxu"), (2, 128, "mxu"),
                                              (1, 128, "mxu"), (2, 64, "gather")])
def test_path_is_chosen_by_shape_and_counted(kv_heads, hd, path):
    """``paged_attention_traces_total{path}``: one query head a K/V head,
    several and all on one trace the fused kernel, and a head size off the
    128 lanes, asked of Mosaic, the XLA gather (``interpret=False`` is traced
    and lowered for no platform: nothing runs)."""
    from moolib_tpu import telemetry

    def counts():
        values = telemetry.get_registry().counter_values()
        return {p: values.get('paged_attention_traces_total{path="%s"}' % p, 0.0)
                for p in ("mxu", "gather")}

    q, clean, _, tables, lengths, active = _kernel_case(
        kv_heads, 16, jnp.bfloat16, hd=hd)
    before = counts()
    jax.make_jaxpr(lambda *a: paged_attention(*a, interpret=False))(
        q, *clean, tables, lengths, active)
    after = counts()
    assert {p: after[p] - before[p] for p in after} == {
        p: float(p == path) for p in after}


def test_kernel_skips_inactive_slots():
    """An inactive slot's stale row and length are not followed: over a pool
    that is NaN throughout, its output is 0 — with every slot inactive too
    (the kernel then copies nothing at all)."""
    q, clean, _, tables, lengths, _ = _kernel_case(2, 4, jnp.float32)
    nans = [jnp.full_like(x, jnp.nan) for x in clean]
    off = jnp.zeros(lengths.shape, bool)
    out = _traced_anew(q, *nans, tables, lengths, off)
    assert not np.asarray(out).any()
    one = off.at[4].set(True)  # and beside an active slot
    out = _traced_anew(q, *clean, tables, lengths, one)
    ref = _gathered(q, clean, tables, lengths)
    _assert_close(np.asarray(out)[4], np.asarray(ref)[4], jnp.float32)
    assert not np.delete(np.asarray(out), 4, axis=0).any()


@pytest.mark.parametrize("patch", [{"_WINDOW_BYTES": 1}, {"_CHUNK_ROWS": 16}],
                         ids=["a_block_a_window", "chunks_of_two_pages"])
def test_kernel_windows_span_many_blocks_and_lengths_clip(monkeypatch, patch):
    """More live blocks than one VMEM window holds (the double-buffered
    copies wrap around) and a length past the table's capacity (clipped).
    With chunks of two pages of 8 rows a window is six
    pages of seven, reduced in three chunks, and the second window's one
    chunk holds a page that was never copied."""
    from moolib_tpu.ops import paged_attention as pa

    for name, value in patch.items():
        monkeypatch.setattr(pa, name, value)
    case = _kernel_case(2, 4, jnp.float32, seed=1, max_blocks=7)
    _check_kernel(case)
    q, clean, _, tables, lengths, active = case
    # Past capacity: every block of the row is live, none beyond is read.
    over = jnp.where(jnp.arange(lengths.shape[0]) == 3, 10_000, lengths)
    full = jnp.where(jnp.arange(lengths.shape[0]) == 3, 4 * 7 - 1, lengths)
    a = _traced_anew(q, *clean, tables, over, active)
    b = _traced_anew(q, *clean, tables, full, active)
    assert np.array_equal(np.asarray(a)[3], np.asarray(b)[3])


def test_kernel_rejects_what_it_cannot_attend():
    q, clean, _, tables, lengths, _ = _kernel_case(2, 4, jnp.float32)
    with pytest.raises(ValueError, match="one query position"):
        paged_attention(jnp.concatenate([q, q], axis=1), *clean, tables, lengths)
    with pytest.raises(ValueError, match="multiple of Hk"):
        paged_attention(q[:, :, :3], *clean, tables, lengths)


def test_engine_observes_the_live_share_every_decode_step():
    """``serve_engine_kv_live_share``: once a decode step, from the host's
    mirrors: blocks holding a position the step attends over, over
    slots x max_blocks_per_seq."""
    from moolib_tpu.engine.engine import _M_KV_LIVE

    def hist():
        v = _M_KV_LIVE.labels().get()
        return v["sum"], v["count"]

    model = TransformerLM(vocab_size=32, d_model=32, num_heads=2,
                          num_layers=1, max_len=32, attention="dense",
                          dtype=jnp.float32, pos_embedding="rotary")
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    eng = ContinuousBatchingEngine(model, params, slots=2, block_size=4,
                                   max_seq_len=16, max_prompt_len=8)
    sum0, n0 = hist()
    slot, _ = eng.submit(np.asarray([1, 2, 3], np.int32), 4)  # 3 more steps
    assert slot is not None
    # ... behind the step that carries the admission, which advances no slot
    # and attends over nothing: it observes 0.
    for _ in range(4):
        eng.step()
    total, n = hist()
    assert n - n0 == 4
    # Lengths 3, 4, 5 in blocks of 4: 1, 2, 2 live blocks of 2 slots x 4.
    assert total - sum0 == pytest.approx((1 + 2 + 2) / 8)


# ------------------------------------------- paged decode against the dense
@pytest.mark.parametrize(
    "kv_heads,block_size,pos",
    [
        (4, 4, "rotary"),    # MHA, tiny blocks (many blocks per sequence)
        (4, 16, "rotary"),   # MHA, one block = max_len (degenerate paging)
        (2, 4, "rotary"),    # GQA
        (2, 8, "rotary"),    # GQA, mid-size blocks
        (2, 4, "learned"),   # GQA + learned positions (paged offset path)
    ],
)
def test_paged_decode_bit_exact_vs_dense(kv_heads, block_size, pos):
    """Step-by-step decode through a SHUFFLED block table must produce
    logits bitwise equal to the dense per-sequence cache path."""
    S, M, V = 3, 16, 50
    nb_per = M // block_size
    num_blocks = 1 + S * nb_per
    kw = dict(vocab_size=V, d_model=32, num_heads=4, num_kv_heads=kv_heads,
              num_layers=2, max_len=M, attention="dense", dtype=jnp.float32,
              pos_embedding=pos)
    dense = TransformerLM(decode=True, **kw)
    paged = TransformerLM(decode=True, kv_num_blocks=num_blocks,
                          kv_block_size=block_size, **kw)
    rng = jax.random.key(0)
    tok0 = jnp.zeros((S, 1), jnp.int32)
    dv = dense.init(rng, tok0)
    p = dv["params"]
    # init() runs a real forward (caches advance to idx=1) — re-zero both
    # caches so the comparison starts from a clean t=0 state.
    cd = jax.tree.map(jnp.zeros_like, dv["cache"])
    # Non-contiguous block placement: correctness must not depend on the
    # allocation order the free list happened to produce.
    ids = np.arange(1, num_blocks)
    np.random.default_rng(0).shuffle(ids)
    tables = jnp.asarray(ids.reshape(S, nb_per), jnp.int32)
    st = PagedState(tables, jnp.zeros((S,), jnp.int32), jnp.ones((S,), bool))
    cp = jax.tree.map(jnp.zeros_like, paged.init(rng, tok0, paged=st)["cache"])
    toks = np.random.default_rng(1).integers(0, V, size=(S, 10))
    toks = toks.astype(np.int32)
    lengths = jnp.zeros((S,), jnp.int32)
    # jitted: the kernel runs in interpret mode here, which traced eagerly
    # would be lowered anew at every step.
    dense_step = jax.jit(lambda c, t: dense.apply(
        {"params": p, "cache": c}, t, mutable=["cache"]))
    paged_step = jax.jit(lambda c, t, st: paged.apply(
        {"params": p, "cache": c}, t, paged=st, mutable=["cache"]))
    for s in range(10):
        t = jnp.asarray(toks[:, s:s + 1])
        ld, ud = dense_step(cd, t)
        cd = ud["cache"]
        stt = PagedState(tables, lengths, jnp.ones((S,), bool))
        lp, up = paged_step(cp, t, stt)
        cp = up["cache"]
        lengths = lengths + 1
        # float32 model: the kernel sums in another order than the dense
        # path's einsum, so the logits agree to float32 rounding (1e-5 of
        # logits of order 1), not bit for bit, and pick the same token.
        ld, lp = np.asarray(ld), np.asarray(lp)
        np.testing.assert_allclose(lp, ld, rtol=0, atol=1e-5, err_msg=f"step {s}")
        assert np.array_equal(ld.argmax(-1), lp.argmax(-1)), f"step {s}"


def test_paged_decode_inactive_slots_write_null_block():
    """Inactive slots scatter into the reserved null block (id 0): their
    presence must not perturb active slots' logits, and no real block may
    be written by an inactive lane."""
    S, M, V, bs = 4, 16, 50, 4
    num_blocks = 1 + S * (M // bs)
    model = TransformerLM(vocab_size=V, d_model=32, num_heads=4,
                          num_kv_heads=2, num_layers=2, max_len=M,
                          attention="dense", dtype=jnp.float32,
                          pos_embedding="rotary", decode=True,
                          kv_num_blocks=num_blocks, kv_block_size=bs)
    rng = jax.random.key(0)
    tok0 = jnp.zeros((S, 1), jnp.int32)
    tables = jnp.arange(1, num_blocks, dtype=jnp.int32).reshape(S, M // bs)
    st = PagedState(tables, jnp.zeros((S,), jnp.int32), jnp.ones((S,), bool))
    v = model.init(rng, tok0, paged=st)
    p = v["params"]
    cache = jax.tree.map(jnp.zeros_like, v["cache"])
    active = jnp.asarray([True, False, True, False])
    toks = jnp.asarray(
        np.random.default_rng(2).integers(0, V, (S, 1)), jnp.int32
    )
    stt = PagedState(tables, jnp.zeros((S,), jnp.int32), active)
    _, upd = model.apply({"params": p, "cache": cache}, toks, paged=stt,
                         mutable=["cache"])
    for name, c in upd["cache"].items():
        for pool in (c["pool_k"], c["pool_v"]):
            arr = np.asarray(pool)
            # Inactive slots 1 and 3 own rows 1 and 3 of the table; their
            # blocks must be untouched (all zeros).
            for slot in (1, 3):
                for blk in np.asarray(tables[slot]):
                    assert not arr[blk].any(), (name, slot, int(blk))


# ----------------------------------------------------------------- BlockPool
def test_block_pool_invariants_random_schedule():
    pool = BlockPool(num_blocks=33, block_size=4)
    rng = np.random.default_rng(42)
    held = []
    for _ in range(300):
        if held and rng.random() < 0.45:
            pool.free(held.pop(rng.integers(len(held))))
        else:
            want = int(rng.integers(1, 5))
            if pool.available() < want:
                with pytest.raises(PoolExhausted):
                    pool.alloc(pool.available() + 1)
            else:
                blocks = pool.alloc(want)
                assert 0 not in blocks  # null block never escapes
                held.append(blocks)
        pool.check_invariants()
    for b in held:
        pool.free(b)
    pool.check_invariants()
    assert pool.available() == 32
    assert pool.stats()["utilization"] == 0.0


def test_block_pool_failed_alloc_is_atomic_and_double_free_raises():
    pool = BlockPool(num_blocks=5, block_size=4)  # 4 usable
    a = pool.alloc(3)
    before = pool.available()
    with pytest.raises(PoolExhausted):
        pool.alloc(2)  # only 1 free: must not half-allocate
    assert pool.available() == before
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)  # double free
    with pytest.raises(ValueError):
        pool.free([0])  # the null block is never owned by anyone
    pool.check_invariants()


def test_block_pool_blocks_for():
    pool = BlockPool(num_blocks=9, block_size=4)
    assert [pool.blocks_for(n) for n in (0, 1, 4, 5, 8, 9)] == [
        1, 1, 1, 2, 2, 3,
    ]


# ------------------------------------------------- engine vs generate()
def test_engine_matches_generate_under_seeded_schedule():
    """Mixed prompt lengths and budgets through slot join/retire must
    reproduce ``generate()`` greedy continuations token-for-token —
    including budget-1 requests that finish at prefill — with the block
    pool fully drained afterwards and ZERO decode-step recompiles after
    warmup (slot churn is data, not shape)."""
    V = 64
    model = TransformerLM(vocab_size=V, d_model=32, num_heads=4,
                          num_kv_heads=2, num_layers=2, max_len=64,
                          attention="dense", dtype=jnp.float32,
                          pos_embedding="rotary")
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    eng = ContinuousBatchingEngine(model, params, slots=3, block_size=4,
                                   max_seq_len=64, max_prompt_len=16)
    eng.warmup()
    step_cache = eng._step_jit._cache_size()
    assert step_cache == 1  # ONE decode shape, compiled once

    rng = np.random.default_rng(3)
    reqs = [
        (rng.integers(1, V, size=rng.integers(3, 12)).astype(np.int32),
         int(mn))
        for mn in (1, 3, 8, 5, 12, 2)
    ]
    refs = [np.asarray(generate(model, params, jnp.asarray(p[None]), mn))[0]
            for p, mn in reqs]

    outs = {}
    slot_of = {}
    pending = list(enumerate(reqs))
    steps = 0
    while len(outs) < len(reqs):
        while pending:
            i, (p, mn) = pending[0]
            if not eng.can_accept(len(p), mn):
                break
            pending.pop(0)
            slot, em = eng.submit(p, mn)
            if slot is None:  # finished at prefill (budget 1)
                outs[i] = np.concatenate([p, np.asarray(em, np.int32)])
            else:
                slot_of[slot] = (i, p)
        _, fin = eng.step()
        steps += 1
        assert steps < 200, "engine never drained"
        for s in fin:
            i, p = slot_of.pop(s)
            outs[i] = np.concatenate([p, np.asarray(eng.retire(s), np.int32)])

    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(outs[i], ref, err_msg=f"request {i}")
    # Continuous batching's throughput claim in miniature: total decode
    # steps track the LONGEST request, not the sum of budgets.
    assert steps < sum(mn for _, mn in reqs)
    # No leaks: every block back on the free list, every slot free.
    eng.pool.check_invariants()
    assert eng.pool.available() == eng.pool.num_blocks - 1
    assert eng.active_count() == 0
    st = eng.stats()
    # every admission of this model rides a decode step, the budget of 1 too:
    # it holds a slot for that one step (under programs of its own it joins none)
    assert st["joins"] == st["retires"] == 6
    # Join/retire churn caused no recompiles.
    assert eng._step_jit._cache_size() == step_cache


def test_engine_rejects_oversized_and_reports_capacity():
    model = TransformerLM(vocab_size=32, d_model=32, num_heads=2,
                          num_layers=1, max_len=32, attention="dense",
                          dtype=jnp.float32, pos_embedding="rotary")
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    eng = ContinuousBatchingEngine(model, params, slots=2, block_size=4,
                                   max_seq_len=16, max_prompt_len=8,
                                   num_blocks=3)  # null + 2 usable
    with pytest.raises(ValueError):
        eng.submit(np.ones(9, np.int32), 2)  # prompt > max_prompt_len
    with pytest.raises(ValueError):
        eng.submit(np.ones(8, np.int32), 9)  # prompt + budget > capacity
    with pytest.raises(ValueError):
        eng.submit(np.zeros(0, np.int32), 2)  # empty prompt
    assert eng.can_accept(4, 2)       # 6 tokens -> 2 blocks: fits
    assert not eng.can_accept(4, 8)   # 12 tokens -> 3 blocks: pool-bound
    assert eng.active_count() == 0


def test_engine_eos_retires_early():
    """A sequence that argmax-emits the EOS id retires before its budget."""
    V = 16
    model = TransformerLM(vocab_size=V, d_model=32, num_heads=2,
                          num_layers=1, max_len=32, attention="dense",
                          dtype=jnp.float32, pos_embedding="rotary")
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    prompt = np.asarray([1, 2, 3], np.int32)
    # Find what greedy decoding emits, then declare that token EOS.
    ref = np.asarray(generate(model, params, jnp.asarray(prompt[None]), 8))[0]
    eos = int(ref[len(prompt) + 2])  # third emitted token
    eng = ContinuousBatchingEngine(model, params, slots=2, block_size=4,
                                   max_seq_len=16, max_prompt_len=8,
                                   eos_id=eos)
    slot, em = eng.submit(prompt, 8)
    if slot is not None:
        for _ in range(20):
            _, fin = eng.step()
            if fin:
                em = eng.retire(fin[0])
                break
    assert em[-1] == eos
    assert len(em) <= 3  # retired at EOS, not at budget 8


# --------------------------------------------- per-token admission control
def test_admission_controller_per_token_mode():
    pending = {"tokens": 0}
    ac = AdmissionController(max_queue=8, per_token=True,
                             pending_tokens=lambda: pending["tokens"])
    assert ac.admit(0, deadline_s=0.001) is None  # no EMA yet
    ac.note_service(0.5, tokens=5)  # 0.1 s/token
    assert ac.ema_batch_seconds() == pytest.approx(0.1)
    pending["tokens"] = 100
    assert ac.estimate_wait(3) == pytest.approx(10.0)  # depth is irrelevant
    assert ac.admit(3, deadline_s=5.0) == "deadline"
    assert ac.admit(3, deadline_s=20.0) is None
    ac.note_service(0.0, tokens=0)  # zero-token step never poisons the EMA
    assert ac.ema_batch_seconds() == pytest.approx(0.1)
    assert ac.admit(8, deadline_s=None) == "queue_full"


# --------------------------------------------------- EngineService over RPC
def _addr_of(rpc: Rpc) -> str:
    return next(
        a for a in rpc._listen_addrs if a.startswith("tcp://127")
    ).replace("tcp://", "")


class EngineHarness:
    """EngineService fronting a real ContinuousBatchingEngine on loopback,
    its loop on a daemon thread (the engine analogue of ServiceHarness in
    test_serving.py)."""

    def __init__(self, **engine_kw):
        self.model = TransformerLM(
            vocab_size=64, d_model=32, num_heads=4, num_kv_heads=2,
            num_layers=2, max_len=64, attention="dense", dtype=jnp.float32,
            pos_embedding="rotary",
        )
        self.params = self.model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )
        self.engine = ContinuousBatchingEngine(
            self.model, self.params, slots=3, block_size=4,
            max_seq_len=64, max_prompt_len=8, **engine_kw,
        )
        self.rpc = Rpc()
        self.rpc.set_name("server")
        self.rpc.listen("127.0.0.1:0")
        self.service = EngineService(self.rpc, self.engine,
                                     default_max_new=4)
        self.addr = _addr_of(self.rpc)
        self._thread = None

    def start(self, total=None):
        self._thread = threading.Thread(
            target=lambda: asyncio.run(self.service.loop(total=total)),
            daemon=True,
        )
        self._thread.start()
        return self

    def close(self):
        self.service.close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        self.rpc.close()


def test_engine_service_roundtrip_mixed_budgets():
    """Concurrent requests with DIFFERENT budgets through the full RPC
    stack must each match ``generate()`` — the convoy-free contract at the
    service boundary, including a budget-1 prefill-finish."""
    h = EngineHarness()
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        rng = np.random.default_rng(7)
        reqs = [(rng.integers(1, 64, size=5 + i % 4).astype(np.int32), mn)
                for i, mn in enumerate((6, 1, 12, 3, 9))]
        refs = [np.asarray(generate(h.model, h.params,
                                    jnp.asarray(p[None]), mn))[0]
                for p, mn in reqs]
        h.start()
        cl = ServeClient(client, fn="generate", replicas=["server"],
                         deadline_s=60.0)
        futs = [cl.submit(p, mn) for p, mn in reqs]
        outs = [np.asarray(f.result(60.0)) for f in futs]
        for i, (out, ref) in enumerate(zip(outs, refs)):
            np.testing.assert_array_equal(out, ref, err_msg=f"request {i}")
        st = h.service.stats()
        assert st["served"] == 5
        assert st["engine"]["retires"] == st["engine"]["joins"]
        assert st["ema_token_seconds"] is not None  # per-token EMA primed
        cl.close()
    finally:
        client.close()
        h.close()


def test_engine_service_hot_swap_between_decode_steps():
    """A weight swap staged mid-decode installs between steps with zero
    errors: every in-flight future completes, the version bumps, and the
    engine keeps serving under the new weights."""
    h = EngineHarness()
    params2 = jax.tree.map(lambda x: x * 1.5, h.params)
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        h.start()
        cl = ServeClient(client, fn="generate", replicas=["server"],
                         deadline_s=60.0)
        rng = np.random.default_rng(9)
        futs = [cl.submit(rng.integers(1, 64, size=6).astype(np.int32), 12)
                for _ in range(4)]
        time.sleep(0.05)
        assert h.service.stage(5, params2, time.monotonic())
        for f in futs:
            np.asarray(f.result(60.0))  # zero errors across the swap
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if h.service.model_version() == 5:
                break
            time.sleep(0.02)
        assert h.service.model_version() == 5
        assert h.service.stats()["hot_swaps"] == 1
        # Post-swap requests answer under the new weights.
        prompt = rng.integers(1, 64, size=6).astype(np.int32)
        ref = np.asarray(generate(h.model, params2,
                                  jnp.asarray(prompt[None]), 5))[0]
        np.testing.assert_array_equal(np.asarray(cl.call(prompt, 5)), ref)
        cl.close()
    finally:
        client.close()
        h.close()
