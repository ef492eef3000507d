"""Paged KV-cache decode attention (a fused kernel over the block pool).

The serving engine's KV layout: instead of one dense ``[B, max_len, Hk, hd]``
cache per sequence, K/V live in a shared device-resident pool of fixed-size
token blocks ``[num_blocks, block_size, Hk, hd]`` and each decode *slot* owns
an int32 row of block ids (its block table).

:func:`paged_attention` is one Pallas (Mosaic) kernel that reads the pool in
place.  The block tables and lengths sit in SMEM; per slot the kernel copies
only the blocks that hold live positions (``<= lengths[s]``) from HBM into a
double-buffered VMEM window of a megabyte, while the previous window is being
reduced, and folds them into an online softmax (running maximum, sum and
weighted sum in float32).  So a decode step costs the live blocks, not
``slots x max_blocks_per_seq``: an inactive slot is skipped (it reads nothing
and gives 0) and a dead table entry is never dereferenced.  Nothing of the
size of the gathered context exists in HBM.

The mathematics is :func:`gathered_decode_attention`'s, which stays the one
definition of it: the dense ``decode=True`` path of ``models.transformer.Block``
calls it directly, and ``tests/test_paged_attention.py`` holds the kernel to
it over a gathered context (:func:`paged_gather`).

The two products are matmuls on the MXU, for every shape.  A window's K (and
V) is one ``[tokens x Hk, hd]`` operand exactly as the pool stores it (a block
``[block_size, Hk, hd]`` is already that, row-major): no transpose, no fold of
tokens into heads, nothing merged after the kernel.  Scores are ``q [H, hd]``
against a chunk of it in one ``dot_general``, scaled in float32 after the
product; a column whose K/V head is not the row's (its group's, where a K/V
head serves ``H // Hk`` query heads), or whose position is past the length,
is set to -1e30 before the maximum; the weights go to the pool's dtype and
meet V in a second ``dot_general``.  Every head is multiplied against every
K/V head's rows, ``Hk`` times the score columns that count: a K/V tile is
loaded onto the MXU once either way and the softmax of the rest lies under
the copies of the next window, so the kernel is bound by the copies at one
query head a K/V head (16 of each) as at eight (PERF.md, PR 45, which also
has what the per-head kernel on the VPU read, that this one replaced).
Precision, the latent kernel's contract: products in the pool's dtype (q is
cast to it) with float32 accumulation; masks, scale and softmax statistics
float32; a float32 pool multiplies in float32 (``Precision.HIGHEST``).  V
rows past the length (a last block's stale tail, a page of the window that
was not copied) are selected to zero before the product, since ``0 x NaN`` is
NaN on the MXU as on the VPU.  A block whose ``block_size x Hk`` rows do not
fill whole sublane tiles of the pool's dtype (16 rows of bfloat16, 8 of
float32) is copied into a page of the window that does, and the padding is
masked like a position past the length.

Like ``ops.flash_attention`` the kernel lowers through Mosaic on ``tpu`` and
runs in Pallas interpret mode on ``cpu`` (where tier-1 CI executes); any other
platform raises.  What Mosaic cannot copy is a head size that is not a
multiple of the 128 lanes (no block can be copied out of a lane-padded pool):
such a call is traced onto :func:`gathered_decode_attention` over the XLA
gather of the whole capacity.  ``paged_attention_traces_total{path}`` counts
at trace time which of the two a call took (``mxu``, ``gather``; a shape has
one, never a mix), and ``paged_gather_reroutes_total`` the second alone.

Block id 0 is the *null block*: never handed out by the allocator, and the
write path redirects inactive slots' scatters at it, so a fixed-shape jitted
step over all S slots never branches on occupancy.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry

_NEG_INF = -1e30

_M_GATHER_REROUTES = telemetry.get_registry().counter(
    "paged_gather_reroutes_total",
    "paged_attention calls traced onto the XLA gather path (cost of the whole "
    "slot capacity) because the head size is not a multiple of the 128 lanes "
    "a Mosaic copy of a pool block needs",
)
_M_TRACES = telemetry.get_registry().counter(
    "paged_attention_traces_total",
    "paged_attention calls traced, by the path the operands' shapes chose: "
    "mxu (the fused kernel, both products matmuls), gather (a head size "
    "Mosaic cannot copy: the XLA gather, also counted in "
    "paged_gather_reroutes_total)",
    labelnames=("path",),
)


class PagedState(NamedTuple):
    """Per-slot decode state threaded through a paged decode step.

    block_tables: int32 [S, max_blocks_per_seq] — pool block ids per slot
        (unused tail entries hold 0, the null block).
    lengths: int32 [S] — tokens already in the cache for each slot; the
        current step writes at position ``lengths`` and attends over
        ``<= lengths`` (the just-written token included).
    active: bool [S] — occupied slots.  Inactive slots still execute the
        step (fixed shape); their writes land in the null block, the
        attention kernel skips them, and their outputs are ignored by the
        engine.
    slots: int32 [R] or None — the slot of each ROW, where the step runs over
        fewer rows than the engine has slots (``engine/engine.py``): the
        three fields above then have R rows, distinct slots all, the active
        ones first.  Pool leaves need nothing of it (a row's table stands
        between it and its K/V); a leaf with a slot axis is read and written
        at ``slots``.  None: row i is slot i.
    """

    block_tables: jax.Array
    lengths: jax.Array
    active: jax.Array
    slots: Optional[jax.Array] = None


def gathered_decode_attention(q, k_ctx, v_ctx, t):
    """Single-position grouped-query attention over a gathered context.

    q: [B, 1, H, hd]; k_ctx/v_ctx: [B, T_ctx, Hk, hd] (any dtype — cast to
    f32 here, like the dense path); t: scalar or [B] int — attend over
    positions ``<= t`` (everything past t contributes exactly 0: the -1e30
    masked scores underflow to 0 in the f32 softmax).  This is the one
    definition of the decode-attention math: the dense ``decode=True`` branch
    calls it, and the paged kernel is tested against it over a gathered
    context (and reroutes onto it for a head size Mosaic cannot copy).
    """
    B, T, H, hd = q.shape
    Hk = k_ctx.shape[2]
    group = H // Hk
    T_ctx = k_ctx.shape[1]
    scale = hd**-0.5
    qg = q.reshape(B, T, Hk, group, hd)
    scores = (
        jnp.einsum(
            "bqhgd,bkhd->bhgqk",
            qg.astype(jnp.float32),
            k_ctx.astype(jnp.float32),
        )
        * scale
    )
    t = jnp.asarray(t)
    pos = jnp.arange(T_ctx)
    if t.ndim == 0:
        mask = pos[None, None, None, None, :] <= t
    else:
        mask = pos[None, None, None, None, :] <= t[:, None, None, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    p_att = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhgqk,bkhd->bqhgd", p_att, v_ctx.astype(jnp.float32))
    return att.reshape(B, T, H, hd).astype(q.dtype)


@jax.named_scope("paged_attention")
def paged_kv_write(pool, x, block_tables, lengths, active):
    """Scatter one new K (or V) row per slot into the block pool, in place.

    pool: [num_blocks, block_size, Hk, hd]; x: [S, Hk, hd] (this step's K or
    V at position ``lengths``); block_tables/lengths/active as in
    :class:`PagedState`.  Inactive slots write to the null block 0 — the
    allocator never hands it out, so the garbage is harmless and the op
    keeps a fixed shape.  Used under donation: ``pool.at[...].set`` on a
    donated buffer updates HBM in place (no copy at join/retire).
    """
    bs = pool.shape[1]
    blk = jnp.take_along_axis(block_tables, (lengths // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(active, blk, 0)
    off = lengths % bs
    return pool.at[blk, off].set(x.astype(pool.dtype))


@jax.named_scope("paged_attention")
def paged_gather(pool, block_tables):
    """Gather each slot's blocks into a contiguous [S, T_ctx, Hk, hd] context
    (T_ctx = max_blocks_per_seq * block_size).  Positions past a slot's
    length are stale pool contents; the attention mask zeroes them."""
    S, nb = block_tables.shape
    ctx = pool[block_tables]  # [S, nb, bs, Hk, hd]
    return ctx.reshape(S, nb * pool.shape[1], *pool.shape[2:])


def _paged_kernel(
    order_ref, count_ref, tables_ref, lengths_ref, q_ref, pool_k_ref, pool_v_ref,
    o_ref, k_buf, v_buf, sem, *, pages, chunk, page_rows, kv_heads, group, scale,
    precision,
):
    """All slots of one layer's decode attention.  In SMEM: order [S] (the
    active slots first), count [1] (how many are active), tables [S, MB] and
    lengths [S]; q [S, Hp, hd] in VMEM in the pool's dtype, unscaled (head h
    reads K/V head h // group; rows past H are padding); the pools
    [NB, rows, hd] stay in HBM, a block as it is stored (rows =
    block_size * Hk: row r is token r // Hk of the block, K/V head r % Hk);
    k_buf/v_buf [2, pages * page_rows, hd] are the two VMEM windows, a page
    ``page_rows`` >= rows apart (whole sublane tiles), reduced ``chunk`` pages
    at a time: every head against every row of a chunk in one product, the
    columns of other heads' K/V heads masked out of the softmax."""
    MB = tables_ref.shape[1]
    Hp, hd = o_ref.shape[1:]
    rows = pool_k_ref.shape[1]
    block_size = rows // kv_heads
    C = chunk * page_rows
    never = 1 << 30  # a position that is never live

    def live_blocks(s):
        # At least one: the copies are chained from window to window, and a
        # slot with none would break the chain.
        return jnp.clip(lengths_ref[s] // block_size + 1, 1, MB)

    def window_pages(s, w):
        return jnp.minimum(live_blocks(s) - w * pages, pages)

    def for_each_copy(s, w, buf, do):
        """``do`` the copy of K and of V for every live block of window w of
        slot s; each window's copies share a semaphore, so starting them and
        waiting for them walk the same descriptors."""

        def body(p, carry):
            blk = tables_ref[s, w * pages + p]
            page = pl.ds(pl.multiple_of(p * page_rows, page_rows), rows)
            do(pltpu.make_async_copy(pool_k_ref.at[blk], k_buf.at[buf, page], sem.at[0, buf]))
            do(pltpu.make_async_copy(pool_v_ref.at[blk], v_buf.at[buf, page], sem.at[1, buf]))
            return carry

        jax.lax.fori_loop(0, window_pages(s, w), body, 0)

    def start(s, w, buf):
        for_each_copy(s, w, buf, lambda copy: copy.start())

    def wait(s, w, buf):
        for_each_copy(s, w, buf, lambda copy: copy.wait())

    def chunk_position(row):
        """Of row ``row`` of a chunk, the position inside the chunk of the
        token it holds; never live where it is a page's padding."""
        page, r = row // page_rows, row % page_rows
        return jnp.where(r < rows, page * block_size + r // kv_heads, never)

    # A chunk's row is a column of the scores: live for the query heads of
    # its K/V head's group only.
    col = jax.lax.broadcasted_iota(jnp.int32, (Hp, C), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (Hp, C), 0)
    position = jnp.where(
        col % page_rows % kv_heads == head // group, chunk_position(col), never)
    row_position = chunk_position(jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0))

    # Slots the loop never visits (inactive ones) read nothing and give 0.
    o_ref[...] = jnp.zeros_like(o_ref)
    count = count_ref[0]

    @pl.when(count > 0)
    def _():
        start(order_ref[0], 0, 0)

    def slot_body(i, n_windows_done):
        s = order_ref[i]
        # Clipped to the table's capacity: a window's last chunk may hold a
        # page past the table's end that nothing copied, and only the length
        # keeps such a page out of the softmax.
        length = jnp.minimum(lengths_ref[s], MB * block_size - 1)
        n_windows = pl.cdiv(live_blocks(s), pages)
        q = q_ref[s]  # [Hp, hd]

        def window_body(w, carry):
            n_done, stats = carry
            buf = n_done % 2
            # The window after this one, which may be the next active slot's
            # first (each has one: position 0 is always attended).
            last = w + 1 >= n_windows
            i_next = jnp.where(last, i + 1, i)
            w_next = jnp.where(last, 0, w + 1)

            @pl.when(i_next < count)
            def _():
                start(order_ref[i_next], w_next, 1 - buf)

            wait(s, w, buf)

            def chunk_body(c, stats):
                m, l, acc = stats
                at = pl.ds(pl.multiple_of(c * C, C), C)
                # Positions of the chunk that the slot attends over: 0 .. left.
                left = length - (w * pages + c * chunk) * block_size
                k = k_buf[buf, at]  # [C, hd]
                v = v_buf[buf, at]
                # Rows past the length are a stale tail, a page's padding or
                # a page that was not copied (whatever VMEM held): V's are
                # zeroed, since 0 x NaN is NaN; K's reach only their own
                # column of the scores, which the select below overwrites.
                v = jnp.where(row_position <= left, v, jnp.zeros_like(v))
                sc = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32) * scale  # [Hp, C]
                sc = jnp.where(position <= left, sc, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
                corr = jnp.exp(m - m_new)
                p_att = jnp.exp(sc - m_new)
                l = l * corr + jnp.sum(p_att, axis=-1, keepdims=True)
                acc = acc * corr + jax.lax.dot_general(
                    p_att.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    precision=precision, preferred_element_type=jnp.float32)
                return m_new, l, acc

            stats = jax.lax.fori_loop(
                0, pl.cdiv(window_pages(s, w), chunk), chunk_body, stats)
            return n_done + 1, stats

        init = (
            jnp.full((Hp, 1), _NEG_INF, jnp.float32),
            jnp.zeros((Hp, 1), jnp.float32),
            jnp.zeros((Hp, hd), jnp.float32),
        )
        n_windows_done, (_m, l, acc) = jax.lax.fori_loop(
            0, n_windows, window_body, (n_windows_done, init)
        )
        o_ref[s] = acc / l
        return n_windows_done

    jax.lax.fori_loop(0, count, slot_body, 0)


# One VMEM window of K (and one of V; two of each are held).
_WINDOW_BYTES = 1 << 20
# Rows of a window reduced in one pair of products: the scores of a chunk are
# [heads, rows] float32 and live in vector registers between the two.
_CHUNK_ROWS = 2048


@jax.named_scope("paged_attention")
def paged_attention(q, pool_k, pool_v, block_tables, lengths, active=None, *,
                    interpret=None):
    """Decode attention against a paged KV pool, read in place by one fused
    kernel (module docstring).  q: [S, 1, H, hd]; pool_k/pool_v:
    [num_blocks, block_size, Hk, hd]; block_tables: int32 [S, max_blocks];
    lengths: int32 [S], slot s attends over positions ``<= lengths[s]``
    (clipped to the table's capacity); returns [S, 1, H, hd] in q's dtype.
    Products are in the pool's dtype (q is cast to it) with float32
    accumulation, the softmax float32.

    ``active`` (bool [S], optional): an inactive slot is skipped, whatever
    its stale row and length hold: it reads nothing and its output is 0,
    for the caller to ignore.  ``interpret`` is chosen from
    ``jax.default_backend()`` when None: Mosaic on ``tpu``, Pallas interpret
    mode on ``cpu``.  The block tables and lengths ride in SMEM whole (8 KB
    at 32 slots x 64 blocks).
    """
    if interpret is None:
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise RuntimeError(
                f"paged_attention has a Mosaic (tpu) lowering and a cpu "
                f"interpret mode for tests; platform {backend!r} has neither"
            )
        interpret = backend == "cpu"
    S, T, H, hd = q.shape
    num_blocks, block_size, Hk, _ = pool_k.shape
    if T != 1 or H % Hk:
        raise ValueError(
            f"paged_attention takes one query position a slot and H a multiple "
            f"of Hk, got q {q.shape} against a pool {pool_k.shape}"
        )
    if not interpret and hd % 128:
        _M_GATHER_REROUTES.inc()
        _M_TRACES.inc(path="gather")
        return gathered_decode_attention(
            q, paged_gather(pool_k, block_tables),
            paged_gather(pool_v, block_tables), lengths,
        )
    _M_TRACES.inc(path="mxu")

    if active is None:
        active = jnp.ones((S,), jnp.bool_)
    itemsize = pool_k.dtype.itemsize
    tile_rows = 32 // itemsize  # sublanes of a (sublane, lane) tile of the dtype
    # A page of the window: a block's rows, padded to whole tiles where
    # block_size x Hk leaves one part empty.
    page_rows = -(-block_size * Hk // tile_rows) * tile_rows
    fit = max(1, min(block_tables.shape[1], _WINDOW_BYTES // (page_rows * hd * itemsize)))
    chunk = max(1, min(fit, _CHUNK_ROWS // page_rows))
    return _paged_call(
        q, pool_k, pool_v, block_tables, lengths, active,
        pages=fit // chunk * chunk,  # whole chunks
        chunk=chunk, page_rows=page_rows, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("pages", "chunk", "page_rows", "interpret"))
def _paged_call(
    q, pool_k, pool_v, block_tables, lengths, active, *, pages, chunk, page_rows,
    interpret,
):
    """:func:`paged_attention` past its checks.  A jit of its own, so that
    the layers of a model, which call it with the same shapes, share one
    trace of the kernel and one lowering (a third of a second of set-up a
    layer)."""
    S, _, H, hd = q.shape
    num_blocks, block_size, Hk, _ = pool_k.shape
    dtype = pool_k.dtype
    # A block as one [tokens x Hk, hd] operand: a row-major reshape of the
    # pool (a bitcast on the chip).
    pool_k = pool_k.reshape(num_blocks, block_size * Hk, hd)
    pool_v = pool_v.reshape(num_blocks, block_size * Hk, hd)
    # Heads fill whole sublane tiles of the pool's dtype; the padding's
    # output is cut off below.
    tile_rows = 32 // dtype.itemsize
    Hp = -(-H // tile_rows) * tile_rows
    qp = jnp.pad(q.astype(dtype).reshape(S, H, hd), ((0, 0), (0, Hp - H), (0, 0)))
    # The active slots first, in slot order: the kernel visits those only.
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    count = jnp.sum(active, dtype=jnp.int32).reshape(1)
    window = (2, pages * page_rows, hd)
    kernel = functools.partial(
        _paged_kernel, pages=pages, chunk=chunk, page_rows=page_rows, kv_heads=Hk,
        group=H // Hk, scale=hd**-0.5,
        # float32 operands multiply in float32 (Mosaic's default is one
        # bfloat16 pass); bfloat16 products are exact in the accumulator.
        precision=jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None,
    )
    # The scope is what the profiler's trace calls the kernel's operation:
    # paged_attention_<result type and shape>.
    with jax.named_scope("paged_attention"):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((S, Hp, hd), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 4 + [
                pl.BlockSpec(memory_space=pltpu.VMEM),  # q
                pl.BlockSpec(memory_space=pl.ANY),  # the pools: HBM, copied by hand
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM(window, dtype),
                pltpu.VMEM(window, dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
            interpret=interpret,
        )(order, count, block_tables, lengths, qp, pool_k, pool_v)
    return out[:, :H].reshape(S, 1, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# The latent layout: every query head over ONE shared row a token
# --------------------------------------------------------------------------
# Latent attention (MLA) caches, for a token and a layer, one row
# ``[c_kv | RoPE(k_r)]`` that all heads share.  In decode the up-projection
# of the keys is absorbed into the query, so a head's score is its absorbed
# query against the whole row and its value is the row's first ``value_width``
# entries: per-head K and V never exist.  The pool is
# ``[num_blocks, layers, block_size, W]`` (W the row, padded to the 128 lanes:
# a block's rows of one layer are one contiguous copy), and one kernel call
# serves one layer.  With H heads against one row the products are matrices
# ([H, W] x [W, tokens] and [H, tokens] x [tokens, value_width]), so this
# kernel feeds the MXU, as the kernel above does.


# Tokens in one VMEM window of the latent kernel (two are held).
_LATENT_WINDOW_TOKENS = 512


def latent_kv_write(pool, rows, layer, block_tables, lengths, active):
    """Write one new row per slot into ``pool`` [NB, L, bs, W] at ``layer``,
    in place under donation (or as a loop's carry).  rows: [S, W]."""
    bs = pool.shape[2]
    blk = jnp.take_along_axis(block_tables, (lengths // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(active, blk, 0)
    return pool.at[blk, layer, lengths % bs].set(rows.astype(pool.dtype))


def latent_gathered_attention(q, pool, layer, block_tables, lengths, *,
                              value_width, scale):
    """The mathematics of :func:`latent_paged_attention` over an XLA gather
    of the whole capacity: the kernel's oracle in the tests, never the chip's
    path.  Returns [S, H, value_width] float32."""
    S, nb = block_tables.shape
    ctx = pool[block_tables, layer].astype(jnp.float32)  # [S, nb, bs, W]
    ctx = ctx.reshape(S, nb * pool.shape[2], pool.shape[3])
    scores = jnp.einsum("shw,stw->sht", q.astype(jnp.float32), ctx,
                        precision=jax.lax.Precision.HIGHEST) * scale
    mask = jnp.arange(ctx.shape[1])[None, None, :] <= lengths[:, None, None]
    p_att = jax.nn.softmax(jnp.where(mask, scores, _NEG_INF), axis=-1)
    return jnp.einsum("sht,stv->shv", p_att, ctx[..., :value_width],
                      precision=jax.lax.Precision.HIGHEST)


def _latent_kernel(
    order_ref, count_ref, tables_ref, lengths_ref, layer_ref, q_ref, pool_ref,
    o_ref, buf, sem, *, pages, value_width, scale,
):
    """All slots of one layer.  SMEM: order [S] (active slots first), count
    [1], tables [S, MB], lengths [S], layer [1]; q [S, Hp, W] in VMEM; the
    pool [NB, L, bs, W] stays in HBM; buf [2, pages * bs, W] are the two
    windows of ``pages`` blocks."""
    MB = tables_ref.shape[1]
    Hp = q_ref.shape[1]
    bs = pool_ref.shape[2]
    layer = layer_ref[0]

    def live_blocks(s):
        return jnp.clip(lengths_ref[s] // bs + 1, 1, MB)

    def for_each_copy(s, w, b, do):
        def body(p, carry):
            blk = tables_ref[s, w * pages + p]
            do(pltpu.make_async_copy(
                pool_ref.at[blk, layer], buf.at[b, pl.ds(p * bs, bs)], sem.at[b]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(live_blocks(s) - w * pages, pages), body, 0)

    def start(s, w, b):
        for_each_copy(s, w, b, lambda copy: copy.start())

    def wait(s, w, b):
        for_each_copy(s, w, b, lambda copy: copy.wait())

    o_ref[...] = jnp.zeros_like(o_ref)
    count = count_ref[0]
    position = jax.lax.broadcasted_iota(jnp.int32, (pages * bs, 1), 0)
    column = jax.lax.broadcasted_iota(jnp.int32, (1, pages * bs), 1)

    @pl.when(count > 0)
    def _():
        start(order_ref[0], 0, 0)

    def slot_body(i, n_done):
        s = order_ref[i]
        length = lengths_ref[s]
        n_windows = pl.cdiv(live_blocks(s), pages)
        q = q_ref[s]  # [Hp, W]

        def window_body(w, carry):
            n_done, m, l, acc = carry
            b = n_done % 2
            last = w + 1 >= n_windows
            i_next = jnp.where(last, i + 1, i)

            @pl.when(i_next < count)
            def _():
                start(order_ref[i_next], jnp.where(last, 0, w + 1), 1 - b)

            wait(s, w, b)
            base = w * pages * bs
            # Rows past the length are a stale tail or a page that was not
            # copied (whatever VMEM held): zeroed, since 0 x NaN is NaN.
            k = jnp.where(base + position <= length, buf[b], jnp.zeros_like(buf[b]))
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Hp, P]
            sc = jnp.where(base + column <= length, sc, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p_att = jnp.exp(sc - m_new)
            l = l * corr + jnp.sum(p_att, axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p_att.astype(k.dtype), k[:, :value_width],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return n_done + 1, m_new, l, acc

        n_done, _m, l, acc = jax.lax.fori_loop(
            0, n_windows, window_body,
            (n_done, jnp.full((Hp, 1), _NEG_INF, jnp.float32),
             jnp.zeros((Hp, 1), jnp.float32),
             jnp.zeros((Hp, value_width), jnp.float32)))
        o_ref[s] = acc / l
        return n_done

    jax.lax.fori_loop(0, count, slot_body, 0)


@functools.partial(
    jax.jit, static_argnames=("value_width", "scale", "interpret"))
def latent_paged_attention(
    q, pool, layer, block_tables, lengths, active, *, value_width, scale,
    interpret=None,
):
    """Decode attention of every head against the shared latent rows of
    ``layer``, read from the pool in place.  q: [S, H, W] (absorbed queries,
    laid out as the rows are); pool: [num_blocks, layers, block_size, W] with
    W a multiple of 128; block_tables [S, MB], lengths [S] (slot s attends
    over positions ``<= lengths[s]``), active [S] bool (an inactive slot reads
    nothing and gives 0); ``layer`` a traced int32 scalar.  Returns
    [S, H, value_width] float32: the softmax-weighted sum of the rows' first
    ``value_width`` entries.  Products are in the pool's dtype on the MXU
    with float32 accumulation; masks and the softmax are float32.  Mosaic on
    ``tpu``, Pallas interpret mode on ``cpu``; a jit of its own so that the
    layers of a model share one lowering."""
    if interpret is None:
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise RuntimeError(
                f"latent_paged_attention has a Mosaic (tpu) lowering and a cpu "
                f"interpret mode for tests; platform {backend!r} has neither")
        interpret = backend == "cpu"
    S, H, W = q.shape
    NB, L, bs, Wp = pool.shape
    if W != Wp or (not interpret and (W % 128 or value_width % 128)):
        raise ValueError(
            f"latent_paged_attention: q {q.shape} against a pool {pool.shape}: "
            "the row must be the pool's, in whole 128-lane tiles")
    tile_rows = 32 // pool.dtype.itemsize
    Hp = -(-H // tile_rows) * tile_rows
    qp = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, Hp - H), (0, 0)))
    pages = max(1, min(block_tables.shape[1], _LATENT_WINDOW_TOKENS // bs))
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    count = jnp.sum(active, dtype=jnp.int32).reshape(1)
    with jax.named_scope("mla_decode_attention"):
        out = pl.pallas_call(
            functools.partial(_latent_kernel, pages=pages,
                              value_width=value_width, scale=scale),
            out_shape=jax.ShapeDtypeStruct((S, Hp, value_width), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 5 + [
                pl.BlockSpec(memory_space=pltpu.VMEM),  # q
                pl.BlockSpec(memory_space=pl.ANY),  # the pool: HBM, copied by hand
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
            name="mla_decode_attention",
        )(order, count, block_tables, lengths,
          jnp.asarray(layer, jnp.int32).reshape(1), qp, pool)
    return out[:, :H]

