"""Pallas TPU flash attention (single-chip blockwise attention).

The single-chip complement of ``moolib_tpu.parallel.ring_attention``: scores
never materialize in HBM — K/V stream through VMEM in blocks while a running
(max, sum, accumulator) triple folds the softmax (same math as the ring
kernel, here over the *local* sequence).  Written with ``pl.pallas_call``
grid (batch*heads, q-blocks, kv-blocks): the kv axis is innermost so the
output block revisits and the scratch accumulators carry across iterations
(standard TPU pallas accumulation pattern).

The reference framework has no attention at all (SURVEY.md §5.7) — this is
new TPU-idiomatic capability for the long-context side of the framework.

``window=W`` (a sliding window: a query sees itself and the W - 1 keys
before it) is a forward of its own, :func:`_flash_window_kernel`: its grid's
key axis is as long as the key blocks ONE query block's window can touch, and
the index map starts it at that block's first, so a key block outside every
window of the query block is never copied or multiplied.  It has no backward:
differentiation raises.  ``window=None`` traces the program it always did.

Operands [B, T, H, D] (:func:`flash_attention`; K and V may hold fewer heads,
each shared by a group of query heads) or one packed projection
[B, T, (H + 2 Hk) * D], columns ``[q heads | k heads | v heads]``
(:func:`flash_attention_packed`).  Where ``D % 128 == 0`` the three kernels
(forward, dq, dk/dv) index those arrays where they lie (``in_place``): a
head's [block, D] tile is a lane-aligned block of the [B, T, heads * D]
array, cut out by the BlockSpec's index map (batch row, block, column of the
head), and the result, dq, dk and dv are written the same way, so nothing is
transposed to a head-major copy and back, a grouped K/V head is read by its
group's query heads and not repeated, and the packed entry hands the kernels
the projection's own output three times over.  Any other head size takes the
``head_major`` form: [B * H, T, D] copies, the same kernels, other index maps
(one lane-aligned block cannot be cut out of ``H * D`` columns there), as the
windowed forward does at every head size.
``flash_attention_traces_total{path}`` counts the calls traced by form.  The
row logsumexp leaves the forward, and enters the backward with ``delta``, as
rows: [B * H, 1, T] float32.

``length=n`` (a traced int32 scalar: the first ``n`` of the ``T`` positions
are real, the rest a serving bucket's padding) is a forward of its own too,
:func:`_flash_length_kernel`: the scalar reaches it as data (scalar prefetch),
so ONE program serves every prompt of a bucket.  Blocks wholly at or past
``n`` are neither copied nor multiplied, the rows at or past it come out as
zeros, and ``T`` need not tile (the grid is ``cdiv``, the last block partial):
any ``T`` of 128 rows or more rides it (:func:`length_call_rides_kernel`).  No
backward either.

Without ``length``, shapes that don't tile (T without a 128-multiple divisor)
take the XLA dense path, counted in ``flash_dense_reroutes_total``; with it,
only ``T < 128`` does.  The kernels lower through Mosaic on the ``tpu``
platform and run in Pallas interpret mode on ``cpu`` (tests); any other
platform raises.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry, utils

_NEG_INF = -1e30

_M_DENSE_REROUTES = telemetry.get_registry().counter(
    "flash_dense_reroutes_total",
    "flash_attention calls traced onto the O(T^2) dense path because the "
    "sequence length has no 128-multiple block divisor (a call with length=: "
    "because it is under 128 rows)",
)
_M_TRACES = telemetry.get_registry().counter(
    "flash_attention_traces_total",
    "flash_attention calls traced onto the kernels, by how the head size lets "
    "them address their operands: in_place (a multiple of 128: blocks of the "
    "[B, T, heads * D] arrays as they lie), head_major ([B * H, T, D] copies: "
    "any other head size, and every windowed call)",
    labelnames=("path",),
)


def _largest_divisor(t: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``t`` and is <= ``cap`` (0 if none)."""
    for b in range(min(cap, t) // 128 * 128, 0, -128):
        if t % b == 0:
            return b
    return 0


def _out_struct(shape, dtype, *operands):
    """ShapeDtypeStruct for a pallas output, carrying the union of the
    operands' varying-mesh-axes (vma) so the kernel works inside shard_map
    (ring attention calls it per chunk) as well as at top level."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_k,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        # Matmuls take the operands at their native dtype (bf16 in → one MXU
        # pass with f32 accumulate); upcasting first would force the slow
        # multi-pass f32 path for bf16 inputs.
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] f32

        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_scr[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            # Rows whose every key is masked: keep them at zero weight.
            p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Skip kv blocks that lie entirely above the diagonal — the causal
        # mask would zero every row, so neither matmul needs to run.
        pl.when((qi + 1) * block_q > ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)
        # Row logsumexp for the backward pass.  The scratch holds it down the
        # sublanes, replicated over the 128 lanes; the backward reads rows:
        # one transpose in VMEM a query block, and block_q floats leave.
        lse = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))
        lse_ref[0] = lse.T[:1]

def _window_blocks(qi, window, block_q, block_k, maximum=max):
    """(first, last) of the key blocks that query block ``qi``'s windows
    touch; ``maximum=jnp.maximum`` where ``qi`` is traced."""
    first = maximum(qi * block_q - (window - 1), 0) // block_k
    return first, ((qi + 1) * block_q - 1) // block_k


def _flash_window_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, window, block_q, block_k,
):
    """Causal attention under a sliding window: grid (batch*heads, q blocks,
    the key blocks a query block's windows can touch).  Step ``j`` of the key
    axis holds key block ``first + j`` of this query block (the index map put
    it there); past the diagonal's block the map repeats that block, nothing
    is copied and nothing runs."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    first, last = _window_blocks(qi, window, block_q, block_k, jnp.maximum)
    ki = first + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki <= last)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] f32
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((q_pos >= k_pos) & (q_pos - k_pos < window), s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # A row with no key in this block keeps zero weight.
        p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def _flash_length_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, block_q, block_k,
):
    """Causal attention over the first ``len_ref[0]`` of ``T`` positions: the
    causal kernel's grid (``cdiv``: the last blocks may be partial) and sweep,
    the length as data.  A row below the length gets what the causal kernel
    gives it (it never saw a key past itself); a row at or past it is written
    as zeros, whatever q, k or v hold there or past ``T`` (NaN too); a block
    wholly at or past the length runs nothing, and the index maps
    (:func:`_flash_length_forward`) copied nothing for it."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    length = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Key blocks up to the diagonal's and up to the last real key's, of a
    # query block that holds a real row.
    @pl.when((qi * block_q < length)
             & (ki * block_k < jnp.minimum((qi + 1) * block_q, length)))
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] f32
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # One compare, as the causal kernel's: the keys of a row are those up
        # to itself and below the length.
        s = jnp.where(k_pos <= jnp.minimum(q_pos, length - 1), s, _NEG_INF)
        # A probability of 0 times a V row of padding (or past T) is NaN where
        # the row holds NaN: the row is taken as zero.
        v_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        v = jnp.where(v_pos < length, v_ref[0], jnp.zeros_like(v_ref[0]))
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        # A block that ran nothing divides its zeros; a padding row inside a
        # live block attended over the real keys (or holds NaN): zeros too.
        row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        out = acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = jnp.where(row < length, out, 0.0).astype(o_ref.dtype)


def _blockwise_attention(q, k, v, causal, block_q, block_k, return_lse=False,
                         window=None):
    """Pure-jax chunked streaming-softmax attention — the differentiable
    reference the backward pass uses (same math as the kernel; O(block)
    score memory thanks to the scan + checkpointed inner step).  ``window``: a
    query sees itself and the ``window - 1`` keys before it (with ``causal``)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = D**-0.5
    nq, nk = Tq // block_q, Tk // block_k
    qb = jnp.moveaxis(
        q.astype(jnp.float32).reshape(B, nq, block_q, H, D), 1, 0
    )  # [nq, B, bq, H, D]
    kb = jnp.moveaxis(k.astype(jnp.float32).reshape(B, nk, block_k, H, D), 1, 0)
    vb = jnp.moveaxis(v.astype(jnp.float32).reshape(B, nk, block_k, H, D), 1, 0)

    def per_q(args):
        qi, q_blk = args  # q_blk [B, bq, H, D]

        def kv_step(carry, inp):
            ki, k_blk, v_blk = inp

            def active(carry):
                from ..parallel.ring_attention import online_softmax_update

                acc, l, m = carry
                s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk) * scale
                if causal:
                    q_pos = qi * block_q + jnp.arange(block_q)
                    k_pos = ki * block_k + jnp.arange(block_k)
                    mask = q_pos[:, None] >= k_pos[None, :]
                    if window is not None:
                        mask &= q_pos[:, None] - k_pos[None, :] < window
                    s = jnp.where(mask[None, None], s, _NEG_INF)
                return online_softmax_update(
                    s, v_blk, acc, l, m, zero_masked_rows=causal
                )

            if causal:
                # Mirror the kernel's pl.when: kv blocks entirely above the
                # diagonal contribute nothing — skip their matmuls.
                carry = jax.lax.cond(
                    (qi + 1) * block_q > ki * block_k, active, lambda c: c, carry
                )
            else:
                carry = active(carry)
            return carry, None

        init = (
            jnp.zeros((B, H, block_q, D), jnp.float32),
            jnp.zeros((B, H, block_q), jnp.float32),
            jnp.full((B, H, block_q), _NEG_INF, jnp.float32),
        )
        (acc, l, m), _ = jax.lax.scan(
            jax.checkpoint(kv_step), init, (jnp.arange(nk), kb, vb)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)  # [B, H, bq, D]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [B, H, bq]
        return jnp.moveaxis(out, 1, 2), jnp.moveaxis(lse, 1, 2)

    outs, lses = jax.lax.map(per_q, (jnp.arange(nq), qb))  # [nq, B, bq, ...]
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Tq, H, D).astype(q.dtype)
    if return_lse:
        return out, jnp.moveaxis(lses, 0, 1).reshape(B, Tq, H)
    return out


def _use_oracle_bwd() -> bool:
    return os.environ.get("MOOLIB_TPU_FLASH_BWD", "pallas") == "jax"


class _Call(NamedTuple):
    """What is static of one call of the causal / full kernels: it selects
    their grids, blocks and index maps.  ``packed``: the operands are one
    projection [B, Tq, (H + 2 Hk) * D], not (q, k, v) [B, T, heads, D], and
    the result is [B, Tq, H * D], not [B, Tq, H, D]."""

    B: int
    Tq: int
    Tk: int
    H: int
    Hk: int
    D: int
    packed: bool
    causal: bool
    block_q: int
    block_k: int
    interpret: bool
    return_lse: bool

    @property
    def in_place(self) -> bool:
        """The kernels index the operands as they lie; else head-major copies."""
        return self.D % 128 == 0


def _to_bh(x):
    """[B, T, heads, D] -> [B * heads, T, D]"""
    B, T, heads, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * heads, T, D)


def _repeat_kv(k, v, group):
    """K and V with every head repeated for its ``group`` query heads."""
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def _unpack(qkv, H, Hk):
    """(q, k, v) [B, T, heads, D] out of the packed [B, T, (H + 2 Hk) * D]."""
    B, T, columns = qkv.shape
    x = qkv.reshape(B, T, H + 2 * Hk, columns // (H + 2 * Hk))
    return x[:, :, :H], x[:, :, H:H + Hk], x[:, :, H + Hk:]


def _kernel_arrays(operands, c):
    """The arrays the kernels index as q, k and v, and the column, in heads,
    at which each one's heads start: the operands as they lie ([B, T, heads *
    D]; the packed projection three times), or head-major copies."""
    if c.packed:
        return operands * 3, (0, c.H, c.H + c.Hk)
    if c.in_place:
        return tuple(x.reshape(*x.shape[:2], -1) for x in operands), (0, 0, 0)
    return tuple(_to_bh(x) for x in operands), (0, 0, 0)


def _of_result(x, c):
    """An array of the result's form (the result, its cotangent) as the
    kernels index it."""
    return x.reshape(c.B, c.Tq, c.H * c.D) if c.in_place else _to_bh(x)


def _of_kernel(x, heads, c):
    """What a kernel wrote for ``heads`` heads, in the operands' form."""
    if c.packed:
        return x
    if c.in_place:
        return x.reshape(c.B, -1, heads, c.D)
    return x.reshape(c.B, heads, -1, c.D).transpose(0, 2, 1, 3)


def _heads_shape(c, heads, T):
    return (c.B, T, heads * c.D) if c.in_place else (c.B * heads, T, c.D)


def _tile(c, heads, first, block, where):
    """BlockSpec of one head's [block, D] tile.  ``where(*grid indices)``
    names it: (batch row, head, block along T).  In place that is block
    ``(row, block, first + head)`` of a [B, T, columns] array, ``first`` the
    column, in heads, of the operand's head 0; head-major, block ``(row *
    heads + head, block, 0)`` of [B * heads, T, D]."""

    def index(*ids):
        b, h, i = where(*ids)
        return (b, i, first + h) if c.in_place else (b * heads + h, i, 0)

    return pl.BlockSpec((1, block, c.D), index)


def _last_causal_block(causal, qi, ki, block_q, block_k):
    """The key block that step ``ki`` of query block ``qi``'s sweep copies.
    Causal: a key block wholly above the diagonal is not computed (the
    kernels' ``pl.when``); the index map points at the last one that is, a
    block already in VMEM, so nothing is copied for it either."""
    if not causal:
        return ki
    return jnp.minimum(ki, jax.lax.div((qi + 1) * block_q - 1, block_k))


def _query_sweep(c, block_q, block_k):
    """Index maps of a grid (batch row x query head, q block, k block), the
    forward's and the dq pass's: where a step's q tile and K/V tile are (for
    :func:`_tile`), and the BlockSpec of the q block's row table."""
    H, group = c.H, c.H // c.Hk

    def q_at(n, i, j):
        return jax.lax.div(n, H), jax.lax.rem(n, H), i

    def kv_at(n, i, j):
        return (jax.lax.div(n, H), jax.lax.div(jax.lax.rem(n, H), group),
                _last_causal_block(c.causal, i, j, block_q, block_k))

    return q_at, kv_at, pl.BlockSpec((1, 1, block_q), lambda n, i, j: (n, 0, i))


def _rows(x, c):
    """A per-row table [B, Tq, H] as the kernels read it: [B * H, 1, Tq] f32.
    The row tables ride with a unit dimension: TPU lowering constrains the
    last two block dims (divisible by (8, 128) or equal to the array dims), so
    a 2-D (1, bq) block over [B * H, Tq] is illegal when B * H > 1 — the unit
    dim must sit in the constrained sublane slot, where 1 == 1 passes."""
    return x.astype(jnp.float32).transpose(0, 2, 1).reshape(c.B * c.H, 1, c.Tq)


def _lse_by_position(lse, c):
    """The kernels' row logsumexp [B * H, 1, Tq] as the public [B, Tq, H]."""
    return lse.reshape(c.B, c.H, c.Tq).transpose(0, 2, 1)


def _oracle(operands, c):
    """The call in pure jax (the blockwise oracle), results in ``_flash``'s form."""
    q, k, v = _unpack(*operands, c.H, c.Hk) if c.packed else operands
    k, v = _repeat_kv(k, v, c.H // c.Hk)
    res = _blockwise_attention(
        q, k, v, c.causal, c.block_q, c.block_k, return_lse=c.return_lse)
    out, lse = res if c.return_lse else (res, None)
    if c.packed:
        out = out.reshape(c.B, c.Tq, c.H * c.D)
    return (out, lse) if c.return_lse else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _flash(operands, c):
    """``operands``: (q, k, v), or (qkv,) where ``c.packed``.  The result in
    their form and, with ``c.return_lse``, the row logsumexp [B, Tq, H] as a
    differentiable second output: ring attention combines per-chunk results
    by logsumexp weights, so gradients flow through it (the lse cotangent
    folds into the backward kernels' delta term — no extra kernel).  Without
    it the training hot path never materializes a zero lse cotangent."""
    return _flash_vjp_fwd(operands, c)[0]


def _flash_vjp_fwd(operands, c):
    out, lse = _flash_forward(operands, c)
    res = (operands, out, lse)
    return ((out, _lse_by_position(lse, c)) if c.return_lse else out), res


def _flash_vjp_bwd(c, res, g):
    operands, out, lse = res
    if _use_oracle_bwd():
        # Oracle path: VJP of the blockwise-jax formulation (recomputes the
        # streaming softmax in pure XLA; same FLOPs class, O(block) score
        # memory).  Kept for parity testing against the pallas kernels.
        _, vjp = jax.vjp(lambda ops: _oracle(ops, c), operands)
        return vjp(g)
    g_out, g_lse = g if c.return_lse else (g, None)
    return (_flash_backward(operands, out, lse, g_out, g_lse, c),)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_window(q, k, v, window, block_q, block_k, interpret):
    return _flash_window_forward(q, k, v, window, block_q, block_k, interpret)


def _flash_window_no_vjp(*_):
    raise NotImplementedError(
        "flash_attention(window=...) has no backward: the windowed kernel is a "
        "forward for serving prefill; a gradient would need windowed dq and "
        "dk/dv passes (differentiate window=None)")


_flash_window.defvjp(_flash_window_no_vjp, _flash_window_no_vjp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _flash_length(operands, length, c):
    """``_flash`` of the first ``length`` ([1] int32, data) of ``c.Tq``
    positions; the result in the operands' form, no logsumexp."""
    return _flash_length_forward(operands, length, c)


def _flash_length_no_vjp(*_):
    raise NotImplementedError(
        "flash_attention(length=...) has no backward: the kernel that takes a "
        "bucket's true length is a forward for serving prefill (differentiate "
        "length=None)")


_flash_length.defvjp(_flash_length_no_vjp, _flash_length_no_vjp)


def _flash_bwd_dq_kernel(
    k_ref, q_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, causal, block_q, block_k,
):
    """dq pass: one q block per (batch*head, qi), kv blocks stream innermost.

    Works in scores-transposed layout — st = k @ qᵀ is [block_k, block_q] —
    so the per-row lse/delta tables enter as natural (1, 1, block_q) row
    vectors (no sublane→lane transpose anywhere on the TPU).
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        st = jax.lax.dot_general(
            k_ref[0], q_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bk, bq] f32
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])  # masked entries underflow to 0
        dpt = jax.lax.dot_general(
            v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, bq]
        dst = pt * (dpt - delta_ref[0]) * scale
        dq_scr[:] += jax.lax.dot_general(
            dst.astype(k_ref.dtype), k_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, D]

    if causal:
        pl.when((qi + 1) * block_q > ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    k_ref, q_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, block_q, block_k, q_blocks,
):
    """dk/dv pass: one kv block per (batch*kv head, ki); the q blocks of the
    head's group of query heads stream innermost, one head's ``q_blocks``
    after the other's.

    Same transposed-scores layout as the dq pass; dk and dv accumulate in
    f32 scratch across the sweep.
    """
    ki = pl.program_id(1)
    step = pl.program_id(2)
    qi = jax.lax.rem(step, q_blocks)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        st = jax.lax.dot_general(
            k_ref[0], q_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bk, bq]
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do_ref.dtype), do_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]
        dpt = jax.lax.dot_general(
            v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dst = pt * (dpt - delta_ref[0]) * scale
        dk_scr[:] += jax.lax.dot_general(
            dst.astype(q_ref.dtype), q_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]

    if causal:
        pl.when((qi + 1) * block_q > ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@jax.named_scope("flash_attention")
def _flash_backward(operands, out, lse, g, g_lse, c):
    """Pallas flash backward: dq pass + dk/dv pass (FlashAttention-2 style).
    The cotangents in the operands' form: (dq, dk, dv), packed (dqkv,).

    ``g_lse`` is the cotangent of the lse output ([B,Tq,H] or None): since
    dL/ds_j = p_j((g·v_j) - (g·out) + g_lse), it folds into the delta row
    table as ``delta - g_lse`` — the kernels are unchanged.
    """
    B, Tq, Tk, H, Hk, D = c.B, c.Tq, c.Tk, c.H, c.Hk, c.D
    group = H // Hk
    # Backward blocks capped at 512x512 (env-tunable for on-chip sweeps;
    # read at TRACE time — the jit cache does not key on env vars, so a
    # sweep must re-trace per value: a fresh process each, as
    # benchmarks/flash_bwd_tune.py does.  Values clamp up to the 128 tile
    # minimum.):
    # the transposed-score intermediates (st, pt, dpt — all [bk, bq] f32)
    # plus two f32 output scratches are live at once, so the forward's
    # 512x1024 tiles would crowd VMEM.  The cap must preserve divisibility
    # (e.g. Tk=1280 forwards with block_k=640; a blind min() would drop the
    # tail kv block) — re-derive the largest dividing block under the cap.
    # Always succeeds: any valid forward block is a multiple of 128
    # dividing T, so 128 divides T.
    cap_q = max(128, int(os.environ.get("MOOLIB_TPU_FLASH_BWD_BLOCK_Q", 512)))
    cap_k = max(128, int(os.environ.get("MOOLIB_TPU_FLASH_BWD_BLOCK_K", 512)))
    bq = _largest_divisor(Tq, min(c.block_q, cap_q))
    bk = _largest_divisor(Tk, min(c.block_k, cap_k))
    nq = Tq // bq

    (qa, ka, va), (q0, k0, v0) = _kernel_arrays(operands, c)
    do = _of_result(g, c)
    # delta_i = Σ_d dO_i · O_i — row table, like lse.  Summed as [.., H, 8, D]:
    # eight rows of T beside D are what a float32 tile of [B, T, H * D] holds,
    # so the products are reduced where they lie; summed as [B, T, H, D] XLA
    # first re-tiles them all (a 67-MB copy a block at B=4, T=2048, H=16).
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
        B, Tq // 8, 8, H, D).transpose(0, 1, 3, 2, 4).sum(axis=-1)
    delta = delta.transpose(0, 2, 1, 3).reshape(B * H, 1, Tq)
    if g_lse is not None:
        delta = delta - _rows(g_lse, c)
    kwargs = dict(scale=D**-0.5, causal=c.causal, block_q=bq, block_k=bk)
    every = (ka, qa, va, do, delta)

    # dq: a program a (batch row x query head, q block), the head's K/V
    # head's blocks innermost.
    q_at, kv_at, row_spec = _query_sweep(c, bq, bk)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kwargs),
        grid=(B * H, nq, Tk // bk),
        in_specs=[
            _tile(c, Hk, k0, bk, kv_at),  # k
            _tile(c, H, q0, bq, q_at),  # q
            _tile(c, Hk, v0, bk, kv_at),  # v
            _tile(c, H, 0, bq, q_at),  # do
            row_spec,  # lse
            row_spec,  # delta
        ],
        out_specs=_tile(c, H, 0, bq, q_at),
        out_shape=_out_struct(_heads_shape(c, H, Tq), qa.dtype, *every),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=c.interpret,
    )(ka, qa, va, do, lse, delta)

    # dk, dv: a program a (batch row x K/V head, k block); innermost, the q
    # blocks of every query head of its group, so a shared head's gradient
    # is summed in the float32 scratch.
    def k_at(n, i, j):
        return jax.lax.div(n, Hk), jax.lax.rem(n, Hk), i

    def q_block(i, j):
        # Causal: a q block wholly before k block i is not computed; the map
        # points at the first one that is, so nothing is copied for it.
        qi = jax.lax.rem(j, nq)
        if not c.causal:
            return qi
        return jnp.maximum(qi, jnp.minimum(jax.lax.div(i * bk, bq), nq - 1))

    def q_of_k_at(n, i, j):
        head = jax.lax.rem(n, Hk) * group + jax.lax.div(j, nq)
        return jax.lax.div(n, Hk), head, q_block(i, j)

    def qrow_at(n, i, j):
        b, head, qi = q_of_k_at(n, i, j)
        return b * H + head, 0, qi

    qrow_spec = pl.BlockSpec((1, 1, bq), qrow_at)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, q_blocks=nq, **kwargs),
        grid=(B * Hk, Tk // bk, group * nq),
        in_specs=[
            _tile(c, Hk, k0, bk, k_at),  # k
            _tile(c, H, q0, bq, q_of_k_at),  # q
            _tile(c, Hk, v0, bk, k_at),  # v
            _tile(c, H, 0, bq, q_of_k_at),  # do
            qrow_spec,  # lse
            qrow_spec,  # delta
        ],
        out_specs=[_tile(c, Hk, 0, bk, k_at), _tile(c, Hk, 0, bk, k_at)],
        out_shape=[
            _out_struct(_heads_shape(c, Hk, Tk), ka.dtype, *every),
            _out_struct(_heads_shape(c, Hk, Tk), va.dtype, *every),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=c.interpret,
    )(ka, qa, va, do, lse, delta)

    if c.packed:
        # The projection's cotangent: the one copy the backward makes.
        return (jnp.concatenate([dq, dk, dv], axis=-1),)
    return _of_kernel(dq, H, c), _of_kernel(dk, Hk, c), _of_kernel(dv, Hk, c)


def _auto_blocks(Tq, Tk, window=None):
    """Defaults from a block sweep on TPU v5e (T=4096, causal): 128x128 blocks
    leave grid overhead dominant (32k tiny steps, 7.7 ms); 512x1024 runs the
    same shape in 1.8 ms while q+k+v+s blocks stay well under VMEM.  The
    largest 128-multiple divisor of T up to the tuned size, so lengths like
    1536 or 2560 still ride the kernel; 0 for a T without one (e.g. 250, or
    160 < 2*128)."""
    # Under a window a key block wider than the window is mostly masked.
    cap_k = 1024 if window is None else min(1024, max(128, window // 128 * 128))
    return _largest_divisor(Tq, 512), _largest_divisor(Tk, cap_k)


def window_key_blocks(T: int, window: int):
    """(visited, causal): the (query block, key block) pairs that a windowed
    causal call over ``T`` positions copies and multiplies a head at its own
    blocks (:func:`_auto_blocks`), and the pairs a causal forward at those
    blocks would.  A window no shorter than ``T`` is no window (every span
    starts at block 0: both counts are the causal one); (0, 0) where ``T``
    takes no kernel.  Plain integers:
    for a host that counts what a prefill's kernel skipped."""
    block_q, block_k = _auto_blocks(T, T, window if window < T else None)
    if block_q < 128 or block_k < 128:
        return 0, 0
    spans = [_window_blocks(i, window, block_q, block_k) for i in range(T // block_q)]
    return (sum(last - first + 1 for first, last in spans),
            sum(last + 1 for _first, last in spans))


def length_call_rides_kernel(T: int) -> bool:
    """Whether a call with ``length`` over ``T`` positions rides the kernel
    (any ``T`` of a block's 128 rows or more: it need not tile) or takes the
    dense path.  A plain integer's question: :func:`flash_attention` chooses
    its path by it, and so does a host that counts which of the two each of
    its prompts' buckets took."""
    return T >= 128


def _interpret(interpret):
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"flash_attention has a Mosaic (tpu) lowering and a cpu "
            f"interpret mode for tests; platform {backend!r} has neither"
        )
    return backend == "cpu"


def _mesh_axis(mesh, name, dim):
    """``name`` where the mesh has that axis and it divides ``dim``."""
    return name if name in mesh.axis_names and dim % mesh.shape[name] == 0 else None


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    return_lse: bool = False,
    mesh=None,
    window: int | None = None,
    length=None,
):
    """Blockwise attention; q: [B, T, H, D], k/v: [B, T, Hk, D] → [B, T, H, D].

    ``Hk`` divides ``H``: K/V head ``j`` serves query heads ``j * H / Hk`` up
    to the next one's (grouped-query attention; ``Hk == H`` is multi-head).
    The kernels read a shared head where it lies, once a query head, and sum
    its gradient over its group: nothing is repeated.

    A head size that is a multiple of 128 is indexed in place: the kernels'
    blocks are cut out of q, k, v as [B, T, heads * D] (a free reshape) and
    the result and the gradients are written in that form.  Any other head
    size goes through head-major [B * H, T, D] copies (module docstring;
    ``flash_attention_traces_total{path}``).

    ``window``: the keys a query sees, itself included (a sliding window of
    ``window`` positions; needs ``causal`` and Tq == Tk).  Key blocks outside
    every window of a query block are skipped, not masked.  Forward only:
    differentiating a windowed call raises, and ``return_lse`` and ``mesh``
    are not offered with it.

    ``length``: a traced int32 scalar, ``0 < length <= T``: the first
    ``length`` positions are real and the rest a bucket's padding (needs
    ``causal`` and Tq == Tk; every batch row has the one length).  A real row
    gets what it gets without it (it never saw a key past itself); a row at or
    past it comes out as ZEROS, whatever the operands hold there (NaN too);
    blocks wholly past it are neither copied nor multiplied.  The scalar is
    data, so one program serves every length, and ``T`` need not tile: any
    ``T`` of 128 or more rides the kernel (:func:`length_call_rides_kernel`),
    in the blocks of ``T`` rounded up to 128, the last block partial.  Forward
    only, as a window is, and without ``return_lse``, ``mesh`` or ``window``.

    ``mesh``: pass the mesh when calling from a program XLA partitions over
    one (a jitted step with sharded inputs).  XLA cannot partition a Mosaic
    kernel, so the call is wrapped in ``shard_map``: the batch splits over
    the mesh's ``dp`` axis and the heads over ``tp`` where those axes exist
    and divide, every other axis computes replicated.  Inside a ``shard_map``
    of your own (ring attention, pipeline stages) leave it None.

    Differentiable: the forward runs the pallas kernel (also emitting the
    row logsumexp); the backward runs two pallas kernels — a dq pass and a
    dk/dv pass (FlashAttention-2 style) — so the TransformerLM trains
    through on-chip kernels at long T.  ``MOOLIB_TPU_FLASH_BWD=jax``
    selects the blockwise-jax VJP oracle instead (parity testing).

    ``return_lse=True`` additionally returns the per-row logsumexp
    ([B, T, H], f32, differentiable) — the combinable form ring attention
    uses to merge chunk results across ICI hops.
    """
    B, Tq, H, D = q.shape
    Tk, Hk = k.shape[1:3]
    if H % Hk or v.shape[2] != Hk:
        raise ValueError(
            f"flash_attention: {H} query heads over K/V heads {Hk}, {v.shape[2]}")
    if window is not None:
        if not causal or Tq != Tk or return_lse or mesh is not None or window < 1:
            raise ValueError(
                "flash_attention(window=...) is causal self-attention over one "
                "sequence (Tq == Tk, window >= 1), without return_lse or mesh")
        if window >= Tq:
            window = None  # every key a query may see is inside its window
    if length is not None:
        if (not causal or Tq != Tk or return_lse or mesh is not None
                or window is not None):
            raise ValueError(
                "flash_attention(length=...) is causal self-attention over one "
                "sequence (Tq == Tk), without return_lse, mesh or window")
        if length_call_rides_kernel(Tq):
            return _length_call((q, k, v), length, H, Hk, D, block_q, block_k, interpret)
        from ..parallel.ring_attention import full_attention

        _M_DENSE_REROUTES.inc()
        real = (jnp.arange(Tq) < length)[None, :, None, None]
        # 0 x NaN is NaN: the padding's K and V rows are taken as zeros, as
        # the kernel takes them.
        k, v = (jnp.where(real, x, jnp.zeros_like(x)) for x in _repeat_kv(k, v, H // Hk))
        out = full_attention(q, k, v, causal=True)
        return jnp.where(real, out, jnp.zeros_like(out))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        # tp cuts the K/V heads, so a chip holds whole groups.
        spec = P(_mesh_axis(mesh, "dp", B), None, _mesh_axis(mesh, "tp", Hk), None)
        lse_spec = P(spec[0], None, spec[2])
        return jax.shard_map(
            functools.partial(
                flash_attention, causal=causal, block_q=block_q, block_k=block_k,
                interpret=interpret, return_lse=return_lse,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, lse_spec) if return_lse else spec,
        )(q, k, v)
    explicit_q = block_q is not None
    explicit_k = block_k is not None
    auto_q, auto_k = _auto_blocks(Tq, Tk, window)
    block_q = block_q if explicit_q else auto_q
    block_k = block_k if explicit_k else auto_k
    # Blocks below the 128-lane tile (T with a large odd factor) aren't worth
    # a pallas launch — use the dense path.  An unusable *caller-supplied*
    # block raises instead (the caller tuning blocks gets a signal, not an
    # O(T²) reroute); an unusable auto-selected one takes the dense path,
    # counted and logged so a run can assert it did not happen.
    bad_q = block_q < 128 or block_q % 128 or Tq % block_q
    bad_k = block_k < 128 or block_k % 128 or Tk % block_k
    if (bad_q and explicit_q) or (bad_k and explicit_k):
        raise ValueError(
            f"flash_attention block_q={block_q}, block_k={block_k} unusable for "
            f"Tq={Tq}, Tk={Tk}: blocks must be multiples of 128 that divide the "
            "sequence length. Omit them to auto-select (or fall back to dense)."
        )
    if bad_q or bad_k:
        from ..parallel.ring_attention import dense_attention_lse, full_attention

        _M_DENSE_REROUTES.inc()
        utils.log_info(
            "flash_attention: Tq=%d Tk=%d does not tile into 128-multiple "
            "blocks; using dense attention", Tq, Tk,
        )
        k, v = _repeat_kv(k, v, H // Hk)
        if return_lse:
            return dense_attention_lse(q, k, v, causal=causal)
        if window is not None:  # the oracle, one block the whole length
            return _blockwise_attention(q, k, v, True, Tq, Tk, window=window)
        return full_attention(q, k, v, causal=causal)
    interpret = _interpret(interpret)
    if window is not None:
        _M_TRACES.inc(path="head_major")
        k, v = _repeat_kv(k, v, H // Hk)
        return _flash_window(q, k, v, window, block_q, block_k, interpret)
    c = _Call(B, Tq, Tk, H, Hk, D, False, causal, block_q, block_k, interpret,
              return_lse)
    _M_TRACES.inc(path="in_place" if c.in_place else "head_major")
    return _flash((q, k, v), c)


def flash_attention_packed(
    qkv: jax.Array,
    num_heads: int,
    num_kv_heads: int | None = None,
    causal: bool = True,
    interpret: bool | None = None,
    mesh=None,
    length=None,
):
    """Self-attention over a packed projection: qkv [B, T, (H + 2 Hk) * D],
    columns ``[q heads | k heads | v heads]`` (``Block``'s ``qkv`` Dense as
    it leaves the matmul) → [B, T, H * D], as the output projection takes it.

    Where the kernels index in place (``D % 128 == 0``, T tiles) they take the
    one array three times, each under its own column map, and the backward
    writes dq, dk and dv in those columns' form and concatenates them: no
    slice, transpose or repeat of the projection is made.  Anything else — a
    head size off the lanes, a T that takes the dense path, a mesh whose
    ``tp`` cuts the heads (the packed columns are not one head axis) — is
    :func:`flash_attention` of the three slices.  ``mesh`` and ``length`` as
    there: with ``length`` any ``T`` of 128 or more is indexed in place.
    """
    B, T, C = qkv.shape
    H, Hk = num_heads, num_kv_heads or num_heads
    D = C // (H + 2 * Hk)
    if H % Hk or D * (H + 2 * Hk) != C:
        raise ValueError(
            f"flash_attention_packed: {C} columns are not {H} + 2 x {Hk} heads")
    if (length is not None and causal and mesh is None and D % 128 == 0
            and length_call_rides_kernel(T)):
        return _length_call((qkv,), length, H, Hk, D, None, None, interpret)
    block_q, block_k = _auto_blocks(T, T)
    cut_heads = mesh is not None and _mesh_axis(mesh, "tp", Hk) and mesh.shape["tp"] > 1
    if D % 128 or not (block_q and block_k) or cut_heads:
        return flash_attention(
            *_unpack(qkv, H, Hk), causal=causal, interpret=interpret, mesh=mesh,
            length=length,
        ).reshape(B, T, H * D)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        spec = P(_mesh_axis(mesh, "dp", B), None, None)
        return jax.shard_map(
            functools.partial(
                flash_attention_packed, num_heads=H, num_kv_heads=Hk,
                causal=causal, interpret=interpret),
            mesh=mesh, in_specs=(spec,), out_specs=spec,
        )(qkv)
    _M_TRACES.inc(path="in_place")
    return _flash((qkv,), _Call(B, T, T, H, Hk, D, True, causal, block_q, block_k,
                                _interpret(interpret), False))


def _length_call(operands, length, H, Hk, D, block_q, block_k, interpret):
    """The call with ``length`` onto its kernel (``operands``: (q, k, v), or
    the packed (qkv,)): the blocks of ``T`` rounded up to 128 (1,984 rows take
    2,048's), or the caller's, multiples of 128."""
    B, T = operands[0].shape[:2]
    tiled = -(-T // 128) * 128
    auto_q, auto_k = _auto_blocks(tiled, tiled)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    if block_q % 128 or block_k % 128:
        raise ValueError(
            f"flash_attention(length=...) block_q={block_q}, block_k={block_k}: "
            "blocks are multiples of 128 (they need not divide T)")
    c = _Call(B, T, T, H, Hk, D, len(operands) == 1, True, block_q, block_k,
              _interpret(interpret), False)
    _M_TRACES.inc(path="in_place" if c.in_place else "head_major")
    return _flash_length(
        operands, jnp.clip(jnp.asarray(length, jnp.int32), 1, T).reshape(1), c)


@functools.partial(jax.jit, static_argnums=(2,))
@jax.named_scope("flash_attention")
def _flash_length_forward(operands, length, c):
    """The causal forward's grid over ``cdiv`` blocks, ``length`` ([1] int32)
    prefetched: the index maps read it, so a query block past the last real
    row and a key block past the last real key (or above the diagonal) name a
    block already in VMEM and nothing is copied for them.  Jitted, so that the
    24 layers of a serving program trace and lower ONE kernel between them
    (40 ms a layer otherwise: a second a program, five at a warm-up)."""
    arrays, (q0, k0, v0) = _kernel_arrays(operands, c)
    H, group, bq, bk = c.H, c.H // c.Hk, c.block_q, c.block_k

    def q_at(n, i, j, len_ref):
        return (jax.lax.div(n, H), jax.lax.rem(n, H),
                jnp.minimum(i, jax.lax.div(len_ref[0] - 1, bq)))

    def kv_at(n, i, j, len_ref):
        last = jnp.minimum((i + 1) * bq, len_ref[0]) - 1  # the last key block i reads
        return (jax.lax.div(n, H), jax.lax.div(jax.lax.rem(n, H), group),
                jnp.minimum(j, jax.lax.div(last, bk)))

    def out_at(n, i, j, len_ref):  # every block is written, a dead one as zeros
        return jax.lax.div(n, H), jax.lax.rem(n, H), i

    out = pl.pallas_call(
        functools.partial(_flash_length_kernel, scale=c.D**-0.5, block_q=bq, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(c.B * H, pl.cdiv(c.Tq, bq), pl.cdiv(c.Tk, bk)),
            in_specs=[
                _tile(c, H, q0, bq, q_at),
                _tile(c, c.Hk, k0, bk, kv_at),
                _tile(c, c.Hk, v0, bk, kv_at),
            ],
            out_specs=_tile(c, H, 0, bq, out_at),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, c.D), jnp.float32),
            ],
        ),
        out_shape=_out_struct(_heads_shape(c, H, c.Tq), arrays[0].dtype, *arrays),
        interpret=c.interpret,
    )(length, *arrays)
    return _of_kernel(out, H, c)


@jax.named_scope("flash_attention")
def _flash_window_forward(q, k, v, window, block_q, block_k, interpret):
    B, T, H, D = q.shape
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    spans = [_window_blocks(i, window, block_q, block_k) for i in range(T // block_q)]

    def key_block(b, i, j):
        first, last = _window_blocks(i, window, block_q, block_k, jnp.maximum)
        return b, jnp.minimum(first + j, last), 0

    out = pl.pallas_call(
        functools.partial(_flash_window_kernel, scale=D**-0.5, window=window,
                          block_q=block_q, block_k=block_k),
        # the key axis: as many blocks as the widest query block's windows touch
        grid=(B * H, T // block_q, max(last - first + 1 for first, last in spans)),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), key_block),
            pl.BlockSpec((1, block_k, D), key_block),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((B * H, T, D), q.dtype, qb, kb, vb),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb)
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


@jax.named_scope("flash_attention")
def _flash_forward(operands, c):
    """(the result in the operands' form: [B, Tq, H, D], packed [B, Tq, H *
    D]; the row logsumexp as the backward reads it, [B * H, 1, Tq] f32)"""
    arrays, (q0, k0, v0) = _kernel_arrays(operands, c)
    H = c.H
    # A program a (batch row x query head, q block), the blocks of the head's
    # K/V head innermost.
    q_at, kv_at, row_spec = _query_sweep(c, c.block_q, c.block_k)
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=c.D**-0.5, causal=c.causal,
            block_q=c.block_q, block_k=c.block_k,
        ),
        grid=(c.B * H, c.Tq // c.block_q, c.Tk // c.block_k),
        in_specs=[
            _tile(c, H, q0, c.block_q, q_at),
            _tile(c, c.Hk, k0, c.block_k, kv_at),
            _tile(c, c.Hk, v0, c.block_k, kv_at),
        ],
        out_specs=[
            _tile(c, H, 0, c.block_q, q_at),
            row_spec,
        ],
        out_shape=[
            _out_struct(_heads_shape(c, H, c.Tq), arrays[0].dtype, *arrays),
            _out_struct((c.B * H, 1, c.Tq), jnp.float32, *arrays),
        ],
        scratch_shapes=[
            pltpu.VMEM((c.block_q, 128), jnp.float32),
            pltpu.VMEM((c.block_q, 128), jnp.float32),
            pltpu.VMEM((c.block_q, c.D), jnp.float32),
        ],
        interpret=c.interpret,
    )(*arrays)
    return _of_kernel(out, H, c), lse
