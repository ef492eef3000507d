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

Layout [B, T, H, D]; shapes that don't tile (T without a 128-multiple
divisor) take the XLA dense path, counted in ``flash_dense_reroutes_total``.
The kernels lower through Mosaic on the ``tpu`` platform and run in Pallas
interpret mode on ``cpu`` (tests); any other platform raises.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry, utils

_NEG_INF = -1e30

_M_DENSE_REROUTES = telemetry.get_registry().counter(
    "flash_dense_reroutes_total",
    "flash_attention calls traced onto the O(T^2) dense path because the "
    "sequence length has no 128-multiple block divisor",
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
        # Row logsumexp for the backward pass, written in the scratch's own
        # lane-replicated (block_q, 128) layout — no in-kernel transpose.
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)[0]


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse_raw = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse_raw)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if _use_oracle_bwd():
        # Oracle path: VJP of the blockwise-jax formulation (recomputes the
        # streaming softmax in pure XLA; same FLOPs class, O(block) score
        # memory).  Kept for parity testing against the pallas kernels.
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _blockwise_attention(
                q_, k_, v_, causal, block_q, block_k
            ),
            q, k, v,
        )
        return vjp(g)
    return _flash_backward(
        q, k, v, out, lse, g, None, causal, block_q, block_k, interpret
    )


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, block_q, block_k, interpret):
    """Like ``_flash`` but returns (out [B,Tq,H,D], lse [B,Tq,H]) with lse a
    differentiable output: ring attention combines per-chunk results by
    logsumexp weights, so gradients flow through it (the lse cotangent folds
    into the backward kernels' delta term — no extra kernel).  A separate
    custom_vjp so the plain path never materializes/consumes a zero lse
    cotangent on the training hot path."""
    out, lse_raw = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    B, Tq, H, D = q.shape
    return out, lse_raw.reshape(B, H, Tq).transpose(0, 2, 1)


def _flash_lse_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse_raw = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    B, Tq, H, D = q.shape
    lse_pub = lse_raw.reshape(B, H, Tq).transpose(0, 2, 1)
    return (out, lse_pub), (q, k, v, out, lse_raw)


def _flash_lse_vjp_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    if _use_oracle_bwd():
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _blockwise_attention(
                q_, k_, v_, causal, block_q, block_k, return_lse=True
            ),
            q, k, v,
        )
        return vjp((g_out, g_lse))
    return _flash_backward(
        q, k, v, out, lse, g_out, g_lse, causal, block_q, block_k, interpret
    )


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


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
    dk_scr, dv_scr, *, scale, causal, block_q, block_k,
):
    """dk/dv pass: one kv block per (batch*head, ki), q blocks stream innermost.

    Same transposed-scores layout as the dq pass; dk and dv accumulate in
    f32 scratch across the q sweep.
    """
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
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

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@jax.named_scope("flash_attention")
def _flash_backward(
    q, k, v, out, lse, g, g_lse, causal, block_q, block_k, interpret
):
    """Pallas flash backward: dq pass + dk/dv pass (FlashAttention-2 style).

    ``g_lse`` is the cotangent of the lse output ([B,Tq,H] or None): since
    dL/ds_j = p_j((g·v_j) - (g·out) + g_lse), it folds into the delta row
    table as ``delta - g_lse`` — the kernels are unchanged.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = D**-0.5
    # Backward blocks capped at 512x512 (env-tunable for on-chip sweeps;
    # read at TRACE time — the jit cache does not key on env vars, so a
    # sweep must re-trace per value: fresh process, cleared caches, or AOT
    # .lower().compile() while the var is set, as flash_bench does for
    # MOOLIB_TPU_FLASH_BWD.  Values clamp up to the 128 tile minimum.):
    # the transposed-score intermediates (st, pt, dpt — all [bk, bq] f32)
    # plus two f32 output scratches are live at once, so the forward's
    # 512x1024 tiles would crowd VMEM.  The cap must preserve divisibility
    # (e.g. Tk=1280 forwards with block_k=640; a blind min() would drop the
    # tail kv block) — re-derive the largest dividing block under the cap.
    # Always succeeds: any valid forward block is a multiple of 128
    # dividing T, so 128 divides T.
    cap_q = max(128, int(os.environ.get("MOOLIB_TPU_FLASH_BWD_BLOCK_Q", 512)))
    cap_k = max(128, int(os.environ.get("MOOLIB_TPU_FLASH_BWD_BLOCK_K", 512)))
    bq = _largest_divisor(Tq, min(block_q, cap_q))
    bk = _largest_divisor(Tk, min(block_k, cap_k))

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    qb, kb, vb, dob = to_bh(q), to_bh(k), to_bh(v), to_bh(g)
    # delta_i = Σ_d dO_i · O_i — row table, like lse, in [B*H, Tq] layout.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(B * H, Tq)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32).transpose(0, 2, 1).reshape(
            B * H, Tq
        )

    kwargs = dict(scale=scale, causal=causal, block_q=bq, block_k=bk)
    # The row tables ride as [B*H, 1, T]: TPU lowering constrains the last
    # two block dims (divisible by (8, 128) or equal to the array dims), so
    # a 2-D (1, bq) block over [B*H, T] is illegal when B*H > 1 — the unit
    # dim must sit in the constrained sublane slot, where 1 == 1 passes.
    lse = lse.reshape(B * H, 1, Tq)
    delta = delta.reshape(B * H, 1, Tq)
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kwargs),
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),  # k
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),  # do
            row_spec,  # lse
            row_spec,  # delta
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((B * H, Tq, D), q.dtype, kb, qb, vb, dob, delta),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(kb, qb, vb, dob, lse, delta)

    qrow_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kwargs),
        grid=(B * H, Tk // bk, Tq // bq),
        in_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),  # k
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, 0)),  # q
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),  # v
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, 0)),  # do
            qrow_spec,  # lse
            qrow_spec,  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((B * H, Tk, D), k.dtype, kb, qb, vb, dob, delta),
            _out_struct((B * H, Tk, D), v.dtype, kb, qb, vb, dob, delta),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(kb, qb, vb, dob, lse, delta)

    def from_bh(x, T):
        return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)

    return from_bh(dq, Tq), from_bh(dk, Tk), from_bh(dv, Tk)


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
):
    """Blockwise attention; q/k/v: [B, T, H, D] → [B, T, H, D].

    ``window``: the keys a query sees, itself included (a sliding window of
    ``window`` positions; needs ``causal`` and Tq == Tk).  Key blocks outside
    every window of a query block are skipped, not masked.  Forward only:
    differentiating a windowed call raises, and ``return_lse`` and ``mesh``
    are not offered with it.

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
    Tk = k.shape[1]
    if window is not None:
        if not causal or Tq != Tk or return_lse or mesh is not None or window < 1:
            raise ValueError(
                "flash_attention(window=...) is causal self-attention over one "
                "sequence (Tq == Tk, window >= 1), without return_lse or mesh")
        if window >= Tq:
            window = None  # every key a query may see is inside its window
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        def axis(name, dim):
            return name if name in mesh.axis_names and dim % mesh.shape[name] == 0 else None

        spec = P(axis("dp", B), None, axis("tp", H), None)
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
    # Defaults from a block sweep on TPU v5e (T=4096, causal): 128x128 blocks
    # leave grid overhead dominant (32k tiny steps, 7.7 ms); 512x1024 runs the
    # same shape in 1.8 ms while q+k+v+s blocks stay well under VMEM.  Use the
    # largest 128-multiple divisor of T up to the tuned size so lengths like
    # 1536 or 2560 still ride the kernel; T without such a divisor (e.g. 250,
    # or 160 < 2*128) takes the dense fallback rather than handing Mosaic a
    # non-tile-aligned block.
    explicit_q = block_q is not None
    explicit_k = block_k is not None
    if block_q is None:
        block_q = _largest_divisor(Tq, 512)
    if block_k is None:
        # Under a window a key block wider than the window is mostly masked.
        cap_k = 1024 if window is None else min(1024, max(128, window // 128 * 128))
        block_k = _largest_divisor(Tk, cap_k)
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
        if return_lse:
            return dense_attention_lse(q, k, v, causal=causal)
        if window is not None:  # the oracle, one block the whole length
            return _blockwise_attention(q, k, v, True, Tq, Tk, window=window)
        return full_attention(q, k, v, causal=causal)
    if interpret is None:
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise RuntimeError(
                f"flash_attention has a Mosaic (tpu) lowering and a cpu "
                f"interpret mode for tests; platform {backend!r} has neither"
            )
        interpret = backend == "cpu"
    if return_lse:
        return _flash_lse(q, k, v, causal, block_q, block_k, interpret)
    if window is not None:
        return _flash_window(q, k, v, window, block_q, block_k, interpret)
    return _flash(q, k, v, causal, block_q, block_k, interpret)


@jax.named_scope("flash_attention")
def _flash_window_forward(q, k, v, window, block_q, block_k, interpret):
    B, T, H, D = q.shape

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
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
def _flash_forward(q, k, v, causal, block_q, block_k, interpret):
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = D**-0.5

    # [B, T, H, D] -> [B*H, T, D]
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    grid = (B * H, Tq // block_q, Tk // block_k)
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((B * H, Tq, D), q.dtype, qb, kb, vb),
            _out_struct((B * H, Tq, 128), jnp.float32, qb, kb, vb),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb)
    # lse comes out lane-replicated; one lane is the [B*H, Tq] row table.
    return out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3), lse[:, :, 0]
