"""Kimi delta attention (KDA, arXiv:2510.26692): the gated delta rule with a
decay for each key channel, as a decode kernel and as a chunked prefill.

Per head, with a state ``S`` [d_k, d_v] in float32, a log-decay ``g_t`` [d_k]
(<= 0), a write strength ``beta_t`` and l2-normalised ``q_t``, ``k_t``::

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`recurrent_kda` is the one definition of it, a token at a time.

:func:`kda_decode` is one Pallas (Mosaic) call a layer for a decode step: for
every ACTIVE slot and head it reads the slot's state once, applies decay,
delta update and read-out, and writes it back in place (the state array is
input-output aliased; the blocks of a slot nobody holds are never copied, so
a freed slot's stale state costs nothing and is left as it was).  The state
is held **transposed**, ``[.., d_v, d_k]`` with the key channels on the 128
lanes: decay, ``k`` and ``q`` then broadcast along sublanes as the row
vectors they arrive as, and ``k^T S`` and ``S^T q`` are lane reductions.  The
two vectors that are needed across sublanes (``v`` in, ``o`` out) change
orientation through a masked reduction against the identity, one pass over a
tile each.  It lowers through Mosaic on ``tpu`` and runs in Pallas interpret
mode on ``cpu``; :func:`kda_step` is the same step in ``jax.numpy``, which the
tests hold the kernel to.

:func:`chunked_kda` is the prefill: chunks of 64 positions in the WY / UT
form.  Inside a chunk, with ``G_t`` the running sum of ``g`` from the chunk's
start, ``u_t`` the delta rule's write (``S_t = diag(exp(g_t)) S_{t-1} + k_t
u_t^T``) and ``S_0`` the state the chunk starts from::

    A_ts = sum_c k_tc k_sc exp(G_tc - G_sc)   (s < t)
    P_ts = sum_c q_tc k_sc exp(G_tc - G_sc)   (s <= t)
    (I + diag(beta) A) [U~ | W] = diag(beta) [V | K exp(G)]
    U = U~ - W S_0;   O = (Q exp(G)) S_0 + P U
    S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

Every exponent is <= 0: ``A`` and ``P`` are made in sub-blocks of 16, a
diagonal sub-block from ``exp(G_t - G_s)`` itself and one below the diagonal
from two factors taken relative to the later sub-block's start (a single
reference for a whole chunk would need ``exp(-G)``, which overflows where a
channel forgets fast).  What does not depend on ``S_0`` is computed for all
chunks at once; only ``U``, ``O`` and ``S`` walk the chunks under a scan.
Plain ``jax.numpy`` at the highest matmul precision: a prefill's chunk algebra
is a few percent of its projections' operations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
_SUB = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def recurrent_kda(q, k, v, g, beta, state):
    """The recurrence, a token at a time.  q, k, g: [T, H, d_k]; v: [T, H,
    d_v]; beta: [T, H]; state: [H, d_k, d_v] float32.  Returns (o [T, H,
    d_v], the state after the last token)."""

    def step(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None]
        u = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, S, precision=_HIGHEST))
        S = S + k[..., None] * u[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q, S, precision=_HIGHEST)

    state, o = jax.lax.scan(step, state.astype(jnp.float32), (q, k, v, g, beta))
    return o, state


# --------------------------------------------------------------------------
# decode: one token a slot, the state read once and written once, in place
# --------------------------------------------------------------------------


def kda_step(q, k, v, g, beta, state, layer, active):
    """:func:`kda_decode` in ``jax.numpy``: one token a slot.  q, k, g: [S, H,
    d_k]; v: [S, H, d_v]; beta: [S, H]; state: [S, L, H, d_v, d_k] float32
    (transposed: module docstring); active: [S] bool.  Returns (o [S, H, d_v],
    the state with ``layer``'s rows of the active slots advanced)."""
    St = state[:, layer] * jnp.exp(g)[:, :, None, :]
    u = beta[..., None] * (v - jnp.sum(St * k[:, :, None, :], axis=-1))
    St = St + u[..., None] * k[:, :, None, :]
    o = jnp.sum(St * q[:, :, None, :], axis=-1)
    St = jnp.where(active[:, None, None, None], St, state[:, layer])
    return o, state.at[:, layer].set(St)


def _kda_decode_kernel(order_ref, count_ref, layer_ref, x_ref, s_ref, o_ref, so_ref,
                       *, heads):
    i = pl.program_id(0)
    count = count_ref[0]

    @pl.when(count == 0)
    def _():  # nobody holds a slot: the one block the grid names goes back as it came
        so_ref[...] = s_ref[...]

    @pl.when(i < count)
    def _():
        d_v, d_k = s_ref.shape[-2:]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (d_v, d_v), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (d_v, d_v), 1))

        def head(h, carry):
            x = x_ref[h]  # [8, 128]: q, k, beta k, g, v, padding
            q, k, kb, g, v = (x[r:r + 1] for r in range(5))
            St = s_ref[h] * jnp.exp(g)  # [d_v, d_k], the decay along the lanes
            kS = jnp.sum(St * k, axis=1, keepdims=True)  # [d_v, 1]
            v_col = jnp.sum(jnp.where(eye, v, 0.0), axis=1, keepdims=True)
            St = St + (v_col - kS) * kb
            so_ref[h] = St
            o_col = jnp.sum(St * q, axis=1, keepdims=True)
            o_ref[pl.ds(h, 1), :] = jnp.sum(jnp.where(eye, o_col, 0.0), axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


_KDA_VMEM_LIMIT = 32 << 20  # of a v5e's 128 MiB: the state's blocks, in and out, twice over


_HEADS_PER_BLOCK = 32  # a block of the state is 2 MB: 128 grid steps a layer at 64 heads


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode(q, k, v, g, beta, state, layer, active, *, interpret=None):
    """One decode step of one KDA layer over every slot: shapes as
    :func:`kda_step`, ``layer`` a traced index into the state's layer axis
    (under a scan a sliced ``state[:, layer]`` would be copied whole each
    iteration).  The state is updated in place where the caller donates it (it is
    aliased to the kernel's output); the output rows of slots that are not ``active`` are 0
    and their state is not touched.  One kernel, named ``kda_decode`` in the
    profiler's trace.  d_k = d_v = 128 lanes on the chip."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, H, d_k = q.shape
    d_v = v.shape[-1]
    if d_k != d_v or (not interpret and d_k != 128):
        raise ValueError(f"kda_decode wants d_k = d_v (= 128 on the chip), got {d_k}, {d_v}")
    hb = min(H, _HEADS_PER_BLOCK)
    if H % hb:
        raise ValueError(f"kda_decode: {H} heads do not tile by {hb}")
    nh = H // hb
    f32 = lambda a: a.astype(jnp.float32)
    # One [8, d_k] tile a slot and head: q, k, beta k, g, v and three rows of 0.
    x = jnp.stack([f32(q), f32(k), f32(k) * f32(beta)[..., None], f32(g), f32(v)], axis=2)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 3), (0, 0)))
    # The kernel visits the active slots only, in slot order; the grid's
    # steps past them name the last block again, which copies nothing.
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    count = jnp.sum(active, dtype=jnp.int32).reshape(1)

    def at(i, h, order, count, layer):
        last = jnp.maximum(count[0] - 1, 0)
        return order[jnp.minimum(i, last)], jnp.where(i < count[0], h, nh - 1)

    def state_at(i, h, order, count, layer):
        slot, hblock = at(i, h, order, count, layer)
        return slot, layer[0], hblock, 0, 0

    def rows_at(i, h, order, count, layer):
        return (*at(i, h, order, count, layer), 0, 0)

    with jax.named_scope("kda_decode"):
        o, state = pl.pallas_call(
            functools.partial(_kda_decode_kernel, heads=hb),
            out_shape=[jax.ShapeDtypeStruct((S, H, d_v), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(S, nh),
                in_specs=[
                    pl.BlockSpec((None, hb, 8, d_k), rows_at),
                    pl.BlockSpec((None, None, hb, d_v, d_k), state_at),
                ],
                out_specs=[
                    pl.BlockSpec((None, hb, d_v), lambda *a: at(*a) + (0,)),
                    pl.BlockSpec((None, None, hb, d_v, d_k), state_at),
                ],
            ),
            # Operand 4 of the call (after the three prefetched scalars and x)
            # is the state, and comes back as output 1.
            input_output_aliases={4: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_KDA_VMEM_LIMIT),
            interpret=interpret,
            name="kda_decode",
        )(order, count, jnp.asarray(layer, jnp.int32).reshape(1), x, state)
    return jnp.where(active[:, None, None], o, 0.0), state


# --------------------------------------------------------------------------
# prefill: chunks of 64 in the WY / UT form
# --------------------------------------------------------------------------


def _decayed_products(a, k, G):
    """``sum_c a_tc k_sc exp(G_tc - G_sc)`` for s <= t inside chunks.  a, k,
    G: [H, N, C, d] with G the running sum of the log-decay inside the chunk.
    Returns [H, N, C, C], zero above the diagonal; no exponent is positive."""
    H, N, C, d = a.shape
    B = C // _SUB
    blocks = lambda x: x.reshape(H, N, B, _SUB, d)
    ab, kb, Gb = blocks(a), blocks(k), blocks(G)
    # A diagonal sub-block, from the difference itself.
    low = jnp.tril(jnp.ones((_SUB, _SUB), jnp.bool_))
    diff = Gb[..., :, None, :] - Gb[..., None, :, :]  # [.., t, s, d]
    decay = jnp.exp(jnp.where(low[..., None], diff, -jnp.inf))
    diag = jnp.sum(ab[..., :, None, :] * kb[..., None, :, :] * decay, axis=-1)
    # Below the diagonal: both factors relative to the later sub-block's
    # start (the running sum before its first position).
    start = jnp.concatenate([jnp.zeros_like(Gb[:, :, :1, 0]), Gb[:, :, :-1, -1]], axis=2)
    fore = ab * jnp.exp(Gb - start[..., None, :])  # [H, N, i, t, d]
    back = jnp.minimum(start[:, :, :, None, None, :] - Gb[:, :, None], 0.0)
    earlier = jnp.arange(B)[:, None] > jnp.arange(B)[None, :]  # [i, j]
    back = jnp.where(earlier[..., None, None], jnp.exp(back), 0.0) * kb[:, :, None]
    off = jnp.einsum("hnitd,hnijsd->hnitjs", fore, back, precision=_HIGHEST)
    same = jnp.eye(B, dtype=jnp.bool_)[:, None, :, None]  # [i, 1, j, 1]
    out = jnp.where(same, diag[:, :, :, :, None, :], off)  # [H, N, i, t, j, s]
    return out.reshape(H, N, C, C)


def chunked_kda(q, k, v, g, beta):
    """The recurrence over a whole sequence from a zero state, in chunks of
    :data:`CHUNK` (module docstring).  q, k, g: [T, H, d_k]; v: [T, H, d_v];
    beta: [T, H]; T a multiple of the chunk.  A position with ``beta`` 0 and
    ``g`` 0 leaves the state as it is (a prompt's bucket padding).  Returns
    (o [T, H, d_v], the last state [H, d_k, d_v])."""
    T, H, d_k = q.shape
    d_v = v.shape[-1]
    if T % CHUNK:
        raise ValueError(f"chunked_kda: {T} positions are not whole chunks of {CHUNK}")
    N = T // CHUNK
    f32 = lambda a: a.astype(jnp.float32)
    chunks = lambda x: f32(x).reshape(N, CHUNK, H, -1).transpose(2, 0, 1, 3)  # [H, N, C, d]
    with jax.named_scope("kda_prefill"):
        q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
        b = chunks(beta)  # [H, N, C, 1]
        G = jnp.cumsum(g, axis=2)
        A = jnp.tril(_decayed_products(k, k, G), -1)
        P = _decayed_products(q, k, G)
        k_fore = k * jnp.exp(G)
        system = jnp.eye(CHUNK, dtype=jnp.float32) + b * A
        solved = jax.scipy.linalg.solve_triangular(
            system, b * jnp.concatenate([v, k_fore], axis=-1), lower=True,
            unit_diagonal=True)
        u_free, w = solved[..., :d_v], solved[..., d_v:]
        q_fore = q * jnp.exp(G)
        k_end = k * jnp.exp(G[:, :, -1:, :] - G)
        total = jnp.exp(G[:, :, -1, :])  # [H, N, d_k]

        def chunk(S, x):
            u_free, w, q_fore, P, k_end, total = x
            u = u_free - jnp.einsum("hck,hkv->hcv", w, S, precision=_HIGHEST)
            o = (jnp.einsum("hck,hkv->hcv", q_fore, S, precision=_HIGHEST)
                 + jnp.einsum("hcs,hsv->hcv", P, u, precision=_HIGHEST))
            S = S * total[..., None] + jnp.einsum("hck,hcv->hkv", k_end, u, precision=_HIGHEST)
            return S, o

        per_chunk = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0),
                                 (u_free, w, q_fore, P, k_end, total))
        state, o = jax.lax.scan(chunk, jnp.zeros((H, d_k, d_v), jnp.float32), per_chunk)
    return o.transpose(0, 2, 1, 3).reshape(T, H, d_v), state
