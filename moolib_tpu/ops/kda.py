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
form, one Pallas (Mosaic) call a layer named ``kda_prefill``.  Inside a chunk,
with ``G_t`` the running sum of ``g`` from the chunk's start, ``u_t`` the delta
rule's write (``S_t = diag(exp(g_t)) S_{t-1} + k_t u_t^T``) and ``S_0`` the
state the chunk starts from::

    A_ts = sum_c k_tc k_sc exp(G_tc - G_sc)   (s < t)
    P_ts = sum_c q_tc k_sc exp(G_tc - G_sc)   (s <= t)
    U = (I + diag(beta) A)^-1 diag(beta) (V - (K exp(G)) S_0)
    O = (Q exp(G)) S_0 + P U
    S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

The grid runs over blocks of heads (parallel) and over the chunks (sequential;
the state stays in the output's VMEM block, transposed as the decode kernel
holds it, from the first chunk of a head block to the last).  A step reads its
chunk's q, k, v, g as ``[64, heads x 128]`` rows of the arrays as the
projections leave them, and beta, and writes ``o`` as ``[heads, 64, d_v]`` of
a ``[H, N, 64, d_v]`` result; everything else of the algebra lives and dies in
VMEM.  All products are float32 matmuls at the highest precision on the MXU.

Every exponent is <= 0, by halving: positions t > s of a chunk part at one
level l, the highest bit in which they differ, where t lies in the later half
of a block of ``2^(l+1)`` and s in the earlier.  Measured from the boundary
between the halves, ``exp(G_t - G_s)`` is a product of two factors <= 1: the
decay from the boundary to t (``fore``, a prefix sum of g inside t's half) and
from s to the boundary (``back``, a suffix sum inside s's half).  So level l
is ONE product a head over the whole chunk, ``(K back) [diag(beta) K fore; Q
fore]^T``, of which the entries of that level are kept, six levels a chunk;
the two sums grow from level to level by a roll of the halves' totals and are
block-local sums of g, never differences of large running sums.  (A single
reference for a whole chunk would need ``exp(-G)``, which overflows where a
channel forgets fast.)  The matrices are held TRANSPOSED, the earlier
position down the sublanes: a level then streams 64 rows through the MXU and
not 128.  The unit lower-triangular ``I + diag(beta) A`` is inverted on the
way up: the inverse of a block of ``2m`` from those of its halves, ``T <- T -
T A_l T`` with ``A_l`` the level's entries: forward substitution by blocks,
two products a level.  The ``[64, 64]`` matrices of two heads share the 128
lanes, and as the blocks of a diagonal they take both heads through one
product; the levels run outside and the heads inside, so that the products of
one head fill the wait for another's.

Two inputs besides, both data: ``length``, the count of real positions (a
scalar in SMEM): a chunk that starts at or past it is not computed and its
inputs are not copied (the index map names the last live chunk again), its
rows of ``o`` are 0; inside the last live chunk the positions from ``length``
on get ``beta = 0, g = 0`` and hold the state still.  ``state``: what the
first chunk starts from (default 0), so a prompt can be prefilled in pieces.

Why a kernel (my chip calls 1 and 5, PR 46, TPU v5 lite, 2,048 positions of
64 heads, a layer): the ``jax.numpy`` form of this took 13.9 ms, about 1.5
TFLOP/s: 3.6 ms in the batched triangular solve (2.7 its custom call), 4.3 in
the sub-block products of ``A`` and ``P`` with their copies, 3.3 under the
scan over the chunks, 2.3 in transposes and elementwise passes between them,
every piece a round trip through HBM; and it ran a bucket's padding at full
price.  The kernel takes 4.1 ms (5.1 with the copies a bare call needs around
it), 3.9 with a third of the bucket padding; two thirds of that is the six
passes a float32 product takes on the MXU (2.4 ms in all at one pass, which
is wrong by 9e-4 where this is by 2e-7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
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
# prefill: chunks of 64 in the WY / UT form, one kernel a layer
# --------------------------------------------------------------------------


def _chunk_levels():
    """[C, 2 C] int32, a pair of heads side by side: at [s, t] for s < t the
    level at which positions s and t of a chunk part (the highest bit in which
    they differ), -1 on and below the diagonal."""
    s, t = np.indices((CHUNK, CHUNK))
    level = np.floor(np.log2(np.maximum(t ^ s, 1))).astype(np.int32)
    return np.tile(np.where(t > s, level, -1).astype(np.int32), (1, 2))


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _kda_prefill_kernel(len_ref, level_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref,
                        o_ref, so_ref, *, heads):
    n = pl.program_id(1)
    length = len_ref[0]
    C = CHUNK
    d_k, d_v = k_ref.shape[1] // heads, v_ref.shape[1] // heads

    @pl.when(n == 0)
    def _():
        so_ref[...] = s_ref[...]

    @pl.when(n * C >= length)
    def _():  # bucket padding: nothing was copied for it, nothing is computed
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n * C < length)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        real = n * C + row < length  # inside the last live chunk the padding holds still
        level = level_ref[...]
        # The [C, C] matrices of two heads lie side by side on the 128 lanes,
        # TRANSPOSED (the earlier position s down the sublanes: a level's
        # product then streams 64 rows through the MXU and not 128); as the
        # two blocks of a diagonal they multiply both heads in one product.
        left = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1) < C
        both = lambda x: jnp.concatenate([jnp.where(left, x, 0.0), jnp.where(left, 0.0, x)], axis=0)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1) % C).astype(jnp.float32)
        q = [q_ref[:, j * d_k:(j + 1) * d_k] for j in range(heads)]
        k = [k_ref[:, j * d_k:(j + 1) * d_k] for j in range(heads)]
        beta = [jnp.where(real, b_ref[:, j:j + 1], 0.0) for j in range(heads)]  # [C, 1]
        # fore: the log-decay from the start of a position's block of 2^l to
        # the position, inclusive; back: from behind the position to the
        # block's end.  Both are sums of g, so no exponent is positive.
        fore = [jnp.where(real, g_ref[:, j * d_k:(j + 1) * d_k], 0.0) for j in range(heads)]
        back = [jnp.zeros((C, d_k), jnp.float32)] * heads
        pairs = range(0, heads, 2)
        Pt = {j: jnp.zeros((C, 2 * C), jnp.float32) for j in pairs}
        inverse = {j: eye for j in pairs}
        # The levels outside, the heads inside: a head's products wait for
        # one another, those of other heads fill the wait.
        for l in range(CHUNK.bit_length() - 1):
            m = 1 << l
            later = (row & m) != 0  # the later half of its block of 2m
            here = level == l
            for j in pairs:
                pt = []  # a head's [A^T diag(beta) | P^T], all pairs of positions
                for i in (j, j + 1):
                    decay = jnp.exp(jnp.where(later, fore[i], back[i]))
                    z = k[i] * decay
                    pt.append(_dot(z, jnp.concatenate([beta[i] * z, q[i] * decay], axis=0), _NT))
                At = jnp.where(here, jnp.where(left, pt[0], pltpu.roll(pt[1], C, 1)), 0.0)
                Pt[j] = jnp.where(here, jnp.where(left, pltpu.roll(pt[0], C, 1), pt[1]), Pt[j])
                # (I + diag(beta) A)^-1 of the blocks of 2m from that of their halves
                inverse[j] = inverse[j] - (At if l == 0 else _dot(
                    inverse[j], both(_dot(At, both(inverse[j]), _NN)), _NN))
                for i in (j, j + 1):
                    whole = fore[i] + back[i]  # the block's sum, at each of its positions
                    fore[i], back[i] = (fore[i] + jnp.where(later, pltpu.roll(whole, m, 0), 0.0),
                                        back[i] + jnp.where(later, 0.0, pltpu.roll(whole, C - m, 0)))
        St = [so_ref[j] for j in range(heads)]  # [d_v, d_k]: the key channels on the lanes
        read = []
        for j in range(heads):
            reach = jnp.exp(fore[j])
            read.append(_dot(jnp.concatenate([k[j] * reach, q[j] * reach], axis=0), St[j], _NT))
        for j in pairs:
            rhs = [beta[i] * (v_ref[:, i * d_v:(i + 1) * d_v] - read[i][:C]) for i in (j, j + 1)]
            u = _dot(both(inverse[j]), jnp.concatenate(rhs, axis=0), _TN)  # [2C, d_v]
            within = _dot(both(Pt[j]), u, _TN)
            for i, half in ((j, slice(0, C)), (j + 1, slice(C, 2 * C))):
                diagonal = jnp.sum(q[i] * k[i], axis=1, keepdims=True)
                o_ref[i] = read[i][C:] + within[half] + diagonal * u[half]
                so_ref[i] = (St[i] * jnp.exp(fore[i][C - 1:C] + back[i][C - 1:C])
                             + _dot(u[half], k[i] * jnp.exp(back[i]), _TN))


_PREFILL_HEADS = 4  # heads a grid step: 512 steps a layer for 2,048 positions of 64 heads


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunked_kda(q, k, v, g, beta, *, length=None, state=None, interpret=None):
    """The recurrence over a whole sequence in chunks of :data:`CHUNK`, one
    kernel named ``kda_prefill`` (module docstring).  q, k, g: [T, H, d_k]; v:
    [T, H, d_v]; beta: [T, H]; T a multiple of the chunk.  ``length`` (int32
    scalar, traced; default T): the first ``length`` positions are real, the
    rest a bucket's padding, which leaves the state as it is; a chunk wholly
    past it is neither copied nor computed and its rows of ``o`` are 0.
    ``state`` [H, d_k, d_v]: what the first chunk starts from (default 0).
    Returns (o [T, H, d_v], the state after position ``length - 1`` [H, d_k,
    d_v]), float32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, H, d_k = q.shape
    d_v = v.shape[-1]
    if T % CHUNK:
        raise ValueError(f"chunked_kda: {T} positions are not whole chunks of {CHUNK}")
    if not interpret and (d_k % 128 or d_v % 128):
        raise ValueError(f"chunked_kda wants heads of whole 128 lanes on the chip, got {d_k}, {d_v}")
    N = T // CHUNK
    if H % 2:
        raise ValueError(f"chunked_kda: {H} heads are not pairs (two share the 128 lanes)")
    hb = max(h for h in range(2, _PREFILL_HEADS + 1, 2) if H % h == 0)
    f32 = lambda a: a.astype(jnp.float32)
    rows = lambda x: f32(x).reshape(T, -1)  # a head's channels are 128 lanes of a row
    b = f32(beta).reshape(T, H // hb, hb).transpose(1, 0, 2)
    length = jnp.clip(jnp.asarray(T if length is None else length, jnp.int32), 0, T).reshape(1)
    start = (jnp.zeros((H, d_v, d_k), jnp.float32) if state is None
             else jnp.swapaxes(f32(state), -1, -2))

    def chunk(n, length):  # past the last live chunk the grid names it again: no copy
        return jnp.minimum(n, jnp.maximum((length[0] - 1) // CHUNK, 0))

    wide = lambda d: pl.BlockSpec((CHUNK, hb * d), lambda h, n, length: (chunk(n, length), h))
    held = pl.BlockSpec((hb, d_v, d_k), lambda h, n, length: (h, 0, 0))
    with jax.named_scope("kda_prefill"):
        o, last = pl.pallas_call(
            functools.partial(_kda_prefill_kernel, heads=hb),
            out_shape=[jax.ShapeDtypeStruct((H, N, CHUNK, d_v), jnp.float32),
                       jax.ShapeDtypeStruct((H, d_v, d_k), jnp.float32)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(H // hb, N),
                in_specs=[
                    pl.BlockSpec((CHUNK, 2 * CHUNK), lambda h, n, length: (0, 0)),
                    wide(d_k), wide(d_k), wide(d_v), wide(d_k),
                    pl.BlockSpec((None, CHUNK, hb),
                                 lambda h, n, length: (h, chunk(n, length), 0)),
                    held,
                ],
                out_specs=[
                    pl.BlockSpec((hb, None, CHUNK, d_v), lambda h, n, length: (h, n, 0, 0)),
                    held,
                ],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="kda_prefill",
        )(length, jnp.asarray(_chunk_levels()), rows(q), rows(k), rows(v), rows(g), b, start)
    return o.transpose(1, 2, 0, 3).reshape(T, H, d_v), jnp.swapaxes(last, -1, -2)
