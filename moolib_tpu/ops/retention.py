"""Power retention at degree 2 (gated power attention, arXiv:2507.04239): a
linear attention whose weights are the SQUARE of a scaled dot product under a
scalar forget gate a K/V head, as a decode kernel over a fixed-size state and
as a blocked prefill.

Per query head h over K/V head g = h // group, with a log-decay ``lam_t`` <= 0
a K/V head and d the head size::

    a(t, j) = exp(sum_{l=j+1..t} lam_l) * (q_t . k_j / sqrt(d))^2      j <= t
    o_t     = sum_j a(t, j) v_j / (sum_j a(t, j) + eps)

Because ``(q . k)^2 = phi(q) . phi(k)`` with :func:`phi` the symmetric square,
the sums are a recurrence over a state a K/V head, float32::

    S_t = exp(lam_t) S_{t-1} + phi(k_t / d^(1/4)) v_t^T
    z_t = exp(lam_t) z_{t-1} + phi(k_t / d^(1/4))
    o_t = phi(q_t / d^(1/4))^T S_t / (phi(q_t / d^(1/4)) . z_t + eps)

**The symmetric square in cyclic diagonals.**  ``phi(u)[j, a] = w_j u_a
u_{(a + j) mod d}`` for j = 0 .. d/2, with ``w_0 = w_{d/2} = 1`` and ``sqrt 2``
between: diagonal j holds every unordered pair at cyclic distance j once (the
pairs at distance d/2 twice, at weight 1), so ``d/2 + 1`` rows of d entries
carry the d (d + 1) / 2 distinct products with d/2 to spare (8,320 for 8,256
at d = 128, where the full outer product has 16,384), every row is a rotation
of ``u`` times ``u``, and the state is ``d/2 + 1`` tiles of ``[d_v, d]``: the
layout ``[.., d/2 + 1, d_v, d]`` this module keeps it in, the diagonal's
entries on the 128 lanes.

:func:`recurrent_retention` is the one definition, a token at a time.
:func:`retention_decode` is one Pallas (Mosaic) call a layer for a decode
step: for every ACTIVE slot and K/V head the state is read once, decayed,
updated by the rank-1 product, read out for the head's ``group`` query heads
and written back in place (state and normaliser are input-output aliased; the
blocks of a slot nobody holds are never copied).  The step's k, its decay and
the group's q ride in as the rows of ONE ``[8, d]`` tile a slot and head; the
kernel turns that tile by j lanes and multiplies it with itself, which is
``phi`` of all of them at diagonal j, keeps the 65 tiles in VMEM, and advances
the normaliser on the way.  Inside the loop over the state decay, ``phi(k)``
and ``phi(q)`` then broadcast along sublanes as the rows they are, a stripe
of 32 value rows at a time, and a read-out is a lane reduction a stripe: the
group's partial sums stay in vector registers.  :func:`retention_step` is the
same step in ``jax.numpy``, which the tests hold the kernel to.

:func:`retention_prefill` is the first form over a whole prompt, two kernels:
``retention_prefill``, the quadratic sum in blocks of 256 positions
(flash-shaped, with no running maximum: no weight is negative or above its
undecayed square), and ``retention_prefill_state``, the state and normaliser
after the last position, ``sum_j exp(sum_{l>j} lam_l) phi(k_j) [v_j, 1]``, a
block of positions and a diagonal at a time in float32 at the highest matmul
precision.  The quadratic sum costs ``4 T d`` operations a position a head and
the state ``4 d_v d (d/2 + 1)``: below some thousands of positions the first
is the cheaper, and the engine's prompts are bounded by its largest bucket.
The prompt's true ``length`` bounds both grids, as data: the quadratic kernel
visits the block pairs of the causal triangle up to the last block that holds
a real position (the pairs in order, from two prefetched tables), the state
kernel the blocks up to it, so a block wholly past the length (a bucket's
padding) is neither copied in nor computed and costs no grid step; inside the
last live block the padding's k, v and log-decay are zeroed and move nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6
_SQRT2 = 2.0 ** 0.5
_HIGHEST = jax.lax.Precision.HIGHEST
_STRIPE = 32  # value rows a read-out accumulates at once: group x 4 vregs of partial sums
_PREFILL_BLOCK = 256
_VMEM_LIMIT = 40 << 20  # of a v5e's 128 MiB: a head's state (4.26 MB), in and out, twice over


def diagonals(d: int) -> int:
    """Rows of :func:`phi` for a head size ``d``."""
    return d // 2 + 1


def norm_rows(d: int) -> int:
    """Rows a normaliser is kept in: the diagonals, up to whole tiles of 8
    sublanes (65 rows alone make XLA re-lay the leaf around the kernel, a
    copy of all of it a step); the rows past ``diagonals(d)`` stay 0."""
    return -(-diagonals(d) // 8) * 8


def _diagonal_weight(j, D):
    """1 on the first and the last diagonal (each product once, or twice at
    weight 1), sqrt 2 between."""
    return jnp.where((j == 0) | (j == D - 1), 1.0, _SQRT2).astype(jnp.float32)


def phi(u):
    """The symmetric square in cyclic diagonals (module docstring): [.., d] ->
    [.., d/2 + 1, d] with ``sum(phi(q) * phi(k)) == (q . k) ** 2``."""
    d = u.shape[-1]
    if d % 2:
        raise ValueError(f"phi wants an even head size, got {d}")
    D = diagonals(d)
    rolled = jnp.stack([jnp.roll(u, -j, axis=-1) for j in range(D)], axis=-2)
    return u[..., None, :] * rolled * _diagonal_weight(jnp.arange(D), D)[:, None]


def _features(q, k):
    """phi of the scaled q and k, the queries by K/V head: q [.., H, d], k
    [.., G, d] -> (pq [.., G, group, D, d], pk [.., G, D, d]) float32."""
    d, G = q.shape[-1], k.shape[-2]
    scale = d ** -0.25
    pq = phi(q.astype(jnp.float32) * scale)
    pq = pq.reshape(*q.shape[:-2], G, q.shape[-2] // G, *pq.shape[-2:])
    return pq, phi(k.astype(jnp.float32) * scale)


def recurrent_retention(q, k, v, lam, state, norm):
    """The recurrence, a token at a time.  q: [T, H, d]; k, v: [T, G, d]; lam:
    [T, G] (the gate's log, <= 0); state: [G, D, d_v, d] and norm: [G,
    norm_rows, d] float32, D = d/2 + 1.  Returns (o [T, H, d_v], the state and
    the normaliser after the last token)."""
    T, H, d = q.shape
    pq, pk = _features(q, k)
    D = diagonals(d)

    def step(carry, x):
        S, z = carry
        pq, pk, v, lam = x
        decay = jnp.exp(lam)
        S = S * decay[:, None, None, None] + pk[:, :, None, :] * v[:, None, :, None]
        z = z * decay[:, None, None] + pk
        num = jnp.einsum("gjda,gdva->gjv", pq, S, precision=_HIGHEST)
        den = jnp.einsum("gjda,gda->gj", pq, z, precision=_HIGHEST)
        return (S, z), num / (den[..., None] + EPS)

    (state, z), o = jax.lax.scan(
        step, (state.astype(jnp.float32), norm[:, :D].astype(jnp.float32)),
        (pq, pk, v.astype(jnp.float32), lam.astype(jnp.float32)))
    return o.reshape(T, H, -1), state, norm.at[:, :D].set(z)


# --------------------------------------------------------------------------
# decode: one token a slot, the state read once and written once, in place
# --------------------------------------------------------------------------


def retention_step(q, k, v, lam, state, norm, layer, active):
    """:func:`retention_decode` in ``jax.numpy``: one token a slot.  q: [S, H,
    d]; k, v: [S, G, d]; lam: [S, G]; state: [S, L, G, D, d_v, d] and norm: [S,
    L, G, norm_rows, d] float32; active: [S] bool.  Returns (o [S, H, d_v], the state
    and the normaliser with ``layer``'s rows of the active slots advanced)."""
    S, H, d = q.shape
    pq, pk = _features(q, k)
    D = diagonals(d)
    decay = jnp.exp(lam.astype(jnp.float32))
    layer_of = lambda x: jax.lax.dynamic_index_in_dim(x, layer, 1, keepdims=False)
    new_s = (layer_of(state) * decay[..., None, None, None]
             + pk[..., None, :] * v.astype(jnp.float32)[:, :, None, :, None])
    old_z = layer_of(norm)
    new_z = old_z[:, :, :D] * decay[..., None, None] + pk
    num = jnp.einsum("sgjda,sgdva->sgjv", pq, new_s, precision=_HIGHEST)
    den = jnp.einsum("sgjda,sgda->sgj", pq, new_z, precision=_HIGHEST)
    new_z = jnp.concatenate([new_z, old_z[:, :, D:]], axis=2)  # the tiles' spare rows stay
    o = (num / (den[..., None] + EPS)).reshape(S, H, -1)
    keep = lambda new, x: jax.lax.dynamic_update_index_in_dim(
        x, jnp.where(active.reshape((S,) + (1,) * (new.ndim - 1)), new, layer_of(x)), layer, 1)
    return jnp.where(active[:, None, None], o, 0.0), keep(new_s, state), keep(new_z, norm)


def _retention_decode_kernel(order_ref, count_ref, layer_ref, x_ref, v_ref, s_ref, z_ref,
                             num_ref, den_ref, so_ref, zo_ref, f_ref, *, group):
    i = pl.program_id(0)
    count = count_ref[0]

    @pl.when(count == 0)
    def _():  # nobody holds a slot: the one block the grid names goes back as it came
        so_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]

    @pl.when(i < count)
    def _():
        D, d_v, d = s_ref.shape
        stripe = min(_STRIPE, d_v)
        x = x_ref[...]  # rows: k, the decay in every lane, q of the group's heads, zeros
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (stripe, d), 1)
        decay = x[1:2]

        # The features a diagonal at a time, phi(k) and phi(q) in the rows of
        # one tile, kept for the read-outs below; the normaliser on the way.
        def features(j, den):
            turned = pltpu.roll(x, (d - j) % d, 1)  # turned[a] = x[(a + j) mod d]
            f = jnp.where(row == 1, x, x * turned * _diagonal_weight(j, D))
            f_ref[j] = f
            z = z_ref[pl.ds(j, 1), :] * decay + f[0:1]
            zo_ref[pl.ds(j, 1), :] = z
            return tuple(a + z * f[2 + h:3 + h] for h, a in enumerate(den))

        den = jax.lax.fori_loop(
            0, D, features, tuple(jnp.zeros((1, d), jnp.float32) for _ in range(group)))
        out = jnp.zeros((1, d), jnp.float32)
        for h, a in enumerate(den):  # head h's denominator in lane h
            out = jnp.where(lane[0:1] == h, jnp.sum(a, axis=1, keepdims=True), out)
        den_ref[...] = jnp.broadcast_to(out, den_ref.shape)

        def rows(s, carry):
            r0 = pl.multiple_of(s * stripe, stripe)
            v_col = v_ref[pl.ds(r0, stripe), :]  # this stripe's values, along the lanes

            def diagonal(j, acc):
                new = s_ref[j, pl.ds(r0, stripe), :] * decay + v_col * f_ref[j, 0:1, :]
                so_ref[j, pl.ds(r0, stripe), :] = new
                return tuple(a + new * f_ref[j, 2 + h:3 + h, :] for h, a in enumerate(acc))

            acc = jax.lax.fori_loop(
                0, D, diagonal, tuple(jnp.zeros((stripe, d), jnp.float32) for _ in range(group)))
            out = jnp.zeros((stripe, d), jnp.float32)
            for h, a in enumerate(acc):  # head h's read-out in lane h
                out = jnp.where(lane == h, jnp.sum(a, axis=1, keepdims=True), out)
            num_ref[pl.ds(r0, stripe), :] = out
            return carry

        jax.lax.fori_loop(0, d_v // stripe, rows, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_decode(q, k, v, lam, state, norm, layer, active, *, interpret=None):
    """One decode step of one retention layer over every slot: shapes as
    :func:`retention_step`, ``layer`` a traced index into the layer axis
    (under a scan a sliced ``state[:, layer]`` would be copied whole each
    iteration).  State and normaliser are updated in place where the caller
    donates them (they are aliased to the kernel's outputs); the output rows of
    slots that are not ``active`` are 0 and their state and normaliser are not
    touched.  One kernel, named ``retention_decode`` in the profiler's trace.
    d = d_v = 128 lanes on the chip, at most 6 query heads a K/V head."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, H, d = q.shape
    G, d_v = k.shape[1], v.shape[-1]
    group, D = H // G, diagonals(d)
    if d != d_v or (not interpret and d != 128) or H % G or d_v % min(_STRIPE, d_v) or group > 6:
        raise ValueError(
            f"retention_decode wants d = d_v (= 128 on the chip) and whole groups of at "
            f"most 6, got {d}, {d_v}, {H} heads over {G}")
    f32 = lambda a: a.astype(jnp.float32)
    # One [8, d] tile a slot and K/V head: k, the decay, the group's q, zeros.
    x = jnp.concatenate(
        [f32(k)[:, :, None] * d ** -0.25,
         jnp.broadcast_to(jnp.exp(f32(lam))[:, :, None, None], (S, G, 1, d)),
         f32(q).reshape(S, G, group, d) * d ** -0.25,
         jnp.zeros((S, G, 6 - group, d), jnp.float32)], axis=2)
    v_cols = jnp.broadcast_to(f32(v)[..., None], (S, G, d_v, d))
    # The kernel visits the active slots only, in slot order; the grid's
    # steps past them name the last block again, which copies nothing.
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    count = jnp.sum(active, dtype=jnp.int32).reshape(1)

    def at(i, g, order, count, layer):
        last = jnp.maximum(count[0] - 1, 0)
        return order[jnp.minimum(i, last)], jnp.where(i < count[0], g, G - 1)

    def held(rank):  # a block of a slot-axis leaf: [slot, layer, head, ...]
        def index(i, g, order, count, layer):
            slot, head = at(i, g, order, count, layer)
            return (slot, layer[0], head) + (0,) * rank
        return index

    tile = lambda rows: pl.BlockSpec((None, None, rows, d), lambda *a: at(*a) + (0, 0))
    state_block = pl.BlockSpec((None, None, None, D, d_v, d), held(3))
    norm_block = pl.BlockSpec((None, None, None, norm.shape[-2], d), held(2))
    with jax.named_scope("retention_decode"):
        num, den, state, norm = pl.pallas_call(
            functools.partial(_retention_decode_kernel, group=group),
            out_shape=[jax.ShapeDtypeStruct((S, G, d_v, d), jnp.float32),
                       jax.ShapeDtypeStruct((S, G, 8, d), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(S, G),
                in_specs=[tile(8), tile(d_v), state_block, norm_block],
                out_specs=[tile(d_v), tile(8), state_block, norm_block],
                scratch_shapes=[pltpu.VMEM((D, 8, d), jnp.float32)],
            ),
            # Operands 5 and 6 of the call (after the three prefetched scalars,
            # x and the values) are state and normaliser: outputs 2 and 3.
            input_output_aliases={5: 2, 6: 3},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="retention_decode",
        )(order, count, jnp.asarray(layer, jnp.int32).reshape(1), x, v_cols, state, norm)
    # [S, G, d_v, lanes] and [S, G, lanes]: head h of the group in lane h
    o = num[..., :group] / (den[:, :, 0, None, :group] + EPS)
    o = o.transpose(0, 1, 3, 2).reshape(S, H, d_v)
    return jnp.where(active[:, None, None], o, 0.0), state, norm


# --------------------------------------------------------------------------
# prefill: the quadratic sum in blocks, and the state after the last position
# --------------------------------------------------------------------------


def _retention_prefill_kernel(i_ref, j_ref, q_ref, k_ref, v_ref, cq_ref, ck_ref, o_ref, acc_ref, den_ref,
                              *, block):
    """Query block i against key block j <= i, the pair this grid step's
    entry of the prefetched tables names: a query block's pairs follow one
    another from j = 0 to j = i."""
    step = pl.program_id(1)
    i, j = i_ref[step], j_ref[step]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    s = jax.lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Bq, Bk]
    row = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    decay = jnp.exp(jnp.minimum(cq_ref[...] - ck_ref[...], 0.0))
    a = jnp.where(row >= col, s * s * decay, 0.0)
    acc_ref[...] += jnp.dot(a.astype(v_ref.dtype), v_ref[...],
                            preferred_element_type=jnp.float32)
    den_ref[...] += jnp.sum(a, axis=1, keepdims=True)

    @pl.when(j == i)
    def _():
        o_ref[...] = acc_ref[...] / (den_ref[...] + EPS)


def _retention_state_kernel(k_ref, v_ref, s_ref, z_ref):
    """A block of positions into a K/V head's state and normaliser: k [block,
    d] carries the scale and the square root of what is left of its position
    at the end; v [d_v, block]."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    D, d = s_ref.shape[0], z_ref.shape[1]
    k, v = k_ref[...], v_ref[...]

    def diagonal(j, carry):
        f = k * pltpu.roll(k, (d - j) % d, 1) * _diagonal_weight(j, D)  # [block, d]
        s_ref[j] += jnp.dot(v, f, precision=_HIGHEST, preferred_element_type=jnp.float32)
        z_ref[pl.ds(j, 1), :] += jnp.sum(f, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, D, diagonal, 0)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def retention_prefill(q, k, v, lam, *, length=None, dtype=jnp.bfloat16, interpret=None):
    """The first form over a whole sequence from an empty state.  q: [T, H,
    d]; k, v: [T, G, d]; lam: [T, G]; T a power of two or a multiple of 256.
    ``length`` (int32 scalar, traced, 0 < length <= T; None: T): the first
    ``length`` positions are real and the rest a bucket's padding, which is
    not read (whatever lies there, NaN too) and moves neither state nor
    normaliser: both kernels' grids end with the last block of 256 positions
    that holds a real one, so a block wholly past ``length`` is neither
    copied nor computed, and ITS ROWS OF ``o`` MEAN NOTHING (they are
    whatever the buffer held: the caller keeps rows below ``length`` only, a
    prefill the one at ``length - 1``).  Without ``length`` a position with
    ``k`` 0 and ``lam`` 0 moves nothing either.  Products in ``dtype``, sums
    in float32.  Returns (o [T, H, d_v] float32, the state [G, D, d_v, d] and
    the normaliser [G, norm_rows, d] after position ``length - 1``,
    float32)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, H, d = q.shape
    G = k.shape[1]
    block = min(_PREFILL_BLOCK, T)
    if T % block or H % G:
        raise ValueError(f"retention_prefill: {T} positions do not tile by {block}")
    group, n = H // G, T // block
    lam = lam.astype(jnp.float32)
    live = n  # blocks that hold a real position: the grids' bounds, data where the length is
    if length is not None:
        real = jnp.arange(T) < length  # inside the last live block the padding holds still
        k, v = (jnp.where(real[:, None, None], x, 0.0) for x in (k, v))
        lam = jnp.where(real[:, None], lam, 0.0)
        live = (jnp.clip(jnp.asarray(length, jnp.int32), 1, T) - 1) // block + 1
    cum = jnp.cumsum(lam, axis=0).T  # [G, T]: the log-decay from the start, inclusive
    heads = lambda x: x.transpose(1, 0, 2)
    qs = heads(q.astype(jnp.float32) * d ** -0.5).astype(dtype)  # the 1 / sqrt(d) inside the square
    # The causal triangle's block pairs, query block by query block: the first
    # live (live + 1) / 2 of them are the live query blocks', and the grid
    # visits those and no other (no step for a pair above the diagonal).
    query_of, key_of = (jnp.asarray(x, jnp.int32) for x in zip(
        *((i, j) for i in range(n) for j in range(i + 1))))
    q_block = pl.BlockSpec((None, block, d), lambda h, s, i, j: (h, i[s], 0))
    kv_block = pl.BlockSpec((None, block, d), lambda h, s, i, j: (h // group, j[s], 0))
    with jax.named_scope("retention_prefill"):
        o = pl.pallas_call(
            functools.partial(_retention_prefill_kernel, block=block),
            out_shape=jax.ShapeDtypeStruct((H, T, d), jnp.float32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(H, live * (live + 1) // 2),
                in_specs=[
                    q_block, kv_block, kv_block,
                    pl.BlockSpec((None, block, 1), lambda h, s, i, j: (h // group, i[s], 0)),
                    pl.BlockSpec((None, 1, block), lambda h, s, i, j: (h // group, 0, j[s])),
                ],
                out_specs=q_block,
                scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                                pltpu.VMEM((block, 1), jnp.float32)],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="retention_prefill",
        )(query_of, key_of, qs, heads(k).astype(dtype), heads(v).astype(dtype),
          cum[:, :, None], cum[:, None, :])
    # phi is of degree 2: the square root of what is left of position j at
    # the end, put into k, leaves phi(k_j) times all of it.
    left = jnp.exp(0.5 * (cum[:, -1:] - cum))  # [G, T]
    ks = heads(k).astype(jnp.float32) * (d ** -0.25 * left[..., None])
    D = diagonals(d)
    with jax.named_scope("retention_prefill"):
        state, norm = pl.pallas_call(
            _retention_state_kernel,
            out_shape=[jax.ShapeDtypeStruct((G, D, d, d), jnp.float32),
                       jax.ShapeDtypeStruct((G, norm_rows(d), d), jnp.float32)],
            grid=(G, live),
            in_specs=[pl.BlockSpec((None, block, d), lambda g, t: (g, t, 0)),
                      pl.BlockSpec((None, d, block), lambda g, t: (g, 0, t))],
            out_specs=[pl.BlockSpec((None, D, d, d), lambda g, t: (g, 0, 0, 0)),
                       pl.BlockSpec((None, norm_rows(d), d), lambda g, t: (g, 0, 0))],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="retention_prefill_state",
        )(ks, heads(v).astype(jnp.float32).transpose(0, 2, 1))
    return o.transpose(1, 0, 2), state, norm
