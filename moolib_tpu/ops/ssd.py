"""The state-space recurrence of Mamba-2 (structured state-space duality,
arXiv:2405.21060): a SCALAR decay a head, as a decode kernel and as a chunked
prefill whose work is matrix products.

Per head ``h`` of ``H``, with a state ``S`` [P, N] in float32 (``P`` the
head's channels, ``N`` the states: 64 and 128 as published), a step ``dt_t``
(> 0, after its softplus, one a head), the decay's rate ``A_h`` (< 0, one a
head) and ``B_t``, ``C_t`` [N] shared by every head (one group)::

    S_t = exp(dt_t A_h) S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t + D_h x_t

:func:`ssd_reference` is the one definition of it, a token at a time.  Both
kernels and :func:`ssd_step` hand back ``S_t C_t`` alone: ``D_h x_t`` is one
multiply-add that the caller's gate fuses.

**The state's layout** is ``[.., H, P, N]``: a head's channels on sublanes,
the states on the 128 lanes, whole (8, 128) tiles and no padding (4 MB a
layer a slot at 128 heads).  ``B_t`` and ``C_t`` then broadcast along sublanes
as the row vectors they arrive as, and the read-out is a sum over lanes.

:func:`ssd_decode` is one Pallas (Mosaic) call a layer for a decode step,
named ``ssd_decode``.  The state leaf ``[slots, layers, H, P, N]`` stays in HBM
and is input-output aliased, the layer an index into it (nothing of it is
sliced out under a scan over layers).  The kernel walks the ACTIVE rows alone
(their order and count in SMEM, as ``ssm_decode`` has them) and moves a slot's
state of the layer through VMEM in **pieces** of :data:`_PIECE_HEADS` heads (1
MB as published), :data:`_BUFFERS` buffers deep: while a piece is computed the
pieces after it are on their way in and the one before it on its way out, so
the loop has ``live slots x pieces`` turns and a slot nobody holds is neither
read nor written.

With the states on the lanes a head's channels are a COLUMN of its tile: the
input ``dt x`` has to be broadcast along lanes and the read-out summed over
them, and one such step a (8, 128) tile (seven rolls each) kept the kernel at
42% of its HBM bound (24 us a live slot a layer; PERF.md, PR 56).  So what a
slot brings and takes away is PACKED, a channel a lane: a group of ``8 N / P``
heads (16 as published) shares one (8, N) tile of ``dt x`` and one of ``y``
(:func:`_packed`); :func:`_spread` unfolds the packed tile into the group's
tiles, each with its eight channels in every lane, by a roll and a select a
tile made, and :func:`_gather` folds the read-outs' tiles back into one packed
tile by two rolls and a select a pair (one roll where the shift is half the
lanes, the widest level of either): three rolls a tile of state where
fourteen were.  The decay ``exp(dt A)`` of each head arrives along a row of
lanes (``[H, N]``: a head's row broadcasts along sublanes) and ``B | C`` as two
rows.  :func:`ssd_step` is the same step in ``jax.numpy``, which the tests hold
the kernel to.

:func:`ssd_prefill` is the prefill, one Pallas (Mosaic) call a layer named
``ssd_prefill``: the chunked form, whose work is matrix products.  Per chunk
of ``Q`` positions and head, with ``s_t`` the running sum of ``dt_r A`` inside
the chunk (every exponent is a difference ``s_t - s_r`` with ``t >= r``, so
<= 0) and ``S`` the state the chunk starts from::

    G = C B^T                                     [Q, Q], ONE for every head
    L[t, r] = exp(s_t - s_r)  (t >= r),  0 (t < r)
    Y = (L o G) (dt x) + exp(s_t) C_t S^T         [Q, P]
    S' = exp(s_Q) S + sum_r exp(s_Q - s_r) (dt_r x_r) B_r^T

The grid runs over blocks of :data:`_PREFILL_HEADS` heads (parallel: heads
meet nowhere in the recurrence) and over the chunks (sequential; the state
stays in the output's VMEM block from the first chunk of a head block to the
last and is handed back).  Heads are taken in PAIRS: two heads' ``P = 64``
channels are the 128 lanes of one tile of ``x`` and of ``y``, their states
stacked are the 128 rows of one product against ``C`` and of one against
``B``, and the product with ``L o G`` takes the pair's tile whole and keeps
each head's half.  ``L``, ``L o G`` and ``G`` live and die in VMEM: nothing of
size ``[T, H, P, N]`` or ``[T, T]`` exists.  All products are float32 at the
highest precision on the MXU, accumulated in float32.  The running sums ``s``
arrive from XLA (a cumulative sum inside each chunk of ``dt A``, once as ``[T,
H]`` for the columns and once transposed for the rows).  The result does not
depend on the chunk: :data:`CHUNK` is this kernel's own (128: half the
exponentials and three quarters of the products of the published 256).

Two inputs besides, both data: ``length``, the count of real positions: a
chunk that starts at or past it is not computed and its inputs are not copied
(the index map names the last live chunk again), its rows of ``y`` are 0;
from ``length`` on inside the last live chunk ``dt = 0``, which makes the
decay 1 and the input 0 and so holds the state still.  ``state``: what the
first chunk starts from (default 0), so a prompt can be prefilled in pieces.

Both kernels lower through Mosaic on ``tpu`` and run in Pallas interpret mode
on ``cpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # positions a grid step of the prefill; a shorter sequence is one chunk
_PREFILL_HEADS = 16  # heads a grid step of the prefill, in pairs: 512 KB of state
_PIECE_HEADS = 32  # heads of a slot's state that move through VMEM at once: 1 MB
_BUFFERS = 4  # pieces in VMEM: one computed, two on their way in, one on its way out
_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_reference(x, dt, A, B, C, D, state):
    """The recurrence, a token at a time.  x: [T, H, P]; dt: [T, H]; A, D: [H];
    B, C: [T, N]; state: [H, P, N] float32.  Returns (y [T, H, P], the state
    after the last token)."""

    def step(S, xs):
        x, dt, B, C = xs
        S = jnp.exp(dt * A)[:, None, None] * S + (dt[:, None] * x)[:, :, None] * B
        return S, jnp.sum(S * C, axis=-1) + D[:, None] * x

    f32 = lambda a: a.astype(jnp.float32)
    state, y = jax.lax.scan(step, f32(state), (f32(x), f32(dt), f32(B), f32(C)))
    return y, state


# --------------------------------------------------------------------------
# decode: one token a slot, the state read once and written once, in place
# --------------------------------------------------------------------------


def ssd_step(x, dt, A, B, C, state, layer, active):
    """:func:`ssd_decode` in ``jax.numpy``: one token a slot.  x: [S, H, P];
    dt: [S, H]; A: [H]; B, C: [S, N]; state: [S, L, H, P, N] float32; active:
    [S] bool.  Returns (``S_t C_t`` [S, H, P] without the ``D x`` term, 0 for
    slots that are not active; the state with ``layer``'s rows of the active
    slots advanced)."""
    old = state[:, layer]
    new = (jnp.exp(dt * A)[:, :, None, None] * old
           + (dt[:, :, None] * x)[..., None] * B[:, None, None, :])
    y = jnp.sum(new * C[:, None, None, :], axis=-1)
    return (jnp.where(active[:, None, None], y, 0.0),
            state.at[:, layer].set(jnp.where(active[:, None, None, None], new, old)))


def _lanes(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _spread(tile, shifts):
    """One tile [8, L] -> ``2 ** len(shifts)`` tiles: tile v holds, in every
    lane l, what ``tile`` held in the lane that is l but for the bits of
    ``shifts``, which are v's (the lowest shift is v's lowest bit).  Two rolls
    and two selects a tile split; one roll where the shift is half the lanes,
    which is its own inverse (the widest level: half of all the rolls)."""
    L = tile.shape[1]
    out = [tile]
    for s in shifts:
        low = (_lanes(tile.shape) // s) % 2 == 0
        up = [pltpu.roll(t, s, 1) for t in out]
        down = up if 2 * s == L else [pltpu.roll(t, L - s, 1) for t in out]
        out = ([jnp.where(low, t, r) for t, r in zip(out, up)]
               + [jnp.where(low, r, t) for t, r in zip(out, down)])
    return out


def _gather(tiles, shifts):
    """``2 ** len(shifts)`` tiles [8, L] -> one: the sums over the lanes that
    differ in the bits of ``shifts`` alone, tile v's in the lanes whose bits
    of ``shifts`` are v's (the highest shift is v's highest bit):
    :func:`_spread` backwards with a sum.  Two rolls and a select a pair; one
    roll and two selects where the shift is half the lanes."""
    L = tiles[0].shape[1]
    for s in shifts:
        low = (_lanes(tiles[0].shape) // s) % 2 == 0
        half = len(tiles) // 2
        pairs = list(zip(tiles[:half], tiles[half:]))
        if 2 * s == L:  # what either tile adds from s lanes away, in one roll
            tiles = [jnp.where(low, x, y) + pltpu.roll(jnp.where(low, y, x), s, 1)
                     for x, y in pairs]
        else:
            tiles = [jnp.where(low, x + pltpu.roll(x, L - s, 1), y + pltpu.roll(y, s, 1))
                     for x, y in pairs]
    tile, = tiles
    return tile


def _ssd_decode_kernel(row_ref, count_ref, layer_ref, d_hbm, a_hbm, bc_hbm, s_hbm,
                       y_hbm, so_hbm, s_buf, d_buf, a_buf, bc_buf, y_buf, sem, *, pieces, heads):
    # ``so_hbm`` is the state's leaf again (output 1 is aliased to ``s_hbm``): a
    # piece is read through the one name and written through the other, once.
    # Turn t is piece ``t % pieces`` of slot ``row_ref[t // pieces]``.
    hp, P, L = s_buf.shape[1:]
    groups = a_buf.shape[1] // pieces  # of a piece: a group is one packed tile of inputs and of y
    per_sub = L // P  # heads a sub-group: its L / 8 tiles are the lanes' high bits
    wide = [L >> i for i in range(1, L.bit_length() - 3)]  # L / 2 .. 8: a sub-group's tiles
    count, layer = count_ref[0], layer_ref[0]
    total = count * pieces

    def piece(t, out):
        slot, at = row_ref[t // pieces], pl.ds((t % pieces) * hp, hp)
        buf = t % _BUFFERS
        if out:
            return pltpu.make_async_copy(
                s_buf.at[buf], so_hbm.at[slot, layer, at], sem.at[1, buf])
        return pltpu.make_async_copy(s_hbm.at[slot, layer, at], s_buf.at[buf], sem.at[0, buf])

    def brought(i):  # what slot i brings to the step: decays, inputs, B | C
        slot, buf = row_ref[i], i % 2
        return [pltpu.make_async_copy(hbm.at[slot], vmem.at[buf], sem.at[2 + j, buf])
                for j, (hbm, vmem) in enumerate(((d_hbm, d_buf), (a_hbm, a_buf), (bc_hbm, bc_buf)))]

    def taken(i):  # what it takes away
        return pltpu.make_async_copy(y_buf.at[i % 2], y_hbm.at[row_ref[i]], sem.at[5, i % 2])

    @pl.when(count > 0)
    def _():
        for copy in brought(0):
            copy.start()

    for t in range(_BUFFERS - 1):
        @pl.when(t < total)
        def _():
            piece(t, False).start()

    def turn(t, carry):
        i, k = t // pieces, t % pieces
        at, buf = i % 2, t % _BUFFERS

        @pl.when(k == 0)
        def _():
            for copy in brought(i):
                copy.wait()

            @pl.when(i + 1 < count)
            def _():
                for copy in brought(i + 1):
                    copy.start()

            @pl.when(i >= 2)
            def _():  # this slot's buffer of y is still on its way out
                taken(i - 2).wait()

        piece(t, False).wait()
        b, c = bc_buf[at, 0:1, :], bc_buf[at, 1:2, :]  # [1, L] each
        for g in range(groups):
            # One packed tile brings the inputs of the group's heads, a channel
            # a lane; a sub-group's tiles are spread from it until each holds
            # its eight channels in every lane, the state's tiles advance, and
            # their read-outs are gathered back into one packed tile of y.
            partial = []
            for j, sub in enumerate(_spread(a_buf[at, k * groups + g], (1, 2, 4))):
                columns = _spread(sub, wide[::-1])
                tiles = []
                for w, column in enumerate(columns):
                    head, rows = g * 8 * per_sub + j * per_sub + w // (P // 8), pl.ds(w % (P // 8) * 8, 8)
                    if head >= heads:  # a group's tail past the last head: nothing to advance
                        tiles.append(jnp.zeros_like(column))
                        continue
                    S = d_buf[at, pl.ds(k * hp + head, 1), :] * s_buf[buf, head, rows, :] + column * b
                    s_buf[buf, head, rows, :] = S
                    tiles.append(S * c)
                partial.append(_gather(tiles, wide))
            y_buf[at, k * groups + g] = _gather(partial, (4, 2, 1))
        piece(t, True).start()

        @pl.when(k == pieces - 1)
        def _():
            taken(i).start()

        @pl.when(t >= 1)
        def _():  # the buffer the next piece in comes into is still on its way out
            piece(t - 1, True).wait()

        @pl.when(t + _BUFFERS - 1 < total)
        def _():
            piece(t + _BUFFERS - 1, False).start()

        return carry

    jax.lax.fori_loop(0, total, turn, 0)

    @pl.when(count > 0)
    def _():
        piece(total - 1, True).wait()
        taken(count - 1).wait()

    @pl.when(count > 1)
    def _():
        taken(count - 2).wait()


def _packed(x, group: int, P: int, L: int):
    """x [S, H, P] as the kernel's packed tiles [S, groups, 8, L]: a group of
    ``group`` = 8 L / P heads a tile, head ``j L / P + h`` of it and channel ``8
    b + s`` at sublane s, lane ``(h P / 8 + b) 8 + j`` (what :func:`_spread` and
    :func:`_gather` pair up); heads past the last are 0."""
    S, H, _ = x.shape
    groups = -(-H // group)
    x = jnp.pad(x, ((0, 0), (0, groups * group - H), (0, 0)))
    x = x.reshape(S, groups, 8, L // P, P // 8, 8)  # .., j, h, b, s
    return x.transpose(0, 1, 5, 3, 4, 2).reshape(S, groups, 8, L)


def _unpacked(y, H: int, P: int):
    """:func:`_packed` backwards: [S, groups, 8, L] -> [S, H, P]."""
    S, groups, _, L = y.shape
    y = y.reshape(S, groups, 8, L // P, P // 8, 8)  # .., s, h, b, j
    return y.transpose(0, 1, 5, 3, 4, 2).reshape(S, groups * 8 * L // P, P)[:, :H]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode(x, dt, A, B, C, state, layer, active, *, interpret=None):
    """One decode step of one layer over every slot: shapes as
    :func:`ssd_step`, ``layer`` a traced index into the state's layer axis
    (under a scan a sliced ``state[:, layer]`` would be copied whole each
    iteration).  The state is updated in place where the caller donates it (it
    is aliased to the kernel's output); the rows of ``y`` of slots that are not
    ``active`` are 0 and their state is neither read nor written.  One kernel,
    named ``ssd_decode`` in the profiler's trace.  The states a power of two
    of at least 8 lanes, a head's channels a multiple of 8 that divides them."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, H, P = x.shape
    N = B.shape[-1]
    if state.shape[0] != S or state.shape[2:] != (H, P, N):
        raise ValueError(
            f"ssd_decode wants a state [{S} slots, layers, {H}, {P}, {N}], got {state.shape}")
    group = 8 * N // P  # heads that share a packed tile
    if N < 8 or N & (N - 1) or P % 8 or N % P:
        raise ValueError(f"ssd_decode: {P} channels a head do not pack into tiles of {N} lanes")
    # whole groups a piece; fewer heads than a group, or heads that are not whole pieces: one piece
    hp = max(group, _PIECE_HEADS // group * group)
    hp = hp if H % hp == 0 else H
    f32 = lambda a: a.astype(jnp.float32)
    x, dt = f32(x), f32(dt)
    decay = jnp.broadcast_to(jnp.exp(dt * f32(A))[:, :, None], (S, H, N))
    inputs = _packed(dt[:, :, None] * x, group, P, N)
    bc = jnp.stack([f32(B), f32(C)], axis=1)  # [S, 2, N]
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    count = jnp.sum(active, dtype=jnp.int32).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    with jax.named_scope("ssd_decode"):
        y, state = pl.pallas_call(
            functools.partial(_ssd_decode_kernel, pieces=H // hp, heads=hp),
            out_shape=[jax.ShapeDtypeStruct(inputs.shape, jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            in_specs=[smem, smem, smem, hbm, hbm, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, hp, P, N), jnp.float32),
                pltpu.VMEM((2, H, N), jnp.float32),
                pltpu.VMEM((2,) + inputs.shape[1:], jnp.float32),
                pltpu.VMEM((2, 2, N), jnp.float32),
                pltpu.VMEM((2,) + inputs.shape[1:], jnp.float32),
                pltpu.SemaphoreType.DMA((6, _BUFFERS)),
            ],
            # Operand 6 of the call is the state, and comes back as output 1.
            input_output_aliases={6: 1},
            interpret=interpret,
            name="ssd_decode",
        )(order, count, jnp.asarray(layer, jnp.int32).reshape(1), decay, inputs, bc, state)
    # a slot the loop never visited left its row of y as it was: anything
    return jnp.where(active[:, None, None], _unpacked(y, H, P), 0.0), state


# --------------------------------------------------------------------------
# prefill: chunks of time as matrix products, the state resident, one kernel a layer
# --------------------------------------------------------------------------


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _ssd_prefill_kernel(len_ref, x_ref, dt_ref, s_ref, st_ref, b_ref, c_ref, h_ref,
                        y_ref, ho_ref, *, chunk, heads, P):
    n = pl.program_id(1)
    Q = chunk

    @pl.when(n == 0)
    def _():
        ho_ref[...] = h_ref[...]

    @pl.when(n * Q >= len_ref[0])
    def _():  # bucket padding: nothing was copied for it, nothing is computed
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n * Q < len_ref[0])
    def _():
        B, C = b_ref[...], c_ref[...]  # [Q, N]
        G = _dot(C, B, _NT)  # [Q, Q]: C_t . B_r, the same for every head
        causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
                  >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
        left = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * P), 1) < P  # the pair's first head
        upper = jax.lax.broadcasted_iota(jnp.int32, (2 * P, 1), 0) < P
        for j in range(0, heads, 2):
            # two heads: 2 P lanes of x and of y, 2 P rows of the state
            lanes, rows = slice(j * P, (j + 2) * P), slice(j * P, (j + 2) * P)
            s = [s_ref[:, i:i + 1] for i in (j, j + 1)]  # [Q, 1]: s_t down the sublanes
            last = [st_ref[i:i + 1, Q - 1:Q] for i in (j, j + 1)]  # [1, 1]: s_Q
            both = lambda a, b: jnp.where(left, a, b)  # a column a head -> [Q, 2 P]
            xdt = x_ref[:, lanes] * both(dt_ref[:, j:j + 1], dt_ref[:, j + 1:j + 2])
            within = []
            for i in (0, 1):
                decay = jnp.exp(jnp.where(causal, s[i] - st_ref[j + i:j + i + 1, :], -1e30))
                within.append(_dot(decay * G, xdt, _NN))  # the head's half is kept
            S = ho_ref[rows, :]  # [2 P, N]
            carried = _dot(C, S, _NT) * both(jnp.exp(s[0]), jnp.exp(s[1]))
            y_ref[:, lanes] = both(within[0], within[1]) + carried
            reach = both(jnp.exp(last[0] - s[0]), jnp.exp(last[1] - s[1]))  # to the chunk's end
            ho_ref[rows, :] = (jnp.where(upper, jnp.exp(last[0]), jnp.exp(last[1])) * S
                               + _dot(xdt * reach, B, _TN))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_prefill(x, dt, A, B, C, *, length=None, state=None, chunk=None, interpret=None):
    """The recurrence over a whole sequence in chunks, one kernel named
    ``ssd_prefill`` (module docstring).  x: [T, H, P]; dt: [T, H]; A: [H]; B,
    C: [T, N].  ``length`` (int32 scalar, traced; default T): the first
    ``length`` positions are real, the rest a bucket's padding, which leaves
    the state as it is; a chunk wholly past it is neither copied nor computed
    and its rows of ``y`` are 0.  ``state`` [H, P, N]: what the first chunk
    starts from (default 0).  ``chunk``: positions a grid step (default
    :data:`CHUNK`; the result does not depend on it).  Returns (``S_t C_t``
    [T, H, P] without the ``D x`` term, the state after position ``length -
    1`` [H, P, N]), float32.  T is padded here to whole chunks (to a multiple
    of 8 where it is shorter than one); a bucket of the serving engine already
    is.  H even: heads are taken in pairs."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, H, P = x.shape
    N = B.shape[-1]
    hb = max(h for h in range(2, _PREFILL_HEADS + 1, 2) if H % h == 0) if H % 2 == 0 else 0
    if not hb or (not interpret and ((2 * P) % 128 or N % 128 or (hb != H and hb % 8))):
        raise ValueError(
            f"ssd_prefill wants pairs of heads whose channels are whole lanes, got {H} heads "
            f"of {P} channels and {N} states")
    chunk = CHUNK if chunk is None else chunk
    chunk = chunk if T >= chunk else -(-T // 8) * 8
    pad = -T % chunk
    Tp, chunks = T + pad, (T + pad) // chunk
    f32 = lambda a: a.astype(jnp.float32)
    rows = lambda a: jnp.pad(f32(a), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    length = jnp.clip(jnp.asarray(T if length is None else length, jnp.int32), 0, T)
    # from ``length`` on the step is 0: the decay 1, the input 0
    dt = jnp.where(jnp.arange(Tp)[:, None] < length, rows(dt), 0.0)
    sums = jnp.cumsum((dt * f32(A)).reshape(chunks, chunk, H), axis=1).reshape(Tp, H)
    by_block = lambda a: a.reshape(Tp, H // hb, hb).transpose(1, 0, 2)  # [H / hb, Tp, hb]
    start = (jnp.zeros((H, P, N), jnp.float32) if state is None else f32(state)).reshape(H * P, N)
    length = length.reshape(1)

    def at(n, length):  # past the last live chunk the grid names it again: no copy
        return jnp.minimum(n, jnp.maximum((length[0] - 1) // chunk, 0))

    wide = pl.BlockSpec((chunk, hb * P), lambda h, n, length: (at(n, length), h))
    column = pl.BlockSpec((None, chunk, hb), lambda h, n, length: (h, at(n, length), 0))
    shared = pl.BlockSpec((chunk, N), lambda h, n, length: (at(n, length), 0))
    held = pl.BlockSpec((hb * P, N), lambda h, n, length: (h, 0))
    with jax.named_scope("ssd_prefill"):
        y, last = pl.pallas_call(
            functools.partial(_ssd_prefill_kernel, chunk=chunk, heads=hb, P=P),
            out_shape=[jax.ShapeDtypeStruct((Tp, H * P), jnp.float32),
                       jax.ShapeDtypeStruct((H * P, N), jnp.float32)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(H // hb, chunks),
                in_specs=[wide, column, column,
                          pl.BlockSpec((hb, chunk), lambda h, n, length: (h, at(n, length))),
                          shared, shared, held],
                out_specs=[pl.BlockSpec((chunk, hb * P), lambda h, n, length: (n, h)), held],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="ssd_prefill",
        )(length, rows(x).reshape(Tp, H * P), by_block(dt), by_block(sums), sums.T,
          rows(B), rows(C), start)
    return y[:T].reshape(T, H, P), last.reshape(H, P, N)
