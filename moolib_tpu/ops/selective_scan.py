"""The selective state-space scan of Mamba-1 (arXiv:2312.00752): a diagonal
recurrence with an input-dependent step, as a decode kernel and as a chunked
prefill.

Per channel ``c`` of ``C`` and state ``n`` of ``N`` (16 as published), with a
state ``h`` [C, N] in float32, a step ``dt_t`` [C] (> 0, after its softplus),
the decay's rate ``A`` [C, N] (< 0), and ``B_t``, ``C_t`` [N] shared by every
channel::

    h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t
    y_t = h_t C_t + D u_t

:func:`selective_scan_reference` is the one definition of it, a token at a
time.

**The state's layout** is ``[.., N, C]``: the 16 states on sublanes, the
channels on the 128 lanes.  As ``[.., C, 16]`` a float32 leaf is padded
eightfold in HBM (16 of 128 lanes), 2.6 MB a layer a slot and not 0.33.  The
step and the input then broadcast along sublanes as the row vectors they
arrive as, ``B_t`` and ``C_t`` along lanes, and the read-out is a sum over
sublanes.

:func:`ssm_decode` is one Pallas (Mosaic) call a layer for a decode step,
named ``ssm_decode``.  The state leaf ``[slots, layers, N, C]`` stays in HBM
and is input-output aliased; the kernel walks the ACTIVE rows alone (their
order and count in SMEM, as ``kda_decode`` and the paged attention kernel have
them, and beside the order the SLOT of each: a step may run over fewer rows
than the leaf has slots, and then reads what a row brings by row and its state
by slot), copies a slot's ``[N, C]`` block of the layer into one of two VMEM
buffers while the slot before it is computed, updates it there and copies it
back: a slot nobody holds is neither read nor written, and the loop has as
many turns as slots are live (a grid over all slots would pay a grid step
for every empty one, 256 times a layer).  The layer is an index
into the leaf, so nothing of it is sliced out under a scan over layers.  What
a slot brings to the step (``dt``, ``dt u``) and takes away (``y``) is laid as
``[rows, C / 128, 128]``, one contiguous copy a row; ``B_t`` and ``C_t`` of
all rows are one small ``[2 N, rows]`` operand in VMEM, of which a row's
column is taken by a masked sum.  :func:`ssm_step` is the same step in
``jax.numpy``, which the tests hold the kernel to.

:func:`conv_tail_write` is the other thing a Mamba layer's decode step keeps
a slot: the short convolution's TAIL, its last three inputs.  The leaf is
``[slots, layers, 3 C / 128, 128]`` float32: a slot's tail of a layer is one
contiguous block of whole (8, 128) tiles (60 KB at 5,120 channels), tap t in
rows ``t C / 128`` onwards.  (As ``[slots, layers, 3, C]`` the chip laid the 3
outside the tiles and the SLOTS on the sublanes, so one slot's tail was 120
pieces of 512 bytes, and a scatter of 128 slots' tails cost 4.1 ms a step
where rewriting all 256 cost 1.3: PERF.md, PR 52.)  The step reads the tails
it needs in XLA (a slice of the layer, or a gather of the rows' slots) and
this kernel, named ``conv_tail_write``, writes the ACTIVE rows' new tails
back where they lie: one HBM-to-HBM copy a row, eight in flight, the leaf
aliased; a slot nobody holds, or no row names, is not touched.
:func:`tail_step` is the same in ``jax.numpy``.

:func:`ssm_prefill` is the prefill, one Pallas (Mosaic) call a layer named
``ssm_prefill``: the grid runs over blocks of channels (parallel: channels do
not meet in the recurrence) and over chunks of :data:`CHUNK` positions
(sequential; the state stays in the output's VMEM block from the first chunk
of a channel block to the last, and in vector registers inside a chunk).
``exp(dt A)`` is computed in VMEM a position at a time and never written to
HBM; ``D u`` and the gate ``silu(z)`` are applied to the chunk's ``y`` before
it leaves.  ``B`` and ``C`` arrive transposed, ``[N, T]``, so that a
position's column broadcasts along the lanes.  Two inputs besides, both data:
``length``, the count of real positions (a scalar in SMEM): a chunk that
starts at or past it is not computed and its inputs are not copied (the index
map names the last live chunk again), its rows of ``y`` are 0; inside the
last live chunk the positions from ``length`` on get ``dt = 0``, which makes
the decay 1 and the input 0 and so holds the state still (their rows of ``y``
read that held state and mean nothing).  ``state``: what
the first chunk starts from (default 0), so a prompt can be prefilled in
pieces.

Both kernels are elementwise work for the VPU and the EUP (one exponential a
position, channel and state) and nothing for the MXU.  They lower through
Mosaic on ``tpu`` and run in Pallas interpret mode on ``cpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # positions a grid step of the prefill; a shorter sequence is one chunk
LANES = 128  # a vector register's lanes: the channels come in tiles of them
_PREFILL_CHANNELS = 512  # channels a grid step: the state of a block is 8 vector registers


def selective_scan_reference(u, dt, A, B, C, D, state):
    """The recurrence, a token at a time.  u, dt: [T, C]; A: [C, N]; B, C:
    [T, N]; D: [C]; state: [C, N] float32.  Returns (y [T, C], the state
    after the last token)."""

    def step(h, x):
        u, dt, B, C = x
        h = jnp.exp(dt[:, None] * A) * h + (dt * u)[:, None] * B[None, :]
        return h, jnp.sum(h * C[None, :], axis=-1) + D * u

    f32 = lambda a: a.astype(jnp.float32)
    state, y = jax.lax.scan(step, f32(state), (f32(u), f32(dt), f32(B), f32(C)))
    return y, state


# --------------------------------------------------------------------------
# decode: one token a slot, the state read once and written once, in place
# --------------------------------------------------------------------------


def ssm_step(u, dt, A, B, C, state, layer, active, slots=None):
    """:func:`ssm_decode` in ``jax.numpy``: one token a row.  u, dt: [R, C];
    A: [N, C] (transposed, as the state is: module docstring); B, C: [R, N];
    state: [S, L, N, C] float32; active: [R] bool; slots: [R] int32, the slot
    of each row, distinct (None: row i is slot i).  Returns (y [R, C] without
    the ``D u`` term, 0 for rows that are not active; the state with
    ``layer``'s rows of the active rows' slots advanced)."""
    slots = jnp.arange(u.shape[0]) if slots is None else slots
    h = state[slots, layer]
    new = jnp.exp(dt[:, None, :] * A) * h + (dt * u)[:, None, :] * B[:, :, None]
    y = jnp.sum(new * C[:, :, None], axis=1)
    keep = active[:, None, None]
    return (jnp.where(active[:, None], y, 0.0),
            state.at[slots, layer].set(jnp.where(keep, new, h)))


def _ssm_decode_kernel(row_ref, slot_ref, count_ref, layer_ref, a_ref, bc_ref, x_hbm, h_hbm,
                       y_hbm, ho_hbm, h_buf, x_buf, y_buf, sem):
    # ``ho_hbm`` is the state's leaf again (output 1 is aliased to ``h_hbm``): a
    # slot is read through the one name and written through the other, once.
    # The i-th turn is row ``row_ref[i]``, whose state is slot ``slot_ref[i]``'s.
    N = a_ref.shape[0]
    R = bc_ref.shape[1]
    tiles = x_buf.shape[2]  # C / 128
    count, layer = count_ref[0], layer_ref[0]

    def copies_in(i, buf):
        r, s = row_ref[i], slot_ref[i]
        return (pltpu.make_async_copy(h_hbm.at[s, layer], h_buf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(x_hbm.at[r], x_buf.at[buf], sem.at[1, buf]))

    def copies_out(i, buf):
        r, s = row_ref[i], slot_ref[i]
        return (pltpu.make_async_copy(h_buf.at[buf], ho_hbm.at[s, layer], sem.at[2, buf]),
                pltpu.make_async_copy(y_buf.at[buf], y_hbm.at[r], sem.at[3, buf]))

    @pl.when(count > 0)
    def _():
        for copy in copies_in(0, 0):
            copy.start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (2 * N, R), 1)

    def slot(i, carry):
        buf = i % 2

        @pl.when(i >= 1)
        def _():  # the other buffer is still on its way out
            for copy in copies_out(i - 1, 1 - buf):
                copy.wait()

        @pl.when(i + 1 < count)
        def _():
            for copy in copies_in(i + 1, 1 - buf):
                copy.start()

        for copy in copies_in(i, buf):
            copy.wait()
        # the row's B | C: its column of [2 N, R], the states down the sublanes
        col = jnp.sum(jnp.where(lane == row_ref[i], bc_ref[...], 0.0), axis=1, keepdims=True)
        b = jnp.broadcast_to(col[:N], (N, LANES))
        c = jnp.broadcast_to(col[N:], (N, LANES))
        for j in range(tiles):  # 128 channels at a time: two registers of state
            at = slice(j * LANES, (j + 1) * LANES)
            dt = x_buf[buf, 0, j:j + 1, :]
            h = jnp.exp(dt * a_ref[:, at]) * h_buf[buf, :, at] + x_buf[buf, 1, j:j + 1, :] * b
            h_buf[buf, :, at] = h
            y_buf[buf, j:j + 1, :] = jnp.sum(h * c, axis=0, keepdims=True)
        for copy in copies_out(i, buf):
            copy.start()
        return carry

    jax.lax.fori_loop(0, count, slot, 0)

    @pl.when(count > 0)
    def _():
        for copy in copies_out(count - 1, (count - 1) % 2):
            copy.wait()


def _turns(name, active, slots, leaf):
    """What a decode kernel walks, for SMEM: the active rows in row order,
    the slot of each (the row itself without ``slots``), and their count."""
    if slots is None and leaf.shape[0] != active.shape[0]:
        raise ValueError(f"{name}: {active.shape[0]} rows over a leaf of {leaf.shape[0]} slots "
                         "need each row's slot")
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    where = order if slots is None else slots.astype(jnp.int32)[order]
    return order, where, jnp.sum(active, dtype=jnp.int32).reshape(1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode(u, dt, A, B, C, state, layer, active, slots=None, *, interpret=None):
    """One decode step of one layer over the step's rows: shapes as
    :func:`ssm_step`, ``layer`` a traced index into the state's layer axis
    (under a scan a sliced ``state[:, layer]`` would be copied whole each
    iteration).  The state is updated in place where the caller donates it (it
    is aliased to the kernel's output); the rows of ``y`` that are not
    ``active`` are 0 and their slots' state is neither read nor written, nor is
    that of a slot no row names.  One kernel, named ``ssm_decode`` in the
    profiler's trace.  C a multiple of the 128 lanes."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    R, channels = u.shape
    N = A.shape[0]
    if channels % LANES or state.shape[2:] != (N, channels):
        raise ValueError(
            f"ssm_decode wants a state [slots, layers, {N}, channels] with the channels a "
            f"multiple of {LANES}, got {state.shape} for {channels} channels")
    order, where, count = _turns("ssm_decode", active, slots, state)
    f32 = lambda a: a.astype(jnp.float32)
    tiles = channels // LANES
    x = jnp.stack([f32(dt), f32(dt) * f32(u)], axis=1).reshape(R, 2, tiles, LANES)
    bc = jnp.concatenate([f32(B), f32(C)], axis=1).T  # [2 N, R]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    with jax.named_scope("ssm_decode"):
        y, state = pl.pallas_call(
            _ssm_decode_kernel,
            out_shape=[jax.ShapeDtypeStruct((R, tiles, LANES), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            in_specs=[smem, smem, smem, smem, vmem, vmem, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, N, channels), jnp.float32),
                pltpu.VMEM((2, 2, tiles, LANES), jnp.float32),
                pltpu.VMEM((2, tiles, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((4, 2)),
            ],
            # Operand 7 of the call is the state, and comes back as output 1.
            input_output_aliases={7: 1},
            interpret=interpret,
            name="ssm_decode",
        )(order, where, count, jnp.asarray(layer, jnp.int32).reshape(1), f32(A), bc, x, state)
    # a row the loop never visited left its row of y as it was: anything
    return jnp.where(active[:, None], y.reshape(R, channels), 0.0), state


# --------------------------------------------------------------------------
# decode: the convolution's tail, the active rows' written back in place
# --------------------------------------------------------------------------

_TAIL_COPIES = 8  # copies in flight


def tail_step(conv, new, layer, active, slots=None):
    """:func:`conv_tail_write` in ``jax.numpy``.  conv: [S, L, 3 C / 128, 128]
    float32; new: [R, 3 C / 128, 128], the rows' tails after this step;
    active: [R] bool; slots: [R] int32, distinct (None: row i is slot i).
    Returns conv with ``layer``'s tail of the active rows' slots replaced."""
    slots = jnp.arange(new.shape[0]) if slots is None else slots
    old = conv[slots, layer]
    return conv.at[slots, layer].set(jnp.where(active[:, None, None], new, old))


def _tail_write_kernel(row_ref, slot_ref, count_ref, layer_ref, new_hbm, conv_hbm, out_hbm, sem):
    # ``out_hbm`` is the leaf again (aliased to ``conv_hbm``, which is not read).
    del conv_hbm
    count, layer = count_ref[0], layer_ref[0]

    def copy(i):
        return pltpu.make_async_copy(
            new_hbm.at[row_ref[i]], out_hbm.at[slot_ref[i], layer], sem.at[i % _TAIL_COPIES])

    def turn(i, carry):
        @pl.when(i >= _TAIL_COPIES)
        def _():  # its semaphore is the copy's of eight turns ago
            copy(i - _TAIL_COPIES).wait()

        copy(i).start()
        return carry

    jax.lax.fori_loop(0, count, turn, 0)
    for j in range(_TAIL_COPIES):
        @pl.when(count > j)
        def _():
            copy(count - 1 - j).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv_tail_write(conv, new, layer, active, slots=None, *, interpret=None):
    """The active rows' new tails into the leaf, in place where the caller
    donates it: shapes as :func:`tail_step`, ``layer`` a traced index.  One
    kernel, named ``conv_tail_write`` in the profiler's trace; it computes
    nothing."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if conv.shape[2:] != new.shape[1:] or new.shape[2] != LANES:
        raise ValueError(
            f"conv_tail_write wants tails [rows, 3 channels / {LANES}, {LANES}] for a leaf "
            f"[slots, layers, 3 channels / {LANES}, {LANES}], got {new.shape} for {conv.shape}")
    order, where, count = _turns("conv_tail_write", active, slots, conv)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    with jax.named_scope("conv_tail_write"):
        return pl.pallas_call(
            _tail_write_kernel,
            out_shape=jax.ShapeDtypeStruct(conv.shape, conv.dtype),
            in_specs=[smem, smem, smem, smem, hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[pltpu.SemaphoreType.DMA((_TAIL_COPIES,))],
            # Operand 5 of the call is the leaf, and comes back as the output.
            input_output_aliases={5: 0},
            interpret=interpret,
            name="conv_tail_write",
        )(order, where, count, jnp.asarray(layer, jnp.int32).reshape(1), new.astype(conv.dtype), conv)


# --------------------------------------------------------------------------
# prefill: chunks of time with the state resident, one kernel a layer
# --------------------------------------------------------------------------


def _ssm_prefill_kernel(len_ref, u_ref, dt_ref, z_ref, bt_ref, ct_ref, a_ref, d_ref, s_ref,
                        y_ref, so_ref, *, chunk):
    n = pl.program_id(1)
    length = len_ref[0]

    @pl.when(n == 0)
    def _():
        so_ref[...] = s_ref[...]

    @pl.when(n * chunk >= length)
    def _():  # bucket padding: nothing was copied for it, nothing is computed
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n * chunk < length)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        # inside the last live chunk the padding holds the state still
        dt = jnp.where(n * chunk + row < length, dt_ref[...], 0.0)
        u = u_ref[...]
        dtu = dt * u
        a, bt, ct = a_ref[...], bt_ref[...], ct_ref[...]
        h = so_ref[...]  # [N, channels of the block]
        for t in range(chunk):
            h = jnp.exp(dt[t:t + 1] * a) * h + dtu[t:t + 1] * bt[:, t:t + 1]
            y_ref[t:t + 1, :] = jnp.sum(h * ct[:, t:t + 1], axis=0, keepdims=True)
        so_ref[...] = h
        y_ref[...] = (y_ref[...] + d_ref[...] * u) * jax.nn.silu(z_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_prefill(u, dt, z, A, B, C, D, *, length=None, state=None, interpret=None):
    """The recurrence over a whole sequence in chunks of :data:`CHUNK`, one
    kernel named ``ssm_prefill`` (module docstring).  u, dt, z: [T, channels];
    A: [N, channels] (transposed, as the state is); B, C: [T, N]; D:
    [channels].  ``length`` (int32 scalar, traced; default T): the first
    ``length`` positions are real, the rest a bucket's padding, which leaves
    the state as it is; a chunk wholly past it is neither copied nor computed
    and its rows of ``y`` are 0.  ``state`` [N, channels]: what the first
    chunk starts from (default 0).  Returns (``(y + D u) silu(z)`` [T,
    channels], the state after position ``length - 1`` [N, channels]),
    float32.  T is padded here to whole chunks (to a multiple of 8 where it
    is shorter than one); a bucket of the serving engine already is."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, channels = u.shape
    N = A.shape[0]
    cb = min(channels, _PREFILL_CHANNELS)
    if channels % cb or (not interpret and cb % LANES):
        raise ValueError(
            f"ssm_prefill: {channels} channels are not blocks of {cb} on whole lanes")
    chunk = CHUNK if T >= CHUNK else -(-T // 8) * 8
    pad = -T % chunk
    f32 = lambda a: a.astype(jnp.float32)
    rows = lambda a: jnp.pad(f32(a), ((0, pad), (0, 0)))
    length = jnp.clip(jnp.asarray(T if length is None else length, jnp.int32), 0, T).reshape(1)
    start = jnp.zeros((N, channels), jnp.float32) if state is None else f32(state)

    def at(n, length):  # past the last live chunk the grid names it again: no copy
        return jnp.minimum(n, jnp.maximum((length[0] - 1) // chunk, 0))

    wide = pl.BlockSpec((chunk, cb), lambda c, n, length: (at(n, length), c))
    narrow = pl.BlockSpec((N, chunk), lambda c, n, length: (0, at(n, length)))
    held = pl.BlockSpec((N, cb), lambda c, n, length: (0, c))
    with jax.named_scope("ssm_prefill"):
        y, last = pl.pallas_call(
            functools.partial(_ssm_prefill_kernel, chunk=chunk),
            out_shape=[jax.ShapeDtypeStruct((T + pad, channels), jnp.float32),
                       jax.ShapeDtypeStruct((N, channels), jnp.float32)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(channels // cb, (T + pad) // chunk),
                in_specs=[wide, wide, wide, narrow, narrow, held,
                          pl.BlockSpec((1, cb), lambda c, n, length: (0, c)), held],
                out_specs=[pl.BlockSpec((chunk, cb), lambda c, n, length: (n, c)), held],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="ssm_prefill",
        )(length, rows(u), rows(dt), rows(z), rows(B).T, rows(C).T, f32(A),
          f32(D).reshape(1, channels), start)
    return y[:T], last
