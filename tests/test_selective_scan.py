"""The selective state-space scan (``ops/selective_scan.py``): both Pallas
kernels in interpret mode on the CPU against the one definition,
``selective_scan_reference``, a token at a time.  float32 throughout; what
separates kernel and reference is the order of a sum over 16 states."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu.ops import selective_scan as ssm

N, CH = 16, 256  # states a channel, channels


def _inputs(T, seed=0, channels=CH):
    ks = jax.random.split(jax.random.key(seed), 8)
    u = jax.random.normal(ks[0], (T, channels))
    # steps from 1e-3 to several: a channel that forgets at once and one that keeps a thousand tokens
    dt = jax.nn.softplus(2.0 * jax.random.normal(ks[1], (T, channels)) - 3.0)
    z = jax.random.normal(ks[2], (T, channels))
    A = -jnp.exp(jax.random.uniform(ks[3], (channels, N), minval=0.0, maxval=jnp.log(16.0)))
    B, C = jax.random.normal(ks[4], (T, N)), jax.random.normal(ks[5], (T, N))
    D = jax.random.normal(ks[6], (channels,))
    return u, dt, z, A, B, C, D, jax.random.normal(ks[7], (channels, N))


def _want(u, dt, z, A, B, C, D, state, n):
    y, last = ssm.selective_scan_reference(u[:n], dt[:n], A, B[:n], C[:n], D, state)
    return y * jax.nn.silu(z[:n]), last


# T, the real positions of it (None: all), a state to start from, the chunk
@pytest.mark.parametrize("T,length,start,chunk", [
    (256, None, False, 128),  # two chunks of the kernel's own size
    (64, None, True, 16),     # whole chunks, a state to start from
    (50, None, False, 16),    # a length that does not divide the chunk: padded inside
    (64, 32, True, 16),       # shorter by whole chunks
    (64, 37, False, 16),      # ... and by part of one
    (64, 1, True, 16),        # one real position
    (32, 0, True, 16),        # none: the state comes back as it went in
    (5, 3, True, 16),         # shorter than the convolution, one chunk of 8
])
def test_prefill_kernel_equals_the_token_by_token_recurrence(monkeypatch, T, length, start, chunk):
    """Positions from ``length`` on are whatever the bucket holds (here: as
    lively as the real ones, unmasked): the state is what position length - 1
    left, the rows of ``y`` below it are the recurrence's with the gate
    applied, and the chunks wholly past it were not run, so their rows are 0."""
    monkeypatch.setattr(ssm, "CHUNK", chunk)
    u, dt, z, A, B, C, D, s0 = _inputs(T, seed=T)
    s0 = s0 if start else jnp.zeros_like(s0)
    n = T if length is None else length
    want_y, want_s = _want(u, dt, z, A, B, C, D, s0, n)
    got_y, got_s = ssm.ssm_prefill(
        u, dt, z, A.T, B, C, D, length=None if length is None else jnp.int32(length),
        state=s0.T if start else None)
    assert got_y.shape == (T, CH) and got_s.shape == (N, CH)  # the state's layout: channels on lanes
    np.testing.assert_allclose(got_y[:n], want_y, atol=2e-5)
    np.testing.assert_allclose(got_s.T, want_s, atol=2e-5)
    if T >= chunk:
        np.testing.assert_array_equal(np.asarray(got_y[-(-n // chunk) * chunk:]), 0.0)


def test_prefill_in_two_calls_is_the_prefill_in_one(monkeypatch):
    """The second call starts from the state the first one left: what a
    prompt prefilled in pieces between decode steps will do."""
    monkeypatch.setattr(ssm, "CHUNK", 16)
    u, dt, z, A, B, C, D, _ = _inputs(64, seed=13)
    whole_y, whole_s = ssm.ssm_prefill(u, dt, z, A.T, B, C, D)
    first_y, s = ssm.ssm_prefill(u[:32], dt[:32], z[:32], A.T, B[:32], C[:32], D)
    second_y, s = ssm.ssm_prefill(u[32:], dt[32:], z[32:], A.T, B[32:], C[32:], D, state=s)
    np.testing.assert_allclose(jnp.concatenate([first_y, second_y]), whole_y, atol=1e-5)
    np.testing.assert_allclose(s, whole_s, atol=1e-5)


# the rows are the slots; a permutation of them; a strict subset (a step over
# fewer rows than the leaf has slots reads what a row brings by row, its state by slot)
@pytest.mark.parametrize("slots", [None, (3, 0, 4, 1, 2), (4, 1, 2)])
@pytest.mark.parametrize("active", [(True, False, True, True, False), (False,) * 5, (True,) * 5])
def test_decode_kernel_in_interpret_mode_equals_the_jnp_step(active, slots):
    R = 5 if slots is None else len(slots)
    u, dt, _z, A, B, C, _D, _ = _inputs(R, seed=3)
    state = jax.random.normal(jax.random.key(5), (5, 3, N, CH))
    active = jnp.asarray(active[:R])
    slot_of = np.arange(R) if slots is None else np.asarray(slots)
    slots = None if slots is None else jnp.asarray(slots, jnp.int32)
    want_y, want_s = ssm.ssm_step(u, dt, A.T, B, C, state, 1, active, slots)
    got_y, got_s = ssm.ssm_decode(u, dt, A.T, B, C, state, jnp.int32(1), active, slots)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    # the step by slot is the step by row over the rows' slots of the state
    rows_y, rows_s = ssm.ssm_step(u, dt, A.T, B, C, state[slot_of], 1, active)
    np.testing.assert_array_equal(np.asarray(want_y), np.asarray(rows_y))
    np.testing.assert_array_equal(np.asarray(want_s)[slot_of], np.asarray(rows_s))
    # other layers, the slots nobody holds and the slots no row names are bit
    # for bit what they were
    idle = np.setdiff1d(np.arange(5), slot_of[np.asarray(active)])
    np.testing.assert_array_equal(np.asarray(got_s)[idle], np.asarray(state)[idle])
    np.testing.assert_array_equal(np.asarray(got_s)[:, [0, 2]], np.asarray(state)[:, [0, 2]])
    np.testing.assert_array_equal(np.asarray(got_y)[~np.asarray(active)], 0.0)


@pytest.mark.parametrize("slots", [None, (3, 0, 4, 1, 2), (4, 1, 2)])
@pytest.mark.parametrize("active", [(True, False, True, True, False), (False,) * 5, (True,) * 5])
def test_tail_write_kernel_in_interpret_mode_equals_the_jnp_step(active, slots):
    """The active rows' new tails land at their slots' blocks of the layer;
    every other block of the leaf is bit for bit what it was."""
    R = 5 if slots is None else len(slots)
    conv = jax.random.normal(jax.random.key(6), (5, 3, 3 * CH // 128, 128))
    new = jax.random.normal(jax.random.key(7), (R, 3 * CH // 128, 128))
    active = jnp.asarray(active[:R])
    slot_of = np.arange(R) if slots is None else np.asarray(slots)
    slots = None if slots is None else jnp.asarray(slots, jnp.int32)
    want = ssm.tail_step(conv, new, 1, active, slots)
    got = ssm.conv_tail_write(conv, new, jnp.int32(1), active, slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    written = slot_of[np.asarray(active)]
    np.testing.assert_array_equal(np.asarray(got)[written, 1], np.asarray(new)[np.asarray(active)])
    idle = np.setdiff1d(np.arange(5), written)
    np.testing.assert_array_equal(np.asarray(got)[idle], np.asarray(conv)[idle])
    np.testing.assert_array_equal(np.asarray(got)[:, [0, 2]], np.asarray(conv)[:, [0, 2]])


def test_more_active_rows_than_copies_in_flight_are_all_written():
    conv = jnp.zeros((40, 2, 6, 128))
    new = jax.random.normal(jax.random.key(8), (23, 6, 128))
    slots = jnp.asarray(np.random.default_rng(0).permutation(40)[:23], jnp.int32)
    active = jnp.arange(23) != 11
    got = ssm.conv_tail_write(conv, new, 0, active, slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ssm.tail_step(conv, new, 0, active, slots)))
    assert int((np.asarray(got) != 0).any(axis=(1, 2, 3)).sum()) == 22


def test_the_jnp_step_is_the_recurrence_transposed():
    u, dt, _z, A, B, C, D, s0 = _inputs(6, seed=4)
    want_y, want_s = ssm.selective_scan_reference(u, dt, A, B, C, D, s0)
    state = s0.T[None, None]  # one slot, one layer, [N, channels]
    for t in range(6):
        y, state = ssm.ssm_step(u[t:t + 1], dt[t:t + 1], A.T, B[t:t + 1], C[t:t + 1],
                                state, 0, jnp.ones((1,), bool))
        np.testing.assert_allclose(y[0] + D * u[t], want_y[t], atol=1e-5)
    np.testing.assert_allclose(state[0, 0].T, want_s, atol=1e-5)


def test_shapes_off_the_lanes_are_refused_by_name():
    u, dt, z, A, B, C, D, _ = _inputs(8, channels=192)
    with pytest.raises(ValueError, match="ssm_decode"):
        ssm.ssm_decode(u, dt, A.T, B, C, jnp.zeros((8, 1, N, 192)), 0, jnp.ones((8,), bool))
    with pytest.raises(ValueError, match="ssm_prefill"):
        ssm.ssm_prefill(u, dt, z, A.T, B, C, D, interpret=False)
    u, dt, z, A, B, C, D, _ = _inputs(4)
    with pytest.raises(ValueError, match="each row's slot"):  # fewer rows than slots, no map
        ssm.ssm_decode(u, dt, A.T, B, C, jnp.zeros((8, 1, N, CH)), 0, jnp.ones((4,), bool))
    with pytest.raises(ValueError, match="conv_tail_write wants"):
        ssm.conv_tail_write(jnp.zeros((8, 1, 3, 256)), jnp.zeros((8, 3, 256)), 0, jnp.ones((8,), bool))
    with pytest.raises(ValueError, match="each row's slot"):
        ssm.conv_tail_write(jnp.zeros((8, 1, 6, 128)), jnp.zeros((4, 6, 128)), 0, jnp.ones((4,), bool))
