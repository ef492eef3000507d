"""The Mamba-2 recurrence (``ops/ssd.py``): both Pallas kernels in interpret
mode on the CPU against the one definition, ``ssd_reference``, a token at a
time.  float32 throughout; what separates kernel and reference is the order of
the sums (over a chunk's positions in the prefill, over the states in both)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu.ops import ssd

H, P, N = 4, 16, 32  # heads, channels a head, states


def _inputs(T, seed=0, heads=H):
    H = heads
    ks = jax.random.split(jax.random.key(seed), 8)
    x = jax.random.normal(ks[0], (T, H, P))
    # steps from 1e-3 to several: a head that forgets at once and one that keeps a thousand tokens
    dt = jax.nn.softplus(2.0 * jax.random.normal(ks[1], (T, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=jnp.log(16.0)))
    B, C = jax.random.normal(ks[3], (T, N)), jax.random.normal(ks[4], (T, N))
    D = jax.random.normal(ks[5], (H,))
    return x, dt, A, B, C, D, jax.random.normal(ks[6], (H, P, N))


def _want(x, dt, A, B, C, state, n):
    """The definition's first ``n`` positions, without its ``D x`` term (the
    kernels leave that to the caller)."""
    return ssd.ssd_reference(x[:n], dt[:n], A, B[:n], C[:n], jnp.zeros_like(A), state)


# T, the real positions of it (None: all), a state to start from, the chunk
@pytest.mark.parametrize("T,length,start,chunk", [
    (256, None, False, None),  # two chunks of the kernel's own size
    (64, None, True, 16),      # whole chunks, a state to start from
    (50, None, False, 16),     # a length that does not divide the chunk: padded inside
    (64, 32, True, 16),        # shorter by whole chunks
    (64, 37, False, 16),       # ... and by part of one
    (64, 1, True, 16),         # one real position
    (32, 0, True, 16),         # none: the state comes back as it went in
    (5, 3, True, 16),          # shorter than the convolution, one chunk of 8
])
def test_prefill_kernel_equals_the_token_by_token_recurrence(T, length, start, chunk):
    """Positions from ``length`` on are whatever the bucket holds (here: as
    lively as the real ones, unmasked): the state handed back is the one at
    ``length - 1``, the rows of ``y`` below it are the recurrence's, and the
    chunks wholly past it were not run, so their rows are 0."""
    x, dt, A, B, C, _D, s0 = _inputs(T, seed=T)
    s0 = s0 if start else jnp.zeros_like(s0)
    n = T if length is None else length
    want_y, want_s = _want(x, dt, A, B, C, s0, n)
    got_y, got_s = ssd.ssd_prefill(
        x, dt, A, B, C, length=None if length is None else jnp.int32(length),
        state=s0 if start else None, chunk=chunk)
    assert got_y.shape == (T, H, P) and got_s.shape == (H, P, N)  # the states on the lanes
    np.testing.assert_allclose(got_y[:n], want_y, atol=5e-5, rtol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=5e-5, rtol=2e-5)
    step = chunk or ssd.CHUNK
    if T >= step:
        np.testing.assert_array_equal(np.asarray(got_y[-(-n // step) * step:]), 0.0)


@pytest.mark.parametrize("chunks", [(8, 32), (16, 64)])
def test_two_chunk_sizes_give_one_result(chunks):
    """``mamba_chunk_size`` is no part of the mathematics: the kernel's own
    chunk and any other give one ``y`` and one state."""
    x, dt, A, B, C, _D, s0 = _inputs(64, seed=7)
    (y_a, s_a), (y_b, s_b) = (
        ssd.ssd_prefill(x, dt, A, B, C, length=jnp.int32(45), state=s0, chunk=c) for c in chunks)
    np.testing.assert_allclose(y_a[:45], y_b[:45], atol=5e-5)
    np.testing.assert_allclose(s_a, s_b, atol=5e-5)


def test_prefill_in_two_calls_is_the_prefill_in_one():
    """The second call starts from the state the first one left: what a
    prompt prefilled in pieces between decode steps will do."""
    x, dt, A, B, C, _D, _ = _inputs(64, seed=13)
    whole_y, whole_s = ssd.ssd_prefill(x, dt, A, B, C, chunk=16)
    first_y, s = ssd.ssd_prefill(x[:32], dt[:32], A, B[:32], C[:32], chunk=16)
    second_y, s = ssd.ssd_prefill(x[32:], dt[32:], A, B[32:], C[32:], state=s, chunk=16)
    np.testing.assert_allclose(jnp.concatenate([first_y, second_y]), whole_y, atol=2e-5)
    np.testing.assert_allclose(s, whole_s, atol=2e-5)


def test_decode_after_prefill_is_the_recurrence_one_token_on():
    """The two kernels meet: a prefill's state, one decode step on, is the
    definition's after one more token, ``D x`` added by the caller."""
    x, dt, A, B, C, D, _ = _inputs(33, seed=21)
    want_y, want_s = ssd.ssd_reference(x, dt, A, B, C, D, jnp.zeros((H, P, N)))
    _, s = ssd.ssd_prefill(x[:32], dt[:32], A, B[:32], C[:32], chunk=16)
    y, leaf = ssd.ssd_decode(x[32:], dt[32:], A, B[32:], C[32:], s[None, None], 0,
                             jnp.ones((1,), bool))
    np.testing.assert_allclose(y[0] + D[:, None] * x[32], want_y[32], atol=5e-5)
    np.testing.assert_allclose(leaf[0, 0], want_s, atol=5e-5)


# heads (a packed tile of inputs holds 8 N / P = 16), heads a piece: fewer heads
# than a tile holds; one piece of three tiles; six pieces through four buffers
@pytest.mark.parametrize("heads,piece", [(4, 32), (48, 64), (96, 16)])
@pytest.mark.parametrize("active", [(True, False, True, True, False), (False,) * 5, (True,) * 5,
                                    (False, False, False, True, False)])
def test_decode_kernel_in_interpret_mode_equals_the_jnp_step(monkeypatch, active, heads, piece):
    monkeypatch.setattr(ssd, "_PIECE_HEADS", piece)
    S = 5
    x, dt, A, B, C, _D, _ = _inputs(S, seed=3, heads=heads)
    state = jax.random.normal(jax.random.key(5), (S, 3, heads, P, N))
    active = jnp.asarray(active)
    want_y, want_s = ssd.ssd_step(x, dt, A, B, C, state, 1, active)
    got_y, got_s = ssd.ssd_decode.__wrapped__(x, dt, A, B, C, state, jnp.int32(1), active)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    # the step is the definition's, a slot at a time
    for slot in np.flatnonzero(np.asarray(active)):
        y, s = ssd.ssd_reference(x[slot][None], dt[slot][None], A, B[slot][None], C[slot][None],
                                 jnp.zeros_like(A), state[slot, 1])
        np.testing.assert_allclose(got_y[slot], y[0], atol=1e-5)
        np.testing.assert_allclose(got_s[slot, 1], s, atol=1e-5)
    # other layers and the slots nobody holds are bit for bit what they were
    idle = np.flatnonzero(~np.asarray(active))
    np.testing.assert_array_equal(np.asarray(got_s)[idle], np.asarray(state)[idle])
    np.testing.assert_array_equal(np.asarray(got_s)[:, [0, 2]], np.asarray(state)[:, [0, 2]])
    np.testing.assert_array_equal(np.asarray(got_y)[idle], 0.0)


def test_shapes_the_kernels_cannot_tile_are_refused_by_name():
    x, dt, A, B, C, _D, s0 = _inputs(8)
    with pytest.raises(ValueError, match="ssd_decode wants a state"):
        ssd.ssd_decode(x, dt, A, B, C, jnp.zeros((8, 1, H, P, N + 1)), 0, jnp.ones((8,), bool))
    with pytest.raises(ValueError, match="pairs of heads"):
        ssd.ssd_prefill(x[:, :3], dt[:, :3], A[:3], B, C)
