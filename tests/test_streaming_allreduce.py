"""Streaming gradient pipeline (docs/DESIGN.md §6e): buckets launch onto the
inter-host wire while the producer is still delivering (backward still
running), via ``buckets.GradientStream`` -> ``Accumulator.reduce_gradients``.

The contract under test:

- bit-exactness: a streaming contribution produces results bit-identical to
  the equivalent barrier contribution (tree, q8 wire, sharded plane, and the
  materializing fallbacks: chunked ring, virtual batching);
- launch lead: every bucket staged before the last one launches EARLY
  (``accum_bucket_launch_lead_seconds`` > 0 for non-final buckets);
- loud failure: a membership-epoch bump with buckets partially in flight
  errors the round (RpcError), and a mid-run sharding change raises
  :class:`GradientShardingError` exactly as on the barrier path;
- D2H ordering: ``deliver()`` issues ``copy_to_host_async`` for every leaf
  of the chunk before any leaf is materialized into the flat buffer.
"""

import threading
import time

import numpy as np
import pytest

import jax

from moolib_tpu import (
    Accumulator, Broker, GradientShardingError, buckets,
)
from moolib_tpu.rpc import RpcError

from test_sharded_allreduce import close_all, make_cohort, pump


# --------------------------------------------------------------- unit layer
def test_coverage_merging():
    c = buckets.Coverage()
    assert c.covers(5, 5)  # empty range is always covered
    assert not c.covers(0, 1)
    c.add(0, 10)
    c.add(20, 30)
    assert c.covers(0, 10) and c.covers(2, 7) and not c.covers(5, 25)
    c.add(10, 20)  # bridges the gap
    assert c.covers(0, 30)
    c.add(5, 15)  # overlapping re-add is a no-op
    assert c.covers(0, 30) and not c.covers(0, 31)


def _leaves(treeish):
    return jax.tree_util.tree_flatten(treeish)


def test_gradient_stream_protocol():
    tree = {"b": np.zeros(4, np.float32), "w": np.zeros((4, 4), np.float32)}
    leaves, treedef = _leaves(tree)
    s = buckets.GradientStream(
        treedef, [l.shape for l in leaves], [l.dtype for l in leaves]
    )
    assert s.n_leaves == 2 and not s.complete
    s.deliver(1, [leaves[1]])
    with pytest.raises(ValueError):
        s.deliver(1, [leaves[1]])  # double delivery
    with pytest.raises(ValueError):
        s.deliver(5, [leaves[0]])  # out of range
    s.deliver(0, [leaves[0]])
    assert s.complete
    got = {}
    while True:
        c = s.next_chunk(1.0)
        if c is None:
            break
        got[c[0]] = c[1]
    assert set(got) == {0, 1}


def test_gradient_stream_timeout_and_fail():
    leaves, treedef = _leaves([np.zeros(4, np.float32)])
    s = buckets.GradientStream(treedef, [(4,)], [np.float32])
    with pytest.raises(TimeoutError):
        s.next_chunk(0.05)
    s.fail(RuntimeError("producer died"))
    with pytest.raises(RuntimeError, match="producer died"):
        s.next_chunk(1.0)


def test_gradient_stream_d2h_before_consumption():
    events = []

    class FakeLeaf:
        """Device-array stand-in: records D2H issue vs host materialize."""

        def __init__(self, i, n):
            self.i, self.shape, self.dtype = i, (n,), np.dtype(np.float32)

        def copy_to_host_async(self):
            events.append(f"d2h:{self.i}")

        def __array__(self, dtype=None, copy=None):
            events.append(f"arr:{self.i}")
            return np.zeros(self.shape, np.float32)

    leaves, treedef = _leaves([np.zeros(4, np.float32), np.zeros(4, np.float32)])
    fakes = [FakeLeaf(0, 4), FakeLeaf(1, 4)]
    s = buckets.GradientStream(treedef, [(4,), (4,)], [np.float32, np.float32])
    s.deliver(0, fakes)
    # deliver() itself starts every transfer, before any consumer runs.
    assert events == ["d2h:0", "d2h:1"]
    lo, ls = s.next_chunk(1.0)
    np.asarray(ls[0]), np.asarray(ls[1])
    assert events[:2] == ["d2h:0", "d2h:1"]
    assert "arr:0" in events and "arr:1" in events


# ------------------------------------------------------------- cohort layer
def _int_trees(n, shape=(64, 64), seed=7):
    rng = np.random.RandomState(seed)
    return [
        {
            "b": rng.randint(-8, 9, size=(shape[0],)).astype(np.float32),
            "w": rng.randint(-8, 9, size=shape).astype(np.float32),
        }
        for _ in range(n)
    ]


def _stream_of(tree, on_bucket=None, shardings=None):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (
        buckets.GradientStream(
            treedef, [l.shape for l in leaves], [l.dtype for l in leaves],
            shardings=shardings, on_bucket=on_bucket,
        ),
        leaves,
    )


def _reduce_streaming(accs, trees, stagger=0.15):
    """Contribute each tree as a stream: tail leaf ("w", the bulk) delivered
    immediately, head leaf ("b") delivered ``stagger`` seconds later from a
    producer thread — the mid-backward shape of the overlap pipeline."""
    threads = []
    for a, t in zip(accs, trees):
        # Host leaves are declared explicitly unsharded: the sharded plane
        # needs per-leaf sharding info to build its wire layout on a cold
        # cache (shardings=None would fall back to a barrier round first).
        s, leaves = _stream_of(t, shardings=[None] * 2)
        s.deliver(1, [leaves[1]])  # "w"

        def _late(s=s, leaves=leaves):
            time.sleep(stagger)
            s.deliver(0, [leaves[0]])  # "b"

        threading.Thread(target=_late, daemon=True).start()
        th = threading.Thread(target=a.reduce_gradients, args=(4, s))
        th.start()
        threads.append(th)
    for th in threads:
        th.join(30)
        assert not th.is_alive()


def _collect(accs):
    return [{k: np.array(v) for k, v in a.gradients().items()} for a in accs]


def _ref_mean(trees):
    return {
        k: (sum(np.asarray(t[k], np.float64) for t in trees) / len(trees)
            ).astype(np.float32)
        for k in trees[0]
    }


@pytest.fixture
def small_buckets():
    buckets.set_bucket_bytes(1 << 12)  # 1024 f32 elems: multi-bucket trees
    yield
    buckets.set_bucket_bytes(buckets._DEFAULT_BUCKET_BYTES)


def _run_barrier_round(port, n, trees, sharded=False, q8=False):
    broker, accs = make_cohort(port, n, sharded=sharded)
    try:
        if q8:
            for a in accs:
                a.set_wire_dtype(np.int8)
        assert pump(broker, accs, 30, until=lambda: all(a.connected() for a in accs))
        for a, t in zip(accs, trees):
            a.reduce_gradients(4, t)
        assert pump(broker, accs, 20, until=lambda: all(a.has_gradients() for a in accs))
        return _collect(accs)
    finally:
        close_all(broker, accs)


def _run_streaming_round(port, n, trees, sharded=False, q8=False):
    broker, accs = make_cohort(port, n, sharded=sharded)
    try:
        if q8:
            for a in accs:
                a.set_wire_dtype(np.int8)
        assert pump(broker, accs, 30, until=lambda: all(a.connected() for a in accs))
        done = threading.Event()
        pumper = threading.Thread(
            target=lambda: pump(broker, accs, 30, until=done.is_set), daemon=True
        )
        pumper.start()
        try:
            _reduce_streaming(accs, trees)
            assert pump(broker, accs, 20,
                        until=lambda: all(a.has_gradients() for a in accs))
        finally:
            done.set()
            pumper.join(5)
        leads = [a._last_launch_leads for a in accs]
        return _collect(accs), leads
    finally:
        close_all(broker, accs)


def test_streaming_bit_exact_vs_barrier_and_numpy(free_port, small_buckets):
    from conftest import grab_port

    trees = _int_trees(2)
    barrier = _run_barrier_round(free_port, 2, trees)
    streamed, leads = _run_streaming_round(grab_port(), 2, trees)
    ref = _ref_mean(trees)
    for tree in barrier + streamed:
        for k in ("w", "b"):
            np.testing.assert_array_equal(tree[k], ref[k])
            np.testing.assert_array_equal(tree[k], barrier[0][k])
    # Launch lead: the staggered head leaf makes every earlier bucket's wire
    # op launch ahead of the barrier point (the last launch).
    for peer_leads in leads:
        assert peer_leads is not None and len(peer_leads) >= 2
        assert max(peer_leads) > 0.05
        assert min(peer_leads) == 0.0


def test_streaming_q8_bit_exact_vs_barrier(free_port, small_buckets):
    from conftest import grab_port

    trees = _int_trees(2, seed=11)
    barrier = _run_barrier_round(free_port, 2, trees, q8=True)
    streamed, _ = _run_streaming_round(grab_port(), 2, trees, q8=True)
    # Per-bucket EF-q8 (independent absmax + residual slice per bucket) makes
    # readiness-order quantization bit-identical to the barrier's one pass.
    for b, s in zip(barrier, streamed):
        for k in ("w", "b"):
            np.testing.assert_array_equal(s[k], b[k])
            np.testing.assert_array_equal(s[k], barrier[0][k])


def test_streaming_sharded_bit_exact(free_port, small_buckets):
    from conftest import grab_port

    trees = _int_trees(3, seed=13)
    barrier = _run_barrier_round(free_port, 3, trees, sharded=True)
    streamed, _ = _run_streaming_round(grab_port(), 3, trees, sharded=True)
    ref = _ref_mean(trees)
    for tree in barrier + streamed:
        for k in ("w", "b"):
            np.testing.assert_array_equal(tree[k], ref[k])


def test_streaming_materializes_on_ring_and_vbatch(free_port, small_buckets):
    broker, accs = make_cohort(free_port, 2)
    try:
        for a in accs:
            a.set_chunked_allreduce(True)  # forces the ring: stream must fall back
        assert pump(broker, accs, 30, until=lambda: all(a.connected() for a in accs))
        trees = _int_trees(2, seed=17)
        _reduce_streaming(accs, trees)
        assert pump(broker, accs, 20, until=lambda: all(a.has_gradients() for a in accs))
        ref = _ref_mean(trees)
        for tree in _collect(accs):
            for k in ("w", "b"):
                np.testing.assert_array_equal(tree[k], ref[k])
    finally:
        close_all(broker, accs)


def test_streaming_single_member_degenerates(free_port, small_buckets):
    broker, accs = make_cohort(free_port, 1)
    try:
        assert pump(broker, accs, 30, until=lambda: accs[0].connected())
        tree = _int_trees(1, seed=19)[0]
        s, leaves = _stream_of(tree)
        s.deliver(0, leaves)
        accs[0].reduce_gradients(4, s)
        assert pump(broker, accs, 20, until=lambda: accs[0].has_gradients())
        got = _collect(accs)[0]
        for k in ("w", "b"):
            np.testing.assert_array_equal(got[k], tree[k])
    finally:
        close_all(broker, accs)


def test_on_bucket_callback_fires_per_bucket(free_port, small_buckets):
    broker, accs = make_cohort(free_port, 2)
    try:
        assert pump(broker, accs, 30, until=lambda: all(a.connected() for a in accs))
        trees = _int_trees(2, seed=23)
        hits = []
        streams = []
        for a, t in zip(accs, trees):
            cb = hits.append if a is accs[0] else None
            s, leaves = _stream_of(t, on_bucket=(lambda lo, hi: hits.append((lo, hi))) if cb else None)
            s.deliver(0, leaves)
            streams.append(s)
        ths = [
            threading.Thread(target=a.reduce_gradients, args=(4, s))
            for a, s in zip(accs, streams)
        ]
        for th in ths:
            th.start()
        for th in ths:
            th.join(30)
        assert pump(broker, accs, 20, until=lambda: all(a.has_gradients() for a in accs))
        # Every layout bucket reported exactly once, covering [0, total).
        total = sum(l.size for l in jax.tree_util.tree_leaves(trees[0]))
        assert sorted(hits) == sorted(set(hits))
        assert min(lo for lo, _ in hits) == 0
        assert max(hi for _, hi in hits) == total
    finally:
        close_all(broker, accs)


# ---------------------------------------------------------------- failures
def test_epoch_bump_with_buckets_in_flight_errors_loudly(free_port, small_buckets):
    broker, accs = make_cohort(free_port, 2)
    try:
        assert pump(broker, accs, 30, until=lambda: all(a.connected() for a in accs))
        g = accs[0]._group
        flat = np.zeros(4096, np.float32)
        handle = g.bucketed_stream("__stream_test", flat)
        assert len(handle.bounds) >= 2
        handle.launch(0)
        # Membership-epoch bump with buckets partially in flight: the next
        # launch must raise instead of silently desyncing the cohort.
        with g._lock:
            g._sync_id += 1
        with pytest.raises(RpcError, match="group changed"):
            handle.launch(1)
        assert handle.future.exception() is not None
        with pytest.raises(RpcError, match="already failed"):
            handle.launch(1)
    finally:
        close_all(broker, accs)


def test_producer_failure_aborts_round(free_port, small_buckets):
    broker, accs = make_cohort(free_port, 2)
    closed = []
    try:
        assert pump(broker, accs, 30, until=lambda: all(a.connected() for a in accs))
        tree = _int_trees(1)[0]
        s, leaves = _stream_of(tree, shardings=[None] * 2)
        s.deliver(1, [leaves[1]])
        s.fail(RuntimeError("backward blew up"))
        # Producer failure with buckets already launched: loud error, and
        # the errored round frees its pipeline slot (no wedge).
        with pytest.raises((RuntimeError, RpcError)):
            accs[0].reduce_gradients(4, s)
        assert pump(broker, accs, 20, until=lambda: not accs[0]._inflight)
        # A crashed producer in real life takes its peer down: the epoch
        # bump resynchronizes op sequences, after which fresh rounds work.
        accs[1].close()
        closed.append(accs.pop(1))
        assert pump(broker, accs, 30,
                    until=lambda: len(accs[0]._group.members()) == 1)
        accs[0].reduce_gradients(4, tree)
        assert pump(broker, accs, 20, until=lambda: accs[0].has_gradients())
        got = _collect(accs)[0]
        np.testing.assert_array_equal(got["w"], tree["w"])
    finally:
        close_all(broker, accs)


def test_streaming_sharding_change_raises_typed_error(free_port, small_buckets):
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices (xla_force_host_platform_device_count)")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devs[:2]), ("dp",))
    sh = NamedSharding(mesh, P("dp"))
    params = {"b": np.zeros(64, np.float32), "w": np.zeros((64, 64), np.float32)}
    broker, accs = make_cohort(free_port, 2, sharded=True, params=params)
    try:
        assert pump(broker, accs, 30, until=lambda: all(a.connected() for a in accs))
        g_dev = {
            "b": jax.device_put(np.ones(64, np.float32), sh),
            "w": jax.device_put(np.ones((64, 64), np.float32), sh),
        }
        for a in accs:
            a.reduce_gradients(4, g_dev)
        assert pump(broker, accs, 20, until=lambda: all(a.has_gradients() for a in accs))
        for a in accs:
            a.zero_gradients()
        # Streaming declares different (host) shardings for the same
        # treedef/shapes/dtype: the layout is cohort wire protocol, so the
        # signature guard fires exactly as on the barrier path.
        tree = _int_trees(1)[0]
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        s = buckets.GradientStream(
            treedef, [l.shape for l in leaves], [l.dtype for l in leaves],
            shardings=[None] * len(leaves),
        )
        s.deliver(0, leaves)
        with pytest.raises(GradientShardingError):
            accs[0].reduce_gradients(4, s)
    finally:
        close_all(broker, accs)


# ------------------------------------------------------- train-step overlap
def test_overlap_grads_train_step_end_to_end(free_port, small_buckets):
    import jax.numpy as jnp

    from moolib_tpu import parallel

    def loss_fn(p, b, r):
        h = jnp.tanh(b["x"] @ p["w1"])
        out = h @ p["w2"]
        return jnp.mean((out - b["y"]) ** 2), {"n": out.shape[0]}

    params = {
        "w1": jnp.asarray(np.random.RandomState(3).randn(8, 32), jnp.float32),
        "w2": jnp.asarray(np.random.RandomState(4).randn(32, 4), jnp.float32),
    }
    batch = {
        "x": jnp.ones((16, 8), jnp.float32),
        "y": jnp.zeros((16, 4), jnp.float32),
    }
    rng = jax.random.PRNGKey(0)

    (loss_ref, _), g_ref = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params, batch, rng)

    step = parallel.make_train_step(loss_fn, overlap_grads=True)
    loss, aux, stream = step(params, batch, rng)
    assert isinstance(stream, buckets.GradientStream)
    assert float(loss) == float(loss_ref)

    broker, accs = make_cohort(
        free_port, 1, params={k: np.asarray(v) for k, v in params.items()}
    )
    try:
        assert pump(broker, accs, 30, until=lambda: accs[0].connected())
        accs[0].reduce_gradients(16, stream)
        assert pump(broker, accs, 20, until=lambda: accs[0].has_gradients())
        got = _collect(accs)[0]
        for k in ("w1", "w2"):
            np.testing.assert_allclose(
                got[k], np.asarray(g_ref[k]), rtol=1e-6, atol=1e-7
            )
    finally:
        close_all(broker, accs)


def test_overlap_grads_train_step_guards():
    import optax

    from moolib_tpu import parallel

    def loss_fn(p, b, r):
        return p["w"].sum(), {}

    with pytest.raises(ValueError, match="does not compose with optimizer"):
        parallel.make_train_step(
            loss_fn, optimizer=optax.sgd(0.1), overlap_grads=True
        )
    # No optimizer is fine when streaming (the reduce consumer applies).
    assert parallel.make_train_step(loss_fn, overlap_grads=True) is not None
