"""Device-resident replay: bit-exactness vs the numpy reference, the
fixed-shape (zero-recompile) contract, donation safety, the two-level
cohort draw, and the write-once memfd ingest invariant."""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from moolib_tpu import Rpc, telemetry  # noqa: E402
from moolib_tpu.replay import (  # noqa: E402
    DeviceReplayShard,
    DeviceSumTree,
    DistributedReplay,
    ReplayPublisher,
    ReplayShardService,
    SumTree,
)
from moolib_tpu.replay.host import payload_bytes  # noqa: E402


def _counter(name):
    return telemetry.get_registry().counter_values().get(name, 0.0)


def _counters_matching(substr):
    return {
        k: v
        for k, v in telemetry.get_registry().counter_values().items()
        if substr in k
    }


# ---------------------------------------------------------- bit-exactness


def test_device_sumtree_bitexact_set_and_sample():
    """Same leaf writes, same f32 dtype -> the full-level pairwise rebuild
    must produce the identical tree the reference's touched-path walk
    does, and the lockstep descent must pick identical leaves for
    identical targets."""
    dev = DeviceSumTree(64, name="t_exact")
    ref = SumTree(64, dtype=np.float32)
    rng = np.random.default_rng(3)
    for _ in range(50):
        idx = rng.choice(64, size=8, replace=False)
        vals = (rng.random(8) * 5).astype(np.float32)
        dev.set(idx, vals)
        ref.set(idx, vals)
        assert np.array_equal(np.asarray(dev.tree), ref.tree)
    targets = (rng.random(500) * ref.total()).astype(np.float32)
    assert np.array_equal(np.asarray(dev.sample(targets)), ref.sample(targets))


def test_shard_bitexact_500_op_schedule():
    """Seeded 500-op add/update/sample schedule: the shard's tree stays
    bit-exact with the numpy reference fed through the shard's OWN
    compiled priority transform (same fn, exact equality — no atol)."""
    shard = DeviceReplayShard(128, seed=11, name="t_sched")
    ref = SumTree(128, dtype=np.float32)
    rng = np.random.default_rng(11)

    def tf(p):
        return np.asarray(shard.priority_transform(np.asarray(p, np.float32)))

    for op in range(500):
        kind = op % 5
        if kind in (0, 1):
            items = [
                {"x": rng.normal(size=6).astype(np.float32)} for _ in range(8)
            ]
            prios = (rng.random(8) * 4).astype(np.float32)
            idxs = shard.add(items, prios)
            ref.set(np.asarray(idxs), tf(prios))
        elif kind == 2 and len(shard) >= 16:
            idxs = rng.choice(len(shard), size=16, replace=False)
            prios = (rng.random(16) * 3).astype(np.float32)
            shard.update_priorities(idxs.astype(np.int32), prios)
            ref.set(idxs, tf(prios))
        elif len(shard) > 0:
            shard.sample(16)  # draws must not perturb the tree
        if op % 25 == 0:
            assert np.array_equal(np.asarray(shard.tree), ref.tree)
    assert np.array_equal(np.asarray(shard.tree), ref.tree)
    assert shard.total_host() == ref.total()
    assert np.array_equal(
        np.asarray(shard.leaf_priorities()), ref.tree[ref.capacity :][:128]
    )


def test_shard_default_priority_path_bitexact():
    """Adds without explicit priorities fill with the running max RAW
    priority — mirror the reference store's rule and stay exact."""
    shard = DeviceReplayShard(32, seed=0, name="t_default")
    ref = SumTree(32, dtype=np.float32)
    maxp = 1.0

    def tf(p):
        return np.asarray(shard.priority_transform(np.asarray(p, np.float32)))

    idxs = shard.add([{"x": np.float32(i)} for i in range(4)])
    ref.set(np.asarray(idxs), tf(np.full(4, maxp, np.float32)))
    shard.update_priorities(np.arange(4, dtype=np.int32), np.full(4, 7.0, np.float32))
    ref.set(np.arange(4), tf(np.full(4, 7.0, np.float32)))
    maxp = 7.0
    idxs = shard.add([{"x": np.float32(i)} for i in range(4, 8)])
    ref.set(np.asarray(idxs), tf(np.full(4, maxp, np.float32)))
    assert np.array_equal(np.asarray(shard.tree), ref.tree)


# ------------------------------------------------- fixed-shape / recompiles


def test_fixed_shape_insert_no_recompiles():
    """Slot churn, ring wrap, short batches, device/host priority inputs:
    none of it may register a second abstract signature on any of the
    shard's instrumented jits."""
    shard = DeviceReplayShard(64, seed=0, name="t_fixed")
    tag = shard._tag
    rng = np.random.default_rng(0)
    for i in range(40):
        n = 8 if i % 3 == 0 else 5  # short batches pad to the latched width
        items = [{"x": rng.normal(size=4).astype(np.float32)} for _ in range(n)]
        shard.add(items, (rng.random(n) + 0.1).astype(np.float32))
        if len(shard) >= 16:
            batch, idx, w = shard.sample(16)
            # Write back DEVICE arrays (the learner's TD-error path).
            shard.update_priorities(idx, w + 0.5)
    recompiles = _counters_matching(f'jit_recompiles_total{{fn="{tag}')
    assert sum(recompiles.values()) == 0, recompiles
    compiles = _counters_matching(f'jit_compiles_total{{fn="{tag}')
    assert all(v == 1.0 for v in compiles.values()), compiles
    # The ring wrapped (40 rounds of 5-8 into capacity 64) with no growth
    # in signatures; occupancy saturates at capacity.
    assert len(shard) == 64


def test_insert_width_growth_is_an_error():
    shard = DeviceReplayShard(16, name="t_grow")
    shard.add([{"x": np.float32(0)}, {"x": np.float32(1)}])
    with pytest.raises(ValueError, match="insert width grew"):
        shard.add([{"x": np.float32(i)} for i in range(3)])


def test_drain_splits_stripes_wider_than_latched_width():
    """Publishers with varying batch sizes must not blow up the fixed-shape
    insert: drain() splits stripes wider than the latched width into
    latched-width chunks, priorities sliced in lockstep."""
    r = Rpc()
    try:
        shard = DeviceReplayShard(64, alpha=1.0, name="t_split")
        svc = ReplayShardService(r, "replay_split", shard)
        # First (small, partial) publish latches the insert width at 4.
        svc._on_ingest(
            [{"x": np.float32(i)} for i in range(4)],
            np.full(4, 2.0, np.float32),
        )
        assert svc.drain() == 4
        assert shard.insert_width == 4
        # A wider stripe arrives later: split, not a ValueError inside an
        # RPC handler.
        svc._on_ingest(
            [{"x": np.float32(10 + i)} for i in range(11)],
            (np.arange(11) + 1.0).astype(np.float32),
        )
        svc._on_ingest([{"x": np.float32(30)}], np.full(1, 5.0, np.float32))
        assert svc.drain() == 12
        assert len(shard) == 16
        # Priorities landed aligned with their items (alpha=1 keeps the
        # leaf level equal to the raw clamped priorities).
        leaves = np.asarray(shard.leaf_priorities())[:16]
        expect = np.concatenate(
            [np.full(4, 2.0), np.arange(11) + 1.0, [5.0]]
        ).astype(np.float32)
        assert np.array_equal(leaves, expect)
    finally:
        r.close()


def test_update_priorities_duplicate_indices_last_wins_bitexact():
    """Stratified draws return duplicate indices routinely; the write-back
    must resolve them deterministically last-wins, exactly like the numpy
    reference's sequential ``tree[pos] = value``."""
    shard = DeviceReplayShard(32, seed=9, name="t_dup")
    ref = SumTree(32, dtype=np.float32)

    def tf(p):
        return np.asarray(shard.priority_transform(np.asarray(p, np.float32)))

    prios0 = np.ones(8, np.float32)
    idxs = shard.add([{"x": np.float32(i)} for i in range(8)], prios0)
    ref.set(np.asarray(idxs), tf(prios0))
    dup = np.asarray([3, 5, 3, 3, 7, 5, 0, 3], np.int32)
    prios = np.asarray([0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8], np.float32)
    shard.update_priorities(dup, prios)
    ref.set(dup, tf(prios))  # numpy fancy assignment: last occurrence wins
    assert np.array_equal(np.asarray(shard.tree), ref.tree)
    # Slot 3 took the LAST of its four writes, not an arbitrary one.
    assert np.asarray(shard.leaf_priorities())[3] == tf(prios)[7]


def test_cohort_overrides_never_sample_outside_local_ring():
    """The cohort-wide N only rescales importance weights: descended
    indices clip against the LOCAL occupancy, so a big cohort never lets a
    shard return never-written zero-priority slots (which would flatten
    every other weight after max-normalization)."""
    shard = DeviceReplayShard(16, seed=3, name="t_clip")
    shard.add(
        [{"x": np.float32(i)} for i in range(6)], np.ones(6, np.float32)
    )
    for _ in range(10):
        batch, idx, w = shard.sample(8, size_override=4096, total_override=512.0)
        idx, w = np.asarray(idx), np.asarray(w)
        assert ((0 <= idx) & (idx < 6)).all()
        # Uniform priorities -> uniform weights; a zero-priority row would
        # collapse everything else toward 0 after w / max(w).
        assert w.max() == pytest.approx(1.0)
        assert w.min() == pytest.approx(1.0)


# ----------------------------------------------------------- donation safety


def test_donation_safe_insert_sample_roundtrip():
    """Insert -> sample -> update in a tight loop over donated buffers:
    the data plane must keep serving correct contents (a use-after-donate
    or aliasing bug shows up as garbage rows or a runtime error)."""
    shard = DeviceReplayShard(32, seed=2, name="t_donate")
    for i in range(8):
        items = [
            {"v": np.full(3, 4 * i + j, np.float32)} for j in range(4)
        ]
        shard.add(items, np.full(4, 1e-6, np.float32))
    # Make slot 13 (value 13.0) dominate the distribution completely.
    shard.update_priorities(np.asarray([13], np.int32), np.asarray([1e6], np.float32))
    batch, idx, w = shard.sample(8)
    idx = np.asarray(idx)
    assert (idx == 13).all()
    assert np.array_equal(
        np.asarray(batch["v"]), np.full((8, 3), 13.0, np.float32)
    )
    assert np.asarray(w).max() == pytest.approx(1.0)
    # The donated tree handle the shard holds stays the live one: the
    # total reflects the written spike (1e6 ** alpha with alpha=0.6).
    assert shard.total_host() == pytest.approx(1e6**0.6, rel=0.01)


def test_concurrent_add_sample_update_is_serialized():
    """The shard service drives add (drain on the Rpc worker pool), sample,
    and the inline priority write-back (transport IO thread) concurrently;
    the per-shard mutex must serialize the donated mutations.  Hammer the
    three entry points from threads: no exceptions, consistent ring
    bookkeeping, and a sum-tree whose root still equals its leaf sum."""
    shard = DeviceReplayShard(64, seed=4, name="t_mt")
    shard.add(
        [{"x": np.zeros(4, np.float32)} for _ in range(8)],
        np.ones(8, np.float32),
    )
    errs = []
    stop = threading.Event()
    # What each thread has done, by the thread alone: the run lasts until the
    # ring has been filled over (eight adds of 8 into 64) and every sampler
    # has gone round once, however slow the machine is.
    adds, rounds = [0], [0, 0]

    def adder():
        rng = np.random.default_rng(4)
        try:
            while not stop.is_set():
                shard.add(
                    [{"x": np.zeros(4, np.float32)} for _ in range(8)],
                    (rng.random(8) + 0.1).astype(np.float32),
                )
                adds[0] += 1
        except Exception as e:  # noqa: BLE001 — the assertion payload
            errs.append(e)

    def sampler(i):
        try:
            while not stop.is_set():
                _, idx, w = shard.sample(8)
                shard.update_priorities(
                    idx, np.asarray(w).astype(np.float32) + 0.5
                )
                shard.total_host()
                rounds[i] += 1
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [
        threading.Thread(target=adder),
        threading.Thread(target=sampler, args=(0,)),
        threading.Thread(target=sampler, args=(1,)),
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60
    while not errs and (adds[0] < 8 or min(rounds) < 1):
        if time.monotonic() > deadline:
            stop.set()
            pytest.fail(f"after 60 s: {adds[0]} adds, sampler rounds {rounds}")
        time.sleep(0.01)
    stop.set()
    for t in threads:
        t.join(10)
    assert not errs, errs
    assert len(shard) == 64
    leaves = np.asarray(shard.leaf_priorities())
    assert shard.total_host() == pytest.approx(float(leaves.sum()), rel=1e-4)


# ------------------------------------------------------ two-level cohort draw


def test_two_shard_loopback_cohort_proportional():
    """Two shard services over an ipc loopback cohort: the across-shard
    pick must follow the shards' priority totals, and write-back must
    route to the owning shard."""
    host = Rpc()
    host.set_name("t-replay-cohort")
    host.listen(":0")
    addr = next(a for a in host._listen_addrs if a.startswith("ipc://"))
    spokes, services = [], []
    try:
        for i in range(2):
            r = Rpc()
            r.set_name(f"t-replay-shard{i}")
            r.set_timeout(20)
            shard = DeviceReplayShard(64, alpha=1.0, seed=i, name=f"t_coh{i}")
            services.append(
                ReplayShardService(r, "replay", shard, shard_index=i, num_shards=2)
            )
            r.connect(addr)
            spokes.append(r)
        host.set_timeout(20)

        # Load the shards directly with lopsided priority mass: shard 0
        # carries ~1/10th the total of shard 1 (alpha=1 keeps it linear).
        services[0]._shard.add(
            [{"x": np.float32(i)} for i in range(8)],
            np.full(8, 0.25, np.float32),
        )
        services[1]._shard.add(
            [{"x": np.float32(i)} for i in range(8)],
            np.full(8, 2.25, np.float32),
        )
        rep = DistributedReplay(
            rpc=host,
            remote_peers=["t-replay-shard0", "t-replay-shard1"],
            name="replay",
            seed=5,
        )
        totals = [st["total"] for st in rep.stats()]
        assert totals[1] == pytest.approx(9 * totals[0], rel=1e-5)
        assert rep.size() == 16

        picks = []
        for _ in range(200):
            batch, ref, w = rep.sample(4)
            picks.append(ref.shard)
            assert np.asarray(batch["x"]).shape == (4,)
            assert np.asarray(w).shape == (4,)
        frac1 = np.mean(np.asarray(picks) == 1)
        # Binomial(200, 0.9): ~0.021 std — gate at +-3 sigma.
        assert 0.83 < frac1 < 0.97

        # Write-back routes to the owning shard: flattening priorities to
        # 1.0 moves both shards' totals off the initial lopsided mass.
        for _ in range(20):
            batch, ref, w = rep.sample(4)
            rep.update_priorities(ref, np.full(4, 1.0, np.float32))
        t0 = [st["total"] for st in rep.stats()]
        assert t0 != pytest.approx(totals)
        assert t0[1] < totals[1]  # the heavy shard lost mass
        assert t0[0] > totals[0]  # the light shard gained it
    finally:
        for r in spokes:
            r.close()
        host.close()


def test_local_cohort_weights_use_global_correction():
    """A single local shard sampled through the cohort with an inflated
    global total must see its importance weights relabeled to the global
    distribution (bigger total -> smaller P(i) -> relatively larger raw
    weights, max-normalized to 1)."""
    shard = DeviceReplayShard(16, alpha=1.0, beta=1.0, seed=0, name="t_gw")
    shard.add(
        [{"x": np.float32(i)} for i in range(8)],
        np.asarray([1, 1, 1, 1, 1, 1, 1, 9], np.float32),
    )
    b_local, idx_l, w_local = shard.sample(8)
    b_glob, idx_g, w_glob = shard.sample(8, size_override=32, total_override=64.0)
    # Identical tree, so identical index distributions are drawn from the
    # same stratification; weights scale by the override inputs only.
    assert np.asarray(w_local).max() == pytest.approx(1.0)
    assert np.asarray(w_glob).max() == pytest.approx(1.0)
    # w ratio between two sampled slots depends only on their priorities,
    # not on the override (the override cancels under max-normalization
    # within a draw) — but N enters the unnormalized magnitude; check the
    # normalized shape is priority-consistent: the heavy slot gets the
    # smallest weight in both draws.
    for idx, w in ((idx_l, w_local), (idx_g, w_glob)):
        idx, w = np.asarray(idx), np.asarray(w)
        if (idx == 7).any() and (idx != 7).any():
            assert w[idx == 7].max() < w[idx != 7].min()


# ------------------------------------------------------- write-once ingest


def test_memfd_ingest_write_once_bytes():
    """One publish to a 2-shard same-host cohort: the payload must be
    counted out exactly once (memfd multicast), the stripes must
    partition the items, and drain() must land them in the device rings."""
    hub = Rpc()
    hub.set_name("t-replay-pub")
    hub.listen(":0")
    addr = next(a for a in hub._listen_addrs if a.startswith("ipc://"))
    rng = np.random.default_rng(0)
    # 32 x [21, 512] f32 ~ 1.4 MB: over the 1 MB memfd multicast floor.
    items = [
        {"state": rng.normal(size=(21, 512)).astype(np.float32)}
        for _ in range(32)
    ]
    per_publish = payload_bytes(items)
    assert per_publish > 1024 * 1024

    spokes, services = [], []
    try:
        for i in range(2):
            r = Rpc()
            r.set_name(f"t-ingest-shard{i}")
            services.append(
                ReplayShardService(
                    r,
                    "replay",
                    DeviceReplayShard(64, name=f"t_ing{i}"),
                    shard_index=i,
                    num_shards=2,
                )
            )
            r.connect(addr)
            spokes.append(r)
        pub = ReplayPublisher(
            hub, ["t-ingest-shard0", "t-ingest-shard1"], "replay"
        )
        deadline = time.time() + 10
        while not pub.multicast_ready() and time.time() < deadline:
            time.sleep(0.01)
        assert pub.multicast_ready()

        out0 = _counter('replay_bytes_total{direction="ingest_out"}')
        in0 = _counter('replay_bytes_total{direction="ingest_in"}')
        for _ in range(3):
            pub.publish(items).result(20)
        out_delta = _counter('replay_bytes_total{direction="ingest_out"}') - out0
        in_delta = _counter('replay_bytes_total{direction="ingest_in"}') - in0
        # Write-once: counted once per publish, NOT once per consumer.
        assert out_delta == 3 * per_publish
        # The two stripes partition the items exactly.
        assert in_delta == 3 * per_publish
        assert services[0].drain() == 3 * 16
        assert services[1].drain() == 3 * 16
        assert len(services[0]._shard) == 48
        assert len(services[1]._shard) == 48
        # Stripe contents survived adoption: shard 0 holds the even items.
        b, idx, _ = services[0]._shard.sample(4)
        got = np.asarray(b["state"])
        evens = np.stack([items[2 * i]["state"] for i in range(16)])
        for row in got:
            assert any(np.array_equal(row, e) for e in evens)
    finally:
        for r in spokes:
            r.close()
        hub.close()
