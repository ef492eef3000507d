"""Resilient serving plane (moolib_tpu/serving.py; docs/RESILIENCE.md).

The plane's claims, each pinned by a deterministic scenario instead of a
churn loop:

- hot swap: staged weights install *between* service iterations — a swap
  mid-traffic never errors or drops a request;
- admission control: a request that cannot meet its deadline is rejected
  *immediately* with a typed overload error, not after a transport timeout;
- dedup: a retry racing a slow reply attaches to the in-flight computation
  (and a completed one answers from the done-cache) — the step function
  runs once per logical request, even under seeded frame duplication;
- blast radius: one poisoned request in a dynamic batch fails only its own
  caller (the batch retries unbatched);
- failover: a replica dying mid-stream costs latency, never a lost request
  — every client future completes on a survivor.

Everything here is numpy + the real RPC engine over loopback (no jax in
the serving plane, by design); the subprocess SIGKILL variant lives in
``scripts/serve_soak.py`` (CI runs ``--smoke``).
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from moolib_tpu import Broker, Group, Rpc, RpcError
from moolib_tpu.serving import (
    AdmissionController,
    BrokerUnreachableError,
    ModelPublisher,
    ServeClient,
    ServeOverloadError,
    ServeReplica,
    ServeService,
    bucket,
    bucket_shapes,
    is_overload_error,
)
from moolib_tpu.testing.faults import FaultPlan


def addr_of(rpc: Rpc) -> str:
    return next(
        a for a in rpc._listen_addrs if a.startswith("tcp://127")
    ).replace("tcp://", "")


def scale_step(scale: float):
    """step_fn multiplying each row by ``params['scale']`` — output carries
    the serving version, so a test can see *which* weights answered."""

    def step(params, batch):
        return np.asarray(batch, dtype=np.float64) * params["scale"]

    return step


class ServiceHarness:
    """One ServeService on a listening Rpc, its loop on a daemon thread."""

    def __init__(self, step_fn, params, *, name="generate", **kw):
        self.rpc = Rpc()
        self.rpc.set_name(kw.pop("peer_name", "server"))
        self.rpc.listen("127.0.0.1:0")
        self.service = ServeService(self.rpc, step_fn, params, name=name, **kw)
        self.addr = addr_of(self.rpc)
        self._thread = None

    def start(self, total=None):
        self._thread = threading.Thread(
            target=lambda: asyncio.run(self.service.loop(total=total)),
            daemon=True,
        )
        self._thread.start()
        return self

    def close(self):
        self.service.close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        self.rpc.close()


# ---------------------------------------------------------------- admission
def test_admission_controller_estimates_and_rejects():
    ac = AdmissionController(max_queue=4, batch_size=2)
    # No EMA yet: only queue_full applies.
    assert ac.admit(0, deadline_s=0.001) is None
    assert ac.admit(4, deadline_s=None) == "queue_full"
    ac.note_service(0.1)
    assert ac.ema_batch_seconds() == pytest.approx(0.1)
    # depth 3 -> ceil(4/2)=2 batches ahead + 1 in service = 0.3s.
    assert ac.estimate_wait(3) == pytest.approx(0.3)
    assert ac.admit(3, deadline_s=0.2) == "deadline"
    assert ac.admit(3, deadline_s=1.0) is None
    # EMA is exponential, not a mean.
    ac.note_service(0.5)
    assert ac.ema_batch_seconds() == pytest.approx(0.1 + 0.25 * 0.4)


def test_admission_one_stalled_sample_does_not_reject_the_next_request():
    """A decode step that straddled a host stall reports seconds-per-token a
    hundred times the EMA (PERF.md section 7: the refusals of PRs 26, 27).
    One such sample may move the estimate by at most its own size, so the
    next request's deadline still holds; a lasting slowdown is still seen."""
    ac = AdmissionController(max_queue=256, per_token=True,
                             pending_tokens=lambda: 2000)
    for _ in range(8):
        ac.note_service(0.0092, tokens=4)  # 2.3 ms a token
    assert ac.ema_batch_seconds() == pytest.approx(0.0023)
    assert ac.admit(0, deadline_s=96.0) is None  # 2000 x 2.3 ms = 4.6 s
    ac.note_service(1.2, tokens=4)  # the stalled step: 0.3 s a token
    assert ac.ema_batch_seconds() <= 2 * 0.0023
    assert ac.admit(0, deadline_s=96.0) is None
    # Unbounded, the same sample read 0.0023 + 0.25 * 0.2977 = 0.0767 s a
    # token, 153 s for the pending tokens: a typed reject.
    for _ in range(40):  # a slowdown that lasts is followed
        ac.note_service(1.2, tokens=4)
    assert ac.ema_batch_seconds() == pytest.approx(0.3, rel=0.05)
    assert ac.admit(0, deadline_s=96.0) == "deadline"


def test_bucket_policy_canonical_in_serving():
    assert [bucket(n, 16) for n in (1, 2, 3, 5, 9, 16, 40)] == [
        1, 2, 4, 8, 16, 16, 16,
    ]
    assert sorted(bucket_shapes(16)) == [1, 2, 4, 8, 16]
    # lm_serve must alias THIS policy (one definition; warmup enumerates it).
    from moolib_tpu.examples import lm_serve

    assert lm_serve._bucket is bucket
    assert lm_serve._bucket_shapes is bucket_shapes


# ------------------------------------------------------------------ service
def test_serve_basic_roundtrip_and_stats():
    h = ServiceHarness(scale_step(1.0), {"scale": 2.0}, batch_size=4).start()
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        cl = ServeClient(client, fn="generate", replicas=["server"],
                         deadline_s=10.0)
        out = cl.call(np.arange(4.0))
        np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 2.0)
        st = client.sync("server", "generate_stats")
        assert st["served"] == 1
        assert st["model_version"] == 0
        assert st["ema_batch_seconds"] is not None
        cl.close()
    finally:
        client.close()
        h.close()


def test_hot_swap_mid_traffic_zero_errors():
    h = ServiceHarness(scale_step(1.0), {"scale": 1.0}, batch_size=4).start()
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        cl = ServeClient(client, fn="generate", replicas=["server"],
                         deadline_s=10.0)
        futs = []
        swapped = False
        for i in range(40):
            futs.append(cl.submit(np.ones(3)))
            if i == 15 and not swapped:
                announced = time.monotonic()
                assert h.service.stage(5, {"scale": 10.0}, announced)
                swapped = True
            time.sleep(0.002)
        results = [np.asarray(f.result(10.0)) for f in futs]  # no errors
        scales = sorted({float(r[0]) for r in results})
        assert scales[0] == 1.0 and scales[-1] == 10.0  # both versions served
        st = h.service.stats()
        assert st["hot_swaps"] == 1
        assert st["model_version"] == 5
        assert st["last_swap_seconds"] is not None and st["last_swap_seconds"] >= 0
        # Staging an older version is a no-op (stale announcement).
        assert not h.service.stage(3, {"scale": -1.0})
        cl.close()
    finally:
        client.close()
        h.close()


def test_admission_rejects_are_immediate_and_typed():
    # Slow model (~0.15 s/batch), batch_size 1: the EMA makes the wait
    # estimate honest, so a 50 ms deadline behind two queued batches is
    # hopeless (estimate >= 0.45 s) — but still wide enough that the
    # client's own pre-attempt expiry check can't race the dispatch.
    def slow(params, batch):
        time.sleep(0.15)
        return np.asarray(batch)

    h = ServiceHarness(slow, {}, batch_size=1, dynamic_batching=False,
                       max_queue=2).start()
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        cl = ServeClient(client, fn="generate", replicas=["server"],
                         deadline_s=10.0)
        cl.call(np.ones(2))  # prime the EMA
        blockers = [cl.submit(np.ones(2)) for _ in range(2)]
        t0 = time.monotonic()
        with pytest.raises(ServeOverloadError) as ei:
            cl.call(np.ones(2), deadline_s=0.05)
        elapsed = time.monotonic() - t0
        assert elapsed < 5.0  # typed reject, not a transport timeout
        assert is_overload_error(ei.value)
        for f in blockers:  # admitted requests still complete
            f.result(10.0)
        st = h.service.stats()
        assert st["admission_rejects"] >= 1
        assert cl.stats()["overload"] == 1
        cl.close()
    finally:
        client.close()
        h.close()


def test_queue_full_rejects_without_ema():
    h = ServiceHarness(scale_step(1.0), {"scale": 1.0}, max_queue=3,
                       batch_size=4)
    # Loop NOT started: requests pile up at admission.
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        futs = [client.async_("server", "generate", np.ones(2))
                for _ in range(3)]
        time.sleep(0.3)  # let all three enqueue
        with pytest.raises(Exception) as ei:
            client.sync("server", "generate", np.ones(2))
        assert is_overload_error(ei.value)
        assert "queue_full" in str(ei.value)
        h.start(total=3)
        for f in futs:
            f.result(10.0)
    finally:
        client.close()
        h.close()


def test_deadline_miss_is_counted_not_dropped():
    def slow(params, batch):
        time.sleep(0.2)
        return np.asarray(batch)

    h = ServiceHarness(slow, {}, batch_size=1, dynamic_batching=False).start()
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        # No EMA yet -> admitted despite the hopeless deadline; the answer
        # still arrives (late), and the miss is accounted.
        out = client.sync("server", "generate", np.ones(2), deadline_s=0.01,
                          req_id="r-late")
        np.testing.assert_allclose(np.asarray(out), np.ones(2))
        assert h.service.stats()["deadline_misses"] == 1
    finally:
        client.close()
        h.close()


# -------------------------------------------------------------------- dedup
def test_req_id_dedup_inflight_and_done_cache():
    calls = []

    def step(params, batch):
        calls.append(np.asarray(batch).shape[0])
        time.sleep(0.15)  # wide race window for the retry
        return np.asarray(batch)

    h = ServiceHarness(step, {}, batch_size=4).start()
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        f1 = client.async_("server", "generate", np.ones(3), req_id="r-1")
        time.sleep(0.05)  # original admitted / in service
        f2 = client.async_("server", "generate", np.ones(3), req_id="r-1")
        np.testing.assert_allclose(np.asarray(f1.result(10.0)), np.ones(3))
        np.testing.assert_allclose(np.asarray(f2.result(10.0)), np.ones(3))
        time.sleep(0.1)
        # Done-cache: a third retry after completion answers immediately.
        f3 = client.async_("server", "generate", np.ones(3), req_id="r-1")
        np.testing.assert_allclose(np.asarray(f3.result(10.0)), np.ones(3))
        assert calls == [1]  # ONE step call, one row: never re-served
        assert h.service.stats()["dedup_hits"] == 2
    finally:
        client.close()
        h.close()


def test_dedup_under_seeded_frame_duplication():
    served = []

    def step(params, batch):
        arr = np.asarray(batch)
        served.extend(float(x) for x in arr[:, 0])
        return arr

    # pad_buckets off: padding repeats the last row, which would alias a
    # legitimate re-serve in this row-count assertion.
    h = ServiceHarness(step, {}, batch_size=8, pad_buckets=False).start()
    plan = FaultPlan(seed=11)
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        with plan.frame_faults(dup=0.3, hold=0.1):
            cl = ServeClient(client, fn="generate", replicas=["server"],
                             deadline_s=15.0)
            futs = [cl.submit(np.full(2, float(i))) for i in range(20)]
            results = [np.asarray(f.result(15.0)) for f in futs]
        for i, r in enumerate(results):
            np.testing.assert_allclose(r, np.full(2, float(i)))
        # Exactly-once per logical request: duplicated frames (receiver
        # dedup) and client retries (serving req_id dedup) never re-serve.
        assert sorted(served) == [float(i) for i in range(20)]
        cl.close()
    finally:
        client.close()
        h.close()


# ------------------------------------------------------------- blast radius
def test_poisoned_request_fails_only_its_caller():
    POISON = -7.0

    def step(params, batch):
        arr = np.asarray(batch)
        if (arr == POISON).any():
            raise ValueError("poisoned row")
        return arr * 2.0

    h = ServiceHarness(step, {}, batch_size=8)
    client = Rpc()
    client.set_name("cli")
    client.connect(h.addr)
    try:
        futs = [client.async_("server", "generate", np.full(2, float(i)))
                for i in range(3)]
        bad = client.async_("server", "generate", np.full(2, POISON))
        time.sleep(0.3)  # everything queues into ONE dynamic batch
        h.start(total=4)
        for i, f in enumerate(futs):
            np.testing.assert_allclose(np.asarray(f.result(10.0)),
                                       np.full(2, float(i) * 2.0))
        with pytest.raises(Exception, match="poisoned"):
            bad.result(10.0)
        st = h.service.stats()
        assert st["batch_retries"] == 1
    finally:
        client.close()
        h.close()


# ---------------------------------------------------- discovery + failover
def make_broker(port: int):
    broker = Broker()
    broker.set_name("broker")
    broker.listen(f"127.0.0.1:{port}")
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            broker.update()
            stop.wait(0.05)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    return broker, stop


def make_replica(peer_name: str, broker_addr: str, scale: float,
                 publisher=None):
    rpc = Rpc()
    rpc.set_name(peer_name)
    rpc.listen("127.0.0.1:0")
    rep = ServeReplica(
        rpc, scale_step(1.0), {"scale": scale}, name="generate",
        batch_size=4, broker=broker_addr, publisher=publisher,
        poll_interval=0.1,
    )
    t = threading.Thread(target=lambda: asyncio.run(rep.loop()), daemon=True)
    t.start()
    return rpc, rep, t


def test_observer_registration_does_not_touch_member_epoch(free_port):
    broker, stop = make_broker(free_port)
    addr = f"127.0.0.1:{free_port}"
    member_rpc = Rpc()
    member_rpc.set_name("member0")
    member_rpc.listen("127.0.0.1:0")
    member_rpc.connect(addr)
    g = Group(member_rpc, "serve")
    rep_rpc = rep = rep_t = None
    try:
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and not g.active():
            g.update()
            time.sleep(0.02)
        assert g.active()
        epoch = g.sync_id()
        rep_rpc, rep, rep_t = make_replica("rep0", addr, 3.0)
        cl = ServeClient(broker=addr, deadline_s=10.0)
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            g.update()
            if cl.replicas() == ["rep0"]:
                break
            time.sleep(0.02)
        assert cl.replicas() == ["rep0"]  # discovered through __broker_list
        g.update()
        assert g.sync_id() == epoch      # observer never bumped the epoch
        assert g.members() == ["member0"]  # and never joined membership
        out = np.asarray(cl.call(np.ones(2)))
        np.testing.assert_allclose(out, np.ones(2) * 3.0)
        cl.close()
    finally:
        if rep is not None:
            rep.close()
        if rep_rpc is not None:
            rep_rpc.close()
        member_rpc.close()
        stop.set()
        broker.close()


def test_failover_replica_death_loses_no_requests(free_port):
    broker, stop = make_broker(free_port)
    addr = f"127.0.0.1:{free_port}"
    r0 = make_replica("rep0", addr, 1.0)
    r1 = make_replica("rep1", addr, 1.0)
    cl = ServeClient(broker=addr, deadline_s=20.0, attempt_timeout=1.0)
    try:
        cl.wait_for_replicas(2, timeout=15.0)
        futs = [cl.submit(np.full(2, float(i))) for i in range(12)]
        # Abrupt death mid-stream: close rep0's engine out from under its
        # in-flight batch (the in-process stand-in for SIGKILL; the real
        # signal variant is scripts/serve_soak.py).
        r0[0].close()
        more = [cl.submit(np.full(2, float(12 + i))) for i in range(6)]
        for i, f in enumerate(futs + more):
            np.testing.assert_allclose(np.asarray(f.result(25.0)),
                                       np.full(2, float(i)))
        st = cl.stats()
        assert st["error"] == 0 and st["deadline"] == 0  # zero lost requests
        cl.close()
    finally:
        stop.set()
        for rpc, rep, _t in (r0, r1):
            try:
                rep.close()
            except Exception:
                pass
            rpc.close()
        broker.close()


# ----------------------------------------------------- publisher hot path
def test_publisher_subscriber_hot_swap_two_replicas(free_port):
    broker, stop = make_broker(free_port)
    addr = f"127.0.0.1:{free_port}"
    pub_rpc = Rpc()
    pub_rpc.set_name("pusher")
    pub_rpc.listen("127.0.0.1:0")
    pub = ModelPublisher(pub_rpc, name="model")
    r0 = make_replica("rep0", addr, 1.0, publisher="pusher")
    r1 = make_replica("rep1", addr, 1.0, publisher="pusher")
    # Replicas reach "pusher" by name through the broker's gossip.
    pub_rpc.connect(addr)
    cl = ServeClient(broker=addr, deadline_s=20.0)
    try:
        cl.wait_for_replicas(2, timeout=15.0)
        np.testing.assert_allclose(np.asarray(cl.call(np.ones(2))), np.ones(2))
        pub.publish({"scale": 9.0}, version=4)
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if all(r.service.model_version() == 4 for _, r, _t in (r0, r1)):
                break
            time.sleep(0.05)
        assert all(r.service.model_version() == 4 for _, r, _t in (r0, r1))
        for _, rep, _t in (r0, r1):
            st = rep.service.stats()
            assert st["hot_swaps"] == 1
            assert st["last_swap_seconds"] is not None
        np.testing.assert_allclose(np.asarray(cl.call(np.ones(2))),
                                   np.ones(2) * 9.0)
        cl.close()
    finally:
        stop.set()
        for rpc, rep, _t in (r0, r1):
            rep.close()
            rpc.close()
        pub.close()
        pub_rpc.close()
        broker.close()


# ------------------------------------------------------------- fault plan
def test_replica_kill_schedule_is_seeded():
    a, b = FaultPlan(seed=7), FaultPlan(seed=7)
    ta, tb = a.replica_kill_time(10.0), b.replica_kill_time(10.0)
    assert ta == tb
    assert 2.5 <= ta <= 7.5  # middle half: always mid-stream
    assert FaultPlan(seed=8).replica_kill_time(10.0) != ta

    class FakeProc:
        def __init__(self, pid):
            self.pid = pid

    import os

    procs = [FakeProc(os.getpid()), FakeProc(os.getpid())]
    idx = a.replica_kill(procs, sig=0)  # sig 0: existence probe, no kill
    assert idx == b.replica_kill(procs, sig=0)
    assert a.actions[-1][0] == "replica_kill"


# ------------------------------------------------- broker HA (ISSUE 10)
def make_ha_brokers(promote_grace=1.0, replicate_interval=0.1):
    """Primary + hot-standby broker pair, each pumped on a daemon thread
    (a closed broker's pump just absorbs the shutdown errors)."""
    from conftest import grab_port

    addr0 = f"127.0.0.1:{grab_port()}"
    addr1 = f"127.0.0.1:{grab_port()}"
    b0 = Broker()
    b0.set_name("broker0")
    b1 = Broker(standby=True)
    b1.set_name("broker1")
    stop = threading.Event()
    for b, addr, other in ((b0, addr0, addr1), (b1, addr1, addr0)):
        b.set_promote_grace(promote_grace)
        b.set_replicate_interval(replicate_interval)
        b.listen(addr)
        b.set_peer_brokers([other])

        def pump(b=b):
            while not stop.is_set():
                try:
                    b.update()
                except Exception:  # noqa: BLE001 - closed mid-test
                    pass
                stop.wait(0.05)

        threading.Thread(target=pump, daemon=True).start()
    return (b0, addr0), (b1, addr1), stop


def test_serve_client_discovery_fails_over_to_standby():
    """ISSUE 10 satellite: ServeClient discovery re-resolves from the broker
    ADDRESS LIST.  When the primary dies, the refresh loop suspects it and
    reads the roster from the standby's replicated state (then from it as
    the new primary) — replicas stay discoverable and calls keep landing."""
    from moolib_tpu import telemetry

    (b0, addr0), (b1, addr1), stop = make_ha_brokers()
    rpc = Rpc()
    rpc.set_name("rep0")
    rpc.listen("127.0.0.1:0")
    rep = ServeReplica(
        rpc, scale_step(1.0), {"scale": 2.0}, name="generate", batch_size=4,
        brokers=[addr0, addr1], poll_interval=0.1,
    )
    rep._group.set_broker_fail_after(1.5)
    t = threading.Thread(target=lambda: asyncio.run(rep.loop()), daemon=True)
    t.start()
    failovers = telemetry.get_registry().counter(
        "serve_client_broker_failovers_total", "").labels()
    before = failovers.get()
    cl = ServeClient(brokers=[addr0, addr1], deadline_s=20.0,
                     attempt_timeout=2.0, refresh_interval=0.2,
                     broker_unreachable_after=8.0)
    try:
        cl.wait_for_replicas(1, timeout=20.0)
        assert cl.replicas() == ["rep0"]
        np.testing.assert_allclose(np.asarray(cl.call(np.ones(2))),
                                   np.ones(2) * 2.0)
        assert cl._broker_addr == addr0

        b0.close()  # primary dies mid-serve
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if cl._broker_addr == addr1 and b1.is_primary:
                break
            time.sleep(0.05)
        assert cl._broker_addr == addr1, "discovery never failed over"
        assert b1.is_primary, "standby never promoted"
        assert failovers.get() > before
        assert cl.replicas() == ["rep0"]  # roster survived the failover
        np.testing.assert_allclose(np.asarray(cl.call(np.ones(2))),
                                   np.ones(2) * 2.0)
        st = cl.stats()
        assert st["error"] == 0 and st["deadline"] == 0
        cl.close()
    finally:
        stop.set()
        rep.close()
        rpc.close()
        b0.close()
        b1.close()


def test_broker_unreachable_typed_error():
    """ISSUE 10 satellite: every broker in the list dead + empty roster ->
    a typed BrokerUnreachableError (an RpcError subclass), fast — never a
    silent deadline burn."""
    from conftest import grab_port

    dead = [f"127.0.0.1:{grab_port()}", f"127.0.0.1:{grab_port()}"]
    cl = ServeClient(brokers=dead, deadline_s=6.0, refresh_interval=0.1,
                     broker_unreachable_after=0.5)
    try:
        assert issubclass(BrokerUnreachableError, RpcError)
        t0 = time.monotonic()
        with pytest.raises(BrokerUnreachableError):
            cl.wait_for_replicas(1, timeout=15.0)
        assert time.monotonic() - t0 < 10.0
        with pytest.raises(BrokerUnreachableError):
            cl.submit(np.ones(2)).result(15.0)
    finally:
        cl.close()
