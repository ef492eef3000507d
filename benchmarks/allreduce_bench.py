"""Allreduce bandwidth benchmark — RPC tree (DCN) and XLA psum (ICI).

Counterpart of the reference's multi-node benchmark
(``test/test_multinode_allreduce.cc:16-181``: WORLD_SIZE/RANK env vars,
chunked ring allreduce over raw RPC, throughput per payload size).  Two
modes:

- ``rpc``: N peers + broker (single process by default, or one rank per
  process via WORLD_SIZE/RANK/BROKER_ADDR env vars like the reference)
  running the elastic binary-tree allreduce over loopback/DCN.  The tree
  rows ride the flat-bucket data plane (zero-copy serialization, in-place
  combine, memfd-multicast share — docs/DESIGN.md "Gradient data plane");
  ``--legacy`` adds rows on the old per-leaf path for comparison.
- ``ici``: jitted ``psum`` over every local device — the TPU data plane the
  reference never had. On one chip this measures HBM-loopback; on a slice
  it measures real ICI collective bandwidth.

Timing: one untimed warmup op per row (first use compiles codecs, dials
transport upgrades, faults fresh buffers), then the MEDIAN of per-iteration
wall times — so bucket-size sweeps compare medians, not means skewed by a
cold first iteration.

Knobs: ``--bucket_bytes N`` sets the flat-bucket size for the sweep (0 =
payload-sized buckets: one bucket per op, the loopback single-core optimum;
production multi-core hosts pipeline with the 4 MiB default).  ``--wire
q8`` adds int8-compressed rows.  ``--grad_tree`` shapes each payload as a
transformer-like gradient pytree instead of one flat array (exercises the
tree-flatten staging path).  Non-legacy tree rows run the Accumulator's
``owned=True`` contract (in-place folds, read-only memfd-adopted result
views — the gradient data plane as trained code exercises it);
``--no_owned`` measures the copying public default.  ``--smoke`` runs a
fast correctness pass (bucketed vs legacy vs owned vs numpy reference,
tree + ring + q8) and prints a loopback bandwidth line — scripts/ci.sh
runs it.

Prints one line per size: elements, MB, milliseconds, MB/s (bytes, not the
reference's ambiguous "M/s" element count).  max_peer_tx counts LOGICAL
per-peer payload bytes (a memfd-multicast share writes those bytes once but
accounts them on every receiver's connection).

``--sharded`` A/Bs the sharded hierarchical gradient plane (docs/DESIGN.md
§6d: reduce-scatter between hosts + owner redistribution) against the
legacy full-tree plane over a REAL Accumulator cohort — the sharded plane
is Accumulator protocol, not a raw ``Group.all_reduce`` option, so the arm
drives the trained gradient path end to end.  Each row adds the per-host
DCN gradient bytes per round (``accum_interhost_bytes_total{kind="grad"}``):
the sharded claim is that column, (N-1)/N of the payload per host vs the
full payload on the legacy plane.  ``--sharded --smoke`` is the CI gate:
bit-exactness vs the legacy plane AND a numpy reference, plus the byte
ratio bound — single process by default, or one rank per process via
WORLD_SIZE/RANK/BROKER_ADDR (scripts/ci.sh runs the 2-process form so the
inter-host byte drop is measured across real process boundaries).

``--overlap`` A/Bs the streaming gradient pipeline (docs/DESIGN.md §6e:
buckets launch into the inter-host allreduce while backward is still
producing gradients) against the barrier plane over the same real
Accumulator cohort.  Each round simulates a ``--compute_ms`` backward that
delivers gradient leaves tail-first at an even pace; the claim is the
``exposed_ms`` column — comm left after the LAST gradient is ready — which
streaming cuts to the final bucket's tail where the barrier arm pays the
whole allreduce.  ``--overlap --smoke`` is the CI gate: bit-exactness
streaming vs barrier vs numpy, positive launch lead for every non-final
bucket (``accum_bucket_launch_lead_seconds``), and exposed comm per step
<= 0.5x barrier at the 10 MB tree — same WORLD_SIZE/RANK/BROKER_ADDR
2-process contract as the sharded smoke.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _grad_tree(rng, size):
    """A transformer-ish gradient pytree with ~``size`` total f32 elements
    (a few big matrices, some vectors) — the tree-flatten staging workload."""
    leaves = {}
    remaining = size
    i = 0
    while remaining > 0:
        if remaining > 4096:
            side = int(min(np.sqrt(remaining // 2), 2048))
            n = side * side
            leaves[f"w{i}"] = rng.standard_normal(n).astype(np.float32).reshape(side, side)
        else:
            n = remaining
            leaves[f"b{i}"] = rng.standard_normal(n).astype(np.float32)
        remaining -= n
        i += 1
    return leaves


def _tree_elems(t):
    return sum(int(np.asarray(l).size) for l in t.values()) if isinstance(t, dict) else t.size


class _Cohort:
    """N peers + broker on loopback (or one rank per process)."""

    def __init__(self, args):
        from moolib_tpu import Broker, Group, Rpc

        world_size = int(os.environ.get("WORLD_SIZE", args.world_size))
        rank = os.environ.get("RANK")
        broker_addr = os.environ.get("BROKER_ADDR", args.broker_addr)
        self.world_size = world_size
        self.broker = None
        self.peers = []
        if rank is None:
            # Single-process cohort (the reference's loopback test pattern).
            self.broker = Broker()
            self.broker.set_name("broker")
            self.broker.listen(broker_addr)
            for i in range(world_size):
                rpc = Rpc()
                rpc.set_name(f"rank{i}")
                # Bare ":0" listens on TCP *and* an auto unix socket, so
                # same-host peers discover the ipc listener and big frames
                # ride memfd.
                rpc.listen(":0")
                rpc.connect(broker_addr)
                g = Group(rpc, "bench")
                g.set_timeout(60)
                self.peers.append((rpc, g))
        else:
            # Multi-process/multi-host mode (the reference's env-var pattern,
            # test/test_multinode_allreduce.cc:155-181): one process per
            # rank, rank 0 hosts the broker.  Every rank runs the same rows.
            rank = int(rank)
            if rank == 0:
                self.broker = Broker()
                self.broker.set_name("broker")
                host, _, port = broker_addr.rpartition(":")
                self.broker.listen(
                    f":{port}" if host in ("", "127.0.0.1", "0.0.0.0") else broker_addr
                )
            rpc = Rpc()
            rpc.set_name(f"rank{rank}")
            rpc.listen(":0")
            rpc.connect(broker_addr)
            g = Group(rpc, "bench")
            g.set_timeout(120)
            self.peers.append((rpc, g))
        self.groups = [g for _, g in self.peers]

    def pump(self):
        if self.broker is not None:
            self.broker.update()
        for g in self.groups:
            g.update()

    def converge(self):
        deadline = time.time() + 120
        ok = lambda: all(  # noqa: E731
            g.active() and len(g.members()) == self.world_size for g in self.groups
        )
        while not ok() and time.time() < deadline:
            self.pump()
            time.sleep(0.01)
        assert ok(), f"cohort never converged: {[g.members() for g in self.groups]}"

    def wait(self, futs):
        """Event-driven wait: block on the first pending future's event (the
        IO engines complete ops on their own threads) with a short timeout
        so the broker ping / timeout sweep keeps running."""
        while True:
            pending = [f for f in futs if not f.done()]
            if not pending:
                return
            self.pump()
            try:
                pending[0].wait(0.003)
            except TimeoutError:
                pass

    def close(self):
        for rpc, _ in self.peers:
            rpc.close()
        if self.broker is not None:
            self.broker.close()


def _allreduce_kwargs(algo, wire, legacy, owned=True):
    kw = {}
    if algo == "ring":
        kw["chunked"] = True
        if wire:
            kw["wire"] = wire
    else:
        kw["chunked"] = False
        if legacy:
            kw["bucketed"] = False
        else:
            # The gradient data plane's contract: the Accumulator hands its
            # staged flats over with owned=True (folds may accumulate in
            # place, results may be read-only adopted views) — that is what
            # unlocks the memfd-adopt zero-copy share terminus the headline
            # number measures.  --no_owned measures the copying public
            # default instead.
            if owned:
                kw["owned"] = True
            if wire:
                kw["bucketed"] = True
                kw["wire"] = wire
        # else: auto (bucketed above MOOLIB_BUCKET_THRESHOLD)
    return kw


def bench_rpc(args):
    import moolib_tpu.buckets as buckets

    if args.bucket_bytes == 0:
        # Payload-sized buckets: one bucket per op.  On a single-core
        # loopback box the per-bucket pipeline cannot overlap, so the
        # fixed per-bucket cost is pure loss; production multi-core hosts
        # use the 4 MiB default for staging/wire overlap.
        buckets.set_bucket_bytes(1 << 31)
        bucket_note = "payload-sized"
    else:
        buckets.set_bucket_bytes(args.bucket_bytes)
        bucket_note = f"{args.bucket_bytes} B"

    cohort = _Cohort(args)
    cohort.converge()
    peers, groups = cohort.peers, cohort.groups
    rng = np.random.default_rng(0)

    def run_rows(algo: str, wire=None, legacy=False):
        # chunked= forces the path: the auto rule (Group.ring_auto) would
        # keep a same-host loopback cohort on the tree, and the bench's job
        # is to measure BOTH algorithms wherever it runs.
        mode = f"{algo}{'+q8' if wire == 'q8' else ''}{' legacy' if legacy else ''}"
        shape = "grad-tree" if args.grad_tree else "flat array"
        contract = "owned" if (not legacy and not args.no_owned) else "copying"
        print(
            f"# rpc {mode} allreduce, {cohort.world_size} peers, loopback, "
            f"{shape}, buckets={bucket_note}, {contract} contract "
            f"(max_peer_tx = busiest peer's LOGICAL payload bytes per op; "
            f"memfd-multicast shares write them once)"
        )
        print(f"{'elems':>10} {'MB':>8} {'ms':>9} {'MB/s':>10} {'max_peer_tx_MB':>15}")
        kw = _allreduce_kwargs(algo, wire, legacy, owned=not args.no_owned)
        for size in args.sizes:
            if args.grad_tree:
                data = [_grad_tree(rng, size) for _ in peers]
            else:
                data = [rng.standard_normal(size).astype(np.float32) for _ in peers]
            futs = [
                g.all_reduce("w" + mode, d, **kw) for g, d in zip(groups, data)
            ]
            cohort.wait(futs)  # warmup op: codec compiles, transport upgrades
            before = [rpc.transport_stats()["tx_bytes"] for rpc, _ in peers]
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                futs = [
                    g.all_reduce("x" + mode, d, **kw) for g, d in zip(groups, data)
                ]
                cohort.wait(futs)
                for f in futs:
                    f.result(0)
                times.append(time.perf_counter() - t0)
            # Median-of-iters: a straggler iteration (GC pause, page-cache
            # churn) must not skew a bucket-size sweep.
            dt = statistics.median(times)
            after = [rpc.transport_stats()["tx_bytes"] for rpc, _ in peers]
            local_max = max(a - b for a, b in zip(after, before)) / args.iters / 1e6
            # The busiest-PEER number must span the whole cohort: in
            # multi-process mode each process sees only its own counters, so
            # max-allreduce the local figure (tiny scalar, tree path).
            mfuts = [
                g.all_reduce(f"tx{mode}{size}", local_max, op=lambda a, b: max(a, b))
                for g in groups
            ]
            cohort.wait(mfuts)
            max_tx = max(f.result(0) for f in mfuts)
            mb = size * 4 / 1e6
            print(
                f"{size:>10} {mb:>8.2f} {dt*1e3:>9.2f} {mb/dt:>10.1f} {max_tx:>15.2f}"
            )

    run_rows("tree")
    run_rows("ring")
    if args.wire in ("q8", "both"):
        run_rows("tree", wire="q8")
        run_rows("ring", wire="q8")
    if args.legacy:
        run_rows("tree", legacy=True)
    # Exit barrier: no rank tears down while another is mid-row.
    cohort.wait([g.all_reduce("bye", 1) for g in groups])
    cohort.close()


def bench_smoke(args):
    """Fast correctness pass for CI: bucketed tree/ring/q8 results must
    match the legacy path and a numpy reference; prints one bandwidth line.

    Bit-exactness is asserted on integer-valued f32 payloads (exact in any
    summation order); random payloads additionally assert cross-peer BIT
    IDENTITY (all peers decode the same root bytes) and closeness to the
    reference (fold order between tree siblings is arrival-order, exactly
    like the legacy tree)."""
    import moolib_tpu.buckets as buckets

    args.world_size = min(args.world_size, 4)
    cohort = _Cohort(args)
    cohort.converge()
    groups = cohort.groups
    rng = np.random.default_rng(7)
    n = 200_000
    ints = [rng.integers(-1000, 1000, n).astype(np.float32) for _ in groups]
    ref = np.sum(np.stack(ints), axis=0, dtype=np.float64).astype(np.float32)
    fails = []

    def check(tag, futs, tol=0.0, expect=None):
        cohort.wait(futs)
        outs = [np.asarray(f.result(0)) for f in futs]
        for o in outs[1:]:
            if o.tobytes() != outs[0].tobytes():
                fails.append(f"{tag}: peers disagree bit-wise")
                return outs
        e = ref if expect is None else expect
        if tol == 0.0:
            if not np.array_equal(outs[0], e):
                fails.append(f"{tag}: not bit-exact vs reference")
        elif not np.allclose(outs[0], e, atol=tol):
            fails.append(f"{tag}: out of tolerance {tol}")
        return outs

    # Bucketed tree, bit-exact vs numpy reference (integer-valued f32).
    check("tree-bucketed", [g.all_reduce("sa", d, bucketed=True) for g, d in zip(groups, ints)])
    # Owned contract (the Accumulator's): in-place folds + read-only
    # memfd-adopted result views must produce the same bits.  Inputs are
    # copies — owned=True lets the op accumulate into them.
    check("tree-owned", [g.all_reduce("sa2", d.copy(), bucketed=True, owned=True)
                         for g, d in zip(groups, ints)])
    # Legacy tree must agree bit-for-bit with the same reference.
    check("tree-legacy", [g.all_reduce("sb", d, bucketed=False, chunked=False)
                          for g, d in zip(groups, ints)])
    # Ring (bucket-aligned chunks).
    check("ring", [g.all_reduce("sc", d, chunked=True,
                                chunk_align=buckets.bucket_bytes() // 4)
                   for g, d in zip(groups, ints)])
    # q8 wire: quantization tolerance, plus cross-peer bit identity.
    tol = max(np.abs(d).max() for d in ints) / 127 * (len(groups) + 1)
    check("tree-q8", [g.all_reduce("sd", d, bucketed=True, wire="q8")
                      for g, d in zip(groups, ints)], tol=tol)
    # Throughput one-liner (tree, 4 MB payload).
    big = [rng.standard_normal(1_000_000).astype(np.float32) for _ in groups]
    futs = [g.all_reduce("sw", d, owned=True) for g, d in zip(groups, big)]
    cohort.wait(futs)
    t0 = time.perf_counter()
    for _ in range(3):
        futs = [g.all_reduce("sx", d, owned=True) for g, d in zip(groups, big)]
        cohort.wait(futs)
    dt = (time.perf_counter() - t0) / 3
    print(f"smoke: loopback {cohort.world_size}-peer tree 4MB: {4.0/dt:.0f} MB/s")
    cohort.wait([g.all_reduce("bye", 1) for g in groups])
    cohort.close()
    if fails:
        for f in fails:
            print("SMOKE FAIL:", f)
        raise SystemExit(1)
    print("smoke: bucketed/owned/legacy/ring/q8 allreduce results verified")


def _int_grad_trees(world_size, size):
    """Deterministic integer-valued f32 gradient trees (exact under any
    summation order): every rank rebuilds every peer's contribution and the
    numpy reference without communicating."""
    return [
        {"g": np.random.default_rng(1000 + r).integers(-32, 33, size).astype(np.float32)}
        for r in range(world_size)
    ]


def _accum_grad_bytes(kind="grad"):
    """Process-local ``accum_interhost_bytes_total`` for one kind label."""
    from moolib_tpu import telemetry

    for m in telemetry.get_registry().collect():
        if m.name == "accum_interhost_bytes_total":
            return sum(v for labels, v in m.samples() if labels.get("kind") == kind)
    return 0.0


class _AccumCohort:
    """N Accumulator peers + broker on loopback (or one rank per process,
    same WORLD_SIZE/RANK/BROKER_ADDR contract as :class:`_Cohort`).  Rounds
    are lockstep by construction — a peer's ``has_gradients()`` only rises
    once the cohort round completes — so toggling the plane between rounds
    stays wire-consistent on every rank."""

    def __init__(self, args, params):
        from moolib_tpu import Accumulator, Broker

        world_size = int(os.environ.get("WORLD_SIZE", args.world_size))
        rank = os.environ.get("RANK")
        broker_addr = os.environ.get("BROKER_ADDR", args.broker_addr)
        self.world_size = world_size
        self.local_ranks = list(range(world_size)) if rank is None else [int(rank)]
        self.broker = None
        if rank is None or int(rank) == 0:
            self.broker = Broker()
            self.broker.set_name("broker")
            if rank is None:
                self.broker.listen(broker_addr)
            else:
                host, _, port = broker_addr.rpartition(":")
                self.broker.listen(
                    f":{port}" if host in ("", "127.0.0.1", "0.0.0.0") else broker_addr
                )
        self.accs = []
        for i in self.local_ranks:
            acc = Accumulator("bench", {k: np.copy(v) for k, v in params.items()})
            acc.set_name(f"rank{i}")
            acc._rpc.set_timeout(60)
            acc.listen(":0")
            acc.connect(broker_addr)
            self.accs.append(acc)

    def pump(self):
        if self.broker is not None:
            self.broker.update()
        for a in self.accs:
            a.update()
            if a.wants_state():
                a.set_state({"step": 0})

    def converge(self):
        deadline = time.time() + 120
        ok = lambda: all(  # noqa: E731
            a.connected() and len(a._group.members()) == self.world_size
            for a in self.accs
        )
        while not ok() and time.time() < deadline:
            self.pump()
            time.sleep(0.005)
        assert ok(), "accumulator cohort never converged"

    def set_sharded(self, enabled):
        for a in self.accs:
            a.set_sharded_allreduce(enabled)

    def round(self, trees):
        """One gradient round: every local peer contributes its tree, wait
        for the cohort result, hand it back, re-arm for the next round."""
        for a, t in zip(self.accs, trees):
            a.reduce_gradients(1, t)
        deadline = time.time() + 120
        while not all(a.has_gradients() for a in self.accs):
            assert time.time() < deadline, "gradient round wedged"
            self.pump()
            time.sleep(0.001)
        outs = [
            {k: np.asarray(v) for k, v in a.gradients().items()} for a in self.accs
        ]
        for a in self.accs:
            a.zero_gradients()
        return outs

    def close(self):
        for a in self.accs:
            a.close()
        if self.broker is not None:
            self.broker.close()


def bench_sharded(args):
    """A/B rows: legacy full-tree vs sharded hierarchical gradient rounds
    over a real Accumulator cohort, plus a ratio section stating the
    per-host byte claim as data rows (each section under its own banner)."""
    import moolib_tpu.buckets as buckets

    if args.bucket_bytes:
        buckets.set_bucket_bytes(args.bucket_bytes)
    cohort = _AccumCohort(args, {"g": np.zeros(8, np.float32)})
    cohort.converge()
    n = cohort.world_size
    local_n = len(cohort.accs)

    def run_rows(sharded):
        cohort.set_sharded(sharded)
        plane = "sharded-hier" if sharded else "legacy full-tree"
        print(
            f"# accum grad rounds ({plane}), {n} hosts, loopback "
            f"(grad_MB_host = per-host DCN gradient bytes per round, "
            f"accum_interhost_bytes_total{{kind=grad}})"
        )
        print(f"{'elems':>10} {'MB':>8} {'ms':>9} {'MB/s':>10} {'grad_MB_host':>13}")
        per_host = {}
        for size in args.sizes:
            trees = _int_grad_trees(n, size)
            local = [trees[i] for i in cohort.local_ranks]
            cohort.round(local)  # warmup: layouts, codecs, transport upgrades
            b0 = _accum_grad_bytes()
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                cohort.round(local)
                times.append(time.perf_counter() - t0)
            dt = statistics.median(times)
            gb = (_accum_grad_bytes() - b0) / args.iters / local_n / 1e6
            mb = size * 4 / 1e6
            print(f"{size:>10} {mb:>8.2f} {dt*1e3:>9.2f} {mb/dt:>10.1f} {gb:>13.3f}")
            per_host[size] = gb
        return per_host

    legacy = run_rows(False)
    shard = run_rows(True)
    print(
        f"# sharded/legacy per-host grad bytes per round "
        f"(ideal (N-1)/N = {(n - 1) / n:.3f} for {n} hosts)"
    )
    print(f"{'elems':>10} {'ratio':>8}")
    for size in args.sizes:
        if legacy[size] > 0:
            print(f"{size:>10} {shard[size] / legacy[size]:>8.3f}")
    cohort.close()


def bench_sharded_smoke(args):
    """CI gate for the sharded plane: one legacy and one sharded round over
    the SAME contributions must be bit-identical to each other and to the
    numpy reference, and the sharded per-host grad bytes must come in under
    (N-1)/N + 0.05 of legacy (0.55x for 2 hosts — the ISSUE acceptance
    bound).  In multi-process mode every rank gates on its OWN counters, so
    a 2-process run proves the drop across real process boundaries."""
    cohort = _AccumCohort(args, {"g": np.zeros(8, np.float32)})
    cohort.converge()
    n = cohort.world_size
    size = 200_000
    trees = _int_grad_trees(n, size)
    local = [trees[i] for i in cohort.local_ranks]
    # Mirror the accumulator's averaging expression (f32 sum / python int)
    # so the reference check is bit-exact, not approximate.
    total = np.sum(
        np.stack([t["g"] for t in trees]), axis=0, dtype=np.float64
    ).astype(np.float32)
    ref = total / n
    fails = []

    def run_plane(sharded):
        cohort.set_sharded(sharded)
        cohort.round(local)  # warmup (layouts, transport upgrades)
        b0 = _accum_grad_bytes()
        outs = cohort.round(local)
        return outs, (_accum_grad_bytes() - b0) / len(cohort.accs)

    legacy_outs, legacy_b = run_plane(False)
    shard_outs, shard_b = run_plane(True)
    for tag, outs in (("legacy", legacy_outs), ("sharded", shard_outs)):
        for o in outs:
            if o["g"].tobytes() != ref.tobytes():
                fails.append(f"{tag}: not bit-exact vs numpy reference")
                break
    for lo, so in zip(legacy_outs, shard_outs):
        if lo["g"].tobytes() != so["g"].tobytes():
            fails.append("sharded differs bit-wise from legacy")
            break
    bound = (n - 1) / n + 0.05
    if legacy_b <= 0 or shard_b <= 0:
        fails.append(
            f"byte counters did not move (legacy={legacy_b}, sharded={shard_b})"
        )
    elif shard_b > bound * legacy_b:
        fails.append(
            f"per-host grad bytes ratio {shard_b / legacy_b:.3f} > bound {bound:.3f}"
        )
    cohort.close()
    if fails:
        for f in fails:
            print("SMOKE FAIL:", f)
        raise SystemExit(1)
    print(
        f"smoke: sharded allreduce bit-exact vs legacy and numpy reference "
        f"({n} hosts)"
    )
    print(
        f"smoke: per-host grad bytes/round sharded {shard_b / 1e6:.2f} MB vs "
        f"legacy {legacy_b / 1e6:.2f} MB "
        f"(ratio {shard_b / legacy_b:.3f} <= {bound:.3f})"
    )


def _overlap_trees(world_size, size, n_leaves=8):
    """Deterministic integer-valued multi-leaf gradient trees for the
    overlap arm: the streaming pipeline needs several leaves so the paced
    backward has buckets to launch early.  Zero-padded keys keep the dict
    flatten order equal to build order; integer values keep every summation
    order bit-exact."""
    trees = []
    for r in range(world_size):
        rng = np.random.default_rng(1000 + r)
        tree, left, i = {}, size, 0
        per = max(1, size // n_leaves)
        while left > 0:
            n = left if i >= n_leaves - 1 else min(per, left)
            tree[f"g{i:02d}"] = rng.integers(-32, 33, n).astype(np.float32)
            left -= n
            i += 1
        trees.append(tree)
    return trees


def _overlap_round(cohort, local_trees, compute_s, streaming):
    """One gradient round with a simulated backward of ``compute_s``
    seconds.  Barrier arm: every gradient materializes only at the end of
    backward, then the whole allreduce runs exposed.  Streaming arm: leaves
    are delivered tail-first at an even pace across the backward window
    (the readiness order reverse-mode AD produces) and buckets launch
    mid-backward; only what remains after the LAST delivery is exposed.
    Returns ``(outs, exposed_s)`` where exposed = wall seconds from
    backward-end (last leaf ready) to the cohort result landing."""
    import threading

    import jax.tree_util as jtu

    import moolib_tpu.buckets as buckets

    reducers = []
    t_bw_end = [0.0]
    if streaming:
        lock = threading.Lock()

        def produce(stream, leaves):
            pace = compute_s / max(1, len(leaves))
            for i in range(len(leaves) - 1, -1, -1):
                time.sleep(pace)
                stream.deliver(i, [leaves[i]])
            with lock:
                t_bw_end[0] = max(t_bw_end[0], time.perf_counter())

        for a, t in zip(cohort.accs, local_trees):
            leaves, treedef = jtu.tree_flatten(t)
            # Host leaves are declared explicitly unsharded so a cold cache
            # streams instead of falling back to a barrier round (the
            # sharded plane's layout is signature-guarded).
            stream = buckets.GradientStream(
                treedef,
                [l.shape for l in leaves],
                [l.dtype for l in leaves],
                shardings=[None] * len(leaves),
            )
            threading.Thread(
                target=produce, args=(stream, leaves), daemon=True
            ).start()
            th = threading.Thread(target=a.reduce_gradients, args=(1, stream))
            th.start()
            reducers.append(th)
    else:
        time.sleep(compute_s)  # simulated backward: grads ready only at the end
        t_bw_end[0] = time.perf_counter()
        for a, t in zip(cohort.accs, local_trees):
            a.reduce_gradients(1, t)
    deadline = time.time() + 120
    while not all(a.has_gradients() for a in cohort.accs):
        assert time.time() < deadline, "overlap gradient round wedged"
        cohort.pump()
        time.sleep(0.001)
    t_done = time.perf_counter()
    for th in reducers:
        th.join(120)
    outs = [
        {k: np.asarray(v) for k, v in a.gradients().items()} for a in cohort.accs
    ]
    for a in cohort.accs:
        a.zero_gradients()
    return outs, max(0.0, t_done - t_bw_end[0])


def _overlap_measure(cohort, local, compute_s, iters, streaming):
    """Warmup (layouts, codecs, transport upgrades) then median-of-iters
    round wall time and exposed comm for one arm."""
    _overlap_round(cohort, local, min(compute_s, 0.05), streaming)
    times, exps, outs = [], [], None
    for _ in range(iters):
        t0 = time.perf_counter()
        outs, e = _overlap_round(cohort, local, compute_s, streaming)
        times.append(time.perf_counter() - t0)
        exps.append(e)
    return outs, statistics.median(times), statistics.median(exps)


def _overlap_banner(streaming, n, compute_ms):
    arm = "streaming" if streaming else "barrier"
    return (
        f"# accum grad rounds ({arm} arm, overlap A/B), {n} hosts, loopback "
        f"(simulated backward {compute_ms:.0f} ms; exposed_ms = comm left "
        f"after the last gradient leaf is ready)"
    )


_OVERLAP_HEADER = (
    f"{'elems':>10} {'MB':>8} {'round_ms':>9} {'exposed_ms':>11} {'MB/s':>10}"
)


def _overlap_row(size, dt, exposed):
    mb = size * 4 / 1e6
    return (
        f"{size:>10} {mb:>8.2f} {dt * 1e3:>9.2f} {exposed * 1e3:>11.2f} "
        f"{mb / dt:>10.1f}"
    )


def bench_overlap(args):
    """A/B rows: barrier vs streaming gradient rounds over a real
    Accumulator cohort with a simulated backward window (docs/DESIGN.md
    §6e).  The claim is the exposed_ms column: the streaming arm launches
    each bucket's inter-host reduce as soon as backward fills it, so only
    the tail of the allreduce remains after the last gradient is ready,
    where the barrier arm pays the whole allreduce after backward.  Each
    section prints under its own banner."""
    import moolib_tpu.buckets as buckets

    buckets.set_bucket_bytes(args.bucket_bytes or (1 << 20))
    cohort = _AccumCohort(args, {"g": np.zeros(8, np.float32)})
    cohort.converge()
    n = cohort.world_size
    compute_s = args.compute_ms / 1e3

    def run_rows(streaming):
        print(_overlap_banner(streaming, n, args.compute_ms))
        print(_OVERLAP_HEADER)
        exposed = {}
        for size in args.sizes:
            trees = _overlap_trees(n, size)
            local = [trees[i] for i in cohort.local_ranks]
            _, dt, ex = _overlap_measure(
                cohort, local, compute_s, args.iters, streaming
            )
            print(_overlap_row(size, dt, ex))
            exposed[size] = ex
        return exposed

    barrier = run_rows(False)
    stream = run_rows(True)
    print(
        "# streaming/barrier exposed comm per step "
        "(<= 0.5 at the 10 MB tree is the DESIGN.md 6e acceptance bound)"
    )
    print(f"{'elems':>10} {'ratio':>8}")
    for size in args.sizes:
        if barrier[size] > 0:
            print(f"{size:>10} {stream[size] / barrier[size]:>8.3f}")
    cohort.close()


def bench_overlap_smoke(args):
    """CI gate for the streaming gradient pipeline (docs/DESIGN.md §6e) at
    the 10 MB acceptance point: streaming and barrier rounds over the SAME
    contributions must be bit-identical to each other and to the numpy
    reference; the streaming round must really have streamed (every
    non-final bucket launched with positive lead —
    ``accum_bucket_launch_lead_seconds`` > 0); and the exposed comm per
    step must come in at <= 0.5x the barrier arm.  Prints the measured A/B
    rows under the sweep's banners.  In multi-process mode every rank gates
    its OWN exposure and leads, so the 2-process form proves the cut across
    real process boundaries."""
    import moolib_tpu.buckets as buckets

    buckets.set_bucket_bytes(args.bucket_bytes or (1 << 20))
    cohort = _AccumCohort(args, {"g": np.zeros(8, np.float32)})
    cohort.converge()
    n = cohort.world_size
    size = 2_621_440  # 10 MB of f32 — the acceptance point
    compute_s = args.compute_ms / 1e3
    trees = _overlap_trees(n, size)
    local = [trees[i] for i in cohort.local_ranks]
    # Mirror the accumulator's averaging expression (f32 total / python int)
    # so the reference check is bit-exact, not approximate.
    ref = {
        k: np.sum(
            np.stack([t[k] for t in trees]), axis=0, dtype=np.float64
        ).astype(np.float32) / n
        for k in trees[0]
    }
    fails = []

    barrier_outs, barrier_dt, barrier_ex = _overlap_measure(
        cohort, local, compute_s, args.iters, streaming=False
    )
    for a in cohort.accs:
        # Cleared so a silent fallback to the barrier path (which never
        # records launch leads) is caught below, not masked by the warmup.
        a._last_launch_leads = None
    stream_outs, stream_dt, stream_ex = _overlap_measure(
        cohort, local, compute_s, args.iters, streaming=True
    )

    print(_overlap_banner(False, n, args.compute_ms))
    print(_OVERLAP_HEADER)
    print(_overlap_row(size, barrier_dt, barrier_ex))
    print(_overlap_banner(True, n, args.compute_ms))
    print(_OVERLAP_HEADER)
    print(_overlap_row(size, stream_dt, stream_ex))

    for tag, outs in (("barrier", barrier_outs), ("streaming", stream_outs)):
        for o in outs:
            if any(o[k].tobytes() != ref[k].tobytes() for k in ref):
                fails.append(f"{tag}: not bit-exact vs numpy reference")
                break
    for bo, so in zip(barrier_outs, stream_outs):
        if any(bo[k].tobytes() != so[k].tobytes() for k in ref):
            fails.append("streaming differs bit-wise from barrier")
            break
    max_lead = 0.0
    for rank, a in zip(cohort.local_ranks, cohort.accs):
        leads = getattr(a, "_last_launch_leads", None)
        if not leads:
            fails.append(
                f"rank{rank}: no bucket launch leads recorded — the round "
                f"fell back to the barrier path instead of streaming"
            )
            continue
        # Leads are t_final_launch - t_launch: the FINAL bucket is the one
        # with lead exactly 0 (the smallest); every other bucket must have
        # launched strictly earlier.
        nonfinal = sorted(leads)[1:]
        if len(leads) < 2:
            fails.append(f"rank{rank}: only {len(leads)} bucket(s) launched")
        elif min(nonfinal) <= 0.0:
            fails.append(
                f"rank{rank}: a non-final bucket launched with zero lead "
                f"(leads={['%.3f' % l for l in sorted(leads)]})"
            )
        elif max(leads) < compute_s / 2:
            fails.append(
                f"rank{rank}: max launch lead {max(leads) * 1e3:.1f} ms < "
                f"half the backward window — buckets are not launching "
                f"mid-backward"
            )
        max_lead = max(max_lead, max(leads))
    if barrier_ex <= 0:
        fails.append(f"barrier exposed comm did not register ({barrier_ex})")
    elif stream_ex > 0.5 * barrier_ex:
        fails.append(
            f"exposed comm per step ratio {stream_ex / barrier_ex:.3f} > "
            f"acceptance bound 0.500 "
            f"(streaming {stream_ex * 1e3:.2f} ms vs barrier "
            f"{barrier_ex * 1e3:.2f} ms)"
        )
    cohort.close()
    if fails:
        for f in fails:
            print("SMOKE FAIL:", f)
        raise SystemExit(1)
    print(
        f"smoke: streaming allreduce bit-exact vs barrier and numpy "
        f"reference ({n} hosts, {size * 4 / 1e6:.1f} MB tree)"
    )
    print(
        f"smoke: exposed comm per step streaming {stream_ex * 1e3:.2f} ms vs "
        f"barrier {barrier_ex * 1e3:.2f} ms "
        f"(ratio {stream_ex / barrier_ex:.3f} <= 0.500)"
    )
    print(
        f"smoke: every non-final bucket launched with positive lead "
        f"(max lead {max_lead * 1e3:.1f} ms of a {args.compute_ms:.0f} ms "
        f"backward window)"
    )


def bench_ici(args):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from moolib_tpu import parallel

    devices = jax.devices()
    mesh = parallel.make_mesh({"dp": len(devices)})
    note = ""
    if devices[0].platform == "cpu":
        note = (
            " — host-mesh sanity row (no ICI on CPU; collective cost is "
            "memcpy); run on a TPU slice for real interconnect bandwidth"
        )
        if len(devices) == 1:
            note = (
                " — 1-device row is a pure memcpy, NOT a collective; set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8"
            )
    print(f"# XLA psum over {len(devices)} x {devices[0].platform} (ICI data plane){note}")
    print(f"{'elems':>10} {'MB':>8} {'ms':>9} {'MB/s':>10}")

    for size in args.sizes:
        n = len(devices)
        per = (size + n - 1) // n
        x = jnp.zeros((n, per), jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P("dp")))

        @jax.jit
        def allreduce(x):
            return jax.shard_map(
                lambda v: jax.lax.psum(v, "dp"),
                mesh=mesh,
                in_specs=P("dp"),
                out_specs=P("dp"),
            )(x)

        # Warm up once (compile + first dispatch), then median-of-iters —
        # the old mean-of-total silently absorbed a slow first iteration.
        out = allreduce(x)
        jax.block_until_ready(out)
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            out = allreduce(x)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        dt = statistics.median(times)
        mb = size * 4 / 1e6
        print(f"{size:>10} {mb:>8.2f} {dt*1e3:>9.2f} {mb/dt:>10.1f}")


def main(argv=None):
    p = argparse.ArgumentParser(description="moolib_tpu allreduce benchmark")
    p.add_argument("mode", choices=["rpc", "ici"], nargs="?", default="rpc")
    p.add_argument("--world_size", type=int, default=4)
    p.add_argument("--broker_addr", default="127.0.0.1:4499")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument(
        "--bucket_bytes", type=int, default=0,
        help="flat-bucket size for the sweep; 0 = payload-sized (single "
        "bucket per op, the single-core loopback optimum)",
    )
    p.add_argument("--wire", choices=["none", "q8", "both"], default="none",
                   help="add int8-compressed rows")
    p.add_argument("--grad_tree", action="store_true",
                   help="payloads shaped as a transformer-like gradient "
                   "pytree instead of one flat array")
    p.add_argument("--no_owned", action="store_true",
                   help="measure the copying owned=False public default "
                        "instead of the Accumulator's owned=True contract "
                        "(in-place folds, read-only adopted result views)")
    p.add_argument("--legacy", action="store_true",
                   help="add rows on the legacy per-leaf tree path")
    p.add_argument("--smoke", action="store_true",
                   help="fast correctness pass (CI): bucketed vs legacy vs "
                   "numpy reference, then one bandwidth line")
    p.add_argument("--sharded", action="store_true",
                   help="A/B the sharded hierarchical gradient plane "
                   "(DESIGN.md §6d) against the legacy full-tree plane over "
                   "a real Accumulator cohort; with --smoke, gate "
                   "bit-exactness vs numpy and the per-host byte ratio "
                   "instead of printing sweep rows")
    p.add_argument("--overlap", action="store_true",
                   help="A/B the streaming gradient pipeline (DESIGN.md "
                   "§6e) against the barrier plane over a real Accumulator "
                   "cohort with a simulated backward window; with --smoke, "
                   "gate bit-exactness, bucket launch leads, and the "
                   "exposed-comm-per-step cut at the 10 MB tree")
    p.add_argument("--compute_ms", type=float, default=300.0,
                   help="simulated backward window for the --overlap arm "
                   "(gradient leaves are delivered tail-first at an even "
                   "pace across it)")
    p.add_argument(
        "--sizes",
        type=int,
        nargs="+",
        default=[400, 10_000, 100_000, 1_000_000, 2_621_440],
    )
    args = p.parse_args(argv)
    if args.overlap and args.smoke:
        bench_overlap_smoke(args)
    elif args.overlap:
        bench_overlap(args)
    elif args.sharded and args.smoke:
        bench_sharded_smoke(args)
    elif args.sharded:
        bench_sharded(args)
    elif args.smoke:
        bench_smoke(args)
    elif args.mode == "rpc":
        bench_rpc(args)
    else:
        bench_ici(args)


if __name__ == "__main__":
    main()


