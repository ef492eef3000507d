"""Batcher: assemble pytrees into device-resident batches.

Counterpart of the reference's C++ ``Batcher`` (``src/moolib.cc:595-889,
1411-1488``; ctor args size/device/dim at ``:1888``): accumulate pytree items
by ``stack`` (one slot per call along a new axis ``dim``) or ``cat``
(concatenate along existing axis ``dim``, with arbitrary-length items split
across batch boundaries — the carry-over path, reference ``:767-811``).  When
a batch fills, ``get()`` returns it; ``empty()``/``size()`` poll; awaiting
the batcher yields filled batches in asyncio code.

TPU-first: instead of preallocating torch storage on a CUDA device and
copying slot-by-slot, items are accumulated as host numpy and the completed
batch goes to the accelerator in one ``jax.device_put`` of the whole stacked
pytree (one contiguous host→HBM DMA per leaf; a ``jax.sharding.Sharding``
may be passed as ``device`` to land the batch pre-sharded across a mesh).

Two assembly paths (docs/DESIGN.md "Actor data plane"):

- **host** (numpy items): leaves accumulate as host numpy — device-array
  leaves are coerced down (a D2H crossing, counted in
  ``batcher_d2h_bytes_total``) — and the completed batch crosses up in one
  ``device_put`` when a device is set (``batcher_h2d_bytes_total``).  This
  is the legacy rollout data plane: every batch pays a down-and-up round
  trip.
- **device** (jax.Array items, e.g. the unrolls a
  :class:`~moolib_tpu.rollout.DeviceRollout` hands over): leaves stay on
  the device; stack/cat/split run as XLA ops and the "completed batch" is
  device-resident already — zero host-boundary bytes.  ``device_put`` still
  applies a sharding when one was requested (mesh learners).

The path is latched from the first item's leaf type unless forced with the
``host=`` constructor argument.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, List, Optional

import jax
import numpy as np

from . import telemetry
from .utils import nest

# Batch-assembly metrics (docs/TELEMETRY.md): how full batches run and how
# long completed batches sit ready before the consumer drains them (a
# persistent ready-wait means the learner, not assembly, is the bottleneck).
_REG = telemetry.get_registry()
_M_BATCHES = _REG.counter("batcher_batches_total", "completed batches")
_M_ITEMS = _REG.counter("batcher_items_total", "rows batched (batch-axis length)")
_M_READY_DEPTH = _REG.gauge("batcher_ready_depth", "completed batches awaiting get()")
_M_READY_WAIT = _REG.histogram(
    "batcher_ready_wait_seconds", "batch completion to get()/await"
)
# Host-boundary traffic of batch assembly (docs/TELEMETRY.md): the host path
# pays D2H per coerced device leaf and H2D per completed-batch device_put;
# the device path pays neither.
_M_D2H_BYTES = _REG.counter(
    "batcher_d2h_bytes_total", "device leaves coerced to host during assembly"
)
_M_H2D_BYTES = _REG.counter(
    "batcher_h2d_bytes_total", "completed host batches uploaded by device_put"
)
# Sebulba (arXiv:2104.06272): when the device path's target sharding lives on
# a DIFFERENT device set than the incoming leaves (actor submesh -> learner
# submesh), the batcher IS the inter-mesh queue and its device_put is the
# trajectory handoff — counted here, never in the host-boundary counters
# (the bytes ride ICI, not PCIe).
_M_D2D_BYTES = _REG.counter(
    "batcher_d2d_bytes_total",
    "device batches re-placed across device sets (inter-mesh handoff)",
)
# Flow control at the Sebulba seam (ROADMAP item 2): with ``max_outstanding``
# set, producers block once this many completed batches sit unconsumed —
# actor lead over the learner is bounded instead of growing without limit.
# Per-instance label so the autoscaler can tell the learn queue from others.
_M_QUEUE_DEPTH = _REG.gauge(
    "batcher_queue_depth",
    "completed batches held in the (optionally bounded) ready queue",
    ("batcher",),
)
_M_PUT_BLOCKED = _REG.histogram(
    "batcher_put_blocked_seconds",
    "producer time spent blocked on a full bounded ready queue",
    ("batcher",),
)


def _host_stack_leaves(xs, dim):
    """numpy counterpart of ``nest._stack_leaves`` (same object-leaf
    fallback) — the host path must never bounce through jnp."""
    try:
        return np.stack(xs, axis=dim)
    except (TypeError, ValueError):
        out = np.empty(len(xs), dtype=object)
        for i, x in enumerate(xs):
            out[i] = x
        return out


def _resolve_device(device):
    if device is None or isinstance(device, str) and device in ("cpu", ""):
        return None
    if isinstance(device, str):
        # "tpu", "tpu:0", "cuda:0"-style strings map to jax devices.
        kind, _, idx = device.partition(":")
        if kind == "cuda":  # reference configs say cuda; we run on TPU
            kind = "tpu"
        devs = [d for d in jax.devices() if d.platform.startswith(kind)]
        if not devs:
            raise ValueError(
                f"device {device!r} requested but jax has no {kind!r} device "
                f"(have {sorted({d.platform for d in jax.devices()})})"
            )
        return devs[int(idx) if idx else 0]
    return device  # jax.Device or Sharding


class Batcher:
    """See module docstring. API: stack(item), cat(item), empty(), size(),
    get(), plus awaitable batches."""

    def __init__(self, size: int, device: Optional[str] = None, dim: int = 0,
                 host: Optional[bool] = None,
                 max_outstanding: Optional[int] = None, name: str = "batcher"):
        if size < 1:
            raise ValueError("batch size must be >= 1")
        if max_outstanding is not None and max_outstanding < 1:
            raise ValueError("max_outstanding must be >= 1 (or None = unbounded)")
        self._size = size
        self._dim = dim
        self._device = _resolve_device(device)
        # None = latch from the first item: jax.Array leaves keep the
        # device-side path (XLA stack/cat, no crossings), anything else
        # accumulates as host numpy.  True/False forces a path.
        self._host = host
        # Bounded ready queue: with max_outstanding set, the producer's
        # stack()/cat() BLOCKS once this many completed batches await get()
        # — backpressure instead of unbounded actor lead.  None keeps the
        # legacy unbounded behavior (and can never deadlock single-threaded
        # fill-then-drain code).
        self._max_outstanding = max_outstanding
        self._name = name
        self._lock = threading.Lock()
        self._not_full = threading.Condition(self._lock)
        self._slots: List[Any] = []
        self._cat_count = 0
        self._ready: collections.deque = collections.deque()
        self._waiters: collections.deque = collections.deque()

    def _latch_path(self, item) -> None:
        if self._host is None:
            leaf = next(nest.flatten(item), None)
            self._host = not isinstance(leaf, jax.Array)

    def _to_host(self, item):
        """Host-path coercion: device leaves come down (counted D2H)."""

        def _coerce(x):
            if isinstance(x, jax.Array):
                out = np.asarray(x)
                _M_D2H_BYTES.inc(out.nbytes)
                return out
            return x

        return nest.map(_coerce, item)

    def _assemble(self, items):
        """Stack slot items into a batch on the latched path."""
        if self._host:
            return nest.map_many(
                lambda *xs: _host_stack_leaves(xs, self._dim), *items
            )
        return nest.stack(items, dim=self._dim)

    def _assemble_cat(self, items):
        if self._host:
            return nest.map_many(
                lambda *xs: np.concatenate(xs, axis=self._dim), *items
            )
        return nest.cat(items, dim=self._dim)

    # ---------------------------------------------------------------- fill
    def stack(self, item) -> None:
        """Add one item; a batch completes after ``size`` calls (new axis)."""
        with self._lock:
            self._latch_path(item)
            if self._host:
                item = self._to_host(item)
            self._slots.append(item)
            if len(self._slots) >= self._size:
                items, self._slots = self._slots[: self._size], self._slots[self._size :]
                self._finish(self._assemble(items))

    def cat(self, item) -> None:
        """Add an item whose leaves already have the batch axis; completes
        when ``size`` rows accumulate, splitting oversized items (carry-over)."""
        with self._lock:
            self._latch_path(item)
            if self._host:
                item = self._to_host(item)
            length = self._item_length(item)
            offset = 0
            while offset < length:
                room = self._size - self._cat_count
                take = min(room, length - offset)
                part = (
                    item
                    if take == length and offset == 0
                    else nest.map(lambda x: self._slice(x, offset, take), item)
                )
                self._slots.append(part)
                self._cat_count += take
                offset += take
                if self._cat_count >= self._size:
                    items, self._slots = self._slots, []
                    self._cat_count = 0
                    self._finish(
                        items[0] if len(items) == 1 else self._assemble_cat(items)
                    )

    def _item_length(self, item) -> int:
        leaves = list(nest.flatten(item))
        if not leaves:
            raise ValueError("empty item")
        return int(np.shape(leaves[0])[self._dim])

    def _slice(self, x, offset: int, take: int):
        idx = [slice(None)] * np.ndim(x)
        idx[self._dim] = slice(offset, offset + take)
        return x[tuple(idx)]

    def _target_devices(self):
        d = self._device
        if hasattr(d, "device_set"):  # jax.sharding.Sharding
            return frozenset(d.device_set)
        return frozenset((d,))

    def _finish(self, batch) -> None:
        # Backpressure BEFORE the device_put: a blocked producer must not keep
        # uploading batches to device memory.  wait() releases the lock, so
        # consumers drain (get()/await notify via _pop_ready_locked).  A
        # waiter present means immediate handoff — no queue growth, no block.
        if self._max_outstanding is not None:
            t0 = None
            while len(self._ready) >= self._max_outstanding and not self._waiters:
                if t0 is None:
                    t0 = time.monotonic()
                self._not_full.wait()
            if t0 is not None:
                _M_PUT_BLOCKED.observe(time.monotonic() - t0, batcher=self._name)
        # One device_put of the whole pytree: a single host->HBM hop per leaf.
        if self._device is not None:
            if self._host:
                _M_H2D_BYTES.inc(
                    sum(getattr(x, "nbytes", 0) for x in nest.flatten(batch))
                )
            else:
                # Device path: a same-device-set put is a no-op/reshard; a
                # cross-set put is the Sebulba actor->learner handoff.
                tgt = self._target_devices()
                moved = sum(
                    x.nbytes
                    for x in nest.flatten(batch)
                    if isinstance(x, jax.Array)
                    and frozenset(x.sharding.device_set) != tgt
                )
                if moved:
                    _M_D2D_BYTES.inc(moved)
            batch = jax.device_put(batch, self._device)
        _M_BATCHES.inc()
        _M_ITEMS.inc(self._size)
        if self._waiters:
            loop, af = self._waiters.popleft()
            _M_READY_WAIT.observe(0.0)  # a consumer was already waiting
            loop.call_soon_threadsafe(_set_result, af, batch)
        else:
            self._ready.append((batch, time.monotonic()))
            _M_READY_DEPTH.inc()
            _M_QUEUE_DEPTH.set(len(self._ready), batcher=self._name)

    # --------------------------------------------------------------- drain
    def empty(self) -> bool:
        with self._lock:
            return not self._ready

    def size(self) -> int:
        """Items currently buffered toward the next batch (reference ``size``)."""
        with self._lock:
            return self._cat_count if self._cat_count else len(self._slots)

    def get(self):
        with self._lock:
            if not self._ready:
                raise RuntimeError("Batcher.get() called with no complete batch")
            return self._pop_ready_locked()

    def _pop_ready_locked(self):
        batch, done_at = self._ready.popleft()
        _M_READY_DEPTH.dec()
        _M_QUEUE_DEPTH.set(len(self._ready), batcher=self._name)
        _M_READY_WAIT.observe(time.monotonic() - done_at)
        self._not_full.notify()
        return batch

    def __await__(self):
        import asyncio

        loop = asyncio.get_event_loop()
        af = loop.create_future()
        with self._lock:
            if self._ready:
                af.set_result(self._pop_ready_locked())
            else:
                self._waiters.append((loop, af))
        return af.__await__()

    __iter__ = __await__


def _set_result(af, value):
    if not af.cancelled():
        af.set_result(value)
