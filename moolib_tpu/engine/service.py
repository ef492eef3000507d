"""EngineService: continuous batching under the ServeService contract.

Subclasses :class:`..serving.ServeService` so the whole resilience surface
is inherited unchanged — bounded admission with typed overload rejects,
req-id dedup, per-request deadlines, ``{name}_stats``, and staged weights —
while the service loop is replaced: instead of take-a-batch / run-to-the-
longest, each iteration drains admitted requests into free decode slots
(prefill + join, both dispatched and neither waited for, or recorded for the
step to carry where the model lets it) and advances ALL
occupied slots by one fixed-shape decode step.  Hot swaps still land
between iterations (here: between decode steps); in-flight sequences
continue under the new weights.

The admission controller runs in per-token units: the wait estimate is
``(queued budgets + active remaining budgets) * EMA seconds-per-token``,
which tracks the engine's actual service rate far better than a per-batch
EMA ever could (a "batch" is no longer the unit of service).
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, List, Tuple

import numpy as np

from .. import telemetry
from ..rpc import Rpc
from ..serving import (
    AdmissionController,
    ServeService,
    _M_DEPTH,
    _M_PHASE,
    _Request,
)
from .engine import ContinuousBatchingEngine, NoFreeSlot
from .kv_pool import PoolExhausted

_M_LOOP = telemetry.get_registry().counter(
    "serve_loop_seconds_total",
    "seconds EngineService.loop spent in each of its three states: busy (a "
    "pass that had work), empty (no slot active, nothing queued), blocked "
    "(a queued request the engine cannot take, no slot active)",
    labelnames=("state",),
)
_LOOP_SECONDS = {state: _M_LOOP.labels(state=state)
                 for state in ("busy", "empty", "blocked")}


class EngineService(ServeService):
    """See module docstring.  ``step_fn``/``params`` of the base class are
    unused (the engine owns the model); everything else — admission, dedup,
    hot-swap staging, stats, close — is the inherited contract."""

    def __init__(self, rpc: Rpc, engine: ContinuousBatchingEngine, *,
                 name: str = "generate", version: int = 0,
                 max_queue: int = 128, dedup_ttl: float = 60.0,
                 default_max_new: int = 16):
        super().__init__(
            rpc, None, None, name=name, version=version,
            batch_size=engine.slots, max_queue=max_queue,
            dedup_ttl=dedup_ttl, default_max_new=default_max_new,
        )
        self._engine = engine
        self._slot_req: Dict[int, _Request] = {}
        # Requests joined whose first token no ``step()`` has booked yet, each
        # with its slot's ``emitted`` list: empty until one does (the next
        # where the admission took programs of its own, the one after where
        # it rode a step).
        self._first_due: List[Tuple[_Request, List[int]]] = []
        # Per-token admission: pending_tokens is called under self._lock
        # (from admit/estimate_wait inside _on_request) — it only reads.
        self.admission = AdmissionController(
            max_queue=max_queue, per_token=True,
            pending_tokens=self._pending_tokens,
        )

    def _pending_tokens(self) -> int:
        queued = sum(
            (r.max_new if r.max_new else self._default_max_new)
            for r in self._queue
        )
        return queued + self._engine.pending_decode_tokens()

    # ------------------------------------------------------------------ swap
    def _maybe_swap_locked(self) -> None:
        before = self._version
        super()._maybe_swap_locked()
        if self._version != before:
            # Between-iteration cutover: the engine re-places the weights;
            # slot state and KV pools are untouched, in-flight sequences
            # finish under the new version.
            self._engine.set_params(self._params)

    # ------------------------------------------------------------------ loop
    def _take_one_locked(self) -> Tuple[str, _Request]:
        """Pop the queue head if the engine can take it.  Returns
        ("none", _) on empty/full, ("join", req) to prefill, ("error", req)
        for shapes the engine cannot serve."""
        if self._closed or not self._queue:
            return "none", None
        req = self._queue[0]
        if req.prompt.shape[0] != 1:
            self._queue.pop(0)
            self._note_take_locked(req)
            return "error", req
        tp = int(req.prompt.shape[1])
        mn = req.max_new if req.max_new else self._default_max_new
        if not self._engine.can_accept(tp, mn):
            return "none", None
        self._queue.pop(0)
        self._note_take_locked(req)
        return "join", req

    def _note_take_locked(self, req: _Request) -> None:
        _M_DEPTH.dec()
        wait = time.monotonic() - req.t_enq
        s = self._stats
        s["takes"] += 1
        s["items"] += 1
        s["wait_s_sum"] += wait
        s["wait_s_max"] = max(s["wait_s_max"], wait)
        _M_PHASE.observe(wait, phase="queue")
        self._note_queue_wait(wait)

    def _admit_joins(self) -> Tuple[int, int]:
        """Drain admitted requests into free slots (prefill + join), oldest
        first — FIFO order is part of the latency contract.  Stops at the
        first request the engine cannot take (slots or blocks full).
        Returns ``(joined, answered)``: requests that entered a slot, and
        requests already answered (prefill-finished or failed).  Accounting
        lands BEFORE the response goes out — a client that sees its reply
        and immediately reads ``{name}_stats`` must see itself counted."""
        joined = answered = 0
        while True:
            with self._lock:
                kind, req = self._take_one_locked()
            if kind == "none":
                return joined, answered
            if kind == "error":
                self._count_answered(1)
                self._respond(
                    req, None,
                    "generate failed: the engine serves single-row prompts "
                    "(got a multi-row request)",
                )
                answered += 1
                continue
            mn = req.max_new if req.max_new else self._default_max_new
            t0 = time.monotonic()
            try:
                slot, emitted = self._engine.submit(req.prompt[0], mn)
            except (NoFreeSlot, PoolExhausted):
                # Raced capacity away (shouldn't happen single-threaded,
                # but stay loss-free): back to the head of the queue.
                with self._lock:
                    self._queue.insert(0, req)
                    _M_DEPTH.inc()
                return joined, answered
            except ValueError as e:
                # A request the engine refuses (oversized prompt or budget)
                # fails alone; a device or compile failure is not caught and
                # takes the replica down.
                self._count_answered(1)
                self._respond(req, None, f"generate failed: {e}")
                answered += 1
                continue
            now = time.monotonic()
            # Host time of ``submit``: two dispatches or none, no device wait.
            _M_PHASE.observe(now - t0, phase="prefill")
            if slot is None:
                # Finished at prefill (budget 1): the one submit that waits.
                _M_PHASE.observe(now - req.t_enq, phase="first_token")
                self._count_answered(1)
                self._finish(req, emitted)
                answered += 1
            else:
                self._slot_req[slot] = req
                self._first_due.append((req, emitted))
                joined += 1

    def _count_answered(self, n: int) -> None:
        self._stats["served"] += n
        self._note_answered(n)

    def _finish(self, req: _Request, emitted: List[int]) -> None:
        out = np.concatenate(
            [req.prompt[0].astype(np.int32), np.asarray(emitted, np.int32)]  # mtlint: allow-host-sync(emitted is a host List[int])
        )
        self._respond(req, out if req.single else out[None], None)

    async def loop(self, total=None) -> int:
        """Serve until ``total`` requests have been answered (None =
        forever).  Returns the number of decode iterations — with mixed
        budgets this is far below baseline's requests x max-budget steps,
        which is the engine's whole throughput story.

        At every instant the loop is in one of three states, each under a
        span and a share of ``serve_loop_seconds_total{state}`` (added to
        once a pass, start of pass to start of the next, so the three sum to
        the loop's lifetime): *busy*, a pass that had work
        (``serve.iteration``); *empty*, no slot active and nothing queued
        (``serve.empty``); *blocked*, a queued request the engine cannot
        take and no slot active (``serve.blocked``)."""
        telemetry.ensure_host_monitor()
        self._loop = asyncio.get_event_loop()
        self._wake = asyncio.Event()
        served = 0
        eng = self._engine
        # Start of the last pass that ran a decode step and left a slot
        # waiting for its next token; the next pass's start closes the gap.
        prev_start = None
        state, since = None, 0.0  # of the pass in progress

        def enter(new, now):
            nonlocal state, since
            if state is not None:
                _LOOP_SECONDS[state].inc(now - since)
            state, since = new, now

        try:
            while not self._closed and (total is None or served < total):
                if not eng.active_count() and not self._queue:
                    with telemetry.span("serve.empty"):
                        now = time.monotonic()
                        enter("empty", now)
                        with self._lock:
                            self._maybe_swap_locked()
                            self._sweep_done_locked(now)
                        await self._idle_tick()
                    continue
                with telemetry.span("serve.iteration") as iteration:
                    start = time.monotonic()
                    enter("busy", start)
                    if prev_start is not None:
                        _M_PHASE.observe(start - prev_start, phase="iteration")
                    with self._lock:
                        self._maybe_swap_locked()
                        self._sweep_done_locked(start)
                    with telemetry.span("serve.admit"):
                        joined, answered = self._admit_joins()
                    served += answered
                    active = eng.active_count()
                    if active:
                        finished = self._decode_and_reply()
                        iteration.set(active=active, joined=joined,
                                      finished=finished)
                        served += finished
                        # Yield so RPC callbacks and swap stagings interleave
                        # between decode steps.
                        await asyncio.sleep(0)
                prev_start = start if active and eng.active_count() else None
                if not active and not answered:
                    # A queued request the engine cannot take yet.
                    with telemetry.span("serve.blocked"):
                        enter("blocked", time.monotonic())
                        await self._idle_tick()
        finally:
            enter(None, time.monotonic())
            with self._lock:
                self._loop = None
                self._wake = None
            if self._closed:
                self._fail_inflight()
        return self._stats["iterations"]

    def _decode_and_reply(self) -> int:
        """One decode step over the occupied slots, and the replies of those
        that finished.  Returns how many requests it answered."""
        eng = self._engine
        t0 = time.monotonic()
        emissions, finished = eng.step()
        now = time.monotonic()
        dt = now - t0
        # Server-side time to first token: enqueue to the first token on the
        # host (queue wait included), observed at the ``step()`` that booked it.
        for req, emitted in self._first_due:
            if emitted:
                _M_PHASE.observe(now - req.t_enq, phase="first_token")
        self._first_due = [due for due in self._first_due if not due[1]]
        if emissions:
            self.admission.note_service(dt, tokens=len(emissions))
            _M_PHASE.observe(dt, phase="device")
        self._stats["iterations"] += 1
        if not finished:
            return 0
        with telemetry.span("serve.reply"):
            done = [(self._slot_req.pop(s), eng.retire(s)) for s in finished]
            self._count_answered(len(done))
            for req, toks in done:
                self._finish(req, toks)
        return len(done)

    async def _idle_tick(self) -> None:
        """Nothing to decode: wait for a request (at most 50 ms), then let
        the serve_qps window close at zero and the wait EMA decay, so the
        autoscaler's idle-shrink signal sees true silence instead of the
        last busy spell's frozen gauges."""
        try:
            await asyncio.wait_for(self._wake.wait(), timeout=0.05)
        except asyncio.TimeoutError:
            pass
        self._wake.clear()
        self._note_answered(0)
        if not self._queue:
            self._note_queue_wait(0.0)

    # ----------------------------------------------------------------- stats
    def stats(self):
        out = super().stats()
        out["engine"] = self._engine.stats()
        out["ema_token_seconds"] = self.admission.ema_batch_seconds()
        return out

    def close(self) -> None:
        """Safe from any thread.  While the service loop runs, the slot
        table is its own: it sees ``_closed`` between two iterations and
        answers the in-flight requests on its way out.  With no loop
        running they are answered here."""
        super().close()
        with self._lock:
            running = self._loop is not None
        if not running:
            self._fail_inflight()

    def _fail_inflight(self) -> None:
        # The engine's step in flight goes first: booked later, it would
        # finish slots whose requests are answered here.
        self._engine.close()
        with self._lock:
            inflight, self._slot_req = self._slot_req, {}
            self._first_due = []
        for req in inflight.values():
            try:
                self._respond(req, None, f"serve {self._name}: closed")
            except Exception:  # noqa: BLE001
                pass
