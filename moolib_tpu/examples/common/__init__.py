"""Shared agent plumbing (counterpart of reference ``examples/common/``):
stats with cohort-wide delta allreduce, per-actor-batch state threading, and
TSV logging."""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.stats import RunningMeanStd, StatMean, StatSum  # noqa: F401
from ...utils.config import Config  # noqa: F401
from ...batcher import Batcher


def finalize_flags(parser, argv=None):
    """Parse example-agent flags the hydra-ish way (reference agents use
    hydra; ``examples/vtrace/experiment.py:214-224``): argparse ``--flags``
    provide defaults and ``--help``; an optional ``--cfg config.yaml``
    overlays a file; trailing positional ``key=value`` overrides win.
    Returns a :class:`moolib_tpu.utils.config.Config` (attribute access,
    interpolation, ``to_yaml``)."""
    import argparse as _argparse

    if not any(a.dest == "cfg" for a in parser._actions):  # idempotent
        parser.add_argument("--cfg", default=None, help="YAML config file overlay")
        parser.add_argument(
            "overrides", nargs="*", metavar="key=value", help="config overrides"
        )
    ns = parser.parse_args(argv)
    data = vars(ns)
    cfg_path = data.pop("cfg")
    kv_overrides = data.pop("overrides")
    # Priority: parser defaults < config file < explicit --flags < key=value.
    # argparse can't distinguish explicit values after one parse, so parse a
    # second time with every default suppressed to learn which flags the
    # user actually typed.
    saved = [(a, a.default) for a in parser._actions]
    try:
        for a, _ in saved:
            if a.dest != "help":
                a.default = _argparse.SUPPRESS
        explicit = vars(parser.parse_known_args(argv)[0])
    finally:
        for a, default in saved:
            a.default = default
    explicit.pop("cfg", None)
    explicit.pop("overrides", None)
    cfg = Config.load(cfg_path, defaults=data)
    for k, v in explicit.items():
        cfg[k] = v
    for ov in kv_overrides:
        cfg.apply_override(ov)
    return cfg


def placement_of(tree) -> dict:
    """Where ``tree``'s arrays really live: the sorted platforms of their
    devices (host numpy leaves count as ``"host"``) and the least number of
    distinct devices holding addressable shards of any one jax array."""
    platforms, per_leaf = set(), []
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            devs = {s.device for s in leaf.addressable_shards}
            platforms.update(d.platform for d in devs)
            per_leaf.append(len(devs))
        else:
            platforms.add("host")
    return {"platforms": sorted(platforms),
            "devices_per_array": min(per_leaf, default=0)}


def run_report(result: dict, started: float) -> dict:
    """What a run of an example was made of, as one JSON-ready dict: the
    device jax computed on, wall and compile seconds, persistent compile
    cache traffic, which native components were built, device memory, and
    the example's own ``result``.  ``started`` is a ``time.monotonic()``
    stamp from the top of ``main``.  Entry points print it through
    :func:`print_report` so a harness reads facts, not log lines."""
    from ... import native, telemetry

    devices = jax.devices()
    return {
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "wall_s": round(time.monotonic() - started, 3),
        **telemetry.devmon.compile_summary(),
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "native": native.status(),
        "memory": telemetry.devmon.sample_memory(),
        "result": result,
    }


REPORT_PREFIX = "RUN_REPORT "


def print_report(result: dict, started: float) -> None:
    print(REPORT_PREFIX + json.dumps(run_report(result, started), default=str),
          flush=True)


class GlobalStatsAccumulator:
    """Allreduce stat *deltas* cohort-wide (reference
    ``examples/common/__init__.py:65-121``): each peer tracks the snapshot it
    last reduced, reduces the difference, and re-queues the delta if the
    reduction fails (e.g. on a membership change)."""

    def __init__(self, group, stats: Dict):
        self._group = group
        self._stats = stats
        self._last = {k: v.snapshot() for k, v in stats.items()}
        self._pending_delta: Optional[dict] = None
        self._inflight = None
        # Serializes reduce()/local_reset()/reset() (train thread) against
        # on_done (RPC callback thread): both sides mutate the delta
        # baseline, and an unserialized local_reset concurrent with a
        # result application would broadcast a negative-delta storm.
        self._mutex = threading.Lock()

    def reduce(self, stats: Dict) -> None:
        with self._mutex:
            if self._inflight is not None:
                return
            delta = {k: v.delta(self._last[k]) for k, v in stats.items()}
            if self._pending_delta is not None:
                for k, d in self._pending_delta.items():
                    delta[k] = _delta_add(delta[k], d)
            self._last = {k: v.snapshot() for k, v in stats.items()}
            self._pending_delta = None
            self._inflight = object()  # block re-entry before the callback binds

        def on_done(f, delta=delta):
            with self._mutex:
                try:
                    exc = f.exception()
                    if exc is not None:
                        # Failed (churn): re-queue our delta so nothing is lost.
                        self._pending_delta = (
                            delta
                            if self._pending_delta is None
                            else {k: _delta_add(self._pending_delta[k], d)
                                  for k, d in delta.items()}
                        )
                        return
                    total = f.result(0)
                    for k, v in self._stats.items():
                        # Apply everyone else's contribution (total minus
                        # ours) to the value AND the delta baseline: remote
                        # contributions we merely learned about are not OUR
                        # progress, and leaving them out of the baseline
                        # re-broadcasts them as our next delta — a
                        # (n-1)x-per-round amplification that inflated
                        # steps_done ~1000x in the round-5 soak (which then
                        # hit the agents' total_steps budget years early).
                        rem = _delta_sub(total[k], delta[k])
                        v.apply_delta(rem)
                        self._last[k].apply_delta(rem)
                finally:
                    # ALWAYS cleared, or one malformed cohort result would
                    # wedge reduce() (it early-returns while this is set).
                    self._inflight = None

        fut = self._group.all_reduce("__global_stats", delta, op=_delta_reduce_op)
        fut.add_done_callback(on_done)

    def reset(self) -> None:
        with self._mutex:
            for k, v in self._stats.items():
                v.reset()
            self._last = {k: v.snapshot() for k, v in self._stats.items()}

    def local_reset(self, *keys: str) -> None:
        """Reset chosen stats for local windowing without desyncing the delta
        protocol (re-snapshots them so the next reduce sends a zero delta)."""
        with self._mutex:
            for k in keys:
                self._stats[k].reset()
                self._last[k] = self._stats[k].snapshot()


def _delta_add(a, b):
    if isinstance(a, tuple):
        return tuple(x + y for x, y in zip(a, b))
    if isinstance(a, dict):
        # Union of keys: telemetry CohortCounters deltas are {series: incr}
        # maps whose keys appear over time (a new label set binds) and can
        # differ across peers; a missing series means "started at zero".
        return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in set(a) | set(b)}
    return a + b


def _delta_sub(a, b):
    if isinstance(a, tuple):
        return tuple(x - y for x, y in zip(a, b))
    if isinstance(a, dict):
        return {k: a.get(k, 0.0) - b.get(k, 0.0) for k in set(a) | set(b)}
    return a - b


def _delta_reduce_op(a, b):
    return {k: _delta_add(a[k], b[k]) for k in a}


class EnvBatchState:
    """Per-actor-batch bookkeeping (reference
    ``examples/common/__init__.py:154-207``): previous action, carried LSTM
    state, time batcher assembling [T+1, B, ...] unrolls with the last step
    carried into the next unroll, and episode return/step accounting."""

    def __init__(self, batch_size: int, unroll_length: int, model, device=None):
        self.batch_size = batch_size
        self.unroll_length = unroll_length
        self.prev_action = jnp.zeros((batch_size,), jnp.int32)
        # Host mirror of prev_action for the legacy host-batcher path: the
        # realized action of the previous step, so the unroll row never
        # forces an extra device round trip.
        self.prev_action_host = np.zeros((batch_size,), np.int32)
        self.core_state = model.initial_state(batch_size)
        self.initial_core_state = self.core_state
        self.time_batcher = Batcher(unroll_length + 1, device=None, dim=0)
        self.future = None
        # Device-rollout mode (moolib_tpu.rollout.DeviceRollout): assigned by
        # the experiment when --device_rollout is on; owns the on-chip
        # [T+1, B] buffer, carried core state, and on-device prev_action —
        # the host fields above then serve only the stats accounting below.
        self.rollout = None
        self.episode_return = np.zeros(batch_size, np.float64)
        self.episode_step = np.zeros(batch_size, np.int64)
        self.running_reward = np.zeros(batch_size, np.float64)
        self.step_count = 0

    def update(self, obs: Dict[str, np.ndarray], stats: Optional[Dict] = None) -> None:
        """Account rewards/episodes for a fresh observation batch."""
        reward = np.asarray(obs["reward"], np.float64)
        done = np.asarray(obs["done"], bool)
        self.episode_return += reward
        self.episode_step += 1
        self.step_count += self.batch_size
        if stats is not None:
            for i in np.nonzero(done)[0]:
                stats["mean_episode_return"] += float(self.episode_return[i])
                stats["mean_episode_step"] += float(self.episode_step[i])
                stats["episodes_done"] += 1
            stats["steps_done"] += self.batch_size
        self.episode_return[done] = 0.0
        self.episode_step[done] = 0


class TsvLogger:
    """Incremental TSV logging (reference ``examples/common/record.py``):
    writes a header once, appends rows, creates a ``latest`` symlink and a
    run ``metadata.json`` (argv, env, start time — reference ``:32-84``)."""

    def __init__(self, path: str, symlink: bool = True, metadata: Optional[dict] = None):
        self.path = path
        self._fields = None
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        if symlink:
            link = os.path.join(os.path.dirname(path) or ".", "latest.tsv")
            try:
                if os.path.islink(link):
                    os.unlink(link)
                os.symlink(os.path.basename(path), link)
            except OSError:
                pass
        import json
        import sys

        meta = {
            "argv": sys.argv,
            "start_time": time.time(),
            "log": os.path.basename(path),
        }
        if metadata:
            meta.update(metadata)
        try:
            with open(os.path.join(os.path.dirname(path) or ".", "metadata.json"), "w") as f:
                json.dump(meta, f, indent=2, default=str)
        except OSError:
            pass

    def log(self, **fields) -> None:
        if self._fields is None:
            self._fields = list(fields)
            with open(self.path, "a") as f:
                f.write("\t".join(["time"] + self._fields) + "\n")
        row = [f"{time.time():.3f}"] + [str(fields.get(k, "")) for k in self._fields]
        with open(self.path, "a") as f:
            f.write("\t".join(row) + "\n")
