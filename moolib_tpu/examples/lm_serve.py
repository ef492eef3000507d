"""Batched LM generation served over RPC — inference batching (SURVEY.md
§2.3, ``define_queue(dynamic_batching=True)``) applied to the TransformerLM.

A server peer owns the model and a dynamic-batching queue: concurrent
single-prompt calls from many client peers are stacked into one batch, run
through :func:`..models.transformer.generate` (KV-cache decoding) in a
single jitted call, and unbatched back to each caller — the reference's
cross-caller inference batching (``src/moolib.cc:1007-1178``), here feeding
a TPU generation step instead of a torch policy.

Serve:  python -m moolib_tpu.examples.lm_serve --listen 127.0.0.1:4460
Client: python -m moolib_tpu.examples.lm_serve --connect 127.0.0.1:4460 \\
            --prompts 3 (sends 3 concurrent prompts, prints continuations)

The resilient tier (``moolib_tpu.serving``) layers on top: start N servers
with ``--broker`` (each registers as a non-contributing cohort observer and
subscribes to ``--publisher`` for zero-downtime weight hot-swap), and point
clients at the broker instead of a replica — they discover the fleet,
spread load, and retry idempotently across replica deaths:

Broker:   python -m moolib_tpu.broker --address 127.0.0.1:4431
Replica:  python -m moolib_tpu.examples.lm_serve --listen 127.0.0.1:4460 \\
              --broker 127.0.0.1:4431 --name replica0 [--publisher pusher]
Client:   python -m moolib_tpu.examples.lm_serve --broker 127.0.0.1:4431

``--connect`` stays the single-shot, no-retry baseline against one server.

With a replicated broker control plane (a primary plus hot standbys, see
docs/RESILIENCE.md "Broker failover"), pass the whole list instead —
replicas and clients ping the primary and fail over on its death:

    --broker_addrs 127.0.0.1:4431,127.0.0.1:4432

Prompts in one batch must share a length (the queue stacks them); pad
client-side for mixed lengths.

``--engine`` (ISSUE 12) swaps the batch-synchronous replica plane for the
continuous-batching engine (``moolib_tpu.engine``): decode slots over a
paged KV cache, per-request token budgets (clients pass ``max_new`` as the
second positional arg), admission in per-token units — same broker
registration, hot-swap, and stats surface, so every client above works
unchanged.  Without ``--engine`` the replica arm still honors per-request
budgets (``per_request_tokens``), but decodes each batch to the row max —
the convoy the engine arm removes.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry, utils
from ..models.transformer import TransformerLM, generate
from ..rpc import Rpc
from ..serving import bucket as _bucket
from ..serving import bucket_shapes as _bucket_shapes
from . import common

# Same registry object serving.py binds (registration is idempotent): the
# legacy serve() loop and ServeService count batch retries into one metric.
_M_BATCH_RETRY = telemetry.get_registry().counter(
    "serve_batch_retries_total",
    "failed batches retried unbatched (blast-radius isolation)",
)


def make_model(flags):
    return TransformerLM(
        vocab_size=flags.vocab,
        d_model=flags.d_model,
        num_heads=flags.heads,
        num_kv_heads=getattr(flags, "kv_heads", 0) or None,
        num_layers=flags.layers,
        attention="dense",
        dtype=jnp.float32,
        pos_embedding="rotary",
        max_len=flags.seq_len + flags.max_new_tokens,
    )


def serve(rpc: Rpc, model, params, max_new_tokens: int, *, name: str = "generate",
          batch_size: int = 16, total=None, mesh=None, dynamic_batching: bool = True,
          warm_seq_len: Optional[int] = None):
    """Coroutine serving ``total`` prompts (None = forever).  Returns the
    number of *service iterations* — with concurrent callers this is smaller
    than the prompt count, which is the point of dynamic batching.

    ``mesh``: serve tensor-parallel — the generate step runs sharded over
    the mesh (params via ``parallel.auto_shardings``), so one server peer
    can front a model larger than a single chip's HBM.  ``dynamic_batching``
    off serves one call per iteration (the serve_bench baseline).

    Dynamic batches are PADDED to the next power-of-two bucket (capped at
    ``batch_size``) before the jitted generate: XLA compiles per shape, so
    letting the batch dimension float would turn every new queue depth into
    a multi-second compile (measured as 100x p99 spikes in serve_bench),
    while always padding to the full cap wastes pad-row compute whenever
    the offered load is below it (measured as cap 16 at avg fill 4.7 — 70%
    waste — losing to batch-1 on CPU).  Buckets bound the compile count to
    log2(batch_size)+1 shapes and the waste to <2x actual load."""
    queue = rpc.define_queue(
        name,
        batch_size=batch_size if dynamic_batching else None,
        dynamic_batching=dynamic_batching,
    )
    # Service-quality introspection for load benches: queue wait/fill/depth
    # counters plus the server's own iteration count (serve_bench diffs two
    # snapshots around its measurement window).
    counters = {"served": 0, "iterations": 0, "bucket_pad_rows": 0,
                "batch_retries": 0}
    rpc.define(f"{name}_stats", lambda: {**queue.stats(), **counters,
                                         "batch_size": batch_size if dynamic_batching else 1})
    if mesh is not None:
        # Built ONCE: the returned fn is a plain jit, so repeated batches of
        # the same prompt shape hit the compile cache.
        from ..models.transformer import sharded_generator

        jgen = sharded_generator(model, params, max_new_tokens, mesh)
    else:
        jgen = jax.jit(lambda p, prompts: generate(model, p, prompts, max_new_tokens))

    if warm_seq_len is not None:
        # Non-dynamic service runs single prompts as (1, L); dynamic runs
        # every bucket shape up to the cap.
        shapes = _bucket_shapes(batch_size) if dynamic_batching else [1]
        for b in shapes:
            np.asarray(jgen(params, jnp.zeros((b, warm_seq_len), jnp.int32)))

    async def loop():
        served = iterations = 0
        while total is None or served < total:
            ret_cb, args, kwargs = await queue
            prompts = np.asarray(args[0])
            single = prompts.ndim == 1
            if single:
                prompts = prompts[None]
            n = prompts.shape[0]
            served += n
            iterations += 1
            counters["served"], counters["iterations"] = served, iterations
            if dynamic_batching and n < batch_size:
                bucket = _bucket(n, batch_size)
                if n < bucket:
                    pad = np.repeat(prompts[-1:], bucket - n, axis=0)
                    batch = np.concatenate([prompts, pad], axis=0)
                else:
                    batch = prompts
                counters["bucket_pad_rows"] += bucket - n
            else:
                batch = prompts
            try:
                out = np.asarray(jgen(params, jnp.asarray(batch)))[:n]
            except jax.errors.JaxRuntimeError:
                raise  # the device or the compiler failed, not the request
            except Exception as e:  # noqa: BLE001 — fail small, keep serving
                rets = getattr(ret_cb, "rets", None)
                if rets is None:
                    # Single caller: the failure is already its own.
                    ret_cb.error(f"generate failed: {e}")
                    continue
                # Blast-radius isolation: one poisoned prompt must not error
                # every caller stacked into its batch — retry once unbatched
                # (row i belongs to caller i) so only the offender fails.
                counters["batch_retries"] += 1
                _M_BATCH_RETRY.inc()
                for i, ret in enumerate(rets):
                    try:
                        row = np.asarray(
                            jgen(params, jnp.asarray(prompts[i][None]))
                        )[0]
                    except jax.errors.JaxRuntimeError:
                        raise
                    except Exception as e2:  # noqa: BLE001
                        ret.error(f"generate failed: {e2}")
                        continue
                    ret(row)
                continue
            ret_cb(out[0] if single else out)
        return iterations

    return loop()


def main(argv=None):
    p = argparse.ArgumentParser(description="batched LM generation over RPC")
    p.add_argument("--listen", default=None, help="serve on this address")
    p.add_argument("--connect", default=None,
                   help="request from this address (single-shot, no-retry "
                   "baseline against one server)")
    p.add_argument("--broker", default=None,
                   help="broker address: with --listen, register this "
                   "server as a serving replica (non-contributing cohort "
                   "observer, ServeClient-discoverable); without --listen, "
                   "run the resilient client (replica discovery + retry + "
                   "failover)")
    p.add_argument("--broker_addrs", default=None,
                   help="comma-separated broker addresses (primary + hot "
                   "standbys, docs/RESILIENCE.md 'Broker failover'): like "
                   "--broker but replicas and clients fail over across the "
                   "list on primary death; supersedes --broker when both "
                   "are given")
    p.add_argument("--broker_name", default="broker")
    p.add_argument("--group", default="serve",
                   help="broker group replicas register in / clients "
                   "discover from")
    p.add_argument("--name", default="lm_server",
                   help="this server's peer name (replicas need unique "
                   "names; --connect clients call this name)")
    p.add_argument("--publisher", default=None,
                   help="server: subscribe to this peer's ModelPublisher "
                   "for zero-downtime weight hot-swap")
    p.add_argument("--model_channel", default="model",
                   help="publisher endpoint prefix under --publisher")
    p.add_argument("--max_queue", type=int, default=128,
                   help="replica admission-queue bound (requests beyond it "
                   "are rejected immediately with a typed overload error)")
    p.add_argument("--deadline_s", type=float, default=30.0,
                   help="client per-request deadline budget (replicas "
                   "reject requests that cannot meet it)")
    p.add_argument("--prompts", type=int, default=3, help="concurrent client prompts")
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--seq_len", type=int, default=16)
    p.add_argument("--d_model", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument(
        "--kv_heads", type=int, default=0,
        help="grouped-query attention (0 = heads): shrinks the decode "
        "KV cache by heads/kv_heads",
    )
    p.add_argument("--max_new_tokens", type=int, default=16)
    p.add_argument(
        "--batch_size", type=int, default=16,
        help="dynamic-batching cap: batches pad to power-of-two buckets up "
        "to this (all bucket shapes pre-compiled at startup)",
    )
    p.add_argument(
        "--mesh",
        default="",
        help='serve tensor-parallel over these axes, e.g. "tp=8" (server '
        "side only, not with --engine; params sharded via auto_shardings)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--no_dynamic_batching", action="store_true",
        help="serve one call per iteration (latency baseline for serve_bench)",
    )
    p.add_argument(
        "--engine", action="store_true",
        help="serve with the continuous-batching engine (paged KV cache, "
        "per-request budgets, no convoy) instead of batch-synchronous "
        "generate",
    )
    p.add_argument(
        "--config", default="",
        help="with --engine: build the model from this configuration file "
        "(published keys, as chipbench/configs/*.json; its \"model\" names "
        "the class: models/latent_moe.py, models/hybrid_kda.py) instead of "
        "from --vocab/--d_model/--layers/--heads",
    )
    p.add_argument(
        "--slots", type=int, default=0,
        help="engine decode slots (0 = --batch_size)",
    )
    p.add_argument(
        "--block_size", type=int, default=16,
        help="engine KV pool block size in tokens",
    )
    p.add_argument(
        "--service_delay_ms", type=float, default=0.0,
        help="add this many milliseconds to every service iteration — a "
        "load-testing hook that makes saturation (and so the autoscaler's "
        "queue-wait signal) deterministic on any host; never use in "
        "production",
    )
    p.add_argument(
        "--localdir", default=None,
        help="per-peer scratch dir: the autoscaler's decommission flag is "
        "polled here (set MOOLIB_TELEMETRY_DIR to it for snapshots)",
    )
    flags = p.parse_args(argv)
    started = time.monotonic()
    # One broker list everywhere below: --broker_addrs (HA) wins, --broker
    # stays as the single-address alias.
    broker_list = [a.strip() for a in (flags.broker_addrs or "").split(",")
                   if a.strip()]
    if not broker_list and flags.broker:
        broker_list = [flags.broker]
    if flags.listen is None and (flags.connect is None) == (not broker_list):
        raise SystemExit(
            "pass --listen, --connect, or --broker/--broker_addrs (client mode)")
    if flags.listen is not None and flags.connect is not None:
        raise SystemExit("--listen and --connect are mutually exclusive")
    if flags.config and not (flags.engine and flags.listen):
        raise SystemExit("--config builds a model only the engine serves: "
                         "pass --engine and --listen with it")
    if flags.engine and flags.mesh:
        raise SystemExit("--engine serves from one device: --mesh is the "
                         "batch-synchronous arm's")
    utils.init_compile_cache()  # before the first jit (utils/compile_cache.py)
    telemetry.init_from_env()  # opt-in exporters (docs/TELEMETRY.md)

    model = None if flags.config else make_model(flags)
    if flags.listen:
        from .. import parallel

        mesh = parallel.parse_mesh_spec(flags.mesh)
        if flags.config:
            # The file names the class that builds it ("model":
            # "<module>:<class>"), as the benchmark's runner reads it.
            with open(flags.config) as f:
                module, _, name = json.load(f).get(
                    "model", "moolib_tpu.models.latent_moe:LatentMoELM").partition(":")
            model_class = getattr(importlib.import_module(module), name)
            # bfloat16 as the configurations state it, on the chip; the CPU
            # backend has no bfloat16 x bfloat16 -> float32 product.
            model = model_class.from_config(
                flags.config, max_len=flags.seq_len + flags.max_new_tokens,
                dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32)
            params = jax.jit(model.init)(jax.random.key(flags.seed))
        else:
            rng = np.random.default_rng(flags.seed)
            toks = jnp.asarray(rng.integers(0, flags.vocab, (1, flags.seq_len), dtype=np.int32))
            params = model.init(jax.random.key(flags.seed), toks)
        rpc = Rpc()
        rpc.set_name(flags.name)
        rpc.listen(flags.listen)
        replica = None
        try:
            # serve() defines the queue and pre-compiles every bucket shape
            # BEFORE the readiness line prints: clients arriving at
            # "serving" must never queue behind a startup compile.  The
            # pre-compile line below is the harness's proof of life: a
            # benchmark can tell "server is compiling (be patient)" from
            # "server never came up" (serve_bench keys its two timeouts on
            # exactly these two lines).
            if flags.engine:
                nbuckets = len(set(_bucket_shapes(flags.seq_len))) + 1
            elif flags.no_dynamic_batching:
                nbuckets = 1
            else:
                nbuckets = len(_bucket_shapes(flags.batch_size))
            print(
                f"precompiling {nbuckets} bucket shape(s) "
                f"[platform={jax.devices()[0].platform}]",
                flush=True,
            )
            if flags.engine:
                # Continuous-batching arm: slots over a paged KV cache
                # under the same ServeService contract (engine/service.py).
                # warmup() compiles every prefill bucket, every join block
                # count, and the decode step BEFORE the readiness line.
                from .. import serving as serving_mod
                from ..engine import ContinuousBatchingEngine, EngineService

                engine = ContinuousBatchingEngine(
                    model, params,
                    slots=flags.slots or flags.batch_size,
                    block_size=flags.block_size,
                    max_prompt_len=flags.seq_len,
                )
                engine.warmup()
                if flags.service_delay_ms > 0:
                    _eng_step = engine.step

                    def _slow_step():
                        time.sleep(flags.service_delay_ms / 1e3)
                        return _eng_step()

                    engine.step = _slow_step
                service = EngineService(
                    rpc, engine, name="generate",
                    max_queue=flags.max_queue,
                    default_max_new=flags.max_new_tokens,
                )
                replica = serving_mod.ServeReplica(
                    rpc, None, params, name="generate", service=service,
                    broker=broker_list[0] if broker_list else None,
                    brokers=broker_list[1:],
                    broker_name=flags.broker_name,
                    group=flags.group,
                    publisher=flags.publisher,
                    model_channel=flags.model_channel,
                )
                loop = replica.loop()
            elif broker_list or flags.publisher:
                # Resilient replica: admission control + request dedup +
                # hot-swap staging (moolib_tpu.serving), with the same
                # bucket policy and pre-compile contract as serve().
                # Per-request budgets ride as a third step_fn argument;
                # each batch decodes to its row-max budget (bucketed so
                # the jit cache stays bounded: one entry per (rows, decode
                # bucket) pair).
                from .. import serving as serving_mod

                jits = {}

                def _jgen(mn):
                    fn = jits.get(mn)
                    if fn is None:
                        fn = jax.jit(
                            lambda p_, prompts, m=mn: generate(
                                model, p_, prompts, m
                            )
                        )
                        jits[mn] = fn
                    return fn

                def step(p_, batch, budgets=None):
                    if flags.service_delay_ms > 0:
                        time.sleep(flags.service_delay_ms / 1e3)
                    mn = (flags.max_new_tokens if budgets is None
                          else int(np.max(budgets)))
                    mn = _bucket(mn, flags.max_new_tokens)
                    return np.asarray(_jgen(mn)(p_, jnp.asarray(batch)))

                shapes = (_bucket_shapes(flags.batch_size)
                          if not flags.no_dynamic_batching else [1])
                for b in shapes:
                    np.asarray(_jgen(flags.max_new_tokens)(
                        params, jnp.zeros((b, flags.seq_len), jnp.int32)
                    ))
                replica = serving_mod.ServeReplica(
                    rpc, step, params,
                    name="generate",
                    batch_size=flags.batch_size,
                    dynamic_batching=not flags.no_dynamic_batching,
                    max_queue=flags.max_queue,
                    broker=broker_list[0] if broker_list else None,
                    brokers=broker_list[1:],
                    broker_name=flags.broker_name,
                    group=flags.group,
                    publisher=flags.publisher,
                    model_channel=flags.model_channel,
                    per_request_tokens=True,
                    default_max_new=flags.max_new_tokens,
                )
                loop = replica.loop()
            else:
                loop = serve(
                    rpc, model, params, flags.max_new_tokens, mesh=mesh,
                    batch_size=flags.batch_size,
                    dynamic_batching=not flags.no_dynamic_batching,
                    warm_seq_len=flags.seq_len,
                )
            print(
                f"serving 'generate' on {flags.listen} "
                f"[platform={jax.devices()[0].platform}]",
                flush=True,
            )
            # Everything serving can hit is compiled by now: the readiness
            # report is the replica's whole set-up bill.
            common.print_report(
                {"engine": flags.engine, "warm_shapes": nbuckets,
                 "param_placement": common.placement_of(params)},
                started,
            )
            if flags.localdir:
                # Fleet membership: the autoscaler decommissions a serving
                # replica by dropping the flag file; draining is the
                # service close (queued requests get typed errors, the
                # broker sees an explicit leave via replica.close()).
                import threading

                from .. import autoscaler as autoscaler_mod

                rep = replica

                def _watch_decommission():
                    while True:
                        if autoscaler_mod.decommission_requested(
                                flags.localdir):
                            print("decommission requested; leaving",
                                  flush=True)
                            if rep is not None:
                                rep.close()
                            else:
                                rpc.close()
                            return
                        time.sleep(0.5)

                threading.Thread(target=_watch_decommission,
                                 daemon=True).start()
            asyncio.run(loop)
        finally:
            if replica is not None:
                replica.close()
            rpc.close()
    else:
        from .. import serving as serving_mod

        rpc = Rpc()
        rpc.set_name("lm_client")
        if flags.connect:
            # Single-shot baseline: one static server, no retries, no
            # metadata (works against the legacy serve() queue).
            rpc.connect(flags.connect)
            client = serving_mod.ServeClient(
                rpc, fn="generate", replicas=[flags.name],
                deadline_s=flags.deadline_s, max_attempts=1, metadata=False,
            )
        else:
            # Resilient path: broker discovery, load spreading, idempotent
            # retry with capped exponential backoff across replica deaths.
            client = serving_mod.ServeClient(
                rpc, fn="generate", broker=broker_list[0],
                brokers=broker_list[1:],
                broker_name=flags.broker_name, group=flags.group,
                deadline_s=flags.deadline_s,
            )
            client.wait_for_replicas(1, timeout=flags.deadline_s)
        rng = np.random.default_rng(flags.seed + 1)
        futs = []
        for _ in range(flags.prompts):
            prompt = rng.integers(2, flags.vocab, flags.seq_len).astype(np.int32)
            futs.append((prompt, client.submit(prompt)))
        for prompt, fut in futs:
            out = np.asarray(fut.result(flags.deadline_s + 5.0))
            print(f"prompt={prompt.tolist()}\n  -> {out[len(prompt):].tolist()}")
        client.close()
        rpc.close()


if __name__ == "__main__":
    main()
