#!/bin/bash
# One-command local CI: the same gates .github/workflows/ci.yml runs, with
# graceful degradation for tools this box doesn't have (black/flake8 are
# GitHub-runner-only; the syntax floor is compileall).
#
#   bash scripts/ci.sh            # everything
#   bash scripts/ci.sh quick      # skip the full pytest suite (docs+lint+sanitizers)
set -u
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
fail=0

step() { echo; echo "=== $1"; }

step "syntax floor (compileall)"
python -m compileall -q moolib_tpu tests benchmarks docs/gen_api.py || fail=1

step "lint (black/flake8 if available)"
if python -m black --version >/dev/null 2>&1; then
  # Advisory, matching ci.yml's continue-on-error until a repo-wide format lands.
  python -m black --check --line-length 100 moolib_tpu tests benchmarks \
    || echo "black: formatting differences (advisory)"
else
  echo "black not installed here - runs in .github/workflows/ci.yml"
fi
if python -m flake8 --version >/dev/null 2>&1; then
  python -m flake8 --select=E9,F63,F7,F82 moolib_tpu tests benchmarks || fail=1
else
  echo "flake8 not installed here - runs in .github/workflows/ci.yml"
fi

step "API reference renders (every listed module imports, every page is non-trivial)"
python -m pytest tests/test_docs_api.py -q || fail=1

step "telemetry guard (no bare perf_counter timing outside telemetry/profiling)"
# New timing blocks belong in telemetry spans / Histogram.time() /
# StepTimer (or utils.Timer for raw harnesses), not hand-rolled
# time.perf_counter() pairs — those are invisible to every exporter.
# AST-based (docs/ANALYSIS.md): catches aliased imports the old shell grep
# never saw; intentional sites carry inline pragmas or baseline entries.
python -m moolib_tpu.analysis --check bare-timer || fail=1

step "contract lint (mtlint: host-sync, donation-safety, raw-rng, recompile-risk, blocking-under-lock, metric-docs)"
# Zero NEW findings over the committed baseline (docs/ANALYSIS.md).  The
# baseline for rollout.py, engine/, serving.py and group.py is empty by
# construction — hot-path regressions in those modules fail outright.
python -m moolib_tpu.analysis || fail=1

step "telemetry tests"
python -m pytest tests/test_telemetry.py tests/test_profiling.py -q || fail=1

step "trace-merge tests (cohort stitching, clock-skew correction)"
python -m pytest tests/test_trace_merge.py -q || fail=1

step "device performance plane tests (recompile detector, HBM gauges, MFU, cohort skew)"
python -m pytest tests/test_devmon.py -q || fail=1

step "distributed tracing tests (context propagation, sibling resend spans under frame faults)"
python -m pytest tests/test_tracing_distributed.py -q || fail=1

step "trace-merge smoke (multi-process allreduce + serve request -> one merged Chrome trace)"
# Real subprocesses prove the context actually rides the wire: the merged
# timeline must validate as JSON with >= 1 cross-process parent/child span
# edge per phase (docs/TELEMETRY.md "Distributed tracing").
python scripts/trace_smoke.py --smoke || fail=1

step "fault-domain supervision tests (envpool respawn, watchdog, checkpoint integrity, distributed checkpoints)"
python -m pytest tests/test_envpool_supervision.py tests/test_watchdog.py \
  tests/test_checkpoint_corrupt.py tests/test_checkpoint_distributed.py \
  -q || fail=1

step "warm-rejoin plane tests (chunked model sync resume, compile cache)"
python -m pytest tests/test_accumulator_rejoin.py tests/test_compile_cache.py \
  -q || fail=1

step "flat-bucket data plane (zero-copy serialization, layout golden, bit-exact allreduce)"
python -m pytest tests/test_buckets.py -q || fail=1

step "actor data plane (device rollout vs legacy host batcher: bit-exactness, async fetch, donation safety)"
python -m pytest tests/test_rollout.py -q || fail=1

step "zero-crossing actor plane (jitted on-device envs: backend bit-exactness, scan==per-step, Sebulba handoff)"
python -m pytest tests/test_jax_envs.py -q || fail=1

step "replay data plane tests (host store + RPC shim, device shard bit-exactness, zero recompiles, cohort draw, write-once ingest)"
python -m pytest tests/test_replay.py tests/test_replay_device.py -q || fail=1

step "replay 2-process smoke (memfd-multicast ingest + cohort sampling across a real process boundary)"
# The parent multicasts >1 MB trajectory batches to an in-process shard
# AND a real child-process shard: the publish must take the write-once
# memfd path (bytes counted once per publish, not per consumer), stripes
# must partition, the two-level draw must serve batches from both shards,
# and write-back must move both totals (docs/DESIGN.md §4d).
# MOOLIB_LOCKGRAPH=1: the inline ingest handlers run on the transport IO
# thread against drain/sample on the caller's thread — an observed ABBA
# lock cycle in either process fails at teardown.
MOOLIB_LOCKGRAPH=1 python scripts/replay_smoke.py --smoke || fail=1

step "r2d2 replay A/B (host vs host-RPC vs device store through the full learner cycle)"
# One invocation, shared config: --check fails unless every arm produces
# throughput, device priorities are bit-exact vs the numpy SumTree run
# through the shard's own compiled transform, and ingest is write-once.
# The rows it prints are CPU timings: read, never recorded.
MOOLIB_ALLOW_CPU=1 python benchmarks/r2d2_bench.py --check || fail=1

step "agent smoke (whole-agent loop, all three rollout planes)"
# Smoke gate for the actor data planes (docs/DESIGN.md "Actor data plane" +
# §4c): every plane must finish with steady_sps > 0, and the jax (Anakin)
# arm must additionally measure host_boundary_bytes_per_frame == 0 (both
# enforced by --check).
python benchmarks/agent_bench.py --scale small --rollout all --check || fail=1

step "allreduce smoke (bucketed vs legacy vs numpy reference: tree + ring + q8, loopback bandwidth)"
# Correctness gate for the gradient data plane (docs/DESIGN.md §6b): the
# bucketed tree/ring/q8 results must be bit-consistent cohort-wide and
# match the legacy path / numpy reference; also prints loopback MB/s.
python benchmarks/allreduce_bench.py --smoke || fail=1

step "sharded hierarchical allreduce tests (shard-aligned layouts, typed sharding guard, skip/vbatch composition)"
python -m pytest tests/test_sharded_allreduce.py -q || fail=1

step "sharded allreduce 2-process smoke (per-host grad bytes must drop by the shard factor)"
# Two real processes over loopback run one legacy and one sharded gradient
# round on identical contributions (DESIGN.md §6d): results must be
# bit-identical to the legacy plane AND a numpy reference, and each rank's
# own accum_interhost_bytes_total{kind="grad"} per round must come in at
# <= 0.55x legacy for 2 hosts ((N-1)/N + margin) — the byte drop is
# measured across real process boundaries, not simulated in one process.
shard_port=$((21000 + RANDOM % 20000))
shard_log0="${TMPDIR:-/tmp}/moolib_ci_sharded_r0.log"
shard_log1="${TMPDIR:-/tmp}/moolib_ci_sharded_r1.log"
WORLD_SIZE=2 RANK=1 BROKER_ADDR="127.0.0.1:${shard_port}" \
  python benchmarks/allreduce_bench.py rpc --sharded --smoke > "$shard_log1" 2>&1 &
shard_pid=$!
WORLD_SIZE=2 RANK=0 BROKER_ADDR="127.0.0.1:${shard_port}" \
  python benchmarks/allreduce_bench.py rpc --sharded --smoke > "$shard_log0" 2>&1
shard_rc0=$?
wait "$shard_pid"; shard_rc1=$?
cat "$shard_log0"
if [ "$shard_rc0" != 0 ] || [ "$shard_rc1" != 0 ]; then
  echo "sharded 2-process smoke failed (rc0=$shard_rc0 rc1=$shard_rc1)"
  cat "$shard_log1"
  fail=1
fi

step "sharded allreduce A/B rows (legacy vs sharded per-host bytes)"
# Per-host grad bytes per round on both planes plus the ratio section, over
# three sizes: the spawned world must run every size to its end on both
# planes.
python benchmarks/allreduce_bench.py rpc --sharded --world_size 2 --iters 3 \
  --sizes 10000 100000 1000000 \
  --broker_addr "127.0.0.1:$((21000 + RANDOM % 20000))" || fail=1

step "streaming gradient pipeline tests (bit-exact vs barrier/numpy: tree+ring+q8+sharded, launch leads, epoch-bump + sharding-change failure paths, two-jit overlap step)"
python -m pytest tests/test_streaming_allreduce.py -q || fail=1

step "streaming overlap 2-process smoke (exposed comm per step must drop >= 50% vs barrier at the 10 MB tree)"
# Two real processes over loopback run barrier and streaming gradient
# rounds on identical contributions with a simulated paced backward
# (DESIGN.md §6e): results must be bit-identical to each other AND a numpy
# reference, every non-final bucket must launch with positive lead
# (accum_bucket_launch_lead_seconds > 0), and each rank's OWN exposed comm
# per step must come in at <= 0.5x the barrier arm — the latency-hiding
# claim measured across real process boundaries.  MOOLIB_LOCKGRAPH=1: the
# streaming consume loop holds producer/consumer + accumulator + group
# locks across threads; an observed ABBA cycle fails the run at teardown.
ov_port=$((21000 + RANDOM % 20000))
ov_log0="${TMPDIR:-/tmp}/moolib_ci_overlap_r0.log"
ov_log1="${TMPDIR:-/tmp}/moolib_ci_overlap_r1.log"
WORLD_SIZE=2 RANK=1 BROKER_ADDR="127.0.0.1:${ov_port}" MOOLIB_LOCKGRAPH=1 \
  python benchmarks/allreduce_bench.py rpc --overlap --smoke --iters 3 > "$ov_log1" 2>&1 &
ov_pid=$!
WORLD_SIZE=2 RANK=0 BROKER_ADDR="127.0.0.1:${ov_port}" MOOLIB_LOCKGRAPH=1 \
  python benchmarks/allreduce_bench.py rpc --overlap --smoke --iters 3 > "$ov_log0" 2>&1
ov_rc0=$?
wait "$ov_pid"; ov_rc1=$?
cat "$ov_log0"
if [ "$ov_rc0" != 0 ] || [ "$ov_rc1" != 0 ]; then
  echo "overlap 2-process smoke failed (rc0=$ov_rc0 rc1=$ov_rc1)"
  cat "$ov_log1"
  fail=1
fi

step "streaming overlap A/B rows (barrier vs streaming exposed comm per step)"
# Round wall time and exposed_ms per step on both arms plus the ratio
# section, over two sizes: the spawned world must run both arms to their end
# under the lock-order detector.
MOOLIB_LOCKGRAPH=1 python benchmarks/allreduce_bench.py rpc --overlap \
  --world_size 2 --iters 3 --sizes 1000000 2621440 \
  --broker_addr "127.0.0.1:$((21000 + RANDOM % 20000))" || fail=1

step "chaos soak (seeded, ~80 s smoke: worker/peer kills + respawn SLO, RPC frame chaos, forced-kill resume, mid-shard-write kill + distributed checkpoint resume)"
# Exits non-zero if any phase stalls past its watchdog/deadline, or the
# respawned peer misses its recovery bound (docs/RESILIENCE.md recovery
# budget).  The persistent compile cache (utils/compile_cache.py: where
# JAX_COMPILATION_CACHE_DIR says, else <repo>/.jax_cache) is what keeps the
# respawn's first_compile phase inside the bound — the soak exercises the
# same mechanism production restarts rely on.
# MOOLIB_LOCKGRAPH=1: every threading.Lock/RLock in every soak process is
# instrumented; an observed ABBA acquisition-order cycle fails the run at
# teardown with both stacks (moolib_tpu/testing/lockgraph.py).
MOOLIB_LOCKGRAPH=1 \
  python scripts/chaos_soak.py --smoke --recovery_bound_s 60 || fail=1

step "autoscaler tests (policy decisions, graceful leave, vbatch stability across resize)"
python -m pytest tests/test_autoscaler.py -q || fail=1

step "autoscale soak (Poisson preemption: respawn SLO, sub-second graceful decommission, vbatch stability)"
# Exits non-zero on any unrecovered kill (replacement not contributing
# within --recovery_bound_s), a graceful decommission that burned the
# ping-eviction timeout instead of __broker_leave, or any vbatch_violation
# in a worker log (docs/RESILIENCE.md "Autoscaling").
python scripts/autoscale_soak.py --smoke --recovery_bound_s 90 || fail=1

step "serving plane tests (hot swap mid-traffic, typed admission rejects, req-id dedup, failover)"
python -m pytest tests/test_serving.py -q || fail=1

step "serving soak (seeded, ~40 s smoke: replica SIGKILL mid-stream + live hot-swap under paced load)"
# Exits non-zero on any lost request (a future that errored or never
# resolved), a hot swap that failed to land / record serve_swap_seconds,
# or any admission reject attributable to the swap
# (docs/RESILIENCE.md "Serving soak").
# Thread-heaviest path in the tree — runs under the lock-order detector.
MOOLIB_LOCKGRAPH=1 python scripts/serve_soak.py --smoke || fail=1

step "paged-attention / engine tests (paged==dense bit-exact MHA+GQA, pool invariants, one-compile decode)"
python -m pytest tests/test_paged_attention.py -q || fail=1

step "engine serving soak (same SIGKILL + hot-swap gates through the continuous-batching arm)"
# The engine replica must satisfy the identical resilience contract as the
# batch-synchronous arm: zero lost requests across the kill, swap lands
# between iterations, no swap-attributable rejects (DESIGN.md §6c).
MOOLIB_LOCKGRAPH=1 python scripts/serve_soak.py --smoke --engine || fail=1

step "elasticity swing soak (calm -> 5x surge -> quiet through real engine replicas + autoscaler)"
# Gates: fleet grows on sustained serve_queue_wait_s during the surge,
# reaches two replicas, gracefully shrinks back on serve_idle when quiet,
# and zero requests are lost across the scale events (DESIGN.md §6c;
# --service_delay_ms pins per-iteration cost so saturation is
# deterministic on any host).
MOOLIB_LOCKGRAPH=1 python scripts/serve_soak.py --smoke --swing || fail=1

step "engine A/B smoke (continuous batching vs batch-sync under mixed budgets)"
# Same broker, same admission contract, same paced open-loop load — only
# the service loop differs.  --check fails on any hard/deadline error in
# either arm or engine tokens/s below the baseline's (DESIGN.md §6c).
python benchmarks/serve_bench.py --qps 100 --seconds 6 --engine \
  --mixed_tokens 8 8 32 96 --d_model 128 --layers 2 --heads 4 \
  --batch_sizes 8 --max_new_tokens 96 --deadline_s 20 --max_queue 256 \
  --check || fail=1

step "broker HA tests (hot-standby failover, partition healing, generation fencing)"
python -m pytest tests/test_group.py -q \
  -k "broker_failover or partition_heals or split_brain or zombie or stale_push or standby_serves" || fail=1

step "broker soak (seeded, ~30 s smoke: primary SIGKILL mid-allreduce + mid-serve)"
# Exits non-zero on any recovery_seconds{phase="broker_failover"} span past
# the budget, a peer left on a stale generation fence, or any lost serve
# request across the takeover (docs/RESILIENCE.md "Broker failover").
python scripts/broker_soak.py --smoke || fail=1

step "sanitizer matrix (skips where the runtime is missing)"
python -m pytest tests/test_native_sanitizers.py -q || fail=1

if [ "${1:-}" != "quick" ]; then
  step "full suite (~25 min on a 1-core box)"
  python -m pytest tests/ -x -q || fail=1
fi

echo
[ "$fail" = 0 ] && echo "CI OK" || echo "CI FAILED"
exit $fail
