"""Headline benchmark: IMPALA learner throughput on the flagship model.

Times the full jitted train step (ImpalaNet forward + v-trace loss + backward
+ RMSProp update) on the reference's Atari configuration
(``examples/vtrace/config.yaml:23-65``: 84x84x4 frames, batch_size 32 unrolls,
unroll_length 20) and reports environment frames consumed per second by the
learner — the north-star "IMPALA Atari SPS per chip" metric (BASELINE.json) —
plus **MFU** (model FLOPs per step from XLA cost analysis / chip peak from
``telemetry.devmon``'s table).

One process, one chip.  Fails non-zero unless jax's platform is ``tpu``: a
number from any other backend is not this benchmark's metric.  Prints one
JSON line naming the device it ran on:
  {"metric": ..., "value": N, "unit": ..., "platform": "tpu",
   "device_kind": ..., "device_count": N, ...}

Timing is a host clock around ``block_until_ready``; compilation is reported
apart as set-up.

The reference repo publishes no numeric baselines (BASELINE.md), so
``vs_baseline`` is reported against the reference's only hard floor: the
config's own real-time requirement (learner must keep up with 2*128 actor
envs at ~60 fps emulator speed ≈ 15,360 frames/s) — values > 1 mean the
learner outpaces the reference's full actor fleet.
"""

import json
import os
import time

# Reference IMPALA defaults (examples/vtrace/config.yaml).
NUM_ACTIONS = 6
OBS = (84, 84, 4)
DISCOUNTING = 0.99
REALTIME_FLOOR_SPS = 2 * 128 * 60.0  # reference actor fleet at emulator speed
# Encoder widths.  The default is the reference geometry whose narrow
# channels cap the MXU lane-occupancy ceiling at 0.148 (not measured on the
# chip yet); a wide run (MOOLIB_BENCH_CHANNELS=64,128,128, analytic ceiling
# 0.789) makes that explanation falsifiable on hardware: if the ceiling story
# is right, measured MFU must rise with width, at a similar mfu_vs_ceiling
# fraction.
REF_CHANNELS = (16, 32, 32)  # single source for the reference geometry
CHANNELS = tuple(
    int(c) for c in os.environ["MOOLIB_BENCH_CHANNELS"].split(",")
) if "MOOLIB_BENCH_CHANNELS" in os.environ else REF_CHANNELS
# Unroll/batch overrides make a smaller or larger cell.  Overridden shapes
# are labeled: the metric gains a _smoke suffix and the row records T/B, so
# such a run can never pass for the headline row.
REF_T, REF_B = 20, 32  # unroll_length, batch_size (unrolls per learner step)
T = int(os.environ.get("MOOLIB_BENCH_T", REF_T))
B = int(os.environ.get("MOOLIB_BENCH_B", REF_B))


def _metric_name():
    """Row label carrying the geometry/shape overrides, so a non-reference
    configuration can never publish under the headline metric name."""
    metric = "impala_learner_sps_wide" if CHANNELS != REF_CHANNELS else "impala_learner_sps"
    if (T, B) != (REF_T, REF_B):
        metric += "_smoke"
    return metric


def build_step():
    """Construct the reference-config IMPALA learner step: ImpalaNet forward
    + v-trace loss + RMSProp update on the Atari shapes.  Shared by the
    benchmark loop below and ``benchmarks/impala_roofline.py`` so the
    roofline analysis characterizes exactly the step that is timed.

    Returns ``(step, params, opt_state, batch)`` with ``step`` jitted and
    donating params/opt_state (the update happens in place in HBM instead of
    allocating fresh buffers every step — matters at Atari-model size).
    """
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from moolib_tpu.models import ImpalaNet
    from moolib_tpu.ops import entropy_loss, softmax_cross_entropy, vtrace

    def loss_fn(params, batch, model):
        out, _ = model.apply(params, batch, ())
        target_logits = out["policy_logits"][:-1]
        baseline = out["baseline"]
        vt = vtrace.from_logits(
            batch["policy_logits"][:-1],
            target_logits,
            batch["action"][:-1],
            (~batch["done"][1:]).astype(jnp.float32) * DISCOUNTING,
            jnp.clip(batch["reward"][1:], -1, 1),
            baseline[:-1],
            jax.lax.stop_gradient(baseline[-1]),
        )
        pg = jnp.mean(
            softmax_cross_entropy(target_logits, batch["action"][:-1]) * vt.pg_advantages
        )
        bl = 0.5 * jnp.mean((vt.vs - baseline[:-1]) ** 2)
        ent = entropy_loss(target_logits)
        return pg + 0.5 * bl + 0.01 * ent

    model = ImpalaNet(
        num_actions=NUM_ACTIONS, use_lstm=False, dtype=jnp.bfloat16,
        channels=CHANNELS,
    )
    rng = np.random.default_rng(0)
    batch = {
        "state": jnp.asarray(rng.integers(0, 256, size=(T + 1, B, *OBS), dtype=np.uint8)),
        "reward": jnp.asarray(rng.normal(size=(T + 1, B)).astype(np.float32)),
        "done": jnp.asarray(rng.random((T + 1, B)) < 0.02),
        "prev_action": jnp.asarray(rng.integers(0, NUM_ACTIONS, size=(T + 1, B))),
        "action": jnp.asarray(rng.integers(0, NUM_ACTIONS, size=(T + 1, B))),
        "policy_logits": jnp.asarray(rng.normal(size=(T + 1, B, NUM_ACTIONS)).astype(np.float32)),
    }
    params = model.init(jax.random.key(0), batch, ())
    opt = optax.rmsprop(1e-3, decay=0.99, eps=0.01)
    opt_state = opt.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(partial(loss_fn, model=model))(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step, params, opt_state, batch


def main(warmup: int = 3, iters: int = 20):
    import jax

    from moolib_tpu.telemetry import devmon
    from moolib_tpu.utils import init_compile_cache

    init_compile_cache()
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip: platform is {device.platform!r}, "
            "not 'tpu'"
        )
    step, params, opt_state, batch = build_step()

    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    compile_s = time.perf_counter() - t0
    cost = devmon.step_cost("bench.step", step, params, opt_state, batch)

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    sps = T * B * iters / dt
    out = {
        "metric": _metric_name(),
        "value": round(sps, 1),
        "unit": "env_frames/s",
        "vs_baseline": round(sps / REALTIME_FLOOR_SPS, 3),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(devices),
        "step_ms": round(dt / iters * 1000, 3),
        "compile_s": round(compile_s, 2),
    }
    if CHANNELS != REF_CHANNELS:
        out["channels"] = list(CHANNELS)
    if (T, B) != (REF_T, REF_B):
        out["T"], out["B"] = T, B
    if cost is not None:
        out["model_tflops_per_step"] = round(cost.flops / 1e12, 4)
        out["mfu"] = round(
            cost.flops * iters / dt / devmon.peak_flops(device.device_kind), 4
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
