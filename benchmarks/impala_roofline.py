"""Roofline bound analysis for the IMPALA learner step.

Compiles bench.py's exact train step (``bench.build_step()``: ImpalaNet +
v-trace + RMSProp at the reference's Atari config) and pulls XLA cost
analysis: model FLOPs and bytes accessed per step.  Arithmetic intensity vs
the chip's compute/bandwidth ratio states which resource bounds the step —
the profile-backed statement that must accompany the MFU number.  Optionally
captures a jax profiler trace (--trace_dir) for later inspection.

Peak FLOP/s and HBM bandwidth come from the per-chip tables in
``moolib_tpu.telemetry.devmon`` — the same numbers the always-on ``step_mfu``
gauge is computed against, so this script and production telemetry can never
disagree about the denominator.  On the CPU backend (no peaks) only the
counted cost and the geometry ceiling are reported.

    python benchmarks/impala_roofline.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def analytic_mxu_ceiling(channels=None, obs=None,
                         t1=None, b=None, hidden=256, num_actions=None):
    """MXU-utilization ceiling implied by the model's *geometry alone*.

    The TPU MXU is a 128x128 systolic array: a matmul whose contraction dim
    K or output dim N is below 128 (or not a multiple of it) leaves lanes
    idle no matter how well XLA schedules.  An ImpalaNet conv is a matmul
    with K = 3*3*C_in and N = C_out, so at the reference's 16/32-channel
    geometry every conv is capped at N/128 <= 25% lane occupancy.  This
    computes the per-layer ceiling K/ceil128(K) * N/ceil128(N), weights it
    by each layer's FLOP share, and returns the step-level ceiling that an
    *ideal* schedule could reach — the honest denominator for the measured
    MFU.  Forward geometry is used for the fwd+bwd step (backward matmul
    shapes keep the same narrow-channel N; documented approximation).

    Needs no accelerator: pure arithmetic on the model config.  Geometry
    defaults resolve from bench.py's constants (stdlib-only import) so the
    published ceiling cannot silently desync from the benchmarked step;
    channels/hidden mirror ImpalaNet's defaults and are cross-checked
    against XLA's counted FLOPs in tests/test_roofline.py.
    """
    import math

    import bench

    if channels is None:
        # Track bench.py's (env-overridable) geometry so the ceiling printed
        # beside a measured step can never desync from the model measured —
        # including a MOOLIB_BENCH_CHANNELS wide run.
        channels = bench.CHANNELS
    if obs is None:
        obs = bench.OBS
    if t1 is None:
        t1 = bench.T + 1
    if b is None:
        b = bench.B
    if num_actions is None:
        num_actions = bench.NUM_ACTIONS

    layers = []

    def mm(name, m, k, n, flops=None):
        f = flops if flops is not None else 2.0 * m * k * n
        util = (k / (math.ceil(k / 128) * 128)) * (n / (math.ceil(n / 128) * 128))
        layers.append({"layer": name, "gflops": f / 1e9, "mxu_util_ceiling": util})

    h, w, cin = obs
    for ch in channels:
        mm(f"conv{h}x{w} {cin}->{ch}", t1 * b * h * w, 9 * cin, ch)
        h, w = math.ceil(h / 2), math.ceil(w / 2)
        for _ in range(4):  # two residual blocks, two convs each
            mm(f"conv{h}x{w} {ch}->{ch}", t1 * b * h * w, 9 * ch, ch)
        cin = ch
    flat = h * w * cin
    mm(f"fc {flat}->{hidden}", t1 * b, flat, hidden)
    mm("policy head", t1 * b, hidden + 1 + num_actions, num_actions)
    mm("baseline head", t1 * b, hidden + 1 + num_actions, 1)

    total = sum(l["gflops"] for l in layers)
    ceiling = sum(l["gflops"] * l["mxu_util_ceiling"] for l in layers) / total
    for l in layers:
        l["gflops"] = round(l["gflops"], 3)
        l["mxu_util_ceiling"] = round(l["mxu_util_ceiling"], 3)
        l["flop_share"] = round(l["gflops"] / total, 3)
    max_ch = max(channels)
    return {
        "channels": list(channels),  # label the geometry the ceiling is FOR
        "forward_gflops": round(total, 2),
        "weighted_mxu_ceiling": round(ceiling, 4),
        "note": (
            f"geometry-implied MFU ceiling at channels={list(channels)}: convs "
            f"with C_out<={max_ch} use <={min(100, round(100 * max_ch / 128))}% "
            "of the MXU's 128 output lanes; no schedule or batch size can "
            "exceed this at this model shape"
        ),
        "layers": layers,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace_dir", default=None,
                    help="also capture a jax profiler trace of a few steps")
    ap.add_argument("--analytic_only", action="store_true",
                    help="print the geometry ceiling and exit (no accelerator)")
    args = ap.parse_args()

    # Print the chip-free analytic bound FIRST and flush: a hung TPU backend
    # init (the round 3-4 failure mode) must not erase the part of the
    # analysis that needs no hardware.
    analytic = analytic_mxu_ceiling()
    ceiling = analytic["weighted_mxu_ceiling"]
    print(json.dumps({"analytic": {k: v for k, v in analytic.items() if k != "layers"},
                      "per_layer": analytic["layers"]}), flush=True)
    if args.analytic_only:
        return

    import jax

    import bench  # repo-root bench.py: the exact step the benchmark times
    from moolib_tpu.telemetry import devmon

    device = jax.devices()[0]
    step, params, opt_state, batch = bench.build_step()
    compiled = step.lower(params, opt_state, batch).compile()
    # XLA-counted step cost via the shared devmon path (the FLOPs/bytes
    # arithmetic that used to live here, hand-duplicated).
    sc = devmon.step_cost("roofline.step", step, params, opt_state, batch)
    flops = sc.flops if sc is not None else 0.0
    byts = sc.bytes_accessed if sc is not None else 0.0

    out = {
        "device": device.device_kind,
        "platform": device.platform,
        "channels": analytic["channels"],
        "model_tflops_per_step": round(flops / 1e12, 4),
        "bytes_accessed_per_step_mb": round(byts / 1e6, 1),
        "arithmetic_intensity_flop_per_byte": round(flops / byts, 1) if byts else None,
    }
    out["geometry_mxu_ceiling"] = ceiling
    rf = devmon.roofline(flops, byts, device.device_kind) if flops and byts else None
    if rf is not None and rf.get("roofline_mfu_ceiling") is not None:
        out["ridge_flop_per_byte"] = round(rf["ridge_flop_per_byte"], 1)
        out["min_step_ms_compute"] = round(rf["min_step_s_compute"] * 1e3, 3)
        out["min_step_ms_memory"] = round(rf["min_step_s_memory"] * 1e3, 3)
        bw_ceiling = round(rf["roofline_mfu_ceiling"], 3)
        out["roofline_mfu_ceiling"] = bw_ceiling
        # The binding constraint is whichever ceiling is lower: HBM traffic
        # (classic roofline) or MXU lane occupancy (narrow-channel geometry).
        if ceiling < bw_ceiling:
            out["bound"] = "MXU lane occupancy (channels < 128)"
        elif rf["bound"] == "memory":
            out["bound"] = "memory (HBM bandwidth)"
        else:
            out["bound"] = "compute (MXU)"
        out["mfu_ceiling"] = round(min(ceiling, bw_ceiling), 4)

    if args.trace_dir:
        # AOT `compiled` is used directly so no retrace/recompile lands
        # inside the captured trace window.
        p2, s2 = params, opt_state
        p2, s2, l = compiled(p2, s2, batch)  # warmup outside the trace
        with jax.profiler.trace(args.trace_dir):
            for _ in range(5):
                p2, s2, l = compiled(p2, s2, batch)
            jax.block_until_ready(l)
        out["trace_dir"] = args.trace_dir

    print(json.dumps(out))


if __name__ == "__main__":
    main()
