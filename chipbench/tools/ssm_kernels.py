"""Both selective-scan kernels alone on the chip, for a builder: held to the
token-by-token definition at the published widths, then timed.

    chiprun -- python3 chipbench/tools/ssm_kernels.py

``ssm_decode`` at the cell's 256 slots x the configuration's 26 scan layers
(both files are read, no width is written here) with 256, 85 and 1 slots live (one
layer's call; the state leaf donated, so the update is in place);
``ssm_prefill`` at the buckets 4,096, 2,048 and 256 with the whole bucket real
and with a third of it padding.  One JSON line a reading goes to
``chiprun_out/ssm_kernels.jsonl`` and to standard output.  Times are the
median of ``--repeats`` programs between two points where the host waited for
the device, each a scan over layers (26 decode calls, 8 prefill calls, with the
elementwise input of each made from the last one's output), over the calls; a share of the HBM bound beside each, as the benchmark's two
rooflines count it (``kernel_bytes_ssm.py``)."""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, kernel_bytes_ssm  # noqa: E402

# the widths are the configuration file's and the slots the cell's, as the metrics read them
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "ai21-jamba2-3b.json")
N, CHANNELS = CONFIG["mamba_d_state"], CONFIG["mamba_expand"] * CONFIG["hidden_size"]
SLOTS = harness.load_json(harness.BENCH_DIR, "traffic", "serve_reasoning.json")["slots"]
_PERIOD = CONFIG["attn_layer_period"]  # one layer a period is attention, the rest scans
LAYERS = CONFIG["num_hidden_layers"] // _PERIOD * (_PERIOD - 1)
PREFILL_LAYERS = 8  # calls of the prefill kernel under one scan: a host round trip is 0.8 ms


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--repeats", type=int, default=20)
    args = p.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops import selective_scan as ssm

    peak = harness.load_json(harness.BENCH_DIR, "hbm_peaks.json")["device_kinds"][
        jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    out_path = os.path.join(ROOT, "chiprun_out", "ssm_kernels.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    out = open(out_path, "a")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    ks = jax.random.split(jax.random.key(7), 10)
    A = -jnp.exp(jax.random.uniform(ks[0], (CHANNELS, N), minval=0.0, maxval=jnp.log(16.0)))
    D = jax.random.normal(ks[1], (CHANNELS,))

    # ---- decode: against the jax.numpy step, then timed a layer's call
    u, dt = jax.random.normal(ks[2], (SLOTS, CHANNELS)), jax.nn.softplus(
        jax.random.normal(ks[3], (SLOTS, CHANNELS)) - 3.0)
    B, C = jax.random.normal(ks[4], (SLOTS, N)), jax.random.normal(ks[5], (SLOTS, N))
    small = jax.random.normal(ks[6], (SLOTS, 2, N, CHANNELS))
    active = jnp.arange(SLOTS) % 3 != 1
    want_y, want_s = ssm.ssm_step(u, dt, A.T, B, C, small, 1, active)
    got_y, got_s = ssm.ssm_decode(u, dt, A.T, B, C, small, 1, active)
    say(kernel="ssm_decode", check="against ssm_step, 171 of 256 slots live",
        y_max_abs_err=float(jnp.abs(got_y - want_y).max()),
        state_max_abs_err=float(jnp.abs(got_s - want_s).max()),
        idle_bit_for_bit=bool((got_s[~active] == small[~active]).all()))
    def all_layers(u, dt, B, C, state, active):  # a call a layer under a scan, as the model runs it
        def layer(carry, l):
            y, state = carry
            y, state = ssm.ssm_decode(u + 1e-3 * y, dt, A.T, B, C, state, l, active)
            return (y, state), None
        return jax.lax.scan(layer, (jnp.zeros_like(u), state), jnp.arange(LAYERS))[0]

    step = jax.jit(all_layers, donate_argnums=(4,))
    state = jnp.zeros((SLOTS, LAYERS, N, CHANNELS), jnp.float32)
    for live in (256, 85, 1):
        mask = jnp.arange(SLOTS) * live // SLOTS != (jnp.arange(SLOTS) - 1) * live // SLOTS
        mask = mask if live < SLOTS else jnp.ones((SLOTS,), bool)
        times = []
        for _ in range(args.repeats + 1):
            t0 = time.perf_counter()
            y, state = step(u, dt, B, C, state, mask)
            jax.block_until_ready(y)
            times.append(time.perf_counter() - t0)
        seconds = statistics.median(times[1:]) / LAYERS
        n = int(mask.sum())
        say(kernel="ssm_decode", live=n, ms=seconds * 1e3,
            hbm_bound_share=100 * kernel_bytes_ssm.ssm_decode(CONFIG, {}, n) / peak / seconds,
            note="a layer's call of 26 under one scan, with the XLA ops around it (argsort, stack, where)")

    # ---- prefill: against the definition at a short length, then timed
    def inputs(T, seed):
        k = jax.random.split(jax.random.key(seed), 5)
        return (jax.random.normal(k[0], (T, CHANNELS)),
                jax.nn.softplus(2.0 * jax.random.normal(k[1], (T, CHANNELS)) - 3.0),
                jax.random.normal(k[2], (T, CHANNELS)), jax.random.normal(k[3], (T, N)),
                jax.random.normal(k[4], (T, N)))

    u, dt, z, B, C = inputs(512, 11)
    want_y, want_s = ssm.selective_scan_reference(
        u[:400], dt[:400], A, B[:400], C[:400], D, jnp.zeros((CHANNELS, N)))
    want_y = want_y * jax.nn.silu(z[:400])
    fn = jax.jit(lambda u, dt, z, B, C, length: ssm.ssm_prefill(
        u, dt, z, A.T, B, C, D, length=length))

    @jax.jit
    def layers(u, dt, z, B, C, length):  # a call a layer under a scan, as the model runs it
        def layer(y, _):
            return ssm.ssm_prefill(u + 1e-3 * y, dt, z, A.T, B, C, D, length=length)[0], None
        return jax.lax.scan(layer, jnp.zeros_like(u), None, length=PREFILL_LAYERS)[0]

    got_y, got_s = fn(u, dt, z, B, C, jnp.int32(400))
    say(kernel="ssm_prefill", check="against the definition, 400 of 512 real",
        y_max_abs_err=float(jnp.abs(got_y[:400] - want_y).max()),
        state_max_abs_err=float(jnp.abs(got_s.T - want_s).max()),
        y_scale=float(jnp.abs(want_y).max()))
    for T in (4096, 2048, 256):
        x = inputs(T, T)
        for real in (T, T * 2 // 3):
            seconds = timed(layers, *x, jnp.int32(real)) / PREFILL_LAYERS
            say(kernel="ssm_prefill", bucket=T, real=real, ms=seconds * 1e3,
                hbm_bound_share=100 * kernel_bytes_ssm.ssm_prefill(CONFIG, real, CHANNELS)
                / peak / seconds,  # the real positions' bytes, as ssm_prefill_roofline counts
                exponentials=real * CHANNELS * N)
    return 0


if __name__ == "__main__":
    sys.exit(main())
