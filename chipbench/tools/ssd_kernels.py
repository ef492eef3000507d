"""Both Mamba-2 kernels alone on the chip, for a builder: held to the
token-by-token definition at the published widths, then timed.

    chiprun -- python3 chipbench/tools/ssd_kernels.py

``ssd_decode`` at the cell's 128 slots x the configuration's 9 Mamba-2 layers
(both files are read, no width is written here) with 128, 43 and 1 slots live
(one layer's call; the state leaf donated, so the update is in place);
``ssd_prefill`` at the buckets 2,048, 1,024 and 128 with the whole bucket real
and with a third of it padding.  One JSON line a reading goes to
``chiprun_out/ssd_kernels.jsonl`` and to standard output.  Times are the
median of ``--repeats`` programs between two points where the host waited for
the device, each a scan over layers (9 decode calls, 8 prefill calls, with the
elementwise input of each made from the last one's output), over the calls;
beside each the share the benchmark's roofline of that kernel counts
(``kernel_bytes_ssd.py`` over the HBM bound, ``flops_ssd.py`` over the MXU's
peak)."""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops_ssd, harness, kernel_bytes_ssd  # noqa: E402

# the widths are the configuration file's and the slots the cell's, as the metrics read them
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "granite-4.0-h-small.json")
H, P, N = CONFIG["mamba_n_heads"], CONFIG["mamba_d_head"], CONFIG["mamba_d_state"]
SLOTS = harness.load_json(harness.BENCH_DIR, "traffic", "serve_sessions.json")["slots"]
LAYERS = CONFIG["layer_types"][:CONFIG["num_hidden_layers"]].count("mamba")
PREFILL_LAYERS = 8  # calls of the prefill kernel under one scan: a host round trip is 0.8 ms


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--repeats", type=int, default=20)
    args = p.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops import ssd

    kind = jax.devices()[0].device_kind
    hbm = harness.load_json(harness.BENCH_DIR, "hbm_peaks.json")["device_kinds"][kind]["hbm_bytes_per_s"]
    mxu = harness.load_json(harness.BENCH_DIR, "peaks.json")["device_kinds"][kind]["bf16_flops_per_s"]
    out_path = os.path.join(ROOT, "chiprun_out", "ssd_kernels.jsonl")
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
    A = -jax.random.uniform(ks[0], (H,), minval=1.0, maxval=16.0)
    D = jax.random.normal(ks[1], (H,))

    # ---- decode: against the jax.numpy step, then timed a layer's call
    x = jax.random.normal(ks[2], (SLOTS, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (SLOTS, H)) - 3.0)
    B, C = jax.random.normal(ks[4], (SLOTS, N)), jax.random.normal(ks[5], (SLOTS, N))
    small = jax.random.normal(ks[6], (SLOTS, 2, H, P, N))
    active = jnp.arange(SLOTS) % 3 != 1
    want_y, want_s = jax.jit(ssd.ssd_step)(x, dt, A, B, C, small, 1, active)
    got_y, got_s = ssd.ssd_decode(x, dt, A, B, C, small, 1, active)
    say(kernel="ssd_decode", check=f"against ssd_step, {int(active.sum())} of {SLOTS} slots live",
        y_max_abs_err=float(jnp.abs(got_y - want_y).max()),
        state_max_abs_err=float(jnp.abs(got_s - want_s).max()),
        idle_bit_for_bit=bool((got_s[~active] == small[~active]).all()),
        other_layer_bit_for_bit=bool((got_s[:, 0] == small[:, 0]).all()))
    del small, want_s, got_s

    def all_layers(x, dt, B, C, state, active):  # a call a layer under a scan, as the model runs it
        def layer(carry, l):
            y, state = carry
            y, state = ssd.ssd_decode(x + 1e-3 * y, dt, A, B, C, state, l, active)
            return (y, state), None
        return jax.lax.scan(layer, (jnp.zeros_like(x), state), jnp.arange(LAYERS))[0]

    step = jax.jit(all_layers, donate_argnums=(4,))
    state = jnp.zeros((SLOTS, LAYERS, H, P, N), jnp.float32)
    for live in (SLOTS, SLOTS // 3, 1):
        mask = jnp.arange(SLOTS) * live // SLOTS != (jnp.arange(SLOTS) - 1) * live // SLOTS
        mask = mask if live < SLOTS else jnp.ones((SLOTS,), bool)
        times = []
        for _ in range(args.repeats + 1):
            t0 = time.perf_counter()
            y, state = step(x, dt, B, C, state, mask)
            jax.block_until_ready(y)
            times.append(time.perf_counter() - t0)
        seconds = statistics.median(times[1:]) / LAYERS
        n = int(mask.sum())
        say(kernel="ssd_decode", live=n, ms=seconds * 1e3,
            hbm_bound_share=100 * kernel_bytes_ssd.ssd_decode(CONFIG, {}, n) / hbm / seconds,
            note=f"a layer's call of {LAYERS} under one scan, with the XLA ops around it "
                 "(argsort, exp, transposes, where)")
    del state

    # ---- prefill: against the definition at a short length, then timed
    def inputs(T, seed):
        k = jax.random.split(jax.random.key(seed), 4)
        return (jax.random.normal(k[0], (T, H, P)),
                jax.nn.softplus(2.0 * jax.random.normal(k[1], (T, H)) - 3.0),
                jax.random.normal(k[2], (T, N)), jax.random.normal(k[3], (T, N)))

    x, dt, B, C = inputs(512, 11)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = jax.jit(ssd.ssd_reference)(
            x[:400], dt[:400], A, B[:400], C[:400], jnp.zeros_like(D), jnp.zeros((H, P, N)))
    fn = jax.jit(lambda x, dt, B, C, length: ssd.ssd_prefill(x, dt, A, B, C, length=length))

    @jax.jit
    def layers(x, dt, B, C, length):  # a call a layer under a scan, as the model runs it
        def layer(y, _):
            return ssd.ssd_prefill(x + 1e-3 * y, dt, A, B, C, length=length)[0], None
        return jax.lax.scan(layer, jnp.zeros_like(x), None, length=PREFILL_LAYERS)[0]

    got_y, got_s = fn(x, dt, B, C, jnp.int32(400))
    say(kernel="ssd_prefill", check="against the definition, 400 of 512 real",
        y_max_abs_err=float(jnp.abs(got_y[:400] - want_y).max()),
        state_max_abs_err=float(jnp.abs(got_s - want_s).max()),
        y_scale=float(jnp.abs(want_y).max()), state_scale=float(jnp.abs(want_s).max()),
        padding_rows_zero=bool((got_y[512 - 512 % ssd.CHUNK or 512:] == 0).all()))
    for T in (2048, 1024, 128):
        xs = inputs(T, T)
        for real in (T, T * 2 // 3):
            seconds = timed(layers, *xs, jnp.int32(real)) / PREFILL_LAYERS
            say(kernel="ssd_prefill", bucket=T, real=real, ms=seconds * 1e3,
                mxu_peak_share=100 * flops_ssd.ssd_prefill(CONFIG, real) / mxu / seconds,
                exponentials=real * H * ssd.CHUNK)
    return 0


if __name__ == "__main__":
    sys.exit(main())
