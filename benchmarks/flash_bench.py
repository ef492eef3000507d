"""Pallas flash attention vs XLA dense attention on the chip.

Times both paths, forward and forward+backward, across T in {512..8192} and
prints one line per size.  One process; fails unless jax's platform is
``tpu`` (interpret-mode timings are meaningless).  Timing is a host clock
around ``block_until_ready`` after a compile-and-warm call.

    python benchmarks/flash_bench.py
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ms(fn, *args, iters: int):
    """Milliseconds per call of ``fn(*args)``, or None where it runs out of
    device memory: dense attention legitimately does at long T (the problem
    flash attention solves).  XLA raises backend-specific OOM types, hence
    string matching; anything else — a compile error — must fail."""
    import jax

    try:
        jax.block_until_ready(fn(*args))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
    except Exception as e:  # noqa: BLE001
        msg = str(e)
        if "RESOURCE_EXHAUSTED" not in msg and "out of memory" not in msg.lower():
            raise
        return None
    return (time.perf_counter() - t0) / iters * 1e3


def _fmt(ms, width=9):
    return f"{'OOM':>{width}}" if ms is None else f"{ms:>{width}.3f}"


def main():
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops.flash_attention import flash_attention
    from moolib_tpu.parallel.ring_attention import full_attention
    from moolib_tpu.utils import init_compile_cache

    init_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"flash_bench measures the chip: platform is {dev.platform!r}")
    B, H, D = 4, 8, 64
    print(f"# platform={dev.platform} device={dev.device_kind} count={len(jax.devices())}")

    def inputs(T):
        rng = np.random.default_rng(T)
        return tuple(
            jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32)).astype(jnp.bfloat16)
            for _ in range(3)
        )

    dense = lambda q, k, v: full_attention(q, k, v, causal=True)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)

    print(f"{'T':>6} {'dense_ms':>9} {'flash_ms':>9} {'speedup':>8}")
    for T in (512, 1024, 2048, 4096, 8192):
        qkv = inputs(T)
        iters = 40 if T <= 2048 else 16
        d_ms = _ms(jax.jit(dense), *qkv, iters=iters)
        f_ms = _ms(jax.jit(flash), *qkv, iters=iters)
        ratio = "inf" if d_ms is None else f"{d_ms / f_ms:.2f}x"
        print(f"{T:>6} {_fmt(d_ms)} {_fmt(f_ms)} {ratio:>8}")

    # Training path: forward + backward.  flash rides the pallas dq and dk/dv
    # kernels (default); "oracle" is the blockwise-jax VJP it replaced
    # (MOOLIB_TPU_FLASH_BWD=jax), AOT-compiled while the env var is set so
    # the comparison is kernel vs pure-XLA recompute at identical math.
    def grad_of(attn):
        return jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2),
            )
        )

    print("# fwd+bwd (sum-of-output gradient wrt q,k,v)")
    print(f"{'T':>6} {'dense_ms':>9} {'flash_ms':>9} {'oracle_ms':>10}")
    for T in (512, 1024, 2048, 4096, 8192):
        qkv = inputs(T)
        os.environ["MOOLIB_TPU_FLASH_BWD"] = "jax"
        try:
            goracle = grad_of(flash).lower(*qkv).compile()
        finally:
            os.environ.pop("MOOLIB_TPU_FLASH_BWD", None)
        iters = 40 if T <= 2048 else 8
        d_ms = _ms(grad_of(dense), *qkv, iters=iters)
        f_ms = _ms(grad_of(flash), *qkv, iters=iters)
        o_ms = _ms(goracle, *qkv, iters=iters)
        print(f"{T:>6} {_fmt(d_ms)} {_fmt(f_ms)} {_fmt(o_ms, 10)}")


if __name__ == "__main__":
    main()
