"""Sweep the flash backward kernels' block sizes on the chip.

The backward caps (MOOLIB_TPU_FLASH_BWD_BLOCK_Q/K, default 512x512) were
sized by VMEM arithmetic and have never been swept.  The env vars are read at
TRACE time, so each config runs in a fresh child process (this script
re-execs itself with --child).  A chip belongs to one process at a time:
the parent never imports jax, the children run one after the other, and each
child names the device it measured on and refuses any platform but ``tpu``.

Timing is a host clock around ``block_until_ready`` after a compile-and-warm
call.  Prints one ms row per config and a final JSON line
{"flash_bwd_tune": {...}}.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

CONFIGS = [(256, 256), (512, 256), (256, 512), (512, 512),
           (512, 1024), (1024, 512)]
T = int(os.environ.get("MOOLIB_FLASH_TUNE_T", 4096))


def child():
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from moolib_tpu.ops.flash_attention import flash_attention
    from moolib_tpu.utils import init_compile_cache

    init_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"flash_bwd_tune measures the chip: platform is {dev.platform!r}"
        )
    B, H, D = 4, 8, 64
    rng = np.random.default_rng(T)
    mk = lambda: jnp.asarray(
        rng.normal(size=(B, T, H, D)).astype(np.float32)
    ).astype(jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    g = jax.jit(
        jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True).astype(jnp.float32)
            ),
            argnums=(0, 1, 2),
        )
    )
    jax.block_until_ready(g(q, k, v))  # compile + warm
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        out = g(q, k, v)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / iters * 1e3
    print(json.dumps({"ms": ms, "platform": dev.platform,
                      "device_kind": dev.device_kind}))


def main():
    print(f"# T={T} fwd+bwd flash-only")
    print(f"{'bq':>6} {'bk':>6} {'ms':>9}")
    rows, device = [], None
    for bq, bk in CONFIGS:
        env = dict(os.environ,
                   MOOLIB_TPU_FLASH_BWD_BLOCK_Q=str(bq),
                   MOOLIB_TPU_FLASH_BWD_BLOCK_K=str(bk))
        # A config can legitimately blow VMEM (Mosaic reject): record it
        # rather than abort the sweep, so already-measured configs always
        # reach the final JSON line.
        try:
            r = subprocess.run(
                [sys.executable, "-u", os.path.abspath(__file__), "--child"],
                env=env, capture_output=True, text=True, timeout=300,
            )
            rc, out_txt, err_txt = r.returncode, r.stdout, r.stderr
        except subprocess.TimeoutExpired:
            rc, out_txt, err_txt = -1, "", "child timed out after 300s"
        result = None
        for line in reversed(out_txt.splitlines()):
            if line.startswith("{"):
                result = json.loads(line)
                break
        if rc != 0 or result is None:
            tail = (err_txt or out_txt).strip().splitlines()[-1:] or ["?"]
            print(f"{bq:>6} {bk:>6} {'error':>9}  # {tail[0][:100]}")
            rows.append({"block_q": bq, "block_k": bk, "error": tail[0][:200]})
            continue
        device = {"platform": result["platform"],
                  "device_kind": result["device_kind"]}
        print(f"{bq:>6} {bk:>6} {result['ms']:>9.3f}")
        rows.append({"block_q": bq, "block_k": bk, "ms": round(result["ms"], 3)})
    ok = [r for r in rows if "ms" in r]
    best = min(ok, key=lambda r: r["ms"]) if ok else None
    print(json.dumps({"flash_bwd_tune": {
        **(device or {}), "T": T,
        "geometry": {"B": 4, "H": 8, "D": 64}, "rows": rows, "best": best,
    }}))
    if not ok:
        raise SystemExit(4)  # nothing was measured


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        main()
