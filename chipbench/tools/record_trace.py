"""Record one small device trace for chipbench/tests (run it on the chip).

    python3 chipbench/tools/record_trace.py chiprun_out/trace_sample

Three calls of a small jitted step (matmul, convert, a while loop) under the
benchmark's own host spans, written as the profiler's ``.xplane.pb``.  The
file committed as ``chipbench/tests/data/sample.xplane.pb.gz`` was made by
this script on a TPU v5 lite; it is the only trace the repository keeps.
"""

import glob
import gzip
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1

    @jax.jit
    def step(x, w):
        def body(i, x):
            return jnp.tanh(x @ w).astype(jnp.bfloat16)

        y = jax.lax.fori_loop(0, 4, body, x)
        return y.astype(jnp.float32).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.001
    float(step(x, w))
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("chipbench.sample_step", step=i):
            float(step(x, w))
        with jax.profiler.TraceAnnotation("chipbench.sample_sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out_dir, "plugins/profile/*/*.xplane.pb")))[-1]
    with open(path, "rb") as f, gzip.open(os.path.join(out_dir, "sample.xplane.pb.gz"), "wb") as g:
        shutil.copyfileobj(f, g)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:6]:
                stats = {k: (str(v)[:120]) for k, v in ev.stats}
                print("     EV", ev.name[:100], ev.start_ns, ev.duration_ns, stats)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
