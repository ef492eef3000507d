"""Persistent compile cache placement (``utils/compile_cache.py``).

Where the cache lives is decided outside the program: where
``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and the module sets
no directory; unset, every process shares one fixed path inside the checkout.
A restarted process — the soak's respawn, the next child of one chip-tool
call — then skips recompilation.  Each case runs in a fresh interpreter:
jax's cache configuration is process-global and decided at the first compile.
"""

import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXED = os.path.join(ROOT, ".jax_cache")


def _run(code: str, **env) -> str:
    base = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        **env,
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=base,
        timeout=240, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


_PLACEMENT = r"""
import jax
updates = []
real = jax.config.update
jax.config.update = lambda name, value: (updates.append(name), real(name, value))
from moolib_tpu.utils import init_compile_cache
print("RETURNED=" + init_compile_cache())
print("CONFIGURED=" + str(jax.config.jax_compilation_cache_dir))
print("DIR_UPDATES=%d" % updates.count("jax_compilation_cache_dir"))
init_compile_cache()  # idempotent
print("DIR_UPDATES_AFTER_SECOND_CALL=%d" % updates.count("jax_compilation_cache_dir"))
"""


def test_env_var_places_the_cache_and_the_module_sets_nothing(tmp_path):
    placed = str(tmp_path / "from_outside")
    out = _run(_PLACEMENT, JAX_COMPILATION_CACHE_DIR=placed)
    assert f"RETURNED={placed}\n" in out
    assert f"CONFIGURED={placed}\n" in out  # jax read the variable itself
    assert "DIR_UPDATES=0\n" in out and "DIR_UPDATES_AFTER_SECOND_CALL=0\n" in out


def test_unset_means_the_fixed_path_inside_the_checkout():
    out = _run(_PLACEMENT)
    assert f"RETURNED={FIXED}\n" in out
    assert f"CONFIGURED={FIXED}\n" in out
    assert "DIR_UPDATES=1\n" in out and "DIR_UPDATES_AFTER_SECOND_CALL=1\n" in out


# A program no other test compiles (the cache is shared with the whole
# suite), taking the time of its compile; persisted whatever it took.
_INCARNATION = r"""
import time
from moolib_tpu.utils import init_compile_cache
print("CACHE_DIR=" + init_compile_cache())
import jax, jax.numpy as jnp
from moolib_tpu.telemetry import devmon
devmon.install_compile_listeners()

def f(x):
    for i in range(80):
        x = jnp.sin(x) @ x + i * %(salt)r
    return x.sum()

x = jnp.ones((64, 64))  # its own small programs may hit or miss: not counted
before = devmon.compile_summary()["cache_hits"]
t0 = time.perf_counter()
jax.jit(f).lower(x).compile()
print("COMPILE_SECONDS=%%.4f" %% (time.perf_counter() - t0))
print("HITS=%%d" %% (devmon.compile_summary()["cache_hits"] - before))
"""


def test_second_incarnation_at_the_fixed_path_hits(tmp_path):
    """Soak-restart shape: two processes, nothing in common but the default
    placement; the second must find what the first compiled."""
    code = _INCARNATION % {"salt": float(os.getpid()) + 0.5}  # new to the cache
    env = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    first, second = _run(code, **env), _run(code, **env)
    for out in (first, second):
        assert f"CACHE_DIR={FIXED}\n" in out
    assert "HITS=0\n" in first
    assert "HITS=1\n" in second
    t1, t2 = (float(re.search(r"COMPILE_SECONDS=([0-9.]+)", o).group(1))
              for o in (first, second))
    if t1 >= 0.3:  # else too fast to compare
        assert t2 < t1 * 0.7, f"no faster from the cache: {t1:.3f}s then {t2:.3f}s"


_SHARDED_STEP = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from moolib_tpu import parallel

# The child never calls init_compile_cache itself: the sharded step path
# does the placing on its own before its first jit.  All inputs are plain
# numpy — jax decides whether the cache is in use at the FIRST compile of
# the process, so even a jnp.zeros() here would lock it off first.
assert jax.config.jax_compilation_cache_dir is None

def loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), {}

mesh = parallel.make_mesh({"dp": 8})
step = parallel.make_train_step(
    loss_fn, mesh=mesh, grad_spec="replicated", batch_spec=P(None, "dp")
)
params = {"w": np.zeros((64, 64), np.float32)}
batch = {"x": np.ones((1, 8, 64), np.float32), "y": np.zeros((1, 8, 64), np.float32)}
loss, aux, grads = step(params, batch, np.uint32(0))
jax.block_until_ready(grads)
print("CONFIGURED=" + str(jax.config.jax_compilation_cache_dir))
"""


def test_sharded_grad_step_places_the_cache_itself():
    """The mesh-sharded grad step (DESIGN.md §6d) places the cache before
    its first jit — a restarted pod-scale learner replays the pjit'd step
    from disk without the caller remembering to."""
    out = _run(_SHARDED_STEP, XLA_FLAGS="--xla_force_host_platform_device_count=8")
    assert f"CONFIGURED={FIXED}\n" in out


class _ProbeEnv:
    """A jax-free env whose observation is what its worker process looks
    like: [a jax backend exists, JAX_PLATFORMS is "cpu", jax's own platform
    setting is "cpu"]."""

    def reset(self):
        import numpy as np

        from moolib_tpu.envpool import _jax_backend_initialized

        jax = sys.modules.get("jax")
        return np.array([
            float(_jax_backend_initialized()),
            float(os.environ.get("JAX_PLATFORMS") == "cpu"),
            float(jax is None or jax.config.jax_platforms == "cpu"),
        ], np.float32)

    def step(self, action):
        return self.reset(), 0.0, False, {}


def test_envpool_worker_stays_off_the_accelerator():
    """An accelerator belongs to the pool's parent.  Importing the package
    loads the jax module in every worker, so what a jax-free env must never
    cause is a jax *backend* — placing the compile cache included — and what
    an env that does use jax must find is the CPU platform, whatever the
    parent's environment says."""
    import numpy as np

    from moolib_tpu import EnvPool

    pool = EnvPool(_ProbeEnv, num_processes=1, batch_size=2, num_batches=1)
    try:
        obs = pool.step(0, np.zeros(2, np.int64)).result()
        backend, env_pinned, config_pinned = obs["state"][0].tolist()
    finally:
        pool.close()
    assert backend == 0.0, "a jax-free env's worker initialised a jax backend"
    assert env_pinned == 1.0 and config_pinned == 1.0
