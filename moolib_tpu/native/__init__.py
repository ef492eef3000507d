"""Native (C++) runtime components with build-on-first-use and fallbacks.

The reference's runtime is C++ throughout (SURVEY.md §2.1); this package
holds the moolib_tpu equivalents:

- ``_moolib_codec``: CPython-extension message codec (tag-based encoding,
  out-of-band zero-copy arrays, pickle fallback, jax host-staging hook) —
  counterpart of ``src/serialization.h`` + ``src/pythonserialization.h``.
- ``libmoolib_shmq``: futex semaphores + SPSC rings in fork-shared memory
  (ctypes) — counterpart of ``src/shm.h``'s SharedSemaphore/SharedQueue.

Sources live in ``<repo>/native/``; they are compiled with g++ on first use
into ``~/.cache/moolib_tpu`` (or $MOOLIB_TPU_CACHE). Every consumer treats
these as accelerators: if a compiler is missing the pure-python paths are
used and everything still works.
"""

from __future__ import annotations

import ctypes
import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig
from typing import Optional

from .. import utils

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "native")


def _cache_dir() -> str:
    d = os.environ.get("MOOLIB_TPU_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "moolib_tpu"
    )
    os.makedirs(d, exist_ok=True)
    return d


def _source_hash(path: str) -> str:
    """Cache tag for a built artifact: source hash + sanitize mode (a
    sanitized build must never be picked up by a normal run or vice versa)."""
    with open(path, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    san = os.environ.get("MOOLIB_TPU_SANITIZE")
    return f"{tag}-{san}" if san else tag


def _build(src: str, out: str, extra_flags=()) -> bool:
    # MOOLIB_TPU_SANITIZE=thread|address builds every native component with
    # the given sanitizer (run python under the matching LD_PRELOAD runtime;
    # see tests/test_native_sanitizers.py for the recipe).
    san = os.environ.get("MOOLIB_TPU_SANITIZE")
    san_flags = (f"-fsanitize={san}",) if san else ()
    cmd = [
        "g++",
        "-O2",
        "-g",
        "-std=c++17",
        "-shared",
        "-fPIC",
        src,
        "-o",
        out,
        *san_flags,
        *extra_flags,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=180)
    except (OSError, subprocess.TimeoutExpired) as e:
        utils.log_error("native build failed to run: %s", e)
        return False
    if proc.returncode != 0:
        utils.log_error("native build failed:\n%s", proc.stderr[-4000:])
        return False
    return True


def _load_codec():
    src = os.path.join(_SRC_DIR, "codec.cc")
    if not os.path.exists(src):
        return None
    tag = _source_hash(src)
    out = os.path.join(_cache_dir(), f"_moolib_codec_{tag}.so")
    if not os.path.exists(out):
        import numpy as np

        py_inc = sysconfig.get_paths()["include"]
        np_inc = np.get_include()
        # Per-process tmp name: concurrent first-use builds must not
        # interleave writes; os.replace makes the install atomic.
        tmp = f"{out}.{os.getpid()}.tmp"
        ok = _build(src, tmp, (f"-I{py_inc}", f"-I{np_inc}"))
        if not ok:
            return None
        os.replace(tmp, out)
    spec = importlib.util.spec_from_file_location("_moolib_codec", out)
    try:
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    except Exception as e:  # noqa: BLE001
        utils.log_error("native codec load failed: %s", e)
        return None
    return mod


def _load_shmq():
    src = os.path.join(_SRC_DIR, "shmq.cc")
    if not os.path.exists(src):
        return None
    tag = _source_hash(src)
    out = os.path.join(_cache_dir(), f"libmoolib_shmq_{tag}.so")
    if not os.path.exists(out):
        tmp = f"{out}.{os.getpid()}.tmp"
        if not _build(src, tmp):
            return None
        os.replace(tmp, out)
    try:
        lib = ctypes.CDLL(out)
    except OSError as e:
        utils.log_error("native shmq load failed: %s", e)
        return None
    lib.moolib_sem_size.restype = ctypes.c_size_t
    lib.moolib_sem_init.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.moolib_sem_post.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.moolib_sem_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.moolib_sem_wait.restype = ctypes.c_int
    lib.moolib_sem_value.argtypes = [ctypes.c_void_p]
    lib.moolib_sem_value.restype = ctypes.c_int32
    lib.moolib_ring_size.argtypes = [ctypes.c_uint32]
    lib.moolib_ring_size.restype = ctypes.c_size_t
    lib.moolib_ring_init.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.moolib_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64]
    lib.moolib_ring_push.restype = ctypes.c_int
    lib.moolib_ring_pop.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64,
    ]
    lib.moolib_ring_pop.restype = ctypes.c_int
    return lib


_codec = None
_codec_tried = False
_shmq = None
_shmq_tried = False


def get_codec():
    """The native codec module, or None (fallback to python serialization)."""
    global _codec, _codec_tried
    if not _codec_tried:
        _codec_tried = True
        if os.environ.get("MOOLIB_TPU_NO_NATIVE") == "1":
            return None
        _codec = _load_codec()
        if _codec is not None:
            _register_jax(_codec)
    return _codec


def _register_jax(codec_mod) -> None:
    import jax
    import numpy as np

    def to_numpy(x):
        return np.asarray(x)

    import jax.numpy as jnp

    def from_numpy(x):
        # device_put can zero-copy alias a host numpy buffer (CPU backend);
        # a view over the transient receive buffer must be copied to an
        # owning array first — jax keeps THAT alive.
        if not x.flags.owndata:
            x = x.copy()
        return jnp.asarray(x)

    codec_mod.register_jax(jax.Array, to_numpy, from_numpy)


def get_shmq():
    """The native shm/futex library, or None (fallback to multiprocessing)."""
    global _shmq, _shmq_tried
    if not _shmq_tried:
        _shmq_tried = True
        if os.environ.get("MOOLIB_TPU_NO_NATIVE") == "1":
            return None
        _shmq = _load_shmq()
    return _shmq


def status() -> dict:
    """Which native components this process runs on (True = built and
    loaded, False = the asyncio / pickle / multiprocessing fallback).
    Builds on first use, like every consumer."""
    from . import transport

    return {
        "transport": transport.get_lib() is not None,
        "codec": get_codec() is not None,
        "shm": get_shmq() is not None,
    }


class NativeSemaphore:
    """Counting semaphore placed in caller-provided shared memory."""

    def __init__(self, lib, addr: int, initialize: bool = True, initial: int = 0):
        self._lib = lib
        self._addr = addr
        if initialize:
            lib.moolib_sem_init(addr, initial)

    @staticmethod
    def size(lib) -> int:
        return lib.moolib_sem_size()

    def release(self, n: int = 1) -> None:
        self._lib.moolib_sem_post(self._addr, n)

    def acquire(self, timeout: Optional[float] = None) -> bool:
        # The C call returns -2 on EINTR so control comes back to python and
        # pending signal handlers (KeyboardInterrupt) run between retries.
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            remaining = -1 if deadline is None else max(0, int((deadline - _time.monotonic()) * 1000))
            rc = self._lib.moolib_sem_wait(self._addr, remaining)
            if rc == 0:
                return True
            if rc == -1:
                return False
            # rc == -2: interrupted; loop (python checks signals here)


class NativeRing:
    """SPSC int32 ring queue in caller-provided shared memory."""

    def __init__(self, lib, addr: int, capacity: int, initialize: bool = True):
        self._lib = lib
        self._addr = addr
        if initialize:
            lib.moolib_ring_init(addr, capacity)

    @staticmethod
    def size(lib, capacity: int) -> int:
        return lib.moolib_ring_size(capacity)

    def push(self, value: int, timeout: Optional[float] = None) -> bool:
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            remaining = -1 if deadline is None else max(0, int((deadline - _time.monotonic()) * 1000))
            rc = self._lib.moolib_ring_push(self._addr, value, remaining)
            if rc == 0:
                return True
            if rc == -1:
                return False
            # EINTR: retry, letting python signal handlers run

    def pop(self, timeout: Optional[float] = None) -> Optional[int]:
        import time as _time

        out = ctypes.c_int32()
        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            remaining = -1 if deadline is None else max(0, int((deadline - _time.monotonic()) * 1000))
            rc = self._lib.moolib_ring_pop(self._addr, ctypes.byref(out), remaining)
            if rc == 0:
                return out.value
            if rc == -1:
                return None
            # EINTR: retry
