"""TSAN/ASAN passes over the native C++ (VERDICT round-3 ask #7).

The reference ships 0 sanitizer coverage (SURVEY §5.2); the inline-send
redesign makes the transport genuinely multi-threaded, so these runs are the
regression gate for its locking:

1. ``stress_transport.cc`` under ``-fsanitize=thread`` — sender threads
   racing the epoll thread's flushes, close/destroy races, memfd frames.
2. The same under ``-fsanitize=address,undefined``.
3. A ctypes-boundary stress: the real ``NativeNet`` binding driving an
   ASAN-built engine inside a subprocess running under the libasan preload
   (``MOOLIB_TPU_SANITIZE=address`` builds the lib).

Each is skipped (not failed) when the toolchain lacks the sanitizer runtime.
"""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "native", "stress_transport.cc")


def _build_and_run(tmp_path, sanitize: str):
    binary = str(tmp_path / f"stress_{sanitize.replace(',', '_')}")
    build = subprocess.run(
        ["g++", "-O1", "-g", "-std=c++17", "-pthread", f"-fsanitize={sanitize}",
         SRC, "-o", binary],
        capture_output=True, text=True, timeout=300,
    )
    if build.returncode != 0:
        pytest.skip(f"-fsanitize={sanitize} unavailable: {build.stderr[-300:]}")
    run = subprocess.run([binary], capture_output=True, text=True, timeout=240)
    assert run.returncode == 0, (run.stdout + run.stderr)[-4000:]
    assert "passed" in run.stdout


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_stress_tsan(tmp_path):
    _build_and_run(tmp_path, "thread")


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_stress_asan(tmp_path):
    _build_and_run(tmp_path, "address,undefined")


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_ctypes_boundary_asan(tmp_path):
    """Drive the real ctypes binding against an ASAN-built engine: threads
    sending small/iov/pinned frames while connections close under them, then
    engine destroy with traffic in flight — the exact Python<->C lifetime
    contracts (pin/release, zero-copy views, callback marshaling)."""
    probe = subprocess.run(
        ["g++", "-print-file-name=libasan.so"], capture_output=True, text=True
    )
    libasan = probe.stdout.strip()
    if not libasan or not os.path.exists(libasan):
        pytest.skip("libasan.so not found")
    script = tmp_path / "ctypes_stress.py"
    script.write_text(
        """
import os, threading, time
from moolib_tpu.native.transport import NativeNet

frames = []
lock = threading.Lock()
def mk(tag):
    conns = []
    def on_accept(cid, t): conns.append(cid)
    def on_frame(cid, frame):
        with lock: frames.append(len(frame))
    def on_close(cid): pass
    def on_connect(rid, cid):
        if cid >= 0:  # -1 = failed connect; counting it would blind the test
            conns.append(cid)
    return NativeNet(on_accept, on_frame, on_close, on_connect), conns

snet, sconns = mk("s")
cnet, cconns = mk("c")
port = snet.listen_tcp("127.0.0.1", 0)
for i in range(3):
    cnet.connect_tcp(i, "127.0.0.1", port)
deadline = time.time() + 10
while len(cconns) < 3 and time.time() < deadline: time.sleep(0.01)
assert len(cconns) == 3, cconns

import numpy as np
big = np.random.default_rng(0).integers(0, 255, 200_000, np.uint8)
def hammer(seed):
    rng = np.random.default_rng(seed)
    for i in range(150):
        conn = cconns[int(rng.integers(len(cconns)))]
        k = int(rng.integers(3))
        if k == 0:
            cnet.send(conn, b"x" * 48)
        elif k == 1:
            cnet.send_iov(conn, [b"h" * 8, b"y" * 40])
        else:
            cnet.send_iov(conn, [b"h" * 8, memoryview(big)])
threads = [threading.Thread(target=hammer, args=(t,)) for t in range(4)]
for t in threads: t.start()
time.sleep(0.05)
cnet.close_conn(cconns[0])  # close under the senders
for t in threads: t.join()
deadline = time.time() + 10
while time.time() < deadline:
    with lock:
        n = len(frames)
    if n >= 300:  # most of the 600 sends (one conn closed mid-run drops some)
        break
    time.sleep(0.02)
snet.destroy()
cnet.destroy()
assert n >= 300, f"only {n} frames delivered"
print("ctypes stress ok", n)
"""
    )
    env = dict(
        os.environ,
        MOOLIB_TPU_SANITIZE="address",
        LD_PRELOAD=libasan,
        ASAN_OPTIONS="detect_leaks=0,abort_on_error=1",
        PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )
    run = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    if run.returncode != 0 and "cannot be preloaded" in run.stderr:
        pytest.skip("libasan preload rejected on this box")
    assert run.returncode == 0, (run.stdout + run.stderr)[-4000:]
    assert "ctypes stress ok" in run.stdout
