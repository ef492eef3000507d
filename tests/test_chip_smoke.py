"""Rehearsal of ``chip_smoke.py``'s control flow, on the CPU at tiny sizes.

What the chip run proves — the three main paths at full width on a TPU — no
CPU run can.  What this file keeps true between chip runs is the script
itself: the phase runner starts every child through the real command lines,
reads their reports, holds them to the platform it was told to expect (here
``cpu``, supplied by the test, where ``main`` says ``tpu``), never imports
jax itself, fails closed, and leaves no process behind.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402  (stdlib-only: importing it is not importing jax)

_W = dict(vocab=256, d_model=64, layers=2, heads=2, learning_rate=3e-3)
TINY = dict(
    # The CPU runs the kernels in interpret mode (no custom call to count)
    # and reports one host memory figure.
    min_mosaic_kernels=0, devices_reporting_memory=1,
    agent=dict(unroll_length=5, batch_size=4, actor_batches=2, env_processes=1,
               total_steps=200, min_sgd_steps=5),
    lm=dict(_W, seq_len=256, batch_size=2, steps=8),
    serve=dict(_W, seq_len=8, max_new_tokens=4, slots=2,
               requests=[(3, 2), (8, 4), (5, 1)], latent_gap_sigma=0.5),
    mesh=dict(_W, seq_len=256, batch_size=4, steps=3, ring_seq_len=512, ring_steps=2),
)

# Run in a fresh interpreter: this process has jax loaded (conftest, other
# tests), and the claim under test is that the smoke's parent never does.
_DRIVER = r"""
import json, sys
sys.path.insert(0, %(root)r)
import chip_smoke
phases = [getattr(chip_smoke, name) for name in %(phases)r]
rc = chip_smoke.smoke(phases, %(sizes)r, expect_platform="cpu",
                      expect_count=%(count)d, log_dir=%(log_dir)r)
print("PARENT_IMPORTED_JAX=%%s" %% ("jax" in sys.modules))
sys.exit(rc)
"""


def _rehearse(phases, count, log_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={count}")
    code = _DRIVER % dict(root=ROOT, phases=phases, sizes=TINY, count=count,
                          log_dir=str(log_dir))
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)


def _verdict(stdout: str):
    """The last line as the driver reads it, or None where it is no verdict."""
    lines = [l for l in stdout.splitlines() if not l.startswith("PARENT_IMPORTED_JAX")]
    try:
        return json.loads(lines[-1])
    except (IndexError, ValueError):
        return None


# The flash-vs-dense phase is left out: the on-chip test it runs skips where
# jax has no accelerator, and the smoke (rightly) does not take a skip for a
# pass — test_a_phase_that_skips_is_a_failure below.
# The four-chip flow (three more LM children on 4 virtual devices, about a
# minute) is for whoever is about to spend a four-chip call: -m slow.
@pytest.mark.parametrize("phases,count", [
    pytest.param(["phase_device", "phase_agent", "phase_lm", "phase_serve"], 1,
                 id="one_chip"),
    pytest.param(["phase_device", "phase_mesh_dp", "phase_mesh_ring"], 4,
                 id="four_chips", marks=pytest.mark.slow),
])
def test_phases_run_through_the_real_entry_points(phases, count, tmp_path):
    out = _rehearse(phases, count, tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PARENT_IMPORTED_JAX=False" in out.stdout
    assert _verdict(out.stdout) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": count}
    }
    facts = [json.loads(l[len("PHASE "):]) for l in out.stdout.splitlines()
             if l.startswith("PHASE ")]
    assert [f["phase"] for f in facts] == [p[len("phase_"):] for p in phases]
    for f in facts[1:]:
        # every child's bill is on its line
        assert {"wall_s", "compile_s", "cache_hits", "cache_misses",
                "native_built"} <= set(f)


def test_a_failing_child_fails_the_run_and_prints_no_verdict(capsys, tmp_path):
    def phase_boom(s):
        s.run("boom", [sys.executable, "-c", "print('partial'); raise SystemExit(3)"],
              timeout=60)
        return {"phase": "boom"}

    def phase_never(s):
        raise AssertionError("a phase ran after a failed one")

    rc = chip_smoke.smoke([phase_boom, phase_never], TINY, expect_platform="cpu",
                          log_dir=str(tmp_path))
    captured = capsys.readouterr()
    assert rc == 1
    assert '"ok"' not in captured.out
    assert "boom: exit code 3" in captured.err and "partial" in captured.err


def test_a_child_on_the_wrong_platform_fails_the_run(capsys, tmp_path):
    """The script as the driver runs it expects ``tpu``: here, where jax has
    only the CPU, its first phase must end the run."""
    rc = chip_smoke.smoke(chip_smoke.ONE_CHIP, chip_smoke.FULL, log_dir=str(tmp_path))
    captured = capsys.readouterr()
    assert rc == 1
    assert '"ok"' not in captured.out
    assert "computed on platform 'cpu', expected 'tpu'" in captured.err


def test_a_phase_that_skips_is_a_failure(capsys, tmp_path):
    rc = chip_smoke.smoke([chip_smoke.phase_flash_check], TINY, expect_platform="cpu",
                          log_dir=str(tmp_path))
    captured = capsys.readouterr()
    assert rc == 1 and '"ok"' not in captured.out
    assert "1 skipped" in captured.err


def test_a_hung_child_is_killed_with_its_process_group(capsys, tmp_path):
    pid_file = str(tmp_path / "grandchild.pid")
    hang = (
        "import subprocess, sys, time\n"
        "p = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(600)'])\n"
        f"open({pid_file!r}, 'w').write(str(p.pid))\n"
        "time.sleep(600)\n"
    )

    def phase_hang(s):
        s.run("hang", [sys.executable, "-c", hang], timeout=3)
        return {"phase": "hang"}

    rc = chip_smoke.smoke([phase_hang], TINY, expect_platform="cpu",
                          log_dir=str(tmp_path))
    assert rc == 1 and "still running after 3 s" in capsys.readouterr().err
    grandchild = int(open(pid_file).read())
    try:  # killed with the group it was born in: gone, or a zombie nobody reaps
        with open(f"/proc/{grandchild}/stat") as f:
            state = f.read().rsplit(")", 1)[1].split()[0]
    except FileNotFoundError:
        state = "gone"
    assert state in ("gone", "Z")
