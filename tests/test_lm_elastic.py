"""Elastic data-parallel LM training: the Accumulator cohort (leader
election, model sync, virtual batches) driving TransformerLM — the same
wants/has plane the RL agents ride, proving it is model-agnostic.
"""

import json
import os
import subprocess
import sys
import time

from conftest import grab_port, subprocess_env

from moolib_tpu.examples.common import REPORT_PREFIX

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_two_peer_elastic_lm_cohort(tmp_path):
    port = grab_port()
    env = subprocess_env(ROOT)
    common = [
        sys.executable, "-m", "moolib_tpu.examples.lm",
        "--steps", "250",
        "--d_model", "32", "--seq_len", "32", "--batch_size", "8",
        "--layers", "2", "--heads", "2",
        "--attention", "dense", "--mesh", "",
        # Global batch = both peers' contributions: one optimizer step per
        # cohort-wide virtual batch, identical on every peer.
        "--virtual_batch_size", "16",
        "--log_interval", "50",
    ]
    logs = [open(tmp_path / f"p{r}.log", "w") for r in range(2)]
    procs = [
        subprocess.Popen(
            common + (
                ["--address", f"127.0.0.1:{port}", "--local_name", "lm0"]
                if r == 0
                else ["--connect", f"127.0.0.1:{port}", "--local_name", "lm1"]
            ),
            stdout=logs[r], stderr=subprocess.STDOUT, text=True, env=env, cwd=ROOT,
        )
        for r in range(2)
    ]
    try:
        deadline = time.time() + 420
        for p in procs:
            p.wait(timeout=max(1.0, deadline - time.time()))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for f in logs:
            f.close()
    outs = [(tmp_path / f"p{r}.log").read_text() for r in range(2)]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"peer {r} failed:\n{out[-3000:]}"
    # The cohort genuinely formed: step logs report 2 members.
    assert any("cohort=2" in o for o in outs), outs[0][-1000:]
    # Both peers trained: final summary line shows progress over the ~4.13
    # random-chance loss and a nonzero reduction count.
    for r, out in enumerate(outs):
        final = out.strip().splitlines()[-1]
        assert final.startswith(REPORT_PREFIX), (r, final)
        report = json.loads(final[len(REPORT_PREFIX):])
        result = report["result"]
        assert report["device"]["platform"] == "cpu", (r, final)
        assert result["steps"] == 250, (r, final)
        assert result["loss"] < 3.6, (r, final)  # clearly below the 4.13 chance floor
        assert result["reduces"] >= 100, (r, final)
