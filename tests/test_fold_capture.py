"""fold_capture.py folds benchmark logs into a record file — a wrong fold
silently corrupts the record, so its guards are pinned here.

Runs the real CLI via subprocess, one tmp capture dir per test.  (The
directory mode these tests drive goes with ROADMAP D5; ``--local`` is the
mode ``scripts/ci.sh`` uses.)
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "benchmarks", "fold_capture.py")


def run_fold(cap, out):
    return subprocess.run(
        [sys.executable, SCRIPT, str(cap), str(out)],
        capture_output=True, text=True, timeout=60,
    )


def impala_line(metric="impala_learner_sps", platform="tpu", **kw):
    row = {"metric": metric, "value": 12345.6, "unit": "env_frames/s",
           "vs_baseline": 0.8, "platform": platform, "device_kind": "TPU v5 lite",
           "step_ms": 7.5, **kw}
    return "MOOLIB_BENCH_RESULT " + json.dumps(row)


def lm_line(rows, platform="tpu"):
    return json.dumps({"lm_train": {
        "platform": platform, "device_kind": "TPU v5 lite",
        "d_model": 1024, "layers": 12, "kv_heads": 8, "rows": rows}})


def test_headline_rejects_smoke_and_cpu_rows(tmp_path):
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    (cap / "impala_bench.log").write_text(
        impala_line(metric="impala_learner_sps_smoke", T=2, B=2) + "\n")
    r = run_fold(cap, out)
    assert "nothing to fold" in r.stdout
    (cap / "impala_bench.log").write_text(impala_line(platform="cpu") + "\n")
    r = run_fold(cap, out)
    assert "nothing to fold" in r.stdout
    (cap / "impala_bench.log").write_text(impala_line() + "\n")
    r = run_fold(cap, out)
    assert "impala_learner" in r.stdout
    assert json.loads(out.read_text())["impala_learner"]["value"] == 12345.6


def test_wide_section_requires_wide_metric(tmp_path):
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    # A narrow row in impala_wide.log must NOT pose as the wide datapoint.
    (cap / "impala_wide.log").write_text(impala_line() + "\n")
    r = run_fold(cap, out)
    assert "nothing to fold" in r.stdout
    (cap / "impala_wide.log").write_text(
        impala_line(metric="impala_learner_sps_wide", channels=[64, 128, 128]) + "\n")
    run_fold(cap, out)
    data = json.loads(out.read_text())
    assert data["impala_wide"]["channels"] == [64, 128, 128]
    assert "impala_learner" not in data  # wide never touches the headline


def test_lm_rows_merge_across_split_logs(tmp_path):
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    (cap / "lm_quick.log").write_text(lm_line([
        {"T": 1024, "B": 16, "remat": False, "tokens_per_s": 100.0},
        {"T": 2048, "B": 8, "remat": False, "tokens_per_s": 90.0}]) + "\n")
    (cap / "lm_full.log").write_text(lm_line([
        {"T": 2048, "B": 8, "remat": False, "tokens_per_s": 95.0},  # overrides quick
        {"T": 8192, "B": 2, "remat": False, "tokens_per_s": 40.0}]) + "\n")
    run_fold(cap, out)
    rows = json.loads(out.read_text())["lm_train"]["rows"]
    by_key = {(r["T"], r["B"]): r["tokens_per_s"] for r in rows}
    assert by_key == {(1024, 16): 100.0, (2048, 8): 95.0, (8192, 2): 40.0}
    assert [r["T"] for r in rows] == [1024, 2048, 8192]  # sorted by config


def test_lm_refold_keeps_baseline_rows_absent_from_logs(tmp_path):
    # A re-armed step's re-run shelves its old log (.log.prev, never read):
    # rows that only exist in the already-folded BENCH_TPU.json — the naive
    # baseline at configs lm_quick re-measures fused — must survive the
    # rebuild, keyed apart by xent mode.
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    out.write_text(json.dumps({"lm_train": {
        "platform": "tpu", "device_kind": "TPU v5 lite", "rows": [
            {"T": 1024, "B": 16, "remat": False, "tokens_per_s": 100.0},
            {"T": 8192, "B": 2, "remat": False, "tokens_per_s": 40.0}]}}))
    (cap / "lm_quick.log").write_text(lm_line([
        {"T": 1024, "B": 16, "remat": False, "xent": "fused",
         "tokens_per_s": 130.0}]) + "\n")
    run_fold(cap, out)
    rows = json.loads(out.read_text())["lm_train"]["rows"]
    by_key = {(r["T"], r["xent"]): r["tokens_per_s"] for r in rows}
    assert by_key == {
        (1024, "naive"): 100.0,   # baseline survived the refold
        (1024, "fused"): 130.0,   # fresh fused row beside it
        (8192, "naive"): 40.0,    # untouched config survived too
    }


def test_lm_remat_policy_rows_key_apart(tmp_path):
    # lm_dots measures the same (T, B, remat) configs as lm_full under a
    # different checkpoint policy; the rows must coexist, and rows folded
    # before the field existed must key as the "full" policy.
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    out.write_text(json.dumps({"lm_train": {
        "platform": "tpu", "device_kind": "TPU v5 lite", "rows": [
            {"T": 8192, "B": 4, "remat": True, "xent": "fused",
             "tokens_per_s": 44.0}]}}))  # pre-field row == full policy
    (cap / "lm_dots.log").write_text(lm_line([
        {"T": 8192, "B": 4, "remat": True, "xent": "fused",
         "remat_policy": "dots", "tokens_per_s": 60.0}]) + "\n")
    run_fold(cap, out)
    rows = json.loads(out.read_text())["lm_train"]["rows"]
    by_key = {r.get("remat_policy", "full"): r["tokens_per_s"] for r in rows}
    assert by_key == {"full": 44.0, "dots": 60.0}
    # A full-policy re-measurement still overrides the pre-field row.
    (cap / "lm_full.log").write_text(lm_line([
        {"T": 8192, "B": 4, "remat": True, "xent": "fused",
         "remat_policy": "full", "tokens_per_s": 45.0}]) + "\n")
    run_fold(cap, out)
    rows = json.loads(out.read_text())["lm_train"]["rows"]
    by_key = {r.get("remat_policy", "full"): r["tokens_per_s"] for r in rows}
    assert by_key == {"full": 45.0, "dots": 60.0}


def test_lm_xl_folds_to_own_section_and_tune_is_cpu_gated(tmp_path):
    # XL-geometry rows must not merge into lm_train (different d_model/layers
    # would mislabel rows under lm_train's single meta header).
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    (cap / "lm_quick.log").write_text(lm_line([
        {"T": 1024, "B": 16, "remat": False, "tokens_per_s": 100.0}]) + "\n")
    (cap / "lm_xl.log").write_text(json.dumps({"lm_train": {
        "platform": "tpu", "device_kind": "TPU v5 lite",
        "d_model": 1536, "layers": 16, "kv_heads": 4, "rows": [
            {"T": 4096, "B": 4, "remat": False, "tokens_per_s": 55.0}]}}) + "\n")
    (cap / "flash_bwd_tune.log").write_text(json.dumps({"flash_bwd_tune": {
        "platform": "cpu", "T": 4096, "rows": []}}) + "\n")
    run_fold(cap, out)
    data = json.loads(out.read_text())
    assert data["lm_train_xl"]["d_model"] == 1536
    assert [r["T"] for r in data["lm_train"]["rows"]] == [1024]  # no mixing
    assert "flash_bwd_tune" not in data  # cpu run refused
    (cap / "flash_bwd_tune.log").write_text(json.dumps({"flash_bwd_tune": {
        "platform": "tpu", "device_kind": "TPU v5 lite", "T": 4096,
        "rows": [{"block_q": 512, "block_k": 512, "ms": 5.6}],
        "best": {"block_q": 512, "block_k": 512, "ms": 5.6}}}) + "\n")
    run_fold(cap, out)
    data = json.loads(out.read_text())
    assert data["flash_bwd_tune"]["best"]["ms"] == 5.6


def test_captured_when_is_log_mtime_not_fold_time(tmp_path):
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    log = cap / "impala_bench.log"
    log.write_text(impala_line() + "\n")
    old = time.time() - 3 * 86400
    os.utime(log, (old, old))
    run_fold(cap, out)
    data = json.loads(out.read_text())
    import datetime
    expect = datetime.date.fromtimestamp(old).isoformat()
    assert data["impala_learner"]["captured_when"] == expect
    assert data["when"] == expect  # re-folds must not restamp staleness


def test_roofline_prefers_fresh_name_and_folds_once(tmp_path):
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    stale = {"platform": "tpu", "arithmetic_intensity_flop_per_byte": 50.0,
             "bound": "stale"}
    fresh = dict(stale, bound="fresh")
    (cap / "impala_roofline.log").write_text(json.dumps(stale) + "\n")
    (cap / "roofline_chip.log").write_text(json.dumps(fresh) + "\n")
    run_fold(cap, out)
    data = json.loads(out.read_text())
    assert data["impala_roofline"]["bound"] == "fresh"
    assert data["provenance"].count("impala_roofline") == 1


def test_garbled_and_partial_logs_are_skipped(tmp_path):
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    (cap / "impala_bench.log").write_text("MOOLIB_BENCH_RESULT {\"metric\": \"impal")
    (cap / "lm_bench.log").write_text("{\"lm_train\": truncated")
    r = run_fold(cap, out)
    assert "nothing to fold" in r.stdout
    assert not out.exists()


def test_existing_sections_survive_partial_fold(tmp_path):
    cap = tmp_path / "cap"
    cap.mkdir()
    out = tmp_path / "BENCH_TPU.json"
    out.write_text(json.dumps({
        "when": "2026-07-29", "flash_attention": {"tests": "11/11"},
        "impala_learner": {"value": 1.0, "curated_note": "keep me"}}))
    (cap / "impala_bench.log").write_text(impala_line() + "\n")
    run_fold(cap, out)
    data = json.loads(out.read_text())
    assert data["flash_attention"] == {"tests": "11/11"}  # untouched
    assert data["impala_learner"]["value"] == 12345.6  # refreshed
    assert data["impala_learner"]["curated_note"] == "keep me"  # merged over


def qps_line(target, achieved):
    return json.dumps({
        "metric": "serve_qps", "qps_target": target, "engine": False,
        "qps_achieved": achieved, "p99_ms": 40.0})


def run_fold_local(log, out):
    return subprocess.run(
        [sys.executable, SCRIPT, "--local", str(log), str(out)],
        capture_output=True, text=True, timeout=60,
    )


def test_local_fold_detects_and_merges_serve_qps_rows(tmp_path):
    log = tmp_path / "serve_qps.log"
    out = tmp_path / "BENCH_LOCAL.json"
    out.write_text(json.dumps({
        "rpc_loopback": {"cmd": "x", "stdout": ["keep"], "rc": 0},
        "serve_qps": {"cmd": "benchmarks/serve_bench.py --qps 50 400",
                      "stdout": [qps_line(50, 11.0), qps_line(400, 9.0)],
                      "rc": 0}}))
    # Driver chatter around the rows must be salvaged through.
    log.write_text("\n".join([
        "replica 0: ready", qps_line(50, 25.0),
        "not json {", qps_line(100, 23.0), "SERVE BENCH OK"]) + "\n")
    r = run_fold_local(log, out)
    assert r.returncode == 0, r.stderr
    assert "serve_qps" in r.stdout
    data = json.loads(out.read_text())
    assert data["rpc_loopback"]["stdout"] == ["keep"]  # other sections intact
    assert data["serve_qps"]["cmd"].endswith("--qps 50 100")  # THIS capture's
    rows = {json.loads(l)["qps_target"]: json.loads(l)
            for l in data["serve_qps"]["stdout"]}
    # Re-measured targets replaced, the stored target not re-measured kept.
    assert rows[50]["qps_achieved"] == 25.0
    assert rows[100]["qps_achieved"] == 23.0
    assert rows[400]["qps_achieved"] == 9.0
