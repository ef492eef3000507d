"""What PR 44 added to the benchmark: the power-retention configuration's
file against its published keys, the traffic file through ``traffic.py``, the
new byte count, the new patterns, and the cell end to end at a tiny size, sound
and with each planted fault."""

import json
import os

import pytest

from chipbench import harness, kernel_bytes_retention
from chipbench import traffic as traffic_mod
from chipbench.readers import kernel_roofline_of, trace_share

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "brumby-14b-base.json")
TRAFFIC = harness.load_json(harness.BENCH_DIR, "traffic", "serve_statebound.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "brumby_serve_statebound")

# config.json of manifestai/Brumby-14B-Base as the model-configs catalog holds it.
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
    "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    entry = next(c for c in BENCH["configs"] if c["name"] == "brumby-14b-base")
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["published"] == {"num_hidden_layers": 40}
    assert CONFIG["num_hidden_layers"] == CONFIG["uses"]["serve"]["num_hidden_layers"] == 6
    assert "pipeline stage of 6" in CONFIG["deployment"]
    assert {"degree", "scale", "normaliser", "gate", "qk_norm_and_rope",
            "initialiser"} <= set(CONFIG["assumed"])
    assert "float32" in CONFIG["precision"]["serve"]["recurrent_state"]
    assert os.path.isfile(os.path.join(harness.ROOT, CONFIG["reference"]))
    assert entry["source"] == CONFIG["source"] and entry["file"].endswith("brumby-14b-base.json")
    assert 0 < CONFIG["tolerance"]["serve_not_argmax_share"] < 1
    assert CONFIG["tolerance"]["serve_not_argmax_why"]


def test_statebound_mix_is_the_issues_and_goes_through_the_generator():
    t = TRAFFIC
    assert t["runner"] == "serve_config" and t["arrivals"] == {"cv": 1.0}
    assert t["prompt_tokens"] == {"median": 1536, "sigma": 0.7, "min": 256, "max": 4096}
    assert t["budget_tokens"] == {"median": 384, "sigma": 0.5, "min": 128, "max": 1536}
    assert (t["slots"], t["positions_per_slot"], t["block_size"]) == (24, 8192, 128)
    assert (t["lead_s"], t["drain_limit_s"], t["max_queue"], t["trace_seconds"]) == (6, 40, 256, 2)
    assert "unread" in t["block_size_why"]
    assert t["prompt_tokens"]["max"] + t["budget_tokens"]["max"] <= t["positions_per_slot"]
    assert round(t["rate_per_s"] * 20, 6) == int(round(t["rate_per_s"] * 20))  # down to 0.05
    assert t["reference_requests"] == [[3500, 16]] + [[600, 1536]] * 8
    assert t["reference_fillers"] == {"count": 15, "prompt_tokens": 256, "budget_tokens": 24}
    assert len(t["reference_requests"]) + t["reference_fillers"]["count"] == t["slots"]
    schedule = traffic_mod.serve_schedule(t, 50.0)
    counted = [r for r in schedule if r["counted"]]
    assert len(counted) == round(t["rate_per_s"] * 50)
    assert schedule == traffic_mod.serve_schedule(t, 50.0)  # the file's one trace
    assert all(256 <= r["prompt_len"] <= 4096 and 128 <= r["budget"] <= 1536 for r in schedule)
    ids = traffic_mod.prompt_tokens(2 ** 31 + 5, 3, 64, CONFIG["vocab_size"])
    assert ids.min() >= 2 and ids.max() < CONFIG["vocab_size"]


def test_the_cell_reports_the_slot_following_median_and_its_own_layers():
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    e2e = {m["name"] for m in harness.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"req_ms_per_token_p50.moe", "setup_s"}
    layer = {m["name"] for m in harness.metrics_for(BENCH, CELL, "per_layer")}
    assert {"retention_decode_share", "retention_decode_roofline", "retention_prefill_share",
            "retention_live_slot_share", "state_write_mean_ms", "slot_occupancy_mean.moe",
            "decode_step_mean_ms.moe", "hbm_peak_GB.serve.moe",
            "device_idle_share.serve.moe"} <= layer
    # it has no pool, no experts, and the silent clock metric is a benchmark PR's
    assert not layer & {"paged_attn_share.moe", "kv_live_block_share.moe", "kda_decode_share",
                        "device_clock_lead_ms.serve.moe", "moe_held_matmul_share",
                        "state_live_slot_share"}
    for m in BENCH["per_layer"]:
        if m.get("workloads") == ["brumby_serve_statebound"]:
            assert m["moves"] == "req_ms_per_token_p50.moe" and m["layer"] == "kernels, serving"


def test_bytes_the_kernel_must_move():
    # a slot's symmetric state of one layer: 8 heads x 8,256 x 128 float32, read and written
    assert kernel_bytes_retention.retention_decode(CONFIG, TRAFFIC, 1) == 8 * 8256 * 128 * 4 * 2
    assert 6 * kernel_bytes_retention.retention_decode(CONFIG, TRAFFIC, 1) == 405798912  # 406 MB a slot


def _ctx(ops, histograms, kind="TPU v5 lite"):
    measured = harness.Measured(
        attempted=1, failed=0, correct=True,
        values={"trace_mean." + name: s / c for name, (s, c) in histograms.items()},
        trace=None if ops is None else {"busy_s": 1.0, "op_seconds": ops})
    return {"measured": measured, "config": CONFIG, "device": {"kind": kind}, "traffic": TRAFFIC}


DECODE_CALL = ("%retention_decode.3 = (f32[24,8,128,128]{3,2,1,0:T(8,128)}, f32[24,8,8,128]{3,2,1,0}, "
               "f32[24,6,8,65,128,128]{5,4,3,2,1,0:T(8,128)}, f32[24,6,8,72,128]{4,3,2,1,0}) "
               "custom-call(%sort, %count, %layer, %x, %v, %state, %norm), "
               'custom_call_target="tpu_custom_call"')
PREFILL_CALLS = ["%retention_prefill.1 = f32[40,2048,128]{2,1,0} custom-call(%q, %k, %v, %c, %c2)",
                 "%retention_prefill_state.2 = (f32[8,65,128,128]{3,2,1,0}, f32[8,72,128]{2,1,0}) "
                 "custom-call(%ks, %vt)"]
OTHER = "%get-tuple-element.9 = f32[24,8,128,128]{3,2,1,0} get-tuple-element(%retention_decode.3), index=0"


def test_the_roofline_counts_the_kernels_own_events():
    spec = harness.metric_spec("retention_decode_roofline")
    # 9 live slots: 608.7 MB a layer, 0.743 ms at 819 GB/s; a layer's call took 1 ms
    ops = [(DECODE_CALL, 1e-3), (OTHER, 1e-3), (PREFILL_CALLS[0], 5e-3)] * 6
    got = kernel_roofline_of.read(spec, _ctx(ops, {"serve_engine_state_live_slots": (9.0, 1)}))
    assert got == pytest.approx(100 * 9 * 8 * 8256 * 128 * 4 * 2 / 819e9 / 1e-3)


@pytest.mark.parametrize("ops,histograms", [
    (None, {"serve_engine_state_live_slots": (9.0, 1)}),  # not traced
    ([(OTHER, 1e-3)], {"serve_engine_state_live_slots": (9.0, 1)}),  # the parent: no such kernel
    ([(DECODE_CALL, 1e-3)], {}),  # no such histogram
])
def test_a_roofline_with_nothing_to_read_is_left_out(ops, histograms):
    spec = harness.metric_spec("retention_decode_roofline")
    assert kernel_roofline_of.read(spec, _ctx(ops, histograms)) is None


def test_share_patterns_select_their_kernels_by_name():
    ops = [DECODE_CALL, OTHER] + PREFILL_CALLS
    share = lambda name: trace_share.read(
        harness.metric_spec(name), {"measured": harness.Measured(
            attempted=1, failed=0, correct=True,
            trace={"busy_s": float(len(ops)), "op_seconds": [(t, 1.0) for t in ops],
                   "top_ops": [], "idle_gaps": []})})
    assert share("retention_decode_share") == pytest.approx(25.0)
    assert share("retention_prefill_share") == pytest.approx(50.0)
    assert share("kda_decode_share") == 0.0 and share("paged_attn_share.moe") == 0.0


TINY_LIMIT = 0.02


def _tiny_cell(monkeypatch, capsys, model=None):
    """``run.main`` through runner ``serve_config`` on the CPU: the tiny
    configuration of ``models/retention_lm.py`` in float32 (the CPU backend
    has no bfloat16 x bfloat16 -> float32 product), a few requests.  ``model``
    names another class for the configuration's ``"model"``.  Returns the exit
    code, the result line and the runner's notes."""
    import jax
    import jax.numpy as jnp

    import chipbench.run as bench_run
    from moolib_tpu.models.retention_lm import PowerRetentionLM, tiny_config

    # float32 on both sides, where the sound share is 0: the tiny cell's limit is
    # tighter than the file's, which leaves room for bfloat16 products
    config = {**CONFIG, **tiny_config(), "uses": {"serve": {"num_hidden_layers": 2}},
              "tolerance": {"serve_not_argmax_share": TINY_LIMIT}}
    if model:
        config["model"] = model
    traffic = {**TRAFFIC, "rate_per_s": 4.0, "lead_s": 0.5, "drain_limit_s": 30.0, "slots": 3,
               "positions_per_slot": 160, "trace_seconds": 0.3,
               "reference_requests": [[100, 4], [40, 110]],
               "reference_fillers": {"count": 1, "prompt_tokens": 33, "budget_tokens": 5},
               "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 33, "max": 128},
               "budget_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}
    real = harness.load_json

    def load_json(*parts):
        if parts[-1].endswith("brumby-14b-base.json"):
            return config
        if parts[-2:] == ("traffic", "serve_statebound.json"):
            return dict(traffic)
        return real(*parts)

    build = PowerRetentionLM.from_config.__func__
    monkeypatch.setattr(PowerRetentionLM, "from_config", classmethod(
        lambda cls, config, **kw: build(cls, config, dtype=jnp.float32, **kw)))
    monkeypatch.setattr(harness, "load_json", load_json)
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: jax.devices()[:chips])
    rc = bench_run.main(["--workload", "brumby_serve_statebound", "--seed", str(2 ** 31 + 11),
                         "--seconds", "1.5", "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(t[len("NOTES "):]) for t in out if t.startswith("NOTES "))
    return rc, json.loads(out[-1]), notes


def test_the_cell_runs_end_to_end_at_a_tiny_size(monkeypatch, capsys):
    """An engine without a pool under the runner's own load, and the checked
    request that decodes 110 tokens through one state agrees with the
    reference token for token (float32 on both sides)."""
    rc, line, notes = _tiny_cell(monkeypatch, capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] == 6
    assert notes["reference_tokens_checked"] == 4 + 110 + 5
    assert notes["reference_not_argmax_share"] == 0.0
    assert notes["engine"]["state_bytes"] > 0 and "num_blocks" not in notes["engine"]
    assert {"retention_live_slot_share", "slot_occupancy_mean.moe",
            "decode_step_mean_ms.moe"} <= set(line["metrics"])
    assert "kv_live_block_share.moe" not in line["metrics"]
    # a CPU has no device plane in its trace: the trace readers return nothing
    assert not set(line["metrics"]) & {m["name"] for m in BENCH["per_layer"]
                                       if m["source"] == "device_trace"}


@pytest.mark.parametrize("fault", ["NoStateWrite", "Fp8State", "NoDecay"])
def test_the_cells_own_limit_refuses_a_planted_fault(monkeypatch, capsys, fault):
    """The same run with a fault planted in the model ends ``correct: false``
    by the runner's own limit (the tiny cell's; on the chip the file's 1.9%
    refuses all three: 23.9%, 40.4%, 78.4%), nothing failed and nothing
    compiled in the window: the harness's own ``correct``, not a side script.
    (A state in bfloat16, the precision next below, is told apart by logits
    in ``tests/test_retention.py``; on the chip the file's eight decodes of
    1,536 tokens read it at 3.3-4.0% against 1.0-1.2% sound, refused too:
    the 114 tokens of this tiny cell could not.)"""
    rc, line, notes = _tiny_cell(
        monkeypatch, capsys, "chipbench.tests.planted_faults_retention:" + fault)
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_not_argmax_share"] > TINY_LIMIT
    assert line["correct"] is False
