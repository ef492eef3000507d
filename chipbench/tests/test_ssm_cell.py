"""What PR 51 added to the benchmark: the Mamba / multi-query configuration's
file against its published keys (nothing cut), the traffic file through
``traffic.py``, the new byte counts and the new reader, the new patterns
against HLO texts recorded from the configuration's own programs, and the cell
end to end at a tiny size, sound and with each planted fault."""

import json
import os
import re

import pytest

from chipbench import harness, kernel_bytes_paged, kernel_bytes_ssm
from chipbench import traffic as traffic_mod
from chipbench.readers import kernel_bytes_roofline, kernel_roofline_of, trace_share

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "ai21-jamba2-3b.json")
TRAFFIC = harness.load_json(harness.BENCH_DIR, "traffic", "serve_reasoning.json")
TEXTS = harness.load_json(harness.BENCH_DIR, "tests", "data", "ssm_hlo_texts.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "jamba_serve_reasoning")
PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")
OWN = {"ssm_decode_share", "ssm_decode_roofline", "ssm_prefill_share", "ssm_prefill_roofline",
       "mqa_decode_attn_share", "mqa_decode_attn_roofline", "ssm_live_slot_share"}

# config.json of ai21labs/AI21-Jamba2-3B as the model-configs catalog holds it.
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
    "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}


def test_the_configuration_is_the_published_one_with_nothing_cut():
    assert {k for k, v in PUBLISHED.items() if CONFIG[k] != v} == set()
    entry = next(c for c in BENCH["configs"] if c["name"] == "ai21-jamba2-3b")
    assert CONFIG["reduced"] == {} and entry["reduced"] == [] and "EMPTY" in CONFIG["reduced_why"]
    assert CONFIG["uses"]["serve"] == {"num_hidden_layers": 28}
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in (
        "num_hidden_layers", "vocab_size", "max_position_embeddings")}
    attention = [l for l in range(28) if l % 14 == 7]
    assert attention == [7, 21] and "7 and 21" in CONFIG["assumed"]["layer_order"]
    assert {"layer_order", "inner_norms", "no_positions_no_qk_norm", "state_initialisers",
            "head_dim"} <= set(CONFIG["assumed"])
    assert CONFIG["head_dim"] == 2560 // 20 == 128
    assert {"max_position_embeddings", "num_logits_to_keep", "use_mamba_kernels"} <= set(
        CONFIG["not_run"])
    assert "one chip holds the whole model" in CONFIG["deployment"]
    assert "float32" in CONFIG["precision"]["serve"]["recurrent_state"]
    assert CONFIG["precision"]["serve"]["kv_pool"] == "bfloat16"
    assert CONFIG["model"] == "moolib_tpu.models.jamba:JambaLM"
    assert os.path.isfile(os.path.join(harness.ROOT, CONFIG["reference"]))
    assert entry["source"] == CONFIG["source"] and entry["file"].endswith("ai21-jamba2-3b.json")
    assert 0 < CONFIG["tolerance"]["serve_not_argmax_share"] < 1
    assert CONFIG["tolerance"]["serve_not_argmax_why"]


def test_reasoning_mix_is_the_issues_and_goes_through_the_generator():
    t = TRAFFIC
    assert t["runner"] == "serve_config" and t["arrivals"] == {"cv": 1.0}
    assert t["prompt_tokens"] == {"median": 256, "sigma": 0.9, "min": 32, "max": 2048}
    assert t["budget_tokens"] == {"median": 512, "sigma": 0.7, "min": 64, "max": 2048}
    assert (t["slots"], t["positions_per_slot"], t["block_size"]) == (256, 4096, 256)
    assert (t["lead_s"], t["max_queue"], t["trace_seconds"]) == (8, 512, 2)
    assert t["prompt_tokens"]["max"] + t["budget_tokens"]["max"] <= t["positions_per_slot"]
    assert t["rate_per_s"] * 2 == int(t["rate_per_s"] * 2)  # rounded down to 0.5
    # the largest prompt bucket, six decodes of 1,536, a prompt shorter than the convolution
    assert t["reference_requests"] == [[2000, 16]] + [[600, 1536]] * 6 + [[3, 64]]
    assert t["reference_fillers"] == {"count": 248, "prompt_tokens": 256, "budget_tokens": 24}
    assert len(t["reference_requests"]) + t["reference_fillers"]["count"] == t["slots"]
    assert t["drain_limit_s"] == 40  # the issue's
    schedule = traffic_mod.serve_schedule(t, 50.0)
    counted = [r for r in schedule if r["counted"]]
    # 40 s were sized for 11-12 ms a token and the cell runs 21.0-21.4: the
    # file's own trace must leave the request nearest the deadline (a budget of
    # 1,693 due 0.09 s before the window ends) a tenth more than that to get home
    home_by = t["lead_s"] + 50.0 + t["drain_limit_s"]
    assert min((home_by - r["due_s"]) * 1e3 / r["budget"] for r in counted) > 23.5
    assert len(counted) == round(t["rate_per_s"] * 50)
    assert schedule == traffic_mod.serve_schedule(t, 50.0)  # the file's one trace
    assert all(32 <= r["prompt_len"] <= 2048 and 64 <= r["budget"] <= 2048 for r in schedule)
    # short prompts, long answers: the output is over twice the input
    long = traffic_mod.serve_schedule(t, 400.0)
    assert sum(r["budget"] for r in long) > 1.5 * sum(r["prompt_len"] for r in long)
    ids = traffic_mod.prompt_tokens(2 ** 31 + 5, 3, 64, CONFIG["vocab_size"])
    assert ids.min() >= 2 and ids.max() < CONFIG["vocab_size"]


def test_the_cell_reports_the_expert_median_and_its_own_layers():
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    assert (CELL["config"], CELL["traffic"]) == ("ai21-jamba2-3b", "serve_reasoning")
    e2e = {m["name"] for m in harness.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"req_ms_per_token_p50.moe", "setup_s"}
    layer = {m["name"] for m in harness.metrics_for(BENCH, CELL, "per_layer")}
    assert OWN | {"paged_attn_share.moe", "kv_live_block_share.moe", "state_write_mean_ms",
                  "decode_step_mean_ms.moe", "hbm_peak_GB.serve.moe",
                  "device_idle_share.serve.moe", "slot_occupancy_mean.moe"} <= layer
    # every serving entry under a ``.moe`` name that all the expert cells share, its own seven,
    # and what it shares with some of them: counted from the file, which later PRs append to
    cells = {w["name"] for w in BENCH["workloads"] if w["name"] != CELL["name"]}
    shared = {m["name"] for m in BENCH["per_layer"]
              if CELL["name"] in m["workloads"] and cells & set(m["workloads"])}
    assert layer == OWN | shared and len(OWN) == 7 and len(shared) >= 23 + 3
    # another cell's geometry stays that cell's, and the silent clock metric a benchmark PR's
    assert not layer & {"state_live_slot_share", "retention_live_slot_share", "kda_decode_share",
                        "paged_attn_roofline", "full_decode_attn_share",
                        "device_clock_lead_ms.serve.moe", "moe_held_pair_share"}
    # its own entries, wherever they stand (at the END of their lists when PR 51 appended
    # them; later PRs appended theirs behind)
    own = [m for m in BENCH["per_layer"] if m["name"] in OWN]
    assert len(own) == 7 and "device_clock_lead_ms.serve.moe" not in {m["name"] for m in BENCH["per_layer"]}
    for m in own:
        assert m["workloads"] == ["jamba_serve_reasoning"] and m["unit"] == "%"
        assert m["moves"] == "req_ms_per_token_p50.moe" and m["layer"] == "kernels, serving"


def test_bytes_on_hand_worked_cases():
    # a live slot's state of one layer: 16 x 5,120 x 4 bytes, read and written
    assert kernel_bytes_ssm.ssm_decode(CONFIG, TRAFFIC, 1) == 16 * 5120 * 4 * 2 == 655360
    # every slot live: 168 MB a layer, 4.36 GB over the 26 layers of a step
    assert kernel_bytes_ssm.ssm_decode(CONFIG, TRAFFIC, 256) == 167772160
    # the 4,096 bucket: u, dt, z, y 4 x 83.9 MB, B and C 0.5 MB, the state 0.3 MB
    assert kernel_bytes_ssm.ssm_prefill(CONFIG, 4096, 5120) == 4 * (
        4 * 4096 * 5120 + 2 * 4096 * 16 + 16 * 5120) == 336396288
    # a block of the ONE K/V head: 256 x 128 x 2 bytes, K and V; a share of 256 x 16 blocks
    assert kernel_bytes_paged.paged_attention(CONFIG, TRAFFIC, 1 / 4096) == 256 * 128 * 2 * 2
    assert kernel_bytes_paged.paged_attention(CONFIG, TRAFFIC, 0.1) == pytest.approx(
        0.1 * 256 * 16 * 65536 * 2)


def _ctx(ops, histograms, kind="TPU v5 lite"):
    measured = harness.Measured(
        attempted=1, failed=0, correct=True,
        values={"trace_mean." + name: s / c for name, (s, c) in histograms.items()},
        trace=None if ops is None else {"busy_s": 1.0, "op_seconds": ops})
    return {"measured": measured, "config": CONFIG, "device": {"kind": kind}, "traffic": TRAFFIC,
            "peaks": PEAKS}


def _texts(which, start):
    return [t for t in TEXTS[which] if t.startswith(start)]


def test_rooflines_count_their_own_kernels_events_in_recorded_texts():
    scan, = _texts("decode", "%ssm_decode")
    attend, = _texts("decode", "%paged_attention")
    other = "%get-tuple-element.9 = f32[256,40,128]{2,1,0} get-tuple-element(%ssm_decode.15)"
    ops = [(scan, 0.1e-3), (attend, 0.02e-3), (other, 1e-3)] * 3
    hist = {"serve_engine_state_live_slots": (85.0, 1), "serve_engine_kv_live_share": (0.08, 1)}
    got = kernel_roofline_of.read(harness.metric_spec("ssm_decode_roofline"), _ctx(ops, hist))
    assert got == pytest.approx(100 * 85 * 655360 / 819e9 / 0.1e-3)  # 68.0%
    got = kernel_roofline_of.read(harness.metric_spec("mqa_decode_attn_roofline"), _ctx(ops, hist))
    assert got == pytest.approx(100 * 0.08 * 256 * 16 * 131072 / 819e9 / 0.02e-3)
    # the prefill's call as a run's scan over layers holds it: the stacked
    # states first, then y [bucket, channels]; bytes from each call's own bucket
    long = [t for t in _texts("prefill", "%ssm_prefill") if "f32[2048,5120]" in t]
    short = [t for t in _texts("prefill", "%ssm_prefill") if "f32[256,5120]" in t]
    assert len(long) == 3 and len(short) == 3 and all(t.split(" = ")[1].startswith("(f32[") for t in long)
    bare = "%ssm_prefill.3 = (f32[2048,5120]{1,0:T(8,128)}, f32[16,5120]{1,0:T(8,128)}) custom-call(%a)"
    ops = [(long[0], 1e-3), (short[0], 0.2e-3), (bare, 1e-3), (other, 1e-3)]
    # REAL positions, not the buckets in the calls' shapes: three prompts of
    # 1,500, 1,300 and 200 in the 2,048, 2,048 and 256 buckets, mean 1,000
    hist = {"serve_engine_scan_prefill_positions": (3000.0, 3)}
    got = kernel_bytes_roofline.read(harness.metric_spec("ssm_prefill_roofline"), _ctx(ops, hist))
    moved = sum(kernel_bytes_ssm.ssm_prefill(CONFIG, n, 5120) for n in (1500, 1300, 200))
    assert moved == 3 * kernel_bytes_ssm.ssm_prefill(CONFIG, 1000.0, 5120)
    assert got == pytest.approx(100 * moved / 2.2e-3 / 819e9)
    assert "HBM bound" in harness.metric_spec("ssm_prefill_roofline")["doc"]


@pytest.mark.parametrize("name,ops,histograms,kind", [
    ("ssm_decode_roofline", None, {"serve_engine_state_live_slots": (9.0, 1)}, "TPU v5 lite"),
    # the parent's program: no such kernel, no such histogram
    ("ssm_decode_roofline", [("%fusion.1 = f32[8]{0} fusion(%x)", 1e-3)], {}, "TPU v5 lite"),
    ("ssm_decode_roofline", [("%ssm_decode.1 = (f32[256,40,128]{2,1,0}, f32[256,26,16,5120]"
                              "{3,2,1,0}) custom-call(%a)", 1e-3)], {}, "TPU v5 lite"),
    ("mqa_decode_attn_roofline", [("%paged_attention.1 = f32[64,48,128]{2,1,0} custom-call(%a)",
                                   1e-3)], {"serve_engine_kv_live_share": (0.1, 1)}, "TPU v5 lite"),
    ("ssm_prefill_roofline", None, {"serve_engine_scan_prefill_positions": (300.0, 1)}, "TPU v5 lite"),
    ("ssm_prefill_roofline", [("%fusion.1 = f32[8]{0} fusion(%x)", 1e-3)],
     {"serve_engine_scan_prefill_positions": (300.0, 1)}, "TPU v5 lite"),
    # a program that runs the kernel and does not count the prompts' lengths
    ("ssm_prefill_roofline", [("%ssm_prefill.2 = (f32[2048,5120]{1,0}, f32[16,5120]{1,0}) "
                               "custom-call(%a)", 1e-3)], {}, "TPU v5 lite"),
    ("ssm_prefill_roofline", [("%ssm_prefill.2 = (f32[2048,5120]{1,0}, f32[16,5120]{1,0}) "
                               "custom-call(%a)", 1e-3)],
     {"serve_engine_scan_prefill_positions": (300.0, 1)}, "TPU v9"),  # no peak on record
])
def test_a_roofline_with_nothing_to_read_is_left_out(name, ops, histograms, kind):
    spec = harness.metric_spec(name)
    reader = kernel_bytes_roofline if "prefill" in name else kernel_roofline_of
    assert reader.read(spec, _ctx(ops, histograms, kind)) is None


def test_share_patterns_select_their_kernels_and_nothing_of_the_other_program():
    decode, prefill = TEXTS["decode"], TEXTS["prefill"]
    rx = {name: re.compile(harness.metric_spec(name)["pattern"]) for name in OWN
          if "pattern" in harness.metric_spec(name)}
    assert set(rx) == OWN - {"ssm_live_slot_share"}
    hits = lambda name, texts: [t for t in texts if rx[name].search(t)]
    assert hits("ssm_decode_share", decode) == _texts("decode", "%ssm_decode")
    assert hits("ssm_decode_roofline", decode) == _texts("decode", "%ssm_decode")
    assert hits("mqa_decode_attn_share", decode) == _texts("decode", "%paged_attention")
    assert hits("mqa_decode_attn_roofline", decode) == _texts("decode", "%paged_attention")
    assert all("f32[256,32,128]" in t for t in hits("mqa_decode_attn_share", decode))
    assert hits("ssm_prefill_share", prefill) == _texts("prefill", "%ssm_prefill")
    assert hits("ssm_prefill_roofline", prefill) == _texts("prefill", "%ssm_prefill")
    assert len(_texts("prefill", "%ssm_prefill")) == 6 and len(_texts("prefill", "%flash_attention")) == 2
    # no decode operation matches a prefill pattern, and no prefill operation a decode one
    for name in ("ssm_prefill_share", "ssm_prefill_roofline"):
        assert not hits(name, decode)
    for name in ("ssm_decode_share", "ssm_decode_roofline", "mqa_decode_attn_share",
                 "mqa_decode_attn_roofline"):
        assert not hits(name, prefill)
    # the twin the cell shares with solar and laguna reads the same two calls
    share = lambda name, ops: trace_share.read(
        harness.metric_spec(name), {"measured": harness.Measured(
            attempted=1, failed=0, correct=True,
            trace={"busy_s": float(len(ops)), "op_seconds": [(t, 1.0) for t in ops],
                   "top_ops": [], "idle_gaps": []})})
    assert share("mqa_decode_attn_share", decode) > 0
    assert share("paged_attn_share.moe", decode) == pytest.approx(
        share("mqa_decode_attn_share", decode))


def _tiny_cell(monkeypatch, capsys, model=None):
    """``run.main`` through runner ``serve_config`` on the CPU: the tiny
    configuration of ``models/jamba.py`` in float32 (the CPU backend has no
    bfloat16 x bfloat16 -> float32 product), a few requests, one of them
    shorter than the convolution and one whose bucket is mostly padding.
    ``model`` names another class for the configuration's ``"model"``.
    Returns the exit code, the result line and the runner's notes."""
    import jax
    import jax.numpy as jnp

    import chipbench.run as bench_run
    from moolib_tpu.models.jamba import JambaLM, tiny_config
    from moolib_tpu.ops import selective_scan as ssm

    config = {**CONFIG, **tiny_config(), "uses": {"serve": {"num_hidden_layers": 8}},
              "tolerance": {"serve_not_argmax_share": 0.02}}
    if model:
        config["model"] = model
    traffic = {**TRAFFIC, "rate_per_s": 4.0, "lead_s": 0.5, "drain_limit_s": 30.0, "slots": 4,
               "block_size": 16, "positions_per_slot": 160, "trace_seconds": 0.3,
               "reference_requests": [[70, 30], [3, 40], [33, 20]],
               "reference_fillers": {"count": 1, "prompt_tokens": 33, "budget_tokens": 5},
               "prompt_tokens": {"median": 20, "sigma": 1.0, "min": 4, "max": 128},
               "budget_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
    real = harness.load_json

    def load_json(*parts):
        if parts[-1].endswith("ai21-jamba2-3b.json"):
            return config
        if parts[-2:] == ("traffic", "serve_reasoning.json"):
            return dict(traffic)
        return real(*parts)

    monkeypatch.setattr(harness, "load_json", load_json)
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(ssm, "CHUNK", 16)  # in interpret mode a chunk is unrolled into the program
    # every class the file may name builds in float32 here
    build = JambaLM.from_config.__func__
    monkeypatch.setattr(JambaLM, "from_config", classmethod(
        lambda cls, config, **kw: build(cls, config, dtype=jnp.float32, **kw)))
    rc = bench_run.main(["--workload", "jamba_serve_reasoning", "--seed", str(2 ** 31 + 11),
                         "--seconds", "1.5", "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(t[len("NOTES "):]) for t in out if t.startswith("NOTES "))
    return rc, json.loads(out[-1]), notes


def test_the_cell_runs_end_to_end_at_a_tiny_size(monkeypatch, capsys):
    """Every counter the cell reads comes from the registry, and the checked
    requests (a prompt of 70 in a bucket of 128, one shorter than the
    convolution decoding 40, one a token past a bucket's edge) agree with the
    reference token for token (float32 on both sides)."""
    rc, line, notes = _tiny_cell(monkeypatch, capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] == 6
    assert notes["reference_tokens_checked"] == 30 + 40 + 20 + 5
    assert notes["reference_not_argmax_share"] == 0.0
    assert {"ssm_live_slot_share", "kv_live_block_share.moe", "decode_step_mean_ms.moe",
            "slot_occupancy_mean.moe"} <= set(line["metrics"])
    assert 0 < line["metrics"]["ssm_live_slot_share"]["value"] <= 100 * 4 / 256
    assert notes["engine"]["state_bytes"] == 4 * 6 * (16 + 3) * 256 * 4
    # a CPU has no device plane in its trace: the trace readers return nothing
    assert not set(line["metrics"]) & {m["name"] for m in BENCH["per_layer"]
                                       if m["source"] == "device_trace"}


@pytest.mark.parametrize("fault", ["NoStateWrite", "StateAtBucketEnd", "NoInnerNorms",
                                   "NoTailShift"])
def test_the_cells_own_limit_refuses_a_planted_fault(monkeypatch, capsys, fault):
    """The same run with a fault planted in the model ends ``correct: false``
    by the runner's own limit (the tiny cell's 2%; on the chip the file's limit
    refuses all five: its ``tolerance`` has the readings), nothing failed and
    nothing compiled in the window: the harness's own ``correct``, not a side
    script.  (A state in bfloat16, the precision next below, is told apart by
    logits in ``tests/test_jamba.py`` and on the chip by the file's six decodes
    of 1,536 tokens: the 95 tokens of this tiny cell could not.)"""
    rc, line, notes = _tiny_cell(
        monkeypatch, capsys, "chipbench.tests.planted_faults_ssm:" + fault)
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_not_argmax_share"] > 0.02
    assert line["correct"] is False


def test_the_bfloat16_state_control_runs_through_the_cell(monkeypatch, capsys):
    rc, line, notes = _tiny_cell(monkeypatch, capsys, "chipbench.tests.planted_faults_ssm:Bf16State")
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_tokens_checked"] == 30 + 40 + 20 + 5
    assert notes["reference_gap_sigma_mean"] > 0
