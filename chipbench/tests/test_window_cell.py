"""What PR 48 added to the benchmark: the sliding-window configuration's file
against its published keys, the traffic file through ``traffic.py``, the new
byte and operation counts and the new reader, the new patterns against HLO
texts recorded from the configuration's own programs, and the cell end to end
at a tiny size, sound and with each planted fault."""

import json
import os
import re

import pytest

from chipbench import flops_window, harness, kernel_bytes, kernel_bytes_paged, kernel_bytes_window
from chipbench import traffic as traffic_mod
from chipbench.readers import kernel_flops_roofline, kernel_roofline, kernel_roofline_of, trace_share

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "laguna-s-2.1.json")
TRAFFIC = harness.load_json(harness.BENCH_DIR, "traffic", "serve_mixedlen.json")
TEXTS = harness.load_json(harness.BENCH_DIR, "tests", "data", "window_hlo_texts.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "laguna_serve_mixedlen")
PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")

# config.json of poolside/Laguna-S-2.1 as the model-configs catalog holds it.
_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 1048576,
    "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
            "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
    "layer_types": (["full_attention"] + _PERIOD * 12)[:48],
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47, "gating_types": ["per_head"] * 48,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": ([48] + [72, 72, 72, 48] * 12)[:48],
    "moe_router_logit_softcapping": 0,
}


def test_the_configuration_is_the_published_one_cut_to_a_chips_share():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    entry = next(c for c in BENCH["configs"] if c["name"] == "laguna-s-2.1")
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in changed}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"], CONFIG["vocab_size"]) == (
        9, 32, 12544)
    # the floors: the leading dense layer and whole periods (two), an eighth of
    # the experts' chips and of the vocabulary; the router's width is never cut
    assert (CONFIG["num_hidden_layers"] - 1) % 4 == 0 and CONFIG["num_hidden_layers"] - 1 >= 4
    assert CONFIG["num_experts"] * 8 == 256 == CONFIG["router_experts"]
    assert CONFIG["vocab_size"] * 8 == 100352
    assert "8 chips share each layer" in CONFIG["deployment"] and CONFIG["held_from"] == 0
    assert {"gate", "router", "qk_norm_and_shared_gate", "initialiser"} <= set(CONFIG["assumed"])
    assert "bfloat16" in CONFIG["precision"]["serve"]["kv_ring"]
    assert os.path.isfile(os.path.join(harness.ROOT, CONFIG["reference"]))
    assert entry["source"] == CONFIG["source"] and entry["file"].endswith("laguna-s-2.1.json")
    assert 0 < CONFIG["tolerance"]["serve_not_argmax_share"] < 1
    assert CONFIG["tolerance"]["serve_not_argmax_why"]


def test_mixedlen_mix_is_the_issues_and_goes_through_the_generator():
    t = TRAFFIC
    assert t["runner"] == "serve_config" and t["arrivals"] == {"cv": 1.0}
    assert t["prompt_tokens"] == {"median": 640, "sigma": 1.1, "min": 64, "max": 4096}
    assert t["budget_tokens"] == {"median": 256, "sigma": 0.9, "min": 32, "max": 2048}
    assert (t["slots"], t["positions_per_slot"], t["block_size"]) == (64, 6144, 128)
    assert (t["lead_s"], t["drain_limit_s"], t["max_queue"], t["trace_seconds"]) == (6, 40, 256, 2)
    assert t["prompt_tokens"]["max"] + t["budget_tokens"]["max"] <= t["positions_per_slot"]
    assert t["rate_per_s"] * 2 == int(t["rate_per_s"] * 2)  # rounded down to 0.5
    assert t["reference_requests"] == [[3500, 16], [700, 512], [400, 200]]
    assert t["reference_fillers"] == {"count": 61, "prompt_tokens": 256, "budget_tokens": 24}
    assert len(t["reference_requests"]) + t["reference_fillers"]["count"] == t["slots"]
    # a decode that wraps the ring a second time, and one that crosses the window
    window = CONFIG["sliding_window"]
    assert (700 + 512) // window - 700 // window >= 1 and 400 < window < 400 + 200
    schedule = traffic_mod.serve_schedule(t, 50.0)
    counted = [r for r in schedule if r["counted"]]
    assert len(counted) == round(t["rate_per_s"] * 50)
    assert schedule == traffic_mod.serve_schedule(t, 50.0)  # the file's one trace
    assert all(64 <= r["prompt_len"] <= 4096 and 32 <= r["budget"] <= 2048 for r in schedule)
    # both sides of the window in one queue: a fifth under 256, a seventh over 2,048
    lengths = [r["prompt_len"] for r in traffic_mod.serve_schedule(t, 400.0)]
    assert 0.12 < sum(n < 256 for n in lengths) / len(lengths) < 0.28
    assert 0.08 < sum(n > 2048 for n in lengths) / len(lengths) < 0.22
    ids = traffic_mod.prompt_tokens(2 ** 31 + 5, 3, 64, CONFIG["vocab_size"])
    assert ids.min() >= 2 and ids.max() < CONFIG["vocab_size"]  # drawn from the slice


def test_the_cell_reports_the_expert_median_and_its_own_layers():
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    e2e = {m["name"] for m in harness.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"req_ms_per_token_p50.moe", "setup_s"}
    layer = {m["name"] for m in harness.metrics_for(BENCH, CELL, "per_layer")}
    own = {"swa_decode_attn_share", "swa_decode_attn_roofline", "full_decode_attn_share",
           "full_decode_attn_roofline", "swa_prefill_attn_share", "swa_prefill_attn_roofline",
           "ring_live_row_share", "moe_held_touched_share.laguna",
           "moe_held_matmul_roofline.laguna"}
    assert own | {"moe_held_pair_share", "moe_held_matmul_share", "paged_attn_share.moe",
                  "moe_held_prefill_load_max_over_mean", "kv_live_block_share.moe",
                  "state_write_mean_ms", "decode_step_mean_ms.moe", "hbm_peak_GB.serve.moe",
                  "device_idle_share.serve.moe"} <= layer
    # another cell's geometry stays that cell's, and the silent clock metric a benchmark PR's
    assert not layer & {"moe_held_touched_share", "moe_held_matmul_roofline", "paged_attn_roofline",
                        "state_live_slot_share", "retention_live_slot_share", "kda_decode_share",
                        "device_clock_lead_ms.serve.moe", "moe_expert_matmul_roofline"}
    for m in BENCH["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == ["laguna_serve_mixedlen"]
            assert m["moves"] == "req_ms_per_token_p50.moe"
            assert m["layer"] in ("kernels, serving", "expert layer, serving")


def test_bytes_and_operations_on_hand_worked_cases():
    # a ring row: 8 K/V heads x 128 x 2 bytes, K and V
    assert kernel_bytes_window.swa_decode_attention(CONFIG, TRAFFIC, 1) == 8 * 128 * 2 * 2 == 4096
    # 21 slots live on full rings: 21 x 512 rows = 44.0 MB a sliding layer
    assert kernel_bytes_window.swa_decode_attention(CONFIG, TRAFFIC, 21 * 512) == 44040192
    # a full layer's live blocks: a share of 64 x 48 blocks of 512 KB
    assert kernel_bytes_paged.paged_attention(CONFIG, TRAFFIC, 0.1) == pytest.approx(
        0.1 * 64 * 48 * 128 * 8 * 128 * 2 * 2)
    # a held expert: gate, up and down of 3072 x 1024 at 2 bytes
    assert kernel_bytes.moe_expert_matmul(CONFIG, TRAFFIC, 1) == 3 * 3072 * 1024 * 2
    # pairs: under the window a triangle; past it the window's triangle and a band
    assert flops_window.window_pairs(3, 512) == 6 and flops_window.window_pairs(512, 512) == 131328
    assert flops_window.window_pairs(4096, 512) == 131328 + 3584 * 512 == 1966336  # the issue's 1.97 M
    assert flops_window.window_pairs(4096, 4) == 1 + 2 + 3 + 4 + 4092 * 4
    assert flops_window.windowed_attention(CONFIG, 72, 4096) == 4 * 128 * 72 * 1966336  # 72.5 GFLOP


def _ctx(ops, histograms, kind="TPU v5 lite"):
    measured = harness.Measured(
        attempted=1, failed=0, correct=True,
        values={"trace_mean." + name: s / c for name, (s, c) in histograms.items()},
        trace=None if ops is None else {"busy_s": 1.0, "op_seconds": ops})
    return {"measured": measured, "config": CONFIG, "device": {"kind": kind}, "traffic": TRAFFIC,
            "peaks": PEAKS}


def _texts(which, start):
    return [t for t in TEXTS[which] if t.startswith(start)]


def _call(which, start, shape):
    return next(t for t in _texts(which, start) if shape in t)


def test_rooflines_count_their_own_kernels_events_in_recorded_texts():
    swa = _call("decode", "%paged_attention", "f32[64,80,128]")
    full = _call("decode", "%paged_attention", "f32[64,48,128]")
    other = "%get-tuple-element.9 = f32[64,80,128]{2,1,0} get-tuple-element(%paged_attention.19)"
    ops = [(swa, 0.1e-3), (full, 0.4e-3), (other, 1e-3)] * 3
    hist = {"serve_engine_ring_rows_read": (21 * 512.0, 1), "serve_engine_kv_live_share": (0.1, 1)}
    got = kernel_roofline_of.read(harness.metric_spec("swa_decode_attn_roofline"), _ctx(ops, hist))
    assert got == pytest.approx(100 * 44040192 / 819e9 / 0.1e-3)  # 53.8%
    got = kernel_roofline_of.read(harness.metric_spec("full_decode_attn_roofline"), _ctx(ops, hist))
    assert got == pytest.approx(100 * 0.1 * 64 * 48 * 524288 / 819e9 / 0.4e-3)
    gate_up, down = _texts("decode", "%moe_expert_matmul")[:2]
    assert "[768,2048]" in gate_up and "[768,3072]" in down
    prefill = _call("prefill", "%moe_expert_matmul", "[40960,")
    ops = [(gate_up, 0.7e-3), (down, 0.3e-3), (prefill, 5e-3), (other, 1e-3)] * 3
    got = kernel_roofline.read(harness.metric_spec("moe_held_matmul_roofline.laguna"),
                               _ctx(ops, {"serve_engine_held_experts_touched": (18.0, 1)}))
    assert got == pytest.approx(100 * 18 * 3 * 3072 * 1024 * 2 / 819e9 / 1e-3)
    # the windowed kernel at 4,096 (one result) and the causal one a bucket under
    # the window takes (two results): operations from each call's own length
    long = _call("prefill", "%flash_attention", "bf16[72,4096,128]")
    short = _call("prefill", "%flash_attention", "(bf16[72,256,128]")
    full_prefill = _call("prefill", "%flash_attention", "(bf16[48,4096,128]")
    ops = [(long, 1e-3), (long, 1e-3), (short, 0.05e-3), (full_prefill, 3e-3)]
    got = kernel_flops_roofline.read(harness.metric_spec("swa_prefill_attn_roofline"), _ctx(ops, {}))
    flops = 2 * 4 * 128 * 72 * 1966336 + 4 * 128 * 72 * (256 * 257 // 2)
    assert got == pytest.approx(100 * flops / 2.05e-3 / 197e12)


@pytest.mark.parametrize("name,ops,histograms,kind", [
    ("swa_decode_attn_roofline", None, {"serve_engine_ring_rows_read": (9.0, 1)}, "TPU v5 lite"),
    # the parent's program: no such kernel, no such histogram
    ("swa_decode_attn_roofline", [("%fusion.1 = f32[8]{0} fusion(%x)", 1e-3)], {}, "TPU v5 lite"),
    ("swa_decode_attn_roofline", [("%paged_attention.1 = f32[64,80,128]{2,1,0} custom-call(%a)",
                                   1e-3)], {}, "TPU v5 lite"),
    ("full_decode_attn_roofline", [("%paged_attention.1 = f32[64,80,128]{2,1,0} custom-call(%a)",
                                    1e-3)], {"serve_engine_kv_live_share": (0.1, 1)}, "TPU v5 lite"),
    ("swa_prefill_attn_roofline", None, {}, "TPU v5 lite"),
    ("swa_prefill_attn_roofline", [("%fusion.1 = f32[8]{0} fusion(%x)", 1e-3)], {}, "TPU v5 lite"),
    ("swa_prefill_attn_roofline", [("%flash_attention.2 = bf16[72,4096,128]{2,1,0} custom-call(%a)",
                                    1e-3)], {}, "TPU v9"),  # no peak on record
])
def test_a_roofline_with_nothing_to_read_is_left_out(name, ops, histograms, kind):
    spec = harness.metric_spec(name)
    reader = kernel_flops_roofline if "prefill" in name else kernel_roofline_of
    assert reader.read(spec, _ctx(ops, histograms, kind)) is None


def test_share_patterns_select_their_kernels_and_nothing_of_the_other_program():
    share = lambda name, ops: trace_share.read(
        harness.metric_spec(name), {"measured": harness.Measured(
            attempted=1, failed=0, correct=True,
            trace={"busy_s": float(len(ops)), "op_seconds": [(t, 1.0) for t in ops],
                   "top_ops": [], "idle_gaps": []})})
    decode, prefill = TEXTS["decode"], TEXTS["prefill"]
    rx = {name: re.compile(harness.metric_spec(name)["pattern"]) for name in (
        "swa_decode_attn_share", "full_decode_attn_share", "swa_prefill_attn_share",
        "swa_decode_attn_roofline", "full_decode_attn_roofline", "swa_prefill_attn_roofline",
        "moe_held_matmul_roofline.laguna")}
    hits = lambda name, texts: [t for t in texts if rx[name].search(t)]
    # the two attention kinds of a decode step: each pattern its own calls, never the other's
    swa, full = hits("swa_decode_attn_share", decode), hits("full_decode_attn_share", decode)
    assert swa and full and not set(swa) & set(full)
    assert all("f32[64,80,128]" in t for t in swa) and all("f32[64,48,128]" in t for t in full)
    assert sorted(swa + full) == sorted(_texts("decode", "%paged_attention"))
    assert hits("swa_decode_attn_roofline", decode) == swa
    assert hits("full_decode_attn_roofline", decode) == full
    assert share("paged_attn_share.moe", decode) == pytest.approx(
        share("swa_decode_attn_share", decode) + share("full_decode_attn_share", decode))
    # the prefill's: the sliding layers' flash calls (72 heads), windowed or, in a
    # bucket under the window, causal; never the full layers' (48 heads)
    windowed = hits("swa_prefill_attn_share", prefill)
    assert len(windowed) == 2 and all("bf16[72," in t for t in windowed)
    assert hits("swa_prefill_attn_roofline", prefill) == windowed
    assert len(_texts("prefill", "%flash_attention")) == 4
    # no decode operation matches a prefill pattern, and no prefill operation a decode one
    assert not hits("swa_prefill_attn_share", decode)
    for name in ("swa_decode_attn_share", "full_decode_attn_share",
                 "moe_held_matmul_roofline.laguna"):
        assert not hits(name, prefill)
    assert len(hits("moe_held_matmul_roofline.laguna", decode)) == 2  # gate|up and down


def _tiny_cell(monkeypatch, capsys, model=None):
    """``run.main`` through runner ``serve_config`` on the CPU: the tiny
    configuration of ``models/swa_moe.py`` in float32 (the CPU backend has no
    bfloat16 x bfloat16 -> float32 product), a few requests on both sides of
    its window of 8.  ``model`` names another class for the configuration's
    ``"model"``.  Returns the exit code, the result line and the runner's
    notes."""
    import jax
    import jax.numpy as jnp

    import chipbench.run as bench_run
    from moolib_tpu.models.swa_moe import SlidingGqaMoELM, tiny_config

    config = {**CONFIG, **tiny_config(), "uses": {"serve": {"num_hidden_layers": 5}}}
    if model:
        config["model"] = model
    traffic = {**TRAFFIC, "rate_per_s": 4.0, "lead_s": 0.5, "drain_limit_s": 30.0, "slots": 4,
               "block_size": 16, "positions_per_slot": 160, "trace_seconds": 0.3,
               "reference_requests": [[100, 4], [20, 40], [5, 20]],
               "reference_fillers": {"count": 1, "prompt_tokens": 33, "budget_tokens": 5},
               "prompt_tokens": {"median": 20, "sigma": 1.0, "min": 4, "max": 128},
               "budget_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
    real = harness.load_json

    def load_json(*parts):
        if parts[-1].endswith("laguna-s-2.1.json"):
            return config
        if parts[-2:] == ("traffic", "serve_mixedlen.json"):
            return dict(traffic)
        return real(*parts)

    monkeypatch.setattr(harness, "load_json", load_json)
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: jax.devices()[:chips])
    # every class the file may name builds in float32 here
    build = SlidingGqaMoELM.from_config.__func__
    monkeypatch.setattr(SlidingGqaMoELM, "from_config", classmethod(
        lambda cls, config, **kw: build(cls, config, dtype=jnp.float32, **kw)))
    rc = bench_run.main(["--workload", "laguna_serve_mixedlen", "--seed", str(2 ** 31 + 11),
                         "--seconds", "1.5", "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(t[len("NOTES "):]) for t in out if t.startswith("NOTES "))
    return rc, json.loads(out[-1]), notes


def test_the_cell_runs_end_to_end_at_a_tiny_size(monkeypatch, capsys):
    """Every counter this PR adds is read from the registry, and the checked
    requests (a prompt past the window, a decode that wraps the ring five
    times, a prompt under the window whose decode crosses it) agree with the
    reference token for token (float32 on both sides)."""
    rc, line, notes = _tiny_cell(monkeypatch, capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] == 6
    assert notes["reference_tokens_checked"] == 4 + 40 + 20 + 5
    assert notes["reference_not_argmax_share"] == 0.0
    assert {"ring_live_row_share", "moe_held_pair_share", "moe_held_touched_share.laguna",
            "moe_held_prefill_load_max_over_mean", "kv_live_block_share.moe",
            "decode_step_mean_ms.moe"} <= set(line["metrics"])
    assert 0 < line["metrics"]["ring_live_row_share"]["value"] <= 100 * 8 / 512
    # a CPU has no device plane in its trace: the trace readers return nothing
    assert not set(line["metrics"]) & {m["name"] for m in BENCH["per_layer"]
                                       if m["source"] == "device_trace"}


@pytest.mark.parametrize("fault", ["NoWindowMask", "NoRingWrite", "ClippedRing", "PlainFullRope"])
def test_the_cells_own_limit_refuses_a_planted_fault(monkeypatch, capsys, fault):
    """The same run with a fault planted in the model ends ``correct: false``
    by the configuration's own limit, nothing failed and nothing compiled in
    the window: the harness's own ``correct``, not a side script."""
    rc, line, notes = _tiny_cell(
        monkeypatch, capsys, "chipbench.tests.planted_faults_window:" + fault)
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_not_argmax_share"] > CONFIG["tolerance"]["serve_not_argmax_share"]
    assert line["correct"] is False


@pytest.mark.parametrize("control", ["Fp8Ring", "Fp8Pool", "Bf16Router", "Bf16Residual"])
def test_a_precision_control_runs_through_the_cell(monkeypatch, capsys, control):
    """The lower-precision controls that set the limit's upper reading on the
    chip run through the harness and move tokens off the float32 reference's
    (what share is refused is a reading of the chip, in the file's
    ``tolerance``: this model is float32 here)."""
    rc, line, notes = _tiny_cell(
        monkeypatch, capsys, "chipbench.tests.planted_faults_window:" + control)
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_tokens_checked"] == 4 + 40 + 20 + 5
    assert notes["reference_gap_sigma_mean"] > 0
