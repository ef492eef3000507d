"""What PR 56 added to the benchmark: the Mamba-2 / NoPE GQA / experts
configuration's file against its published keys and its cut, the traffic file
through ``traffic.py``, the new byte and operation counts and the new reader,
the new patterns against HLO texts recorded from the configuration's own
programs, and the cell end to end at a tiny size, sound and with each planted
fault."""

import json
import os
import re

import pytest

from chipbench import flops_ssd, harness, kernel_bytes, kernel_bytes_paged, kernel_bytes_ssd
from chipbench import traffic as traffic_mod
from chipbench.readers import kernel_flops_of, kernel_roofline, kernel_roofline_of, trace_share

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "granite-4.0-h-small.json")
TRAFFIC = harness.load_json(harness.BENCH_DIR, "traffic", "serve_sessions.json")
TEXTS = harness.load_json(harness.BENCH_DIR, "tests", "data", "ssd_hlo_texts.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "granite_serve_sessions")
PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")
OWN = ["ssd_decode_share", "ssd_decode_roofline", "ssd_prefill_share", "ssd_prefill_roofline",
       "ssd_live_slot_share", "moe_held_touched_share.granite", "moe_held_matmul_roofline.granite"]
CUT = {"num_hidden_layers": 10, "num_local_experts": 18, "vocab_size": 25088}

# config.json of ibm-granite/granite-4.0-h-small as the model-configs catalog holds it.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True, "vocab_size": 100352,
}


def test_the_configuration_is_the_published_one_cut_to_one_stage_of_sixteen_chips():
    assert {k for k, v in PUBLISHED.items() if CONFIG[k] != v} == set(CUT)
    assert {k: CONFIG[k] for k in CUT} == CUT
    entry = next(c for c in BENCH["configs"] if c["name"] == "granite-4.0-h-small")
    assert entry["reduced"] == list(CUT) and set(CUT) < set(CONFIG["reduced"])
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in CUT}
    assert CONFIG["uses"]["serve"] == {"num_hidden_layers": 10}
    # one whole period: five Mamba-2 layers, attention at 5, four more
    kinds = CONFIG["layer_types"][:10]
    assert kinds.count("attention") == 1 and kinds.index("attention") == 5
    assert (CONFIG["router_experts"], CONFIG["held_from"]) == (72, 0)
    assert CONFIG["head_dim"] == 4096 // 32 == 128
    assert CONFIG["moe_intermediate_size"] == CONFIG["intermediate_size"] == 768
    assert CONFIG["mamba_n_heads"] * CONFIG["mamba_d_head"] == 2 * CONFIG["hidden_size"]
    assert "16 chips" in CONFIG["deployment"] and "four pipeline stages" in CONFIG["deployment"]
    assert {"intermediate_size", "head_dim", "mamba", "attention", "multipliers", "router",
            "state_initialisers", "initialiser"} <= set(CONFIG["assumed"])
    assert {"max_position_embeddings", "rope_theta", "mamba_chunk_size"} <= set(CONFIG["not_run"])
    assert "float32" in CONFIG["precision"]["serve"]["recurrent_state"]
    assert CONFIG["precision"]["serve"]["kv_pool"] == "bfloat16"
    assert CONFIG["model"] == "moolib_tpu.models.ssd_moe:SsdGqaMoELM"
    assert os.path.isfile(os.path.join(harness.ROOT, CONFIG["reference"]))
    assert entry["source"] == CONFIG["source"] and entry["file"].endswith("granite-4.0-h-small.json")
    assert 0 < CONFIG["tolerance"]["serve_not_argmax_share"] < 1
    assert CONFIG["tolerance"]["serve_not_argmax_why"]


def test_sessions_mix_is_the_issues_and_goes_through_the_generator():
    t = TRAFFIC
    assert t["runner"] == "serve_config" and t["arrivals"] == {"cv": 1.0}
    assert t["prompt_tokens"] == {"median": 640, "sigma": 0.8, "min": 128, "max": 2048}
    assert t["budget_tokens"] == {"median": 256, "sigma": 0.7, "min": 64, "max": 1024}
    assert (t["slots"], t["positions_per_slot"], t["block_size"]) == (128, 3072, 128)
    assert (t["lead_s"], t["max_queue"], t["trace_seconds"], t["drain_limit_s"]) == (8, 512, 2, 40)
    assert t["prompt_tokens"]["max"] + t["budget_tokens"]["max"] <= t["positions_per_slot"]
    assert t["rate_per_s"] * 2 == int(t["rate_per_s"] * 2)  # rounded down to 0.5
    # the largest bucket, six decodes of 1,536, a prompt two past a bucket's edge
    assert t["reference_requests"] == [[1900, 16]] + [[600, 1536]] * 6 + [[130, 64]]
    assert t["reference_fillers"] == {"count": 120, "prompt_tokens": 256, "budget_tokens": 24}
    assert len(t["reference_requests"]) + t["reference_fillers"]["count"] == t["slots"]
    schedule = traffic_mod.serve_schedule(t, 50.0)
    counted = [r for r in schedule if r["counted"]]
    # the file's own trace leaves the request nearest the deadline (the drain
    # of 40 s against the 15.5 ms a token the cell runs) over three times that
    home_by = t["lead_s"] + 50.0 + t["drain_limit_s"]
    assert min((home_by - r["due_s"]) * 1e3 / r["budget"] for r in counted) > NEAREST_MS_A_TOKEN
    assert len(counted) == round(t["rate_per_s"] * 50)
    assert schedule == traffic_mod.serve_schedule(t, 50.0)  # the file's one trace
    assert all(128 <= r["prompt_len"] <= 2048 and 64 <= r["budget"] <= 1024 for r in schedule)
    # retrieved contexts, short answers: the input is over twice the output
    long = traffic_mod.serve_schedule(t, 400.0)
    assert sum(r["prompt_len"] for r in long) > 2 * sum(r["budget"] for r in long)
    # a prompt of exactly 128 tokens, the mix's floor (its prefill has the decode
    # step's 1,280 expert rows): 2.3% of arrivals
    assert 0.02 < sum(r["prompt_len"] == 128 for r in long) / len(long) < 0.026
    ids = traffic_mod.prompt_tokens(2 ** 31 + 5, 3, 64, CONFIG["vocab_size"])
    assert ids.min() >= 2 and ids.max() < CONFIG["vocab_size"]


NEAREST_MS_A_TOKEN = 50.0  # the kept trace (5603 at 3.5/s) leaves a budget of 893 due at 52.58 s 50.9 ms a token; the cell runs 15.5


def test_the_cell_reports_the_expert_median_and_its_own_layers():
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    assert (CELL["config"], CELL["traffic"]) == ("granite-4.0-h-small", "serve_sessions")
    e2e = {m["name"] for m in harness.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"req_ms_per_token_p50.moe", "setup_s"}
    layer = {m["name"] for m in harness.metrics_for(BENCH, CELL, "per_layer")}
    assert set(OWN) | {"paged_attn_share.moe", "kv_live_block_share.moe", "state_write_mean_ms",
                       "moe_held_pair_share", "moe_held_matmul_share",
                       "moe_held_prefill_load_max_over_mean", "decode_step_mean_ms.moe",
                       "hbm_peak_GB.serve.moe", "device_idle_share.serve.moe",
                       "slot_occupancy_mean.moe"} <= layer
    assert len(layer) >= 29 + 6 + 7
    # another cell's geometry stays that cell's, and the silent clock metric a benchmark PR's
    assert not layer & {"state_live_slot_share", "ssm_live_slot_share", "ssm_decode_share",
                        "kda_decode_share", "paged_attn_roofline", "moe_held_touched_share", "moe_held_touched_share.laguna",
                        "moe_held_matmul_roofline", "device_clock_lead_ms.serve.moe"}
    # entries behind everything the benchmark had (a later PR's come behind these)
    names = lambda key: [entry["name"] for entry in BENCH[key]]
    assert names("workloads").index(CELL["name"]) == names("workloads").index("jamba_serve_reasoning") + 1
    assert names("configs").index("granite-4.0-h-small") == names("configs").index("ai21-jamba2-3b") + 1
    first = names("per_layer").index("step_program_mean_ms.train") + 1
    assert names("per_layer")[first:first + 7] == OWN
    for m in BENCH["per_layer"][first:first + 7]:
        assert m["workloads"] == ["granite_serve_sessions"] and m["unit"] == "%"
        assert m["moves"] == "req_ms_per_token_p50.moe"
        assert m["layer"] == ("expert layer, serving" if m["name"].startswith("moe_")
                              else "kernels, serving")
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        ours = m.get("workloads", [])
        if "granite_serve_sessions" in ours and "jamba_serve_reasoning" in ours:
            assert ours.index("granite_serve_sessions") > ours.index("jamba_serve_reasoning")


def test_bytes_and_operations_on_hand_worked_cases():
    # a live slot's state of one layer: 128 x 64 x 128 x 4 bytes, read and written
    assert kernel_bytes_ssd.ssd_decode(CONFIG, TRAFFIC, 1) == 128 * 64 * 128 * 4 * 2 == 8388608
    # every slot live: 1.07 GB a layer, 9.66 GB over the 9 layers of a step
    assert kernel_bytes_ssd.ssd_decode(CONFIG, TRAFFIC, 128) == 1073741824
    # one published chunk of 256: C B^T once, then 128 heads of (L o G)(dt x), C S^T and the update
    chunk = 2 * 256 * 256 * 128 + 128 * (2 * 256 * 256 * 64 + 4 * 256 * 128 * 64)
    assert flops_ssd.ssd_prefill(CONFIG, 256) == chunk == 2164260864
    assert flops_ssd.ssd_prefill(CONFIG, 2048) == 8 * chunk  # 8.45 MFLOP a token a layer
    assert flops_ssd.ssd_prefill(CONFIG, 800.0) == pytest.approx(800 / 256 * chunk)
    # an expert's three matrices at the weights' 2 bytes: 18.9 MB
    assert kernel_bytes.moe_expert_matmul(CONFIG, TRAFFIC, 1) == 3 * 4096 * 768 * 2 == 18874368
    # a block of the 8 K/V heads: 128 x 8 x 128 x 2 bytes, K and V; a share of 128 x 24 blocks
    assert kernel_bytes_paged.paged_attention(CONFIG, TRAFFIC, 1 / 3072) == 128 * 8 * 128 * 2 * 2


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
    scan, = _texts("decode", "%ssd_decode")
    gu, down = _texts("decode", "%moe_expert_matmul")
    other = "%get-tuple-element.9 = f32[128,64,128]{2,1,0} get-tuple-element(%ssd_decode.10)"
    ops = [(scan, 0.5e-3), (gu, 0.2e-3), (down, 0.1e-3), (other, 1e-3)] * 3
    hist = {"serve_engine_state_live_slots": (40.0, 1), "serve_engine_held_experts_touched": (17.0, 1)}
    got = kernel_roofline_of.read(harness.metric_spec("ssd_decode_roofline"), _ctx(ops, hist))
    assert got == pytest.approx(100 * 40 * 8388608 / 819e9 / 0.5e-3)  # 81.9%
    got = kernel_roofline.read(harness.metric_spec("moe_held_matmul_roofline.granite"), _ctx(ops, hist))
    assert got == pytest.approx(100 * 17 * 18874368 / 819e9 / 0.3e-3)
    # the prefill's calls: operations from the prompts' REAL positions, not the
    # buckets in the calls' shapes: two prompts of 1,500 and 100 in the 2,048 and 128 buckets
    long, short = _texts("prefill", "%ssd_prefill")
    assert "f32[2048,8192]" in long and "f32[128,8192]" in short
    ops = [(long, 1.2e-3), (short, 0.1e-3), (other, 1e-3)]
    hist = {"serve_engine_scan_prefill_positions": (1600.0, 2)}
    spec = harness.metric_spec("ssd_prefill_roofline")
    got = kernel_flops_of.read(spec, _ctx(ops, hist))
    done = flops_ssd.ssd_prefill(CONFIG, 1500) + flops_ssd.ssd_prefill(CONFIG, 100)
    assert done == pytest.approx(2 * flops_ssd.ssd_prefill(CONFIG, 800.0))
    assert got == pytest.approx(100 * done / 1.3e-3 / 197e12)  # 5.3%
    assert "share of the MXU's bfloat16 PEAK" in spec["doc"] and "bounds the kernel" in spec["doc"]


@pytest.mark.parametrize("name,reader,ops,histograms,kind", [
    ("ssd_decode_roofline", kernel_roofline_of, None,
     {"serve_engine_state_live_slots": (9.0, 1)}, "TPU v5 lite"),
    # the parent's program: no such kernel, no such histogram
    ("ssd_decode_roofline", kernel_roofline_of, [("%fusion.1 = f32[8]{0} fusion(%x)", 1e-3)], {},
     "TPU v5 lite"),
    ("ssd_decode_roofline", kernel_roofline_of,
     [("%ssd_decode.1 = (f32[128,64,128]{2,1,0}, f32[128,9,128,64,128]{4,3,2,1,0}) "
       "custom-call(%a)", 1e-3)], {}, "TPU v5 lite"),
    ("ssd_prefill_roofline", kernel_flops_of, None,
     {"serve_engine_scan_prefill_positions": (300.0, 1)}, "TPU v5 lite"),
    ("ssd_prefill_roofline", kernel_flops_of, [("%fusion.1 = f32[8]{0} fusion(%x)", 1e-3)],
     {"serve_engine_scan_prefill_positions": (300.0, 1)}, "TPU v5 lite"),
    # the Mamba-1 configuration's program: another kernel's name, the same histogram
    ("ssd_prefill_roofline", kernel_flops_of,
     [("%ssm_prefill.2 = (f32[2048,5120]{1,0}, f32[16,5120]{1,0}) custom-call(%a)", 1e-3)],
     {"serve_engine_scan_prefill_positions": (300.0, 1)}, "TPU v5 lite"),
    ("ssd_prefill_roofline", kernel_flops_of,
     [("%ssd_prefill.2 = (f32[2048,8192]{1,0}, f32[8192,128]{1,0}) custom-call(%a)", 1e-3)], {},
     "TPU v5 lite"),
    ("ssd_prefill_roofline", kernel_flops_of,
     [("%ssd_prefill.2 = (f32[2048,8192]{1,0}, f32[8192,128]{1,0}) custom-call(%a)", 1e-3)],
     {"serve_engine_scan_prefill_positions": (300.0, 1)}, "TPU v9"),  # no peak on record
    # another cell's decode rows: this cell's pattern selects its own 1,280 alone
    ("moe_held_matmul_roofline.granite", kernel_roofline,
     [("%moe_expert_matmul.1 = bf16[768,2048]{1,0} custom-call(%a)", 1e-3)],
     {"serve_engine_held_experts_touched": (17.0, 1)}, "TPU v5 lite"),
])
def test_a_roofline_with_nothing_to_read_is_left_out(name, reader, ops, histograms, kind):
    assert reader.read(harness.metric_spec(name), _ctx(ops, histograms, kind)) is None


def test_share_patterns_select_their_kernels_and_nothing_of_the_other_program():
    decode, prefill = TEXTS["decode"], TEXTS["prefill"]
    rx = {name: re.compile(harness.metric_spec(name)["pattern"]) for name in OWN
          if "pattern" in harness.metric_spec(name)}
    assert set(rx) == set(OWN) - {"ssd_live_slot_share", "moe_held_touched_share.granite"}
    hits = lambda name, texts: [t for t in texts if rx[name].search(t)]
    assert hits("ssd_decode_share", decode) == _texts("decode", "%ssd_decode")
    assert hits("ssd_decode_roofline", decode) == _texts("decode", "%ssd_decode")
    assert hits("ssd_prefill_share", prefill) == _texts("prefill", "%ssd_prefill")
    assert hits("ssd_prefill_roofline", prefill) == _texts("prefill", "%ssd_prefill")
    assert len(_texts("decode", "%ssd_decode")) == 1 and len(_texts("prefill", "%ssd_prefill")) == 2
    # the decode step's two grouped products, and the 128-bucket prefill's, which has its rows
    assert hits("moe_held_matmul_roofline.granite", decode) == _texts("decode", "%moe_expert_matmul")
    assert len(hits("moe_held_matmul_roofline.granite", prefill)) == 2
    assert all("[1280," in t for t in hits("moe_held_matmul_roofline.granite", prefill))
    # no decode operation matches a prefill pattern, and no prefill operation a decode one
    for name in ("ssd_prefill_share", "ssd_prefill_roofline"):
        assert not hits(name, decode)
    for name in ("ssd_decode_share", "ssd_decode_roofline"):
        assert not hits(name, prefill)
    # ssd_* never matches ssm_* (the Mamba-1 configuration's kernels), nor the reverse
    theirs = harness.load_json(harness.BENCH_DIR, "tests", "data", "ssm_hlo_texts.json")
    for name in ("ssd_decode_share", "ssd_decode_roofline", "ssd_prefill_share", "ssd_prefill_roofline"):
        assert not hits(name, theirs["decode"] + theirs["prefill"])
    for name in ("ssm_decode_share", "ssm_decode_roofline", "ssm_prefill_share", "ssm_prefill_roofline"):
        pattern = re.compile(harness.metric_spec(name)["pattern"])
        assert not [t for t in decode + prefill if pattern.search(t)]
    # the twins the cell shares with the other cells read its paged call and its grouped products
    share = lambda name, ops: trace_share.read(
        harness.metric_spec(name), {"measured": harness.Measured(
            attempted=1, failed=0, correct=True,
            trace={"busy_s": float(len(ops)), "op_seconds": [(t, 1.0) for t in ops],
                   "top_ops": [], "idle_gaps": []})})
    assert share("paged_attn_share.moe", decode) == pytest.approx(100 / len(decode))
    assert share("moe_held_matmul_share", decode) == pytest.approx(200 / len(decode))
    assert share("ssd_decode_share", decode) == pytest.approx(100 / len(decode))


def _tiny_cell(monkeypatch, capsys, model=None, limit=0.02):
    """``run.main`` through runner ``serve_config`` on the CPU: the tiny
    configuration of ``models/ssd_moe.py`` in float32 (the CPU backend has no
    bfloat16 x bfloat16 -> float32 product), a few requests, one of them two
    past a bucket's edge and one whose prefill spans several chunks.  ``model``
    names another class for the configuration's ``"model"``.  Returns the exit
    code, the result line and the runner's notes."""
    import jax
    import jax.numpy as jnp

    import chipbench.run as bench_run
    from moolib_tpu.models.ssd_moe import SsdGqaMoELM, tiny_config
    from moolib_tpu.ops import ssd

    config = {**CONFIG, **tiny_config(), "uses": {"serve": {"num_hidden_layers": 10}},
              "tolerance": {"serve_not_argmax_share": limit}}
    if model:
        config["model"] = model
    traffic = {**TRAFFIC, "rate_per_s": 4.0, "lead_s": 0.5, "drain_limit_s": 30.0, "slots": 4,
               "block_size": 16, "positions_per_slot": 160, "trace_seconds": 0.3,
               "reference_requests": [[70, 30], [18, 40], [33, 20]],
               "reference_fillers": {"count": 1, "prompt_tokens": 33, "budget_tokens": 5},
               "prompt_tokens": {"median": 20, "sigma": 1.0, "min": 17, "max": 128},
               "budget_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
    real = harness.load_json

    def load_json(*parts):
        if parts[-1].endswith("granite-4.0-h-small.json"):
            return config
        if parts[-2:] == ("traffic", "serve_sessions.json"):
            return dict(traffic)
        return real(*parts)

    monkeypatch.setattr(harness, "load_json", load_json)
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(ssd, "CHUNK", 16)  # a prompt of 70 spans five chunks
    # every class the file may name builds in float32 here
    build = SsdGqaMoELM.from_config.__func__
    monkeypatch.setattr(SsdGqaMoELM, "from_config", classmethod(
        lambda cls, config, **kw: build(cls, config, dtype=jnp.float32, **kw)))
    rc = bench_run.main(["--workload", "granite_serve_sessions", "--seed", str(2 ** 31 + 11),
                         "--seconds", "1.5", "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(t[len("NOTES "):]) for t in out if t.startswith("NOTES "))
    return rc, json.loads(out[-1]), notes


def test_the_cell_runs_end_to_end_at_a_tiny_size(monkeypatch, capsys):
    """Every counter the cell reads comes from the registry, and the checked
    requests (a prompt of 70 in a bucket of 128, one of 18 decoding 40, one a
    token past a bucket's edge) agree with the reference token for token
    (float32 on both sides)."""
    rc, line, notes = _tiny_cell(monkeypatch, capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] == 6
    assert notes["reference_tokens_checked"] == 30 + 40 + 20 + 5
    assert notes["reference_not_argmax_share"] == 0.0
    assert {"ssd_live_slot_share", "moe_held_touched_share.granite", "moe_held_pair_share",
            "moe_held_prefill_load_max_over_mean", "kv_live_block_share.moe",
            "decode_step_mean_ms.moe", "slot_occupancy_mean.moe"} <= set(line["metrics"])
    assert 0 < line["metrics"]["ssd_live_slot_share"]["value"] <= 100 * 4 / 128
    # 4 of the tiny router's 8 experts are held: half the pairs, near enough
    assert 25 < line["metrics"]["moe_held_pair_share"]["value"] < 75
    assert notes["engine"]["state_bytes"] == 4 * 8 * (4 * 64 * 64 + 16 * 128) * 4
    # a CPU has no device plane in its trace: the trace readers return nothing
    assert not set(line["metrics"]) & {m["name"] for m in BENCH["per_layer"]
                                       if m["source"] == "device_trace"}


# (b), (c): two of the three the chip's check MUST refuse; (d), (e), (f): read
# and reported on the chip, refused outright at this size
@pytest.mark.parametrize("fault", ["ChunksFromEmptyState", "NoTailWrite", "ResidualOne",
                                   "SqrtScores", "GateAfterNorm"])
def test_the_cells_own_limit_refuses_a_planted_fault(monkeypatch, capsys, fault):
    """The same run with a fault planted in the model ends ``correct: false``
    by the runner's own limit (the tiny cell's 2%; on the chip the file's
    ``tolerance`` has the readings), nothing failed and nothing compiled in the
    window: the harness's own ``correct``, not a side script.  ((a), a state in
    bfloat16, the precision next below, is told apart by logits in
    ``tests/test_ssd_moe.py`` and on the chip by the file's six decodes of 1,536
    tokens: the 95 tokens of this tiny cell could not.)"""
    rc, line, notes = _tiny_cell(
        monkeypatch, capsys, "chipbench.tests.planted_faults_ssd:" + fault)
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_not_argmax_share"] > 0.02
    assert line["correct"] is False


def test_the_bfloat16_state_control_runs_through_the_cell(monkeypatch, capsys):
    rc, line, notes = _tiny_cell(monkeypatch, capsys, "chipbench.tests.planted_faults_ssd:Bf16State")
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_tokens_checked"] == 30 + 40 + 20 + 5
    assert notes["reference_gap_sigma_mean"] > 0
