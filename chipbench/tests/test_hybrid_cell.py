"""What PR 41 added to the benchmark: the hybrid configuration's file against
its published keys, the traffic file through ``traffic.py``, the new byte
counts and reader, the new patterns against HLO texts recorded from the
configuration's own programs, and the cell end to end at a tiny size."""

import json
import os
import re

import pytest

from chipbench import harness, kernel_bytes, kernel_bytes_hybrid
from chipbench import traffic as traffic_mod
from chipbench.readers import kernel_roofline, kernel_roofline_of, trace_share

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "solar-open2-250b.json")
TRAFFIC = harness.load_json(harness.BENCH_DIR, "traffic", "serve_longgen.json")
TEXTS = harness.load_json(harness.BENCH_DIR, "tests", "data", "hybrid_hlo_texts.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "solar_serve_longgen")

# config.json of upstage/Solar-Open2-250B as the model-configs catalog holds it.
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                           "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8,
}


def test_the_configuration_is_the_published_one_cut_to_a_chips_share():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    entry = next(c for c in BENCH["configs"] if c["name"] == "solar-open2-250b")
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in changed}
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == (
        4, 40, 24576)
    # the floors: a whole period, an eighth of the experts' chips and of the vocabulary
    assert CONFIG["num_hidden_layers"] % (CONFIG["gqa_interval"] + 1) == 0
    assert CONFIG["n_routed_experts"] * 8 == 320 and CONFIG["vocab_size"] * 8 == 196608
    assert "8 chips share each layer" in CONFIG["deployment"] and CONFIG["held_from"] == 0
    assert {"kda_gate_rank", "kda_decay", "short_conv", "gqa_gate", "scoring_func",
            "n_shared_experts_width", "initialiser"} <= set(CONFIG["assumed"])
    assert "float32" in CONFIG["precision"]["serve"]["recurrent_state"]
    assert os.path.isfile(os.path.join(harness.ROOT, CONFIG["reference"]))
    assert entry["source"] == CONFIG["source"] and entry["file"].endswith("solar-open2-250b.json")
    assert 0 < CONFIG["tolerance"]["serve_not_argmax_share"] < 1
    assert CONFIG["tolerance"]["serve_not_argmax_why"]


def test_longgen_mix_is_the_issues_and_goes_through_the_generator():
    t = TRAFFIC
    assert t["runner"] == "serve_config" and t["arrivals"] == {"cv": 1.0}
    assert t["prompt_tokens"] == {"median": 1024, "sigma": 0.7, "min": 256, "max": 4096}
    assert t["budget_tokens"] == {"median": 768, "sigma": 0.5, "min": 256, "max": 2048}
    assert (t["positions_per_slot"], t["lead_s"], t["drain_limit_s"]) == (6144, 6.0, 40.0)
    assert t["slots"] in (32, 64) and t["block_size"] in (64, 128) and t["block_size_why"]
    assert t["prompt_tokens"]["max"] + t["budget_tokens"]["max"] <= t["positions_per_slot"]
    assert t["rate_per_s"] * 2 == int(t["rate_per_s"] * 2)  # rounded down to 0.5
    assert len(t["reference_requests"]) + t["reference_fillers"]["count"] == t["slots"]
    (long, b1), (short, b2) = t["reference_requests"]
    assert 3000 <= long <= 4000 and 500 <= short <= 900 and b1 == 16
    assert 256 <= b2 <= 1024  # some hundreds of decode steps on one state, past block edges
    assert (short + b2) // t["block_size"] - short // t["block_size"] >= 2
    schedule = traffic_mod.serve_schedule(t, 50.0)
    counted = [r for r in schedule if r["counted"]]
    assert len(counted) == round(t["rate_per_s"] * 50)
    assert schedule == traffic_mod.serve_schedule(t, 50.0)  # the file's one trace
    assert all(256 <= r["prompt_len"] <= 4096 and 256 <= r["budget"] <= 2048 for r in schedule)
    assert all(t["lead_s"] <= r["due_s"] < t["lead_s"] + 50.0 for r in counted)
    ids = traffic_mod.prompt_tokens(2 ** 31 + 5, 3, 64, CONFIG["vocab_size"])
    assert ids.min() >= 2 and ids.max() < CONFIG["vocab_size"]  # drawn from the slice


def test_the_cell_reports_the_expert_median_and_its_own_layers():
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    e2e = {m["name"] for m in harness.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"req_ms_per_token_p50.moe", "setup_s"}
    layer = {m["name"] for m in harness.metrics_for(BENCH, CELL, "per_layer")}
    assert {"kda_decode_share", "kda_decode_roofline", "kda_prefill_share",
            "state_live_slot_share", "moe_held_pair_share", "moe_held_touched_share",
            "moe_held_matmul_share", "moe_held_matmul_roofline", "paged_attn_share.moe",
            "kv_live_block_share.moe", "state_write_mean_ms", "decode_step_mean_ms.moe",
            "hbm_peak_GB.serve.moe",
            "device_idle_share.serve.moe"} <= layer
    # glm's own stay glm's
    assert not layer & {"moe_expert_matmul_roofline", "moe_experts_touched_share",
                        "mla_decode_attn_share", "latent_live_row_share"}
    for m in BENCH["per_layer"]:
        if m.get("workloads") == ["solar_serve_longgen"]:
            assert m["moves"] == "req_ms_per_token_p50.moe"


def test_bytes_a_kernel_must_move():
    # a slot's state of one layer: 64 heads of 128 x 128 float32, read and written
    assert kernel_bytes_hybrid.kda_decode(CONFIG, TRAFFIC, 1) == 64 * 128 * 128 * 4 * 2 == 8388608
    # a held expert: gate, up and down of 4096 x 1280 at 2 bytes, by glm's count
    assert kernel_bytes.moe_expert_matmul(CONFIG, TRAFFIC, 1) == 3 * 4096 * 1280 * 2


def _ctx(ops, histograms, kind="TPU v5 lite"):
    measured = harness.Measured(
        attempted=1, failed=0, correct=True,
        values={"trace_mean." + name: s / c for name, (s, c) in histograms.items()},
        trace=None if ops is None else {"busy_s": 1.0, "op_seconds": ops})
    return {"measured": measured, "config": CONFIG, "device": {"kind": kind}, "traffic": TRAFFIC}


def _texts(which, start):
    return [t for t in TEXTS[which] if t.startswith(start)]


def test_rooflines_count_the_kernels_own_events_in_recorded_texts():
    kda_spec = harness.metric_spec("kda_decode_roofline")
    kda_call = _texts("decode", "%kda_decode")[0]
    consumer = "%get-tuple-element.9 = f32[64,64,128]{2,1,0} get-tuple-element(%kda_decode.7), index=0"
    # 29 live slots: 243 MB, 0.297 ms at 819 GB/s; a layer's call took 0.4 ms
    ops = [(kda_call, 0.4e-3), (consumer, 1e-3)] * 3
    got = kernel_roofline_of.read(kda_spec, _ctx(ops, {"serve_engine_state_live_slots": (29.0, 1)}))
    assert got == pytest.approx(100 * 29 * 8388608 / 819e9 / 0.4e-3)
    moe_spec = harness.metric_spec("moe_held_matmul_roofline")
    gate_up, down = _texts("decode", "%moe_expert_matmul")[:2]
    assert "[512,2560]" in gate_up and "[512,4096]" in down
    prefill = _texts("prefill", "%moe_expert_matmul")[0]
    assert "[32768," in prefill
    ops = [(gate_up, 0.7e-3), (down, 0.3e-3), (prefill, 5e-3), (consumer, 1e-3)] * 3
    assert moe_spec["reader"] == "kernel_roofline"  # glm's reader, this cell's rows and counter
    got = kernel_roofline.read(
        moe_spec, _ctx(ops, {"serve_engine_held_experts_touched": (20.0, 1)}))
    assert got == pytest.approx(100 * 20 * 31457280 / 819e9 / 1e-3)


@pytest.mark.parametrize("ops,histograms,kind", [
    (None, {"serve_engine_state_live_slots": (29.0, 1)}, "TPU v5 lite"),  # not traced
    ([("%fusion.1 = f32[8]{0} fusion(%x)", 1e-3)], {"serve_engine_state_live_slots": (29.0, 1)},
     "TPU v5 lite"),  # the parent's program: no such kernel
    ([("%kda_decode.7 = (f32[64,64,128]{2,1,0}) custom-call(%a)", 1e-3)], {}, "TPU v5 lite"),
    ([("%kda_decode.7 = (f32[64,64,128]{2,1,0}) custom-call(%a)", 1e-3)],
     {"serve_engine_state_live_slots": (29.0, 1)}, "TPU v9"),  # no peak on record
])
def test_a_roofline_with_nothing_to_read_is_left_out(ops, histograms, kind):
    spec = harness.metric_spec("kda_decode_roofline")
    assert kernel_roofline_of.read(spec, _ctx(ops, histograms, kind)) is None


def test_share_patterns_select_their_kernels_and_nothing_of_the_other_program():
    share = lambda name, ops: trace_share.read(
        harness.metric_spec(name), {"measured": harness.Measured(
            attempted=1, failed=0, correct=True,
            trace={"busy_s": float(len(ops)), "op_seconds": [(t, 1.0) for t in ops],
                   "top_ops": [], "idle_gaps": []})})
    decode, prefill = TEXTS["decode"], TEXTS["prefill"]
    n = lambda start: len(_texts("decode", start))
    assert n("%kda_decode") >= 1 and n("%paged_attention") >= 1 and n("%moe_expert_matmul") >= 2
    assert share("kda_decode_share", decode) == pytest.approx(100 * n("%kda_decode") / len(decode))
    assert share("paged_attn_share.moe", decode) == pytest.approx(
        100 * n("%paged_attention") / len(decode))
    assert share("moe_held_matmul_share", decode) == pytest.approx(
        100 * n("%moe_expert_matmul") / len(decode))
    # the prefill's chunk algebra has no name: it is known by its shapes, and
    # no operation of the decode program has them
    assert share("kda_prefill_share", decode) == 0.0
    tagged = [t for t in prefill if "kda_prefill" in t]
    rx = re.compile(harness.metric_spec("kda_prefill_share")["pattern"])
    hits = [t for t in tagged if rx.search(t)]
    assert len(tagged) >= 90 and len(hits) >= 0.5 * len(tagged)
    assert not [t for t in prefill if rx.search(t) and "kda_prefill" not in t
                and "metadata" in t]
    assert share("kda_decode_share", prefill) == 0.0


def _tiny_cell(monkeypatch, capsys, model=None, reference_requests=((100, 4), (40, 4))):
    """``run.main`` through runner ``serve_config`` on the CPU: the tiny
    configuration of ``models/hybrid_kda.py`` in float32 (the CPU backend has
    no bfloat16 x bfloat16 -> float32 product), a few requests.  ``model``
    names another class for the configuration's ``"model"``.  Returns the
    exit code, the result line and the runner's notes."""
    import jax
    import jax.numpy as jnp

    import chipbench.run as bench_run
    from moolib_tpu.models.hybrid_kda import HybridKdaMoELM, tiny_config

    config = {**CONFIG, **tiny_config(), "num_hidden_layers": 4,
              "uses": {"serve": {"num_hidden_layers": 4}}}
    if model:
        config["model"] = model
    traffic = {**TRAFFIC, "rate_per_s": 4.0, "lead_s": 0.5, "drain_limit_s": 30.0, "slots": 3,
               "block_size": 16, "positions_per_slot": 160, "trace_seconds": 0.3,
               "reference_requests": [list(r) for r in reference_requests],
               "reference_fillers": {"count": 1, "prompt_tokens": 33, "budget_tokens": 5},
               "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 33, "max": 128},
               "budget_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}
    real = harness.load_json

    def load_json(*parts):
        if parts[-1].endswith("solar-open2-250b.json"):
            return config
        if parts[-2:] == ("traffic", "serve_longgen.json"):
            return dict(traffic)
        return real(*parts)

    build = HybridKdaMoELM.from_config.__func__
    monkeypatch.setattr(HybridKdaMoELM, "from_config", classmethod(
        lambda cls, config, **kw: build(cls, config, dtype=jnp.float32, **kw)))
    monkeypatch.setattr(harness, "load_json", load_json)
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: jax.devices()[:chips])
    rc = bench_run.main(["--workload", "solar_serve_longgen", "--seed", str(2 ** 31 + 11),
                         "--seconds", "1.5", "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(t[len("NOTES "):]) for t in out if t.startswith("NOTES "))
    return rc, json.loads(out[-1]), notes


def test_the_cell_runs_end_to_end_at_a_tiny_size(monkeypatch, capsys):
    """Every counter this PR adds is read from the registry, and the one
    checked request that decodes past block edges agrees with the reference
    token for token (float32 on both sides)."""
    rc, line, notes = _tiny_cell(monkeypatch, capsys, reference_requests=((100, 4), (40, 40)))
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] == 6
    assert notes["reference_tokens_checked"] == 4 + 40 + 5
    assert notes["reference_not_argmax_share"] == 0.0
    assert {"state_live_slot_share", "moe_held_pair_share", "moe_held_touched_share",
            "moe_held_prefill_load_max_over_mean", "kv_live_block_share.moe",
            "decode_step_mean_ms.moe"} <= set(line["metrics"])
    assert 0 < line["metrics"]["moe_held_pair_share"]["value"] <= 100
    # a CPU has no device plane in its trace: the trace readers return nothing
    assert not set(line["metrics"]) & {m["name"] for m in BENCH["per_layer"]
                                       if m["source"] == "device_trace"}


@pytest.mark.parametrize("fault,refused", [
    ("NoStateWrite", True),  # a slot decodes from a state that is not its prompt's
    ("Fp8State", True),      # the recurrent state two precisions below the stated one
])
def test_the_cells_own_limit_refuses_a_planted_fault(monkeypatch, capsys, fault, refused):
    """The same run with a fault planted in the model ends ``correct: false``
    by the configuration's own limit, nothing failed and nothing compiled
    in the window: the harness's own ``correct``, not a side script.  (A state
    in bfloat16, the precision next below, is NOT refused by a share of
    argmax, here or on the chip: PERF.md section 4;
    ``tests/test_hybrid_kda.py`` tells it apart by logits.)"""
    rc, line, notes = _tiny_cell(monkeypatch, capsys, "chipbench.tests.planted_faults:" + fault,
                                 reference_requests=((100, 4), (40, 40)))
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_not_argmax_share"] > CONFIG["tolerance"]["serve_not_argmax_share"]
    assert (line["correct"] is False) == refused
