"""What PR 32 added to the benchmark: the configuration's file against its
published keys, the traffic file, the roofline reader and its byte
counts, and the runner's promise to fail before it listens."""

import ast
import os

import pytest

from chipbench import harness, kernel_bytes
from chipbench.readers import kernel_roofline

CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "glm-4.7-flash.json")

# config.json of zai-org/GLM-4.7-Flash, the keys that give its shape.
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 10240, "moe_intermediate_size": 1536,
    "num_attention_heads": 20, "num_key_value_heads": 20, "n_routed_experts": 64,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
    "first_k_dense_replace": 1, "num_hidden_layers": 47, "num_nextn_predict_layers": 1,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
    "rms_norm_eps": 1e-05, "rope_theta": 1000000, "n_group": 1, "topk_group": 1,
    "max_position_embeddings": 202752, "partial_rotary_factor": 1,
}


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["num_hidden_layers"] == 7 and CONFIG["published"]["num_hidden_layers"] == 47
    assert CONFIG["rope_scaling"] is None and CONFIG["norm_topk_prob"] is True
    assert set(CONFIG["not_run"]) == {"num_nextn_predict_layers"}
    assert {"rope_pairing", "e_score_correction_bias", "row_padding"} <= set(CONFIG["assumed"])
    assert os.path.isfile(os.path.join(harness.ROOT, CONFIG["reference"]))
    assert 0 < CONFIG["tolerance"]["serve_not_argmax_share"] < 1
    assert CONFIG["tolerance"]["serve_not_argmax_why"]


def test_docqa_mix_is_the_issues():
    t = harness.load_json(harness.BENCH_DIR, "traffic", "serve_docqa.json")
    assert t["runner"] == "serve_config" and t["arrivals"] == {"cv": 1.0}
    assert t["prompt_tokens"] == {"median": 1024, "sigma": 0.6, "min": 256, "max": 2048}
    assert t["budget_tokens"] == {"median": 256, "sigma": 0.6, "min": 64, "max": 768}
    assert (t["slots"], t["positions_per_slot"], t["max_queue"]) == (32, 3072, 256)
    assert t["reference_requests"] == [[1900, 16], [600, 16]]
    assert t["reference_fillers"] == {"count": 30, "prompt_tokens": 256, "budget_tokens": 24}
    assert t["rate_per_s"] * 2 == int(t["rate_per_s"] * 2)  # rounded down to 0.5
    assert t["prompt_tokens"]["max"] + t["budget_tokens"]["max"] <= t["positions_per_slot"]


def test_bytes_a_kernel_must_move():
    # an expert: gate, up and down of 2048 x 1536 at 2 bytes
    assert kernel_bytes.moe_expert_matmul(CONFIG, {}, 1) == 3 * 2048 * 1536 * 2
    traffic = {"slots": 32, "positions_per_slot": 3072}
    assert kernel_bytes.mla_decode_attn(CONFIG, traffic, 1.0) == 32 * 3072 * 1152


def _ctx(ops, histograms, kind="TPU v5 lite"):
    measured = harness.Measured(
        attempted=1, failed=0, correct=True,
        values={"trace_mean." + name: s / c for name, (s, c) in histograms.items()},
        trace=None if ops is None else {"busy_s": 1.0, "op_seconds": ops})
    return {"measured": measured, "config": CONFIG, "device": {"kind": kind},
            "traffic": {"slots": 32, "positions_per_slot": 3072}}


MOE = harness.load_json(harness.BENCH_DIR, "metrics", "moe_expert_matmul_roofline.json")
MLA = harness.load_json(harness.BENCH_DIR, "metrics", "mla_decode_attn_roofline.json")


def test_roofline_share_counts_the_kernels_own_events():
    gate_up = "%moe_expert_matmul.3 = bf16[128,3072]{1,0} custom-call(%a, %b)"
    down = "%moe_expert_matmul.4 = bf16[128,2048]{1,0} custom-call(%a, %b)"
    prefill = "%moe_expert_matmul.9 = bf16[4096,3072]{1,0} custom-call(%a, %b)"
    consumer = "%fusion.7 = bf16[128,1536]{1,0} fusion(%moe_expert_matmul.3)"
    # 32 experts touched: 604 MB, 0.737 ms at 819 GB/s; a layer's two calls took 1 ms.
    ops = [(gate_up, 0.7e-3), (down, 0.3e-3), (prefill, 5e-3), (consumer, 1e-3)] * 3
    got = kernel_roofline.read(MOE, _ctx(ops, {"serve_engine_experts_touched": (32.0, 1)}))
    assert got == pytest.approx(100 * 32 * 18874368 / 819e9 / 1e-3)
    attn = "%mla_decode_attention.2 = f32[32,32,512]{2,1,0} custom-call(%q, %pool)"
    sliced = "%slice.1 = f32[32,20,512]{2,1,0} slice(%mla_decode_attention.2)"
    got = kernel_roofline.read(
        MLA, _ctx([(attn, 50e-6), (sliced, 9e-6)], {"serve_engine_live_row_share": (0.25, 1)}))
    assert got == pytest.approx(100 * 0.25 * 32 * 3072 * 1152 / 819e9 / 50e-6)


@pytest.mark.parametrize("ops,histograms,kind", [
    (None, {"serve_engine_experts_touched": (32.0, 1)}, "TPU v5 lite"),  # not traced
    ([("%fusion.1 = f32[8]{0} fusion(%x)", 1e-3)], {"serve_engine_experts_touched": (32.0, 1)},
     "TPU v5 lite"),  # an older program: no such kernel
    ([("%moe_expert_matmul.3 = bf16[128,3072]{1,0} custom-call(%a)", 1e-3)], {}, "TPU v5 lite"),
    ([("%moe_expert_matmul.3 = bf16[128,3072]{1,0} custom-call(%a)", 1e-3)],
     {"serve_engine_experts_touched": (32.0, 1)}, "TPU v9"),  # no peak on record
])
def test_roofline_share_with_nothing_to_read_is_left_out(ops, histograms, kind):
    assert kernel_roofline.read(MOE, _ctx(ops, histograms, kind)) is None


def test_the_runner_imports_the_programs_new_module_before_it_listens():
    path = os.path.join(harness.BENCH_DIR, "runners", "serve_config.py")
    run = next(n for n in ast.parse(open(path).read()).body
               if isinstance(n, ast.FunctionDef) and n.name == "run")
    src = ast.get_source_segment(open(path).read(), run)
    assert src.index("load_model(config)") < src.index("rpc.listen(")
    assert src.index("load_model(config)") < src.index("subprocess.Popen(")


def test_trace_means_are_the_traced_windows_own():
    from chipbench.runners import serve_config

    snap = lambda s, c: {"h": {"series": [{"labels": {}, "value": {"sum": s, "count": c}}]},
                         "labelled": {"series": [{"labels": {"phase": "x"},
                                                  "value": {"sum": 1.0, "count": 1}}]},
                         "gauge": {"series": [{"labels": {}, "value": 3.0}]}}
    got = serve_config._trace_means({"trace_before": snap(100.0, 10), "trace_after": snap(130.0, 16)})
    assert got == {"trace_mean.h": 5.0}
    assert serve_config._trace_means({}) == {}
