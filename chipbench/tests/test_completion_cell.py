"""What PR 59 added to the benchmark: ``mellum2-12b-a2.5b-instruct``'s file
against its published keys, the traffic file through ``traffic.py``, the
operation count of the grouped matmul against a count by hand, the new
patterns against texts recorded from the configuration's own programs (the
kernels' events of a traced run of the cell on the chip), the readers of the
new counters, and each planted fault's class against the model it differs
from.  No engine and no cell is run here (``tests/test_swa_moe.py`` holds the
model to the reference): this module is imported into tier 1 by
``tests/test_chipbench_files.py``."""

import os
import re

import pytest

from chipbench import flops_moe, flops_window, harness, kernel_bytes, kernel_bytes_window
from chipbench import traffic as traffic_mod
from chipbench.readers import (histogram_mean, kernel_flops_of, kernel_flops_roofline,
                               registry_delta, trace_share)

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "mellum2-12b-a2.5b-instruct.json")
TRAFFIC = harness.load_json(harness.BENCH_DIR, "traffic", "serve_completion.json")
TEXTS = harness.load_json(harness.BENCH_DIR, "tests", "data", "completion_hlo_texts.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "mellum_serve_completion")
PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")

# config.json of JetBrains/Mellum2-12B-A2.5B-Instruct as the model-configs catalog holds it.
_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 7168, "layer_types": _PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
            "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304,
    "use_sliding_window": True,
}
# BENCHMARK.json may hold 128 per-layer metrics and held 122: six are this cell's own.
OWN = {"moe_prefill_matmul_share.mellum", "moe_prefill_matmul_roofline.mellum",
       "moe_prefill_rows_per_expert.mellum", "swa_prefill_attn_roofline.mellum",
       "swa_window_blocks_visited_share.mellum", "prefill_pad_share.mellum"}


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    entry = next(c for c in BENCH["configs"] if c["name"] == "mellum2-12b-a2.5b-instruct")
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["published"] == {"num_hidden_layers": 28}
    # two whole periods; every expert, the router's whole width, the whole vocabulary
    assert CONFIG["num_hidden_layers"] == CONFIG["uses"]["serve"]["num_hidden_layers"] == 8
    assert CONFIG["layer_types"][:8] == _PERIOD * 2 and len(CONFIG["layer_types"]) == 28
    assert CONFIG["num_experts"] == CONFIG["router_experts"] == 64 and CONFIG["held_from"] == 0
    assert CONFIG["vocab_size"] == 98304
    assert CONFIG["qk_norm"] is True and "qwen3_moe" in CONFIG["assumed"]["qk_norm"]
    assert {"qk_norm", "router", "rotation", "initialiser", "weights"} <= set(CONFIG["assumed"])
    assert {"intermediate_size", "max_window_layers_use_sliding_window", "mtp_head",
            "max_position_embeddings"} <= set(CONFIG["not_run"])
    assert "pipeline stages of 8, 8, 8 and 4" in CONFIG["deployment"]
    assert "bfloat16" in CONFIG["precision"]["serve"]["kv_ring"]
    assert CONFIG["model"] == "moolib_tpu.models.swa_moe:SlidingGqaMoELM"
    assert os.path.isfile(os.path.join(harness.ROOT, CONFIG["reference"]))
    assert entry["source"] == CONFIG["source"] and entry["file"].endswith(
        "mellum2-12b-a2.5b-instruct.json")
    # the initialiser's one constant of this file's own, argued where it is assumed
    assert CONFIG["embed_init_scale"] == 2.0 and "embed_init_scale" in CONFIG["assumed"]["initialiser"]
    assert 0 < CONFIG["tolerance"]["serve_not_argmax_share"] < 1
    # nothing stands for a reading that was never made
    for text in (CONFIG["tolerance"]["serve_not_argmax_why"], TRAFFIC["doc"], CELL["why"]):
        assert text and not re.search(r"PLACEHOLDER|RATE|KNEE", text)


def test_the_reference_imports_neither_the_program_nor_another_reference():
    with open(os.path.join(harness.ROOT, CONFIG["reference"])) as f:
        source = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert set(imports) <= {"__future__", "functools", "math", "typing", "jax", "jax.numpy"}
    assert 'default_matmul_precision("highest")' in source and "float32" in source


def test_completion_mix_is_the_issues_and_goes_through_the_generator():
    t = TRAFFIC
    assert (t["runner"], t["use"], t["arrivals"]) == ("serve_config", "serve", {"cv": 1.0})
    assert t["prompt_tokens"] == {"median": 1792, "sigma": 0.5, "min": 512, "max": 4096}
    assert t["budget_tokens"] == {"median": 24, "sigma": 0.6, "min": 8, "max": 128}
    assert (t["slots"], t["positions_per_slot"], t["block_size"]) == (32, 4224, 128)
    assert (t["lead_s"], t["drain_limit_s"], t["max_queue"], t["trace_seconds"]) == (6, 40, 256, 4)
    assert t["prompt_tokens"]["max"] + t["budget_tokens"]["max"] == t["positions_per_slot"]
    assert t["rate_per_s"] * 2 == int(t["rate_per_s"] * 2)  # rounded down to 0.5
    assert t["reference_requests"] == [[4000, 16], [1500, 256], [700, 1024]]
    assert t["reference_fillers"] == {"count": 29, "prompt_tokens": 256, "budget_tokens": 24}
    assert len(t["reference_requests"]) + t["reference_fillers"]["count"] == t["slots"]
    # a prompt past the window; a decode that crosses the ring's first wrap and
    # ends 700 rows into the second turn; a decode across a pool's block edge
    window = CONFIG["sliding_window"]
    assert 1500 > window and 700 < window < 700 + 1024 and (700 + 1024) % window == 700
    assert 1500 // 128 < (1500 + 256) // 128
    assert sum(b for _p, b in t["reference_requests"]) + 29 * 24 == 1992
    schedule = traffic_mod.serve_schedule(t, 50.0)
    counted = [r for r in schedule if r["counted"]]
    assert len(counted) == round(t["rate_per_s"] * 50)
    assert schedule == traffic_mod.serve_schedule(t, 50.0)  # the file's one trace
    assert all(512 <= r["prompt_len"] <= 4096 and 8 <= r["budget"] <= 128 for r in schedule)
    # the buckets: about 13% in 1,024 and under, 47% in 2,048, 40% in 4,096; seven
    # prompts in eight longer than the window
    lengths = [r["prompt_len"] for r in traffic_mod.serve_schedule(t, 600.0)]
    share = lambda lo, hi: sum(lo < n <= hi for n in lengths) / len(lengths)
    assert 0.09 < share(0, 1024) < 0.17 and 0.42 < share(1024, 2048) < 0.52
    assert 0.35 < share(2048, 4096) < 0.45 and 0.84 < share(1024, 4096) < 0.91
    ids = traffic_mod.prompt_tokens(2 ** 31 + 5, 3, 64, CONFIG["vocab_size"])
    assert ids.min() >= 2 and ids.max() < CONFIG["vocab_size"]


def test_the_cell_reports_the_expert_median_and_its_own_layers():
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    assert (CELL["config"], CELL["traffic"]) == ("mellum2-12b-a2.5b-instruct", "serve_completion")
    e2e = {m["name"] for m in harness.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"req_ms_per_token_p50.moe", "setup_s"}
    layer = {m["name"]: m for m in harness.metrics_for(BENCH, CELL, "per_layer")}
    assert OWN <= set(layer)
    assert len(BENCH["per_layer"]) <= 128
    assert {"prefill_device_share.moe", "prefill_program_mean_ms.moe", "decode_program_mean_ms.moe",
            "paged_attn_share.moe", "kv_live_block_share.moe", "state_write_mean_ms",
            "moe_held_matmul_share", "moe_held_prefill_load_max_over_mean",
            "hbm_peak_GB.serve.moe", "device_idle_share.serve.moe", "queue_wait_mean_ms.moe",
            "slot_occupancy_mean.moe"} <= set(layer)
    # nothing of another family's shapes: Laguna's own patterns name 72 and 48 heads
    assert not set(layer) & {"swa_decode_attn_share", "swa_prefill_attn_share",
                             "moe_held_matmul_roofline.laguna", "moe_held_touched_share.laguna"}
    for name in OWN:
        assert layer[name]["moves"] == "req_ms_per_token_p50.moe"
        assert layer[name]["workloads"] == ["mellum_serve_completion"]
        harness.metric_spec(name)  # its file is there and names a file that is
    # the new entries stand at the END of their lists
    assert BENCH["configs"][-1]["name"] == "mellum2-12b-a2.5b-instruct"
    assert BENCH["workloads"][-1] is CELL
    assert {m["name"] for m in BENCH["per_layer"][-len(OWN):]} == OWN


def test_operations_and_bytes_against_a_count_by_hand():
    # one (token, expert) pair: gate and up 2304 -> 896 each, down 896 -> 2304
    by_hand = 2 * (2304 * 896 + 2304 * 896 + 896 * 2304)
    assert flops_moe.expert_layer(CONFIG, 1) == by_hand == 2 * (2304 * 1792 + 896 * 2304)
    assert flops_moe.expert_layer(CONFIG, 1) == 12386304
    # a prompt of 2,000 tokens, 8 experts a token; the layer's two events share it
    assert flops_moe.expert_layer(CONFIG, 16000) == 16000 * by_hand
    assert 2 * flops_moe.expert_matmul_call(CONFIG, 16000) == flops_moe.expert_layer(CONFIG, 16000)
    # the issue's 99 MFLOP a row a layer in eight experts
    assert flops_moe.expert_layer(CONFIG, 8) == pytest.approx(99.1e6, rel=1e-3)
    # an expert's three matrices at 2 bytes: 12.4 MB
    assert kernel_bytes.moe_expert_matmul(CONFIG, TRAFFIC, 1) == 3 * 2304 * 896 * 2 == 12386304
    # a ring row: K and V of 4 heads of 128 at 2 bytes
    assert kernel_bytes_window.swa_decode_attention(CONFIG, TRAFFIC, 1) == 4 * 128 * 2 * 2
    # the window of 1,024 over 4,096 positions: a triangle, then 1,024 keys a query
    pairs = 1024 * 1025 // 2 + 3072 * 1024
    assert flops_window.window_pairs(4096, 1024) == pairs
    assert flops_window.windowed_attention(CONFIG, 32, 4096) == 4 * 128 * 32 * pairs


def _ctx(ops, histograms=None, before=None, after=None, kind="TPU v5 lite"):
    measured = harness.Measured(
        attempted=1, failed=0, correct=True,
        values={"trace_mean." + name: s / c for name, (s, c) in (histograms or {}).items()},
        counters_before=before or {}, counters_after=after or {},
        trace=None if ops is None else {"busy_s": 1.0, "op_seconds": ops})
    return {"measured": measured, "config": CONFIG, "device": {"kind": kind}, "traffic": TRAFFIC,
            "peaks": PEAKS}


def _texts(which, start):
    return [t for t in TEXTS[which] if t.startswith(start)]


def _call(which, start, shape):
    return next(t for t in _texts(which, start) if shape in t)


def test_patterns_select_their_kernels_in_recorded_texts():
    rx = {name: re.compile(harness.metric_spec(name)["pattern"]) for name in OWN
          if "pattern" in harness.metric_spec(name)}
    assert set(rx) == {"moe_prefill_matmul_share.mellum", "moe_prefill_matmul_roofline.mellum",
                       "swa_prefill_attn_roofline.mellum"}
    hits = lambda name, texts: [t for t in texts if rx[name].search(t)]
    decode, prefill = TEXTS["decode"], TEXTS["prefill"]
    # the grouped matmul: a step's calls have 256 rows, a prefill's any other count
    step_calls = _texts("decode", "%moe_expert_matmul")
    assert len(step_calls) == 4 and all("[256," in t for t in step_calls)  # distinct texts: 2 x (scan body, full layer)
    prefill_calls = hits("moe_prefill_matmul_share.mellum", prefill)
    assert sorted(prefill_calls) == sorted(_texts("prefill", "%moe_expert_matmul"))
    assert {re.search(r"= bf16\[(\d+),", t).group(1) for t in prefill_calls} == {"32768", "8192"}
    assert hits("moe_prefill_matmul_roofline.mellum", prefill) == prefill_calls
    # the flash kernel: windowed (ONE result, head-major) in the buckets past the
    # window; causal (two results, in place) for the full layers and, in a
    # bucket no longer than the window, for the sliding layers too: not counted
    windowed = hits("swa_prefill_attn_roofline.mellum", prefill)
    assert windowed and all(re.search(r"= bf16\[32,4096,128\]", t) for t in windowed)
    causal = [t for t in _texts("prefill", "%flash_attention") if t not in windowed]
    assert causal and all(re.search(r"= \(bf16\[1,(4096|1024),4096\]", t) for t in causal)
    for name in rx:  # no pattern of a prefill's kernels matches anything of a step
        assert not hits(name, decode), name
    # the shared entries the cell joins read it as they are: every grouped matmul
    # of both programs, and both kinds' paged calls (told apart, were there room
    # for the metrics, by the block table each carries: 8 blocks a ring, 33 a slot)
    every = re.compile(harness.metric_spec("moe_held_matmul_share")["pattern"])
    assert [t for t in decode if every.search(t)] == step_calls
    paged = re.compile(harness.metric_spec("paged_attn_share.moe")["pattern"])
    calls = [t for t in decode if paged.search(t)]
    assert sorted(calls) == sorted(_texts("decode", "%paged_attention"))
    assert any("s32[32,8]" in t for t in calls) and any("s32[32,33]" in t for t in calls)
    assert all(("s32[32,8]" in t) != ("s32[32,33]" in t) for t in calls)


def test_the_new_readers_read_what_their_files_say():
    gate_up = _call("prefill", "%moe_expert_matmul", "[32768,1792]")
    down = _call("prefill", "%moe_expert_matmul", "[32768,2304]")
    step = _call("decode", "%moe_expert_matmul", "[256,1792]")
    ops = [(gate_up, 6e-3), (down, 4e-3), (step, 1e-3)] * 2
    hist = {"serve_moe_prefill_pairs": (2 * 30000.0, 2)}
    got = kernel_flops_of.read(harness.metric_spec("moe_prefill_matmul_roofline.mellum"),
                               _ctx(ops, hist))
    # two layers' pairs x 12.39 MFLOP a pair over their 20 ms, of 197 TFLOP/s
    assert got == pytest.approx(100 * 2 * 30000 * 12386304 / 20e-3 / 197e12)
    assert trace_share.read(harness.metric_spec("moe_prefill_matmul_share.mellum"),
                            _ctx(ops)) == pytest.approx(100 * 20e-3)
    long = _call("prefill", "%flash_attention", "bf16[32,4096,128]")
    ops = [(long, 2e-3), (long, 2e-3)]
    got = kernel_flops_roofline.read(harness.metric_spec("swa_prefill_attn_roofline.mellum"),
                                     _ctx(ops))
    assert got == pytest.approx(100 * 2 * 4 * 128 * 32 * 3670528 / 4e-3 / 197e12)
    # the registry's side: the new histogram and the two counters of the windowed forward
    family = lambda visited, skipped: {"serve_engine_window_key_blocks": {"series": [
        {"labels": {"blocks": "visited"}, "value": visited},
        {"labels": {"blocks": "skipped"}, "value": skipped}]}}
    got = registry_delta.read(harness.metric_spec("swa_window_blocks_visited_share.mellum"),
                              _ctx(None, before=family(60.0, 0.0), after=family(60.0 + 84, 36.0)))
    assert got == pytest.approx(70.0)  # 6 layers x 14 of 20 at 4,096
    hist = lambda name, s, c: {name: {"series": [{"labels": {}, "value": {"sum": s, "count": c}}]}}
    assert histogram_mean.read(
        harness.metric_spec("moe_prefill_rows_per_expert.mellum"),
        _ctx(None, after=hist("serve_moe_prefill_rows_per_expert", 750.0, 3))) == pytest.approx(250.0)
    pads = {"serve_pad_tokens_total": {"series": [{"labels": {}, "value": 300.0}]},
            "serve_engine_prefill_tokens_total": {"series": [{"labels": {}, "value": 700.0}]}}
    assert registry_delta.read(harness.metric_spec("prefill_pad_share.mellum"),
                               _ctx(None, after=pads)) == pytest.approx(30.0)


@pytest.mark.parametrize("name", sorted(OWN))
def test_a_metric_with_nothing_to_read_is_left_out(name):
    """On the parent's program (no such kernel call, histogram or counter) and
    without a trace every new metric returns nothing and does not raise (a
    share of a trace in which none of its operations ran reads 0)."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    other = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 1e-3)]
    assert harness.read_metric(entry, {**_ctx(None), "cell": CELL}) is None
    assert harness.read_metric(entry, {**_ctx(other), "cell": CELL}) in (None, 0.0)


FAULTS = {"NoWindowMask": {"_swa_prefill"}, "ClippedRing": {"_ring_row"},
          "PlainFullRope": {"_rotate_full"}, "NoTopkRenorm": {"_ffn"}, "NoQkNorm": {"_qkv"},
          "Fp8Ring": {"_rounded", "write_state", "decode"}}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_faults_class_differs_from_the_model_in_the_one_place_it_names(fault):
    """Each class overrides the one method of its mechanism and nothing else,
    builds from the cell's own file, and at a tiny size of the file's shape
    moves what it says it moves: a prefill's logits where the fault is in the
    prompt's pass, the ring's row or its rounding where it is in the decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.tests import planted_faults_completion as planted
    from chipbench.tests.planted_faults_window import _fp8
    from moolib_tpu.models.swa_moe import SlidingGqaMoELM, tiny_mellum_config

    cls = getattr(planted, fault)
    assert issubclass(cls, SlidingGqaMoELM) and cls is not SlidingGqaMoELM
    assert {k for k in vars(cls) if not k.startswith("__")} == FAULTS[fault]
    built = cls.from_config(CONFIG, max_len=TRAFFIC["positions_per_slot"],
                            **CONFIG["uses"]["serve"])
    assert type(built) is cls and built.runs == (3, 3, 0) and built.qk_norm
    sound = SlidingGqaMoELM.from_config(tiny_mellum_config(), dtype=jnp.float32, max_len=64)
    faulty = cls.from_config(tiny_mellum_config(), dtype=jnp.float32, max_len=64)
    if fault == "ClippedRing":
        pos = jnp.arange(20)
        np.testing.assert_array_equal(faulty._ring_row(pos)[:8], sound._ring_row(pos)[:8])
        assert np.all(np.asarray(faulty._ring_row(pos)[8:]) == 7)
        return
    if fault == "Fp8Ring":
        x = jnp.asarray([1.0, 1.06, 3.3, -0.3], jnp.float32)
        assert np.any(np.asarray(_fp8(x)) != np.asarray(x))
        np.testing.assert_array_equal(_fp8(_fp8(x)), _fp8(x))
        return
    params = jax.jit(sound.init)(jax.random.key(3))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 384, 40), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, got = jax.jit(sound.logits)(params, toks), jax.jit(faulty.logits)(params, toks)
    assert float(jnp.max(jnp.abs(want - got))) > 0.05
    if fault == "NoWindowMask":  # the first window's positions see the same keys either way
        np.testing.assert_allclose(got[:8], want[:8], atol=2e-4)
