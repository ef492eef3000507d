"""What PR 45 added to the benchmark: the byte count of the paged
decode-attention kernel and the metric ``paged_attn_roofline`` that reads
it, against the HLO texts of the kernel before (per-head, VPU: two results)
and after (grouped, MXU: one) and against a program that has neither."""

import pytest

from chipbench import harness, kernel_bytes_paged
from chipbench.readers import kernel_roofline_of

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH_DIR, "configs", "solar-open2-250b.json")
TRAFFIC = harness.load_json(harness.BENCH_DIR, "traffic", "serve_longgen.json")
TEXTS = harness.load_json(harness.BENCH_DIR, "tests", "data", "hybrid_hlo_texts.json")

# The kernel's operation as the parent's program names it (recorded, PR 41)
# and as this PR's does: one float32 result [slots, heads, head size].
VPU_CALL = next(t for t in TEXTS["decode"] if t.startswith("%paged_attention"))
MXU_CALL = ("%paged_attention.1 = f32[64,64,128]{2,1,0:T(8,128)S(1)} custom-call(%sort.38, %bitcast, "
            "%copy.33, %copy-done.11, %pad, /*index=5*/%bitcast.22, %bitcast.23), "
            'custom_call_target="tpu_custom_call"')
CONSUMER = "%slice.4 = f32[64,64,128]{2,1,0} slice(%paged_attention.1), slice={[0:64], [0:64], [0:128]}"
WRITE = "%fusion.7 = bf16[3073,128,8,128]{3,2,1,0} fusion(%paged_attention_pool), kind=kLoop"


def _ctx(ops, live_share):
    values = {} if live_share is None else {"trace_mean.serve_engine_kv_live_share": live_share}
    measured = harness.Measured(
        attempted=1, failed=0, correct=True, values=values,
        trace=None if ops is None else {"busy_s": 1.0, "op_seconds": ops})
    return {"measured": measured, "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "traffic": TRAFFIC}


def test_the_metric_is_the_long_generation_cells_and_moves_its_median():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "paged_attn_roofline")
    assert entry == {"name": "paged_attn_roofline", "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels, serving",
                     "moves": "req_ms_per_token_p50.moe", "workloads": ["solar_serve_longgen"]}
    # (it was the last entry when PR 45 appended it; later PRs appended theirs behind it)
    share = harness.metric_spec("paged_attn_share.moe")
    assert harness.metric_spec("paged_attn_roofline")["pattern"] == share["pattern"]


def test_bytes_the_kernel_must_move():
    # a block: 128 tokens x 8 K/V heads x 128 x 2 bytes, K and V: 512 KB; the pool 3,072 of them
    assert kernel_bytes_paged.paged_attention(CONFIG, TRAFFIC, 1 / 3072) == pytest.approx(524288)
    # the ledger's live share of PR 44: 183 blocks, 95.9 MB, 0.117 ms at 819 GB/s
    assert kernel_bytes_paged.paged_attention(CONFIG, TRAFFIC, 0.0596) == pytest.approx(95.99e6, rel=1e-3)


@pytest.mark.parametrize("call,seconds,expected", [
    (VPU_CALL, 0.49e-3, 23.9),  # the parent at the ledger's 0.49 ms a call
    (MXU_CALL, 0.15e-3, 78.1),
])
def test_the_roofline_counts_the_kernels_own_events_on_either_side(call, seconds, expected):
    spec = harness.metric_spec("paged_attn_roofline")
    ops = [(call, seconds), (CONSUMER, 1e-3), (WRITE, 1e-3)] * 5
    got = kernel_roofline_of.read(spec, _ctx(ops, 0.0596))
    assert got == pytest.approx(100 * 0.0596 * 3072 * 524288 / 819e9 / seconds)
    assert got == pytest.approx(expected, abs=0.1)


@pytest.mark.parametrize("ops,live_share", [
    (None, 0.0596),  # not traced
    ([(CONSUMER, 1e-3), (WRITE, 1e-3)], 0.0596),  # a program without the kernel
    ([(MXU_CALL, 0.15e-3)], None),  # no such histogram: a runner that hands no trace_mean.*
])
def test_a_roofline_with_nothing_to_read_is_left_out(ops, live_share):
    spec = harness.metric_spec("paged_attn_roofline")
    assert kernel_roofline_of.read(spec, _ctx(ops, live_share)) is None
