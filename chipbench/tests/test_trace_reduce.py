"""The trace reducer, on the one recorded chip trace the repository keeps
(``data/sample.xplane.pb.gz``: ``tools/record_trace.py`` on a TPU v5 lite,
three calls of a small step with 20 ms sleeps between) and on made-up events."""

import os

import pytest

from chipbench import trace_reduce as tr

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "sample.xplane.pb.gz")


@pytest.fixture(scope="module")
def sample():
    return tr.extract(tr.load(SAMPLE))


def test_sample_has_one_device_and_the_benchmarks_spans(sample):
    assert list(sample["devices"]) == [0]
    assert len(sample["devices"][0]) == 42  # 14 operations a call, 3 calls
    names = {s[0] for s in sample["spans"]}
    assert names == {"chipbench.sample_step", "chipbench.sample_sleep"}


def test_sample_busy_idle_and_top_operation(sample):
    s = tr.reduce(sample, n_devices=1)
    assert s["devices"] == 1
    assert s["busy_s"] == pytest.approx(168.652e-6, rel=1e-6)
    assert s["window_s"] == pytest.approx(43.931211e-3, rel=1e-6)
    assert 0 < s["busy_s"] < s["window_s"]
    top, seconds = s["top_ops"][0]
    assert top == "convolution_tanh_fusion_bf16_1024_1024_"
    assert seconds == pytest.approx(138.84e-6, rel=1e-3)
    # the loop's own time is what its body does not cover: next to nothing
    assert dict(s["top_ops"])["while_bf16_1024_1024_"] < 1e-6
    # self times add up to busy time: nothing is counted twice
    assert sum(v for _k, v in s["top_ops"]) == pytest.approx(s["busy_s"], rel=1e-6)
    assert s["collective_exposed_s"] == 0.0
    # the device sat idle while the host slept
    assert s["idle_gaps"][0][0] == "sample_sleep"
    assert s["idle_gaps"][0][1] == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-3)


def test_pattern_seconds_selects_by_hlo_text(sample):
    s = tr.reduce(sample, n_devices=1)
    matmul = tr.pattern_seconds(s, r"convolution_tanh_fusion")
    assert matmul == pytest.approx(138.84e-6, rel=1e-3)
    assert tr.pattern_seconds(s, r"no_such_operation") == 0.0


def test_op_key():
    assert tr.op_key("%convert.3 = f32[2048,16,16,128]{3,2,1,0} convert(%p)") == \
        "convert_f32_2048_16_16_128_"
    assert tr.op_key("%all-reduce-start.1 = (bf16[8,4]{1,0}) all-reduce-start(%x)") == \
        "all_reduce_start_bf16_8_4_"
    # several results: named by the largest (here the matmul's product, not the
    # LayerNorm reductions fused beside it)
    assert tr.op_key("%multiply_reduce_fusion.21 = (f32[2048]{0:T(1024)S(1)}, f32[4,2048]{1,0:T(4,128)S(1)}, "
                     "bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)S(1)}) fusion(%a, %b), kind=kOutput") == \
        "multiply_reduce_fusion_bf16_4_2048_2048_"
    assert tr.op_key("%while = (s32[]{:T(128)}, bf16[8,8]{1,0}) while((s32[]) %t)") == "while_bf16_8_8_"
    assert tr.op_key("no hlo here") == "no_hlo_here"


def test_self_time_window_collectives_and_gap_owner():
    ops = [
        ("%while.1 = s32[] while(%t)", 100.0, 100.0),            # spans the next two
        ("%fusion.1 = bf16[8,8]{1,0} fusion(%a)", 100.0, 40.0),
        ("%all-reduce.2 = bf16[8,8]{1,0} all-reduce(%b)", 150.0, 30.0),
        ("%fusion.2 = bf16[8,8]{1,0} fusion(%c)", 400.0, 100.0),  # half outside
    ]
    spans = [
        ("chipbench.trace_window", 0.0, 450.0),
        ("chipbench.outer", 0.0, 450.0),
        ("chipbench.inner", 250.0, 100.0),
    ]
    s = tr.reduce({"devices": {0: ops}, "spans": spans}, n_devices=1)
    assert s["window_s"] == pytest.approx(450e-9)
    assert s["busy_s"] == pytest.approx((100 + 50) * 1e-9)
    keys = dict(s["top_ops"])
    assert keys["while_s32__"] == pytest.approx(30e-9)          # 100 - 40 - 30
    assert keys["fusion_bf16_8_8_"] == pytest.approx(90e-9)     # 40 + 50 (clipped)
    assert s["collective_exposed_s"] == pytest.approx(30e-9)
    gaps = dict(s["idle_gaps"])
    assert gaps["outer"] == pytest.approx(100e-9)               # 0..100
    assert gaps["inner"] == pytest.approx(200e-9)               # 200..400, midpoint 300
    edge, count, seconds = s["idle_gap_sizes"][0]                # both gaps are under 10 us
    assert (edge, count) == (1e-5, 2) and seconds == pytest.approx(300e-9)
    assert sum(c for _e, c, _s in s["idle_gap_sizes"]) == 2
    assert tr.reduce({"devices": {0: []}, "spans": []}, n_devices=1) is None
