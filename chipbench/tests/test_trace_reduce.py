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


# Instruction texts of ``lm_train_dp4``'s step as this compiler writes them
# (compiled for a described v5e:2x2, as ``tests/test_chip_compile.py`` does;
# layouts' tilings and the long ``backend_config`` cut): a reduce-scatter is a
# fusion that CALLS ``all-reduce-scatter.N``, with no ``(`` after the name.
REDUCE_SCATTER_HEAD = (
    '%fusion.10 = f32[512,50257]{0,1:T(8,128)} fusion(%fusion.18), kind=kCustom, '
    'calls=%all-reduce-scatter.4, metadata={op_name="jit(step)/transpose(jvp(TransformerLM))'
    '/lm_head/dot_general" stack_frame_id=124}, backend_config={"flag_configs":[],'
    '"collective_algorithm_config":{"emitter":"SingleInputAllReduceScatterFusion"}}')
REDUCE_SCATTER_QKV = (
    '%fusion.9 = bf16[2048,1536]{1,0:T(8,128)(2,1)} fusion(%fusion.113), kind=kCustom, '
    'calls=%all-reduce-scatter.3, metadata={op_name="jit(step)/transpose(jvp(TransformerLM))'
    '/block0/qkv/dot_general" stack_frame_id=41}')
ALL_TO_ALL = ('%all-to-all = bf16[4,2048,4,512]{1,3,0,2:T(8,128)(2,1)S(1)} all-to-all(%copy.112), '
              'channel_id=28, replica_groups=[1,4]<=[4], dimensions={2}')
# compute that overlaps a gather, and a plain fusion: neither holds the line for a collective
OVERLAPPED = ('%fusion.222 = (bf16[4,2048,6144]{2,1,0:T(8,128)(2,1)}, bf16[512,2048]{0,1:T(8,128)(2,1)S(1)}) '
              'fusion(%p.1, %p.2), kind=kCustom, calls=%async_collective_fusion.222')
PLAIN = '%fusion.113 = bf16[2048,6144]{1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput, calls=%fused_computation.97'


@pytest.mark.parametrize("text,collective", [
    (REDUCE_SCATTER_HEAD, True), (REDUCE_SCATTER_QKV, True), (ALL_TO_ALL, True),
    ("%all-gather-done.3 = bf16[50257,2048]{1,0} all-gather-done(%all-gather-start.3)", True),
    ("%reduce-scatter.1 = f32[512]{0} reduce-scatter(%x), dimensions={0}", True),
    (OVERLAPPED, False), (PLAIN, False),
    ("%fusion.7 = f32[8]{0} fusion(%all-reduce.2), kind=kLoop, calls=%fused_computation.1", False),
])
def test_collective_pattern_on_recorded_texts(text, collective):
    assert bool(tr.COLLECTIVE.search(text)) is collective


def test_a_fusion_that_calls_a_reduce_scatter_counts_as_exposed():
    ops = [(REDUCE_SCATTER_HEAD, 0.0, 500.0), (OVERLAPPED, 500.0, 300.0),
           (REDUCE_SCATTER_QKV, 800.0, 100.0), (ALL_TO_ALL, 900.0, 50.0), (PLAIN, 950.0, 50.0)]
    s = tr.reduce({"devices": {0: ops}, "spans": [("chipbench.trace_window", 0.0, 1000.0)]}, 1)
    assert s["collective_exposed_s"] == pytest.approx(650e-9)   # 6.0% of the window before: the all-to-all
    assert s["busy_s"] == pytest.approx(1000e-9)
    assert dict(s["top_ops"])["fusion_f32_512_50257_"] == pytest.approx(500e-9)
