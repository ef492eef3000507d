"""Each kind of reader on made-up measurements."""

import pytest

from chipbench import harness
from chipbench.readers import (gauge_mean, histogram_mean, memory_peak, mfu, quantile,
                               ratio, trace_share, trace_value, value)


def hist(count, total):
    return {"kind": "histogram", "series": [
        {"labels": {"phase": "queue"}, "value": {"count": count, "sum": total, "buckets": []}}]}


@pytest.fixture
def ctx():
    m = harness.Measured(
        attempted=4, failed=0, correct=True,
        values={"tokens": 8192.0 * 10, "window_s": 2.0, "n_layer": 4, "seq_len": 2048},
        lists={"x": [1.0, 2.0, 3.0, 4.0, 5.0]},
        counters_before={"h": hist(10, 1.0)},
        counters_after={"h": hist(30, 5.0)},
        samples={"g": [0.5, 0.7]},
        trace={"busy_s": 2.0, "window_s": 4.0, "collective_exposed_s": 0.4,
               "op_seconds": [("%fusion.1 = bf16[8]{0} fusion(%a)", 0.5),
                              ("%custom-call.2 = bf16[8]{0} custom-call(%b)", 1.0)]})
    return {"measured": m, "device": {"kind": "TPU v5e", "count": 1, "memory_peak_bytes": 2.5e9},
            "config": harness.load_json(harness.BENCH_DIR, "configs", "cerebras-gpt-1.3b.json"),
            "peaks": harness.load_json(harness.BENCH_DIR, "peaks.json")}


@pytest.mark.parametrize("reader,spec,want", [
    (value, {"key": "window_s", "scale": 10.0}, 20.0),
    (value, {"key": "absent"}, None),
    (ratio, {"num": "tokens", "den": "window_s"}, 40960.0),
    (quantile, {"list": "x", "q": 0.5}, 3.0),
    (quantile, {"list": "x", "q": 0.9}, 4.6),
    (quantile, {"list": "absent", "q": 0.9}, None),
    (histogram_mean, {"metric": "h", "labels": {"phase": "queue"}, "scale": 1000.0}, 200.0),
    (histogram_mean, {"metric": "absent"}, None),
    (gauge_mean, {"gauge": "g", "scale": 100.0}, 60.0),
    (trace_share, {"pattern": r"custom-call\("}, 50.0),
    (trace_value, {"figure": "idle_share"}, 50.0),
    (trace_value, {"figure": "collective_exposed_share"}, 10.0),
    (memory_peak, {}, 2.5),
    (mfu, {"flops": "gpt_train_flops_per_token"}, 100.0 * 1_926_230_016 * 40960 / 197e12),
])
def test_reader(ctx, reader, spec, want):
    got = reader.read(spec, ctx)
    assert got is None if want is None else got == pytest.approx(want)


def test_trace_readers_return_nothing_without_a_trace(ctx):
    ctx["measured"].trace = None
    assert trace_share.read({"pattern": "x"}, ctx) is None
    assert trace_value.read({"figure": "idle_share"}, ctx) is None


def test_unknown_device_kind_is_an_error(ctx):
    ctx["device"]["kind"] = "TPU v9"
    with pytest.raises(KeyError):
        mfu.read({"flops": "gpt_train_flops_per_token"}, ctx)


# Operation texts of ``lm_serve_steady``'s decode step as the chip's trace names
# them (TPU v5 lite, jax 0.9): the kernel's custom call under its scope's name,
# the in-place K/V write and a q/k/v slice, which the shapes of PR 23's pattern
# selected in the kernel's place, and the latent layout's kernel.
PAGED_KERNEL = ("%paged_attention.5 = (f32[32,1,16,128]{3,2,1,0:T(8,128)}, f32[32,1,16,128]{3,2,1,0:T(8,128)}) "
                "custom-call(%order, %count, %tables, %lengths, %q, %pool_k, %pool_v), "
                'custom_call_target="tpu_custom_call"')
KV_WRITE = "%fusion.41 = bf16[2049,16,16,128]{3,2,1,0:T(8,128)(2,1)} fusion(%pool, %k, %at), kind=kLoop"
QKV_SLICE = "%slice.7 = bf16[32,16,128]{2,1,0:T(8,128)(2,1)} slice(%fusion.12), slice={[0:32], [0:16], [0:128]}"
CONSUMER = "%fusion.44 = bf16[32,2048]{1,0} fusion(%paged_attention.5, %w), kind=kOutput"
LATENT_KERNEL = "%mla_decode_attention.2 = f32[32,32,512]{2,1,0} custom-call(%q, %pool)"


def test_paged_attn_share_reads_the_kernel_by_its_name(ctx):
    spec = harness.load_json(harness.BENCH_DIR, "metrics", "paged_attn_share.json")
    ctx["measured"].trace = {"busy_s": 4.0, "op_seconds": [
        (PAGED_KERNEL, 0.6), (KV_WRITE, 0.2), (QKV_SLICE, 0.1), (CONSUMER, 1.0), (LATENT_KERNEL, 0.3)]}
    assert trace_share.read(spec, ctx) == pytest.approx(15.0)
    # an older program, or the latent layout alone: the kernel is not there, and 0 is what it took
    ctx["measured"].trace["op_seconds"] = [(KV_WRITE, 0.2), (LATENT_KERNEL, 0.3)]
    assert trace_share.read(spec, ctx) == 0.0
    latent = harness.load_json(harness.BENCH_DIR, "metrics", "mla_decode_attn_share.json")
    assert trace_share.read(latent, ctx) == pytest.approx(7.5)
