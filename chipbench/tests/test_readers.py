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
