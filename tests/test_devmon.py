"""Device performance plane (telemetry.devmon + CohortAggregator.step_skew):
recompile detection, memory gauges, XLA step cost / MFU and cohort straggler
attribution."""

import pytest

from moolib_tpu import telemetry
from moolib_tpu.telemetry import devmon


@pytest.fixture(autouse=True)
def _devmon_state():
    devmon.reset_for_tests()
    yield
    devmon.reset_for_tests()


def _events(name):
    return [
        (n, args)
        for _, n, args in telemetry.get_flight_recorder().events()
        if n == name
    ]


def _counter(name):
    return telemetry.get_registry().counter_values().get(name, 0.0)


# --------------------------------------------------------------- recompiles
def test_recompile_detector_fires_once_on_shape_change():
    import jax
    import jax.numpy as jnp

    telemetry.get_flight_recorder().clear()
    f = devmon.instrument_jit(jax.jit(lambda x: x * 2 + 1), "t.shapechange")
    a = jnp.ones((4, 4), jnp.float32)
    b = jnp.ones((8, 4), jnp.float32)
    f(a)
    f(a)  # cache hit: no new signature
    assert _counter('jit_compiles_total{fn="t.shapechange"}') == 1
    assert not _events("devmon.recompile")
    f(b)  # recompile: exactly one event carrying the diff
    evs = _events("devmon.recompile")
    assert len(evs) == 1
    assert evs[0][1]["fn"] == "t.shapechange"
    assert "(4, 4)/float32 -> (8, 4)/float32" in evs[0][1]["diff"]
    assert devmon.last_recompile("t.shapechange") == evs[0][1]["diff"]
    f(a)  # returning to a SEEN signature is a jit-cache hit: silent
    f(b)
    assert len(_events("devmon.recompile")) == 1
    assert _counter('jit_compiles_total{fn="t.shapechange"}') == 2
    assert _counter('jit_recompiles_total{fn="t.shapechange"}') == 1


def test_stable_loop_is_silent():
    import jax
    import jax.numpy as jnp

    telemetry.get_flight_recorder().clear()
    f = devmon.instrument_jit(jax.jit(lambda x: x + 1), "t.stable")
    x = jnp.zeros((3,), jnp.float32)
    for _ in range(5):
        x = f(x)
    assert _counter('jit_compiles_total{fn="t.stable"}') == 1
    assert not _events("devmon.recompile")
    assert devmon.last_recompile("t.stable") is None


def test_instrument_jit_forwards_attributes_and_is_idempotent():
    import jax

    f = jax.jit(lambda x: x)
    g = devmon.instrument_jit(f, "t.fwd")
    assert devmon.instrument_jit(g, "other") is g
    # AOT surface must survive the wrap (tests elsewhere rely on it).
    assert callable(g.lower)


def test_observe_call_never_raises():
    class Unflattenable:
        __slots__ = ()

    devmon.observe_call("t.closure", (object(),), {"k": Unflattenable()})
    devmon.observe_call("t.closure", (object(),))


# ------------------------------------------------------------------- memory
def test_memory_gauges_populate_on_any_backend():
    out = devmon.sample_memory()
    if not out:
        pytest.skip("no device memory_stats and no /proc on this platform")
    snap = telemetry.get_registry().snapshot()
    labels = {
        s["labels"]["device"] for s in snap["hbm_bytes_in_use"]["series"]
    }
    for label, row in out.items():
        assert label in labels
        assert row["bytes_in_use"] > 0
    # Watermark tracking survives a second (possibly lower) sample.
    devmon.sample_memory()
    assert "memory" in devmon.summary_text()


def test_hbm_pressure_warns_once_per_excursion(monkeypatch):
    telemetry.get_flight_recorder().clear()
    monkeypatch.setenv("MOOLIB_DEVMON_HBM_WARN_FRACTION", "0.000001")
    out = devmon.sample_memory()
    if not any(r.get("bytes_limit", 0) > 0 for r in out.values()):
        pytest.skip("no memory limit reading on this platform")
    devmon.sample_memory()  # still over: no second event
    evs = _events("devmon.hbm_pressure")
    labels = {e[1]["device"] for e in evs}
    assert len(evs) == len(labels)  # at most one per device
    monkeypatch.setenv("MOOLIB_DEVMON_HBM_WARN_FRACTION", "2.0")
    devmon.sample_memory()  # drops back under: re-armed
    monkeypatch.setenv("MOOLIB_DEVMON_HBM_WARN_FRACTION", "0.000001")
    devmon.sample_memory()
    assert len(_events("devmon.hbm_pressure")) >= len(evs) + 1


# ------------------------------------------------------------- step cost/MFU
def test_step_cost_counts_flops_for_lm_like_step():
    import jax
    import jax.numpy as jnp

    def step(w, x):
        return jnp.tanh(x @ w).sum()

    j = jax.jit(step)
    w = jnp.ones((64, 64), jnp.float32)
    x = jnp.ones((8, 64), jnp.float32)
    sc = devmon.step_cost("t.lmstep", j, w, x)
    if sc is None:
        pytest.skip("cost analysis unavailable on this backend")
    # The matmul alone is 2*8*64*64 = 65536 flops.
    assert sc.flops >= 2 * 8 * 64 * 64
    assert sc.bytes_accessed > 0
    # Golden sanity bound: a dense step's bytes/flop sits well inside
    # (0.001, 100) — orders of magnitude outside means the fields swapped.
    bpf = sc.bytes_accessed / sc.flops
    assert 1e-3 < bpf < 100
    # Cached per signature: same call returns the same object, no re-lower.
    assert devmon.step_cost("t.lmstep", j, w, x) is sc
    snap = telemetry.get_registry().snapshot()
    assert any(
        s["labels"]["fn"] == "t.lmstep" and s["value"] > 0
        for s in snap["step_flops"]["series"]
    )


_SCHEDULE = """\
%fused_computation.1 (p: bf16[2048,2048]) -> bf16[2048,8192] {
  %ag = bf16[2048,8192]{1,0} all-gather(%p), channel_id=1, dimensions={1}
}

%async_collective_fusion.7 (p0: bf16[8320,2048], p1: bf16[4,2048,2048]) -> (f32[2048,50257], bf16[2080,2048]) {
  %convolution.1 = f32[2048,50257]{0,1} convolution(%p1, %p1), window={size=4}
  %reduce-scatter.3 = bf16[2080,2048]{1,0:T(8,128)(2,1)S(1)} reduce-scatter(%p0), channel_id=2, dimensions={0}
}

%fused_computation.2 (p: bf16[8320,2048]) -> bf16[2080,2048] {
  %reduce-scatter.4 = bf16[2080,2048]{1,0} reduce-scatter(%p), channel_id=2, dimensions={0}
}

%all-reduce-scatter.4.clone (input: f32[2048,50257]) -> f32[512,50257] {
  %all-reduce.9 = f32[2048,50257]{0,1:T(8,128)} all-reduce(%input), channel_id=3, to_apply=%add
  ROOT %dynamic-slice.1 = f32[512,50257]{0,1} dynamic-slice(%all-reduce.9, %i, %z)
}

ENTRY %main (a: bf16[512,2048]) -> f32[512,50257] {
  %all-gather.68 = bf16[50257,2048]{1,0:T(8,128)(2,1)} all-gather(%convert.7), channel_id=4, dimensions={1}
  %async-collective-start = (bf16[2048,2048], bf16[2048,8192]) fusion(%a), kind=kCustom, calls=%fused_computation.1
  %fusion.7 = (f32[2048,50257], bf16[2080,2048]) fusion(%b, %c), kind=kOutput, calls=%async_collective_fusion.7
  %async-collective-done.3 = bf16[2080,2048]{1,0} fusion(%fusion.7), kind=kCustom, calls=%fused_computation.2
  %collective-permute-start = (bf16[96,2048], bf16[96,2048]) collective-permute-start(%d), channel_id=5
  %collective-permute-done = bf16[96,2048] collective-permute-done(%collective-permute-start)
  %all-reduce.30 = (f32[2048]{0}, f32[2048]{0}) all-reduce(%e, %f), channel_id=6, to_apply=%add
  ROOT %fusion.10 = f32[512,50257]{0,1} fusion(%fusion.7), kind=kCustom, calls=%all-reduce-scatter.4.clone
}
"""


def test_sync_collectives_counts_what_no_fusion_overlaps():
    """Of a TPU step's collectives, the ones on the operation line: the
    table's gather, the head's all-reduce inside its ``all-reduce-scatter``
    fusion and a pair of vectors' all-reduce, with their own results' bytes.
    Not the gather an ``async-collective-start`` opens, not the
    reduce-scatter a matmul fusion carries and its end closes, not a
    ``-start`` / ``-done`` pair."""
    count, nbytes = devmon.sync_collectives(_SCHEDULE)
    assert count == 3
    assert nbytes == 50257 * 2048 * 2 + 2048 * 50257 * 4 + 2 * 2048 * 4
    assert devmon.sync_collectives("ENTRY %main () -> f32[] {\n  %c = f32[] constant(0)\n}") == (0, 0)


def test_step_cost_reports_the_sync_collectives_of_a_sharded_step():
    """On this backend every collective of a partitioned step is one: the
    sum over four devices shows in ``StepCost.sync_collectives`` and in
    ``program()``, which ``lm.train`` logs and returns."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    x = jax.device_put(jnp.ones((8, 64), jnp.float32), NamedSharding(mesh, P("dp", None)))
    j = jax.jit(lambda x: (x @ x.T).sum(0), out_shardings=NamedSharding(mesh, P()))
    sc = devmon.step_cost("t.sharded", j, x)
    if sc is None:
        pytest.skip("cost analysis unavailable on this backend")
    held, nbytes = sc.sync_collectives
    assert held == sum(sc.collectives.values()) >= 1 and nbytes > 0
    assert sc.program()["sync_collectives"] == held
    assert sc.program()["sync_collective_bytes"] == nbytes


def test_publish_step_finite_mfu_and_roofline():
    cost = devmon.StepCost(flops=1e9, bytes_accessed=1e8)
    out = devmon.publish_step("t.pub", cost, step_seconds=0.01,
                              device_kind="TPU v5 lite")
    assert out is not None
    assert out["mfu"] == pytest.approx(1e9 / 0.01 / 197e12)
    assert out["bound"] in ("memory", "compute")
    snap = telemetry.get_registry().snapshot()
    vals = {
        s["labels"]["fn"]: s["value"] for s in snap["step_mfu"]["series"]
    }
    assert vals["t.pub"] == pytest.approx(out["mfu"])
    # The CPU backend has no peak: no MFU is published for it, against any
    # stand-in number (the process here runs on cpu, so the default kind
    # resolves to it too).
    assert devmon.publish_step("t.cpu", cost, 0.01, device_kind="cpu") is None
    assert devmon.publish_step("t.cpu", cost, 0.01) is None
    assert "t.cpu" not in {
        s["labels"]["fn"]
        for s in telemetry.get_registry().snapshot()["step_mfu"]["series"]
    }
    # Degenerate inputs publish nothing instead of inf/NaN.
    assert devmon.publish_step("t.pub", cost, 0.0, "TPU v4") is None
    assert devmon.publish_step("t.pub", None, 1.0, "TPU v4") is None


def test_peak_tables_unknown_kind_raises():
    assert devmon.peak_flops("TPU v4") == 275e12
    assert devmon.peak_flops("TPU v5 lite") == 197e12
    assert devmon.peak_flops("TPU v5p") == 459e12
    # Substring order matters: "v5e"/"v5p" must not fall through to the
    # bare "v5" (pod) row, and the v6 generation resolves across the
    # spellings device_kind uses ("TPU v6e", "TPU v6 lite").
    assert devmon.peak_flops("TPU v5e") == 197e12
    assert devmon.peak_flops("TPU v6e") == 918e12
    assert devmon.peak_flops("TPU v6 lite") == 918e12
    assert devmon.peak_bandwidth("TPU v5e") == 819e9
    assert devmon.peak_bandwidth("TPU v5p") == 2765e9
    assert devmon.peak_bandwidth("TPU v6e") == 1640e9
    # The CPU has no peak; a kind the table lacks is an error, not a default.
    assert devmon.peak_flops("cpu") is None
    assert devmon.peak_bandwidth("cpu") is None
    for fn in (devmon.peak_flops, devmon.peak_bandwidth):
        with pytest.raises(ValueError, match="not in devmon's peak tables"):
            fn("NVIDIA H100")
    with pytest.raises(ValueError):
        devmon.publish_step(
            "t.unknown", devmon.StepCost(1e9, 1e8), 0.01, device_kind="TPU v9"
        )


def test_roofline_classification():
    # v5e ridge = 197e12 / 819e9 ~ 240 flop/byte: far below is memory-bound,
    # far above compute-bound.
    mem = devmon.roofline(1e6, 1e9, "TPU v5e")
    assert mem["bound"] == "memory"
    comp = devmon.roofline(1e12, 1e6, "TPU v5e")
    assert comp["bound"] == "compute"
    assert comp["roofline_mfu_ceiling"] == 1.0
    assert devmon.roofline(0.0, 1e6, "TPU v5e")["bound"] is None
    # No peaks on the CPU: intensity only, no verdict.
    cpu = devmon.roofline(1e12, 1e6, "cpu")
    assert cpu["bound"] is None and cpu["peak_flops"] is None
    assert cpu["arithmetic_intensity_flop_per_byte"] == pytest.approx(1e6)


# -------------------------------------------------------------- cohort skew
class _FakeRpc:
    def get_name(self):
        return "observer"


def _hist_fam(total, count):
    return {
        "kind": "histogram",
        "help": "",
        "buckets": [0.1, 1.0],
        "series": [
            {"labels": {}, "value": {"buckets": [1, 1, 0], "sum": total,
                                     "count": count}}
        ],
    }


def _peer_row(t, dispatch_sum, count, psum_sum=0.0, psum_count=0.0, steps=None):
    met = {
        "train_step_dispatch_seconds": _hist_fam(dispatch_sum, count),
        "accum_psum_seconds": _hist_fam(psum_sum, psum_count),
    }
    if steps is not None:
        met["train_steps_total"] = {
            "kind": "counter", "help": "",
            "series": [{"labels": {}, "value": steps}],
        }
    return {"time": t, "pid": 1, "metrics": met}


def _agg():
    return telemetry.CohortAggregator(_FakeRpc(), "broker")


def test_step_skew_flags_delayed_peer():
    telemetry.get_flight_recorder().clear()
    agg = _agg()
    fused = {"time": 1.0, "errors": {}, "peers": {
        "fast-1": _peer_row(1.0, dispatch_sum=10.0, count=100),   # 0.1 s/step
        "fast-2": _peer_row(1.0, dispatch_sum=11.0, count=100),
        "slow": _peer_row(1.0, dispatch_sum=40.0, count=100,      # 0.4 + psum
                          psum_sum=10.0, psum_count=100),
    }}
    agg._fused = fused
    out = agg.step_skew(threshold=1.5, sustain=3)
    assert out["straggler"] == "slow"
    assert out["ratio"] > 1.5
    assert out["peers"]["slow"]["psum_seconds"] == pytest.approx(0.1)
    assert not out["sustained"]
    assert not _events("devmon.straggler")
    agg.step_skew(threshold=1.5, sustain=3)
    out = agg.step_skew(threshold=1.5, sustain=3)  # third consecutive flag
    assert out["sustained"]
    evs = _events("devmon.straggler")
    assert len(evs) == 1 and evs[0][1]["peer"] == "slow"
    # Sustained again: announced once per excursion, not per call.
    agg.step_skew(threshold=1.5, sustain=3)
    assert len(_events("devmon.straggler")) == 1
    vals = telemetry.get_registry().snapshot()["cohort_step_skew_ratio"]
    assert vals["series"][0]["value"] == pytest.approx(out["ratio"])


def test_step_skew_single_peer_is_neutral():
    agg = _agg()
    agg._fused = {"time": 1.0, "errors": {}, "peers": {
        "only": _peer_row(1.0, dispatch_sum=10.0, count=10),
    }}
    out = agg.step_skew()
    assert out == {"ratio": 1.0, "peers": {
        "only": {"step_seconds": 1.0, "dispatch_seconds": 1.0,
                 "psum_seconds": 0.0}}, "straggler": None, "sustained": False}


def test_step_skew_uses_window_deltas():
    agg = _agg()
    agg._fused = {"time": 1.0, "errors": {}, "peers": {
        "a": _peer_row(1.0, dispatch_sum=100.0, count=100),  # slow history
        "b": _peer_row(1.0, dispatch_sum=10.0, count=100),
    }}
    agg.step_skew()
    # Peer "a" recovered: the WINDOW delta is 10 steps at 0.1 s/step even
    # though its lifetime mean is still 1.0 s/step.
    agg._fused = {"time": 2.0, "errors": {}, "peers": {
        "a": _peer_row(2.0, dispatch_sum=101.0, count=110),
        "b": _peer_row(2.0, dispatch_sum=11.0, count=110),
    }}
    out = agg.step_skew(threshold=1.5)
    assert out["peers"]["a"]["step_seconds"] == pytest.approx(0.1)
    assert out["straggler"] is None


def test_peer_samples_parity_and_counter_reset():
    from moolib_tpu import autoscaler

    agg = _agg()
    row = _peer_row(100.0, dispatch_sum=1.0, count=10, steps=500.0)
    row["metrics"]["serve_qps"] = {
        "kind": "gauge", "help": "",
        "series": [{"labels": {}, "value": 7.5}],
    }
    agg._fused = {"time": 100.0, "errors": {}, "peers": {"p1": row}}
    (s,) = agg.peer_samples()
    # Parity: the aggregator extracts exactly what sample_from_snapshot does.
    ref = autoscaler.sample_from_snapshot("p1", row)
    for f in ("steps", "serve_qps", "queue_depth", "vbatch_fill",
              "serve_depth", "serve_wait", "slot_occupancy"):
        assert getattr(s, f) == getattr(ref, f)
    assert s.step_rate is None  # first scrape: no delta yet
    # Second scrape: positive rate from the delta.
    row2 = _peer_row(110.0, dispatch_sum=2.0, count=20, steps=600.0)
    agg._fused = {"time": 110.0, "errors": {}, "peers": {"p1": row2}}
    (s2,) = agg.peer_samples()
    assert s2.step_rate == pytest.approx(10.0)
    # Counter reset (peer restarted): fresh baseline, NOT a negative rate.
    row3 = _peer_row(120.0, dispatch_sum=0.1, count=1, steps=50.0)
    agg._fused = {"time": 120.0, "errors": {}, "peers": {"p1": row3}}
    (s3,) = agg.peer_samples()
    assert s3.step_rate is None
    # ... and the reset reading seeds the next delta.
    row4 = _peer_row(130.0, dispatch_sum=0.2, count=2, steps=150.0)
    agg._fused = {"time": 130.0, "errors": {}, "peers": {"p1": row4}}
    (s4,) = agg.peer_samples()
    assert s4.step_rate == pytest.approx(10.0)


def test_peer_samples_prunes_departed_peers():
    agg = _agg()
    agg._fused = {"time": 1.0, "errors": {}, "peers": {
        "p1": _peer_row(1.0, 1.0, 10, steps=100.0),
        "p2": _peer_row(1.0, 1.0, 10, steps=100.0),
    }}
    agg.peer_samples()
    assert set(agg._last_steps) == {"p1", "p2"}
    agg._fused = {"time": 2.0, "errors": {}, "peers": {
        "p1": _peer_row(2.0, 2.0, 20, steps=200.0),
    }}
    agg.peer_samples()
    # A departed peer's baseline must not outlive it (a respawn reusing the
    # name would inherit a stale delta).
    assert set(agg._last_steps) == {"p1"}


# ------------------------------------------------------------------ summary
def test_summary_text_in_dump_diagnostics():
    import io

    devmon.observe_call("t.dump", ((1, 2),))
    buf = io.StringIO()
    telemetry.dump_diagnostics(file=buf)
    out = buf.getvalue()
    assert "devmon (device performance plane)" in out
    assert "t.dump" in out
