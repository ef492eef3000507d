"""Each runner end to end through ``run.main`` at a tiny size on the CPU.

The platform check, the compile cache's placement and the sizes are replaced
here, in the test; the program and the harness get no option for it.  What the
line reports from a CPU is control flow and counts, never a device metric."""

import json

import jax
import pytest

from chipbench import harness
from chipbench import run as bench_run


@pytest.fixture
def tiny(monkeypatch):
    config = harness.load_json(harness.BENCH_DIR, "configs", "cerebras-gpt-1.3b.json")
    config.update(n_embd=64, n_head=4, n_inner=256, vocab_size=97, n_positions=128)
    config["uses"]["train"].update(n_layer=2, attention="dense")
    config["uses"]["serve"].update(n_layer=2)
    # 64 wide, a step of 3e-4 is large against the weights and bfloat16 rounding
    # against the loss: the chip's tolerance is for the published widths
    config["tolerance"]["train_loss_after_updates_rel"] = 2e-3
    traffic = {}
    for name in ("train_t2048", "train_dp4"):
        traffic[name] = harness.load_json(harness.BENCH_DIR, "traffic", name + ".json")
        traffic[name].update(seq_len=32, batch_per_chip=2, trace_seconds=0.3)
    serve = harness.load_json(harness.BENCH_DIR, "traffic", "serve_steady.json")
    serve.update(
        rate_per_s=8.0, lead_s=0.5, drain_limit_s=10.0, slots=4, positions_per_slot=64,
        trace_seconds=0.3, reference_requests=[[30, 4], [5, 6]],
        reference_fillers={"count": 2, "prompt_tokens": 7, "budget_tokens": 6},
        prompt_tokens={"median": 12, "sigma": 0.5, "min": 4, "max": 32},
        budget_tokens={"median": 6, "sigma": 0.5, "min": 2, "max": 16})
    traffic["serve_steady"] = serve
    knee = harness.load_json(harness.BENCH_DIR, "traffic", "serve_knee.json")
    traffic["serve_knee"] = {**serve, "schedule_seed": knee["schedule_seed"], "rate_per_s": 12.0}
    real = harness.load_json

    def load_json(*parts):
        if parts[-1].endswith("cerebras-gpt-1.3b.json"):
            return config
        if parts[-2:-1] == ("traffic",):
            return dict(traffic[parts[-1][:-len(".json")]])
        out = real(*parts)
        if parts[-1] == "peaks.json":  # so that the MFU arithmetic runs; the value means nothing
            out["device_kinds"]["cpu"] = out["device_kinds"]["TPU v5e"]
        return out

    monkeypatch.setattr(harness, "load_json", load_json)
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: jax.devices()[:chips])


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# What a traced serving run of ``cerebras-gpt-1.3b`` reports from a CPU: the
# host clock's and the registry's metrics.  A CPU has no device plane in its
# trace, so the trace readers (shares, spans, tails) return nothing.
SERVE_TRACED_ON_CPU = {
    "req_ms_per_token_p90.steady", "gen_lateness_p99_ms", "queue_wait_mean_ms",
    "prefill_mean_ms", "decode_step_mean_ms", "slot_occupancy_mean",
    "iteration_period_mean_ms", "first_token_mean_ms", "decode_dispatch_mean_ms",
    "decode_fetch_mean_ms", "kv_live_block_share", "engine_empty_share.serve",
    "iteration_longest_le_ms", "host_stall_longest_le_ms.serve",
    "host_gc_pause_longest_le_ms.serve"}


@pytest.mark.parametrize("workload,traced,expect", [
    ("lm_train_t2048", 0, {"train_tokens_per_s", "setup_s"}),
    ("lm_train_t2048", 1, {"mfu.train", "step_period_p50_ms.train"}),
    ("lm_train_dp4", 0, {"train_tokens_per_s", "setup_s"}),
    ("lm_serve_steady", 0, {"req_ms_per_token_p50", "setup_s"}),
    ("lm_serve_steady", 1, SERVE_TRACED_ON_CPU),
    ("lm_serve_knee", 0, {"req_ms_per_token_p50", "setup_s"}),
    ("lm_serve_knee", 1, SERVE_TRACED_ON_CPU),
])
def test_runner_end_to_end(tiny, capsys, workload, traced, expect):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == workload)
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} (virtual) devices: XLA_FLAGS=--xla_force_host_platform_device_count=4")
    rc = bench_run.main(["--workload", workload, "--seed", str(2**31 + 11),
                         "--seconds", "1.5", "--trace", str(traced)])
    line = last_line(capsys)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # a CPU has no device plane in its trace: the trace readers return nothing
    # at least these (a later PR's metric of the same lists is one more), and
    # nothing that is read from a device's trace
    assert set(line["metrics"]) >= expect
    assert not set(line["metrics"]) & {m["name"] for m in bench["per_layer"]
                                       if m["source"] == "device_trace"}
    # an end-to-end metric is never 0; a per-layer one may be at this size
    # (an occupancy sampled on an engine that is idle most of the time)
    assert all(m["value"] > 0 if not traced else m["value"] >= 0
               for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == chips


def test_a_configuration_names_its_model():
    """``serve_config`` builds the class the configuration's file names; a file
    that names a module the program lacks fails at the import, before anything
    listens (``test_latent_cell.py`` pins the order)."""
    from chipbench.runners import serve_config
    from moolib_tpu.models.latent_moe import LatentMoELM

    config = harness.load_json(harness.BENCH_DIR, "configs", "glm-4.7-flash.json")
    assert config["model"] == "moolib_tpu.models.latent_moe:LatentMoELM"
    assert serve_config.load_model(config) is LatentMoELM
    assert callable(serve_config.load_model(config).from_config)
    with pytest.raises(ModuleNotFoundError):
        serve_config.load_model({"model": "moolib_tpu.models.no_such_model:Model"})
    with pytest.raises(AttributeError):
        serve_config.load_model({"model": "moolib_tpu.models.latent_moe:NoSuchClass"})
    with pytest.raises(KeyError):
        serve_config.load_model({})


def test_no_accelerator_is_an_error_not_a_fallback(capsys):
    rc = bench_run.main(["--workload", "lm_train_t2048", "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
