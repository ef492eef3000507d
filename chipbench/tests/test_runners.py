"""Each runner end to end through ``run.main`` at a tiny size on the CPU.

The platform check, the compile cache's placement and the sizes are replaced
here, in the test; the program and the harness get no option for it.  What the
line reports from a CPU is control flow and counts, never a device metric."""

import json

import jax
import pytest

from chipbench import harness
from chipbench import run as bench_run


# the long-prompt cell's own limits at the tiny size (64 wide, a vocabulary of 97): over the
# program's readings and under the float8 control's on the seeds the tests use
TINY_LIMITS = {"gap_sigma_max": 0.05, "gap_sigma_mean": 0.004}


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
    # the long-prompt cell in small: the longest prompt decodes to the model's last position
    # (112 + 16 = 128), one prompt lies one past a bucket's edge (65 in the capped bucket of
    # 112), the fillers keep the other slots in use, and every counted prompt is half a
    # context or more
    long = harness.load_json(harness.BENCH_DIR, "traffic", "serve_longprompt.json")
    traffic["serve_longprompt"] = {
        **serve, "schedule_seed": long["schedule_seed"], "rate_per_s": 6.0, "positions_per_slot": 128,
        "reference_requests": [[112, 16], [65, 4]],
        "reference_fillers": {"count": 2, "prompt_tokens": 70, "budget_tokens": 6},
        "reference_limits": TINY_LIMITS,
        "prompt_tokens": {"median": 80, "sigma": 0.15, "min": 65, "max": 112},
        "budget_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}
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
    "host_gc_pause_longest_le_ms.serve", "prefill_pad_share", "admit_rode_step_share"}


@pytest.mark.parametrize("workload,traced,expect", [
    ("lm_train_t2048", 0, {"train_tokens_per_s", "setup_s"}),
    ("lm_train_t2048", 1, {"mfu.train", "step_period_p50_ms.train"}),
    ("lm_train_dp4", 0, {"train_tokens_per_s", "setup_s"}),
    ("lm_serve_steady", 0, {"req_ms_per_token_p50", "setup_s"}),
    ("lm_serve_steady", 1, SERVE_TRACED_ON_CPU),
    ("lm_serve_knee", 0, {"req_ms_per_token_p50", "setup_s"}),
    ("lm_serve_knee", 1, SERVE_TRACED_ON_CPU),
    ("lm_serve_longprompt", 0, {"req_ms_per_token_p50", "setup_s"}),
    ("lm_serve_longprompt", 1, SERVE_TRACED_ON_CPU),
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
    # each number that decided ``correct`` beside its limit, under the line's LAST key
    assert list(line)[-1] == "compared" and len(line["compared"]) >= 3
    assert all(set(pair) == {"value", "limit"} and pair["value"] <= pair["limit"]
               for pair in line["compared"].values())


def test_the_long_prompt_cell_is_its_files():
    """``serve_knee.json`` key for key but for what makes the prompt do the
    work; the traffic's own draw says what ``prefill_pad_share`` should read."""
    from chipbench import traffic as traffic_mod

    long, knee = (harness.load_json(harness.BENCH_DIR, "traffic", name + ".json")
                  for name in ("serve_longprompt", "serve_knee"))
    assert set(long) == set(knee) | {"reference_limits"} and long["runner"] == long["use"] == "serve"
    same = {"arrivals", "pairing_seed", "lead_s", "drain_limit_s", "block_size", "max_queue", "trace_seconds"}
    assert all(long[k] == knee[k] for k in same)
    # the issue's median and bound; the deviation and the lower end so that 3% of the prompts are
    # clipped to an end (2.0% and 1.1%) where the issue's 0.3 and 1,024 clipped 27% to two lengths
    assert long["prompt_tokens"] == {"median": 1400, "sigma": 0.15, "min": 1025, "max": 1984}
    assert long["budget_tokens"] == {"median": 32, "sigma": 0.5, "min": 16, "max": 64}
    assert (long["slots"], long["positions_per_slot"]) == (16, 2048)  # the model's n_positions
    assert long["reference_requests"] == [[1984, 64], [1025, 16]]
    assert long["reference_fillers"] == {"count": 14, "prompt_tokens": 1100, "budget_tokens": 24}
    assert len(long["reference_requests"]) + long["reference_fillers"]["count"] == long["slots"]
    assert set(long["reference_limits"]) == {"gap_sigma_max", "gap_sigma_mean"}
    n = round(long["rate_per_s"] * 50)
    pairs = traffic_mod.pairs(long, n)
    assert all(1025 <= p <= 1984 and 16 <= b <= 64 and p + b <= 2048 for p, b in pairs)
    at_an_end = sum(p in (1025, 1984) for p, _b in pairs) / n
    assert 0.02 < at_an_end < 0.04
    # every prompt takes ONE bucket, of 1,984 rows (the next power of two, capped at the traffic's
    # longest prompt), and warm-up compiles no admit step below it
    from chipbench.runners import serve
    assert serve.min_prompt_len(long) == 1025
    pad, real_tokens = sum(1984 - p for p, _b in pairs), sum(p for p, _b in pairs)
    assert 100.0 * pad / (pad + real_tokens) == pytest.approx(28.6, abs=0.2)
    assert 1410 < real_tokens / n < 1422


@pytest.mark.parametrize("workload", ["lm_serve_longprompt", "lm_serve_knee"])
def test_a_token_altered_where_it_is_produced_is_not_correct(tiny, capsys, monkeypatch, workload):
    """The rest of a run with the timed path broken underneath: the decoder the
    engine steps hands back each row's logits moved one place along the
    vocabulary, so every token the device picks is its neighbour.  Nothing
    fails, nothing compiles in the window, and ``correct`` comes out false by
    the configuration's own limit."""
    import jax.numpy as jnp

    from moolib_tpu.models.transformer import PagedTransformerLM

    real_decode, real_both = PagedTransformerLM.decode, PagedTransformerLM.decode_with_prompt

    def decode(self, *a, **kw):
        logits, *rest = real_decode(self, *a, **kw)
        return (jnp.roll(logits, 1, axis=-1), *rest)

    def decode_with_prompt(self, *a, **kw):
        logits, first, *rest = real_both(self, *a, **kw)
        return (jnp.roll(logits, 1, axis=-1), jnp.roll(first, 1, axis=-1), *rest)

    monkeypatch.setattr(PagedTransformerLM, "decode", decode)
    monkeypatch.setattr(PagedTransformerLM, "decode_with_prompt", decode_with_prompt)
    rc = bench_run.main(["--workload", workload, "--seed", str(2**31 + 12), "--seconds", "1.5", "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(t[len("NOTES "):]) for t in out if t.startswith("NOTES "))
    line = json.loads(out[-1])
    assert rc == 0 and line["failed"] == 0 and notes["compiles_in_window"] == 0
    assert notes["reference_gap_sigma_max"] > 10 * harness.load_json(
        harness.BENCH_DIR, "configs", "cerebras-gpt-1.3b.json")["tolerance"]["serve_gap_sigma"]
    assert line["correct"] is False


def test_the_precision_control_is_not_correct(tiny, capsys, tmp_path):
    """``tools/gap_control.py`` whole, at a size a test run holds: the engine
    serves the cell's checked requests and fillers with every slot in use, and
    the runner's own comparison finds the program correct and the control (the
    reference's forward pass with every product's operands at float8's 3 bits
    of mantissa, at the same prompts and served tokens) not, on every seed.
    What the cell's own limits refuse is a reading of the chip at the cell's
    own size (PERF.md)."""
    from chipbench.tools import gap_control

    out = tmp_path / "gaps.jsonl"
    seeds = [2**31 + 21, 2**31 + 22, 2**31 + 23]
    assert gap_control.main(["lm_serve_longprompt", "--out", str(out), "--seeds", *map(str, seeds)]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"seeds": 3, "program_correct": 3, "control_not_correct": 3}
    lines = [json.loads(t) for t in out.read_text().splitlines()]
    assert [ln["seed"] for ln in lines] == seeds
    for ln in lines:  # the two checked requests and the two fillers, every token of each
        assert ln["tokens"] == len(ln["program_gaps"]) == len(ln["control_gaps"]) == 16 + 4 + 2 * 6
        assert set(ln["compared"]["control"]) == {"reference_gap_sigma_max", "reference_gap_sigma_mean"}


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
