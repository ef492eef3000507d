"""Profiling utilities (SURVEY §5.1: the tracing/observability subsystem)."""

import time

import jax

from moolib_tpu.utils.profiling import StepTimer


def test_step_timer_sections_and_report():
    t = StepTimer(alpha=0.5)
    for _ in range(3):
        with t.section("act"):
            time.sleep(0.002)
        with t.section("learn"):
            time.sleep(0.005)
    s = t.summary()
    assert set(s) == {"act", "learn"}
    assert s["learn"] > s["act"] > 0
    rep = t.report()
    assert "learn=" in rep and "%" in rep


def test_step_timer_publishes_to_registry():
    from moolib_tpu import telemetry

    reg = telemetry.Registry()
    tracer = telemetry.Tracer()
    t = StepTimer(alpha=0.5, registry=reg, tracer=tracer)
    with t.section("act"):
        time.sleep(0.001)
    hist = reg.histogram("loop_section_seconds", labelnames=("section",))
    s = hist.labels(section="act").get()
    assert s["count"] == 1 and s["sum"] >= 0.001
    assert [sp.name for sp in tracer.spans()] == ["act"]


def test_step_timer_publish_opt_out():
    from moolib_tpu import telemetry

    before = len(telemetry.get_tracer().spans())
    t = StepTimer(publish=False)
    with t.section("quiet"):
        pass
    assert t.summary()["quiet"] >= 0
    assert len(telemetry.get_tracer().spans()) == before


# ---------------------------------------------- on-demand device-trace windows
# moolib_tpu.telemetry.profiling: the __telemetry_profile RPC surface and the
# SIGUSR2 toggle.  The real jax.profiler is swapped for a recorder — its
# first start_trace costs seconds of plugin init and only one capture slot
# exists process-wide, so driving it for real would serialize (and slow)
# every test that traces.
import os
import signal

import pytest

from moolib_tpu import telemetry
from moolib_tpu.telemetry import profiling as devprof


@pytest.fixture
def fake_profiler(monkeypatch):
    calls = {"start": [], "stop": 0}
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda logdir: calls["start"].append(logdir)
    )

    def _stop():
        calls["stop"] += 1

    monkeypatch.setattr(jax.profiler, "stop_trace", _stop)
    # A dangling window from a failed earlier test must not poison this one.
    if devprof.profile_status()["active"]:
        devprof.stop_device_trace()
    yield calls
    if devprof.profile_status()["active"]:
        devprof.stop_device_trace()


def test_profile_window_lifecycle_and_anchors(fake_profiler, tmp_path):
    logdir = str(tmp_path / "win")
    res = devprof.start_device_trace(logdir)
    assert res["ok"] and res["logdir"] == logdir
    # Anchors on both clocks so offline tooling can rebase the XLA trace
    # onto the host tracer's axis.
    assert res["unix_time_ns"] > 0 and res["perf_counter_ns"] > 0
    assert fake_profiler["start"] == [logdir]
    assert devprof.profile_status() == {"active": True, "logdir": logdir}
    # The slot is exclusive: a second start reports, never stacks.
    dup = devprof.start_device_trace()
    assert not dup["ok"] and "already active" in dup["error"]
    assert fake_profiler["start"] == [logdir]
    out = devprof.stop_device_trace()
    assert out["ok"] and out["logdir"] == logdir and out["duration_s"] >= 0
    assert fake_profiler["stop"] == 1
    assert devprof.profile_status() == {"active": False}
    # The closed window landed as a host span on the shared tracer clock.
    spans = [s for s in telemetry.get_tracer().spans()
             if s.name == "device_profile"]
    assert spans and spans[-1].args["logdir"] == logdir
    again = devprof.stop_device_trace()
    assert not again["ok"] and "no profile active" in again["error"]


def test_profile_handle_command_rpc_surface(fake_profiler, tmp_path):
    assert devprof.handle_command("status") == {"active": False}
    res = devprof.handle_command("start", logdir=str(tmp_path / "rpc"))
    assert res["ok"]
    assert devprof.handle_command("status")["active"]
    assert devprof.handle_command("stop")["ok"]
    bad = devprof.handle_command("rewind")
    assert not bad["ok"] and "unknown action" in bad["error"]
    # "window" auto-closes on a daemon timer: the requester may die right
    # after asking and the stop still happens.
    res = devprof.handle_command("window", seconds=0.1)
    assert res["ok"] and res["window_s"] == pytest.approx(0.1)
    deadline = time.monotonic() + 5.0
    while devprof.profile_status()["active"]:
        assert time.monotonic() < deadline, "window never auto-closed"
        time.sleep(0.01)
    assert fake_profiler["stop"] == 2


def test_profile_no_jax_degrades_to_error(monkeypatch):
    # A box without jax answers the RPC with an error dict — the import is
    # lazy inside the start path, and None in sys.modules makes it raise.
    import sys

    monkeypatch.setitem(sys.modules, "jax", None)
    res = devprof.start_device_trace()
    assert res == {"ok": False, "error": "jax unavailable"}
    assert not devprof.profile_status()["active"]


def test_profile_start_failure_is_reported_not_raised(monkeypatch, tmp_path):
    def _boom(logdir):
        raise RuntimeError("plugin exploded")

    monkeypatch.setattr(jax.profiler, "start_trace", _boom)
    res = devprof.start_device_trace(str(tmp_path / "x"))
    assert not res["ok"] and "plugin exploded" in res["error"]
    assert not devprof.profile_status()["active"]


def test_profile_sigusr2_toggle(fake_profiler, tmp_path):
    old = signal.getsignal(signal.SIGUSR2)
    try:
        assert devprof.install_signal_toggle(logdir=str(tmp_path / "sig"))
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 5.0
        while not devprof.profile_status()["active"]:
            assert time.monotonic() < deadline, "toggle-on never landed"
            time.sleep(0.01)
        os.kill(os.getpid(), signal.SIGUSR2)
        while devprof.profile_status()["active"]:
            assert time.monotonic() < deadline, "toggle-off never landed"
            time.sleep(0.01)
        assert fake_profiler["start"] and fake_profiler["stop"] == 1
    finally:
        signal.signal(signal.SIGUSR2, old)


def test_profile_abandoned_window_force_stopped(fake_profiler, monkeypatch,
                                                tmp_path):
    # A requester killed mid-window must not leave the profiler armed
    # forever: the max-window guard force-stops it and flags the abandon.
    monkeypatch.setenv("MOOLIB_PROFILE_MAX_WINDOW_S", "0.15")
    telemetry.get_flight_recorder().clear()
    res = devprof.start_device_trace(str(tmp_path / "dead"))
    assert res["ok"]
    deadline = time.monotonic() + 10.0
    while devprof.profile_status()["active"]:
        assert time.monotonic() < deadline, "guard never fired"
        time.sleep(0.02)
    assert fake_profiler["stop"] == 1
    names = [n for _t, n, _a in telemetry.get_flight_recorder().events()]
    assert "profile.abandoned" in names


def test_profile_guard_disabled_and_bad_env(fake_profiler, monkeypatch):
    monkeypatch.setenv("MOOLIB_PROFILE_MAX_WINDOW_S", "0")
    res = devprof.start_device_trace()
    assert res["ok"]
    with devprof._lock:
        assert devprof._active["guard"] is None
    devprof.stop_device_trace()
    monkeypatch.setenv("MOOLIB_PROFILE_MAX_WINDOW_S", "not-a-number")
    assert devprof._max_window_s() == devprof.DEFAULT_MAX_WINDOW_S
