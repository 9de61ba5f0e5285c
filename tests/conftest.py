"""Test config: force CPU backend with 8 virtual devices so distributed
(DP/TP/PP/sharding) logic is testable without TPUs — the SURVEY.md §4
translation of the reference's subprocess-on-localhost TestDistBase."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags +
                               " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Numeric tests verify math, not precision policy: pin fp32-exact matmuls
# (prod default keeps the fast MXU path).
import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

import signal as _signal  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy tests excluded from tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "multihost(timeout): multi-process elastic/simulation tests, "
        "bounded by a SIGALRM watchdog (default 300s) so a wedged "
        "subprocess cannot eat the tier-1 budget")


# ---------------------------------------------------------------------------
# Tier-1 wall-time guard: the controlling process times the session's wall
# and sums the seconds of each test file, and writes both as JSON at session
# end (TIER1_DURATIONS_JSON, default /tmp/tier1_durations.json). Fail-soft: a
# wall over the budget prints a loud warning and sets "over_budget" -- it
# does NOT fail the run. The budget is the limit tier-1 runs under,
# `timeout 1470` around `pytest -n 6 --dist loadfile`.
# ---------------------------------------------------------------------------

_DURATIONS = {}  # test file (nodeid prefix) -> summed setup+call+teardown s
_TIER1_BUDGET_S = 1470.0
_session_start = None  # monotonic seconds, set in the lead process


def pytest_sessionstart(session):
    global _session_start
    if not hasattr(session.config, "workerinput"):    # not an xdist worker
        _session_start = time.monotonic()


def pytest_runtest_logreport(report):
    if report.when in ("setup", "call", "teardown"):
        path = report.nodeid.split("::", 1)[0]
        _DURATIONS[path] = _DURATIONS.get(path, 0.0) + report.duration


def pytest_sessionfinish(session, exitstatus):
    import json as _json
    if _session_start is None or not _DURATIONS:
        return                                  # a worker, or no test ran
    wall = time.monotonic() - _session_start
    slow_lane = "slow" in session.config.getoption("-m", default="")\
        .replace("not slow", "")
    out = {
        "wall_s": round(wall, 2),
        "total_s": round(sum(_DURATIONS.values()), 2),
        "budget_s": _TIER1_BUDGET_S,
        "over_budget": wall > _TIER1_BUDGET_S,
        "markexpr": session.config.getoption("-m", default=""),
        "per_file": {k: round(v, 2) for k, v in sorted(
            _DURATIONS.items(), key=lambda kv: -kv[1])},
    }
    path = os.environ.get("TIER1_DURATIONS_JSON",
                          "/tmp/tier1_durations.json")
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            _json.dump(out, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        return
    if out["over_budget"] and not slow_lane:
        top = list(out["per_file"].items())[:5]
        tw = session.config.get_terminal_writer()
        tw.line(
            f"\nWARNING: suite wall time {wall:.0f}s exceeds the "
            f"{_TIER1_BUDGET_S:.0f}s tier-1 limit. Heaviest files (test "
            f"seconds): " + ", ".join(f"{k}={v:.0f}s" for k, v in top)
            + f". Full report: {path}", yellow=True, bold=True)


@pytest.fixture
def flash_staged():
    """A fresh telemetry registry, and the flash kernels' two staged
    functions' caches dropped before and after: their counters count where
    a kernel is staged, and a shape an earlier test staged would count
    nothing. Gives the registry's metric of a name."""
    import importlib

    from paddle_tpu import telemetry
    from paddle_tpu.telemetry.metrics import Registry
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    prev, reg = telemetry.get_registry(), Registry()
    telemetry._set_registry(reg)
    telemetry.enable()
    fa._fwd.clear_cache()
    fa._bwd_calls.clear_cache()
    yield reg.get
    telemetry.disable()
    telemetry._set_registry(prev)
    fa._fwd.clear_cache()
    fa._bwd_calls.clear_cache()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("multihost")
    if marker is None or not hasattr(_signal, "SIGALRM"):
        yield
        return
    timeout = int(marker.kwargs.get(
        "timeout", marker.args[0] if marker.args else 300))

    def _alarm(signum, frame):
        raise TimeoutError(
            f"multihost test exceeded its {timeout}s watchdog")

    prev = _signal.signal(_signal.SIGALRM, _alarm)
    _signal.alarm(timeout)
    try:
        yield
    finally:
        _signal.alarm(0)
        _signal.signal(_signal.SIGALRM, prev)
