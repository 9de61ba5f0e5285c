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
# Tier-1 wall-time headroom guard: aggregate per-test-file durations and
# write a JSON report at session end. Fail-soft: exceeding the budget
# prints a loud warning and sets "over_budget" in the JSON — it does NOT
# fail the run (the hard bound stays the driver's `timeout 870`). Tune
# with TIER1_DURATIONS_JSON / TIER1_BUDGET_S.
# ---------------------------------------------------------------------------

_DURATIONS = {}  # test file (nodeid prefix) -> summed call+setup seconds
_TIER1_BUDGET_S = float(os.environ.get("TIER1_BUDGET_S", "800"))


def pytest_runtest_logreport(report):
    if report.when in ("setup", "call", "teardown"):
        path = report.nodeid.split("::", 1)[0]
        _DURATIONS[path] = _DURATIONS.get(path, 0.0) + report.duration


def pytest_sessionfinish(session, exitstatus):
    import json as _json
    if not _DURATIONS:
        return
    total = sum(_DURATIONS.values())
    slow_lane = "slow" in session.config.getoption("-m", default="")\
        .replace("not slow", "")
    out = {
        "total_s": round(total, 2),
        "budget_s": _TIER1_BUDGET_S,
        "over_budget": total > _TIER1_BUDGET_S,
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
            f"\nWARNING: suite wall time {total:.0f}s exceeds the "
            f"~{_TIER1_BUDGET_S:.0f}s tier-1 headroom budget "
            f"(hard cap 870s). Heaviest files: "
            + ", ".join(f"{k}={v:.0f}s" for k, v in top)
            + f". Full report: {path}", yellow=True, bold=True)


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
