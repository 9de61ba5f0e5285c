"""Smoke the repo's scripts on CPU so they cannot rot: each runs as a
fresh process with ``JAX_PLATFORMS=cpu`` at its tiny CPU shapes and is
held to its one-line JSON contract and exit code — ``chip_smoke.py``'s
no-chip contract and rehearsal, the ``tools/`` CLIs, the chaos
scenarios. A KeyError in a script fails HERE, not on the chip.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# NOTE: these tests intentionally do NOT inherit conftest's in-process jax
# config — the children are fresh processes that read JAX_PLATFORMS.


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_chip_smoke_without_a_chip_fails_and_prints_no_result():
    """`python chip_smoke.py` where jax finds no TPU: non-zero exit within
    seconds, the missing TPU named on stderr, nothing on stdout — it
    never runs a phase on the CPU unasked."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=_env())
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr and "cpu" in proc.stderr


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_walks_every_phase_and_never_passes():
    """--rehearse-cpu runs all four phases at toy sizes (kernels
    interpreted, four virtual devices); every line says cpu, the last
    says ok=false and the exit code is 2, never 0."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, env=_env())
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert lines, proc.stderr[-2000:]
    assert all(l["device"]["platform"] == "cpu" for l in lines)
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal_passed"], \
        proc.stdout[-3000:] + proc.stderr[-2000:]
    assert lines[-2]["verdicts"] == {p: "ok" for p in (
        "train", "kernels", "serve", "multichip")}
    assert proc.returncode == 2


@pytest.mark.slow
def test_bench_collectives_smoke_telemetry():
    """tools/bench_collectives.py --smoke: tiny shapes, telemetry wired
    through telemetry.scope, wire-byte counters asserted in-process and
    re-checked here from the one-line JSON contract."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_collectives.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600, env=_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    res = json.loads(lines[-1])
    assert res["metric"] == "int8_vs_fp32_bytes_x"
    assert res["value"] > 1.0
    extra = res["extra"]
    assert extra["smoke"] is True
    wb = extra["telemetry"]["wire_bytes"]
    assert wb["int8"] > 0
    assert wb["fp32"] > wb["int8"]
    assert extra["telemetry"]["prometheus_bytes"] > 0
    # the K=2 overlap model smoke piggybacks on the exchange suite
    ov = extra["overlap_smoke"]
    assert ov["overlap_efficiency"] > 0
    assert ov["n_collectives"] >= 2
    assert len(ov["buckets"]) >= 2


@pytest.mark.slow
def test_bench_collectives_overlap_suite_smoke():
    """tools/bench_collectives.py --suite overlap --smoke --json: the
    overlap-efficiency metric contract — staged K=1 vs K=buckets on the
    tiny GPT, bucketed strictly better, full per-K summaries under
    --json."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_collectives.py"),
         "--suite", "overlap", "--smoke", "--json", "--buckets", "4"],
        capture_output=True, text=True, timeout=600, env=_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    res = json.loads(lines[-1])
    assert res["metric"] == "grad_sync_overlap_efficiency"
    assert res["value"] is not None and res["value"] > 0
    assert res["vs_baseline"] is None or res["value"] > res["vs_baseline"]
    extra = res["extra"]
    assert extra["k"] == 4
    assert extra["k4"]["n_collectives"] >= 4
    assert len(extra["k4"]["buckets"]) >= 2
    assert extra["k1"]["buckets"] == [sum(extra["k4"]["buckets"])]
    assert extra["hidden_wire_seconds"] > 0


@pytest.mark.slow
def test_bench_collectives_calibrate_suite_smoke():
    """tools/bench_collectives.py --suite calibrate --smoke: the fitting
    sweep (ISSUE 18) — measured psum ladder + real train steps fit
    corrected constants into a tempdir overlay DB, and the re-priced
    predicted step time must land strictly closer to measured than the
    uncalibrated default (asserted in-process; re-checked here from the
    schema-2 JSON contract)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_collectives.py"),
         "--suite", "calibrate", "--smoke"],
        capture_output=True, text=True, timeout=600, env=_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    res = json.loads(lines[-1])
    assert res["schema_version"] == 2
    assert res["metric"] == "calibration_step_time_drift"
    import math
    assert abs(math.log(res["value"])) < abs(math.log(res["vs_baseline"]))
    cal = res["calibration"]["step_time"]
    assert cal["predicted"] > 0 and cal["measured"] > 0
    assert cal["drift"] == pytest.approx(
        cal["measured"] / cal["predicted"])
    fitted = res["extra"]["fitted"]
    assert fitted["links"]["ici"]["bandwidth_bps"] > 0
    assert fitted["peak_flops_per_sec"] > 0


@pytest.mark.slow
def test_bench_plan_smoke():
    """tools/bench_plan.py --smoke: the auto-parallel planner searches
    the space at 8 simulated chips, its pick strictly beats the all-DP
    and memory-ordered baselines on calibrated predicted time, the
    chosen config RUNS, and the predicted/measured pair lands under the
    planner_step_time calibration key (schema_version 2 contract)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_plan.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600, env=_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    res = json.loads(lines[-1])
    assert res["schema_version"] == 2
    assert res["metric"] == "planner_step_time_ms"
    assert res["devices"] == 8
    assert res["value"] > 0 and res["measured_ms"] > 0
    assert res["baselines"]["pick_beats_all_dp"] is True
    assert res["baselines"]["pick_beats_memory_pick"] is True
    # the staged tier re-scored the pick from its real staged step and
    # refined the memory estimate's provenance
    assert res["pick"]["predicted"]["tier"] == "staged"
    assert res["pick"]["memory"]["source"] == "peak-live-bytes/chip"
    cal = res["calibration"]
    assert cal["key"] == "planner_step_time"
    assert cal["predicted"] > 0 and cal["measured"] > 0
    assert cal["drift"] == pytest.approx(cal["measured"] / cal["predicted"])


def test_nightly_report_smoke():
    """tools/nightly_report.py --smoke: the nightly-lane summary self-
    test (green / red / missing-input flows against synthetic slow-lane
    and tier-1 duration files in a tempdir)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "nightly_report.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=120, env=_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    res = json.loads(lines[-1])
    assert res["metric"] == "nightly_report_smoke"
    assert res["value"] == 1


@pytest.mark.slow
@pytest.mark.multihost(timeout=420)
def test_chaos_host_loss_scenario():
    """tools/chaos_smoke.py --scenario host_loss: the ISSUE acceptance
    path — 3 subprocess hosts with divergent seeded checkpoints (host0
    valid to step 10, host1/host2 to step 8) coordinate a restore of step
    8, host1 dies mid-run, the survivors remesh and run to completion."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_smoke.py"),
         "--scenario", "host_loss"],
        capture_output=True, text=True, timeout=400, env=_env())
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    res = json.loads(lines[-1])
    assert res["exit_code"] == 0, res
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["scenario"] == "host_loss"
    assert res["hosts_lost"] == 1
    assert res["restored_step"] == 8   # min-reduced over {10, 8, 8}
    assert res["remeshes"] >= 1
    assert res["barrier_steps"] and res["barrier_steps"][0] == 8
    assert res["disagreements"] >= 1
    assert res["merged_metric_count"] > 0


@pytest.mark.slow
def test_chaos_sdc_scenario():
    """tools/chaos_smoke.py --scenario sdc: the ISSUE 9 acceptance path —
    a flipped mantissa bit on replica 3 at step 5 is caught by the
    step-6 in-graph fingerprint check, the outlier replica is
    quarantined, the run rolls back to the step-4 checkpoint and
    converges; the non-check program carries zero fingerprint
    collectives."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_smoke.py"),
         "--scenario", "sdc"],
        capture_output=True, text=True, timeout=300, env=_env())
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    res = json.loads(lines[-1])
    assert res["exit_code"] == 0, res
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["scenario"] == "sdc"
    assert res["divergence_detected"] == 1
    assert res["hosts_quarantined"] == 1
    assert res["restored_step"] == 4
    assert res["fingerprint_collectives_nocheck"] == 0
    assert res["fingerprint_collectives_check"] > 0
    # the divergence verdict must have dumped the flight ring and the
    # tainted step's trace must be tail-kept, with closed accounting
    assert res["flight_dumps_divergence"] >= 1
    assert res["kept_divergence_traces"] >= 1
    assert res["trace_accounting_closed"] is True


@pytest.mark.slow
@pytest.mark.multihost(timeout=420)
def test_chaos_host_hang_scenario():
    """tools/chaos_smoke.py --scenario host_hang: host1 wedges at step
    12, its watchdog fires and stops heartbeat pumping, the coordinator
    reclassifies it as lost on staleness, and the survivors remesh and
    finish."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_smoke.py"),
         "--scenario", "host_hang"],
        capture_output=True, text=True, timeout=400, env=_env())
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    res = json.loads(lines[-1])
    assert res["exit_code"] == 0, res
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["hosts_hung"] == 1
    assert res["remeshes"] >= 1
    # the wedged host's watchdog flight-dumped before os._exit, tagged
    # with its process_index, and the per-host dumps merge rank-0 side
    assert res["flight_dumps_hang"] == 1
    assert res["hang_dump_hosts"] == [1]
    assert res["merged_span_count"] > 0


def test_fsck_ckpt_smoke():
    """tools/fsck_ckpt.py --smoke on a TIERED tree (deep_every=2):
    shallow fsck catches the cheap-tier tamper without digests, deep
    fsck additionally catches a bit flip whose file CRC was re-attested
    on a deep step, tiers are labelled, and latest_valid_step falls back
    to the newest clean cheap step."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fsck_ckpt.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=300, env=_env())
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    res = json.loads(lines[-1])
    assert res["exit_code"] == 0, res
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["smoke"] is True
    assert res["clean_tiers"] == {"1": "deep", "2": "cheap",
                                  "3": "deep", "4": "cheap"}
    assert res["shallow"]["4"] == "corrupt"   # cheap tamper, shallow catch
    assert res["deep"]["3"] == "corrupt"      # deep-only catch
    assert res["latest_valid_step_deep"] == 2  # cheap-tier fallback


@pytest.mark.slow
@pytest.mark.multihost(timeout=600)
def test_chaos_crash_during_async_save_scenario():
    """tools/chaos_smoke.py --scenario crash_during_async_save: the ISSUE
    13 acceptance path — a child training with async_commit saves dies by
    REAL SIGKILL (a) with a snapshot staged pre-commit and (b) mid-commit
    between payload write and manifest; both times restore lands on the
    previous committed step with ckpt_restore_fallbacks_total unchanged,
    and a dirty in-flight snapshot is provably never committed."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_smoke.py"),
         "--scenario", "crash_during_async_save", "--steps", "3"],
        capture_output=True, text=True, timeout=560, env=_env())
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    res = json.loads(lines[-1])
    assert res["exit_code"] == 0, res
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["killed"] == 2                  # both windows really died
    assert res["restore_fallbacks"] == 0       # debris costs no fallback
    assert res["restored_step_staged"] == 2
    assert res["restored_step_mid_commit"] == 2
    assert res["dirty_suppressed"] == 1
    assert res["accounted"] is True


@pytest.mark.slow
def test_bench_ckpt_smoke():
    """tools/bench_ckpt.py --smoke: the ISSUE 13 perf acceptance — async
    ckpt_step_stall_ms p50 < 0.5x the synchronous save wall at the same
    cadence, with bitwise-identical restored state and the new telemetry
    series recorded."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_ckpt.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=560, env=_env())
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    res = json.loads(lines[-1])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["metric"] == "ckpt_async_stall_ratio"
    assert res["value"] is not None and res["value"] < 0.5
    extra = res["extra"]
    assert extra["bitwise_identical"] is True
    assert all(extra["telemetry_series"].values())
    assert extra["accounting"]["accounted"] is True
    assert res["schema_version"] >= 1
    # every ckpt_save trace kept (snapshot on the step thread, commit on
    # the committer) and written to the run dir for trace_view
    assert extra["ckpt_traces_kept"] >= 1
    assert extra["trace_accounting_closed"] is True
    assert extra["kept_traces_path"]


@pytest.mark.slow
def test_replay_step_smoke():
    """tools/replay_step.py --smoke: replay of a recorded step says
    ``ok``; after tampering one recorded digest it says ``sdc`` with the
    tampered key pinned."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "replay_step.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=400, env=_env())
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    res = json.loads(lines[-1])
    assert res["exit_code"] == 0, res
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["clean_verdict"] == "ok"
    assert res["tampered_verdict"] == "sdc"


def test_bench_serving_smoke():
    """tools/bench_serving.py --smoke: the ISSUE 10 acceptance path —
    Poisson open-loop traffic against the serving runtime: the 2x
    overload phase sheds with the completed p99 within deadline, goodput
    stays within a bounded band of baseline, an injected replica_stall
    fails over with zero admitted-and-feasible requests lost, and the
    recompile count stops growing after warmup (shape buckets closed) —
    plus the ISSUE 11 decode phase: prefix-heavy generations over the
    paged KV cache hit >= 0.5 of their prompt tokens, compute <= 0.5x
    the no-sharing prefill baseline, exercise LRU eviction, and add
    zero compiled shapes beyond the primed set — plus the spec-decode
    phase: speculative generations exact vs dense_generate with
    tokens/target-step >= 1.5 and zero leaked pages.

    The contract includes wall-clock checks (p99-in-deadline, goodput
    band, tracing-overhead p50); on a loaded CI box a single run can
    flake on those, so one retry is allowed — two consecutive failures
    fail the test, and the first failure's check names are printed."""
    for attempt in range(2):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_serving.py"),
             "--smoke"],
            capture_output=True, text=True, timeout=400, env=_env())
        lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
        assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
        res = json.loads(lines[-1])
        extra = res["extra"]
        if extra["exit_code"] == 0:
            break
        print(f"bench_serving --smoke attempt {attempt} failed checks: "
              f"{[k for k, v in extra['checks'].items() if not v]}")
    assert extra["exit_code"] == 0, res
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["metric"] == "serving_overload_goodput_rps"
    assert res["value"] > 0
    assert all(extra["checks"].values()), extra["checks"]
    assert extra["requests_shed_total"] > 0
    assert extra["overload"]["p99_s"] <= extra["overload"]["deadline_s"]
    assert extra["replica_failover_total"] >= 1
    assert extra["failover"]["stall_fired"] == 1
    # ISSUE 11 decode acceptance: sharing halves prefill at hit-rate
    # >= 0.5, eviction fired, and the compiled set stayed closed
    dec = extra["decode"]
    assert extra["kv_cache_hit_rate"] >= 0.5
    assert dec["prefill_tokens_computed"] \
        <= 0.5 * dec["prefill_tokens_no_sharing"]
    assert dec["prefix_hit_tokens"] > 0
    assert dec["evictions"] >= 1
    assert dec["decode_goodput_tokens_per_s"] > 0
    assert dec["jit_shapes"]["final"] == dec["jit_shapes"]["primed"]
    assert dec["failed"] == 0
    assert extra["failover"]["failed"] == 0
    assert extra["accounted"] is True
    assert extra["serving_recompiles_total"]["closed"] is True
    assert extra["telemetry"]["prometheus_bytes"] > 0
    # tracing acceptance: always-on recording with nothing kept costs
    # <= 3% p50, the disabled path allocates nothing, the failover phase
    # tail-keeps traces, and the drain shutdown wrote a flight dump
    assert res["schema_version"] >= 1
    tr = extra["tracing"]
    assert tr["overhead_frac"] is not None and tr["overhead_frac"] <= 0.03
    assert tr["spans_recorded"] > 0 and tr["kept_while_keep_none"] == 0
    assert tr["failover_traces_kept"] >= 1
    assert tr["kept_traces_path"]
    assert any("flight_drain_" in p for p in extra["flight_dumps"])


def test_metric_catalogue_in_sync():
    """tools/check_metric_catalogue.py: every metric registered in the
    source tree has a catalogue row in paddle_tpu/telemetry/__init__.py
    and vice versa — catalogue drift fails tier-1 here."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "check_metric_catalogue.py")],
        capture_output=True, text=True, timeout=120, env=_env())
    assert proc.returncode == 0, proc.stdout + proc.stderr[-1000:]
    assert "catalogue ok" in proc.stdout


def test_trace_view_smoke():
    """tools/trace_view.py --smoke: the text summariser renders a
    synthetic kept trace (waterfall, events, slowest-span table)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_view.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=120, env=_env())
    assert proc.returncode == 0, proc.stdout + proc.stderr[-1000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["exit_code"] == 0 and all(res["checks"].values()), res


@pytest.mark.slow
def test_numerics_smoke_cpu():
    """tools/numerics_smoke.py: all kernel-vs-dense checks pass on the
    CPU interpreter; on-chip runs reuse the same script (r3 item 10)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "numerics_smoke.py")],
        capture_output=True, text=True, timeout=600, env=_env())
    lines = proc.stdout.strip().splitlines()
    assert lines, f"stderr: {proc.stderr[-2000:]}"
    summary = json.loads(lines[-1])
    assert summary["numerics_ok"], proc.stdout
    assert summary["n_checks"] >= 7
    assert proc.returncode == 0


def test_lint_program_smoke_strict():
    """lint_program --smoke --strict over every registered program
    (bench trainers + decode executors) PLUS the declared program
    families: any future rule regression, new warning, or schedule
    hazard on the shipped programs fails tier-1 here, not at snapshot
    time. Every per-program record must carry its collective-schedule
    fingerprint and be individually ok."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_program.py"),
         "--smoke", "--strict", "--json"],
        capture_output=True, text=True, timeout=900, env=_env())
    assert proc.returncode == 0, (
        f"lint rc={proc.returncode}\nstdout tail: {proc.stdout[-3000:]}\n"
        f"stderr tail: {proc.stderr[-2000:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    programs = {"gpt", "gpt-planner", "bert", "decode-mixed",
                "decode-decode", "decode-verify"}
    assert programs | {"__families__"} <= set(out)
    for name in programs:
        rep = out[name]
        assert rep["ok"], f"{name}: {rep['findings']}"
        fp = rep["schedule_fingerprint"]
        assert isinstance(fp, str) and len(fp) == 64, (name, fp)
        assert rep["num_collectives"] >= 0
    fams = out["__families__"]
    assert {"trainer-step", "localsgd-step", "decode-executor"} \
        <= set(fams)
    for fname, res in fams.items():
        assert res["ok"], f"{fname}: {json.dumps(res)}"
        for member, m in res["members"].items():
            assert m["fingerprint"] == res["fingerprints"][member]


def test_nightly_scheduler_dry_run():
    """tools/nightly_scheduler.sh --dry-run: the nightly cron/CI stanza's
    self-check — run_slow_lane.sh and nightly_report.py present and
    runnable, the report's synthetic self-check green, the CI workflow
    file in place — without paying the slow lane. Keeps the scheduler
    wiring itself from bit-rotting."""
    script = os.path.join(REPO, "tools", "nightly_scheduler.sh")
    proc = subprocess.run([script, "--dry-run"], capture_output=True,
                          text=True, timeout=120, env=_env(), cwd=REPO)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["scheduler"] == "nightly"
    assert res["mode"] == "dry_run"
    assert res["ok"] is True
    assert res["problems"] == []
    # cron points at the stanza itself, so cron and CI share one pipeline
    assert "nightly_scheduler.sh" in res["cron"]
    proc2 = subprocess.run([script, "--print-cron"], capture_output=True,
                           text=True, timeout=60, env=_env(), cwd=REPO)
    assert proc2.returncode == 0
    assert proc2.stdout.strip() == res["cron"]


def test_chaos_hot_swap_scenario():
    """tools/chaos_smoke.py --scenario hot_swap: the ISSUE 19 serving-
    fleet acceptance — an SLO burn-rate breach under overload fires the
    rule's registered scale-up action; an exponent-poisoned checkpoint
    (CRC-committed fine) is canaried on shadow traffic, fails the
    output-sanity gate and rolls back with the pinned incumbent still
    serving finite outputs; a good checkpoint then promotes fleet-wide.
    Zero requests lost fleet-wide, zero compile cold starts (persistent
    executor cache)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_smoke.py"),
         "--scenario", "hot_swap"],
        capture_output=True, text=True, timeout=400, env=_env())
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    res = json.loads(lines[-1])
    assert res["exit_code"] == 0, res
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["scenario"] == "hot_swap"
    assert res["slo_alerts"] >= 1 and res["scale_ups"] >= 1
    assert res["members_after_burst"] >= 3
    assert res["canary_rolled_back"] == 1
    assert res["canary_checks_bad"]["sanity"] is False
    assert res["canary_promoted"] == 1
    assert res["generation_final"] == 2
    assert res["requests_lost"] == 0
    assert res["recompiles"] == 0 and res["cold_starts_closed"] is True
    assert res["accounted"] is True
