"""paddle_tpu.telemetry — unified training telemetry (ISSUE 3).

The framework-wide observability spine: a labeled metrics registry
(Counter / Gauge / Histogram), exporters (Prometheus text, JSONL event
log, chrome-trace counter merge), and a ``scope(run_dir)`` context that
wires registry + profiler + sink together for a run.

Hot-path contract: instrumentation sites (engine.train_step, dataloader,
checkpoint, collectives) call ``telemetry.enabled()`` first — a single
module-global read — and only touch the registry when it returns True.
Metrics themselves are recorded host-side around jitted calls, never
inside traces.  ``monitor.StatValue`` is a thin bridge onto this
registry (one source of truth).

Typical use::

    with paddle_tpu.telemetry.scope("runs/gpt13b") as tel:
        trainer.compile(inputs, labels)
        for batch in loader:
            trainer.train_step(*batch)
    # -> runs/gpt13b/{events.jsonl, metrics.prom, trace.json}

Metric catalogue (recorded by the built-in instrumentation; see README
"Telemetry" for label conventions):

=============================  =========  =================================
name                           kind       source
=============================  =========  =================================
step_time_seconds              histogram  engine.train_step / hapi callback
stage_time_seconds             histogram  engine._stage cache miss: the
                                          span paddle_tpu.trainer.make_step
compile_time_seconds           histogram  engine.compile
recompiles_total               counter    engine._stage misses + jit shape
                                          misses
staging_seconds_total          counter    telemetry.staging: seconds jax
                                          spent staging a function, from
                                          jax.monitoring's events {phase=
                                          trace|lower|compile, fun=...};
                                          a staging inside another counts
                                          in the outer one only
staging_programs_total         counter    programs of a function the
                                          backend compiled or loaded from
                                          the persistent cache {fun=...};
                                          fun=train_step above 1 is a
                                          step staged again
compile_cache_hits_total       counter    programs loaded from jax's
                                          persistent compilation cache
compile_cache_misses_total     counter    programs compiled and written
                                          to it
tokens_per_sec                 gauge      engine.train_step
mfu                            gauge      analysis.cost FLOPs / step time /
                                          peak_flops_per_sec()
peak_live_bytes                gauge      analysis.cost over the staged step
donated_bytes                  gauge      donated state (params+opt+residual)
grad_sync_bytes_total          counter    logical wire bytes
                                          {policy=..., link=ici|dcn,
                                          bucket=0..K-1}
grad_sync_compression_x        gauge      fp32 bytes / policy bytes
grad_sync_residual_norm        gauge      int8/int4 error-feedback
                                          residual L2
grad_sync_overlap_efficiency   gauge      analysis.cost.overlap_summary
                                          over the staged step (fraction
                                          of collective time hidden
                                          under backward compute)
collective_calls_total         counter    collective.py, trace time {op=...}
dataloader_fetch_seconds       histogram  io.DataLoader batch fetch
dataloader_batches_total       counter    io.DataLoader batches served
checkpoint_save_seconds        histogram  distributed.checkpoint
checkpoint_restore_seconds     histogram  distributed.checkpoint
checkpoint_bytes_total         counter    distributed.checkpoint {op=...}
pallas_config_resolved_total   counter    ops.pallas.tuner.resolve, trace
                                          time {kernel=...,
                                          source=db|default|fallback}
flash_tiles_staged_total       counter    ops.pallas.flash_attention, where
                                          a kernel is staged: the tiles one
                                          lane block's grid walks
                                          {kernel=flash_fwd|flash_bwd_dq|
                                          flash_bwd_dkv, kind=dense|
                                          triangular|masked}
flash_steps_held_total         counter    ops.pallas.flash_attention, beside
                                          it: the other steps of that grid,
                                          hidden by the causal mask, skipped
                                          with their block index held so
                                          that they fetch nothing {kernel}
rope_calls_staged_total        counter    nn.functional.rotary_embedding,
                                          where a call is staged: the path
                                          its input took {path=pallas|xla,
                                          norm=0|1 (the QK norm folded in)}
linear_attn_calls_staged_total counter    nn.functional.gated_delta_rule,
                                          where a call is staged: the path
                                          it took {path=pallas (the
                                          kernels)|chunked (XLA's batched
                                          products)|recurrent}
latent_attn_calls_staged_total counter    text.models MultiHeadLatent
                                          Attention, where a call is staged:
                                          the path the rotation of its
                                          queries took {rope=pallas|xla}
gated_delta_chunks_total       counter    chunk states a row of those calls
                                          walks one after another (seq /
                                          chunk; seq on the recurrent path)
ssd_scan_calls_staged_total    counter    nn.functional.ssd_scan, where a
                                          call is staged: the path it took
                                          {path=chunked|recurrent}
ssd_chunks_total               counter    chunk states a row of those calls
                                          walks one after another (seq /
                                          chunk; seq on the recurrent path)
moe_tokens_routed_total        counter    incubate.moe DroplessMoELayer.
                                          publish_routing: tokens routed
moe_held_assignments_total     counter    (token, expert) assignments on
                                          the experts held here; none is
                                          dropped
moe_max_load_over_mean         gauge      fullest held expert's
                                          assignments over the mean per
                                          held expert, last call
block_diffusion_masked_share   gauge      text.models MixedDecoderFor
                                          BlockDiffusion.publish_noise:
                                          share of a step's clean tokens
                                          the noise masked, last call
mtp_main_loss                  gauge      text.models MixedDecoderFor
                                          Pretraining.publish_losses: the
                                          next-token cross entropy of the
                                          last call, unweighted
mtp_next_loss                  gauge      same: the token-after-next term
                                          (the multi-token-prediction
                                          module's), unweighted
retries_total                  counter    resilience.retry {site=...}
retry_exhausted_total          counter    resilience.retry {site=...}
retry_bytes_abandoned_total    counter    resilience.retry byte budget
                                          {site=...}
ckpt_retry_bytes_abandoned_total counter  checkpoint saves degraded to
                                          local staging
ckpt_restore_fallbacks_total   counter    CheckpointManager.restore steps
                                          skipped over {reason=manifest|
                                          deep|restore|staged}
ckpt_step_stall_ms             histogram  time the step loop actually
                                          blocked on checkpointing (sync:
                                          the whole save; async: the
                                          device->host snapshot only) —
                                          the headline async-vs-sync
                                          metric
ckpt_snapshot_ms               histogram  async save device->host
                                          staging-buffer copy
ckpt_commit_ms                 histogram  background committer write->
                                          fsync->CRC->manifest->GC per
                                          committed step
ckpt_inflight                  gauge      snapshots staged or mid-commit
                                          (0..2, double-buffered)
ckpt_suppressed_total          counter    async snapshots whose commit was
                                          suppressed {reason=dirty|
                                          superseded}
resilience_faults_injected_total counter  resilience.faults {kind=...,
                                          site=...}
resilience_restarts_total      counter    run_resilient crash recoveries
resilience_resumes_total       counter    run_resilient checkpoint resumes
resilience_steps_skipped       gauge      run_resilient (NaN-guard skips)
elastic_restore_barrier_total  counter    resilience.elastic coordinated
                                          restore barriers completed
elastic_step_disagreements_total counter  restore barriers where hosts
                                          reported divergent steps
elastic_remesh_total           counter    reshard_trainer remesh ops
elastic_remesh_failed_total    counter    remesh attempts that fell back
                                          to the relaunch path (exit 75)
elastic_residual_dropped_norm_total counter  L2 norm of comm_err rows
                                          dropped by a scale-down remap
integrity_check_steps_total    counter    engine train steps that ran the
                                          fingerprint-check program
replica_divergence_total       counter    replicas disagreeing on a
                                          parameter fingerprint {leaf=...}
hosts_quarantined_total        counter    resilience.integrity replicas /
                                          hosts quarantined by majority
                                          vote
hang_watchdog_fired_total      counter    HangWatchdog deadlines blown
                                          (step armed but not disarmed in
                                          time)
serving_requests_total         counter    inference.serving request
                                          outcomes {outcome=completed|
                                          shed|expired|failed}
serving_requests_shed_total    counter    admission rejections {cause=
                                          queue_full|deadline_infeasible|
                                          deadline_expired_in_queue|
                                          draining}
serving_queue_wait_seconds     histogram  admission -> first dispatch
serving_execute_seconds        histogram  replica batch execute
serving_e2e_seconds            histogram  admission -> terminal state
serving_batch_occupancy        gauge      dispatched rows / bucket rows
serving_queue_depth            gauge      admission deque length
serving_batches_total          counter    batches dispatched
serving_recompiles_total       counter    first-seen (signature, bucket)
                                          pairs — stops growing once the
                                          compiled set closes
serving_tokens_total           counter    tokens completed
serving_replica_failover_total counter    batches failed over to another
                                          replica
serving_replica_unhealthy_total counter   replicas benched {reason=
                                          stall|io_error}
serving_replicas_healthy       gauge      replicas currently in rotation
serving_requeued_requests_total counter   requests requeued by failover
serving_execute_errors_total   counter    executor exceptions {error=...}
serving_weight_compression_x   gauge      fp weight bytes / quantized
                                          bytes {policy=int8|int4}
kv_cache_pages_total           gauge      paged KV cache pool size
kv_cache_pages_used            gauge      pages allocated or held by the
                                          shared-prefix table
kv_cache_prefix_hits_total     counter    prompt TOKENS served from
                                          shared prefix pages at
                                          admission (not recomputed)
kv_cache_evictions_total       counter    registered pages reclaimed
                                          {cause=capacity|trim}
decode_tokens_total            counter    generated tokens committed by
                                          the decode scheduler
spec_draft_tokens_total        counter    draft tokens proposed by the
                                          speculative-decode drafter
spec_accepted_tokens_total     counter    draft tokens the verify step
                                          accepted (greedy match)
spec_accept_rate               histogram  per-verify-step accepted / K
spec_verify_steps_total        counter    speculative verify target-model
                                          steps committed
predicted_reshard_collectives  gauge      engine.compile(analyze=True):
                                          implicit resharding collectives
                                          the static sharding pass
                                          (analysis/sharding.py) predicts
                                          in the staged step
predicted_reshard_seconds      gauge      modeled per-step wall seconds
                                          of that implicit resharding
                                          (ring model over axis_links)
spans_recorded_total           counter    telemetry.tracing span ends
                                          (every one also lands in the
                                          flight-recorder ring)
traces_kept_total              counter    tail-sampled traces kept at
                                          close {reason=shed|expired|
                                          failed|failover|divergence|
                                          deadline|latency_percentile|
                                          forced}
flight_dumps_total             counter    flight-recorder ring dumps
                                          written {reason=hang_watchdog|
                                          divergence|drain|sigusr2|
                                          slo_*}
slo_alerts_total               counter    telemetry.slo rolling-window
                                          burn-rate breaches {rule=...}
fleet_replicas                 gauge      live serving-fleet members
                                          (heartbeated membership files
                                          under the coordinator root)
fleet_scale_events_total       counter    fleet autoscale actions
                                          {direction=up|down, reason=
                                          modeled_wait|queue_depth|
                                          slo_*|idle|...}
hot_swap_total                 counter    model hot-swap rollouts
                                          {outcome=promoted|rolled_back}
canary_health_checks_total     counter    canary verdicts during hot-swap
                                          {outcome=pass|fail}
schedule_verify_total          counter    cross-rank collective-schedule
                                          fingerprint verifications
                                          (bootstrap + every elastic
                                          remesh re-entry)
collective_schedule_mismatch_total counter programs whose collective-
                                          schedule fingerprints diverged
                                          across hosts (the verify
                                          aborts with a diff instead of
                                          letting the ranks hang)
calibration_drift_ratio        gauge      measured / predicted per
                                          calibration key {key=step_time|
                                          serving_queue_wait|
                                          collective_<link>|tuner:<k>|
                                          planner_step_time}
                                          (telemetry.calibration)
calibration_samples_total      counter    (prediction, measurement)
                                          pairs recorded {key=...}
calibration_drift_breaches_total counter  latched |log drift| > bound
                                          events per key; each fires one
                                          reason-tagged flight dump
                                          (calibration_drift)
planner_candidates_total       counter    auto.plan_search candidates per
                                          processing tier {tier=enumerated|
                                          pruned_bounds|pruned_memory|
                                          scored_analytic|scored_staged}
planner_search_ms              histogram  plan_search wall time
                                          (enumeration + pruning +
                                          analytic/staged scoring)
=============================  =========  =================================

The staging record (``telemetry.staging``) behind the four ``staging_*``
/ ``compile_cache_*`` series is on without ``telemetry.scope`` or
``enable()``: a listener on jax's own events, installed when this
package is imported, that costs nothing where nothing is staged.
``staging.summary()`` / ``staging.entries()`` and
``ParallelTrainer.staging_summary()`` read it in any run; the registry
gets the series only while telemetry is enabled. The trainer's phases
are ``jax.profiler.TraceAnnotation`` spans, always on, which the record
keeps too: ``paddle_tpu.trainer.init_state``, ``paddle_tpu.trainer.build``
and ``paddle_tpu.trainer.make_step`` around its construction, beside
``paddle_tpu.trainer.stage`` and ``paddle_tpu.trainer.launch`` around
each step.

Multi-host merge: ``telemetry.aggregate.gather_registries()`` allgathers
every process's ``Registry.to_dict()`` and merges on rank 0 with
``process_index`` labels (per-host series stay distinct, so straggler
skew survives the merge).
"""
from __future__ import annotations

import os
import time
from typing import Optional

from .metrics import (Counter, DEFAULT_BUCKETS, Gauge, Histogram,  # noqa: F401
                      Registry)
from .scope import TelemetryScope, scope  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "DEFAULT_BUCKETS",
    "scope", "TelemetryScope", "aggregate", "tracing", "flight", "slo",
    "calibration", "staging",
    "enable", "disable", "enabled", "is_enabled",
    "get_registry", "counter", "gauge", "histogram",
    "prometheus_text", "emit", "peak_flops_per_sec", "published_peak",
    "PUBLISHED_PEAKS",
]

_enabled = False
_registry = Registry()
_sink = None  # active JsonlSink, installed by scope(run_dir=...)


def enable(on: bool = True):
    """Turn the instrumentation sites on (or off with ``enable(False)``)."""
    global _enabled
    _enabled = bool(on)


def disable():
    enable(False)


def enabled() -> bool:
    """The one check every instrumentation site makes per event."""
    return _enabled


is_enabled = enabled


def get_registry() -> Registry:
    return _registry


def _set_registry(reg: Registry):
    global _registry
    _registry = reg


def _set_sink(sink):
    global _sink
    _sink = sink


def counter(name: str, help: str = "") -> Counter:
    return _registry.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return _registry.gauge(name, help)


def histogram(name: str, help: str = "", buckets=None) -> Histogram:
    return _registry.histogram(name, help, buckets=buckets)


def prometheus_text(registry: Optional[Registry] = None) -> str:
    from .export import prometheus_text as _pt
    return _pt(registry if registry is not None else _registry)


def emit(event: str, **fields):
    """Append an event to the run's JSONL log (no-op outside scope(run_dir))."""
    s = _sink
    if s is not None:
        s.emit({"event": event, "ts": time.time(), **fields})


from . import aggregate  # noqa: E402,F401  (stdlib-only module, safe here)
from . import calibration  # noqa: E402,F401
from . import flight  # noqa: E402,F401
from . import slo  # noqa: E402,F401
from . import tracing  # noqa: E402,F401
# the one listener on jax's staging events, installed here and always on
from . import staging  # noqa: E402,F401


# Published per-chip peaks keyed by ``jax.devices()[0].device_kind`` — the
# one table every utilization in the repo divides by. A TPU that is not in
# it is an error, not a default. Source: Google Cloud documentation,
# "TPU v5e" (the v5e reports itself as "TPU v5 lite").
PUBLISHED_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_sec": 197e12,
                    "hbm_bytes_per_sec": 819e9,
                    "hbm_bytes": 16e9},
}


def published_peak(device_kind: str) -> dict:
    """The :data:`PUBLISHED_PEAKS` row of ``device_kind``; raises for a
    device nobody has entered a published figure for."""
    try:
        return PUBLISHED_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add its "
            f"row (with source) to telemetry.PUBLISHED_PEAKS — known: "
            f"{sorted(PUBLISHED_PEAKS)}") from None


def peak_flops_per_sec() -> float:
    """Hardware peak used as the MFU denominator.

    Precedence: ``PADDLE_TPU_PEAK_FLOPS`` env (e.g. per-chip bf16 peak
    of the actual slice) > the calibration DB's fitted effective peak
    (``telemetry.calibration``, written by ``bench_collectives --suite
    calibrate``) > on TPU the published bf16 peak of the device kind
    (:func:`published_peak`; an unknown kind raises), and a nominal
    1 TFLOP/s elsewhere so the cost model has a rate to plan with on
    CPU test meshes.
    """
    env = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    fitted = calibration.peak_flops_override()
    if fitted is not None:
        return fitted
    import jax
    if jax.default_backend() != "tpu":
        return 1e12
    return published_peak(
        jax.devices()[0].device_kind)["bf16_flops_per_sec"]
