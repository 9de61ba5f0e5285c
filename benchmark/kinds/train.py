"""The loop of a ``"kind": "train"`` cell.

A user's training loop, no more: take the next global batch from the
pool (host memory), call ``ParallelTrainer.train_step`` back to back
with the program's telemetry off, and fetch the losses every
``sync_every`` steps, as a job that logs does. The clock stops after
the fetch, so every whole sync group is device work that has finished.
The transfer of each batch to the device is inside. The rate is that of
the window's median sync group.

Set-up is everything before the window: the pool, the model and
trainer from ``--seed``, and ``warmup_steps`` steps that compile the one
step program (or load it from the cache). The untraced run then
measures until the first group boundary at or after ``--seconds``; the
traced run records ``trace_steps`` steps under the profiler instead.
``correct`` is decided after the window and after the peak is read.
"""
from __future__ import annotations

import glob
import math
import os
import time

import numpy as np

from benchmark import compare, harness, manifest, traffic_gen


def run(ctx) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from paddle_tpu.distributed.mesh import build_mesh

    cell = ctx.cell
    w, config, mix = cell["workload"], cell["config"], cell["traffic"]
    family = manifest.plugin("families", config["family"])
    reference = manifest.plugin("reference", family.REFERENCE)
    if ctx.rehearsal:
        w, config, mix = toy(w, config, mix, family)
    recipe = {**config["run"], **w.get("run", {})}
    chips, sync, seq = w["chips"], w["sync_every"], mix["seq"]
    rows = w["rows_per_chip"] * chips
    devices = ctx.devices[:chips]

    # -- set-up ----------------------------------------------------------------
    pool_ids, pool_labels = traffic_gen.make_pool(
        mix, config["vocab_used"], config["eos_token_id"], rows, ctx.seed)
    mesh = build_mesh(w["mesh"], devices=devices)
    built = family.build(config, recipe, ctx.seed, mesh)
    trainer = built.trainer
    ctx.say(event="built", params=family.param_count(config), rows=rows,
            seq=seq, pool_batches=len(pool_ids))

    losses, attempted, failed, step = [], 0, 0, 0

    def group(n: int):
        """``n`` steps back to back, then one fetch of their losses."""
        nonlocal step, attempted, failed
        pending, before = [], attempted
        try:
            for _ in range(n):
                attempted += 1
                with TraceAnnotation("bench.next_batch"):
                    i = step % len(pool_ids)
                    args = built.step_args(pool_ids[i], pool_labels[i])
                with TraceAnnotation("bench.train_step_call"):
                    pending.append(trainer.train_step(*args))
                step += 1
            with TraceAnnotation("bench.sync"):
                values = [float(x) for x in pending]
        except Exception as e:  # none of this group's losses was seen
            failed += attempted - before
            ctx.say(event="step_failed", step=step, error=repr(e)[:2000])
            return None
        failed += sum(not math.isfinite(v) for v in values)
        losses.extend(values)
        return values

    if group(w["warmup_steps"]) is None:
        raise RuntimeError("a warm-up step failed; see the step_failed line")
    warm_losses = len(losses)
    attempted = failed = 0                       # the window's own count
    compiles_before = ctx.compiles.n
    setup_s = time.perf_counter() - ctx.t0

    # -- the window ------------------------------------------------------------
    trace_dir = None
    if ctx.trace:
        trace_dir = ctx.fresh_dir("trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the loop's spans are enough
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    start = end = time.perf_counter()
    group_s = []
    try:
        while True:
            if group(sync) is None:
                break
            now = time.perf_counter()
            group_s.append(now - end)
            end = now
            done = step - w["warmup_steps"]
            if ctx.trace or ctx.rehearsal:
                if done >= w["trace_steps"]:
                    break
            elif end - start >= ctx.seconds:
                break
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    steps = len(losses) - warm_losses            # whole groups only
    if steps == 0:
        raise RuntimeError("no sync group completed inside the window")
    compiles_in_window = ctx.compiles.n - compiles_before

    # -- what a user sees ------------------------------------------------------
    peak = harness.hbm_peak_bytes(devices)
    # the rate of the median sync group, not of the window's total: in 5
    # of 20 runs one group of the window stalled for 0.05 to 2.5 s (the
    # two cells that fill 13.8 GB; my chip runs, PR 22), which is 0.5 to
    # 24% of a 10 s window and nothing a change to the step moves. The
    # stall is in the detail line.
    window_s, median_s = end - start, float(np.median(group_s))
    tokens_per_s_per_chip = sync * rows * seq / median_s / chips
    flops = family.model_flops_per_token(config, seq)
    end_to_end = {"tokens_per_s_per_chip": tokens_per_s_per_chip,
                  "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
    if ctx.peaks is not None:
        end_to_end["mfu"] = (tokens_per_s_per_chip * flops["total"]
                             / ctx.peaks["bf16_flops_per_s"])
    ctx.say(event="window", steps=steps, window_s=window_s,
            group_s_min_median_max=[min(group_s), median_s, max(group_s)],
            stall_s=window_s - len(group_s) * median_s,
            tokens_per_s_per_chip_of_the_total=steps * rows * seq
            / window_s / chips,
            warmup_losses=losses[:warm_losses], first_losses=losses[
                warm_losses:warm_losses + sync], last_losses=losses[-sync:],
            compiles_in_window=compiles_in_window,
            model_flops_per_token=flops)

    # -- correct ---------------------------------------------------------------
    check = w["check"]
    params = dict(trainer.state["params"])
    first_leaf = next(iter(params))
    checks = {"losses_fall": compare.losses_fall(losses, sync,
                                                 check["loss_margin"]),
              "compiles_in_window": {"ok": compiles_in_window == 0,
                                     "count": compiles_in_window}}
    if chips > 1:
        checks["replicas_equal"] = compare.replicas_equal(
            params[first_leaf], chips)
        params = jax.device_put(params, devices[0])
    # the optimizer's state makes room for the float32 reference
    trainer.state = None
    rng = np.random.default_rng(ctx.seed)
    pick = rng.choice(len(pool_ids) * rows, check["sample_rows"],
                      replace=False)
    sample = (pool_ids.reshape(-1, seq)[pick],
              pool_labels.reshape(-1, seq)[pick])
    with jax.default_device(devices[0]):
        checks["reference"] = compare.against_reference(
            built, reference, params, *sample, check)
        del params
        if chips > 1:
            checks["one_chip_forward"] = compare.one_chip_forward(
                built, ctx.seed, pool_ids[0], pool_labels[0],
                w["rows_per_chip"], losses[0], check["one_chip_rtol"])
    for name, c in checks.items():
        ctx.say(event="check", check=name, **c)

    xplane = None
    if trace_dir:
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                               f"found {found}")
        xplane = found[0]
    return {"correct": all(c["ok"] for c in checks.values()) and failed == 0,
            "attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "memory_peak_bytes": peak,
            "xplane": xplane, "steps": steps,
            "counters": {"backend_compile": compiles_in_window},
            "rows_per_chip": w["rows_per_chip"], "seq": seq,
            "config": config}


def toy(w, config, mix, family):
    """The cell's files shrunk for the CPU rehearsal: the same keys, the
    same code, toy numbers."""
    config = family.toy(config)
    seq = max(16, mix["seq"] // 16)
    mix = {**mix, "seq": seq, "pool_batches": 4, "doc_length_median": 12,
           "doc_length_min": 2}
    run = dict(w.get("run", {}))
    if "loss_chunk" in {**config["run"], **run}:
        run["loss_chunk"] = 128
    # ten steps of a toy barely move the loss: the margin shrinks with it
    w = {**w, "rows_per_chip": 2, "warmup_steps": 2, "trace_steps":
         2 * w["sync_every"], "run": run,
         "check": {**w["check"], "loss_margin": 0.1}}
    return w, config, mix
