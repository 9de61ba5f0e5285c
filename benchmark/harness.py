"""What every kind of cell shares: arguments, the device check, the
compile cache, the compile counter, the per-layer readers and the one
result line."""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

from benchmark import manifest, trace_reduce

OUT_DIR = ".bench_out"        # under the checkout, git-ignored


class CompileCounter:
    """Counts XLA backend compilations anywhere in the process through
    jax's own monitoring events (the idea is chip_smoke.py's)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


class Context:
    """One run: the cell's files, the arguments, the devices, and the
    one way a detail line reaches the output."""

    def __init__(self, cell, seed, seconds, trace, t0, jax, rehearsal=False):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.t0, self.rehearsal = trace, t0, rehearsal
        self.devices = jax.devices()
        first = self.devices[0]
        self.device = {"platform": first.platform, "kind": first.device_kind,
                       "count": len(self.devices)}
        self.peaks = (manifest.peaks(first.device_kind)
                      if first.platform == "tpu" else None)
        self.compiles = CompileCounter(jax)

    def say(self, **fields):
        """A detail line: JSON on stderr. Stdout carries the result line
        and nothing else, so a run that breaks prints no result."""
        fields["t"] = time.perf_counter() - self.t0
        print(json.dumps(fields), file=sys.stderr, flush=True)

    def fresh_dir(self, name: str) -> str:
        path = os.path.join(manifest.ROOT, OUT_DIR, self.cell["name"], name)
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
        return path


def tpu_problem(device: dict, chips: int):
    """None where jax found the TPUs a cell asks for, else what it found."""
    if device["platform"] == "tpu" and device["count"] >= chips:
        return None
    return (f"needs {chips} TPU chip(s); jax found {device} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). Nothing was run; "
            f"benchmark/rehearse.py walks a cell at toy size on the CPU.")


def use_compile_cache(jax) -> str:
    """jax's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache`` -
    one fixed path, because the path is part of what an entry is found
    by. Every program is kept, however quick to compile: building a
    model runs many small ones."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(manifest.ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def hbm_peak_bytes(devices) -> int:
    """The peak on the fullest chip, from what the TPU runtime reports.

    ``peak_bytes_in_use`` counts live buffers only: it read 2.50 GB for
    GPT-2 small at 16 rows and at 32 (my chip run, PR 22), the moment
    the trainer was built. The scratch memory a loaded program holds is
    ``bytes_reserved`` (7.5 GB and 14.6 GB there, what the compiler's
    memory analysis gives as temp). So the peak is the larger of the
    two moments the counters show: the most live buffers ever, and
    the live buffers now plus the most scratch ever reserved."""
    def of(stats):
        return max(stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return max(of(d.memory_stats() or {}) for d in devices)


class Reading:
    """What a per-layer reader reads: the trace of the window, the step
    count, the counters the harness kept, and the cell's sizes."""

    def __init__(self, result, ctx):
        self.trace = (trace_reduce.load(result["xplane"])
                      if result.get("xplane") else None)
        self.steps = result["steps"]
        self.counters = result["counters"]
        self.config = result["config"]
        self.rows_per_chip = result["rows_per_chip"]
        self.seq = result["seq"]
        self.peaks = ctx.peaks


def layer_metrics(man, cell, reading, ctx) -> dict:
    """Each per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell["per_layer"]:
        spec = man.layer_metric(m["name"])
        reducer = manifest.plugin("reducers", spec["reducer"])
        value = reducer.reduce(reading, **spec.get("args", {}))
        if isinstance(value, tuple):
            value, note = value
            ctx.say(event="layer_metric", metric=m["name"], value=value,
                    **note)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv, t0) -> int:
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    man = manifest.Manifest()
    cell = man.cell(args.workload)
    kind = manifest.plugin("kinds", cell["workload"]["kind"])

    import jax

    cache = use_compile_cache(jax)
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), t0, jax)
    problem = tpu_problem(ctx.device, cell["entry"]["chips"])
    if problem:
        print(f"benchmark: {args.workload} {problem}", file=sys.stderr)
        return 1
    ctx.say(event="start", workload=args.workload, seed=args.seed,
            seconds=args.seconds, trace=args.trace, device=ctx.device,
            compile_cache=cache,
            cache_entries=len(os.listdir(cache)) if os.path.isdir(cache)
            else 0)

    try:
        result = kind.run(ctx)
    except Exception:
        traceback.print_exc()
        return 1

    device = dict(ctx.device,
                  memory_peak_bytes=result["memory_peak_bytes"])
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        reading = Reading(result, ctx)
        line["metrics"] = layer_metrics(man, cell, reading, ctx)
        busy_s, window_s = trace_reduce.busy_and_window_s(reading.trace)
        device.update(busy_s=busy_s, window_s=window_s)
        line["breakdown"] = trace_reduce.breakdown(reading.trace)
    else:
        missing = [m["name"] for m in cell["end_to_end"]
                   if m["name"] not in result["end_to_end"]]
        if missing:
            print(f"benchmark: the {cell['workload']['kind']} kind did not "
                  f"measure {missing}", file=sys.stderr)
            return 1
        line["metrics"] = {
            m["name"]: {"value": result["end_to_end"][m["name"]],
                        "unit": m["unit"]} for m in cell["end_to_end"]}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0
