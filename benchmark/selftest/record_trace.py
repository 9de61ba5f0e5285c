"""Record the small traces that ``trace_reduce`` is checked against.

    chiprun --chips <1|4> -- python3 benchmark/selftest/record_trace.py <1|4>

A few steps of a two-layer GPT (hidden 768, 12 heads, 1,024 positions,
vocab 8,192; 4 rows a chip) through the same kind, family and spans as
a cell, under the profiler, on 1 or 4 chips. The trace is written,
gzipped, to ``chiprun_out/selftest/trace_<n>chip.xplane.pb.gz``; the
copies under ``benchmark/selftest/data/`` were recorded this way on a
TPU v5e in PR 22 and ``tests/benchmark_selftest`` holds the reducers to
values worked out from them by hand.
"""
import time

T0 = time.perf_counter()

import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import harness, manifest  # noqa: E402


def selftest_cell(chips: int) -> dict:
    man = manifest.Manifest()
    cell = man.cell("gpt2-small.seq1024.dp4" if chips == 4
                    else "gpt2-small.seq1024")
    cell["name"] = f"selftest.{chips}chip"
    cell["config"] = dict(cell["config"], n_layer=2, vocab_size=8192,
                          vocab_used=8192, eos_token_id=8191)
    cell["traffic"] = dict(cell["traffic"], pool_batches=4)
    cell["workload"] = dict(cell["workload"], rows_per_chip=4, sync_every=2,
                            warmup_steps=2, trace_steps=4)
    return cell


def main(chips: int) -> int:
    import jax

    harness.use_compile_cache(jax)
    cell = selftest_cell(chips)
    ctx = harness.Context(cell, 0, 0.0, True, T0, jax)
    problem = harness.tpu_problem(ctx.device, chips)
    if problem:
        print(f"record_trace: {problem}", file=sys.stderr)
        return 1
    result = manifest.plugin("kinds", "train").run(ctx)
    out = os.path.join(manifest.ROOT, "chiprun_out", "selftest")
    os.makedirs(out, exist_ok=True)
    target = os.path.join(out, f"trace_{chips}chip.xplane.pb.gz")
    with open(result["xplane"], "rb") as src, \
            gzip.open(target, "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    print(json.dumps({"recorded": target, "bytes": os.path.getsize(target),
                      "steps": result["steps"], "correct": result["correct"],
                      "device": ctx.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1])))
