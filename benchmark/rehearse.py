"""Walk a cell's code on the CPU at toy size, to debug the benchmark
without spending chip time.

    python3 benchmark/rehearse.py --workload <name> [--seed <n>]

The same manifest, files, family, kind, traffic generator and
comparisons as ``run.py``, with the sizes the kind's and the family's
``toy()`` give, on ``chips`` forced host devices. It prints counts and
the comparisons' numbers, never a time or a rate, its last line says
``"correct": false`` and ``"rehearsal": true``, and it exits 2 when it
walked through and 1 when it broke: a rehearsal is never a result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import harness, manifest  # noqa: E402

# what a rehearsal may say of a detail line: counts and the comparisons'
# numbers, nothing measured by a clock
_TIMED = ("t", "window_s", "group_s_min_median_max", "stall_s",
          "tokens_per_s_per_chip_of_the_total")


def walk(workload: str, seed: int = 0) -> dict:
    """One rehearsal in this process (jax must see the CPU, with as many
    devices as the cell has chips). Returns the last line as a dict,
    with the detail lines under ``"details"``."""
    import jax

    cell = manifest.Manifest().cell(workload)
    kind = manifest.plugin("kinds", cell["workload"]["kind"])
    ctx = harness.Context(cell, seed, 0.0, False, T0, jax, rehearsal=True)
    if ctx.device["platform"] != "cpu":
        raise RuntimeError(f"a rehearsal runs on the CPU, jax found "
                           f"{ctx.device}")
    details = []
    ctx.say = lambda **f: details.append(
        {k: v for k, v in f.items() if k not in _TIMED})
    result = kind.run(ctx)
    return {"rehearsal": True, "correct": False,
            "walked_through": bool(result["correct"]),
            "attempted": result["attempted"], "failed": result["failed"],
            "counts": {"steps": result["steps"], **result["counters"]},
            "would_report": sorted(result["end_to_end"]),
            "device": ctx.device, "details": details}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    chips = manifest.Manifest().cell(args.workload)["entry"]["chips"]
    # before jax starts: the CPU, as many devices as the cell has chips
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}").strip()
    try:
        line = walk(args.workload, args.seed)
    except Exception:
        traceback.print_exc()
        return 1
    for detail in line.pop("details"):
        print(json.dumps(detail))
    print(json.dumps(line))
    return 2 if line["walked_through"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
