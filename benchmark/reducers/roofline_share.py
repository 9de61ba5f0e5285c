"""A kernel's share of its roofline, in percent: the least time the
chip could take for the calls' operations and bytes (the larger of
operations over the published peak FLOP/s and bytes over the published
peak bytes/s; both computed from the cell's shapes by the function
``kernel`` of ``benchmark/kernel_costs.py``) over the device time of
the ops matching ``pattern``. Says which of the two bounds applies."""
from benchmark import kernel_costs
from benchmark.reducers import sum_per_step


def reduce(reading, pattern: str, kernel: str):
    ms = sum_per_step.reduce(reading, pattern)
    if not ms:
        return None
    cost = getattr(kernel_costs, kernel)(
        reading.config, reading.rows_per_chip, reading.seq)
    by_flops = cost["flops"] / reading.peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / reading.peaks["hbm_bytes_per_s"]
    least_s = max(by_flops, by_bytes)
    return 100.0 * least_s / (ms / 1e3), {
        "bound": "compute" if by_flops >= by_bytes else "memory",
        "least_ms": least_s * 1e3, "measured_ms": ms,
        "flops_per_step": cost["flops"], "bytes_per_step": cost["bytes"]}
