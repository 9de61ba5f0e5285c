"""A kernel's share of its roofline, in percent, as ``roofline_share``
computes it, for costs and times that reducer cannot reach: the cost
function ``kernel`` comes from the module ``benchmark/<costs>.py``, and the
measured time is either the device time of the ops whose trace name
matches ``pattern`` (``sum_per_step``) or of the step program's ops staged
under any of ``scopes``, names as ``named_scopes_per_step`` takes them.
Returns None where nothing ran to be measured."""
import importlib

from benchmark.reducers import named_scopes_per_step, sum_per_step


def reduce(reading, costs: str, kernel: str, pattern: str = None,
           scopes: str = None):
    if (pattern is None) == (scopes is None):
        raise ValueError("give one of pattern and scopes")
    if pattern is not None:
        ms = sum_per_step.reduce(reading, pattern)
    else:
        ms = named_scopes_per_step.reduce(reading, scopes)
        if isinstance(ms, tuple):          # (value or None, note)
            ms = ms[0]
    if not ms:
        return None
    cost = getattr(importlib.import_module(f"benchmark.{costs}"), kernel)(
        reading.config, reading.rows_per_chip, reading.seq)
    by_flops = cost["flops"] / reading.peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / reading.peaks["hbm_bytes_per_s"]
    least_s = max(by_flops, by_bytes)
    return 100.0 * least_s / (ms / 1e3), {
        "bound": "compute" if by_flops >= by_bytes else "memory",
        "least_ms": least_s * 1e3, "measured_ms": ms,
        "flops_per_step": cost["flops"], "bytes_per_step": cost["bytes"]}
