"""Device time per step of the ops whose trace name matches ``pattern``:
the length of the union of their intervals inside the window, averaged
over the cell's chips, over the steps, in milliseconds. On one line ops
do not overlap, so there the union is the sum; over ``lines`` that
overlap (an asynchronous pair and the op that waits for it) it counts
each instant once."""
from benchmark import trace_reduce


def reduce(reading, pattern: str, lines=(trace_reduce.OPS_LINE,)):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    total = sum(trace_reduce.length(
        trace_reduce.matching(trace, chip, pattern, lines))
        for chip in trace.devices)
    return total / len(trace.devices) / reading.steps / 1e6
