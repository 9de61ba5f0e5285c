"""The part of the matching ops' time during which no other op runs on
that chip: the union of their intervals (over ``lines``) minus the
union of every non-matching op of the core's own line, averaged over
the chips, per step, in milliseconds. For a collective this is the
communication that compute does not hide."""
from benchmark import trace_reduce


def reduce(reading, pattern: str, lines=(trace_reduce.OPS_LINE,)):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    total = 0.0
    for chip in trace.devices:
        ours = trace_reduce.matching(trace, chip, pattern, lines)
        others = trace_reduce.matching(trace, chip, pattern, invert=True)
        total += trace_reduce.length(trace_reduce.subtract(ours, others))
    return total / len(trace.devices) / reading.steps / 1e6
