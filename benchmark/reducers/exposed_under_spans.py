"""Idle device time that the program's own host spans cover: where no op
runs on a chip inside the window (``trace_reduce.gaps``), split over the
program's spans whose name starts with ``prefix`` (``attribute``: the
later-started span wins), per step, mean over chips, in milliseconds.
What the chip waits for while the host is inside those spans. The note
splits it by span. None where the program opens no such span."""
from benchmark import trace_reduce, xplane_scopes


def reduce(reading, prefix: str):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    spans = [x for x in xplane_scopes.for_reading(reading).spans
             if x[2].startswith(prefix)]
    if not spans:
        return None
    lo, hi = trace_reduce.window(trace)
    by_span = {}
    for chip in trace.devices:
        idle = trace_reduce.gaps(trace_reduce.busy(trace, chip), lo, hi)
        for name, ns in trace_reduce.attribute(idle, spans).items():
            if name != "(no span)":
                by_span[name] = by_span.get(name, 0.0) + ns
    per_step_ms = 1.0 / len(trace.devices) / reading.steps / 1e6
    return sum(by_span.values()) * per_step_ms, {
        "by_span_ms": {k: v * per_step_ms for k, v in by_span.items()}}
