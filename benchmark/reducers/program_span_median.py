"""The median length of the program's own host spans named ``span``
(``paddle_tpu.<name>``, on the profiler's clock) that start inside the
traced window, in milliseconds. None where the program opens no such
span."""
import statistics

from benchmark import trace_reduce, xplane_scopes


def reduce(reading, span: str):
    if reading.trace is None:
        return None
    lo, hi = trace_reduce.window(reading.trace)
    found = [e - s for s, e, name in xplane_scopes.for_reading(reading).spans
             if name == span and lo <= s <= hi]
    return statistics.median(found) / 1e6 if found else None
