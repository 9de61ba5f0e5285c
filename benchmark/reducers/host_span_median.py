"""The median length of the benchmark's host spans named ``span`` in the
traced window, in milliseconds."""
import statistics


def reduce(reading, span: str):
    if reading.trace is None:
        return None
    found = [e - s for s, e, name in reading.trace.spans if name == span]
    return statistics.median(found) / 1e6 if found else None
