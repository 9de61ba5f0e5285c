"""From a jax profiler trace (``*.xplane.pb``) to intervals and numbers.

What the trace of a TPU v5e holds (read off a recorded trace, PR 22):

- one plane ``/device:TPU:<n>`` per chip. Its line ``XLA Ops`` has one
  event per executed HLO instruction, never overlapping, named by the
  instruction's full text (``%fusion.28 = bf16[...] fusion(...), ...``);
  ``Async XLA Ops`` has one event per asynchronous pair (a copy or a
  collective from its ``-start`` to its ``-done``), overlapping the ops;
  ``XLA Modules`` has one event per executed program;
- one plane ``/host:CPU`` with a line per thread. ``TraceAnnotation``
  spans of the benchmark's loop are on the line ``python``. Host and
  device clocks agree to about a millisecond.

Times are nanoseconds on the trace's clock. An interval is ``(start,
end)``; a list of intervals is "merged" when sorted and disjoint.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Trace:
    # chip ordinal -> line name -> [(start, end, name)]
    devices: dict = field(default_factory=dict)
    # the benchmark's own host spans, sorted by start: [(start, end, name)]
    spans: list = field(default_factory=list)


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read one ``.xplane.pb`` with nothing but jax."""
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events]
                if events:
                    lines[line.name] = events
            if lines:
                trace.devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith(span_prefix))
    trace.spans.sort()
    return trace


# -- interval arithmetic ------------------------------------------------------

def union(intervals) -> list:
    """Merged form of any list of intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo, hi) -> list:
    """Where nothing of ``merged`` runs inside ``[lo, hi]``."""
    return subtract([(lo, hi)], clip(merged, lo, hi))


def attribute(idle, spans) -> dict:
    """Idle time by what the host was doing: each gap is split over the
    host spans that overlap it (the later-started span wins where two
    overlap), the rest goes to ``"(no span)"``. ``{name: ns}``."""
    out = {}
    for s, e in idle:
        left = [(s, e)]
        for ss, se, name in sorted(spans, key=lambda x: -x[0]):
            if se <= s or ss >= e:
                continue
            got = clip(left, ss, se)
            if got:
                out[name] = out.get(name, 0.0) + length(got)
                left = subtract(left, got)
        if left:
            out["(no span)"] = out.get("(no span)", 0.0) + length(left)
    return out


# -- what the metrics read ----------------------------------------------------

def window(trace: Trace):
    """The traced loop: first to last of the benchmark's host spans."""
    if not trace.spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    return trace.spans[0][0], max(e for _, e, _ in trace.spans)


def matching(trace: Trace, chip: int, pattern: str, lines=(OPS_LINE,),
             invert: bool = False) -> list:
    """Merged intervals, inside the window, of the chip's events on
    ``lines`` whose name matches (or, inverted, does not match)."""
    rx = re.compile(pattern)
    lo, hi = window(trace)
    found = [(s, e) for line in lines
             for s, e, name in trace.devices[chip].get(line, ())
             if bool(rx.search(name)) != invert]
    return clip(union(found), lo, hi)


def busy(trace: Trace, chip: int) -> list:
    """When an operation ran on the chip's core: the union of its
    ``XLA Ops`` events. Asynchronous copies overlap these and are not
    counted as the core being busy."""
    return matching(trace, chip, "")


def busy_and_window_s(trace: Trace):
    """``(busy_s, window_s)``: busy seconds averaged over the chips that
    ran anything, and the length of the traced loop."""
    lo, hi = window(trace)
    per_chip = [length(busy(trace, c)) for c in trace.devices]
    if not per_chip:
        return 0.0, (hi - lo) / 1e9
    return sum(per_chip) / len(per_chip) / 1e9, (hi - lo) / 1e9


_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")


def op_label(name: str) -> str:
    """A trace op name short enough to print: the instruction's own name,
    its result shapes without layouts, its opcode and fusion kind.
    ``%fusion.485 = (bf16[8,1024], bf16[8,1024,50304]) fusion kOutput``"""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    rest = _LAYOUT.sub("", rest)          # layouts and /*index=5*/ marks
    # the result type is a balanced (...) tuple or a single token; the
    # opcode is the word before the first "(" that follows it
    depth, i = 0, 0
    while i < len(rest):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
        elif rest[i] == " " and depth == 0:
            break
        i += 1
    result, tail = rest[:i], rest[i + 1:]
    opcode = tail.split("(", 1)[0]
    kind = re.search(r"kind=(\w+)", tail)
    target = re.search(r'custom_call_target="([^"]+)"', tail)
    extra = kind.group(1) if kind else (target.group(1) if target else "")
    return " ".join(x for x in (head, "=", result[:70], opcode, extra) if x)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (seconds inside the
    window, averaged over chips) and the idle time by what the host was
    doing, each at most ``top`` entries, longest first."""
    lo, hi = window(trace)
    n = max(len(trace.devices), 1)
    ops, idle = {}, {}
    for chip, lines in trace.devices.items():
        for s, e, name in lines.get(OPS_LINE, ()):
            s, e = max(s, lo), min(e, hi)
            if e > s:                  # by full name: every step repeats it
                ops[name] = ops.get(name, 0.0) + (e - s)
        for name, ns in attribute(gaps(busy(trace, chip), lo, hi),
                                  trace.spans).items():
            idle[name] = idle.get(name, 0.0) + ns

    def ranked(d, label=str):
        return [[label(k), v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": ranked(ops, op_label), "idle_gaps": ranked(idle)}
