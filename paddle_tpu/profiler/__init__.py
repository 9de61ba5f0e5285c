"""paddle_tpu.profiler — host event tracing + device (XLA) profiling.

Capability map (reference, not copied):
- ``RecordEvent`` RAII host ranges     ← platform/profiler.h:127 RecordEvent
- ``start_profiler``/``stop_profiler`` ← fluid/profiler.py:190,257 and
  platform/profiler.h:213 EnableProfiler/DisableProfiler
- ``profiler`` context manager         ← fluid/profiler.py:314
- device tracing                       ← platform/device_tracer.h:43 (CUPTI);
  here the device side is jax.profiler (XPlane/TensorBoard) — XLA already
  correlates host/device, so no hand-rolled CUPTI analogue is needed.
- chrome-trace export                  ← tools/timeline.py (proto → chrome);
  here host events are written directly in the chrome://tracing JSON format.

Host events nest via a thread-local stack; each event also opens a
``jax.profiler.TraceAnnotation`` named ``paddle_tpu.<name>``, so the range
shows up in the host plane of a jax profiler trace, on the same clock as
the device's ops. It opens no ``jax.named_scope``: a host range is not a
staging scope, and one that is open while a step is first traced would
write itself into that program's op names.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import defaultdict
from typing import Optional

import jax

__all__ = [
    "RecordEvent", "record_event", "start_profiler", "stop_profiler",
    "reset_profiler", "profiler", "is_profiler_enabled", "export_chrome_tracing",
    "snapshot_events", "thread_names",
]

_state = threading.local()
_lock = threading.Lock()
_enabled = False
_events = []          # completed: (name, parent_path, start_ns, end_ns, tid)
_tid_names = {}       # tid -> thread name at record time (export metadata)
_trace_dir = None     # jax.profiler output dir when device tracing is on
_start_wall_ns = 0
_session = 0          # bumped by start/stop; pairs RecordEvent begin/end


def _stack():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


def is_profiler_enabled() -> bool:
    return _enabled


class RecordEvent:
    """Named host range; usable as context manager or start()/end() pair.

    reference: platform/profiler.h:127 (RAII RecordEvent) and the public
    paddle.profiler.RecordEvent of later versions.

    Pair-safe across profiler state changes: each ``begin()`` captures the
    profiler session it started in, and ``end()`` only records the range
    if the SAME session is still active — a start/stop between the pair
    silently drops the range instead of writing garbage timestamps into
    the new session. The nesting stack holds the event objects themselves
    (removed by identity), so an ``end()`` arriving out of LIFO order can
    never pop another event's entry; the ``TraceAnnotation`` is always
    exited iff it was entered.
    """

    def __init__(self, name: str):
        self.name = name
        self._t0 = None
        self._span = None
        self._session = None

    def begin(self):
        if _enabled:
            self._session = _session
            self._t0 = time.perf_counter_ns()
            _stack().append(self)
            self._span = jax.profiler.TraceAnnotation(
                "paddle_tpu." + self.name)
            self._span.__enter__()
        return self

    def end(self):
        if self._t0 is None:
            return
        t1 = time.perf_counter_ns()
        stack = _stack()
        try:
            stack.remove(self)
        except ValueError:
            pass  # stack was cleared by a profiler restart
        if _enabled and self._session == _session:
            parent = "/".join(e.name for e in stack
                              if e._session == _session)
            cur = threading.current_thread()
            with _lock:
                _events.append((self.name, parent, self._t0, t1, cur.ident))
                _tid_names[cur.ident] = cur.name
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        self._t0 = None
        self._session = None

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False


@contextlib.contextmanager
def record_event(name: str):
    ev = RecordEvent(name)
    ev.begin()
    try:
        yield ev
    finally:
        ev.end()


def reset_profiler():
    """reference: fluid/profiler.py:168."""
    global _events
    with _lock:
        _events = []


def start_profiler(state: str = "All", tracer_option: str = "Default",
                   trace_dir: Optional[str] = None):
    """Enable host-event recording; if ``state`` includes the device
    ("GPU"/"TPU"/"All") also start jax.profiler device tracing.

    reference: fluid/profiler.py:190 (states CPU/GPU/All).
    """
    global _enabled, _trace_dir, _start_wall_ns, _session
    if state not in ("CPU", "GPU", "TPU", "All"):
        raise ValueError(f"state must be CPU/GPU/TPU/All, got {state}")
    reset_profiler()
    _session += 1  # invalidate RecordEvents begun before this point
    _start_wall_ns = time.perf_counter_ns()
    _enabled = True
    if state in ("GPU", "TPU", "All") and tracer_option != "HostOnly":
        _trace_dir = trace_dir or os.path.join(
            os.getcwd(), "profiler_output")
        try:
            jax.profiler.start_trace(_trace_dir)
        except Exception:   # already tracing / backend without profiler
            _trace_dir = None


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: str = "/tmp/profile",
                  verbose: bool = True):
    """Disable recording; print a summary table sorted by ``sorted_key``
    (total/calls/max/min/ave) and write chrome tracing json to
    ``profile_path``. ``verbose=False`` suppresses the summary print
    (telemetry.scope stops the profiler quietly and exports its own
    merged trace).

    reference: fluid/profiler.py:257.
    """
    global _enabled, _trace_dir, _session
    if not _enabled:
        return
    _enabled = False
    _session += 1  # RecordEvents still open will not record into the next run
    if _trace_dir is not None:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _trace_dir = None
    if profile_path:
        try:
            export_chrome_tracing(profile_path)
        except OSError:
            pass
    if verbose:
        _print_summary(sorted_key)


def _aggregate():
    agg = defaultdict(lambda: [0, 0.0, 0.0, float("inf")])  # calls,total,max,min
    with _lock:
        events = list(_events)
    for name, parent, t0, t1, _tid in events:
        ms = (t1 - t0) / 1e6
        key = f"{parent}/{name}" if parent else name
        a = agg[key]
        a[0] += 1
        a[1] += ms
        a[2] = max(a[2], ms)
        a[3] = min(a[3], ms)
    return agg


def _print_summary(sorted_key):
    agg = _aggregate()
    if not agg:
        return
    rows = [(k, c, tot, tot / c, mx, mn)
            for k, (c, tot, mx, mn) in agg.items()]
    order = {"total": 2, "calls": 1, "ave": 3, "max": 4, "min": 5}
    rows.sort(key=lambda r: r[order.get(sorted_key or "total", 2)],
              reverse=True)
    name_w = max(len(r[0]) for r in rows)
    name_w = max(name_w, len("Event"))
    print(f"{'Event':<{name_w}}  {'Calls':>7} {'Total(ms)':>11} "
          f"{'Avg(ms)':>9} {'Max(ms)':>9} {'Min(ms)':>9}")
    for name, calls, tot, ave, mx, mn in rows:
        print(f"{name:<{name_w}}  {calls:>7} {tot:>11.3f} {ave:>9.3f} "
              f"{mx:>9.3f} {mn:>9.3f}")


def snapshot_events():
    """Raw completed events + the session start timestamp, for exporters
    that merge host ranges with other timelines (telemetry.export)."""
    with _lock:
        return list(_events), _start_wall_ns


def thread_names():
    """tid -> thread-name map observed while recording (chrome ``ph:"M"``
    thread_name metadata in the merged export)."""
    with _lock:
        return dict(_tid_names)


def export_chrome_tracing(path: str):
    """Write completed host events as chrome://tracing JSON (the reference
    reaches the same format via tools/timeline.py over profiler.proto).

    The time origin is the EARLIEST of the session start and any recorded
    event's begin — events that slipped in from before ``start_profiler``
    reset ``_start_wall_ns`` must not produce negative timestamps (chrome
    silently drops those)."""
    events, start_ns = snapshot_events()
    base = min([start_ns] + [t0 for _n, _p, t0, _t1, _tid in events])
    trace = []
    for name, parent, t0, t1, tid in events:
        trace.append({
            "name": name, "cat": "host", "ph": "X",
            "ts": (t0 - base) / 1e3,
            "dur": (t1 - t0) / 1e3,
            "pid": os.getpid(), "tid": tid,
            "args": {"parent": parent} if parent else {},
        })
    with open(path, "w") as f:
        json.dump({"traceEvents": trace}, f)


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: str = "/tmp/profile", tracer_option: str = "Default"):
    """reference: fluid/profiler.py:314 — the `with profiler(...)` guard."""
    start_profiler(state, tracer_option=tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key=sorted_key, profile_path=profile_path)


def get_events():
    """Completed host events as dicts (for tests / tooling)."""
    with _lock:
        return [dict(name=n, parent=p, dur_ms=(t1 - t0) / 1e6, tid=tid)
                for n, p, t0, t1, tid in _events]
