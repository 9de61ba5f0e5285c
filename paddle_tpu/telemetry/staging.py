"""paddle_tpu.telemetry.staging — where a job's set-up seconds go.

jax hands every trace, lowering and backend compile to the listeners of
``jax.monitoring`` with the function's name, a start and an end
(``jax/_src/dispatch.py`` ``LogElapsedTimeContextManager``), and the
persistent compilation cache reports its hits, misses and retrieval
time the same way. This module is the bridge from those events into the
telemetry package: one listener, installed when ``paddle_tpu.telemetry``
is imported and always on, that keeps what was staged in this process
as a bounded list of entries and a total for each function. It answers
what an operator asks of a slow start: why did the job take three
minutes to reach step 1, and did step 2 compile again.

An entry is a dict::

    {"phase": "trace" | "lower" | "compile",
     "fun": "train_step", "start": <time.time()>, "end": <time.time()>,
     "program": 2}            # the function's n-th program

with ``"span"`` (the program's own span it ran under, see below),
``"step"`` (the ``train_step`` call that staged it) and the cache's
``"cache_hits"``, ``"cache_misses"``, ``"cache_retrieval_s"``,
``"compile_saved_s"`` where they apply. ``fun`` is jax's ``fun_name``
without its wrapper, so that ``train_step`` (traced), and
``jit(train_step)`` (lowered, compiled) are one function. A compile is a
backend compile or a load from the persistent cache; the cache's counts
tell the two apart.

**Nesting.** A staging that runs inside another on the same thread (the
flash kernels' ``jit(_fwd)`` traced while ``train_step`` is traced, an
eager ``jnp.zeros`` compiled under a trace) counts in the outer one
only: it gets no entry and its cache events go to the outer entry. One
function's phases therefore never add up to more than the wall time
they took. jax's enter events (``record_scalar`` with the event's name)
give the nesting.

**The program's own phases.** :class:`span` opens a
``jax.profiler.TraceAnnotation`` (the profiler's clock, beside the
device's ops in a trace that is running) and writes the same interval
into the record as an entry of phase ``"span"`` with the span's name as
``fun`` (and no ``program``). ``ParallelTrainer`` opens three:
``paddle_tpu.trainer.init_state``, ``paddle_tpu.trainer.build`` and
``paddle_tpu.trainer.make_step``. What jax stages inside one keeps its
own entry and names the span.

**Cost.** The listeners run where jax stages something and nowhere
else; a steady step stages nothing. :func:`programs` is one dict read.

While ``telemetry.enabled()`` the same numbers are published as
``staging_seconds_total{phase, fun}``, ``staging_programs_total{fun}``,
``compile_cache_hits_total`` and ``compile_cache_misses_total``; while
it is not, the registry stays empty and the record still fills.
"""
from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax

__all__ = ["PHASES", "MAX_ENTRIES", "span", "entries", "summary",
           "programs", "tag_step", "fun_of", "reset"]

#: jax's staging events and the phase each one is
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: the persistent cache's events (two counts, two durations) and the key
#: each one adds to in an entry and in a function's totals
_CACHE = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_saved_s",
}
#: entries kept; the totals of :func:`summary` outlive it
MAX_ENTRIES = 4096

_WRAPPER = re.compile(r"^\w+\((.*)\)$")        # jit(train_step), pmap(f)

_entries: deque = deque(maxlen=MAX_ENTRIES)
_totals: Dict[str, dict] = {}                  # fun -> summary()'s row
_programs: Dict[str, int] = {}                 # fun -> compiles so far
_lock = threading.Lock()


class _Thread(threading.local):
    """What is open on this thread, innermost last."""

    def __init__(self):
        self.frames = []        # jax's stagings: [event, fun_name, cache]
        self.spans = []         # the program's spans, by name


_thread = _Thread()


def fun_of(fun_name: str) -> str:
    """jax's ``fun_name`` without ``jit(...)`` / ``pmap(...)`` around it."""
    m = _WRAPPER.match(fun_name)
    while m:
        fun_name = m.group(1)
        m = _WRAPPER.match(fun_name)
    return fun_name


def _append(phase: str, fun: str, start: float, end: float, extra: dict):
    spans = _thread.spans
    with _lock:
        row = _totals.get(fun)
        if row is None:
            row = _totals[fun] = {"spans": 0, "span_s": 0.0} \
                if phase == "span" else {
                    "programs": 0, "trace_s": 0.0, "lower_s": 0.0,
                    "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0,
                    "cache_retrieval_s": 0.0, "compile_saved_s": 0.0}
        entry = {"phase": phase, "fun": fun, "start": start, "end": end}
        row[phase + "_s"] += end - start
        if phase == "span":
            row["spans"] += 1
        else:
            entry["program"] = _programs.get(fun, 0) + 1
            if spans:
                entry["span"] = spans[-1]
            if extra:
                entry.update(extra)
                for key, value in extra.items():
                    row[key] += value
            if phase == "compile":
                row["programs"] = _programs[fun] = entry["program"]
        _entries.append(entry)
    _publish(entry)


def _publish(entry: dict):
    from paddle_tpu import telemetry
    if not telemetry.enabled() or entry["phase"] == "span":
        return
    telemetry.counter(
        "staging_seconds_total",
        "seconds jax spent staging a function, outermost stagings only"
    ).inc(entry["end"] - entry["start"], phase=entry["phase"],
          fun=entry["fun"])
    if entry["phase"] == "compile":
        telemetry.counter(
            "staging_programs_total",
            "programs of a function the backend compiled or loaded from "
            "the persistent cache").inc(fun=entry["fun"])
    # the names spelled out where they are registered:
    # tools/check_metric_catalogue.py finds a series by its literal
    if entry.get("cache_hits"):
        telemetry.counter(
            "compile_cache_hits_total",
            "programs loaded from the persistent compilation cache"
        ).inc(entry["cache_hits"])
    if entry.get("cache_misses"):
        telemetry.counter(
            "compile_cache_misses_total",
            "programs compiled and written to the persistent compilation "
            "cache").inc(entry["cache_misses"])


# -- jax's side: the listeners ----------------------------------------------
def _on_enter(event: str, value, fun_name: str = "", **_):
    if event in PHASES:
        _thread.frames.append([event, fun_name, None])


def _on_span(event: str, start: float, end: float, fun_name: str = "", **_):
    if event not in PHASES:
        return
    frames = _thread.frames
    extra = None
    while frames:           # the top one, unless an exit went missing
        ev, name, extra = frames.pop()
        if ev == event and name == fun_name:
            break
    if not frames:          # outermost: this one counts
        _append(PHASES[event], fun_of(fun_name), start, end, extra)


def _on_cache(event: str, amount=1, **_):
    """A cache event (a count without ``amount``, a duration with it)
    goes to the outermost staging in flight."""
    key = _CACHE.get(event)
    frames = _thread.frames
    if key and frames:
        outer = frames[0]
        if outer[2] is None:
            outer[2] = {}
        outer[2][key] = outer[2].get(key, 0) + amount


jax.monitoring.register_scalar_listener(_on_enter)
jax.monitoring.register_event_time_span_listener(_on_span)
jax.monitoring.register_event_listener(_on_cache)
jax.monitoring.register_event_duration_secs_listener(_on_cache)


# -- the program's side ------------------------------------------------------
class span:
    """A phase of the program's own, as a ``jax.profiler.TraceAnnotation``
    and, with the same start and end, as an entry of phase ``"span"``::

        with staging.span("paddle_tpu.trainer.build") as s:
            ...
        s.seconds
    """

    __slots__ = ("name", "start", "end", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self.start = self.end = None

    def __enter__(self):
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        _thread.spans.append(self.name)
        self.start = time.time()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end = time.time()
        _thread.spans.pop()
        self._annotation.__exit__(exc_type, exc, tb)
        _append("span", self.name, self.start, self.end, None)
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start


def programs(fun: str) -> int:
    """How many programs of ``fun`` were compiled or loaded so far."""
    return _programs.get(fun, 0)


def tag_step(fun: str, after: int, step: int):
    """Give the entries of ``fun``'s programs beyond the ``after``-th the
    ``train_step`` call that staged them."""
    with _lock:
        for entry in reversed(_entries):
            if entry["fun"] != fun or entry["phase"] == "span":
                continue
            if entry["program"] <= after:
                break
            entry["step"] = step


def reset():
    """Forget what was staged so far (a test; a process that starts a
    second job and wants its set-up alone)."""
    with _lock:
        _entries.clear()
        _totals.clear()
        _programs.clear()


def entries(fun: Optional[str] = None) -> List[dict]:
    """The record, oldest first, as copies; of one function with ``fun``."""
    with _lock:
        return [dict(e) for e in _entries if fun is None or e["fun"] == fun]


def summary() -> Dict[str, dict]:
    """For each function: programs, seconds by phase, the cache's hits,
    misses, retrieval seconds and the compile seconds its hits saved;
    for each span its count and seconds. Exact however many entries the
    bounded list has let go."""
    with _lock:
        return {fun: dict(row) for fun, row in _totals.items()}
