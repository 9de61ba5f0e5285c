"""Where set-up went, from the program's own staging record
(``paddle_tpu.telemetry.staging``: jax's trace, lower and compile of
every function and the trainer's construction spans, kept always, on
``time.time()``). Reducers run after the run in the process that ran
the cell, so the record is this run's.

``read`` picks the number; each comes with a note:

``step_trace_s`` / ``step_lower_s`` / ``step_compile_s``
    seconds of that phase of ``train_step``, all its programs; the note
    gives the seconds a program and, for the compile, the persistent
    cache's hits, misses and retrieval seconds.
``step_programs``
    how many programs of ``train_step`` were compiled or loaded, and in
    which ``train_step`` call each.
``trainer_init_s``
    the spans ``paddle_tpu.trainer.init_state`` + ``.build``, split, with
    the programs jax staged inside each.
``other_staging_s``
    trace + lower + compile of every other function outside those two
    spans that ended before the step's last program was ready (after
    that come the window and the comparisons), with the count of
    programs and the five functions that took longest.

The five in seconds share no interval: a function's phases follow one
another, a staging inside another has no entry of its own, and what ran
inside the trainer's two spans is counted there. None where the program
keeps no such record, or holds nothing of the step.
"""
from collections import defaultdict

STEP = "train_step"
STEP_PHASES = {"step_trace_s": "trace", "step_lower_s": "lower",
               "step_compile_s": "compile"}
INIT_SPANS = ("paddle_tpu.trainer.init_state", "paddle_tpu.trainer.build")


def reduce(reading, read: str):
    try:
        from paddle_tpu.telemetry import staging
    except ImportError:         # a program from before the record
        return None
    entries = staging.entries()
    step = [e for e in entries if e["fun"] == STEP and e["phase"] != "span"]
    if not step:
        return None
    if read in STEP_PHASES:
        return _step_phase(step, STEP_PHASES[read], staging.summary()[STEP])
    if read == "step_programs":
        return staging.summary()[STEP]["programs"], {
            "staged_in_steps": [e.get("step") for e in step
                                if e["phase"] == "compile"]}
    if read == "trainer_init_s":
        return _trainer_init(entries)
    if read == "other_staging_s":
        ready = [e["end"] for e in step if e["phase"] == "compile"]
        return _other(entries, max(ready)) if ready else None
    raise ValueError(f"staging_record reads no {read!r}")


def _seconds(entries) -> float:
    return sum(e["end"] - e["start"] for e in entries)


def _step_phase(step, phase, totals):
    mine = [e for e in step if e["phase"] == phase]
    note = {"seconds_a_program": [e["end"] - e["start"] for e in mine]}
    if phase == "compile":
        note.update({k: totals[k] for k in (
            "cache_hits", "cache_misses", "cache_retrieval_s",
            "compile_saved_s")})
    return totals[phase + "_s"], note


def _trainer_init(entries):
    spans = {name: [e for e in entries
                    if e["phase"] == "span" and e["fun"] == name]
             for name in INIT_SPANS}
    if not any(spans.values()):
        return None
    note = {}
    for name, found in spans.items():
        inside = [e for e in entries if e.get("span") == name]
        note[name.rsplit(".", 1)[1]] = {
            "seconds": _seconds(found),
            "programs_staged": sum(e["phase"] == "compile" for e in inside),
            "staging_seconds": _seconds(inside)}
    return sum(_seconds(found) for found in spans.values()), note


def _other(entries, ready):
    other = [e for e in entries
             if e["phase"] != "span" and e["fun"] != STEP
             and e.get("span") not in INIT_SPANS and e["end"] <= ready]
    by_fun = defaultdict(float)
    for e in other:
        by_fun[e["fun"]] += e["end"] - e["start"]
    longest = sorted(by_fun.items(), key=lambda kv: -kv[1])[:5]
    return _seconds(other), {
        "programs": sum(e["phase"] == "compile" for e in other),
        "functions": len(by_fun),
        "longest_s": dict(longest),
        "by_phase_s": {p: _seconds(e for e in other if e["phase"] == p)
                       for p in ("trace", "lower", "compile")},
        "first_staging_to_step_ready_s":
            ready - min(e["start"] for e in entries)}
