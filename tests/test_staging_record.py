"""The staging record (``paddle_tpu.telemetry.staging``): jax's trace,
lower and compile events and the trainer's construction spans, kept
always, published into the registry only while telemetry is enabled."""
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, telemetry
from paddle_tpu.distributed.engine import ParallelTrainer
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.telemetry import staging
from paddle_tpu.telemetry.metrics import Registry

STAGED = ("trace", "lower", "compile")


@pytest.fixture
def fresh_registry():
    prev = telemetry.get_registry()
    reg = Registry()
    telemetry._set_registry(reg)
    yield reg
    telemetry._set_registry(prev)


def test_one_staging_gives_one_entry_a_phase_with_its_name():
    def staged_once_fn(x):
        return x * 2 + 1

    f = jax.jit(staged_once_fn)
    f(jnp.ones(3))
    f(jnp.ones(3))                      # the second call stages nothing
    found = staging.entries("staged_once_fn")
    assert [e["phase"] for e in found] == list(STAGED)
    assert all(e["fun"] == "staged_once_fn" and e["program"] == 1
               and e["end"] >= e["start"] for e in found)
    # a function's phases follow one another: they share no interval
    assert all(a["end"] <= b["start"] for a, b in zip(found, found[1:]))
    row = staging.summary()["staged_once_fn"]
    assert row["programs"] == staging.programs("staged_once_fn") == 1
    for e in found:
        assert row[e["phase"] + "_s"] == pytest.approx(e["end"] - e["start"])


@pytest.mark.parametrize("name,want", [
    ("train_step", "train_step"), ("jit(train_step)", "train_step"),
    ("pmap(jit(f))", "f"), ("<lambda>", "<lambda>")])
def test_the_wrapper_is_not_part_of_a_functions_name(name, want):
    assert staging.fun_of(name) == want


def test_a_nested_staging_counts_in_the_outer_function_only():
    @jax.jit
    def nested_inner_fn(x):
        return jnp.sin(x)

    def nested_outer_fn(x):
        return nested_inner_fn(x) + 1

    jax.jit(nested_outer_fn)(jnp.ones(3))
    assert staging.entries("nested_inner_fn") == []
    assert "nested_inner_fn" not in staging.summary()
    assert [e["phase"] for e in staging.entries("nested_outer_fn")] == \
        list(STAGED)


def test_another_pytree_type_is_a_second_program():
    def two_trees_fn(state):
        return {"w": state["w"] + 1}

    f = jax.jit(two_trees_fn)
    out = f(OrderedDict(w=jnp.ones(3)))       # an OrderedDict in ...
    f(out)                                    # ... a dict out: staged again
    f(out)
    assert staging.programs("two_trees_fn") == 2
    found = staging.entries("two_trees_fn")
    assert [(e["phase"], e["program"]) for e in found] == \
        [(p, n) for n in (1, 2) for p in STAGED]


def test_disabled_the_registry_stays_empty_and_the_record_fills(
        fresh_registry):
    assert not telemetry.enabled()

    def quiet_fn(x):
        return x - 1

    jax.jit(quiet_fn)(jnp.ones(2))
    assert fresh_registry.to_dict() == {}
    assert staging.programs("quiet_fn") == 1


def test_enabled_the_same_numbers_reach_the_registry(fresh_registry):
    def published_fn(x):
        return x * 3

    telemetry.enable()
    try:
        jax.jit(published_fn)(jnp.ones(2))
    finally:
        telemetry.disable()
    row = staging.summary()["published_fn"]
    seconds = fresh_registry.get("staging_seconds_total")
    for phase in STAGED:
        assert seconds.value(phase=phase, fun="published_fn") == \
            pytest.approx(row[phase + "_s"])
    assert fresh_registry.get("staging_programs_total").value(
        fun="published_fn") == 1


def test_cache_events_go_to_the_compile_in_flight():
    def cached_fn(x):
        return x

    # jax's own order of events around a load from the persistent cache
    compile_event = next(e for e, p in staging.PHASES.items()
                         if p == "compile")
    jax.monitoring.record_scalar(compile_event, 10.0,
                                 fun_name="jit(cached_fn)")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", 7.0)
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    jax.monitoring.record_event_time_span(compile_event, 10.0, 11.0,
                                          fun_name="jit(cached_fn)")
    (entry,) = staging.entries("cached_fn")
    assert entry["phase"] == "compile" and entry["cache_hits"] == 1
    assert entry["cache_retrieval_s"] == 0.5
    row = staging.summary()["cached_fn"]
    assert (row["cache_hits"], row["cache_misses"]) == (1, 0)
    assert row["compile_saved_s"] == 7.0 and row["compile_s"] == 1.0


def test_a_span_is_an_entry_and_names_what_was_staged_inside_it():
    def under_span_fn(x):
        return x + 2

    with staging.span("tests.staging.a_span") as s:
        jax.jit(under_span_fn)(jnp.ones(2))
    (entry,) = staging.entries("tests.staging.a_span")
    assert entry["phase"] == "span"
    assert (entry["start"], entry["end"]) == (s.start, s.end)
    assert s.seconds == entry["end"] - entry["start"] > 0
    inside = staging.entries("under_span_fn")
    assert [e["span"] for e in inside] == ["tests.staging.a_span"] * 3
    assert all(entry["start"] <= e["start"] and e["end"] <= entry["end"]
               for e in inside)


def _trainer():
    paddle.seed(7)

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            # a width no other test file's trainer has: jax stages the
            # leaves' copies once a process, and a file that ran before
            # this one in the same worker would have staged them already
            self.l1 = nn.Linear(16, 27)
            self.l2 = nn.Linear(27, 4)

        def forward(self, x):
            return self.l2(nn.functional.relu(self.l1(x)))

    model = MLP()
    opt = paddle.optimizer.Momentum(0.05, momentum=0.9,
                                    parameters=model.parameters())
    return ParallelTrainer(model, opt,
                           lambda out, y: jnp.mean((out - y) ** 2),
                           mesh=build_mesh({"data": 2}))


def test_a_trainer_fills_its_phases_and_counts_the_steps_programs(
        fresh_registry):
    seen, listening = [], [True]

    def independent(event, duration, fun_name="", **_):
        if listening and event.endswith("backend_compile_duration") and \
                fun_name == "jit(train_step)":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(independent)
    before = staging.programs("train_step")
    spans_before = {name: len(staging.entries(name)) for name in (
        "paddle_tpu.trainer.init_state", "paddle_tpu.trainer.build",
        "paddle_tpu.trainer.make_step")}
    rng = np.random.RandomState(3)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randn(8, 4).astype(np.float32)
    try:
        trainer = _trainer()
        for _ in range(3):
            trainer.train_step(x, y)
    finally:
        listening.clear()       # jax has no public way to take it off

    for name, n in spans_before.items():
        found = staging.entries(name)
        assert len(found) == n + 1 and found[-1]["phase"] == "span"
    # three calls, one program: the state goes in as it comes out
    staged = staging.programs("train_step") - before
    assert staged == len(seen) == 1
    # each program of the step carries the train_step call that staged it
    mine = [e for e in staging.entries("train_step")
            if e["program"] > before]
    assert {e["step"] for e in mine} == {1}
    assert sorted({e["program"] for e in mine if e["phase"] == "compile"}) \
        == list(range(before + 1, before + staged + 1))
    # jax staged the leaves' copies inside init_state, and says so
    assert any(e.get("span") == "paddle_tpu.trainer.init_state"
               for e in staging.entries())
    summary = trainer.staging_summary()
    assert summary["train_step"]["programs"] == before + staged
    assert summary["train_step"]["staged_in_steps"][-staged:] == \
        sorted(e["step"] for e in mine if e["phase"] == "compile")
    assert all(summary[name] > 0 for name in spans_before)
    # telemetry is off: none of this reached the registry
    assert fresh_registry.to_dict() == {}


def test_threads_stage_side_by_side_without_losing_a_count():
    """Each thread has its own nesting; the list and the totals are
    shared. 16 threads, 200 compiles each with a nested one inside."""
    import sys
    import threading

    compile_event = next(e for e, p in staging.PHASES.items()
                         if p == "compile")
    threads, each = 16, 200

    def work(i):
        name = f"jit(threaded_fn_{i})"
        for k in range(each):
            jax.monitoring.record_scalar(compile_event, float(k),
                                         fun_name=name)
            jax.monitoring.record_scalar(compile_event, float(k),
                                         fun_name="jit(threaded_inner_fn)")
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
            jax.monitoring.record_event_time_span(
                compile_event, float(k), k + 0.25,
                fun_name="jit(threaded_inner_fn)")
            jax.monitoring.record_event_time_span(
                compile_event, float(k), k + 0.5, fun_name=name)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=work, args=(i,))
                   for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not any(w.is_alive() for w in workers)
    summary = staging.summary()
    assert "threaded_inner_fn" not in summary
    for i in range(threads):
        row = summary[f"threaded_fn_{i}"]
        assert row["programs"] == row["cache_hits"] == each
        assert row["compile_s"] == pytest.approx(each * 0.5)
        assert staging.programs(f"threaded_fn_{i}") == each


def test_the_list_is_bounded_and_the_totals_are_not():
    # last in the file: it floods the list and then empties the record
    trace_event = next(e for e, p in staging.PHASES.items() if p == "trace")
    n = staging.MAX_ENTRIES + 5
    for i in range(n):
        jax.monitoring.record_event_time_span(trace_event, float(i), i + 0.5,
                                              fun_name="flood_fn")
    assert len(staging.entries()) == staging.MAX_ENTRIES
    assert staging.summary()["flood_fn"]["trace_s"] == pytest.approx(n * 0.5)
    staging.reset()
    assert staging.entries() == [] and staging.summary() == {}
    assert staging.programs("train_step") == 0
