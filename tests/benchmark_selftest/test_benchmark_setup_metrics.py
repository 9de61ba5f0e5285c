"""The six per-layer metrics that move ``setup_s``: the manifest
resolves with them, and after a toy walk on the CPU each reader gives a
number from the program's staging record that an independent listener
and the walk's own wall time bound. Counts and bounds only: a time from
the CPU is never a device's."""
import sys
import time

import jax
import pytest

from benchmark import manifest, rehearse
from benchmark.reducers import staging_record
from paddle_tpu import telemetry
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.telemetry import staging

MAN = manifest.Manifest()
CELL = "gpt2-small.seq1024"
SETUP = ("setup_step_trace_s", "setup_step_lower_s", "setup_step_compile_s",
         "setup_step_programs", "setup_trainer_init_s",
         "setup_other_staging_s")
SECONDS = tuple(n for n in SETUP if n != "setup_step_programs")


def read(name):
    spec = MAN.layer_metric(name)
    assert spec["reducer"] == "staging_record"
    return manifest.plugin("reducers", spec["reducer"]).reduce(
        None, **spec["args"])


@pytest.fixture(scope="module")
def walked():
    """One toy walk of a cell from an empty record, with a listener of
    the test's own counting the step's backend compiles beside it."""
    compiles, listening = [], [True]

    def independent(event, duration, fun_name="", **_):
        if listening and event.endswith("backend_compile_duration") and \
                fun_name == "jit(train_step)":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(independent)
    before = mesh_mod.get_mesh()
    staging.reset()
    start = time.time()
    try:
        line = rehearse.walk(CELL, seed=1)
    finally:
        listening.clear()
        mesh_mod.set_mesh(before)
    assert line["walked_through"]
    return {"wall_s": time.time() - start, "step_compiles": len(compiles),
            "values": {name: read(name) for name in SETUP}}


def test_the_manifest_resolves_with_the_six():
    assert MAN.problems() == []
    assert [m["name"] for m in MAN.doc["per_layer"][-6:]] == list(SETUP)
    for name in SETUP:
        entry = MAN.per_layer[name]
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert "workloads" not in entry           # every cell pays set-up
    for cell in MAN.workloads:
        reported = {m["name"] for m in MAN.cell(cell)["per_layer"]}
        assert set(SETUP) <= reported


@pytest.mark.parametrize("name", SETUP)
def test_each_reader_gives_a_number_with_its_note(name, walked):
    value, note = walked["values"][name]
    assert value > 0 and isinstance(note, dict) and note
    # what the harness spreads into its layer_metric line
    assert not {"event", "metric", "value", "t"} & set(note)


def test_the_steps_programs_are_what_an_independent_listener_counts(walked):
    programs, note = walked["values"]["setup_step_programs"]
    assert programs == walked["step_compiles"] >= 1
    # each program names the train_step call that staged it; the walk
    # warms up from step 1
    assert len(note["staged_in_steps"]) == programs
    assert note["staged_in_steps"][0] == 1
    assert note["staged_in_steps"] == sorted(note["staged_in_steps"])
    for phase in ("trace", "lower", "compile"):
        _, per = walked["values"][f"setup_step_{phase}_s"]
        assert len(per["seconds_a_program"]) == programs


@pytest.mark.parametrize("name", SECONDS)
def test_no_reader_counts_more_than_the_walk_took(name, walked):
    value, _ = walked["values"][name]
    assert value <= walked["wall_s"]


def test_the_five_in_seconds_share_no_interval(walked):
    # so together they fit between the first staging and the step's last
    # program, which the walk's wall time holds
    total = sum(walked["values"][name][0] for name in SECONDS)
    _, note = walked["values"]["setup_other_staging_s"]
    assert total <= note["first_staging_to_step_ready_s"] <= walked["wall_s"]
    _, init = walked["values"]["setup_trainer_init_s"]
    assert set(init) == {"init_state", "build"}
    assert init["init_state"]["staging_seconds"] <= \
        init["init_state"]["seconds"]
    assert init["init_state"]["programs_staged"] >= 1


@pytest.mark.parametrize("name", SETUP)
def test_an_empty_record_reads_as_nothing(name, walked):
    staging.reset()           # the walk's numbers were read in the fixture
    assert read(name) is None


def test_a_program_from_before_the_record_reads_as_nothing(monkeypatch):
    # the parent commit under this PR's benchmark files: no such module
    monkeypatch.delattr(telemetry, "staging")
    monkeypatch.setitem(sys.modules, "paddle_tpu.telemetry.staging", None)
    for name in SETUP:
        spec = MAN.layer_metric(name)
        assert staging_record.reduce(None, **spec["args"]) is None
