"""benchmark/xplane_scopes.py and the three reducers over it.

Three kinds of case: bytes and intervals made up here, where every
answer can be read off the numbers; the two traces recorded in PR 22,
before the program opened any scope, which are what a step loaded from
a stale compilation cache looks like; and the two recorded in PR 24
(``trace_<n>chip_scoped``, by the same ``record_trace.py``: four steps
of a two-layer GPT on one chip and on four), where the answers were
worked out from the raw events and are written down below."""
import gzip
import json
import os
import re
import shutil
from types import SimpleNamespace

import pytest

from benchmark import harness, manifest, trace_reduce as tr, xplane_scopes
from benchmark.reducers import (exposed_under_spans, program_span_median,
                                scope_per_step, sum_per_step)

DATA = os.path.join(manifest.HERE, "selftest", "data")
MAN = manifest.Manifest()
SCOPE_METRICS = sorted(
    m for m in MAN.per_layer
    if MAN.layer_metric(m)["reducer"] == "scope_per_step")
PHASES = ["fwd_ms_per_step", "bwd_ms_per_step", "update_ms_per_step",
          "grad_exchange_ms_per_step", "unscoped_ms_per_step"]


def args_of(metric):
    return MAN.layer_metric(metric)["args"]


# -- the wire format, on bytes made up here -------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    return varint(number << 3 | 2) + varint(len(value)) + value


def stat_meta(i, name):
    return field(5, field(1, i) + field(2, field(1, i) + field(2, name)))


def event_meta(i, name, *stats):
    body = field(1, i) + field(2, name) + b"".join(field(5, s) for s in stats)
    return field(4, field(1, i) + field(2, body))


def plane(name, *entries):
    # id = 1, name = 2, then a line (3) to be skipped whole
    return field(1, field(1, 7) + field(2, name) + field(3, b"\x0a\x01x")
                 + b"".join(entries))


FIXED64 = varint(2 << 3 | 1) + b"\0" * 8      # XStat.double_value
FIXED32 = varint(9 << 3 | 5) + b"\0" * 4


@pytest.fixture
def made_up_xplane(tmp_path):
    device = plane(
        b"/device:TPU:0",
        stat_meta(1, b"tf_op"), stat_meta(2, b"flops"),
        stat_meta(300, b"jit(f)/update/mul:"),
        event_meta(1, b"%a = f32[] add()",
                   field(1, 2) + FIXED64,
                   field(1, 1) + field(5, b"jit(f)/jvp(net)/h.0/add:")),
        event_meta(2, b"%b = f32[] mul()", field(1, 1) + field(7, 300)),
        event_meta(3, b"%c = f32[] copy-done()",
                   field(1, 2) + field(4, 12) + FIXED32),
        event_meta(4, b"%d = f32[] neg()", field(1, 1) + field(7, 999)))
    second = plane(b"/device:TPU:1", stat_meta(5, b"tf_op"),
                   event_meta(1, b"%e = f32[] exp()",
                              field(1, 5) + field(5, b"jit(f)/exp:")))
    host = plane(b"/host:CPU", stat_meta(1, b"tf_op"),
                 event_meta(1, b"not a device op",
                            field(1, 1) + field(5, b"jit(f)/host:")))
    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(host + device + second)
    return str(path)


def test_wire_reader_on_made_up_bytes(made_up_xplane):
    """A str_value is read, a ref_value resolves through stat_metadata,
    an event without tf_op (or whose reference leads nowhere) is absent,
    fixed-width fields are skipped, only device planes are read and
    every device plane is."""
    assert xplane_scopes.op_scopes(made_up_xplane) == {
        "%a = f32[] add()": "jit(f)/jvp(net)/h.0/add:",
        "%b = f32[] mul()": "jit(f)/update/mul:",
        "%e = f32[] exp()": "jit(f)/exp:"}


@pytest.mark.parametrize("n, expected", [(0, b"\x00"), (127, b"\x7f"),
                                         (128, b"\x80\x01"),
                                         (300, b"\xac\x02"),
                                         (2 ** 40, b"\x80" * 5 + b"\x20")])
def test_varint_both_ways(n, expected):
    assert varint(n) == expected
    assert xplane_scopes._varint(expected + b"\xff", 0) == (n, len(expected))


def test_wire_reader_refuses_what_is_not_protobuf(tmp_path):
    path = tmp_path / "bad.xplane.pb"
    path.write_bytes(varint(1 << 3 | 3))          # a group: never in xplane
    with pytest.raises(ValueError, match="wire type 3"):
        xplane_scopes.op_scopes(str(path))


# -- the recorded traces -----------------------------------------------------------

def unpacked(name, tmp_path):
    path = tmp_path / name
    with gzip.open(os.path.join(DATA, name + ".gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def recorded(name, tmp_path):
    """A reading over one of the recorded traces (4 steps each), its
    scopes read from the same file."""
    path = unpacked(name, tmp_path)
    reading = SimpleNamespace(trace=tr.load(path), steps=4, counters={})
    reading._scopes = xplane_scopes.Scopes(path)
    return reading


@pytest.mark.parametrize("name, names, events, with_tf_op", [
    ("trace_1chip.xplane.pb", 213, 3800, 852),
    ("trace_4chip.xplane.pb", 275, 5160, 1104)])
def test_wire_reader_on_the_unscoped_traces(name, names, events, with_tf_op,
                                            tmp_path):
    r = recorded(name, tmp_path)
    scopes = r._scopes.ops
    assert len(scopes) == names
    assert all(v.startswith("jit(") and v.endswith(":")
               for v in scopes.values())
    ops = r.trace.devices[0][tr.OPS_LINE]
    assert len(ops) == events
    assert sum(name in scopes for _, _, name in ops) == with_tf_op
    # the waits carry no tf_op: absent, not empty
    absent = {name.split(" = ")[0].rstrip(".0123456789")
              for _, _, name in ops if name not in scopes}
    assert "%copy-done" in absent
    kernel = next(v for k, v in scopes.items() if k.startswith("%jvp__."))
    assert kernel.endswith("jvp()/pallas_call:")
    assert r._scopes.spans == []          # the program had no span then


def test_for_reading_finds_the_file_by_its_first_span(tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    opened = []
    host_spans = xplane_scopes.host_spans
    monkeypatch.setattr(xplane_scopes, "host_spans", lambda path, prefixes: (
        opened.append(path), host_spans(path, prefixes))[1])
    where = tmp_path / harness.OUT_DIR / "some.cell" / "trace" / "plugins"
    where.mkdir(parents=True)
    one = unpacked("trace_1chip.xplane.pb", where)
    # the trace another cell left beside it is older and is not opened
    other = tmp_path / harness.OUT_DIR / "other.cell" / "trace"
    other.mkdir(parents=True)
    sibling = unpacked("trace_4chip.xplane.pb", other)
    os.utime(sibling, (1, 1))
    reading = SimpleNamespace(trace=tr.load(one))
    found = xplane_scopes.for_reading(reading)
    assert found.path == one and len(found.ops) == 213
    assert opened == [one]
    assert xplane_scopes.for_reading(reading) is found      # read once
    assert opened == [one]
    # a newer file that is not the run's is opened, and passed over
    os.utime(sibling)
    os.utime(one, (2, 2))
    again = xplane_scopes.for_reading(SimpleNamespace(trace=reading.trace))
    assert again.path == one and opened == [one, sibling, one]
    # two files of one run cannot be; the newest is taken, nothing fails
    copy = shutil.copy(one, where / "again.xplane.pb")
    os.utime(copy, (3, 3))
    os.utime(sibling, (1, 1))
    assert xplane_scopes.for_reading(
        SimpleNamespace(trace=reading.trace)).path == str(copy)
    shutil.rmtree(tmp_path / harness.OUT_DIR / "some.cell")
    with pytest.raises(RuntimeError, match="no .xplane.pb"):
        xplane_scopes.for_reading(SimpleNamespace(trace=reading.trace))


@pytest.mark.parametrize("name, count", [
    ("trace_1chip.xplane.pb", 0), ("trace_1chip_scoped.xplane.pb", 8),
    ("trace_4chip_scoped.xplane.pb", 8)])
def test_program_spans_are_what_the_whole_load_keeps(name, count, tmp_path):
    """``program_spans`` walks the host planes alone and gives what
    ``trace_reduce.load`` would with the program's prefix."""
    path = unpacked(name, tmp_path)
    spans = xplane_scopes.program_spans(path)
    assert spans == tr.load(path, span_prefix="paddle_tpu.").spans
    assert len(spans) == count
    both = xplane_scopes.host_spans(path, ("bench.", "paddle_tpu."))
    assert [x for x in both if x[2].startswith("bench.")] == \
        tr.load(path).spans


@pytest.mark.parametrize("name", ["trace_1chip.xplane.pb",
                                  "trace_4chip.xplane.pb"])
@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_a_step_staged_without_scopes_reads_stale_not_zero(metric, name,
                                                           tmp_path):
    """The traces of PR 22: ops carry tf_ops (93% of the step's device
    time) and none of the program's scopes, as a step that jax loaded
    from a compilation cache an older commit filled does."""
    r = recorded(name, tmp_path)
    value, note = scope_per_step.reduce(r, **args_of(metric))
    assert value is None and note["stale_metadata"] is True
    assert note["scoped_share"] == 0.0
    assert 0.92 < note["tf_op_share"] < 0.93


def test_stale_and_absent_metrics_are_left_out_of_the_line(tmp_path):
    """Through the harness's own loop, on a program that opens neither
    scope nor span (the parent commit under this PR's benchmark files):
    no new metric is printed, no reader raises, the old ones stand."""
    r = recorded("trace_1chip.xplane.pb", tmp_path)
    r.config = dict(n_embd=768, n_head=12, n_layer=2)
    r.rows_per_chip, r.seq = 4, 1024
    r.peaks = manifest.peaks("TPU v5 lite")
    r.counters = {"backend_compile": 0}
    said = []
    ctx = SimpleNamespace(say=lambda **f: said.append(f))
    line = harness.layer_metrics(MAN, MAN.cell("gpt2-small.seq1024.dp4"), r,
                                 ctx)
    new = set(SCOPE_METRICS) | {"trainer_stage_ms", "trainer_launch_ms",
                                "dispatch_exposed_ms_per_step"}
    assert len(new) == 10 and not new & set(line)
    assert {"device_step_ms", "flash_attn_ms_per_step",
            "host_dispatch_ms"} <= set(line)
    stale = {f["metric"] for f in said if f.get("stale_metadata")}
    assert stale == set(SCOPE_METRICS)
    json.dumps(said)


# -- patterns ----------------------------------------------------------------------

S = "jit(train_step)/shard_map/"
TF_OPS = [
    # (tf_op, the one phase it belongs to, the other scope metrics it is in)
    (S + "jvp(gptforpretraining)/gpt/h.3/attn/qkv_proj/dot_general:",
     "fwd", []),
    (S + "jvp(gptforpretraining)/gpt/h.3/attn/sdpa/flash/flash_fwd/"
     "pallas_call:", "fwd", ["attn_path"]),
    (S + "transpose(jvp(gptforpretraining))/gpt/h.0/attn/sdpa/flash/"
     "flash_bwd_dkv/pallas_call:", "bwd", ["attn_path"]),
    (S + "transpose(jvp(gptforpretraining))/gpt/h.0/attn/sdpa/xla/"
     "bhst,bthd->bshd/dot_general:", "bwd", ["attn_path"]),
    (S + "jvp(gptforpretraining)/gpt/h.0/attn/not_sdpa/mul:", "fwd", []),
    (S + "jvp(gptforpretraining)/lm_head/dot_general:", "fwd",
     ["lm_head_loss"]),
    (S + "transpose(jvp(gptforpretraining))/lm_head/transpose:", "bwd",
     ["lm_head_loss"]),
    (S + "jvp(loss)/jit(take_along_axis)/gather:", "fwd", ["lm_head_loss"]),
    (S + "transpose(jvp(loss))/reduce_sum:", "bwd", ["lm_head_loss"]),
    (S + "jvp(fusedloss)/inner/fused_head_loss/dot_general:", "fwd", []),
    (S + "jvp(gptforpretraining)/gpt/embeddings/word_embeddings/"
     "jit(_take)/gather:", "fwd", []),
    (S + "grad_exchange/convert_element_type:", "grad_exchange", []),
    (S + "grad_exchange/psum:", "grad_exchange", []),
    (S + "transpose(jvp(grad_exchange))/psum:", "grad_exchange", []),
    ("jit(train_step)/update/jit(_where)/select_n:", "update", []),
    ("jit(train_step)/update/reduce_and:", "update", []),
    ("jit(train_step)/update:", "update", []),
    ("jit(train_step)/jvp(updater)/mul:", "fwd", []),
    (S + "jvp()/psum:", "fwd", []),
    ("jit(train_step)/mul:", "unscoped", []),
    (S + "convert_element_type:", "unscoped", []),
    (None, "unscoped", []),
]


def belongs(metric, tf_op):
    spec = args_of(metric)
    hit = tf_op is not None and bool(re.search(spec["pattern"], tf_op))
    return hit != spec.get("invert", False)


@pytest.mark.parametrize("tf_op, phase, others", TF_OPS)
def test_each_op_is_in_exactly_one_phase(tf_op, phase, others):
    assert [p for p in PHASES if belongs(p, tf_op)] == \
        [phase + "_ms_per_step"]
    rest = sorted(set(SCOPE_METRICS) - set(PHASES))
    assert [m for m in rest if belongs(m, tf_op)] == \
        sorted(o + "_ms_per_step" for o in others)


@pytest.mark.parametrize("tf_op, scoped", [
    (S + "jvp(gptforpretraining)/gpt/ln_f/mul:", True),
    (S + "transpose(jvp(fusedloss))/inner/gpt/ln_f/mul:", True),
    (S + "transpose(jvp(grad_exchange))/psum:", True),
    ("jit(train_step)/update/mul:", True),
    ("jit(train_step)/jvp(loss):", True),
    ("jit(train_step)/jvp()/dot_general:", False),
    ("jit(train_step)/transpose(jvp(jit(_var)))/reduce_sum:", False),
    ("jit(train_step)/jit(_where)/select_n:", False),
    ("jit(train_step)/shard_map/convert.51:", False),
    ("jit(train_step)/updates/mul:", False)])
def test_what_counts_as_a_scope_of_the_program(tf_op, scoped):
    assert bool(scope_per_step.SCOPED.search(tf_op)) == scoped


# -- the reducers, on intervals made up here ---------------------------------------

def made_up():
    """One chip, two steps, window 0..200. The step's program runs
    10..90 and 110..190; between them a program of another name."""
    fwd = S + "jvp(net)/h.0/attn/sdpa/xla/dot_general:"
    scopes = {"%f": fwd,
              "%h": S + "jvp(net)/lm_head/dot_general:",
              "%b": S + "transpose(jvp(net))/h.0/attn/sdpa/xla/mul:",
              "%l": S + "transpose(jvp(loss))/sub:",
              "%x": S + "grad_exchange/psum:",
              "%u": "jit(train_step)/update/jit(_where)/select_n:",
              "%m": "jit(train_step)/mul:",
              "%s": "jit(_threefry_split)/add:"}
    ops, modules = [], []
    for t in (10, 110):
        modules.append((t, t + 80, "jit_train_step(123)"))
        ops += [(t, t + 10, "%f"), (t + 10, t + 15, "%h"),
                (t + 15, t + 35, "%b"), (t + 35, t + 40, "%l"),
                (t + 40, t + 50, "%x"), (t + 50, t + 53, "%wait"),
                (t + 53, t + 60, "%u"), (t + 60, t + 61, "%m")]
    modules.append((95, 100, "jit__threefry_split(9)"))
    ops.append((95, 100, "%s"))
    bench = [(0, 8, "bench.train_step_call"), (8, 100, "bench.sync"),
             (100, 108, "bench.train_step_call"), (108, 200, "bench.sync")]
    program = [(1, 4, "paddle_tpu.trainer.stage"),
               (4, 7, "paddle_tpu.trainer.launch"),
               (101, 106, "paddle_tpu.trainer.stage"),
               (106, 112, "paddle_tpu.trainer.launch"),
               (150, 160, "paddle_tpu.checkpoint_save")]
    trace = tr.Trace(devices={0: {tr.OPS_LINE: sorted(ops),
                                  "XLA Modules": modules}}, spans=bench)
    reading = SimpleNamespace(trace=trace, steps=2, counters={})
    reading._scopes = SimpleNamespace(ops=scopes, spans=program, step_ops={})
    return reading


@pytest.mark.parametrize("metric, ns_per_step", [
    ("fwd_ms_per_step", 15), ("bwd_ms_per_step", 25),
    ("grad_exchange_ms_per_step", 10), ("update_ms_per_step", 7),
    ("unscoped_ms_per_step", 4), ("attn_path_ms_per_step", 30),
    ("lm_head_loss_ms_per_step", 10)])
def test_scope_per_step_on_made_up_intervals(metric, ns_per_step):
    value, note = scope_per_step.reduce(made_up(), **args_of(metric))
    assert value == pytest.approx(ns_per_step / 1e6)
    split = note.pop("by_scope_ms", None)
    # of the step's 61 ns: 57 under a scope of the program (not the wait,
    # not the bare mul), 58 with a tf_op
    assert note == {"scoped_share": pytest.approx(57 / 61),
                    "tf_op_share": pytest.approx(58 / 61)}
    # attention alone says which path it took and what each kernel had
    assert split == ({"xla": pytest.approx(30 / 1e6), "flash": 0,
                      "flash_fwd": 0, "flash_bwd_dq": 0, "flash_bwd_dkv": 0}
                     if metric == "attn_path_ms_per_step" else None)


def test_phases_add_up_to_the_step_program_not_to_the_device():
    r = made_up()
    total = sum(scope_per_step.reduce(r, **args_of(m))[0] for m in PHASES)
    assert total == pytest.approx(61 / 1e6)
    # the rng split's 5 ns are another program's: device time, not a phase
    assert sum_per_step.reduce(r, pattern="") == pytest.approx(63.5 / 1e6)


def test_scope_per_step_module_window_and_empty_readings():
    r = made_up()
    # another program's ops are counted when asked for: the rng split
    # has tf_ops and opens no scope of the program
    value, note = scope_per_step.reduce(r, pattern="add",
                                        module="jit__threefry")
    assert value is None and note == {
        "scoped_share": 0.0, "tf_op_share": 1.0, "stale_metadata": True}
    assert scope_per_step.reduce(r, pattern="", module="jit_absent(") is None
    # ops are clipped to the window: end it inside the second step's %b
    # (the first step's %b and %l whole, 5 ns of the second's %b)
    r = made_up()
    r.trace.spans = [(0, 8, "bench.train_step_call"), (8, 130, "bench.sync")]
    assert scope_per_step.reduce(r, **args_of("bwd_ms_per_step"))[0] == \
        pytest.approx((25 + 5) / 2 / 1e6)
    none = SimpleNamespace(trace=None, steps=2, counters={})
    for reducer, args in ((scope_per_step, {"pattern": ""}),
                          (program_span_median, {"span": "x"}),
                          (exposed_under_spans, {"prefix": "x"})):
        assert reducer.reduce(none, **args) is None


def test_program_span_median_on_made_up_spans():
    r = made_up()
    assert program_span_median.reduce(
        r, **args_of("trainer_stage_ms")) == pytest.approx(4 / 1e6)
    assert program_span_median.reduce(
        r, **args_of("trainer_launch_ms")) == pytest.approx(4.5 / 1e6)
    assert program_span_median.reduce(r, span="paddle_tpu.absent") is None
    # a span that starts after the window is not of this run's loop
    r._scopes.spans.append((300, 400, "paddle_tpu.trainer.stage"))
    assert program_span_median.reduce(
        r, **args_of("trainer_stage_ms")) == pytest.approx(4 / 1e6)


def test_exposed_under_spans_on_made_up_spans():
    """Idle: 0..10, 71..95, 100..110, 171..200. The trainer's spans cover
    1..7 of the first gap and 101..110 of the third (stage 101..106,
    launch the rest until the device starts at 110): 15 ns, 7.5 a step.
    The checkpoint's span lies over busy time and another prefix."""
    r = made_up()
    value, note = exposed_under_spans.reduce(
        r, **args_of("dispatch_exposed_ms_per_step"))
    assert value == pytest.approx(7.5 / 1e6)
    assert note == {"by_span_ms": {
        "paddle_tpu.trainer.stage": pytest.approx((3 + 5) / 2 / 1e6),
        "paddle_tpu.trainer.launch": pytest.approx((3 + 4) / 2 / 1e6)}}
    assert exposed_under_spans.reduce(r, prefix="paddle_tpu.absent") is None
    assert exposed_under_spans.reduce(r, prefix="paddle_tpu.checkpoint")[
        0] == 0.0


# -- the traces recorded with the program's scopes ---------------------------------
#
# Worked out from the raw events with plain loops (no interval code, no
# pattern from a metric's file): every ``XLA Ops`` event inside the loop's
# window and inside a ``jit_train_step(`` event of its chip's ``XLA
# Modules`` line, by what its ``tf_op`` holds as a whole component. The
# numbers are nanoseconds over the trace's four steps, summed over chips.

HAND = {
    "trace_1chip_scoped.xplane.pb": {
        # 3,796 ops in the window sum to 26,648,436 ns, 26,638,338 of them
        # in the step's program (the rest: rng split, batch slicing)
        "chips": 1, "names": 213, "window_ns": 34_873_789,
        "device_ns": 26_648_436, "step_ns": 26_638_338,
        # every op that has a tf_op has it from a scope of the program
        "tf_op_ns": 24_711_506, "scoped_ns": 24_711_506,
        "ns": {"fwd_ms_per_step": 7_948_191,
               "bwd_ms_per_step": 14_769_639,
               "update_ms_per_step": 1_993_676,
               "unscoped_ms_per_step": 1_926_832,
               "attn_path_ms_per_step": 9_457_685,
               "lm_head_loss_ms_per_step": 3_857_683},
        # the spans' lengths, in the order they were opened
        # of attn_path: all of it on the flash path; the three kernels,
        # and 831,367 ns of layout changes around them
        "attn_ns": {"flash": 9_457_685, "xla": 0, "flash_fwd": 3_183_841,
                    "flash_bwd_dq": 2_101_206, "flash_bwd_dkv": 3_341_271},
        "stage": [2_149_649, 1_545_260, 1_791_659, 1_554_960],
        "launch": [1_322_249, 992_969, 1_041_239, 1_146_829],
        "call": [3_703_239, 2_681_799, 3_003_909, 2_945_209],
        # idle: 8,225,353 ns, of it under the trainer's spans:
        "exposed": {"paddle_tpu.trainer.stage": 3_782_429,
                    "paddle_tpu.trainer.launch": 540}},
    "trace_4chip_scoped.xplane.pb": {
        # mesh data=4. Per chip, ops in the window: 35,237,726 35,215,614
        # 35,221,041 35,217,066 ns; in the step's program 35,220,425 and
        # then the same three (only chip 0 runs the rng split). XLA merged
        # the gradients' all-reduce into the loss's, and the merged op
        # keeps the loss's tf_op, ``shard_map/jvp()/psum:``, under no scope
        # of the program: 23,231,255 ns that count as forward, and why
        # scoped_ns is that much under tf_op_ns. Under grad_exchange only
        # the casts remain (797,424 798,083 798,077 797,306 ns).
        "chips": 4, "names": 275, "window_ns": 53_602_005,
        "device_ns": 140_891_447, "step_ns": 140_874_146,
        "tf_op_ns": 130_300_204, "scoped_ns": 106_888_681,
        "ns": {"fwd_ms_per_step": 55_610_467,
               "bwd_ms_per_step": 60_387_064,
               "update_ms_per_step": 11_000_140,
               "grad_exchange_ms_per_step": 3_190_890,
               "unscoped_ms_per_step": 10_685_585,
               "attn_path_ms_per_step": 39_866_187,
               "lm_head_loss_ms_per_step": 15_516_019},
        "attn_ns": {"flash": 39_866_187, "xla": 0, "flash_fwd": 13_103_889,
                    "flash_bwd_dq": 9_002_887, "flash_bwd_dkv": 14_107_606},
        "stage": [5_233_129, 3_856_250, 3_851_539, 3_275_400],
        "launch": [2_540_540, 2_235_120, 2_551_010, 2_233_979],
        "call": [8_251_609, 6_708_140, 6_855_129, 6_032_450],
        # idle per chip 18,364,279 18,386,391 18,380,964 18,384,939 ns
        "exposed": {"paddle_tpu.trainer.stage": 36_341_683,
                    "paddle_tpu.trainer.launch": 6_942_944}},
}
HAND_CASES = [(name, metric) for name in sorted(HAND)
              for metric in sorted(HAND[name]["ns"])]


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """Each scoped trace unpacked and read once for this file's cases."""
    where = tmp_path_factory.mktemp("scoped")
    return {name: recorded(name, where) for name in HAND}


@pytest.mark.parametrize("name, metric", HAND_CASES)
def test_scoped_trace_against_hand_values(name, metric, scoped):
    hand, r = HAND[name], scoped[name]
    value, note = scope_per_step.reduce(r, **args_of(metric))
    assert value == pytest.approx(
        hand["ns"][metric] / hand["chips"] / 4 / 1e6, rel=1e-9)
    if metric == "attn_path_ms_per_step":
        assert note.pop("by_scope_ms") == {
            k: pytest.approx(ns / hand["chips"] / 4 / 1e6, rel=1e-9)
            for k, ns in hand["attn_ns"].items()}
    assert note == {
        "scoped_share": pytest.approx(
            hand["scoped_ns"] / hand["step_ns"], rel=1e-8),
        "tf_op_share": pytest.approx(
            hand["tf_op_ns"] / hand["step_ns"], rel=1e-8)}


@pytest.mark.parametrize("name", sorted(HAND))
def test_scoped_trace_phases_add_up_to_the_step(name, scoped):
    hand, r = HAND[name], scoped[name]
    lo, hi = tr.window(r.trace)
    assert hi - lo == hand["window_ns"] and len(r._scopes.ops) == hand["names"]
    phases = [p for p in PHASES if p in hand["ns"]]
    assert sum(hand["ns"][p] for p in phases) == hand["step_ns"]
    total = sum(scope_per_step.reduce(r, **args_of(p))[0] for p in phases)
    assert total == pytest.approx(hand["step_ns"] / hand["chips"] / 4 / 1e6,
                                  rel=1e-9)
    device = sum_per_step.reduce(r, **args_of("device_step_ms"))
    assert device == pytest.approx(
        hand["device_ns"] / hand["chips"] / 4 / 1e6, rel=1e-9)
    assert 0 <= device - total < 0.02 * device
    # the kernels are found by scope and, as before, by their operands
    flash = sum_per_step.reduce(r, **args_of("flash_attn_ms_per_step"))
    by_scope = scope_per_step.reduce(
        r, pattern=scope_per_step.scope("flash_fwd|flash_bwd_dq|flash_bwd_dkv"))
    assert by_scope[0] == pytest.approx(flash, rel=1e-12)
    assert flash < scope_per_step.reduce(
        r, **args_of("attn_path_ms_per_step"))[0]
    kernels = {k.split(".")[0] for k in r._scopes.ops
               if "custom-call" in k and "tpu_custom_call" in k}
    assert kernels == {"%flash_fwd", "%flash_bwd_dq", "%flash_bwd_dkv"}


@pytest.mark.parametrize("name", sorted(HAND))
def test_scoped_trace_spans_against_hand_values(name, scoped):
    import statistics

    hand, r = HAND[name], scoped[name]
    spans = r._scopes.spans
    assert [e - s for s, e, n in spans if n.endswith(".stage")] == \
        pytest.approx(hand["stage"], abs=1)
    assert [e - s for s, e, n in spans if n.endswith(".launch")] == \
        pytest.approx(hand["launch"], abs=1)
    stage = program_span_median.reduce(r, **args_of("trainer_stage_ms"))
    launch = program_span_median.reduce(r, **args_of("trainer_launch_ms"))
    assert stage == pytest.approx(
        statistics.median(hand["stage"]) / 1e6, rel=1e-6)
    assert launch == pytest.approx(
        statistics.median(hand["launch"]) / 1e6, rel=1e-6)
    # each pair lies inside the loop's span around the same call
    for s, l, call in zip(hand["stage"], hand["launch"], hand["call"]):
        assert s + l < call
    value, note = exposed_under_spans.reduce(
        r, **args_of("dispatch_exposed_ms_per_step"))
    per_step = 1 / hand["chips"] / 4 / 1e6
    assert value == pytest.approx(
        sum(hand["exposed"].values()) * per_step, rel=1e-6)
    assert note["by_span_ms"] == {
        k: pytest.approx(v * per_step, rel=1e-3, abs=1e-6)
        for k, v in hand["exposed"].items()}
