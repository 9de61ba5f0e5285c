"""benchmark/trace_reduce.py and the reducers over it.

Two kinds of case: intervals made up here, where every answer can be
read off the numbers; and the two small traces recorded on a TPU v5e in
PR 22 (benchmark/selftest/record_trace.py: four steps of a two-layer
GPT, on one chip and on four), where the answers were worked out by
hand from the raw events and are written down below."""
import gzip
import os
import re
import shutil
from types import SimpleNamespace

import pytest

from benchmark import manifest, trace_reduce as tr
from benchmark.reducers import (event_count, exposed_per_step,
                                host_span_median, roofline_share,
                                sum_per_step)

DATA = os.path.join(manifest.HERE, "selftest", "data")


# -- made-up intervals --------------------------------------------------------

def test_union_merges_overlap_and_touching_and_drops_empty():
    assert tr.union([(5, 6), (0, 2), (1, 3), (6, 7), (9, 9)]) == \
        [(0, 3), (5, 7)]
    assert tr.length(tr.union([(0, 2), (1, 3), (5, 6)])) == 4


def test_subtract_and_gaps():
    assert tr.subtract([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == \
        [(0, 1), (2, 4), (5, 9)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert tr.subtract([(0, 2)], []) == [(0, 2)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps([(-5, 2), (4, 50)], 0, 6) == [(2, 4)]


def test_gap_attribution_later_span_wins_and_rest_is_unnamed():
    spans = [(1, 3, "a"), (2, 5, "b"), (8, 12, "c")]
    assert tr.attribute([(0, 10)], spans) == \
        {"a": 1, "b": 3, "c": 2, "(no span)": 4}
    assert tr.attribute([(20, 30)], spans) == {"(no span)": 10}


def synthetic():
    """One chip, window 0..100 (the spans). Ops: compute 10..40, a
    synchronous all-reduce 40..50 (which XLA names after the jax op, so
    only its opcode says what it is), compute 60..80; an asynchronous
    all-gather in flight 70..95, awaited by its -done op 80..95."""
    ops = [(10, 40, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"),
           (40, 50, "%psum.3 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%r"),
           (60, 80, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop"),
           (80, 95, "%all-gather-done.1 = f32[8]{0} all-gather-done(%s)")]
    asyncs = [(70, 95, "%all-gather-start.1 = (f32[2]{0}, f32[8]{0}) "
                       "all-gather-start(f32[2]{0} %y)")]
    spans = [(0, 45, "bench.train_step_call"), (45, 100, "bench.sync")]
    trace = tr.Trace(devices={0: {tr.OPS_LINE: ops, tr.ASYNC_LINE: asyncs}},
                     spans=spans)
    return SimpleNamespace(trace=trace, steps=2, counters={"compile": 0})


COLLECTIVE = manifest.Manifest().layer_metric(
    "collective_ms_per_step")["args"]


def test_synthetic_busy_window_and_idle():
    r = synthetic()
    assert tr.window(r.trace) == (0, 100)
    # busy 10..50 and 60..95 = 75 of 100
    assert tr.busy_and_window_s(r.trace) == (75e-9, 100e-9)
    b = tr.breakdown(r.trace)
    assert dict(map(tuple, b["idle_gaps"])) == {
        "bench.train_step_call": 10e-9,          # 0..10
        "bench.sync": 15e-9}                     # 50..60 and 95..100
    assert b["device_ops"][0] == ["%fusion.1 = f32[8] fusion kLoop", 30e-9]


def test_synthetic_collective_time_and_its_exposed_part():
    r = synthetic()
    # in flight: 40..50 and 70..95 = 35 ns over 2 steps, in ms
    assert sum_per_step.reduce(r, **COLLECTIVE) == pytest.approx(35 / 2 / 1e6)
    # compute covers 70..80 of that: exposed 40..50 and 80..95 = 25 ns
    assert exposed_per_step.reduce(r, **COLLECTIVE) == \
        pytest.approx(25 / 2 / 1e6)
    # on the core's line alone the -done op and the all-reduce: 25 ns
    assert sum_per_step.reduce(r, pattern=COLLECTIVE["pattern"]) == \
        pytest.approx(25 / 2 / 1e6)


def test_synthetic_spans_counts_and_empty_readings():
    r = synthetic()
    assert host_span_median.reduce(r, span="bench.sync") == 55 / 1e6
    assert host_span_median.reduce(r, span="bench.absent") is None
    assert event_count.reduce(r, event="compile") == 0
    none = SimpleNamespace(trace=None, steps=2, counters={})
    assert sum_per_step.reduce(none, pattern="") is None
    assert exposed_per_step.reduce(none, pattern="") is None
    assert event_count.reduce(none, event="compile") is None


def test_op_label_keeps_name_result_opcode_and_kind():
    name = ("%fusion.485 = (bf16[8,1024]{1,0:T(8,128)(2,1)S(1)}, "
            "bf16[8,1024,50304]{2,1,0:T(8,128)(2,1)}) fusion(bf16[50304,768]"
            "{1,0:T(8,128)(2,1)S(1)} %custom-call.12), kind=kOutput, "
            "calls=%fused_computation.711")
    assert tr.op_label(name) == \
        "%fusion.485 = (bf16[8,1024], bf16[8,1024,50304]) fusion kOutput"
    call = ('%jvp__.3 = (bf16[96,1024,64]{2,1,0}, f32[96,1024,8]{2,1,0}) '
            'custom-call(s32[96]{0} %a, s32[1]{0} %b, bf16[96,1024,64]{2,1,0} '
            '%c), custom_call_target="tpu_custom_call"')
    assert tr.op_label(call) == ("%jvp__.3 = (bf16[96,1024,64], "
                                 "f32[96,1024,8]) custom-call tpu_custom_call")
    assert tr.op_label("no equals sign") == "no equals sign"


# -- the traces recorded on the chip -------------------------------------------

def recorded(name, tmp_path):
    """A reading over one of the recorded traces (4 steps each)."""
    path = tmp_path / name
    with gzip.open(os.path.join(DATA, name + ".gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return SimpleNamespace(trace=tr.load(str(path)), steps=4, counters={})


def metric_args(name):
    return manifest.Manifest().layer_metric(name)["args"]


def test_one_chip_trace_against_hand_values(tmp_path):
    """trace_1chip: worked out from the raw events with plain loops (no
    interval code): the ten host spans run from 0 to 35,146,697 ns; the
    3,686 ops inside that window never overlap and sum to 26,649,078 ns;
    the 24 Mosaic custom calls (6 a step: forward, dq and dkv of 2
    layers) sum to 8,627,018 ns, the longest %transpose_jvp___.5 at
    1,671,201 ns; no collective runs on one chip."""
    r = recorded("trace_1chip.xplane.pb", tmp_path)
    lo, hi = tr.window(r.trace)
    assert hi - lo == 35_146_697 and len(r.trace.spans) == 10
    assert list(r.trace.devices) == [0]
    busy_s, window_s = tr.busy_and_window_s(r.trace)
    assert busy_s == pytest.approx(26_649_078e-9, rel=1e-12)
    assert window_s == pytest.approx(35_146_697e-9, rel=1e-12)
    assert sum_per_step.reduce(r, **metric_args("device_step_ms")) == \
        pytest.approx(26_649_078 / 4 / 1e6, rel=1e-12)
    assert sum_per_step.reduce(r, **metric_args("flash_attn_ms_per_step")) \
        == pytest.approx(8_627_018 / 4 / 1e6, rel=1e-12)
    assert sum_per_step.reduce(r, **COLLECTIVE) == 0.0
    assert exposed_per_step.reduce(r, **COLLECTIVE) == 0.0
    # train_step_call spans: 3,533,520 3,421,710 3,463,459 2,781,220 ns
    assert host_span_median.reduce(r, **metric_args("host_dispatch_ms")) == \
        pytest.approx((3_421_710 + 3_463_459) / 2 / 1e6, rel=1e-9)
    b = tr.breakdown(r.trace)
    assert b["device_ops"][0][0].startswith("%transpose_jvp___.5 = (bf16[")
    assert b["device_ops"][0][1] == pytest.approx(1_671_201e-9, rel=1e-12)
    assert len(b["device_ops"]) == 10
    # idle is the window less busy, all of it attributed to something
    assert sum(s for _, s in b["idle_gaps"]) == \
        pytest.approx(8_497_619e-9, rel=1e-9)
    assert b["idle_gaps"][0][0] == "bench.sync"


def test_one_chip_trace_roofline_share(tmp_path):
    """The least time of the flash kernels at 4 rows x 12 heads x 2
    layers, seq 1,024, head width 64 over their measured 2.157 ms a
    step, from the published peaks."""
    r = recorded("trace_1chip.xplane.pb", tmp_path)
    r.config = dict(n_embd=768, n_head=12, n_layer=2)
    r.rows_per_chip, r.seq = 4, 1024
    r.peaks = manifest.peaks("TPU v5 lite")
    calls = 4 * 12 * 2
    flops = calls * 9 * 1024 * 1024 * 64
    nbytes = calls * (15 * 1024 * 64 * 2 + 5 * 1024 * 8 * 4)
    least = max(flops / 197e12, nbytes / 819e9)
    share, note = roofline_share.reduce(
        r, **metric_args("flash_attn_roofline"))
    assert share == pytest.approx(100 * least / (8_627_018e-9 / 4), rel=1e-9)
    assert note["bound"] == "compute" and 10 < share < 20
    assert note["flops_per_step"] == flops and note["bytes_per_step"] == nbytes


def test_four_chip_trace_against_hand_values(tmp_path):
    """trace_4chip (mesh data=4, 4 rows a chip): the loop's spans run
    54,610,529 ns. Per chip, from the raw events: busy 35,238,833
    35,218,442 35,223,687 35,217,944 ns; 8 collectives (per step the
    loss's ``%psum.165`` and the gradients' ``%all-reduce``, both with
    the opcode all-reduce) summing to 5,826,528 5,828,994 5,832,126
    5,822,202 ns; flash kernels 9,055,507 9,051,870 9,052,736 9,055,375
    ns. Every collective is a synchronous op on the core's own line,
    where ops never overlap, and no asynchronous pair exists, so all of
    the collective time is exposed."""
    r = recorded("trace_4chip.xplane.pb", tmp_path)
    assert sorted(r.trace.devices) == [0, 1, 2, 3]
    lo, hi = tr.window(r.trace)
    assert hi - lo == 54_610_529
    per_chip = [tr.length(tr.busy(r.trace, c)) for c in range(4)]
    assert per_chip == [35_238_833, 35_218_442, 35_223_687, 35_217_944]
    busy_s, window_s = tr.busy_and_window_s(r.trace)
    assert busy_s == pytest.approx(35_224_726.5e-9, rel=1e-12)
    assert window_s == pytest.approx(54_610_529e-9, rel=1e-12)
    per_chip = [tr.length(tr.matching(r.trace, c, COLLECTIVE["pattern"],
                                      COLLECTIVE["lines"]))
                for c in range(4)]
    assert per_chip == [5_826_528, 5_828_994, 5_832_126, 5_822_202]
    collective = sum_per_step.reduce(r, **COLLECTIVE)
    assert collective == pytest.approx(5_827_462.5 / 4 / 1e6, rel=1e-12)
    assert exposed_per_step.reduce(r, **COLLECTIVE) == \
        pytest.approx(collective, rel=1e-12)
    assert sum_per_step.reduce(r, **metric_args("flash_attn_ms_per_step")) \
        == pytest.approx(9_053_872 / 4 / 1e6, rel=1e-12)
    names = {name.split(" = ")[0] for _, _, name in
             r.trace.devices[0][tr.OPS_LINE]
             if re.search(COLLECTIVE["pattern"], name)}
    assert names == {"%psum.165", "%all-reduce"}
