"""What ``trace_reduce.load`` does not keep of a ``.xplane.pb``: which
scope of the program each device op belongs to, and the program's own
host spans.

An ``XLA Ops`` event is named by its instruction's text without the
metadata, so a ``jax.named_scope`` never reaches an event's name. The
scope is in the file all the same: on each device plane the event's
``XEventMetadata`` carries a stat ``tf_op`` holding the instruction's
``op_name``, the path of jax transforms and named scopes it was staged
under (``jit(train_step)/transpose(jvp(gptforpretraining))/gpt/h.0/
attn/sdpa/flash/flash_bwd_dq/pallas_call:``). A fusion carries the
``tf_op`` of its root instruction. ``jax.profiler.ProfileData`` exposes
no metadata stat, so ``op_scopes`` reads the protobuf wire format itself,
and only these fields (tensorflow/tsl/profiler/protobuf/xplane.proto):

    XSpace          planes = 1
    XPlane          name = 2, event_metadata = 4, stat_metadata = 5
                    (maps: an entry's key = 1, value = 2)
    XEventMetadata  name = 2, stats = 5
    XStatMetadata   id = 1, name = 2
    XStat           metadata_id = 1, str_value = 5, ref_value = 7
                    (a ref_value is the id of a stat_metadata whose
                    name is the string)

No time is parsed here: the map joins to ``Trace.devices[chip][line]``'s
``(start, end, name)`` by ``name``.
"""
from __future__ import annotations

import glob
import os

from benchmark import harness, manifest

PROGRAM_SPANS = "paddle_tpu."
_DEVICE_PLANE = b"/device:TPU:"
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == _VARINT:
            value, i = _varint(buf, i)
            yield tag >> 3, value
        elif kind == _BYTES:
            size, i = _varint(buf, i)
            yield tag >> 3, buf[i:i + size]
            i += size
        elif kind == _FIXED64:
            i += 8
        elif kind == _FIXED32:
            i += 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}: not an xplane")


def _first(buf, number, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def _plane_scopes(plane: bytes) -> dict:
    """``{event name: tf_op}`` of one plane."""
    stat_names, events = {}, []
    for number, entry in _fields(plane):
        if number == 5:                       # stat_metadata entry
            meta = _first(entry, 2, b"")
            stat_names[_first(meta, 1, 0)] = _first(meta, 2, b"")
        elif number == 4:                     # event_metadata entry
            events.append(_first(entry, 2, b""))
    tf_op = {i for i, name in stat_names.items() if name == b"tf_op"}
    out = {}
    for meta in events:
        name = None
        for number, value in _fields(meta):
            if number == 2:
                name = value
            elif number == 5:                 # an XStat of the metadata
                if _first(value, 1) not in tf_op:
                    continue                  # most are: read no further
                stat = dict(_fields(value))
                text = stat.get(5)
                if text is None and 7 in stat:
                    text = stat_names.get(stat[7])
                if text and name is not None:
                    out[name.decode()] = text.decode()
    return out


def op_scopes(path: str) -> dict:
    """``{event name: tf_op}`` over the file's ``/device:TPU:<n>`` planes.
    An event whose metadata has no ``tf_op`` is absent."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for number, plane in _fields(space):
        if number == 1 and _first(plane, 2, b"").startswith(_DEVICE_PLANE):
            out.update(_plane_scopes(plane))
    return out


def host_spans(path: str, prefixes) -> list:
    """The host planes' events whose name starts with one of ``prefixes``
    (a string or a tuple of them), sorted by start: ``[(start, end,
    name)]`` on the trace's clock, as ``trace_reduce.load`` gives them.
    The device planes, nearly all of a file's events, are not walked."""
    from jax.profiler import ProfileData

    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(prefixes))


def program_spans(path: str) -> list:
    """The program's own host spans (``paddle_tpu.<name>``): what
    ``trace_reduce.load(path, span_prefix="paddle_tpu.").spans`` holds."""
    return host_spans(path, PROGRAM_SPANS)


class Scopes:
    """What the scope and span readers read of one run's file."""

    def __init__(self, path: str, spans=None):
        self.path = path
        self.ops = op_scopes(path)
        self.spans = program_spans(path) if spans is None else spans
        self.step_ops = {}      # scope_per_step's own, by module


def for_reading(reading) -> Scopes:
    """The ``Scopes`` of the file a reading's trace was loaded from.

    The harness keeps the trace and not its path, so the file is found:
    the ``*.xplane.pb`` under ``<ROOT>/.bench_out/*/trace/`` whose first
    ``bench.`` span starts where the reading's does. The newest file is
    tried first and is the run's own (one process runs one cell and
    ``fresh_dir`` empties its directory), so the traces other cells left
    in the checkout are not opened; no match is an error. Beyond the
    harness's own parse the file costs one pass over its host planes and
    one of the wire reader. Read once, kept on the reading."""
    cached = getattr(reading, "_scopes", None)
    if cached is not None:
        return cached
    start = reading.trace.spans[0][0]
    pattern = os.path.join(manifest.ROOT, harness.OUT_DIR, "*", "trace",
                           "**", "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        spans = host_spans(path, ("bench.", PROGRAM_SPANS))
        if start == next((s for s, _, name in spans
                          if name.startswith("bench.")), None):
            reading._scopes = Scopes(path, [
                x for x in spans if x[2].startswith(PROGRAM_SPANS)])
            return reading._scopes
    raise RuntimeError(
        f"no .xplane.pb under {pattern} whose first bench. span starts at "
        f"{start}")
