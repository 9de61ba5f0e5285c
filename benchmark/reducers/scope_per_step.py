"""Device time per step of the step program's ops whose scope matches
``pattern``: the length of the union of their intervals inside the
window, averaged over the cell's chips, over the steps, in milliseconds.

An op's scope is its ``tf_op`` (``benchmark/xplane_scopes.py``): the
path of jax transforms and of the scopes the program opens that the
instruction was staged under. A fusion carries the ``tf_op`` of its root
instruction alone, so a fusion that crosses two scopes counts whole for
the root's. With ``invert`` the ops count whose ``tf_op`` does not match,
or that have none (``copy-done`` and other waits).

Only ops of the step's program count: those that run inside the chip's
``XLA Modules`` events whose name starts with ``module``.

The note gives ``scoped_share``: the part of the step program's device
time whose ``tf_op`` holds any scope the program opens (``SCOPED``). With
``split``, a list of scope names, it also gives ``by_scope_ms``: the part
of the metric's own time under each of them (scopes inside the metric's:
the path attention took, each flash kernel).
Where ops have a ``tf_op`` and none of them holds such a scope, the
program was staged without its scopes, as one loaded from a compilation
cache that an older commit filled is (jax's cache key leaves metadata
out): the value is None and the note says ``"stale_metadata": true``,
so the metric is left out instead of reading 0.
"""
import bisect
import re

from benchmark import trace_reduce, xplane_scopes

MODULES_LINE = "XLA Modules"


def scope(names: str) -> str:
    """A pattern for any of ``names`` (``a|b``) as a whole component of a
    ``tf_op``: between ``/``, ``(``, ``)``, the ends and the ``:`` that
    closes the path, so that ``loss`` does not match ``fused_head_loss``."""
    return rf"(?:^|[/(])(?:{names})(?:[/):]|$)"


# Any scope the program opens: a Layer's (the root of the model is the
# first scope inside jax's jvp(...), which is otherwise empty or holds
# jit(...)) or one of those opened by name in distributed/engine.py,
# nn/functional/attention.py and ops/pallas/flash_attention.py.
SCOPED = re.compile(r"jvp\([\w.]+\)|" + scope(
    "update|grad_exchange|loss|sdpa|flash|xla|flash_fwd|flash_bwd_dq|"
    "flash_bwd_dkv"))


def device_ns(ops, keep) -> float:
    """Time in which an op with a ``tf_op`` that ``keep`` accepts runs."""
    return trace_reduce.length(trace_reduce.union(
        (s, e) for s, e, tf_op in ops if keep(tf_op)))


def step_ops(reading, module: str):
    """``({chip: [(start, end, tf_op or None)]}, note)``: each chip's ops
    that run inside a program whose name starts with ``module``, clipped
    to the window, and the shares every metric's note gives (None where
    no such op ran). Worked out once for a reading."""
    scopes = xplane_scopes.for_reading(reading)
    if module in scopes.step_ops:
        return scopes.step_ops[module]
    lo, hi = trace_reduce.window(reading.trace)
    by_chip = {}
    total = named = scoped = 0.0
    for chip, lines in reading.trace.devices.items():
        programs = trace_reduce.union(
            (s, e) for s, e, name in lines.get(MODULES_LINE, ())
            if name.startswith(module))
        starts = [s for s, _ in programs]
        ops = by_chip[chip] = []
        for s, e, name in lines.get(trace_reduce.OPS_LINE, ()):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= programs[i][1]:
                continue
            s, e = max(s, lo), min(e, hi)
            if e > s:
                ops.append((s, e, scopes.ops.get(name)))
        total += device_ns(ops, lambda t: True)
        named += device_ns(ops, lambda t: t is not None)
        scoped += device_ns(ops, lambda t: t is not None
                            and bool(SCOPED.search(t)))
    note = None
    if total:
        note = {"scoped_share": scoped / total, "tf_op_share": named / total}
        if named and not scoped:
            note["stale_metadata"] = True
    scopes.step_ops[module] = by_chip, note
    return scopes.step_ops[module]


def reduce(reading, pattern: str, invert: bool = False,
           module: str = "jit_train_step(", split=()):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    by_chip, note = step_ops(reading, module)
    if note is None:
        return None
    if note.get("stale_metadata"):
        return None, note
    rx = re.compile(pattern)

    def ours(tf_op):
        return (tf_op is not None and bool(rx.search(tf_op))) != invert

    def per_step_ms(keep):
        return sum(device_ns(ops, keep) for ops in by_chip.values()) \
            / len(trace.devices) / reading.steps / 1e6

    if split:
        def under(name):
            inner = re.compile(scope(name))
            return per_step_ms(lambda t: ours(t) and t is not None
                               and bool(inner.search(t)))
        note = dict(note, by_scope_ms={name: under(name) for name in split})
    return per_step_ms(ours), note
