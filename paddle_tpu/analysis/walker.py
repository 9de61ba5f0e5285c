"""Canonical recursive jaxpr walker.

The repo's IR is the jaxpr (SURVEY.md §7: jaxprs + XLA replace
ProgramDesc/Graph), but until this module three call sites each grew
their own partial walker: ``onnx/_trace_writer.py`` (inline dispatch for
pjit/remat/custom_vjp that silently missed ``remat2``),
``static.Program.num_ops`` (top-level equations only), and
``tools/pipeline_flops.py`` (``_sub_jaxprs`` generic param scan). This is
the one shared traversal they all use now: it knows every higher-order
primitive's inner-jaxpr layout (pjit/scan/while/cond/checkpoint/
custom_jvp/custom_vjp/shard_map), tracks the axis names each shard_map
binds, and carries loop trip counts so cost models can price scan bodies
per-iteration (XLA's cost_analysis prices a While body once).

Three entry points:
- ``walk(jaxpr)``       — yield an :class:`EqnSite` for every equation,
  recursively, with path/bound-axes/trip-count context.
- ``subjaxprs(eqn)``    — the inner jaxprs of one equation, labeled and
  classified (call/scan/while/cond), for structure-aware recursion.
- ``inline_target(eqn)``— the single transparently-inlineable body of a
  call-like equation (pjit/jit/remat/checkpoint/custom_jvp/custom_vjp),
  or None — the ONNX converter's dispatch predicate.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

__all__ = [
    "EqnSite", "SubJaxpr", "INLINE_CALL_PRIMS", "unwrap", "inline_target",
    "subjaxprs", "has_inner", "walk", "iter_jaxprs", "count_eqns",
    "pallas_kernel_name", "source_summary", "SchedNode", "linear_schedule",
]

# call-like primitives whose single inner jaxpr is semantically the
# equation itself (no control flow, no axis binding): safe to inline.
# Spellings across jax versions: remat/remat2/checkpoint, jit/pjit,
# custom_{jvp,vjp}_call[_jaxpr].
INLINE_CALL_PRIMS = frozenset({
    "jit", "pjit", "xla_call", "closed_call", "core_call", "call",
    "remat", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr", "custom_lin",
})

# params that hold the inline body, in lookup order (pjit/scan use
# "jaxpr", call primitives "call_jaxpr", older custom_vjp "fun_jaxpr")
_INLINE_PARAM_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _is_jaxpr(obj) -> bool:
    return hasattr(obj, "eqns") or hasattr(obj, "jaxpr")


def unwrap(obj) -> Tuple[object, list]:
    """(raw jaxpr, consts) from a ClosedJaxpr or raw Jaxpr."""
    if hasattr(obj, "jaxpr"):
        return obj.jaxpr, list(getattr(obj, "consts", ()) or ())
    return obj, []


def inline_target(eqn):
    """Inner jaxpr (ClosedJaxpr or raw) of a transparently-inlineable
    call equation; None for control flow / shard_map / leaf primitives."""
    if eqn.primitive.name not in INLINE_CALL_PRIMS:
        return None
    for k in _INLINE_PARAM_KEYS:
        v = eqn.params.get(k)
        if v is not None and _is_jaxpr(v):
            return v
    return None


@dataclass(frozen=True)
class SubJaxpr:
    """One inner jaxpr of an equation, with traversal semantics.

    kind:  "call" (transparent), "scan" (trips = static length),
           "while" (trips unknown), "cond" (one of mutually-exclusive
           branches), "shard_map" (binds mesh axes), "other".
    trips: per-entry execution count of the body, None when unknown
           (while loops).
    """
    label: str
    jaxpr: object  # raw Jaxpr
    consts: tuple
    kind: str = "call"
    trips: Optional[float] = 1.0


def _label(eqn) -> str:
    name = eqn.primitive.name
    fn_name = eqn.params.get("name")
    if isinstance(fn_name, str) and fn_name:
        return f"{name}:{fn_name}"
    return name


def subjaxprs(eqn) -> Iterator[SubJaxpr]:
    """The inner jaxprs of one equation, labeled and classified."""
    name = eqn.primitive.name
    params = eqn.params
    if name == "scan":
        j, c = unwrap(params["jaxpr"])
        yield SubJaxpr("scan", j, tuple(c), kind="scan",
                       trips=float(params.get("length", 1)))
        return
    if name == "while":
        for key in ("cond_jaxpr", "body_jaxpr"):
            j, c = unwrap(params[key])
            yield SubJaxpr(f"while[{key.split('_')[0]}]", j, tuple(c),
                           kind="while", trips=None)
        return
    if name == "cond":
        for i, br in enumerate(params.get("branches", ())):
            j, c = unwrap(br)
            yield SubJaxpr(f"cond[{i}]", j, tuple(c), kind="cond")
        return
    if name == "shard_map":
        j, c = unwrap(params["jaxpr"])
        yield SubJaxpr("shard_map", j, tuple(c), kind="shard_map")
        return
    inner = inline_target(eqn)
    if inner is not None:
        j, c = unwrap(inner)
        yield SubJaxpr(_label(eqn), j, tuple(c), kind="call")
        return
    # generic fallback: any params value that is (or contains) a jaxpr —
    # keeps the walker total over primitives it has never heard of
    for v in params.values():
        if _is_jaxpr(v):
            j, c = unwrap(v)
            yield SubJaxpr(_label(eqn), j, tuple(c), kind="other")
        elif isinstance(v, (list, tuple)):
            for x in v:
                if _is_jaxpr(x):
                    j, c = unwrap(x)
                    yield SubJaxpr(_label(eqn), j, tuple(c), kind="other")


def has_inner(eqn) -> bool:
    """True when the equation carries any inner jaxpr (higher-order)."""
    for _ in subjaxprs(eqn):
        return True
    return False


@dataclass(frozen=True)
class EqnSite:
    """One equation plus its traversal context."""
    eqn: object
    path: Tuple[str, ...]      # labels of the enclosing call stack
    index: int                 # position within its own jaxpr
    bound_axes: frozenset      # mesh axes bound by enclosing shard_maps
    trips: float               # product of enclosing static trip counts
    in_loop: bool              # inside any scan/while body
    in_branch: bool            # inside a cond branch

    @property
    def depth(self) -> int:
        return len(self.path)

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name

    def where(self) -> str:
        loc = "/".join(self.path) or "<top>"
        return f"{loc}#{self.index}"


def walk(jaxpr, bound_axes=frozenset(), _path=(), _trips=1.0,
         _in_loop=False, _in_branch=False) -> Iterator[EqnSite]:
    """Yield an EqnSite for every equation, outer-before-inner."""
    raw, _ = unwrap(jaxpr)
    for i, eqn in enumerate(raw.eqns):
        yield EqnSite(eqn, _path, i, bound_axes, _trips, _in_loop,
                      _in_branch)
        for sub in subjaxprs(eqn):
            axes = bound_axes
            if sub.kind == "shard_map":
                mesh = eqn.params.get("mesh")
                axes = bound_axes | set(getattr(mesh, "axis_names", ()))
            trips = _trips * (sub.trips if sub.trips else 1.0)
            yield from walk(
                sub.jaxpr, axes, _path + (sub.label,), trips,
                _in_loop or sub.kind in ("scan", "while"),
                _in_branch or sub.kind == "cond")


def iter_jaxprs(jaxpr, _path=()) -> Iterator[Tuple[Tuple[str, ...], object]]:
    """Yield (path, raw jaxpr) for the program and every nested jaxpr —
    for per-scope analyses (liveness, producer maps)."""
    raw, _ = unwrap(jaxpr)
    yield _path, raw
    for eqn in raw.eqns:
        for sub in subjaxprs(eqn):
            yield from iter_jaxprs(sub.jaxpr, _path + (sub.label,))


@dataclass(frozen=True)
class SchedNode:
    """One step of the linearized program order (see
    :func:`linear_schedule`).

    atomic: the node stands for a whole inner program (scan/while/cond/
    unknown higher-order) billed as one compute block; transparent call
    shells and shard_map never appear — their bodies are flattened in.
    in_ids/out_ids: canonical variable identities for dataflow (opaque
    hashables) — call / shard_map boundary variables are aliased
    through, so a consumer's in_id matches the producer's out_id across
    those boundaries. Identities are namespaced per inlined body
    INSTANCE: jax caches and shares jaxpr bodies across call sites, so
    the same Var object shows up in every instantiation and raw id()
    would weld unrelated call sites together.
    """
    eqn: object
    path: Tuple[str, ...]
    index: int
    bound_axes: frozenset
    trips: float
    atomic: bool
    in_ids: Tuple
    out_ids: Tuple

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name


_TRANSPARENT_KINDS = ("call", "shard_map")


def linear_schedule(jaxpr) -> list:
    """Linearize a program into the flat equation order a sequential
    executor would run: transparent call shells (pjit/remat/custom_vjp)
    and shard_map are flattened into their bodies with boundary-variable
    aliasing, while control flow (scan/while/cond) stays atomic — one
    node billed as its whole body. This is the schedule the overlap model
    (analysis/cost.py) simulates: equation order IS issue order, and the
    aliased ids give true producer->consumer edges across call shells, so
    a collective issued mid-backward is visibly separated from the
    compute that consumes its result."""
    nodes = []
    alias = {}
    frames = iter(range(1 << 62))  # fresh namespace per inlined body

    def canon(fid, v):
        i = (fid, id(v))
        seen = 0
        while i in alias and seen < 1000:
            i = alias[i]
            seen += 1
        return i

    def is_var(a):
        return hasattr(a, "aval") and not hasattr(a, "val")

    def go(raw, bound_axes, path, trips, fid):
        for i, eqn in enumerate(raw.eqns):
            subs = list(subjaxprs(eqn))
            sub = subs[0] if len(subs) == 1 else None
            if sub is not None and sub.kind in _TRANSPARENT_KINDS:
                inner = sub.jaxpr
                gid = next(frames)
                axes = bound_axes
                if sub.kind == "shard_map":
                    mesh = eqn.params.get("mesh")
                    axes = bound_axes | set(
                        getattr(mesh, "axis_names", ()))
                outer_in = list(eqn.invars)
                inner_in = list(inner.invars)
                # call consts ride first in the outer invars: align the
                # body's invars with the outer TAIL; skip aliasing
                # entirely on an unexpected arity mismatch (the body
                # still linearizes, its inputs just read as ready)
                if len(outer_in) > len(inner_in):
                    outer_in = outer_in[len(outer_in) - len(inner_in):]
                if len(outer_in) == len(inner_in):
                    for ov, iv in zip(outer_in, inner_in):
                        if is_var(ov):
                            alias[(gid, id(iv))] = canon(fid, ov)
                for ov, iv in zip(eqn.outvars, inner.outvars):
                    if is_var(iv):
                        alias[(fid, id(ov))] = canon(gid, iv)
                go(inner, axes, path + (sub.label,), trips, gid)
                continue
            nodes.append(SchedNode(
                eqn=eqn, path=path, index=i, bound_axes=bound_axes,
                trips=trips, atomic=bool(subs),
                in_ids=tuple(canon(fid, a) for a in eqn.invars
                             if is_var(a)),
                out_ids=tuple(canon(fid, v) for v in eqn.outvars)))

    raw, _ = unwrap(jaxpr)
    go(raw, frozenset(), (), 1.0, next(frames))
    return nodes


def count_eqns(jaxpr) -> int:
    """Total equation count, recursively through all inner jaxprs."""
    return sum(1 for _ in walk(jaxpr))


def pallas_kernel_name(eqn) -> str:
    """Kernel name of a ``pallas_call`` equation: the ``name`` the call
    was given, else the kernel function's name from the kernel jaxpr's
    debug info (where jax 0.9 keeps it); "" when neither is there."""
    return eqn.params.get("name") or getattr(
        getattr(eqn.params.get("jaxpr"), "debug_info", None),
        "func_name", "") or ""


def source_summary(eqn) -> Optional[str]:
    """Best-effort user-code provenance ("file.py:42 (fn)") for an
    equation; None when jax's source-info internals moved."""
    try:
        from jax._src import source_info_util
        s = source_info_util.summarize(eqn.source_info)
        return s or None
    except Exception:
        return None
