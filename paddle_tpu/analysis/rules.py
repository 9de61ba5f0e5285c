"""Pluggable rule registry for the jaxpr analyzer.

A rule is a generator taking a :class:`RuleContext` and yielding
:class:`~paddle_tpu.analysis.report.Finding`s via ``ctx.finding(...)``
(rule id and severity are stamped by the runner from the registration).
Register with::

    @register_rule("my-rule", "warning")
    def my_rule(ctx):
        for site in ctx.sites:
            if looks_wrong(site.eqn):
                yield ctx.finding(site, "why it is wrong")

Severity contract: "error" findings gate CI (tools/lint_program.py exits
non-zero); "warning" is a likely perf/correctness hazard the shipped
models are allowed to carry; "info" is advisory. Built-in rules below
cover the reference platform's pre-execution pass checklist translated
to jaxpr-land: dtype-promotion leaks, collective misuse, host
round-trips, donation misses, recompilation hazards, dead code, and
oversized gathers.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Dict, Iterable, List, Optional

from .report import SEVERITIES, Finding
from .walker import (EqnSite, iter_jaxprs, pallas_kernel_name,
                     source_summary, subjaxprs, unwrap, walk)

__all__ = [
    "AnalysisConfig", "RuleContext", "Rule", "RULES", "register_rule",
    "run_rules", "COLLECTIVE_AXIS_PARAMS", "collective_axes",
]


@dataclass(frozen=True)
class AnalysisConfig:
    """Thresholds and knobs shared by all rules."""
    donate_min_bytes: float = 1 << 20      # 1 MiB: smaller args are cheap
    allgather_warn_bytes: float = 64 << 20  # 64 MiB gathered output
    while_trips: float = 1.0               # assumed while-loop trip count
    top_k: int = 10                        # cost-table length
    check_fp64: bool = True
    # link-mismatch: fp32 payloads below this cross DCN without a finding
    # (per-block scale exchanges are tiny and legitimately uncompressed)
    dcn_uncompressed_min_bytes: float = 1 << 20
    # exchange-not-overlapped: the caller's intended grad-exchange bucket
    # count. 0 = unknown (rule stays silent); 1 = monolithic mode (gated
    # off by design); >= 2 = bucketed, the rule checks the collectives
    # actually interleave with compute. ParallelTrainer.compile injects
    # its own K when the caller leaves this at 0.
    grad_sync_buckets: int = 0
    # an equation is "compute-heavy" for the overlap rule at/above this
    # many FLOPs (filters out the scalar bookkeeping that trails every
    # program and would hide a genuinely serialized exchange)
    overlap_min_flops: float = 1e5
    # implicit-resharding: sites below this payload are scalar noise
    # (loss all-reduces, guard flags) and stay silent
    reshard_min_bytes: float = 4096.0
    # implicit-resharding escalates warning -> error when the collective
    # crosses a DCN axis at/above this payload
    dcn_reshard_error_bytes: float = 64 << 20
    # replicated-large-param: a replicated invar this big, with a
    # shardable mesh axis available, should be ZeRO-sharded
    replicated_param_min_bytes: float = 8 << 20
    shardable_axes: tuple = ("sharding",)
    disabled_rules: frozenset = frozenset()


class RuleContext:
    """Everything a rule may inspect about one program.

    sites   — every equation recursively, with path/axes/trips context.
    closed  — the ClosedJaxpr under analysis (consts available).
    mesh    — the active device mesh (None = don't check axis membership).
    donated — flat indices of donated top-level invars, or None when the
              caller has no donation info (then the top-level pjit
              equations' own ``donated_invars`` params are consulted).
    in_specs — one PartitionSpec/NamedSharding per flat top-level invar
              (the staged step's real layouts), or None: the seed for
              the sharding-propagation pass (:meth:`sharding`).
    """

    def __init__(self, closed, mesh=None, donated=None,
                 config: Optional[AnalysisConfig] = None, in_specs=None):
        self.closed = closed
        self.raw, self.consts = unwrap(closed)
        self.mesh = mesh
        self.donated = frozenset(donated) if donated is not None else None
        self.config = config or AnalysisConfig()
        self.in_specs = list(in_specs) if in_specs is not None else None
        self._sharding = False  # not-yet-computed sentinel
        # bound_axes starts empty on purpose: only shard_maps inside the
        # program bind axes; the mesh is checked by the membership rule.
        self.sites: List[EqnSite] = list(walk(closed))

    def sharding(self):
        """The sharding-propagation result (analysis/sharding) for this
        program, computed lazily on first rule access; None when no mesh
        or no in_specs were provided (nothing to seed from) or the pass
        failed."""
        if self._sharding is False:
            self._sharding = None
            if self.mesh is not None and self.in_specs is not None:
                try:
                    from .sharding import propagate
                    self._sharding = propagate(
                        self.closed, self.mesh, self.in_specs,
                        while_trips=self.config.while_trips)
                except Exception:
                    self._sharding = None
        return self._sharding

    def finding(self, site: Optional[EqnSite], message: str,
                severity: str = "") -> Finding:
        """A Finding pinned to a site. Rule id is stamped by the runner;
        severity too, unless the rule overrides it here (e.g. a warning
        rule escalating one specific finding to error)."""
        if site is None:
            return Finding(rule="", severity=severity, message=message)
        return Finding(
            rule="", severity=severity, message=message,
            primitive=site.primitive,
            path="/".join(site.path) or "<top>", eqn_index=site.index,
            source=source_summary(site.eqn))

    def finding_at(self, message: str, *, primitive: str = "",
                   path=(), eqn_index: int = -1,
                   source: Optional[str] = None,
                   severity: str = "") -> Finding:
        """A Finding pinned by raw coordinates (for rules working from
        derived site lists rather than EqnSites)."""
        if not isinstance(path, str):
            path = "/".join(path)
        return Finding(
            rule="", severity=severity, message=message,
            primitive=primitive, path=path or "<top>",
            eqn_index=eqn_index, source=source)


@dataclass(frozen=True)
class Rule:
    id: str
    severity: str
    fn: Callable[[RuleContext], Iterable[Finding]]
    doc: str = ""


RULES: Dict[str, Rule] = {}


def register_rule(rule_id: str, severity: str):
    """Decorator adding a rule to the global registry."""
    if severity not in SEVERITIES:
        raise ValueError(f"severity must be one of {SEVERITIES}, "
                         f"got {severity!r}")

    def deco(fn):
        if rule_id in RULES:
            raise ValueError(f"duplicate rule id {rule_id!r}")
        RULES[rule_id] = Rule(rule_id, severity, fn,
                              (fn.__doc__ or "").strip())
        return fn
    return deco


def run_rules(closed, mesh=None, donated=None,
              config: Optional[AnalysisConfig] = None,
              rules: Optional[Iterable[str]] = None,
              in_specs=None, ctx: Optional[RuleContext] = None
              ) -> List[Finding]:
    """Run (a subset of) the registry over one ClosedJaxpr. A finding
    whose rule set an explicit valid severity keeps it (escalation);
    otherwise the rule's registered severity is stamped."""
    cfg = config or AnalysisConfig()
    if ctx is None:
        ctx = RuleContext(closed, mesh=mesh, donated=donated, config=cfg,
                          in_specs=in_specs)
    out: List[Finding] = []
    selected = RULES.keys() if rules is None else rules
    for rid in selected:
        rule = RULES[rid]
        if rid in cfg.disabled_rules:
            continue
        for f in rule.fn(ctx):
            sev = f.severity if f.severity in SEVERITIES else rule.severity
            out.append(replace(f, rule=rule.id, severity=sev))
    return out


# ---------------------------------------------------------------------------
# built-in rules
# ---------------------------------------------------------------------------

COLLECTIVE_AXIS_PARAMS = {
    # primitive -> params key holding its axis name(s)
    "psum": "axes", "pmax": "axes", "pmin": "axes",
    "all_gather": "axis_name", "all_to_all": "axis_name",
    "ppermute": "axis_name", "pbroadcast": "axis_name",
    "psum_scatter": "axis_name", "reduce_scatter": "axis_name",
    "axis_index": "axis_name",
}


def collective_axes(eqn) -> tuple:
    """The *named* axes a collective equation operates over (positional
    vmap axes, which appear as ints, are skipped — they are resolved at
    trace time and cannot be misused here)."""
    key = COLLECTIVE_AXIS_PARAMS.get(eqn.primitive.name)
    if key is None:
        return ()
    axes = eqn.params.get(key)
    if axes is None:
        return ()
    if isinstance(axes, (str,)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


_F64 = ("float64", "complex128")


@register_rule("fp64-leak", "error")
def fp64_leak(ctx):
    """float64/complex128 values in the program: TPUs have no fp64
    units, so these run emulated (or crash compile) — almost always a
    jax_enable_x64 leak or a numpy-double const sneaking in."""
    if not ctx.config.check_fp64:
        return
    for site in ctx.sites:
        bad = [v for v in site.eqn.outvars
               if getattr(getattr(v, "aval", None), "dtype", None) is not None
               and v.aval.dtype.name in _F64]
        if bad:
            yield ctx.finding(
                site, f"{site.primitive} produces {bad[0].aval.dtype.name}; "
                      "TPUs have no fp64 units (check jax_enable_x64 and "
                      "numpy float64 constants)")


@register_rule("amp-fp32-leak", "warning")
def amp_fp32_leak(ctx):
    """A matmul executing in fp32 on operands that were explicitly
    upcast from bf16/fp16 — the silent-promotion pattern that makes an
    AMP region pay full-precision MXU time anyway."""
    low = ("bfloat16", "float16")
    for path, raw in iter_jaxprs(ctx.closed):
        producer = {}
        for eqn in raw.eqns:
            for v in eqn.outvars:
                producer[id(v)] = eqn
        for i, eqn in enumerate(raw.eqns):
            if eqn.primitive.name != "dot_general":
                continue
            out_dt = getattr(eqn.outvars[0].aval.dtype, "name", "")
            if out_dt != "float32":
                continue
            for opnd in eqn.invars[:2]:
                src = producer.get(id(opnd))
                if (src is not None
                        and src.primitive.name == "convert_element_type"
                        and getattr(src.invars[0], "aval", None) is not None
                        and src.invars[0].aval.dtype.name in low
                        and opnd.aval.dtype.name == "float32"):
                    site = EqnSite(eqn, path, i, frozenset(), 1.0,
                                   False, False)
                    yield ctx.finding(
                        site,
                        f"fp32 matmul on operand upcast from "
                        f"{src.invars[0].aval.dtype.name}: the AMP region "
                        "pays full-precision MXU time (keep the matmul in "
                        "bf16 and upcast the result instead)")
                    break


@register_rule("collective-unbound-axis", "error")
def collective_unbound_axis(ctx):
    """A collective over an axis name no enclosing shard_map binds.
    Under jit this NameErrors at trace time, but programs built with
    axis_env tracing or vmap without axis_name reach here with the axis
    dangling — at run time the collective is a no-op or a crash."""
    for site in ctx.sites:
        for ax in collective_axes(site.eqn):
            if ax not in site.bound_axes:
                yield ctx.finding(
                    site, f"{site.primitive} over axis {ax!r} which no "
                          "enclosing shard_map binds (psum under vmap needs "
                          "axis_name; collectives need to run inside "
                          "shard_map over that axis)")


@register_rule("collective-axis-not-in-mesh", "error")
def collective_axis_not_in_mesh(ctx):
    """A collective over an axis that IS bound by a shard_map but does
    not exist in the active device mesh — the program was written for a
    different mesh layout than the one it will run on."""
    if ctx.mesh is None:
        return
    mesh_axes = set(getattr(ctx.mesh, "axis_names", ()))
    for site in ctx.sites:
        for ax in collective_axes(site.eqn):
            if ax in site.bound_axes and ax not in mesh_axes:
                yield ctx.finding(
                    site, f"{site.primitive} over axis {ax!r} which is not "
                          f"in the active mesh (axes: "
                          f"{sorted(mesh_axes)})")


@register_rule("ppermute-non-permutation", "error")
def ppermute_non_permutation(ctx):
    """ppermute whose (src, dst) pairs are not a partial permutation —
    a duplicated source sends twice (one wins arbitrarily) and a
    duplicated destination receives garbage; jax traces it silently."""
    for site in ctx.sites:
        if site.primitive != "ppermute":
            continue
        perm = site.eqn.params.get("perm") or ()
        srcs = [p[0] for p in perm]
        dsts = [p[1] for p in perm]
        if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
            yield ctx.finding(
                site, f"ppermute perm {list(perm)!r} is not a permutation "
                      "(duplicate source or destination device)")


_HOST_CALLBACKS = frozenset({
    "pure_callback", "debug_callback", "io_callback", "callback",
    "host_callback", "outside_call",
})


@register_rule("host-callback", "warning")
def host_callback(ctx):
    """A host round-trip (pure_callback/debug_callback/io_callback)
    inside the program: on TPU this stalls the device every step —
    worse inside a scan/while body where it fires per trip."""
    for site in ctx.sites:
        if site.primitive in _HOST_CALLBACKS:
            where = " inside a loop body" if site.in_loop else ""
            yield ctx.finding(
                site, f"{site.primitive} forces a host round-trip on the "
                      f"hot path{where}; move it out of the jitted step or "
                      "behind a debug flag")


def _aval_nbytes(v) -> float:
    aval = getattr(v, "aval", None)
    dtype = getattr(aval, "dtype", None)
    size = getattr(aval, "size", None)
    if dtype is None or size is None:
        return 0.0
    return float(size) * getattr(dtype, "itemsize", 4)


def _human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0:
            return f"{n:.4g}{unit}"
        n /= 1024.0
    return f"{n:.4g}TiB"


@register_rule("non-donated-large-arg", "warning")
def non_donated_large_arg(ctx):
    """A large input buffer the jitted step does not donate: XLA must
    keep the old value live across the step, doubling its HBM footprint
    — the classic forgotten ``donate_argnums`` on params/opt state."""
    thresh = ctx.config.donate_min_bytes
    if ctx.donated is not None:
        # the caller (e.g. ParallelTrainer.compile) told us exactly
        # which flat invars it donates — authoritative, skip pjit scan
        for i, v in enumerate(ctx.raw.invars):
            nb = _aval_nbytes(v)
            if i not in ctx.donated and nb >= thresh:
                yield ctx.finding(
                    None, f"input #{i} ({_human_bytes(nb)}) is not donated; "
                          "donating it lets XLA reuse the buffer in-place "
                          "(donate_argnums)")
        return
    # otherwise: inspect top-level jit/pjit equations' own donation masks
    for site in ctx.sites:
        if site.path != () or site.primitive not in ("pjit", "jit",
                                                     "xla_call"):
            continue
        donated = site.eqn.params.get("donated_invars")
        if donated is None:
            continue
        for i, (v, d) in enumerate(zip(site.eqn.invars, donated)):
            nb = _aval_nbytes(v)
            if not d and nb >= thresh:
                yield ctx.finding(
                    site, f"jitted call input #{i} ({_human_bytes(nb)}) is "
                          "not donated; donating it lets XLA reuse the "
                          "buffer in-place (donate_argnums)")


@register_rule("recompile-scalar-const", "info")
def recompile_scalar_const(ctx):
    """0-d constants baked into the trace: if the Python value changes
    (a float hyper-parameter, a step count), jit retraces and recompiles
    the whole program — pass it as an argument instead."""
    for cv, val in zip(ctx.raw.constvars, ctx.consts):
        aval = getattr(cv, "aval", None)
        if aval is not None and getattr(aval, "shape", None) == ():
            dt = getattr(getattr(aval, "dtype", None), "name", "?")
            yield ctx.finding(
                None, f"0-d {dt} constant ({val!r}) baked into the trace; "
                      "changing its Python value forces a recompile — pass "
                      "it as an argument")


@register_rule("dead-equation", "info")
def dead_equation(ctx):
    """Equations whose outputs nothing consumes (and which have no side
    effects): wasted compute the user probably thinks is contributing —
    XLA DCEs them, so they also signal a tracing bug (e.g. a metric that
    never made it to the outputs)."""
    for path, raw in iter_jaxprs(ctx.closed):
        live = {id(v) for v in raw.outvars}
        dead_idx = []
        for i in range(len(raw.eqns) - 1, -1, -1):
            eqn = raw.eqns[i]
            if getattr(eqn, "effects", None):
                used = True  # effectful: never dead
            else:
                used = any(id(v) in live for v in eqn.outvars)
            if not used and not any(True for _ in subjaxprs(eqn)):
                dead_idx.append(i)
                continue  # its inputs don't become live
            for a in eqn.invars:
                if hasattr(a, "aval") and not hasattr(a, "val"):
                    live.add(id(a))
        # one finding per (scope, source line), not per equation: a dead
        # value usually drags a whole chain of producers with it and 30
        # findings for one forgotten expression is noise
        groups: dict = {}
        for i in reversed(dead_idx):
            site = EqnSite(raw.eqns[i], path, i, frozenset(), 1.0,
                           False, False)
            key = source_summary(raw.eqns[i])
            groups.setdefault(key, []).append(site)
        for src, sites in groups.items():
            first = sites[0]
            extra = f" (+{len(sites) - 1} more in its dead chain)" \
                if len(sites) > 1 else ""
            yield ctx.finding(
                first,
                f"{first.primitive} output is never used (no "
                f"effects){extra}; dead compute or a value that was "
                "meant to be returned")


_INT16_MAX = 2 ** 15 - 1


@register_rule("int4-grad-sync-overflow", "error")
def int4_grad_sync_overflow(ctx):
    """An int16 sum-reduction over n elements with n*7 > int16 range —
    the int4 grad-sync accumulation pattern (values in [-7, 7] summed
    over the axis size) with an accumulator too narrow for the rank
    count. compressed.int4_accum_dtype auto-widens to int32; a hand-
    rolled exchange that kept int16 silently wraps at ~4682 ranks."""
    for site in ctx.sites:
        if site.primitive != "reduce_sum":
            continue
        eqn = site.eqn
        in_dt = getattr(getattr(eqn.invars[0], "aval", None), "dtype", None)
        out_dt = getattr(getattr(eqn.outvars[0], "aval", None), "dtype",
                         None)
        if getattr(in_dt, "name", "") != "int16" or \
                getattr(out_dt, "name", "") != "int16":
            continue
        shape = getattr(eqn.invars[0].aval, "shape", ())
        axes = eqn.params.get("axes", ())
        n = 1
        for a in axes:
            if isinstance(a, int) and a < len(shape):
                n *= int(shape[a])
        if n * 7 > _INT16_MAX:
            yield ctx.finding(
                site, f"int16 sum over {n} elements: int4-range values "
                      f"(|q| <= 7) can reach {n * 7} > {_INT16_MAX} and "
                      "wrap — widen the accumulation to int32 "
                      "(compressed.int4_accum_dtype does this "
                      f"automatically past {_INT16_MAX // 7} ranks)")


_COMPRESSED_WIRE_DTYPES = ("int8", "uint8", "int4", "uint4")
_LINK_CHECK_PRIMS = ("psum", "all_to_all", "all_gather", "psum_scatter",
                     "reduce_scatter")


@register_rule("compressed-collective-link-mismatch", "warning")
def compressed_collective_link_mismatch(ctx):
    """Compressed (int8/int4-wire) collectives bound to ICI-only axes —
    where quantize overhead loses against the fast intra-slice links —
    and large uncompressed fp32 collectives crossing a DCN axis, using
    the mesh-axis -> link-type map (distributed/mesh.axis_links). Only
    active when the mesh's links were set explicitly or inference found
    a DCN axis: on a single-slice mesh every axis is trivially ICI and
    the gating question does not arise."""
    if ctx.mesh is None:
        return
    try:
        from ..distributed.mesh import axis_links, explicit_axis_links
        explicit = explicit_axis_links(ctx.mesh)
        links = axis_links(ctx.mesh)
    except Exception:
        return
    if explicit is None and "dcn" not in links.values():
        return
    min_bytes = ctx.config.dcn_uncompressed_min_bytes
    for site in ctx.sites:
        if site.primitive not in _LINK_CHECK_PRIMS:
            continue
        axes = [ax for ax in collective_axes(site.eqn) if ax in links]
        if not axes:
            continue
        dtname = getattr(
            getattr(getattr(site.eqn.invars[0], "aval", None), "dtype",
                    None), "name", "")
        nbytes = sum(_aval_nbytes(v) for v in site.eqn.invars)
        if dtname in _COMPRESSED_WIRE_DTYPES:
            if all(links[ax] == "ici" for ax in axes):
                yield ctx.finding(
                    site, f"compressed ({dtname}-wire) {site.primitive} "
                          f"over ICI-only axes {axes!r}: quantize overhead "
                          "loses on intra-slice links — gate the policy to "
                          "DCN axes (grad_sync_dcn_only / per-axis policy)")
        elif dtname == "float32" and nbytes >= min_bytes:
            dcn = [ax for ax in axes if links[ax] == "dcn"]
            if dcn:
                yield ctx.finding(
                    site, f"uncompressed fp32 {site.primitive} "
                          f"({_human_bytes(nbytes)}) crosses DCN axis "
                          f"{dcn[0]!r}: cross-slice bandwidth is ~10-100x "
                          "scarcer than ICI — use the compressed exchange "
                          "(grad_sync=\"int8\"/\"int4\") on this axis")


@register_rule("oversized-allgather", "warning")
def oversized_allgather(ctx):
    """An all_gather whose replicated output exceeds the warning
    threshold: every device materializes the full gathered tensor, the
    usual way model-parallel programs quietly re-densify their memory
    footprint."""
    thresh = ctx.config.allgather_warn_bytes
    for site in ctx.sites:
        if site.primitive != "all_gather":
            continue
        in_b = sum(_aval_nbytes(v) for v in site.eqn.invars)
        # gathered size = participants x per-shard operand bytes; the
        # traced outvar aval under shard_map is per-shard, so sizing from
        # it under-fires by exactly the mesh factor on large meshes
        n = site.eqn.params.get("axis_size")
        if not isinstance(n, int) or n < 1:
            n = 1
            if ctx.mesh is not None:
                for ax in collective_axes(site.eqn):
                    n *= int(ctx.mesh.shape.get(ax, 1))
        out_b = max(in_b * max(n, 1),
                    sum(_aval_nbytes(v) for v in site.eqn.outvars))
        if out_b >= thresh:
            yield ctx.finding(
                site, f"all_gather materializes {_human_bytes(out_b)} "
                      f"({max(n, 1)}x {_human_bytes(in_b)}) on every "
                      "device (threshold "
                      f"{_human_bytes(thresh)}); consider keeping the "
                      "tensor sharded (psum_scatter / rechunk the "
                      "computation)")


@register_rule("pallas-config-untuned", "warning")
def pallas_config_untuned(ctx):
    """A Pallas kernel traced for a (shape-bucket, dtype, device) with no
    tuning-DB entry — it runs on compiled-in default blocks, the silent
    perf loss the autotuner (ops/pallas/tuner.py) exists to close. Run
    ``python -m paddle_tpu.ops.pallas.tuner`` on the target device (or
    ship a generic interpret-validated entry) to clear it."""
    from ..ops.pallas.flash_attention import FWD as flash_fwd
    from ..ops.pallas.tuner import entry_for_traced_call
    seen = set()
    for site in ctx.sites:
        if site.primitive != "pallas_call":
            continue
        kernel_name = pallas_kernel_name(site.eqn)
        # forward kernels only: the paired backward kernels of the same
        # call would re-report the identical missing entry
        if kernel_name not in (flash_fwd, "_ce_fwd_kernel",
                               "_paged_decode_kernel"):
            continue
        try:
            key, entry = entry_for_traced_call(kernel_name, site.eqn)
        except Exception:
            continue
        if key is None or entry is not None or key in seen:
            continue
        seen.add(key)
        yield ctx.finding(
            site, f"Pallas kernel {kernel_name.lstrip('_')} runs with "
                  f"default block configs: no tuning-DB entry for "
                  f"{key!r} (python -m paddle_tpu.ops.pallas.tuner "
                  "persists one)")


# grad-sync collectives: the primitives the compressed/bucketed exchange
# emits, over the batch-reduction axes. The bytes floor keeps scalar
# reductions (the loss pmean, guard flags) from counting as "exchange".
_GRAD_SYNC_PRIMS = ("psum", "pmax", "all_to_all", "all_gather",
                    "psum_scatter", "reduce_scatter")
_GRAD_SYNC_AXES = frozenset(("data", "sharding", "sep"))
_GRAD_SYNC_MIN_BYTES = 4096.0


@register_rule("exchange-not-overlapped", "warning")
def exchange_not_overlapped(ctx):
    """A bucketed (K >= 2) gradient exchange whose collectives all
    cluster together with no compute-heavy equation between the first
    and the last — in linear program order the backward finished before
    any exchange started, so collective time sits fully on the critical
    path and the bucketing bought nothing (hook misplaced, buckets
    collapsed to one, or the exchange got hoisted out of the backward).
    Gated off when ``config.grad_sync_buckets`` is 0 (unknown — callers
    that did not declare their mode) or 1 (monolithic by design)."""
    cfg = ctx.config
    if cfg.grad_sync_buckets < 2:
        return
    from .cost import _atomic_flops, eqn_flops
    from .walker import linear_schedule
    try:
        nodes = linear_schedule(ctx.closed)
    except Exception:
        return
    sync = []          # positions of grad-sync collectives
    heavy = []         # positions of compute-heavy equations
    first_node = None
    for pos, node in enumerate(nodes):
        eqn = node.eqn
        if not node.atomic and node.primitive in _GRAD_SYNC_PRIMS:
            axes = tuple(ax for ax in collective_axes(eqn)
                         if ax in node.bound_axes)
            if axes and set(axes) <= _GRAD_SYNC_AXES:
                if ctx.mesh is not None:
                    n = 1
                    for ax in axes:
                        n *= int(ctx.mesh.shape.get(ax, 1))
                    if n <= 1:
                        continue
                if sum(_aval_nbytes(v) for v in eqn.invars) >= \
                        _GRAD_SYNC_MIN_BYTES:
                    sync.append(pos)
                    if first_node is None:
                        first_node = node
                continue
        f = (_atomic_flops(eqn, cfg.while_trips) if node.atomic
             else eqn_flops(eqn)) * node.trips
        if f >= cfg.overlap_min_flops:
            heavy.append(pos)
    if not sync or not heavy:
        return
    lo, hi = min(sync), max(sync)
    if any(lo < p < hi for p in heavy):
        return  # compute interleaves with the exchange: overlapped
    site = EqnSite(first_node.eqn, first_node.path, first_node.index,
                   first_node.bound_axes, first_node.trips, False, False)
    yield ctx.finding(
        site,
        f"grad_sync_buckets={cfg.grad_sync_buckets} but all {len(sync)} "
        "grad-sync collectives cluster with no compute-heavy equation "
        "between them: the exchange is serialized after the backward "
        "instead of overlapping it (check the per-bucket custom_vjp "
        "hooks and that the buckets did not collapse to one)")


# ---------------------------------------------------------------------------
# sharding-propagation rules (need mesh + in_specs; silent otherwise)
# ---------------------------------------------------------------------------

def _site_key(s) -> tuple:
    """Dedup key collapsing custom_vjp fwd/bwd clones of one layout
    conflict (remat / partial_eval re-trace the same equation under a
    different path, but primitive, axes, payload and source line
    coincide) — the same strategy pallas-config-untuned uses."""
    return (s.kind, s.primitive, s.axes, round(s.bytes), s.source)


@register_rule("implicit-resharding", "warning")
def implicit_resharding(ctx):
    """A layout conflict the SPMD partitioner resolves with a silent
    collective (all-gather / all-to-all / all-reduce) that appears in no
    source line. Escalates to error when the collective crosses a DCN
    axis at/above ``dcn_reshard_error_bytes`` — cross-slice implicit
    traffic there dwarfs the compressed-exchange wins."""
    info = ctx.sharding()
    if info is None:
        return
    cfg = ctx.config
    seen = set()
    for s in info.sites:
        if s.bytes < cfg.reshard_min_bytes:
            continue
        if s.in_loop and s.trips > 1:
            continue   # resharding-in-scan-body owns these
        key = _site_key(s)
        if key in seen:
            continue
        seen.add(key)
        sev = ("error" if s.link == "dcn"
               and s.bytes >= cfg.dcn_reshard_error_bytes else "")
        loop = (f", x{s.trips:g} loop iterations" if s.in_loop
                and s.trips > 1 else "")
        yield ctx.finding_at(
            f"implicit {s.kind} over axes {list(s.axes)} "
            f"({_human_bytes(s.bytes)} payload, "
            f"{s.time_s * 1e6:.0f}us modeled on {s.link}{loop}): "
            f"{s.detail or 'operand layouts conflict'} — add a "
            "with_sharding_constraint or re-layout the producer so the "
            "partitioner need not reshard",
            primitive=s.primitive, path=s.path, eqn_index=s.eqn_index,
            source=s.source, severity=sev)


@register_rule("replicated-large-param", "warning")
def replicated_large_param(ctx):
    """A large donated input (params/optimizer state) enters fully
    replicated while a shardable mesh axis sits idle: every device holds
    the full tensor when ZeRO-style sharding along that axis would cut
    memory by the axis size."""
    if ctx.mesh is None or ctx.in_specs is None:
        return
    cfg = ctx.config
    sizes = {str(k): int(v) for k, v in dict(ctx.mesh.shape).items()}
    idle = [ax for ax in cfg.shardable_axes if sizes.get(ax, 1) > 1]
    if not idle:
        return
    from .sharding import from_pspec
    for i, v in enumerate(ctx.raw.invars):
        if i >= len(ctx.in_specs):
            break
        if ctx.donated is not None and i not in ctx.donated:
            continue
        nbytes = _aval_nbytes(v)
        if nbytes < cfg.replicated_param_min_bytes:
            continue
        aval = getattr(v, "aval", None)
        ndim = len(getattr(aval, "shape", ()))
        if ndim == 0:
            continue
        if from_pspec(ctx.in_specs[i], ndim, sizes).replicated:
            yield ctx.finding_at(
                f"invar {i} ({_human_bytes(nbytes)}, "
                f"{getattr(aval, 'str_short', lambda: '?')()}) is fully "
                f"replicated while mesh axis {idle[0]!r} "
                f"(size {sizes[idle[0]]}) is shardable: ZeRO-shard it "
                f"to cut per-device memory {sizes[idle[0]]}x",
                primitive="<invar>", path="<top>", eqn_index=-1)


@register_rule("sharding-constraint-dropped", "warning")
def sharding_constraint_dropped(ctx):
    """An explicit with_sharding_constraint layout erased before its
    consumer (a reshape/transpose/slice that cannot carry the axes): the
    constraint the author wrote is not the layout the partitioner uses,
    and the reshard it was meant to prevent happens anyway."""
    info = ctx.sharding()
    if info is None:
        return
    seen = set()
    for s in info.dropped_constraints:
        key = _site_key(s)
        if key in seen:
            continue
        seen.add(key)
        yield ctx.finding_at(
            f"sharding_constraint layout dropped at {s.primitive} "
            f"(axes {list(s.axes)}, {_human_bytes(s.bytes)}): "
            f"{s.detail or 'the op cannot carry the constrained axes'} "
            "— move the constraint after this op or constrain the "
            "consumer instead",
            primitive=s.primitive, path=s.path, eqn_index=s.eqn_index,
            source=s.source)


@register_rule("resharding-in-scan-body", "warning")
def resharding_in_scan_body(ctx):
    """An implicit reshard inside a scan/while body: the collective
    fires every iteration, multiplying its cost by the trip count. Hoist
    the layout change out of the loop or align the carry spec."""
    info = ctx.sharding()
    if info is None:
        return
    cfg = ctx.config
    seen = set()
    for s in info.sites:
        if not s.in_loop or s.trips <= 1 or s.bytes < cfg.reshard_min_bytes:
            continue
        key = _site_key(s)
        if key in seen:
            continue
        seen.add(key)
        yield ctx.finding_at(
            f"implicit {s.kind} over axes {list(s.axes)} inside a loop "
            f"body fires ~{s.trips:g}x per step "
            f"({_human_bytes(s.bytes)} payload each, "
            f"{s.time_s * s.trips * 1e6:.0f}us modeled total on "
            f"{s.link}): hoist the reshard out of the loop or make the "
            "carry layout match",
            primitive=s.primitive, path=s.path, eqn_index=s.eqn_index,
            source=s.source)
