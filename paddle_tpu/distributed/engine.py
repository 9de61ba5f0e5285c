"""Hybrid-parallel training engine.

The TPU-native replacement for the reference's entire multi-device execution
stack: ParallelExecutor + SSA graph (framework/parallel_executor.cc),
meta-optimizer program rewriting (fleet/meta_optimizers/*), and the
Trainer/SectionWorker runtime (framework/trainer.h) collapse into ONE jitted
step built here:

    loss/grads  — shard_map over the ("data","pipe","sharding","sep","model")
                  mesh: DP = batch split over data(+sharding) with pmean'd
                  grads; TP = explicit collectives inside mp_layers;
                  PP = GPipe/ppermute schedule (pipeline_parallel.py);
                  SP = ring attention over "sep" (ops/ring_attention.py).
    update      — GSPMD region: optimizer slots carry NamedShardings; ZeRO
                  stage-1/2 fall out of sharding the slots over "sharding"
                  (sharding_parallel.py), XLA inserts the gather/scatter that
                  sharding_optimizer.py:43 hand-writes.

One compiled XLA program per step: collectives are scheduled/overlapped by
XLA's latency-hiding scheduler (replacing reducer.cc:798's manual overlap).
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

shard_map = jax.shard_map

from .. import profiler as _profiler
from .. import telemetry as _telemetry
from ..telemetry import staging as _staging
from ..telemetry import tracing as _tracing
from ..framework.random import get_rng_key
from ..jit.functionalization import functional_call, state_of
from ..resilience.guard import all_finite
from .compressed import (QUANTIZED_POLICIES, compressed_psum_scatter,
                         compressed_tree_mean, normalize_axis_policies)
from .mesh import axis_links, require_mesh
from .meta_parallel.pipeline_parallel import PipelineParallel
from .meta_parallel.sharding_parallel import shard_spec_for

DATA_AXES = ("data", "sharding")  # batch is split over both (ZeRO ⊂ DP)

# what telemetry.staging calls the staged step (make_step's inner function)
_STEP_FUN = "train_step"

# XLA flags that make the TPU compiler schedule collectives asynchronously
# and hide them under compute — the hardware half of the bucketed
# backward-overlapped exchange (the jaxpr half is the per-bucket
# custom_vjp hooks in grads_fn). Must be in the environment BEFORE the
# TPU backend initializes; enable_latency_hiding_scheduler() is the
# idempotent setter.
LATENCY_HIDING_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def enable_latency_hiding_scheduler(env=None) -> bool:
    """Append :data:`LATENCY_HIDING_FLAGS` to ``LIBTPU_INIT_ARGS``,
    skipping any flag already present so operator overrides win. Returns
    True when the environment changed. libtpu reads these at backend
    initialization, so call this before the first jax device query (the
    trainer calls it best-effort when ``grad_sync_buckets > 1``; a late
    call is a no-op for the already-initialized process but still fixes
    child processes). Deliberately NOT mirrored into ``XLA_FLAGS``:
    CPU/GPU jaxlib builds hard-fail on unknown ``--xla_tpu_*`` flags
    there, which would poison every subprocess forked after a bucketed
    trainer is built."""
    import os
    env = os.environ if env is None else env
    cur = env.get("LIBTPU_INIT_ARGS", "")
    missing = [f for f in LATENCY_HIDING_FLAGS
               if f.split("=")[0] not in cur]
    if not missing:
        return False
    env["LIBTPU_INIT_ARGS"] = (cur + " " + " ".join(missing)).strip()
    return True


def partition_reverse_buckets(items, k: int):
    """Partition ``items`` ([(key, nbytes)] in FORWARD layer order) into
    at most ``k`` byte-balanced buckets in REVERSE order: bucket 0 holds
    the last layers — whose grads materialize first during backward — so
    its exchange is issued earliest and gets the longest compute shadow.
    Returns a list of non-empty key lists."""
    items = list(items)
    k = max(1, min(int(k), len(items)))
    total = float(sum(b for _, b in items)) or 1.0
    target = total / k
    buckets, cur, acc = [], [], 0.0
    rev = list(reversed(items))
    for i, (key, b) in enumerate(rev):
        cur.append(key)
        acc += float(b)
        left = len(rev) - i - 1
        need = k - 1 - len(buckets)  # buckets still owed after this one
        if need > 0 and (left == need or (acc >= target and left >= need)):
            buckets.append(cur)
            cur, acc = [], 0.0
    if cur:
        buckets.append(cur)
    return buckets


def _spec_has_axis(spec, axis: str) -> bool:
    return any(ax == axis or (isinstance(ax, tuple) and axis in ax)
               for ax in spec)


def _rank(x) -> int:
    """Array rank; works for arrays, scalars AND ShapeDtypeStructs (which
    jnp.shape rejects) so staging can run on abstract batches."""
    shape = getattr(x, "shape", None)
    return len(shape) if shape is not None else len(jnp.shape(x))


class ParallelTrainer:
    """Builds and runs the sharded jitted train step.

    model: Layer (possibly a meta_parallel wrapper). optimizer: Optimizer or
    HybridParallelOptimizer. loss_fn(outputs, labels) -> scalar (mean over
    the local microbatch).
    """

    def __init__(self, model, optimizer, loss_fn: Callable, mesh=None,
                 micro_batches: int = 1, remat: bool = False,
                 zero_stage: int = 0, accumulate_steps: int = 1,
                 fp16_allreduce: bool = False,
                 grad_sync: Optional[str] = None,
                 grad_sync_block: Optional[int] = None,
                 grad_sync_bucket_bytes: int = 4 << 20,
                 grad_sync_buckets: int = 1,
                 grad_sync_dcn_only: Optional[bool] = None,
                 nan_guard: bool = True,
                 integrity_check_every: int = 0,
                 scaler=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or require_mesh()
        self.micro_batches = micro_batches
        self.remat = remat
        self.zero_stage = zero_stage
        # In-step NaN/Inf guard (reference: check_finite_and_unscale +
        # update_loss_scaling IN the graph): one fused all-finite reduction
        # over the exchanged grads decides — via jnp.where, no host sync,
        # no recompile — whether this step's param/opt/comm_err update
        # applies or is skipped, bumping the skipped-steps counter carried
        # in state["guard"].
        self.nan_guard = nan_guard
        # Optional amp.GradScaler: its functional scale state rides in
        # state["guard"]["amp"]; the traced step scales the loss, unscales
        # the grads, and applies the dynamic incr/decr policy off the same
        # finite flag the guard uses.
        self.scaler = scaler if (scaler is not None
                                 and scaler.is_enable()) else None
        # gradient-exchange policy (distributed/compressed.py): the DP grad
        # sync is a bucketed flat exchange — "fp32" exact, "bf16" half the
        # wire bytes (reference fp16_allreduce_optimizer.py), "int8" the
        # EQuARX-style two-phase block-scaled exchange with error feedback
        # (~4x fewer bytes), "int4" its nibble-packed sibling (~7x).
        # grad_sync_dcn_only gates the quantized policy to the mesh axes
        # whose link type is "dcn" (mesh.axis_links): ICI hops stay fp32.
        # Resolution: explicit arg > the wrapper model's grad_sync
        # attribute (DataParallel / ShardingParallel strategy) > the
        # legacy fp16_allreduce flag.
        if grad_sync is None:
            grad_sync = getattr(model, "grad_sync", None)
            if grad_sync is not None:
                grad_sync_block = getattr(model, "grad_sync_block",
                                          grad_sync_block)
                grad_sync_bucket_bytes = getattr(
                    model, "grad_sync_bucket_bytes", grad_sync_bucket_bytes)
        if grad_sync_dcn_only is None:
            grad_sync_dcn_only = bool(getattr(model, "grad_sync_dcn_only",
                                              False))
        if grad_sync is None:
            grad_sync = "bf16" if fp16_allreduce else "fp32"
        self.grad_sync = grad_sync
        self.grad_sync_block = grad_sync_block
        self.grad_sync_bucket_bytes = grad_sync_bucket_bytes
        # K reverse-layer-order exchange buckets: K=1 is the monolithic
        # post-backward exchange; K>=2 splits the plain trainable leaves
        # into byte-balanced buckets and issues each bucket's exchange
        # INSIDE the backward (per-bucket custom_vjp hooks in grads_fn)
        # as soon as its layers' grads exist, so collective time hides
        # under the remaining backward compute.
        self.grad_sync_buckets = max(
            1, int(getattr(model, "grad_sync_buckets", grad_sync_buckets)))
        if self.grad_sync_buckets > 1:
            enable_latency_hiding_scheduler()
        self.grad_sync_dcn_only = grad_sync_dcn_only
        self.fp16_allreduce = fp16_allreduce or grad_sync == "bf16"
        # GradientMerge (reference: fleet/meta_optimizers
        # gradient_merge_optimizer + DistributedStrategy.gradient_merge):
        # split each batch into k chunks, accumulate grads, one optimizer step
        self.accumulate_steps = accumulate_steps
        # Silent-corruption defense (resilience/integrity.py): every
        # `integrity_check_every` steps the jitted step additionally
        # fingerprints params/opt/comm_err in-graph and compares the
        # data-replicated leaves across ranks with pmin/pmax. Two cached
        # programs (like LocalSGD's sync/non-sync split): the non-check
        # step carries ZERO fingerprint collectives and no recompiles
        # happen after the first check step. 0 disables the feature.
        self.integrity_check_every = max(0, int(getattr(
            model, "integrity_check_every", integrity_check_every)))
        self._steps_run = 0
        self.last_divergence: list = []
        self.state = None
        self._construct()

    @classmethod
    def from_plan(cls, plan, model, optimizer, loss_fn: Callable,
                  mesh=None, **overrides) -> "ParallelTrainer":
        """Build a trainer from an auto-parallel :class:`~.auto.Plan`.

        The plan's searched knobs (mesh degrees, grad_sync policy /
        dcn-gating / bucket count, remat, zero_stage, microbatch or
        accumulate count) become constructor kwargs via ``plan.apply()``;
        explicit ``overrides`` win over the plan, and an explicit
        ``mesh`` suppresses building (and installing) the plan's own."""
        kw = plan.apply(mesh=mesh, build_mesh=mesh is None)
        kw.update(overrides)
        return cls(model, optimizer, loss_fn, **kw)

    # -- state -------------------------------------------------------------
    def _construct(self):
        """State and step construction for ``self.mesh``, each under its
        span: on the profiler's clock, and in the staging record
        (``telemetry.staging``) beside what jax staged inside it."""
        with _staging.span("paddle_tpu.trainer.init_state"):
            self._init_state()
        with _staging.span("paddle_tpu.trainer.build"):
            self._build()
        # layers that manage live training state between steps (the
        # HeterPS hot tier) bind themselves here — forgetting a manual
        # attach() would silently train on the stale eager parameters
        if hasattr(self.model, "named_sublayers"):
            for _, sub in self.model.named_sublayers(include_self=True):
                hook = getattr(sub, "_on_trainer_built", None)
                if hook is not None:
                    hook(self)

    def _param_spec(self, name, p):
        return p.pspec if p.pspec is not None else P()

    def _init_state(self):
        # Every container of the state and of the specs that travel with
        # it is a plain dict, the node type the staged step returns: the
        # first train_step call then passes the pytree every later call
        # passes, and jit stages one program of the step, not two. The
        # dicts keep named_parameters' order for whoever iterates them
        # (the exchange's buckets); jit flattens a dict by sorted key.
        params, buffers = state_of(self.model)
        boxes = dict(self.model.named_parameters())
        self.param_specs = {n: self._param_spec(n, boxes[n]) for n in params}
        # buffers default replicated; models may pin specific buffers to a
        # mesh axis (pipe-stacked stage buffers, pp_layers.buffer_pspecs)
        bspecs = (self.model.named_buffer_pspecs()
                  if hasattr(self.model, "named_buffer_pspecs") else {})
        self.buffer_specs = {n: bspecs.get(n, P()) for n in buffers}
        self.trainable = {n: boxes[n].trainable for n in params}
        # ParamAttr(learning_rate=) multipliers, as Optimizer.step honours
        # them; where every one is 1 the staged update is what it was
        self.lr_scales = {n: boxes[n].optimize_attr["learning_rate"]
                          for n in params if boxes[n].optimize_attr.get(
                              "learning_rate", 1.0) != 1.0} or None
        tparams = {k: v for k, v in params.items() if self.trainable[k]}
        opt_state = self.optimizer.init_state(tparams)
        # place params/opt on the mesh. Copy: the step donates these buffers,
        # and the Layer's Parameters (or another trainer) may alias them.
        def put(v, spec):
            return jax.device_put(jnp.array(v, copy=True),
                                  NamedSharding(self.mesh, spec))

        n_shard = self.mesh.shape.get("sharding", 1)
        # ZeRO-3: shard parameter STORAGE over the "sharding" axis. Inside
        # the step each shard is all-gathered right before use (and the
        # gather's transpose reduce-scatters the grad), so per-device param
        # memory is 1/n_shard — the sharding_optimizer.py:43 capability done
        # the GSPMD way. Params already sharded by ShardingParallel stage 3
        # (pspec on the sharding axis) are honored too.
        if self.zero_stage >= 3 and n_shard > 1:
            for k in list(self.param_specs):
                if self.trainable[k] and self.param_specs[k] == P():
                    self.param_specs[k] = shard_spec_for(params[k], n_shards=n_shard)
        self.zero3_dims = {}
        if n_shard > 1:
            for k, spec in self.param_specs.items():
                for d, ax in enumerate(spec):
                    if ax == "sharding" or (isinstance(ax, tuple)
                                            and "sharding" in ax):
                        self.zero3_dims[k] = d
        # ZeRO-2: gradients leave the step SHARDED over the sharding axis
        # (reduce-scatter instead of all-reduce), so the grad buffers held
        # across gradient-merge accumulation are 1/n_shard per device
        # (reference sharding_optimizer stage os_g). zero-3 leaves are
        # already sharded; this covers the remaining trainable params.
        self.zero2_dims = {}
        if self.zero_stage >= 2 and n_shard > 1:
            for k in self.param_specs:
                if not self.trainable[k] or k in self.zero3_dims:
                    continue
                # only fully-replicated params are eligible: a TP-sharded
                # param (e.g. P(None, "model")) must keep its axis — naively
                # overwriting a dim with "sharding" would declare the grad
                # replicated over "model" while ranks hold different slices
                cur = self.param_specs[k]
                if any(ax is not None for ax in cur):
                    continue
                spec = shard_spec_for(params[k], n_shards=n_shard)
                for d, ax in enumerate(spec):
                    if ax == "sharding":
                        self.zero2_dims[k] = d
        params = {k: put(v, self.param_specs[k]) for k, v in params.items()}
        buffers = {k: put(v, self.buffer_specs[k])
                   for k, v in buffers.items()}
        self.opt_specs = self._slot_specs(opt_state, params, n_shard)
        opt_state = jax.tree_util.tree_map(
            lambda v, s: put(v, s), opt_state, self.opt_specs)
        # int8 grad sync: per-RANK error-feedback residuals, stored
        # replica-major — leading dim = product of the grad-reduce axes,
        # sharded over them so each rank owns exactly its own residual
        # (the DGC local-accumulation slot, kept as engine state because
        # the exchange happens inside the shard_map region)
        sep = self.mesh.shape.get("sep", 1) > 1
        axes = DATA_AXES + ("sep",) if sep else DATA_AXES
        # hand-built meshes may omit axes (build_mesh always has all five)
        self.reduce_axes = tuple(ax for ax in axes if ax in self.mesh.shape)
        # per-axis exchange policy: under DCN gating the quantized policy
        # rides only the axes whose link type is "dcn" (explicit
        # mesh.set_axis_links override, else inferred from slice
        # structure); ICI axes pre-reduce losslessly in fp32.
        if self.grad_sync_dcn_only and self.grad_sync in QUANTIZED_POLICIES:
            links = axis_links(self.mesh)
            self._axis_policy = {
                ax: (self.grad_sync if links.get(ax) == "dcn" else "fp32")
                for ax in self.reduce_axes}
            self._any_quantized = any(
                p in QUANTIZED_POLICIES for p in self._axis_policy.values())
        else:
            self._axis_policy = self.grad_sync
            self._any_quantized = self.grad_sync in QUANTIZED_POLICIES
        self.comm_err_specs = {}
        comm_err = {}
        # the sharded-grad (ZeRO-2/3) leaves exchange per-tensor through
        # compressed_psum_scatter over the "sharding" axis; when that
        # axis's policy quantizes they carry their own per-rank residual
        # (full-tensor shape: each rank's quantization error spans the
        # whole gradient, not just its scattered chunk)
        rs_pol = (self._axis_policy.get("sharding", "fp32")
                  if isinstance(self._axis_policy, dict)
                  else self._axis_policy)
        rs_quant = rs_pol in QUANTIZED_POLICIES
        ppm = self.model if isinstance(self.model, PipelineParallel) \
            else getattr(self.model, "_layers", None)
        is_1f1b = (isinstance(ppm, PipelineParallel)
                   and getattr(ppm, "schedule", "gpipe") == "1f1b")
        if self._any_quantized:
            R = 1
            for ax in self.reduce_axes:
                R *= self.mesh.shape.get(ax, 1)
            for k, v in params.items():
                if not self.trainable[k]:
                    continue
                if k in self.zero2_dims or k in self.zero3_dims:
                    # zero-3 grads only pass through the explicit
                    # (possibly quantized) reduce-scatter on the 1F1B
                    # manual-grad path; the AD path's gather transpose is
                    # a lossless psum_scatter and needs no residual
                    if not rs_quant or (k in self.zero3_dims
                                        and not is_1f1b):
                        continue
                    spec = P(self.reduce_axes)
                    self.comm_err_specs[k] = spec
                    comm_err[k] = put(
                        jnp.zeros((R,) + jnp.shape(v), jnp.float32), spec)
                    continue
                # trailing dims follow the param's own sharding: a TP- or
                # pipe-sharded param's residual differs per shard, so it
                # must be sharded the same way (declaring it replicated
                # would silently keep only one rank's residual)
                spec = P(self.reduce_axes, *self.param_specs[k])
                self.comm_err_specs[k] = spec
                comm_err[k] = put(
                    jnp.zeros((R,) + jnp.shape(v), jnp.float32), spec)
        # guard state: replicated scalars threaded through the jitted step
        # (skipped-step counter; plus the loss-scale state when a scaler is
        # attached) — in state so checkpoints carry it.
        guard = {"skipped": put(jnp.zeros((), jnp.int32), P())}
        if self.scaler is not None:
            guard["amp"] = jax.tree_util.tree_map(
                lambda v: put(v, P()), self.scaler.init_scale_state())
        self.state = {"params": params, "buffers": buffers,
                      "opt": opt_state, "comm_err": comm_err,
                      "guard": guard}

    def _slot_specs(self, opt_state, params, n_shard):
        """Sharding specs for the optimizer state.

        Slots follow their parameter's sharding: a pipe-stacked param
        (P("pipe", ...)) gets pipe-sharded moments — per-device slot memory
        1/pp, matching the reference's per-rank optimizer state under PP.
        With ZeRO (stage>=1) non-pipe params' slots shard over "sharding"
        instead (reference sharding_optimizer.py os segment)."""
        slot_specs = {}
        for k, st in opt_state.get("slots", {}).items():
            pspec = self.param_specs[k]
            has_pipe = _spec_has_axis(pspec, "pipe")
            if self.zero_stage >= 1 and n_shard > 1 and not has_pipe:
                slot_specs[k] = jax.tree_util.tree_map(
                    lambda v: shard_spec_for(v, n_shards=n_shard), st)
            else:
                pshape = jnp.shape(params[k])
                slot_specs[k] = jax.tree_util.tree_map(
                    lambda v: pspec if jnp.shape(v) == pshape else P(), st)
        specs = {kk: jax.tree_util.tree_map(lambda v: P(), vv)
                 for kk, vv in opt_state.items() if kk != "slots"}
        if "slots" in opt_state:
            # wrapper optimizers (LookAhead/ModelAverage) nest the real
            # slots deeper; their whole state replicates (no ZeRO slot
            # sharding through wrappers)
            specs["slots"] = slot_specs
        return specs

    # -- step construction ---------------------------------------------------
    def _build(self):
        mesh = self.mesh
        model = self.model
        loss_fn = self.loss_fn
        M = self.micro_batches
        is_pp = isinstance(model, PipelineParallel) or (
            hasattr(model, "_layers") and isinstance(model._layers, PipelineParallel))
        pp = model if isinstance(model, PipelineParallel) else None
        sep = mesh.shape.get("sep", 1) > 1
        reduce_axes = DATA_AXES + ("sep",) if sep else DATA_AXES

        pp_loss = pp_grads = None
        if pp is not None and sep and pp._uniform_fns() is None:
            raise NotImplementedError(
                "pipeline + context parallelism ('sep' axis) requires a "
                "plan that decomposes into prologue/stacked-body/epilogue "
                "(PipelineLayer.uniform_split): the switch-dispatch "
                "fallback issues ring-attention collectives from "
                "per-device branches, which deadlocks or silently "
                "corrupts the exchange")
        if pp is not None:
            if getattr(pp, "schedule", "gpipe") == "1f1b":
                # 1F1B computes grads itself (manual per-stage VJP inside
                # the tick scan — in-flight microbatches bounded by S)
                pp_grads = pp.build_pipeline_grads_fn(loss_fn, M)
            else:
                pp_loss = pp.build_pipeline_loss_fn(loss_fn, M)

        def local_loss(params, buffers, key, inputs, labels):
            """Runs on each device inside shard_map."""
            if pp_loss is not None:
                return pp_loss(params, buffers, key, inputs, labels)
            fwd = functional_call
            if self.remat:
                def fwd(m, p, b, *a, rng=None):
                    # [0]: keep only the model output — returning the
                    # (out, new_buffers) pair through jax.checkpoint
                    # would hand the tuple to loss_fn as "out"
                    f = jax.checkpoint(
                        lambda pp_, xx: functional_call(
                            m, pp_, b, xx, rng=rng)[0])
                    return f(p, *a), b
            out, _ = fwd(model, params, buffers, inputs, rng=key)
            # Trace scope: the engine is the one place that sees every
            # loss path (a callable, a Layer, a model that returns its
            # own loss). Read by lm_head_loss_ms_per_step.
            with jax.named_scope("loss"):
                return loss_fn(out, labels)

        zero3_dims = self.zero3_dims
        zero2_dims = self.zero2_dims
        n_shard = mesh.shape.get("sharding", 1)
        # ZeRO-2/3 sharded-grad leaves: block-quantized reduce-scatter
        # (phase 1 of the exchange, no gather) when the sharding axis's
        # policy quantizes; lossless policies keep the plain psum_scatter.
        rs_policy = (self._axis_policy.get("sharding", "fp32")
                     if isinstance(self._axis_policy, dict)
                     else self._axis_policy)
        if rs_policy not in QUANTIZED_POLICIES:
            rs_policy = None

        def _reduce_scatter(g, d, res=None):
            """Mean reduce-scatter of one sharded-grad leaf. ``res`` opts
            into error feedback (quantized rs_policy only): returns
            ``(mean_shard, new_residual)`` — the residual stays in the
            un-divided SUM domain, matching what the quantizer sees."""
            if rs_policy is not None:
                out = compressed_psum_scatter(
                    g, "sharding", scatter_dim=d, policy=rs_policy,
                    block=self.grad_sync_block, residual=res)
                if res is not None:
                    out, res = out
                    return out / n_shard, res
                return out / n_shard
            out = lax.psum_scatter(g, "sharding", scatter_dimension=d,
                                   tiled=True) / n_shard
            return out if res is None else (out, res)
        pipe_n = mesh.shape.get("pipe", 1)
        # params NOT sharded over the pipe axis (embedding/norm/head under
        # PP, i.e. everything outside the _StackedStage bodies) are
        # replicated over pipe, but each stage computes only its own
        # (partial, often zero) grad contribution — the psum over "pipe"
        # makes the grad genuinely replicated. Without it, cross-stage
        # reads of updated state (checkpoint save, sync_to_model) would be
        # undefined for stages >= 1 (round-1/2 verdict, engine grads).
        pipe_psum_keys = {
            k for k in self.param_specs
            if is_pp and pipe_n > 1 and self.trainable[k]
            and not _spec_has_axis(self.param_specs[k], "pipe")}

        # grad-exchange axes actually present in this mesh (an absent axis
        # name is unbound inside shard_map — naming it in a collective
        # would fail at trace time)
        sync_axes = tuple(ax for ax in reduce_axes if ax in mesh.shape)
        live_axes = tuple(ax for ax in sync_axes
                          if mesh.shape.get(ax, 1) > 1)
        if not self._any_quantized:
            # fp32/bf16: size-1 axes are pure no-ops, skip them; the
            # quantized policies keep the full tuple so the
            # quantize->dequantize (and the residual update) runs
            # identically at any device count
            sync_axes = live_axes

        # -- replica-divergence fingerprints (resilience/integrity.py) --
        # Per-leaf metadata for the check program: leaves whose spec
        # mentions none of the live data axes are REPLICATED across data
        # ranks — their fingerprints must agree bit-exactly, so they
        # join the pmin/pmax divergence compare. Leaves legitimately
        # sharded over those axes (comm_err's per-rank residual rows,
        # ZeRO slots) get a wrapping-psum combined digest instead:
        # recordable and replay-comparable, but excluded from the
        # cross-rank equality decision (their per-rank bytes differ by
        # design). Entry order == tree_leaves order of (params, opt,
        # comm_err) as the check shard_map receives them.
        self.integrity_axes = live_axes
        entries = []
        for part, vals, specs in (
                ("params", self.state["params"], self.param_specs),
                ("opt", self.state["opt"], self.opt_specs),
                ("comm_err", self.state["comm_err"], self.comm_err_specs)):
            spec_list = []
            jax.tree_util.tree_map(
                lambda v, s: spec_list.append(s), vals, specs)
            flat, _ = jax.tree_util.tree_flatten_with_path(vals)
            for (path, _v), spec in zip(flat, spec_list):
                leaf_axes = tuple(ax for ax in live_axes
                                  if _spec_has_axis(spec, ax))
                entries.append(
                    (part + jax.tree_util.keystr(path), leaf_axes))
        self._integrity_entries = entries
        self._integrity_cmp_idx = [i for i, (_n, a) in enumerate(entries)
                                   if not a]
        # every size>1 mesh axis: the divergence mask is pmax-spread over
        # all of them so rank 0's copy of the verdict is authoritative
        # even when the diverged leaf is model/pipe-sharded
        self._integrity_all_axes = tuple(
            ax for ax in mesh.axis_names if mesh.shape.get(ax, 1) > 1)

        def integrity_check_fn(params, opt_state, comm_err):
            from ..resilience.integrity import fingerprint_array
            leaves = (jax.tree_util.tree_leaves(params)
                      + jax.tree_util.tree_leaves(opt_state)
                      + jax.tree_util.tree_leaves(comm_err))
            fps = []
            for (_name, leaf_axes), leaf in zip(self._integrity_entries,
                                                leaves):
                fp = fingerprint_array(leaf)
                if leaf_axes:
                    # wrap-add is order-independent: the combined digest
                    # of a sharded leaf is deterministic on any backend
                    fp = lax.psum(fp, leaf_axes)
                fps.append(fp)
            fps = (jnp.stack(fps) if fps
                   else jnp.zeros((0,), jnp.uint32))
            cmp_idx = self._integrity_cmp_idx
            if cmp_idx and self.integrity_axes:
                cmp = fps[jnp.asarray(cmp_idx)]
                div = (lax.pmin(cmp, self.integrity_axes)
                       != lax.pmax(cmp, self.integrity_axes)
                       ).astype(jnp.int32)
                if self._integrity_all_axes:
                    div = lax.pmax(div, self._integrity_all_axes)
            else:
                div = jnp.zeros((len(cmp_idx),), jnp.int32)
            return fps, div

        self._integrity_check_fn = integrity_check_fn

        # loss scaling (scaler attached): the loss is scaled BEFORE the
        # backward pass (underflow protection is in the gradient compute,
        # scaling afterwards would be too late) and grads are unscaled
        # before the exchange so comm_err magnitudes stay policy-stable.
        # The pp schedules compute grads manually and skip the scaling —
        # on bf16 TPU scale=1 anyway; the dynamic scale policy still runs
        # off the guard's finite flag.
        use_amp = self.scaler is not None

        # Backward-overlapped exchange buckets: the plain trainable
        # leaves (the flat-exchange set) in named_parameters — i.e.
        # forward/layer — order, split into byte-balanced REVERSE-order
        # buckets. Bucket 0 holds the last layers, whose grads
        # materialize first in the backward, so its exchange is issued
        # earliest and hides under the longest remaining compute. Only
        # the AD path buckets: 1F1B computes grads manually (no backward
        # to hook into), and a single leaf has nothing to split.
        plain_keys = [k for k in self.param_specs
                      if self.trainable[k] and k not in zero2_dims
                      and k not in zero3_dims]
        use_buckets = (self.grad_sync_buckets > 1 and pp_grads is None
                       and bool(sync_axes) and len(plain_keys) >= 2)
        bucket_keys = []
        if use_buckets:
            bucket_keys = partition_reverse_buckets(
                [(k, self.state["params"][k].nbytes) for k in plain_keys],
                self.grad_sync_buckets)
            use_buckets = len(bucket_keys) >= 2
        self.grad_sync_bucket_keys = ([list(b) for b in bucket_keys]
                                      if use_buckets
                                      else ([list(plain_keys)]
                                            if plain_keys else []))
        self._use_buckets = use_buckets
        bucketed = (frozenset(k for b in bucket_keys for k in b)
                    if use_buckets else frozenset())

        def grads_fn(params, buffers, comm_err, scale, key, inputs, labels):
            tparams = {k: v for k, v in params.items() if self.trainable[k]}
            frozen = {k: v for k, v in params.items() if not self.trainable[k]}

            if pp_grads is not None:
                # 1F1B: manual grads. Each device's gacc is the gradient of
                # ITS local (batch-shard) mean loss — the same per-device
                # quantity the AD path produces before reduction — so the
                # reduction block below applies unchanged, except ZeRO-3
                # grads arrive full-size (no gather transpose) and need an
                # explicit reduce-scatter.
                merged = dict(params)
                for k, d in zero3_dims.items():
                    merged[k] = lax.all_gather(merged[k], "sharding",
                                               axis=d, tiled=True)
                loss, grads = pp_grads(merged, buffers, key, inputs,
                                       labels, tuple(tparams))
                for ax in reduce_axes:
                    if mesh.shape.get(ax, 1) > 1:
                        loss = lax.pmean(loss, ax)
            else:
                # Per-bucket exchange hook: identity on the params in the
                # forward; the backward performs the bucket's whole DP
                # exchange (AMP unscale, pipe psum, compressed flat mean)
                # ON the cotangents at the exact point in the backward
                # where the bucket's grads materialize — XLA sees the
                # collective mid-backward and the latency-hiding
                # scheduler can run it under the remaining compute. The
                # bucket's NEW error-feedback residual leaves the
                # backward as the cotangent of the residual input
                # (value_and_grad argnums=(0, 1) below).
                def _exchange_hook():
                    @jax.custom_vjp
                    def hook(sub, res):
                        return sub

                    def h_fwd(sub, res):
                        return sub, res

                    @jax.named_scope("grad_exchange")
                    def h_bwd(res, g):
                        g = dict(g)
                        if use_amp:
                            inv = 1.0 / scale
                            g = {k: v * inv.astype(v.dtype)
                                 for k, v in g.items()}
                        for k in g:
                            if k in pipe_psum_keys:
                                g[k] = lax.psum(g[k], "pipe")
                        mean, new_res = compressed_tree_mean(
                            g, sync_axes, policy=self._axis_policy,
                            block=self.grad_sync_block,
                            bucket_bytes=self.grad_sync_bucket_bytes,
                            residuals=(res if res else None))
                        return mean, (new_res if res else {})

                    hook.defvjp(h_fwd, h_bwd)
                    return hook

                hook = _exchange_hook() if use_buckets else None

                def lf(tp, res_in):
                    tp = dict(tp)
                    if use_buckets:
                        for keys, r in zip(bucket_keys, res_in):
                            tp.update(hook({k: tp[k] for k in keys}, r))
                    merged = dict(frozen)
                    merged.update(tp)
                    # ZeRO-3 storage shards -> full params for this step's
                    # compute; the all_gather transpose reduce-scatters
                    # grads
                    for k, d in zero3_dims.items():
                        merged[k] = lax.all_gather(merged[k], "sharding",
                                                   axis=d, tiled=True)
                    loss = local_loss(merged, buffers, key, inputs, labels)
                    # mean over the data axes (each device saw 1/N of the
                    # batch; under context parallelism also 1/n_sep of the
                    # sequence)
                    for ax in reduce_axes:
                        if mesh.shape.get(ax, 1) > 1:
                            loss = lax.pmean(loss, ax)
                    if use_amp:
                        loss = loss * scale.astype(loss.dtype)
                    return loss

                if use_buckets:
                    res_in = tuple(
                        {k: comm_err[k][0] for k in keys if k in comm_err}
                        for keys in bucket_keys)
                    loss, (grads, gres) = jax.value_and_grad(
                        lf, argnums=(0, 1))(tparams, res_in)
                else:
                    loss, grads = jax.value_and_grad(lf)(tparams, ())
                if use_amp:
                    inv = 1.0 / scale
                    loss = loss * inv.astype(loss.dtype)
                    # bucketed leaves were unscaled inside their hook
                    grads = {k: (g if k in bucketed
                                 else g * inv.astype(g.dtype))
                             for k, g in grads.items()}
            # Trace scope: everything between the backward pass and the
            # optimizer (casts, bucket packing, the collectives,
            # unpacking). The bucketed hook's h_bwd opens the same scope
            # inside the backward pass. Read by the benchmark's
            # grad_exchange_ms_per_step.
            with jax.named_scope("grad_exchange"):
                # DP grad averaging over the data axes; 'model'/'pipe' grads
                # are handled by shard_map transposition of the collectives.
                # Pipe-replicated grads are psum'd FIRST: psum/pmean commute
                # for the exact policies, and the int8 path must quantize the
                # full (pipe-summed) grad so every stage computes the same
                # residual — otherwise the pipe-replicated comm_err state
                # would silently diverge across stages.
                for k in pipe_psum_keys:
                    if k not in bucketed:
                        grads[k] = lax.psum(grads[k], "pipe")
                new_comm_err = dict(comm_err)

                # ZeRO-2/3 leaves keep per-tensor handling: they LEAVE the
                # exchange sharded over "sharding" (reduce-scatter), which the
                # flat bucketed path cannot express.
                def _pmean(g, ax):
                    # fp16_allreduce: fp32 grads cross the wire as bf16
                    if self.fp16_allreduce and g.dtype == jnp.float32:
                        return lax.pmean(g.astype(jnp.bfloat16),
                                         ax).astype(jnp.float32)
                    return lax.pmean(g, ax)

                for k in grads:
                    if k in zero3_dims:
                        # ZeRO-3 grads already carry the SUM over the sharding
                        # axis (all_gather transpose = reduce-scatter): divide
                        # for the mean, pmean over the remaining data axes
                        if pp_grads is not None:
                            # manual grads are wrt the GATHERED param: explicit
                            # reduce-scatter (mean) back onto the storage
                            # shard, threading the leaf's EF residual when the
                            # sharding hop quantizes
                            if k in comm_err:
                                grads[k], r = _reduce_scatter(
                                    grads[k], zero3_dims[k], comm_err[k][0])
                                new_comm_err[k] = r[None]
                            else:
                                grads[k] = _reduce_scatter(
                                    grads[k], zero3_dims[k])
                        else:
                            grads[k] = grads[k] / n_shard
                        for ax in ("data", "sep"):
                            if ax in reduce_axes and mesh.shape.get(ax, 1) > 1:
                                grads[k] = _pmean(grads[k], ax)
                    elif k in zero2_dims:
                        # reduce-scatter (mean) over sharding; pmean over data
                        if k in comm_err:
                            grads[k], r = _reduce_scatter(
                                grads[k], zero2_dims[k], comm_err[k][0])
                            new_comm_err[k] = r[None]
                        else:
                            grads[k] = _reduce_scatter(grads[k], zero2_dims[k])
                        for ax in ("data", "sep"):
                            if ax in reduce_axes and mesh.shape.get(ax, 1) > 1:
                                grads[k] = _pmean(grads[k], ax)

                if use_buckets:
                    # the per-bucket exchanges already ran inside the
                    # backward; fold each bucket's new residual (the
                    # cotangent of its residual input) back into the
                    # replica-major comm_err state
                    for r in gres:
                        for k, v in r.items():
                            new_comm_err[k] = v[None]
                    return loss, grads, new_comm_err

                # plain leaves, monolithic (K=1) mode: ONE bucketed flat
                # exchange (compressed.py) over the data axes instead of one
                # pmean per tensor — the Reducer bucketing, plus bf16/int8
                # wire compression per self.grad_sync. comm_err is the int8
                # error-feedback state, replica-major outside the step; its
                # local view here is (1, *shape).
                plain = {k: grads[k] for k in grads
                         if k not in zero3_dims and k not in zero2_dims}
                if plain and sync_axes:
                    res = ({k: comm_err[k][0] for k in plain
                            if k in comm_err} or None)
                    mean, res = compressed_tree_mean(
                        plain, sync_axes, policy=self._axis_policy,
                        block=self.grad_sync_block,
                        bucket_bytes=self.grad_sync_bucket_bytes,
                        residuals=res)
                    grads.update(mean)
                    if res:
                        new_comm_err.update({k: res[k][None] for k in res})
                return loss, grads, new_comm_err

        def _grad_spec(k):
            if k in zero2_dims:
                # grads leave the step sharded on the zero-2 dim
                d = zero2_dims[k]
                spec = list(self.param_specs[k]) + [None] * (
                    d + 1 - len(self.param_specs[k]))
                spec[d] = "sharding"
                return P(*spec)
            return self.param_specs[k]

        tspecs = {k: _grad_spec(k) for k in self.param_specs
                  if self.trainable[k]}

        opt = self.optimizer

        K = self.accumulate_steps

        def make_step(input_specs, label_specs, do_check=False):
            """Jitted step for one concrete (inputs, labels) pytree shape.

            ``do_check=True`` builds the integrity variant: after the
            update it fingerprints params/opt/comm_err in-graph and
            pmin/pmax-compares the data-replicated leaves across ranks
            (two scalar collectives per compared leaf). The plain
            program carries none of this — zero fingerprint
            collectives, asserted by chaos_smoke's sdc scenario.

            Data specs are per-LEAF: the batch dim always splits over
            data×sharding; with context parallelism ("sep" axis) rank>=2
            leaves additionally split their SEQUENCE dim (dim 1) over "sep"
            — ring attention (ops/ring_attention.py) rotates K/V chunks
            around that axis inside the model. Rank-1 leaves (e.g. per-row
            labels) carry no sequence dim, so they only batch-split — this
            is why specs cannot be a single P for all leaves.
            """
            # check_vma=False: the vma checker cannot statically prove
            # invariances that hold here by construction — size-1 mesh axes
            # skip their pmean, and the custom-vjp collectives in mp_layers
            # (identity-backward psum) hide the reductions that make TP
            # grads replicated. Satisfying it formally would insert real
            # all-reduces over axes whose values are already equal. The
            # replication this declares IS enforced: grads of
            # pipe-replicated params are psum'd over "pipe" above, and
            # test_pipeline_parallel.py::test_tied_state_stays_replicated_
            # across_pipe checks bit-identical per-device state after real
            # updates (set FLAGS_check_replication for the same check at
            # every step).
            sharded_grads = shard_map(
                grads_fn, mesh=mesh,
                in_specs=(self.param_specs, self.buffer_specs,
                          self.comm_err_specs, P(), P(), input_specs,
                          label_specs),
                out_specs=(P(), tspecs, self.comm_err_specs),
                check_vma=False)

            nan_guard = self.nan_guard
            scaler = self.scaler

            check_map = None
            if do_check and self._integrity_entries:
                check_map = shard_map(
                    self._integrity_check_fn, mesh=mesh,
                    in_specs=(self.param_specs, self.opt_specs,
                              self.comm_err_specs),
                    out_specs=(P(), P()), check_vma=False)

            def train_step(params, buffers, opt_state, comm_err, guard,
                           key, lr, taint, inputs, labels):
                # taint: the fault-injection operand (1.0 in normal runs,
                # NaN when faults.py poisons a grad leaf). A traced weak
                # scalar, so flipping it never recompiles.
                comm_err0 = comm_err
                scale = (guard["amp"]["scale"] if use_amp
                         else jnp.float32(1.0))
                if K > 1:
                    # gradient merge: grads averaged over K sequential
                    # chunks (activation memory is 1/K; same numerics as
                    # the big batch). The error-feedback state threads
                    # through the chunks — each chunk's exchange consumes
                    # the residual the previous one left.
                    chunk = jax.tree_util.tree_map(
                        lambda x: jnp.reshape(x, (K, x.shape[0] // K)
                                              + x.shape[1:]),
                        (inputs, labels))
                    keys = jax.random.split(key, K)
                    loss = 0.0
                    grads = None
                    for i in range(K):
                        ins_i, lbs_i = jax.tree_util.tree_map(
                            lambda x: x[i], chunk)
                        l_i, g_i, comm_err = sharded_grads(
                            params, buffers, comm_err, scale, keys[i],
                            ins_i, lbs_i)
                        loss = loss + l_i / K
                        grads = g_i if grads is None else \
                            jax.tree_util.tree_map(
                                lambda a, b: a + b, grads, g_i)
                    grads = jax.tree_util.tree_map(lambda g: g / K, grads)
                else:
                    loss, grads, comm_err = sharded_grads(
                        params, buffers, comm_err, scale, key, inputs,
                        labels)
                if grads:
                    # fault-injection surface: poison ONE grad leaf
                    k0 = next(iter(grads))
                    grads[k0] = grads[k0] * jnp.asarray(
                        taint, grads[k0].dtype)
                tparams = {k: v for k, v in params.items()
                           if self.trainable[k]}
                # Trace scope: the optimizer's update and the guard's
                # select between new and old state, which XLA fuses into
                # one op per leaf, so the device cannot tell them apart.
                # Read by the benchmark's update_ms_per_step.
                with jax.named_scope("update"):
                    new_t, new_opt = opt.apply_gradients(
                        tparams, grads, opt_state, lr=lr,
                        lr_scales=self.lr_scales)
                    new_params = dict(params)
                    new_params.update(new_t)
                    # keep optimizer slots on their ZeRO shardings
                    new_opt = jax.tree_util.tree_map(
                        lambda v, s: lax.with_sharding_constraint(
                            v, NamedSharding(mesh, s)),
                        new_opt, self.opt_specs)
                    new_guard = dict(guard)
                    if nan_guard or use_amp:
                        # ONE fused reduction, fully in-graph: no host sync,
                        # and the same flag serves the loss-scale policy
                        finite = all_finite(grads)
                        if nan_guard:
                            def keep(new, old):
                                return jax.tree_util.tree_map(
                                    lambda n, o: jnp.where(finite, n, o),
                                    new, old)
                            new_params = keep(new_params, params)
                            new_opt = keep(new_opt, opt_state)
                            comm_err = keep(comm_err, comm_err0)
                            new_guard["skipped"] = guard["skipped"] + \
                                (~finite).astype(jnp.int32)
                        if use_amp:
                            new_guard["amp"] = scaler.update_scale_state(
                                guard["amp"], ~finite)
                # integrity fingerprints of the FINAL (possibly
                # guard-reverted) state — exactly what a checkpoint at
                # this step would persist. None on the plain program:
                # an empty pytree output, so both programs unpack alike.
                integ = None
                if check_map is not None:
                    integ = check_map(new_params, new_opt, comm_err)
                return loss, new_params, new_opt, comm_err, new_guard, \
                    integ

            return jax.jit(train_step, donate_argnums=(0, 2, 3, 4))

        self._make_step = make_step
        self._sep = sep
        self._step_cache = {}
        self._step_costs = {}      # cache_key -> analysis.cost numbers
        self._last_cache_key = None

        # Telemetry wire accounting: logical bytes one train_step's
        # bucketed DP exchange moves per rank, split per (policy, link)
        # exchange group so the Prometheus path shows ICI vs DCN bytes
        # separately. Static per trainer (the exchange is
        # shape-independent of the batch); ZeRO-2/3 leaves go through
        # per-tensor (possibly compressed) psum_scatter and are not
        # counted here.
        n_sync = 1
        for ax in sync_axes:
            n_sync *= mesh.shape.get(ax, 1)
        plain_params = {k: v for k, v in self.state["params"].items()
                        if self.trainable[k] and k not in zero2_dims
                        and k not in zero3_dims}
        # [(policy, link, bucket_label, bytes_per_step)] — bucket "0" is
        # the whole exchange in monolithic mode, else the reverse-order
        # bucket index (bucket 0 = last layers, exchanged first)
        self._wire_parts = []
        self._wire_bytes_per_step = 0.0
        self._wire_fp32_per_step = 0.0
        if plain_params and n_sync > 1:
            from .compressed import tree_wire_bytes
            links = axis_links(mesh)
            for bi, keys in enumerate(self.grad_sync_bucket_keys):
                bparams = {k: plain_params[k] for k in keys
                           if k in plain_params}
                if not bparams:
                    continue
                for axes_g, pol in normalize_axis_policies(
                        sync_axes, self._axis_policy):
                    n_g = 1
                    for ax in axes_g:
                        n_g *= mesh.shape.get(ax, 1)
                    if n_g <= 1:
                        continue
                    link = ("dcn" if any(links.get(ax) == "dcn"
                                         for ax in axes_g) else "ici")
                    b = K * tree_wire_bytes(bparams, n_g, pol,
                                            block=self.grad_sync_block)
                    self._wire_parts.append((pol, link, str(bi), b))
            self._wire_bytes_per_step = sum(p[3] for p in self._wire_parts)
            self._wire_fp32_per_step = K * tree_wire_bytes(
                plain_params, n_sync, "fp32", block=self.grad_sync_block)

    def _leaf_spec(self, x):
        """Per-leaf data PartitionSpec (see make_step docstring)."""
        r = _rank(x)
        if r == 0:
            return P()
        if self._sep and r >= 2:
            return P(DATA_AXES, "sep")
        return P(DATA_AXES)

    def _stage(self, inputs, labels, place: bool = True,
               do_check: bool = False):
        """Normalize a batch and get its jitted step from the cache
        (tracing it on first use). ``place=False`` skips device_put so
        ShapeDtypeStruct batches can stage without materializing data.
        ``do_check`` selects the integrity-check program variant (its
        own cache slot: at steady state both programs are staged once
        and the cadence flips between them with no recompiles).
        Returns (inputs, labels, step)."""
        conv = lambda x: x if isinstance(x, jax.ShapeDtypeStruct) \
            else jnp.asarray(x)  # noqa: E731
        inputs = jax.tree_util.tree_map(conv, inputs)
        labels = jax.tree_util.tree_map(conv, labels)
        in_specs = jax.tree_util.tree_map(self._leaf_spec, inputs)
        lb_specs = jax.tree_util.tree_map(self._leaf_spec, labels)
        if place:
            inputs = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self.mesh, s)), inputs, in_specs)
            labels = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self.mesh, s)), labels, lb_specs)
        cache_key = (jax.tree_util.tree_structure((inputs, labels)),
                     tuple(_rank(l) for l in jax.tree_util.tree_leaves(
                         (inputs, labels))),
                     bool(do_check))
        step = self._step_cache.get(cache_key)
        self._last_stage_miss = step is None
        if step is None:
            # One span times the build, for the profiler, the staging
            # record, the "stage" child span and stage_time_seconds alike.
            # "stage" rides as a child of the runner's ambient step span
            # (if one is open): staged-program builds show up inside the
            # step that paid for them. The per-bucket exchange plan is
            # recorded as events here — the exchanges themselves run
            # inside the jitted program, invisible to host-side spans.
            sp = _tracing.child_span("stage", check=bool(do_check))
            try:
                with _staging.span("paddle_tpu.trainer.make_step") as made:
                    step = self._make_step(in_specs, lb_specs, do_check)
            finally:
                if sp is not None:
                    for i, bk in enumerate(
                            getattr(self, "grad_sync_bucket_keys", [])):
                        sp.event(
                            "exchange_bucket", bucket=i, leaves=len(bk),
                            bytes=int(sum(
                                self.state["params"][k].nbytes
                                for k in bk)))
                    sp.end("ok", cache_miss=True, seconds=made.seconds)
            self._step_cache[cache_key] = step
            if _telemetry.enabled():
                _telemetry.counter(
                    "recompiles_total",
                    "train-step stagings (cache misses) + jit shape "
                    "recompiles").inc()
                _telemetry.histogram(
                    "stage_time_seconds",
                    "wall time building a step for a new batch "
                    "structure").observe(made.seconds)
                self._step_costs[cache_key] = self._trace_step_cost(
                    step, inputs, labels)
        self._last_cache_key = cache_key
        return inputs, labels, step

    def _trace_step_cost(self, step, inputs, labels):
        """Static per-step cost of the EXACT staged jaxpr — donation mask,
        comm_err plumbing and all — via analysis.cost. Telemetry-only:
        traces with ShapeDtypeStructs (no rng draw, nothing executed) and
        never raises into the training path."""
        try:
            from ..analysis import cost as _cost
            to_struct = lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
                                   if hasattr(x, "shape") and
                                   hasattr(x, "dtype") else x)
            key_aval = jax.eval_shape(lambda: jax.random.key(0))
            args = jax.tree_util.tree_map(to_struct, (
                self.state["params"], self.state["buffers"],
                self.state["opt"], self.state["comm_err"],
                self.state["guard"]))
            lr = float(self.optimizer.get_lr())
            closed = jax.make_jaxpr(lambda *a: step(*a))(
                *args, key_aval, lr, 1.0,
                jax.tree_util.tree_map(to_struct, inputs),
                jax.tree_util.tree_map(to_struct, labels))
            donated = sum(
                getattr(v, "nbytes", 0)
                for part in (self.state["params"], self.state["opt"],
                             self.state["comm_err"], self.state["guard"])
                for v in jax.tree_util.tree_leaves(part))
            out = {"flops": _cost.total_flops(closed),
                   "peak_live_bytes": _cost.peak_live_bytes(closed),
                   "donated_bytes": float(donated)}
            try:
                out["overlap"] = _cost.overlap_summary(closed, self.mesh)
            except Exception:
                out["overlap"] = None
            return out
        except Exception:
            return None

    # -- staging / analysis -------------------------------------------------
    def compile(self, inputs, labels, lr: Optional[float] = None,
                analyze: bool = False, config=None):
        """Stage the jitted train step for this batch shape without
        running it. Returns the step function; with ``analyze=True``
        returns ``(step, Report)`` where the Report comes from tracing
        the EXACT staged step — donation mask, comm_err / compressed
        grad-sync plumbing and all — through paddle_tpu.analysis.

        ``inputs``/``labels`` may be real arrays or ShapeDtypeStructs
        (nothing is materialized or executed either way)."""
        t0 = time.perf_counter()
        inputs, labels, step = self._stage(inputs, labels, place=False)
        if _telemetry.enabled():
            _telemetry.histogram(
                "compile_time_seconds",
                "ParallelTrainer.compile wall time").observe(
                    time.perf_counter() - t0)
        if not analyze:
            return step
        import dataclasses

        from .. import analysis
        # declare the exchange mode to the overlap rule: 0 means the
        # caller didn't say, so inject the trainer's own effective bucket
        # count (an explicit non-zero value in the config wins)
        cfg = config or analysis.AnalysisConfig()
        if getattr(cfg, "grad_sync_buckets", 0) == 0:
            eff = (len(self.grad_sync_bucket_keys)
                   if getattr(self, "_use_buckets", False) else 1)
            cfg = dataclasses.replace(cfg, grad_sync_buckets=eff)
        config = cfg
        closed, donated = self._staged_jaxpr(step, inputs, labels, lr)
        in_specs = None
        try:
            in_specs = self.staged_in_specs(inputs, labels)
            if len(in_specs) != len(closed.jaxpr.invars):
                in_specs = None   # tree drift: better silent than wrong
        except Exception:
            in_specs = None
        report = analysis.analyze_jaxpr(closed, mesh=self.mesh,
                                        donated=donated, config=config,
                                        in_specs=in_specs)
        if _telemetry.enabled():
            ov = getattr(report.cost, "overlap", None) or {}
            if "n_reshard" in ov:
                _telemetry.gauge(
                    "predicted_reshard_collectives",
                    "implicit resharding collectives the sharding pass "
                    "predicts in the staged step").set(ov["n_reshard"])
                _telemetry.gauge(
                    "predicted_reshard_seconds",
                    "modeled per-step wall seconds of implicit "
                    "resharding").set(ov["reshard_time"])
        return step, report

    def staged_in_specs(self, inputs, labels):
        """One PartitionSpec per flat invar of :meth:`staged_jaxpr`'s
        ClosedJaxpr, in tracing order — the seed the static
        sharding-propagation pass (analysis/sharding.py) needs to
        predict implicit resharding from the exact staged step."""
        def flat(part, spec_tree=None):
            leaves, treedef = jax.tree_util.tree_flatten(part)
            if spec_tree is None:
                return [P()] * len(leaves)
            return list(treedef.flatten_up_to(spec_tree))
        specs = []
        specs += flat(self.state["params"], self.param_specs)
        specs += flat(self.state["buffers"], self.buffer_specs)
        specs += flat(self.state["opt"], self.opt_specs)
        specs += flat(self.state["comm_err"], self.comm_err_specs)
        specs += flat(self.state["guard"])
        specs += [P(), P(), P()]   # rng key, lr, grad-taint scalar
        specs += flat(inputs, jax.tree_util.tree_map(self._leaf_spec,
                                                     inputs))
        specs += flat(labels, jax.tree_util.tree_map(self._leaf_spec,
                                                     labels))
        return specs

    def _staged_jaxpr(self, step, inputs, labels, lr=None):
        """Trace the staged ``step`` to a ClosedJaxpr with this trainer's
        live state as abstract operands. Returns ``(closed, donated)``
        where ``donated`` is the flat invar index set of jit's
        ``donate_argnums``."""
        from ..framework.random import get_rng_key
        lr = self.optimizer.get_lr() if lr is None else lr
        args = (self.state["params"], self.state["buffers"],
                self.state["opt"], self.state["comm_err"],
                self.state["guard"], get_rng_key(), lr, 1.0, inputs, labels)
        closed = jax.make_jaxpr(lambda *a: step(*a))(*args)
        # flat invar indices of jit's donate_argnums=(0, 2, 3, 4)
        donated, off = set(), 0
        for i, a in enumerate(args):
            n = len(jax.tree_util.tree_leaves(a))
            if i in (0, 2, 3, 4):
                donated.update(range(off, off + n))
            off += n
        return closed, donated

    def staged_jaxpr(self, inputs, labels, lr=None, do_check=False):
        """Public tracing hook for tools: stage the train step for this
        batch shape and return its ClosedJaxpr (nothing executed).
        ``do_check=True`` traces the integrity-check program variant."""
        inputs, labels, step = self._stage(inputs, labels, place=False,
                                           do_check=do_check)
        closed, _ = self._staged_jaxpr(step, inputs, labels, lr)
        return closed

    def program_family(self, inputs, labels, lr=None):
        """The integrity do_check pair as a declared
        :class:`~paddle_tpu.analysis.schedule.ProgramFamily`: train_step
        picks between the plain and fingerprint-check programs with
        ``self._steps_run % integrity_check_every`` — a host-replicated
        step counter, so the selection is rank-invariant by
        construction. The schedule verifier checks both members are
        individually hang-free."""
        from ..analysis.schedule import ProgramFamily
        return ProgramFamily(
            name="trainer-step",
            selector="steps_run % integrity_check_every "
                     "(host-replicated step counter)",
            rank_invariant=True,
            members={
                "step": lambda: self.staged_jaxpr(inputs, labels, lr),
                "step-check": lambda: self.staged_jaxpr(
                    inputs, labels, lr, do_check=True),
            },
            mesh=self.mesh)

    # -- run ----------------------------------------------------------------
    def train_step(self, inputs, labels, lr: Optional[float] = None,
                   grad_taint: Optional[float] = None):
        """One jitted step. ``grad_taint`` is the fault-injection operand:
        a scalar multiplied into one gradient leaf inside the step (NaN
        poisons the step; the in-graph guard must then skip the update).
        Normal callers leave it None."""
        # Two host spans on the jax profiler's clock, always on (creating
        # one checks an atomic and does nothing else while no trace runs):
        # "stage" is the rng key, the lr, the batch going to the device and
        # the program lookup; "launch" the call of the staged step until it
        # returns. Neither blocks. Read by the benchmark's trainer_stage_ms,
        # trainer_launch_ms and dispatch_exposed_ms_per_step. A call that
        # stages a program of the step (the first ones, a new batch shape)
        # does so under "launch"; telemetry.staging keeps jax's trace,
        # lower and compile of it and the step it happened in.
        with jax.profiler.TraceAnnotation("paddle_tpu.trainer.stage"):
            key = get_rng_key()
            lr = self.optimizer.get_lr() if lr is None else lr
            leaves = jax.tree_util.tree_leaves(inputs)
            batch0 = jnp.shape(leaves[0])[0] if leaves and \
                len(jnp.shape(leaves[0])) else None
            if self.accumulate_steps > 1 and batch0 is not None and \
                    batch0 % self.accumulate_steps != 0:
                raise ValueError(
                    f"batch size {batch0} is not divisible by "
                    f"accumulate_steps={self.accumulate_steps}")
            # inputs/labels may be arbitrary pytrees (e.g. (mlm, nsp) labels)
            tel = _telemetry.enabled()
            t_start = time.perf_counter() if tel else 0.0
            # integrity cadence: host-side choice between the two cached
            # programs (no recompile, no in-graph branch on the step count)
            self._steps_run += 1
            ce = self.integrity_check_every
            do_check = bool(ce) and self._steps_run % ce == 0
            inputs, labels, step = self._stage(inputs, labels,
                                               do_check=do_check)
        # Host range for the profiler/chrome trace; the telemetry counter
        # track is aligned against these. Skipped entirely (no object)
        # when the profiler is off.
        ev = (_profiler.RecordEvent("train_step").begin()
              if _profiler.is_profiler_enabled() else None)
        n_compiled0 = self._jit_cache_size(step) if tel else None
        taint = 1.0 if grad_taint is None else float(grad_taint)
        staged0 = _staging.programs(_STEP_FUN)
        with jax.profiler.TraceAnnotation("paddle_tpu.trainer.launch"):
            loss, new_params, new_opt, new_comm_err, new_guard, integ = step(
                self.state["params"], self.state["buffers"],
                self.state["opt"], self.state["comm_err"],
                self.state["guard"], key, lr, taint, inputs, labels)
        if _staging.programs(_STEP_FUN) != staged0:
            # this call staged a program of the step: the record says which
            _staging.tag_step(_STEP_FUN, staged0, self._steps_run)
        if tel or ev is not None:
            # the documented telemetry sync point: step wall time includes
            # device execution (loss is the last value the step produces)
            jax.block_until_ready(loss)
        if ev is not None:
            ev.end()
        self.state["params"] = new_params
        self.state["opt"] = new_opt
        self.state["comm_err"] = new_comm_err
        self.state["guard"] = new_guard
        if integ is not None:
            self._record_integrity(integ)
        if tel:
            self._record_step_telemetry(
                time.perf_counter() - t_start, inputs, step, n_compiled0)
        from ..framework import flags as _flags
        if _flags.flag("check_nan_inf"):
            _flags.check_numerics({"loss": loss}, "train_step:")
            _flags.check_numerics(new_params, "params:")
        if _flags.flag("check_replication"):
            self.check_replication()
        if _flags.flag("benchmark"):
            jax.block_until_ready(loss)
        return loss

    def staging_summary(self) -> dict:
        """Where set-up went, for a job's log at its first steady step:
        the staging record's totals (``telemetry.staging.summary``) of
        the step (programs, seconds traced, lowered and compiled, the
        persistent cache's hits and misses), the ``train_step`` call each
        of its programs was staged in, and the seconds of the trainer's
        phases (the ``paddle_tpu.trainer.`` spans the record holds). The
        record is the process's: two trainers in one process add up."""
        totals = _staging.summary()
        steps = sorted({(e["program"], e["step"])
                        for e in _staging.entries(_STEP_FUN) if "step" in e})
        out = {_STEP_FUN: dict(totals.get(_STEP_FUN, {"programs": 0}),
                               staged_in_steps=[s for _, s in steps])}
        out.update((name, row["span_s"]) for name, row in totals.items()
                   if name.startswith("paddle_tpu.trainer."))
        return out

    def _record_integrity(self, integ):
        """Host side of the check step: pull the tiny divergence mask
        (len == compared leaves, int32) and remember which leaves'
        fingerprints disagreed across data ranks. The fingerprints
        themselves stay on device unless someone asks
        (``last_fingerprints``)."""
        fps, div = integ
        self.last_fingerprints = fps
        mask = np.asarray(jax.device_get(div)).reshape(-1)
        names = [self._integrity_entries[i][0]
                 for i in self._integrity_cmp_idx]
        diverged = [n for n, m in zip(names, mask) if int(m)]
        self.last_divergence = diverged
        if _telemetry.enabled():
            _telemetry.counter(
                "integrity_check_steps_total",
                "train steps that ran the fingerprint check program"
            ).inc()
            if diverged:
                c = _telemetry.counter(
                    "replica_divergence_total",
                    "integrity checks where a leaf's fingerprint "
                    "differed across data-parallel ranks")
                for n in diverged:
                    c.inc(leaf=n)
        return diverged

    def consume_divergence(self) -> list:
        """Divergent leaf names from the most recent check step, cleared
        on read — run_resilient polls this after every step and converts
        a non-empty answer into quarantine + rollback."""
        out, self.last_divergence = self.last_divergence, []
        return out

    @staticmethod
    def _jit_cache_size(step):
        """Compiled-executable count of a jitted step (None if this jax
        doesn't expose it). Lets the recompile counter catch SHAPE misses
        — same batch structure/ranks, so a _step_cache hit, but jit still
        retraces — not just staging misses."""
        try:
            return step._cache_size()
        except Exception:
            return None

    def _record_step_telemetry(self, dt, inputs, step, n_compiled0):
        """Host-side per-step metrics (telemetry enabled only)."""
        _telemetry.histogram(
            "step_time_seconds",
            "train_step wall time incl. device execution").observe(dt)
        if not self._last_stage_miss and n_compiled0 is not None:
            n1 = self._jit_cache_size(step)
            if n1 is not None and n1 > n_compiled0:
                _telemetry.counter(
                    "recompiles_total",
                    "train-step stagings (cache misses) + jit shape "
                    "recompiles").inc()
        tokens = None
        leaves = jax.tree_util.tree_leaves(inputs)
        if leaves:
            shape = jnp.shape(leaves[0])
            if len(shape) >= 2:
                tokens = int(shape[0]) * int(shape[1])
            elif len(shape) == 1:
                tokens = int(shape[0])
        tps = None
        if tokens and dt > 0:
            tps = tokens / dt
            _telemetry.gauge(
                "tokens_per_sec",
                "elements of the lead input's first two dims per "
                "second").set(tps)
        mfu = None
        cost = self._step_costs.get(self._last_cache_key)
        if cost:
            if cost["flops"] and dt > 0:
                mfu = cost["flops"] / dt / _telemetry.peak_flops_per_sec()
                _telemetry.gauge(
                    "mfu", "model FLOPs utilization: analysis.cost FLOPs "
                    "of the staged step / wall time / hardware peak"
                ).set(mfu)
            _telemetry.gauge(
                "peak_live_bytes", "liveness-scan peak working set of the "
                "staged step jaxpr").set(cost["peak_live_bytes"])
            _telemetry.gauge(
                "donated_bytes", "bytes of donated state "
                "(params + opt + comm_err)").set(cost["donated_bytes"])
        if self._wire_bytes_per_step:
            wire = _telemetry.counter(
                "grad_sync_bytes_total",
                "logical wire bytes per rank of the bucketed grad "
                "exchange, per exchange group")
            for pol, link, bucket, b in self._wire_parts:
                if b:
                    wire.inc(b, policy=pol, link=link, bucket=bucket)
            if self._wire_bytes_per_step > 0:
                _telemetry.gauge(
                    "grad_sync_compression_x",
                    "fp32 wire bytes / policy wire bytes").set(
                        self._wire_fp32_per_step /
                        self._wire_bytes_per_step)
        if cost and cost.get("overlap") and \
                cost["overlap"].get("overlap_efficiency") is not None:
            _telemetry.gauge(
                "grad_sync_overlap_efficiency",
                "fraction of the staged step's collective time the "
                "overlap model predicts is hidden under compute").set(
                    cost["overlap"]["overlap_efficiency"])
        if cost and cost.get("overlap") and dt > 0:
            makespan = cost["overlap"].get("makespan")
            if makespan:
                # predicted-vs-measured step time (telemetry.calibration):
                # the overlap model's makespan of the staged step vs this
                # step's wall clock
                # (``step`` here is the staged program, not a count)
                _telemetry.calibration.record(
                    "step_time", makespan, dt, step=self._steps_run)
        res = None
        if self.state["comm_err"]:
            from .compressed import residual_norm
            try:
                res = residual_norm(self.state["comm_err"])
                _telemetry.gauge(
                    "grad_sync_residual_norm",
                    "L2 norm of the int8 error-feedback residual").set(res)
            except Exception:
                res = None
        _telemetry.emit(
            "step", step_time=dt,
            **{k: v for k, v in (("tokens_per_sec", tps), ("mfu", mfu),
                                 ("residual_norm", res)) if v is not None})

    def check_replication(self):
        """Debug aid (FLAGS_check_replication): assert every param whose
        spec declares full replication is bit-identical on all devices —
        the runtime form of the invariant that shard_map's check_vma would
        check statically (see the check_vma note in make_step)."""
        import numpy as np
        for k, spec in self.param_specs.items():
            if any(ax is not None for ax in spec):
                continue
            v = self.state["params"][k]
            shards = v.addressable_shards
            base = np.asarray(shards[0].data)
            for s in shards[1:]:
                if not np.array_equal(base, np.asarray(s.data)):
                    raise AssertionError(
                        f"param {k!r} declared replicated but devices "
                        f"{shards[0].device} and {s.device} disagree")

    def skipped_steps(self) -> int:
        """Steps the in-graph NaN guard skipped so far (ONE host sync —
        call at checkpoint/summary boundaries, not per step)."""
        return int(jax.device_get(self.state["guard"]["skipped"]))

    # -- live-state access (HeterPS hot-tier insert/evict between steps) ----
    def param_name_of(self, box) -> Optional[str]:
        """Full name of a model Parameter by identity (None if absent)."""
        for n, b in self.model.named_parameters():
            if b is box:
                return n
        return None

    def get_param(self, name):
        return self.state["params"][name]

    def set_param(self, name, value):
        """Replace a parameter in live state, preserving its sharding."""
        self.state["params"][name] = jax.device_put(
            value, NamedSharding(self.mesh, self.param_specs[name]))

    def get_opt_slot(self, name, slot):
        """Optimizer slot array for a param (None when the optimizer
        keeps no such slot or nests them — wrapper optimizers)."""
        try:
            v = self.state["opt"]["slots"][name][slot]
            return v if hasattr(v, "shape") else None
        except (KeyError, TypeError):
            return None

    def opt_slot_names(self, name):
        """Names of the flat optimizer slot arrays kept for a param."""
        try:
            d = self.state["opt"]["slots"][name]
            return [k for k, v in d.items() if hasattr(v, "shape")]
        except (KeyError, TypeError):
            return []

    def set_opt_slot(self, name, slot, value):
        spec = self.opt_specs["slots"][name][slot]
        self.state["opt"]["slots"][name][slot] = jax.device_put(
            value, NamedSharding(self.mesh, spec))

    def sync_to_model(self):
        boxes = dict(self.model.named_parameters())
        for n, v in self.state["params"].items():
            if n in boxes:
                boxes[n].value = v

    # -- checkpoint ---------------------------------------------------------
    def save_checkpoint(self, path: str, use_async: bool = False):
        """Sharded save of {params, buffers, opt} — each shard written from
        the device/host holding it (reference capability: per-rank sharded
        save, dist_sharding_save.py test)."""
        from .checkpoint import save_checkpoint as _save
        return _save(path, self.state, use_async=use_async)

    def load_checkpoint(self, path: str):
        """Restore state with the trainer's own shardings (mesh-keyed)."""
        from .checkpoint import load_checkpoint as _load
        self.state = _load(path, template=self.state)
        return self.state

    # -- elastic remesh -----------------------------------------------------
    def remesh(self, mesh):
        """Rebuild specs, state placement, and the jitted step programs on
        a new mesh (elastic scale-up/down: the healthy host set changed and
        build_mesh produced a different device array). State re-initializes
        FRESH from the model/optimizer — carrying trained state across
        meshes is the caller's job via the sharded checkpoint
        (resilience.elastic.reshard_trainer: save on the old mesh, restore
        on the new one, remap the comm_err residuals whose replica
        dimension follows the mesh)."""
        from .mesh import set_mesh
        self.mesh = mesh
        set_mesh(mesh)
        self._construct()
        return self
