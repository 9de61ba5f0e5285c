"""LocalSGD / AdaptiveLocalSGD (reference:
fleet/meta_optimizers/localsgd_optimizer.py LocalSGDOptimizer +
AdaptiveLocalSGDOptimizer): each data-parallel replica takes k local
optimizer steps WITHOUT gradient synchronization, then parameters are
averaged across replicas — trading gradient-allreduce bandwidth for
periodic parameter averaging.

TPU-native shape: GSPMD-replicated parameters cannot diverge per replica,
so LocalSGD stores them REPLICA-MAJOR — every trainable param carries a
leading replica dim sharded over the "data" mesh axis (P("data", ...)).
The jitted step computes per-replica grads inside shard_map with NO pmean,
updates per-replica optimizer state elementwise, and on sync steps
averages over the leading dim (XLA lowers the mean over the sharded dim to
the all-reduce the reference's program rewrite inserts). The sync period k
is a runtime operand, so AdaptiveLocalSGD's k schedule (shrunk as loss
falls — sync more often near convergence, reference
localsgd_optimizer.py:425) never recompiles.
"""
from __future__ import annotations

from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...framework.random import get_rng_key
from ...jit.functionalization import functional_call, state_of
from ..compressed import (DEFAULT_BUCKET_BYTES, GRAD_SYNC_POLICIES,
                          QUANTIZED_POLICIES, compressed_tree_mean)
from ..mesh import require_mesh

shard_map = jax.shard_map


def _replica_major(ndim: int) -> P:
    """Spec of a replica-major ``(D, *shape)`` array of rank ``ndim``."""
    return P("data", *([None] * (ndim - 1)))


class LocalSGDTrainer:
    """Data-parallel trainer with k-step local updates + parameter
    averaging. ``k_steps`` fixed (LocalSGD) or adapted from the loss
    (AdaptiveLocalSGD: k ~ ceil(sqrt(lr0*loss/(lr*loss0) * init_k)),
    clamped — replicas sync more often as loss/lr fall).

    ``param_sync`` compresses the periodic parameter exchange
    (distributed/compressed.py): what crosses the wire is each replica's
    DELTA from the shared anchor (the last-synced params) — deltas are
    update-sized, so block-scaled int8/int4 keeps its resolution on them,
    where quantizing absolute parameter values would drown the local
    progress in rounding. The quantized policies carry a per-replica
    error-feedback residual; optimizer moments always average exactly
    (they are not wire-critical: same bytes, but no compounding).

    The step is a TWO-PROGRAM cache keyed like engine's ``_step_cache``
    (program kind × data shapes): the sync program issues the averaging
    collectives, the no-sync program contains NONE — XLA cannot skip a
    collective data-dependently, so the old ``jnp.where(do_sync, ...)``
    still paid the full exchange on every step. The sync decision is a
    host-side modulo (``step_no % k``), so AdaptiveLocalSGD's k schedule
    still never recompiles — it only picks which cached program runs."""

    def __init__(self, model, optimizer, loss_fn: Callable, mesh=None,
                 k_steps: int = 1, adaptive: bool = False,
                 init_k_steps: int = 1, max_k_steps: int = 16,
                 param_sync: str = "fp32",
                 param_sync_block=None,
                 param_sync_bucket_bytes: int = DEFAULT_BUCKET_BYTES):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or require_mesh()
        self.ndata = self.mesh.shape.get("data", 1)
        self.k_steps = init_k_steps if adaptive else k_steps
        self.adaptive = adaptive
        self.init_k_steps = init_k_steps
        self.max_k_steps = max_k_steps
        if param_sync not in GRAD_SYNC_POLICIES:
            raise ValueError(f"param_sync {param_sync!r} not in "
                             f"{GRAD_SYNC_POLICIES}")
        self.param_sync = param_sync
        self.param_sync_block = param_sync_block
        self.param_sync_bucket_bytes = param_sync_bucket_bytes
        self._loss0 = None
        self._step_no = 0
        self._init_state()
        self._build()

    def _init_state(self):
        # plain dicts throughout, the containers the staged programs
        # return: the first call of each passes the pytree every later
        # call passes, and jit stages each program once
        # (ParallelTrainer._init_state)
        params, buffers = state_of(self.model)
        boxes = dict(self.model.named_parameters())
        self.trainable = {n: boxes[n].trainable for n in params}
        tparams = {k: v for k, v in params.items() if self.trainable[k]}
        opt_state = self.optimizer.init_state(tparams)

        def rep(v):  # replica-major: (D, *shape) sharded over "data"
            tiled = jnp.broadcast_to(v[None], (self.ndata,) + v.shape)
            return jax.device_put(
                tiled, NamedSharding(self.mesh, _replica_major(tiled.ndim)))

        # replicate the SLOTS per replica (they diverge between syncs);
        # the step counter stays a shared scalar — replicating it breaks
        # Adam-family bias correction broadcasting ((D,) vs (D, *shape))
        # (on the mesh like every leaf the programs hand back: a scalar
        # left uncommitted would be another input type in the second call)
        rep_sh = NamedSharding(self.mesh, P())
        rep_opt = jax.tree_util.tree_map(
            lambda v: jax.device_put(v, rep_sh),
            {k: v for k, v in opt_state.items() if k != "slots"})
        rep_opt["slots"] = jax.tree_util.tree_map(
            rep, opt_state.get("slots", {}))
        self.state = {
            "params": {k: rep(v) for k, v in tparams.items()},
            "frozen": {k: v for k, v in params.items()
                       if not self.trainable[k]},
            "buffers": dict(buffers),
            "opt": rep_opt,
        }
        # anchor = the last-synced params, identical on every replica (each
        # sync ends with all replicas on the same point); replicated
        # storage. The int8 residual is per-replica. Both empty for the
        # exact fp32 path.
        self.state["anchor"] = (
            {k: jax.device_put(jnp.asarray(v), rep_sh)
             for k, v in tparams.items()}
            if self.param_sync != "fp32" else {})
        self.state["sync_err"] = (
            {k: rep(jnp.zeros(jnp.shape(v), jnp.float32))
             for k, v in tparams.items()}
            if self.param_sync in QUANTIZED_POLICIES else {})

    def _build(self):
        mesh = self.mesh
        model = self.model
        loss_fn = self.loss_fn
        opt = self.optimizer

        def grads_fn(params, frozen, buffers, key, inputs, labels):
            # inside shard_map: leading replica dim is LOCAL (length 1)
            p = {k: v[0] for k, v in params.items()}
            merged = dict(frozen)
            merged.update(p)

            def lf(tp):
                full = dict(merged)
                full.update(tp)
                out, _ = functional_call(model, full, buffers, inputs,
                                         rng=key)
                return loss_fn(out, labels)

            loss, grads = jax.value_and_grad(lf)(p)
            # NO grad pmean — that is the whole point of LocalSGD. The
            # loss leaves PER-REPLICA ((D,) outside) and is averaged on
            # the host: a reporting pmean here would put a collective in
            # the no-sync program, which must contain none.
            return loss[None], {k: g[None] for k, g in grads.items()}

        pspec = {k: _replica_major(v.ndim)
                 for k, v in self.state["params"].items()}
        sharded_grads = shard_map(
            grads_fn, mesh=mesh,
            in_specs=(pspec, P(), P(), P(), P(("data",)), P(("data",))),
            out_specs=(P(("data",)), pspec),
            check_vma=False)

        sharded_sync = None
        if self.param_sync != "fp32":
            err_spec = {k: pspec[k] for k in self.state["sync_err"]}

            def sync_fn(new_p, anchor, sync_err):
                # local views: params (1, *shape); anchor shared (*shape).
                # Exchange the per-replica DELTA from the anchor — the
                # compressed mean of deltas IS the mean param minus anchor
                deltas = {k: v[0] - anchor[k] for k, v in new_p.items()}
                res = ({k: sync_err[k][0] for k in deltas}
                       if sync_err else None)
                mean_d, res = compressed_tree_mean(
                    deltas, "data", policy=self.param_sync,
                    block=self.param_sync_block,
                    bucket_bytes=self.param_sync_bucket_bytes,
                    residuals=res)
                synced = {k: anchor[k] + mean_d[k] for k in deltas}
                out_p = {k: synced[k][None] for k in new_p}
                new_err = ({k: res[k][None] for k in sync_err}
                           if sync_err else sync_err)
                return out_p, dict(synced), new_err

            anchor_spec = {k: P() for k in self.state["anchor"]}
            sharded_sync = shard_map(
                sync_fn, mesh=mesh,
                in_specs=(pspec, anchor_spec, err_spec),
                out_specs=(pspec, anchor_spec, err_spec),
                check_vma=False)

        def make_train_step(do_sync: bool):
            """One of the two programs: with the collectives (sync) or
            with NONE (the truly communication-free local step)."""

            def train_step(params, frozen, buffers, opt_state, anchor,
                           sync_err, key, lr, inputs, labels):
                loss, grads = sharded_grads(params, frozen, buffers, key,
                                            inputs, labels)
                new_p, new_opt = opt.apply_gradients(params, grads,
                                                     opt_state, lr=lr)
                if do_sync:
                    # average params (and moments) over replicas — XLA
                    # inserts the cross-replica all-reduce here. The mean
                    # leaves replica-major as it came: left to XLA it
                    # leaves replicated, D copies a device, and the local
                    # program is staged again for that input
                    def avg(v):
                        return lax.with_sharding_constraint(
                            jnp.broadcast_to(
                                jnp.mean(v, axis=0, keepdims=True), v.shape),
                            NamedSharding(mesh, _replica_major(v.ndim)))

                    if sharded_sync is not None:
                        new_p, anchor, sync_err = sharded_sync(
                            new_p, anchor, sync_err)
                    else:
                        new_p = {k: avg(v) for k, v in new_p.items()}
                    new_opt = dict(new_opt)
                    new_opt["slots"] = jax.tree_util.tree_map(
                        avg, new_opt.get("slots", {}))
                return loss, new_p, new_opt, anchor, sync_err

            return train_step

        self._program_fns = {True: make_train_step(True),
                             False: make_train_step(False)}
        self._step_cache = {}    # (do_sync, data shapes) -> jitted program
        self._cache_hits = 0

    def _cache_key(self, do_sync: bool, inputs, labels):
        shapes = tuple(
            (tuple(jnp.shape(x)), str(jnp.asarray(x).dtype))
            for x in jax.tree_util.tree_leaves((inputs, labels)))
        return (bool(do_sync),) + shapes

    def _get_step(self, do_sync: bool, inputs, labels):
        key = self._cache_key(do_sync, inputs, labels)
        step = self._step_cache.get(key)
        if step is not None:
            self._cache_hits += 1
            return step
        step = jax.jit(self._program_fns[bool(do_sync)],
                       donate_argnums=(0, 3))
        self._step_cache[key] = step
        return step

    def step_jaxpr(self, do_sync: bool, inputs, labels):
        """The jaxpr of the (sync | no-sync) program for the current state
        and these data shapes — the hook tests/analysis use to assert the
        no-sync program carries zero collective primitives."""
        return jax.make_jaxpr(self._program_fns[bool(do_sync)])(
            dict(self.state["params"]), dict(self.state["frozen"]),
            dict(self.state["buffers"]), self.state["opt"],
            dict(self.state["anchor"]), dict(self.state["sync_err"]),
            get_rng_key(), jnp.float32(0.1), jnp.asarray(inputs),
            jnp.asarray(labels))

    def program_family(self, inputs, labels):
        """The sync/no-sync pair as a declared
        :class:`~paddle_tpu.analysis.schedule.ProgramFamily`: the member
        is picked by ``step_no % k_steps`` — a host-replicated counter
        every rank advances identically (the adaptive-k schedule updates
        from the ALL-REDUCED drift, so it stays replicated too), making
        the deliberately divergent schedules safe."""
        from ...analysis.schedule import ProgramFamily
        return ProgramFamily(
            name="localsgd-step",
            selector="step_no % k_steps (host-replicated step counter; "
                     "adaptive k derives from all-reduced drift)",
            rank_invariant=True,
            members={
                "sync": lambda: self.step_jaxpr(True, inputs, labels),
                "no-sync": lambda: self.step_jaxpr(False, inputs, labels),
            },
            mesh=self.mesh)

    def train_step(self, inputs, labels, lr=None):
        lr = self.optimizer.get_lr() if lr is None else lr
        self._step_no += 1
        # host-side sync decision: picks WHICH cached program runs (the
        # adaptive k schedule changes no traced operand, so no recompile)
        do_sync = (self._step_no % self.k_steps) == 0
        data_sh = NamedSharding(self.mesh, P(("data",)))
        inputs = jax.device_put(jnp.asarray(inputs), data_sh)
        labels = jax.device_put(jnp.asarray(labels), data_sh)
        step = self._get_step(do_sync, inputs, labels)
        loss, new_p, new_opt, new_anchor, new_err = step(
            self.state["params"], self.state["frozen"],
            self.state["buffers"], self.state["opt"],
            self.state["anchor"], self.state["sync_err"], get_rng_key(),
            lr, inputs, labels)
        self.state["params"] = new_p
        self.state["opt"] = new_opt
        self.state["anchor"] = new_anchor
        self.state["sync_err"] = new_err
        loss = jnp.mean(loss)    # per-replica losses -> reported mean
        lv = float(loss)
        if self.adaptive:
            # reference localsgd_optimizer.py:425 communicate_avg_loss:
            # next_k = ceil(sqrt(lr_0 * loss / (lr * loss_0) * init_k)),
            # clamped to [1, max] — sync MORE often as loss (or lr) drops
            if self._loss0 is None:
                self._loss0 = max(lv, 1e-12)
                self._lr0 = float(lr)
            self.k_steps = int(np.clip(
                np.ceil(np.sqrt(self._lr0 * max(lv, 1e-12) /
                                (max(float(lr), 1e-12) * self._loss0) *
                                self.init_k_steps)),
                1, self.max_k_steps))
        return loss

    def replica_params(self, k):
        """Per-replica views of a trainable param (for tests/inspection)."""
        return np.asarray(self.state["params"][k])

    def averaged_state_dict(self):
        return {k: jnp.mean(v, axis=0)
                for k, v in self.state["params"].items()}
