"""Mixture-of-Experts layer with expert parallelism over a mesh axis.

The reference has no MoE (SURVEY.md §2 EP row: absent; only
operators/collective/alltoall_op.cc exists as the building block). To meet
"same capabilities" the framework ships the capability class: top-k gated
MoE whose experts are sharded over the "model" (or a dedicated) mesh axis,
with lax.all_to_all dispatch/combine — the TPU-native version of what
alltoall_op.cc enables.

Design (static shapes, MXU-friendly): capacity-based dispatch. Each device
routes its tokens to per-expert buffers of fixed capacity C (drop+pad, like
GShard/Switch), all_to_all's them over the expert axis, applies its local
experts batched, and all_to_all's back. Its dispatch and combine tensors are
``(tokens, experts, capacity)`` one-hots, so it serves a few experts.

``DroplessMoELayer`` (ISSUE 27) is the layer for hundreds of experts: a
top-k router over all of them, a layer that is told which experts it holds,
sorted (token, expert) assignments and grouped matrix products
(``jax.lax.ragged_dot``), a shared expert, and no dropped token. It computes
its own experts' part of the result; the exchange that would bring other
chips' tokens to them is not written yet.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.layers.common import GatedSiluFFN, Linear, SquaredReluFFN

EXPERT_AXIS = "model"


def _in_axis(axis):
    try:
        lax.axis_index(axis)
        return True
    except Exception:
        return False


def top2_gating(logits, capacity):
    """Top-2 gating with load-balancing aux loss (GShard-style).

    logits: (T, E). Returns (combine (T, E, C), dispatch bool (T, E, C),
    aux_loss scalar).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    g1 = jnp.max(probs, axis=-1)
    e1 = jnp.argmax(probs, axis=-1)
    probs_wo1 = probs * (1.0 - jax.nn.one_hot(e1, E))
    g2 = jnp.max(probs_wo1, axis=-1)
    e2 = jnp.argmax(probs_wo1, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    # aux loss: mean prob per expert × fraction of tokens routed to it
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(e1, E), axis=0)
    aux = jnp.sum(me * ce) * E

    def positions(e_idx):
        onehot = jax.nn.one_hot(e_idx, E, dtype=jnp.int32)  # (T, E)
        pos = jnp.cumsum(onehot, axis=0) - 1                # position in expert
        return onehot, pos

    oh1, pos1 = positions(e1)
    # second choice queues behind first-choice tokens of the same expert
    oh2, pos2_raw = positions(e2)
    counts1 = jnp.sum(oh1, axis=0, keepdims=True)
    pos2 = pos2_raw + counts1

    def build(onehot, pos, gate):
        keep = (jnp.sum(onehot * pos, axis=-1) < capacity) & (gate > 0)
        slot = jnp.sum(onehot * pos, axis=-1)
        disp = (onehot.astype(bool) & keep[:, None])[..., None] & \
            (jax.nn.one_hot(slot, capacity, dtype=jnp.int32)[:, None, :] > 0)
        comb = disp.astype(jnp.float32) * gate[:, None, None]
        return comb, disp

    c1, d1 = build(oh1, pos1, g1)
    c2, d2 = build(oh2, pos2, g2)
    return c1 + c2, d1 | d2, aux


class ExpertFFN(Layer):
    def __init__(self, d_model, d_hidden):
        super().__init__()
        self.fc1 = Linear(d_model, d_hidden)
        self.fc2 = Linear(d_hidden, d_model)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class MoELayer(Layer):
    """Top-2 MoE with expert parallelism.

    Expert weights are STACKED along a leading (E, ...) axis — one batched
    einsum applies all (local) experts, and sharding that axis over
    ``axis_name`` (e.g. NamedSharding(mesh, P("model"))) shards parameter
    memory E/n-per-device; inside shard_map the local slice is selected with
    one dynamic_slice, not an O(E) switch. num_experts must be divisible by
    the expert-axis size. Outside shard_map (single device) all experts run
    locally — same numerics.

    The load-balancing aux loss is written to the non-persistable buffer
    ``aux_loss`` so it flows out of jitted functional_call as a value (read
    it from new_buffers, or eagerly as ``moe.aux_loss``) instead of leaking
    a tracer through a Python attribute.
    """

    def __init__(self, d_model, d_hidden, num_experts, capacity_factor=2.0,
                 axis_name=EXPERT_AXIS, gate_weight_attr=None):
        super().__init__()
        from ..nn.initializer import XavierUniform
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.axis_name = axis_name
        self.gate = Linear(d_model, num_experts, bias_attr=False)
        E = num_experts
        self.w1 = self.create_parameter(
            (E, d_model, d_hidden),
            initializer=XavierUniform(fan_in=d_model, fan_out=d_hidden))
        self.b1 = self.create_parameter((E, d_hidden), is_bias=True)
        self.w2 = self.create_parameter(
            (E, d_hidden, d_model),
            initializer=XavierUniform(fan_in=d_hidden, fan_out=d_model))
        self.b2 = self.create_parameter((E, d_model), is_bias=True)
        self.register_buffer("aux_loss", jnp.zeros((), jnp.float32),
                             persistable=False)

    def _run_experts(self, buf, w1, b1, w2, b2):
        """buf: (e, C, D) through e stacked experts → (e, C, D)."""
        dt = buf.dtype
        h = jnp.einsum("ecd,edh->ech", buf, w1.astype(dt)) + \
            b1.astype(dt)[:, None, :]
        h = F.gelu(h, approximate=True)
        return jnp.einsum("ech,ehd->ecd", h, w2.astype(dt)) + \
            b2.astype(dt)[:, None, :]

    def forward(self, x):
        b, s, d = x.shape
        tokens = jnp.reshape(x, (b * s, d))
        T = tokens.shape[0]
        E = self.num_experts
        in_spmd = _in_axis(self.axis_name)
        n = lax.axis_size(self.axis_name) if in_spmd else 1
        cap = int(self.capacity_factor * T * 2 / E) or 1
        # round capacity to a lane-friendly size
        cap = max(8, ((cap + 7) // 8) * 8)

        logits = self.gate(tokens)
        combine, dispatch, aux = top2_gating(logits, cap)
        self.aux_loss = aux

        w1, b1 = self.w1.value, self.b1.value
        w2, b2 = self.w2.value, self.b2.value
        # dispatch: (T, E, C) x (T, D) → (E, C, D)
        expert_in = jnp.einsum("tec,td->ecd",
                               dispatch.astype(tokens.dtype), tokens)
        if in_spmd and n > 1:
            # (E, C, D) → all_to_all over expert axis: every device keeps its
            # E/n experts' buffers from ALL devices → (E/n, n*C, D)
            expert_in = lax.all_to_all(expert_in, self.axis_name,
                                       split_axis=0, concat_axis=1,
                                       tiled=True)
            local = E // n
            start = lax.axis_index(self.axis_name) * local
            expert_out = self._run_experts(
                expert_in,
                lax.dynamic_slice_in_dim(w1, start, local, 0),
                lax.dynamic_slice_in_dim(b1, start, local, 0),
                lax.dynamic_slice_in_dim(w2, start, local, 0),
                lax.dynamic_slice_in_dim(b2, start, local, 0))
            expert_out = lax.all_to_all(expert_out, self.axis_name,
                                        split_axis=1, concat_axis=0,
                                        tiled=True)  # (E, C, D)
        else:
            expert_out = self._run_experts(expert_in, w1, b1, w2, b2)

        out = jnp.einsum("tec,ecd->td", combine.astype(tokens.dtype),
                         expert_out)
        return jnp.reshape(out, (b, s, d))


# ---------------------------------------------------------------------------
# dropless top-k layer over held experts
# ---------------------------------------------------------------------------
def route_top_k(logits, top_k, scoring="sigmoid", scaling_factor=1.0,
                selection_bias=None):
    """``(expert ids int32 (T, k), weights float32 (T, k))`` from float32
    router logits ``(T, E)``: scores are ``sigmoid`` or ``softmax`` of the
    logits, the ``top_k`` largest are chosen, and their weights are the
    scores over their sum, times ``scaling_factor``. With
    ``selection_bias`` ``(E,)`` (auxiliary-loss-free balancing,
    ``topk_method: noaux_tc``) the experts are chosen by ``scores + bias``
    and weighted by their ``scores`` alone; no gradient reaches the bias."""
    logits = logits.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if selection_bias is None:
        top, ids = lax.top_k(scores, top_k)
    else:
        # only the ids read the bias, so no gradient reaches it
        _, ids = lax.top_k(scores + selection_bias.astype(jnp.float32),
                           top_k)
        top = jnp.take_along_axis(scores, ids, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), top * scaling_factor


# the routed and the shared experts' form, by name: the shared expert's
# class (GroupedExperts is either)
ACTIVATIONS = {"silu": GatedSiluFFN, "relu2": SquaredReluFFN}


class GroupedExperts(Layer):
    """``count`` FFNs with stacked weights, applied to rows that are sorted
    by expert. ``activation="silu"``: gated SiLU, ``down(silu(gate(x)) *
    up(x))``, three grouped matrix products; ``"relu2"``: ``down(relu(up(x))
    ^ 2)``, two, and no ``gate_proj``."""

    def __init__(self, count, d_model, d_expert, activation="silu"):
        super().__init__()
        from ..nn.initializer import XavierUniform
        if activation not in ACTIVATIONS:
            raise ValueError(f"unknown expert activation {activation!r}")
        self.activation = activation
        wide = XavierUniform(fan_in=d_model, fan_out=d_expert)
        if activation == "silu":
            self.gate_proj = self.create_parameter(
                (count, d_model, d_expert), initializer=wide)
        self.up_proj = self.create_parameter((count, d_model, d_expert),
                                             initializer=wide)
        self.down_proj = self.create_parameter(
            (count, d_expert, d_model),
            initializer=XavierUniform(fan_in=d_expert, fan_out=d_model))

    def forward(self, rows, group_sizes):
        """rows ``(R, d_model)``, the first ``group_sizes[0]`` of them for
        expert 0 and so on. Rows past ``sum(group_sizes)`` belong to no
        group: on the TPU the kernels do not write them, in the result or
        in its gradient, so the caller masks them."""
        dt = rows.dtype
        gate = None if self.activation == "relu2" else lax.ragged_dot(
            rows, self.gate_proj.value.astype(dt), group_sizes)
        up = lax.ragged_dot(rows, self.up_proj.value.astype(dt), group_sizes)
        hidden = jnp.square(F.relu(up)) if gate is None else F.silu(gate) * up
        return lax.ragged_dot(hidden, self.down_proj.value.astype(dt),
                              group_sizes)



# the compiler's grouped-product kernels work in tiles of this many rows
ROW_TILE = 512
# The part of the buffer that always runs, over the expected assignments.
# Gathers and scatters cost by the row, masked or not (about 0.1 us a row
# on a v5e), so this is paid on every step; the rest of the buffer costs a
# whole pass whenever it runs. Over 12 seeds of the benchmark's traffic
# the held share of a freshly initialised router was 0.83 to 1.22 of the
# expectation, and at 1.25 the second part ran in some layer on most
# steps (PERF.md section 6, PR 27): twice the expectation keeps it for
# real imbalance.
CHUNK_HEADROOM = 2.0


class DroplessMoELayer(Layer):
    """``[gate(x)] shared_expert(x) + sum over the held e in
    top_k(router(x)) of w_e * expert_e(x)``.

    The router scores every token over all ``num_experts`` in float32. The
    layer holds the experts ``held = (first, count)`` (all of them by
    default) and computes their part of the sum; what the experts held
    elsewhere would add is left out.

    Every (token, expert) assignment that falls on a held expert is
    computed, whatever the imbalance. The assignments are sorted by expert,
    held ones first. Up to ``buffer_rows = tokens * min(top_k, count)`` of
    them can fall here (a token's ``top_k`` experts are distinct), and all
    of those are walked, in two parts: the first ``chunk_rows`` (twice the
    expected number ``tokens * top_k * count / num_experts``) always, the
    rest of the buffer only when assignments are left for it (``lax.cond``,
    decided at run time). So a balanced router pays for its load and the
    worst one for the whole buffer, and nothing is ever cut. In each part
    the tokens are gathered, the held experts run as grouped products over
    the sorted rows, and the weighted results are added back to their
    tokens. Rows past the last assignment belong to no group; the kernels
    leave them unwritten, so they are masked on the way in and on the way
    out, in both directions.

    Sublayers ``router``, ``shared_expert``, ``experts`` and the scopes
    ``dispatch`` (sort, gather) and ``combine`` (weights, scatter back) name
    every op in a device trace. Three non-persistable buffers carry the last
    call's counts out of a jitted ``functional_call`` as ``aux_loss`` does
    for ``MoELayer``: ``tokens_routed``, ``held_assignments`` and
    ``max_load_over_mean`` (the fullest held expert's assignments over the
    mean per held expert); ``publish_routing`` writes them to the telemetry
    registry. ``scoring`` is the router's rule, ``"sigmoid"`` or
    ``"softmax"``; either way the chosen scores are divided by their sum and
    multiplied by ``routed_scaling_factor``. ``shared_expert_gate`` puts a
    learned ``sigmoid(x w)`` (``w`` ``[d_model, 1]``, the sublayer
    ``shared_expert_gate``) on the shared expert's output, a weight a
    token. ``router_attr`` is the router
    weight's ``ParamAttr`` (initialiser, learning-rate multiplier): AdamW
    moves a fresh router a full step whatever its gradient, and at a rate
    the rest of a model trains at, the held experts' load runs to nothing or
    to twice its expectation within twenty steps (PERF.md section 6, PRs 27
    and 31). ``selection_bias=True`` registers ``e_score_correction_bias``
    ``(num_experts,)`` float32, zeros: a persistable buffer and not a
    parameter (it is in ``state_dict`` and in a trainer's state, and has no
    gradient and no optimizer slot), which ``route_top_k`` adds to the
    scores to choose the experts and leaves out of their weights. Nothing
    here changes it: the balancing step that would is a training recipe.
    ``activation`` is the routed and the shared experts' form
    (``GroupedExperts``): ``"silu"``, gated, or ``"relu2"``.
    """

    def __init__(self, d_model, d_expert, num_experts, top_k, held=None,
                 routed_scaling_factor=1.0, scoring="sigmoid", d_shared=None,
                 router_attr=None, shared_expert_gate=False,
                 selection_bias=False, activation="silu"):
        super().__init__()
        first, count = held if held is not None else (0, num_experts)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"held={held} is not a range of the "
                             f"{num_experts} experts")
        if top_k > num_experts:
            raise ValueError(f"top_k={top_k} of {num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.count = first, count
        self.routed_scaling_factor = routed_scaling_factor
        self.scoring = scoring
        self.router = Linear(d_model, num_experts, bias_attr=False,
                             weight_attr=router_attr)
        self.shared_expert = (ACTIVATIONS[activation](d_model, d_shared)
                              if d_shared else None)
        self.shared_expert_gate = (
            Linear(d_model, 1, bias_attr=False)
            if shared_expert_gate and d_shared else None)
        self.experts = GroupedExperts(count, d_model, d_expert, activation)
        if selection_bias:
            self.register_buffer("e_score_correction_bias",
                                 jnp.zeros((num_experts,), jnp.float32))
        for name, dtype in (("tokens_routed", jnp.int32),
                            ("held_assignments", jnp.int32),
                            ("max_load_over_mean", jnp.float32)):
            self.register_buffer(name, jnp.zeros((), dtype),
                                 persistable=False)

    def buffer_rows(self, tokens: int) -> int:
        """The most assignments that can fall on the held experts."""
        return tokens * min(self.top_k, self.count)

    def chunk_rows(self, tokens: int) -> int:
        """Rows of the part that always runs: ``CHUNK_HEADROOM`` times the
        expected assignments, in whole kernel tiles, at most
        ``buffer_rows``."""
        expected = tokens * self.top_k * self.count / self.num_experts
        tiles = -(-int(CHUNK_HEADROOM * expected) // ROW_TILE)
        return min(max(tiles, 1) * ROW_TILE, self.buffer_rows(tokens))

    def route(self, tokens):
        """Expert ids and weights ``(T, top_k)`` of ``tokens (T, d_model)``,
        the router's product accumulated and scored in float32."""
        with jax.named_scope("router"):
            logits = lax.dot_general(
                tokens, self.router.weight.value.astype(tokens.dtype),
                (((1,), (0,)), ((), ())), precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            return route_top_k(
                logits, self.top_k, self.scoring, self.routed_scaling_factor,
                self._buffers.get("e_score_correction_bias"))

    def forward(self, x):
        shape = x.shape
        tokens = jnp.reshape(x, (-1, shape[-1]))
        T, k = tokens.shape[0], self.top_k
        ids, weights = self.route(tokens)
        with jax.named_scope("dispatch"):
            local = jnp.reshape(ids, (-1,)) - self.first        # (T*k,)
            is_held = (local >= 0) & (local < self.count)
            # held assignments first, by expert; the others after them
            key = jnp.where(is_held, local, self.count)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            group_sizes = jnp.bincount(key, length=self.count + 1)[
                :self.count].astype(jnp.int32)
            ends = jnp.cumsum(group_sizes)
            starts, n_held = ends - group_sizes, ends[-1]
            flat_weights = jnp.reshape(weights, (-1,))

        def add_rows(acc, lo, size):
            """The sorted assignments ``lo .. lo + size`` added to acc."""
            with jax.named_scope("dispatch"):
                which = order[lo:lo + size]
                valid = lo + jnp.arange(size) < n_held
                token_of = which // k
                rows = jnp.where(valid[:, None],
                                 jnp.take(tokens, token_of, axis=0), 0)
                sizes = jnp.clip(ends - lo, 0, size) \
                    - jnp.clip(starts - lo, 0, size)
            out = self.experts(rows, sizes)
            with jax.named_scope("combine"):
                # masked before the weights touch it: an unwritten row may
                # hold anything, and 0 * nan would reach the router's
                # gradient through the weight
                out = jnp.where(valid[:, None], out, 0).astype(jnp.float32)
                out = out * jnp.take(flat_weights, which)[:, None]
                return acc.at[token_of].add(out.astype(acc.dtype))

        # The first chunk holds the expected load and always runs. The rest
        # of the buffer runs only when assignments are left for it, which a
        # balanced router never has: skipped at run time otherwise, and
        # recomputed in the backward pass so that it keeps nothing for it.
        chunk, rows = self.chunk_rows(T), self.buffer_rows(T)
        routed = add_rows(jnp.zeros_like(tokens), 0, chunk)
        if rows > chunk:
            routed = lax.cond(
                n_held > chunk,
                jax.checkpoint(lambda acc: add_rows(acc, chunk, rows - chunk)),
                lambda acc: acc, routed)

        self.tokens_routed = jnp.asarray(T, jnp.int32)
        self.held_assignments = n_held
        self.max_load_over_mean = jnp.max(group_sizes) * self.count \
            / jnp.maximum(n_held, 1).astype(jnp.float32)

        out = routed
        if self.shared_expert is not None:
            shared = self.shared_expert(tokens)
            if self.shared_expert_gate is not None:
                gate = jax.nn.sigmoid(
                    self.shared_expert_gate(tokens).astype(jnp.float32))
                shared = shared * gate.astype(shared.dtype)
            out = out + shared
        return jnp.reshape(out, shape)

    def publish_routing(self, buffers=None, prefix="", **labels):
        """The last call's counts into the telemetry registry: counters
        ``moe_tokens_routed_total`` and ``moe_held_assignments_total``, gauge
        ``moe_max_load_over_mean``. ``buffers`` is what a jitted
        ``functional_call`` returned (names under ``prefix``); without it
        the layer's own buffers, which an eager call wrote. Host side: it
        fetches three scalars."""
        from .. import telemetry
        src = buffers if buffers is not None else dict(self.named_buffers())
        tokens = int(src[prefix + "tokens_routed"])
        held = int(src[prefix + "held_assignments"])
        telemetry.counter(
            "moe_tokens_routed_total",
            "tokens a dropless expert layer routed").inc(tokens, **labels)
        telemetry.counter(
            "moe_held_assignments_total",
            "(token, expert) assignments that fell on the experts held "
            "here; none is dropped").inc(held, **labels)
        telemetry.gauge(
            "moe_max_load_over_mean",
            "assignments of the fullest held expert over the mean per held "
            "expert, last call").set(
                float(src[prefix + "max_load_over_mean"]), **labels)
