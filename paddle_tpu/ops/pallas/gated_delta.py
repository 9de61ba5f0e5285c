"""The chunked gated delta rule with a chunk's intermediates in VMEM: one
forward and one backward kernel a (row, value head), from ``q, k, v, g,
beta`` to ``o``.

``nn/functional/linear_attention.py`` states the rule and its chunked (WY)
form; this module computes that form, digit for digit the same algebra, in
two kernels whose grid is ``(row, head, tile of positions)``. A grid step
takes ``tile`` chunks of ``CHUNK`` positions of one head: for each of them
the within-chunk decays, ``kk`` and ``qk``, the unit lower-triangular
system ``I + a``, its inverse ``T`` (blocks of 1, 2, 4, ... rows inverted
from the inverses of their halves, as ``inverse_unit_lower``), ``W`` and
``U`` are built independently of one another; then the ``d_k x d_v``
float32 state, which lives in a VMEM scratch over the tiles of a head (the
grid's last axis runs in order), walks the tile's chunks: ``v_new = U - W
S``, ``o = e^gc (q S) + (qk * decay) v_new``, ``S <- e^last S + K^T (dte
v_new)``. HBM sees ``q, k, v`` in and ``o`` out in the operands' dtype, the
cumulative decay and ``beta`` in float32, and one residual: the state
entering each chunk (float32, ``d_k x d_v`` a chunk). Nothing of size
``chunk x chunk`` leaves VMEM.

The order the code is emitted in is the order the MXU is fed in: a chunk's
inverse is a chain of ten dependent products, and written a chunk after
another the MXU waited on each of them (15.5 ms a layer's forward, 8.9 with
the chunks' chains interleaved; PERF.md section 6, PR 34). So what a chunk
does on its own is a generator that yields after every product stage, and
``_lockstep`` advances the tile's chunks a stage at a time.

The backward walks the tiles in reverse with the state's cotangent in the
scratch. It builds each chunk's system, inverse, ``W``, ``U`` and (from the
saved state) ``v_new`` again, runs the reverse chain ``c <- q^T dqS + e^last
c - W^T dv_new`` over the tile's chunks, and writes ``dq, dk, dv`` and the
cotangents of the cumulative decay and of ``beta`` once. The inverse's
cotangent is ``-T^T dT T^T`` under the strict lower mask.

Precision is the configuration's (``linear_attention.PRECISION = HIGH``),
asked for explicitly because Mosaic rounds a float32 operand to bf16 in one
pass at its default: a float32 operand enters a product as two bf16 terms
``hi + lo`` (``hi = bf16(x)``, ``lo = bf16(x - hi)``) and the cross terms
are kept, so a product of a float32 with a bf16 operand is two MXU passes
and one of two float32 operands three (``lo x lo``, 2**-16 of a term, is
dropped: what three bf16 passes give). ``q``, ``k``, ``v`` in bf16 enter as
they are.

Layout: ``q, k, v`` are read as the layer's fusions leave them, ``(batch,
seq, heads x width)`` with a whole number of 128-lane blocks a head (a free
reshape of ``(batch, seq, heads, width)``), and ``o`` is written the same
way. A head narrower than its blocks (Olmo-Hybrid's ``d_k = 96`` and ``d_v
= 192``) arrives with zero lanes after its own, ``pad_lanes``: a zero key
lane adds nothing to ``k k^T``, ``q k^T`` or ``S^T q`` and a zero value lane
writes a zero column of the state and of ``o``, so no value changes, and
the pad fuses into the fusions that make ``q, k, v`` and the cut into the
one that reads ``o``. The per-position
vectors (cumulative decay, ``beta``) arrive with a chunk's positions along
the lanes; the factors of rows come from one small product with a constant
on the MXU (``_Masks.columns``: exact, three bf16 terms of the float32 value), in
every lane of their row, so that no use of them broadcasts.

Forward and backward are staged once a program, each behind one inner
``jax.jit`` (as ``flash_attention._fwd``): ``pallas_call`` names
``gated_delta_fwd`` and ``gated_delta_bwd``.
"""
from __future__ import annotations

import functools
import itertools
import operator

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# positions a chunk: what the kernels are built for (the layer's ``CHUNK``)
CHUNK = 64
# chunks a grid step, unrolled. One layer's rule at (2, 8192, 32, 128) bf16,
# forward / forward + backward: 2 chunks 10.5 / 25.4 ms, 4 8.7 / 21.0, 8
# 8.9 / 21.3 (a sixteenth of the grid steps of one chunk each); at 16 the
# backward asks for 18.3 MB of Mosaic's 16 MB of scoped VMEM (my chip runs,
# PR 34)
TILE = 8
FWD, BWD = "gated_delta_fwd", "gated_delta_bwd"

_F32, _BF16 = jnp.float32, jnp.bfloat16
# dimension numbers of a product of two matrices
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def tile_chunks(chunks, tile=TILE):
    """Chunks a grid step takes of a row of ``chunks`` chunks: ``tile``
    where that divides the row, the whole row where it is shorter, else
    None."""
    if chunks % tile == 0:
        return tile
    return chunks if chunks < tile else None


def pad_lanes(d):
    """The lanes a head of width ``d`` takes in the kernels: ``d`` rounded
    up to whole 128-lane blocks."""
    return -(-d // LANES) * LANES


def _fills(d, blocks):
    """A head of width ``d`` takes at most ``blocks`` 128-lane blocks, and
    more than half of the lanes it takes are its own."""
    return pad_lanes(d) <= blocks * LANES and 2 * d > pad_lanes(d)


def supported(q_shape, v_shape, dtype, chunk):
    """Whether the kernels take ``q`` ``(batch, seq, heads, d_k)`` and ``v``
    ``(batch, seq, heads, d_v)``: a key head of one 128-lane block and a
    value head of two at most, each padded to whole blocks (``pad_lanes``)
    with fewer zero lanes than its own (narrower heads take XLA's path), the
    chunk they are built for, bf16 or float32 operands and a sequence whose
    chunks (the last one padded) are a whole number of tiles."""
    seq = q_shape[1]
    return (_fills(q_shape[-1], 1) and _fills(v_shape[-1], 2)
            and chunk == CHUNK
            and jnp.dtype(dtype) in (jnp.dtype(_BF16), jnp.dtype(_F32))
            and tile_chunks(-(-seq // chunk)) is not None)


# -- products at the configuration's precision -------------------------------

def _terms(x):
    """``x`` as the bf16 terms a product takes: itself in bf16, ``(hi,
    lo)`` in float32."""
    if x.dtype == _BF16:
        return (x,)
    hi = x.astype(_BF16)
    return hi, (x - hi.astype(_F32)).astype(_BF16)


def _dot(a, b, dims=_NN):
    """The product of two operands given by their terms, accumulated in
    float32: every pair of terms but ``lo x lo``. Where the contraction is
    over rows, or over whole 128-lane blocks, the pairs are stacked along
    it and the MXU adds them (``[hi | lo | hi] @ [hi; hi; lo]``): one
    product's results to fetch and none to add (3% of a layer's forward; a
    stack of 64-lane halves costs more than it saves: 20.2 for 15.6 ms; my
    chip runs, PR 34)."""
    pairs = [(x, y) for i, x in enumerate(a) for j, y in enumerate(b)
             if i + j < 2]
    (ca,), (cb,) = dims[0]
    whole = pairs[0][0].shape[ca] % LANES == 0
    if len(pairs) > 1 and (ca == 0 or whole) and (cb == 0 or whole):
        return lax.dot_general(
            jnp.concatenate([x for x, _ in pairs], axis=ca),
            jnp.concatenate([y for _, y in pairs], axis=cb), dims,
            preferred_element_type=_F32)
    return functools.reduce(operator.add, (
        lax.dot_general(x, y, dims, preferred_element_type=_F32)
        for x, y in pairs))


def _lockstep(chunks):
    """Advances the generators of a tile's chunks a stage at a time, so
    that the stage's products of all chunks stand next to one another."""
    for _ in itertools.zip_longest(*chunks):
        pass


# -- a chunk's system --------------------------------------------------------

# the block sizes the inverse merges at, after the first (at 1 the
# inverses of the halves are the identity)
_LEVELS = tuple(2 ** i for i in range(CHUNK.bit_length() - 1))


class _Masks:
    """The index masks of a ``CHUNK x CHUNK`` system, and the constant
    that turns vectors."""

    def __init__(self):
        self.row = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
        self.col = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
        self.eye = self.row == self.col
        self.seen = self.row >= self.col
        self.strict = self.row > self.col
        self.last = lax.broadcasted_iota(jnp.int32, (1, CHUNK), 1) \
            == CHUNK - 1
        self.below = {s: self._below(s) for s in _LEVELS}
        # rows 0 and 1 of each of three 16-row terms to the first and the
        # second 128 lanes
        r = lax.broadcasted_iota(jnp.int32, (48, 2 * LANES), 0) & 15
        lane = lax.broadcasted_iota(jnp.int32, (48, 2 * LANES), 1)
        self.spread = jnp.where(
            ((r == 0) & (lane < LANES)) | ((r == 1) & (lane >= LANES)),
            1.0, 0.0).astype(_BF16)
        self.first = lax.broadcasted_iota(jnp.int32, (16, CHUNK), 0)

    def _below(self, s):
        """Inside every diagonal block of ``2s`` rows, the part below the
        first half and left of the second (``s`` a power of two)."""
        shift = s.bit_length() - 1
        r, c = self.row >> shift, self.col >> shift
        return (r >> 1 == c >> 1) & (r & 1 == 1) & (c & 1 == 0)

    def columns(self, x, y):
        """Two vectors along the lanes ``(1, CHUNK)`` as factors of rows,
        ``(CHUNK, 1)`` each with its value in every lane: ``[x; y]^T`` times
        a constant of ones on the MXU, the float32 values as three bf16
        terms each, which carry all 24 bits, so the sum is the value. (As a
        masked sum over the lanes, every later use paid a broadcast: 8.4 ->
        7.4 ms a layer's forward; my chip run, PR 34.)"""
        rest = jnp.where(self.first == 0, x,
                         jnp.where(self.first == 1, y, 0.0))
        terms = []
        for _ in range(3):
            terms.append(rest.astype(_BF16))
            rest = rest - terms[-1].astype(_F32)
        both = lax.dot_general(jnp.concatenate(terms, axis=0), self.spread,
                               _TN, preferred_element_type=_F32)
        return both[:, :1], both[:, LANES:LANES + 1]

    def to_row(self, x):
        """A factor of rows ``(CHUNK, 1)`` as a vector along the lanes."""
        return jnp.sum(jnp.where(self.eye, x, 0.0), axis=0, keepdims=True)


class _System:
    """What a chunk holds before the state reaches it."""

    def build(self, q, k, v, gc, beta, m):
        """``q, k, v`` ``(CHUNK, 128)``, ``gc`` (the decay summed from the
        chunk's start) and ``beta`` ``(1, CHUNK)`` float32. A generator: a
        stage of products a step. The inverse is
        ``linear_attention.inverse_unit_lower``'s: ``t <- t - t B t`` with
        ``B`` the part of ``a`` that joins the halves of every block of
        ``2s`` rows, ``s`` = 1, 2, ... (at ``s`` = 1 ``t`` is the identity
        and the step is ``I - B``)."""
        self.q, self.k, self.v = _terms(q), _terms(k), _terms(v)
        self.b_row, self.eg_row = beta, jnp.exp(gc)
        gc_col, self.b_col = m.columns(gc, beta)
        self.eg_col = jnp.exp(gc_col)
        # the exponent is masked, not the result: nothing overflows
        self.decay = jnp.exp(jnp.where(m.seen, gc_col - gc, -jnp.inf))
        last = jnp.sum(jnp.where(m.last, gc, 0.0), axis=1, keepdims=True)
        self.dte = jnp.exp(last - gc_col)       # to the chunk's end, a row
        self.al = jnp.exp(last)                 # the whole chunk's, (1, 1)
        kk = _dot(self.k, self.k, _NT)
        qk = _dot(self.q, self.k, _NT)
        yield
        self.p = qk * self.decay
        self.a0 = jnp.where(m.strict, kk * self.decay, 0.0)
        self.a = self.b_col * self.a0
        hi = self.a.astype(_BF16).astype(_F32)
        lo = self.a - hi
        t = jnp.where(m.eye, 1.0, 0.0) - jnp.where(m.below[1], self.a, 0.0)
        for s in _LEVELS[1:]:
            b = tuple(jnp.where(m.below[s], x, 0.0).astype(_BF16)
                      for x in (hi, lo))
            tt = _terms(t)
            tb = _dot(tt, b)
            yield
            t = t - _dot(_terms(tb), tt)
            yield
        self.t = t
        self.tb = t * self.b_row
        self.tb_t, self.tg_t = _terms(self.tb), _terms(self.tb * self.eg_row)
        w = _dot(self.tg_t, self.k)
        self.u = _dot(self.tb_t, self.v)
        yield
        self.w = _terms(w)


def _rows(n):
    return slice(n * CHUNK, (n + 1) * CHUNK)


def _systems(q_ref, k_ref, v_ref, gc_ref, beta_ref, tile, m):
    systems = [_System() for _ in range(tile)]
    _lockstep(c.build(q_ref[0, _rows(n), :], k_ref[0, _rows(n), :],
                      v_ref[0, _rows(n), :], gc_ref[0, 0, 0, n:n + 1, :],
                      beta_ref[0, 0, 0, n:n + 1, :], m)
              for n, c in enumerate(systems))
    return systems


# -- the kernels -------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, o_ref, s_ref, state,
                *, tile):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    systems = _systems(q_ref, k_ref, v_ref, gc_ref, beta_ref, tile, _Masks())
    s = state[...]
    for n, c in enumerate(systems):
        s_ref[0, 0, n] = s
        sp = _terms(s)
        v_new = c.u - _dot(c.w, sp)
        o = c.eg_col * _dot(c.q, sp) + _dot(_terms(c.p), _terms(v_new))
        o_ref[0, _rows(n), :] = o.astype(o_ref.dtype)
        s = c.al * s + _dot(c.k, _terms(c.dte * v_new), _TN)
    state[...] = s


def _bwd_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgc_ref, dbeta_ref, dstate, *, tile):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    m = _Masks()
    systems = _systems(q_ref, k_ref, v_ref, gc_ref, beta_ref, tile, m)

    def alone(n, c):
        """What the saved state gives, a chunk on its own."""
        c.s = s_ref[0, 0, n]
        c.sp = _terms(c.s)
        c.v_new = c.u - _dot(c.w, c.sp)
        do = do_ref[0, _rows(n), :]
        do_t, do = _terms(do), do.astype(_F32)
        qs = _dot(c.q, c.sp)
        dqs = _terms(c.eg_col * do)
        c.dq = _dot(dqs, c.sp, _NT)
        c.ds = _dot(c.q, dqs, _TN)
        c.dv_new = _dot(_terms(c.p), do_t, _TN)
        yield
        c.deg_col = jnp.sum(do * qs, axis=1, keepdims=True)
        c.dp = _dot(do_t, _terms(c.v_new), _NT)

    _lockstep(alone(n, c) for n, c in enumerate(systems))
    # the state's cotangent, from the tile's last chunk to its first
    ct = dstate[...]
    for c in reversed(systems):
        ctp = _terms(ct)
        dz = _dot(c.k, ctp)
        c.dk = _dot(_terms(c.dte * c.v_new), ctp, _NT)
        ddte = jnp.sum(c.v_new * dz, axis=1, keepdims=True) * c.dte
        c.dgc_col = c.deg_col * c.eg_col - ddte
        c.dlast = jnp.sum(ddte, axis=0, keepdims=True) + c.al * jnp.sum(
            jnp.sum(ct * c.s, axis=1, keepdims=True), axis=0, keepdims=True)
        c.dv_new = _terms(c.dv_new + c.dte * dz)
        c.dw = _terms(-_dot(c.dv_new, c.sp, _NT))
        ct = c.ds + c.al * ct - _dot(c.w, c.dv_new, _TN)
    dstate[...] = ct

    def operands(n, c):
        """Through ``W``, ``U`` and the system to the operands."""
        dtg = _dot(c.dw, c.k, _NT)
        dtb = _dot(c.dv_new, c.v, _NT)
        dv = _dot(c.tb_t, c.dv_new, _TN)
        dk = c.dk + _dot(c.tg_t, c.dw, _TN)
        yield
        dtb = dtb + dtg * c.eg_row
        t_t = _terms(c.t)
        x = _dot(_terms(dtb * c.b_row), t_t, _NT)
        yield
        da = jnp.where(m.strict, -_dot(t_t, _terms(x), _TN), 0.0)
        yield
        # d(decay) * decay: from a = beta kk decay and p = qk decay
        e = da * c.a + c.dp * c.p
        dkk = _terms(da * c.b_col * c.decay)
        dqk = _terms(c.dp * c.decay)
        dq = c.dq + _dot(dqk, c.k)
        dk = dk + _dot(dkk, c.k) + _dot(dkk, c.k, _TN) \
            + _dot(dqk, c.q, _TN)
        yield
        dgc_col = c.dgc_col + jnp.sum(e, axis=1, keepdims=True)
        dgc = m.to_row(dgc_col) - jnp.sum(e, axis=0, keepdims=True) \
            + jnp.sum(dtg * c.tb, axis=0, keepdims=True) * c.eg_row \
            + jnp.where(m.last, c.dlast, 0.0)
        dbeta = jnp.sum(dtb * c.t, axis=0, keepdims=True) + m.to_row(
            jnp.sum(da * c.a0, axis=1, keepdims=True))
        dq_ref[0, _rows(n), :] = dq.astype(dq_ref.dtype)
        dk_ref[0, _rows(n), :] = dk.astype(dk_ref.dtype)
        dv_ref[0, _rows(n), :] = dv.astype(dv_ref.dtype)
        dgc_ref[0, 0, 0, n:n + 1, :] = dgc
        dbeta_ref[0, 0, 0, n:n + 1, :] = dbeta

    _lockstep(operands(n, c) for n, c in enumerate(systems))


def _call(name, q, k, v, gc, beta, tile, interpret, states=None, do=None):
    """Forward (``gated_delta_fwd``: ``o`` and the state entering each
    chunk) or backward (``gated_delta_bwd``: ``dq, dk, dv, dgc, dbeta``).
    ``q, k`` ``(batch, seq, heads x dk)``, ``v`` (and ``o``, ``do``)
    ``(batch, seq, heads x dv)``, ``dk`` and ``dv`` whole 128-lane blocks,
    ``gc`` and ``beta`` ``(batch, heads, tiles, tile, CHUNK)`` float32,
    ``states`` ``(batch, heads, chunks, dk, dv)`` float32."""
    backward = name == BWD
    b, heads, tiles = gc.shape[:3]
    seq = q.shape[1]
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    # the backward walks a head's tiles from the last to the first
    at = (lambda t: tiles - 1 - t) if backward else (lambda t: t)
    keys = pl.BlockSpec((1, tile * CHUNK, dk), lambda i, j, t: (i, at(t), j))
    values = pl.BlockSpec((1, tile * CHUNK, dv),
                          lambda i, j, t: (i, at(t), j))
    vec = pl.BlockSpec((1, 1, 1, tile, CHUNK),
                       lambda i, j, t: (i, j, at(t), 0, 0))
    state = pl.BlockSpec((1, 1, tile, dk, dv),
                         lambda i, j, t: (i, j, at(t), 0, 0))
    q_like = jax.ShapeDtypeStruct(q.shape, q.dtype)
    v_like = jax.ShapeDtypeStruct(v.shape, q.dtype)
    vec_like = jax.ShapeDtypeStruct(gc.shape, _F32)
    if backward:
        kernel, operands = _bwd_kernel, (q, k, v, gc, beta, states, do)
        in_specs = [keys] * 2 + [values] + [vec] * 2 + [state, values]
        out_specs = [keys] * 2 + [values] + [vec] * 2
        out_shape = [q_like] * 2 + [v_like] + [vec_like] * 2
    else:
        kernel, operands = _fwd_kernel, (q, k, v, gc, beta)
        in_specs = [keys] * 2 + [values] + [vec] * 2
        out_specs = [values, state]
        out_shape = [v_like, jax.ShapeDtypeStruct(
            (b, heads, seq // CHUNK, dk, dv), _F32)]
    return pl.pallas_call(
        functools.partial(kernel, tile=tile),
        grid=(b, heads, tiles), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            # the state is carried over a head's tiles
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)


# jitted so that the layers of a model share one staged forward and one
# staged backward (PERF.md section 6, PR 28: a ``pallas_call`` costs the
# host a trace and a Mosaic lowering at every call site)
@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd(q, k, v, gc, beta, tile, interpret):
    return _call(FWD, q, k, v, gc, beta, tile, interpret)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _bwd_call(q, k, v, gc, beta, states, do, tile, interpret):
    return _call(BWD, q, k, v, gc, beta, tile, interpret, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, gc, beta, tile, interpret):
    return _fwd(q, k, v, gc, beta, tile, interpret)[0]


def _rule_fwd(q, k, v, gc, beta, tile, interpret):
    o, states = _fwd(q, k, v, gc, beta, tile, interpret)
    return o, (q, k, v, gc, beta, states)


def _rule_bwd(tile, interpret, residuals, do):
    return tuple(_bwd_call(*residuals, do, tile, interpret))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta(q, k, v, g, beta, tile=TILE, interpret=False):
    """``F.gated_delta_rule``'s chunked path through the kernels: ``q, k``
    ``(batch, seq, heads, d_k)``, ``v`` ``(batch, seq, heads, d_v)``, ``g``
    and ``beta`` ``(batch, seq, heads)``; ``q, k, v`` of one dtype, ``o`` in
    it. The caller asks ``supported`` first. A head narrower than whole
    128-lane blocks gets zero lanes after its own (``pad_lanes``) and ``o``
    is cut back to ``d_v``; a sequence that is no whole number of chunks is
    padded with ``beta = 0, g = 0`` and cut again. The sum of ``g`` from a
    chunk's start, and its transpose, stay XLA's. Differentiable in all
    five. ``tile`` is the module's constant for every caller but the tests,
    which take two chunks a grid step so that a short row crosses tiles."""
    b, seq, heads, d_k = q.shape
    d_v = v.shape[-1]
    dk, dv = pad_lanes(d_k), pad_lanes(d_v)
    pad = -seq % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    chunks = (seq + pad) // CHUNK
    tile = tile_chunks(chunks, tile)
    if tile is None:
        raise ValueError(f"{chunks} chunks are no whole number of tiles")

    def vector(x):
        """``(batch, seq, heads)`` as ``(batch, heads, tiles, tile,
        CHUNK)`` float32."""
        x = jnp.moveaxis(x.astype(_F32), 1, 2)
        return jnp.reshape(x, (b, heads, chunks // tile, tile, CHUNK))

    def rows(x, width):
        """``(batch, seq, heads, d)`` as ``(batch, seq, heads x width)``,
        each head's lanes past ``d`` zero."""
        if x.shape[-1] < width:
            x = jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))
        return jnp.reshape(x, (b, seq + pad, heads * width))

    o = _rule(rows(q, dk), rows(k, dk), rows(v, dv),
              jnp.cumsum(vector(g), axis=-1), vector(beta), tile,
              bool(interpret))
    o = jnp.reshape(o, (b, seq + pad, heads, dv))
    return o[:, :seq] if dv == d_v else o[:, :seq, :, :d_v]
