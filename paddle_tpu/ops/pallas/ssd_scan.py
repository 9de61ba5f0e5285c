"""Mamba-2's state-space scan with a chunk's scores and the float32 state in
VMEM: one forward and one backward kernel a (row, group of heads), from
``x, dt, B, C`` to ``y``.

``nn/functional/state_space.py`` states the scan and its chunked form; this
module computes that form in two kernels whose grid is ``(row, group, tile
of chunks)``. A group's heads share ``B`` and ``C``. A grid step takes
``tile`` chunks of ``CHUNK`` positions of one group: for each chunk the
group's ``C B^T`` once, and for each head the masked decay ``L_ij =
exp(a_j+1 + .. + a_i)`` (the exponent masked, not the result), the scores
``C B^T * L * dt_j``, their product with ``x``, the output from the state
``exp(acs_i) C S^T`` and the chunk's increment ``(exp(last - acs) dt x)^T
B``; then the ``P x N`` float32 state of each head, which lives in a VMEM
scratch over the tiles of a row (the grid's last axis runs in order),
decays and takes the increment. HBM sees ``x``, ``B``, ``C`` in and ``y``
out in the operands' dtype, the cumulative decay and ``dt`` in float32, and
one residual: the state entering each chunk. Nothing of size ``chunk x
chunk`` leaves VMEM. ``D x`` is added to ``y`` in the kernel.

Layout: ``x`` is read as the convolution leaves it, ``(batch, seq, heads x
P)`` (a free reshape), a group's heads side by side in ``heads x P / 128``
lane blocks; at ``P = 64`` two heads fill a block. A block's states are
stacked the same way, head after head along the rows, so that one
``128 x 128`` tile holds the states of the block's heads, the increment of
all of them is one product (``x^T B`` reads the block's lanes as rows) and
the output from the states is one product (``C S^T``). Where heads differ
inside a product (the scores times ``x``), each head's scores are taken
against ``x`` with the other heads' lanes zeroed, and the heads are stacked
along the contraction, so that the MXU adds them. Per-position factors
(``exp(acs)``, ``dt``) come from one product with a constant on the MXU
(``_Layout.spread``: the float32 values as three bf16 terms, which carry all
24 bits) that puts each head's value in its own lanes, or in all lanes; sums
over a head's lanes go back to a vector of positions the same way
(``_Layout.reduce``).

The backward walks the tiles in reverse with the states' cotangent in the
scratch. Everything that does not need that cotangent is computed first, a
chunk on its own; the cotangent's walk over the tile's chunks multiplies
and adds and forms no product; then what reads it. It writes ``dx``,
``dB`` and ``dC`` (summed over the group's heads), ``d dt`` and the
cotangent of the cumulative decay once.

Precision is the configuration's (``linear_attention.PRECISION = HIGH``), as
``gated_delta`` asks for it: a float32 operand enters a product as two bf16
terms ``hi + lo`` and the cross terms are kept (``gated_delta._terms``;
``lo x lo`` is dropped). bf16 operands enter as they are.

Forward and backward are staged once a program, each behind one inner
``jax.jit``: ``pallas_call`` names ``ssd_fwd`` and ``ssd_bwd``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import LANES, _NN, _NT, _TN, _terms, tile_chunks

# positions a chunk: what the kernels are built for (the layer's ``chunk``)
CHUNK = 128
# chunks a grid step, unrolled. One layer's scan at (2, 8192, 64, 64) bf16
# on a TPU v5e, forward / forward + backward: 1 chunk 3.57 / 10.27 ms, 2
# 3.48-3.52 / 10.21-10.30, 4 3.46 / 9.72, where the backward asks for 17.1 MB
# of Mosaic's 16 MB of scoped VMEM; XLA's chunked path 9.31 / 20.21
TILE = 2
FWD, BWD = "ssd_fwd", "ssd_bwd"

_F32, _BF16 = jnp.float32, jnp.bfloat16


def supported(x_shape, b_shape, dtype, chunk):
    """Whether the kernels take ``x`` ``(batch, seq, heads, P)`` and ``B``
    ``(batch, seq, groups, N)``: ``P`` dividing a 128-lane block and a
    group's heads a whole number of blocks, ``N`` one block, the chunk they
    are built for, bf16 or float32 operands and a sequence whose chunks (the
    last one padded) are a whole number of tiles."""
    _, seq, heads, p = x_shape
    groups, n = b_shape[2:]
    return (heads % groups == 0 and LANES % p == 0
            and heads // groups * p % LANES == 0 and n == LANES
            and chunk == CHUNK
            and jnp.dtype(dtype) in (jnp.dtype(_BF16), jnp.dtype(_F32))
            and tile_chunks(-(-seq // chunk), TILE) is not None)


def _dots(pairs, dims=_NN):
    """``gated_delta._dot`` over several pairs of operands given by their
    terms: the sum of their products in one product, every pair of terms
    but ``lo x lo`` stacked along the contraction (a whole number of
    128-lane blocks), so that the MXU adds them."""
    (ca,), (cb,) = dims[0]
    a, b = zip(*[(x, y) for ta, tb in pairs for i, x in enumerate(ta)
                 for j, y in enumerate(tb) if i + j < 2])
    if len(a) == 1:
        return lax.dot_general(a[0], b[0], dims, preferred_element_type=_F32)
    return lax.dot_general(jnp.concatenate(a, axis=ca),
                           jnp.concatenate(b, axis=cb), dims,
                           preferred_element_type=_F32)


def _exact(x):
    """``x`` float32 as three bf16 terms, which carry all 24 bits: their
    sum is ``x``."""
    terms = []
    for _ in range(3):
        terms.append(x.astype(_BF16))
        x = x - terms[-1].astype(_F32)
    return terms


def _rows(n):
    return slice(n * CHUNK, (n + 1) * CHUNK)


def _block(k):
    return slice(k * LANES, (k + 1) * LANES)


class _Layout:
    """The masks of a chunk and the constants that move per-position
    values between vectors (a head a row, positions along the lanes) and
    factors (positions along the rows, a head's value in its lanes)."""

    def __init__(self, heads, p):
        self.heads, self.p = heads, p
        self.per_block = LANES // p
        self.blocks = heads // self.per_block
        row = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
        col = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
        self.seen = row >= col
        lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        # the head of the block each lane (or row of a state stack) is in
        self.slot = lane // p
        self.slot_row = lax.broadcasted_iota(jnp.int32, (LANES, 1), 0) // p
        self.last = lane == CHUNK - 1
        # rows of a selector: the group's heads, padded to bf16's 16
        self.sel_rows = -(-heads // 16) * 16
        self.spread_rows = -(-3 * heads // 16) * 16
        self._ones = {}

    def ones(self, kind, rows):
        """``(rows, 128)`` bf16, 1 where a selector row takes the lane, made
        once a kernel. ``kind`` is ``("head", h)`` (all lanes are head
        ``h``'s) or ``("block", k)`` (lane ``l`` is head ``k * per_block +
        l // p``'s); row ``h`` takes head ``h``'s lanes."""
        if (kind, rows) not in self._ones:
            h = lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
            lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
            head = kind[1] if kind[0] == "head" \
                else kind[1] * self.per_block + lane // self.p
            self._ones[kind, rows] = jnp.where(h == head, 1.0, 0.0).astype(
                _BF16)
        return self._ones[kind, rows]

    def spread(self, rows, kinds):
        """``rows`` ``(heads, CHUNK)`` float32, a head's values along the
        lanes, as factors ``(CHUNK, 128)`` a kind (``ones``): position
        ``i`` along the rows, each lane holding the value of the head it
        belongs to. One product with a constant of ones, the values as
        three bf16 terms (exact: the sum is the value)."""
        pad = self.spread_rows - 3 * self.heads
        if kinds not in self._ones:
            zeros = [jnp.zeros((pad, LANES), _BF16)] if pad else []
            self._ones[kinds] = jnp.concatenate([
                jnp.concatenate([self.ones(kind, self.heads)] * 3 + zeros,
                                axis=0) for kind in kinds], axis=1)
        terms = _exact(rows)
        if pad:
            terms.append(jnp.zeros((pad, CHUNK), _BF16))
        out = lax.dot_general(jnp.concatenate(terms, axis=0),
                              self._ones[kinds], _TN,
                              preferred_element_type=_F32)
        return [out[:, _block(i)] for i in range(len(kinds))]

    def reduce(self, parts):
        """``sum`` over ``parts`` (``(array (CHUNK, 128) float32, kind)``) of
        each head's lanes: ``(heads, CHUNK)``, a head's sums along the
        lanes. One product with a constant of ones, each array as three
        bf16 terms: sums whose terms cancel keep their digits."""
        arrays, ones = [], []
        for a, kind in parts:
            arrays += _exact(a)
            ones += [self.ones(kind, self.sel_rows)] * 3
        out = lax.dot_general(jnp.concatenate(ones, axis=1),
                              jnp.concatenate(arrays, axis=1), _NT,
                              preferred_element_type=_F32)
        return out[:self.heads]

    def mask(self, x, u):
        """``x`` with every lane but those of the block's head ``u``
        zeroed."""
        return jnp.where(self.slot == u, x, jnp.zeros_like(x))

    def per_row(self, values):
        """A state stack's factor ``(128, 1)``: row ``p'`` takes the value
        of the head it belongs to, from ``values`` ``[(1, 1)]`` a head of
        the block."""
        out = values[-1]
        for u in range(len(values) - 2, -1, -1):
            out = jnp.where(self.slot_row == u, values[u], out)
        return out


class _Chunk:
    """What the forward and the backward both build for a chunk: the
    operands, ``C B^T`` and the factors."""

    def __init__(self, lay, n, x_ref, b_ref, c_ref, acs_ref, dt_ref, d_ref):
        self.n = n
        self.x = [x_ref[0, _rows(n), _block(k)] for k in range(lay.blocks)]
        self.b = b_ref[0, _rows(n), :]
        self.c = c_ref[0, _rows(n), :]
        self.bt, self.ct = _terms(self.b), _terms(self.c)
        self.d = [d_ref[0, :, _block(k)] for k in range(lay.blocks)]
        self.acs = acs_ref[0, 0, 0, n]                # (heads, CHUNK)
        self.dt = dt_ref[0, 0, 0, n]
        self.cb = _dots([(self.ct, self.bt)], _NT)
        blocks = [("block", k) for k in range(lay.blocks)]
        spread = lay.spread(self.acs, tuple(blocks) + tuple(
            ("head", h) for h in range(lay.heads)))
        acs_b, self.col = spread[:lay.blocks], spread[lay.blocks:]
        dt_b = lay.spread(self.dt, tuple(blocks))
        # exp(acs_i) (the output from the state) and exp(last - acs_j) dt_j
        # (the increment), in each head's lanes
        self.fe = [jnp.exp(a) for a in acs_b]
        self.expo = [jnp.exp(a[CHUNK - 1:, :] - a) for a in acs_b]
        self.w = [e * d for e, d in zip(self.expo, dt_b)]
        self.dt_b = dt_b
        last = jnp.exp(self.acs[:, CHUNK - 1:])       # (heads, 1)
        self.el = [lay.per_row([last[k * lay.per_block + u:
                                     k * lay.per_block + u + 1]
                                for u in range(lay.per_block)])
                   for k in range(lay.blocks)]
        self.last = last

    def scores(self, lay, h):
        """``(C B^T * L * dt_j, L)`` of head ``h``."""
        decay = jnp.exp(jnp.where(lay.seen,
                                  self.col[h] - self.acs[h:h + 1, :],
                                  -jnp.inf))
        return self.cb * decay * self.dt[h:h + 1, :], decay


def _fwd_kernel(x_ref, b_ref, c_ref, acs_ref, dt_ref, d_ref, y_ref, s_ref,
                state, *, tile, heads, p):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    lay = _Layout(heads, p)
    s = [state[_block(k), :] for k in range(lay.blocks)]
    for n in range(tile):
        c = _Chunk(lay, n, x_ref, b_ref, c_ref, acs_ref, dt_ref, d_ref)
        for k in range(lay.blocks):
            s_ref[0, 0, n, _block(k), :] = s[k]
            heads_k = range(k * lay.per_block, (k + 1) * lay.per_block)
            intra = _dots([(_terms(c.scores(lay, h)[0]),
                            _terms(lay.mask(c.x[k], u)))
                           for u, h in enumerate(heads_k)])
            sp = _terms(s[k])
            x32 = c.x[k].astype(_F32)
            y = intra + c.fe[k] * _dots([(c.ct, sp)], _NT) + x32 * c.d[k]
            y_ref[0, _rows(n), _block(k)] = y.astype(y_ref.dtype)
            s[k] = c.el[k] * s[k] + _dots([(_terms(c.w[k] * x32), c.bt)],
                                          _TN)
    for k in range(lay.blocks):
        state[_block(k), :] = s[k]


def _bwd_kernel(x_ref, b_ref, c_ref, acs_ref, dt_ref, d_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dacs_ref, ddt_ref, dstate, *, tile,
                heads, p):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    lay = _Layout(heads, p)
    chunks = []
    # what a chunk gives on its own: the scores' cotangents, the output
    # from the saved state's, and what its outputs add to the state's
    # cotangent (``q``)
    for n in range(tile):
        c = _Chunk(lay, n, x_ref, b_ref, c_ref, acs_ref, dt_ref, d_ref)
        c.s = [s_ref[0, 0, n, _block(k), :] for k in range(lay.blocks)]
        c.dy = [dy_ref[0, _rows(n), _block(k)] for k in range(lay.blocks)]
        dcb, b1, sums, c.dx, c.q = 0.0, [], [], [], []
        dc = 0.0
        for k in range(lay.blocks):
            xt = _terms(c.x[k])
            scores = []
            for u in range(lay.per_block):
                h = k * lay.per_block + u
                m, decay = c.scores(lay, h)
                scores.append(m)
                dm = _dots([(_terms(lay.mask(c.dy[k], u)), xt)], _NT)
                tc = dm * decay
                dcb = dcb + tc * c.dt[h:h + 1, :]
                tc = tc * c.cb
                b1.append(jnp.sum(tc, axis=0, keepdims=True))
                sums.append((tc * c.dt[h:h + 1, :], ("head", h)))
            c.dx.append(_dots([(_terms(m), _terms(lay.mask(c.dy[k], u)))
                               for u, m in enumerate(scores)], _TN))
            fdy = c.fe[k] * c.dy[k].astype(_F32)
            fdyt, sp = _terms(fdy), _terms(c.s[k])
            ys = _dots([(c.ct, sp)], _NT)
            sums.append((fdy * ys, ("block", k)))
            c.q.append(_dots([(fdyt, c.ct)], _TN))
            dc = dc + _dots([(fdyt, sp)])
        dcbt = _terms(dcb)
        c.dc = dc + _dots([(dcbt, c.bt)])
        c.db = _dots([(dcbt, c.ct)], _TN)
        b1 = jnp.concatenate(b1, axis=0)
        c.dacs = lay.reduce(sums) - c.dt * b1
        c.ddt = b1
        chunks.append(c)
    # the states' cotangent, from the tile's last chunk to its first
    g = [dstate[_block(k), :] for k in range(lay.blocks)]
    for c in reversed(chunks):
        c.g = g
        # d last from the state's decay: exp(last) sum(G * S), a head
        dlast = []
        for k in range(lay.blocks):
            rows = jnp.sum(g[k] * c.s[k], axis=1, keepdims=True)
            for u in range(lay.per_block):
                dlast.append(jnp.sum(jnp.where(lay.slot_row == u, rows, 0.0),
                                     axis=0, keepdims=True))
        c.dlast = jnp.concatenate(dlast, axis=0) * c.last   # (heads, 1)
        g = [c.el[k] * g[k] + c.q[k] for k in range(lay.blocks)]
    for k in range(lay.blocks):
        dstate[_block(k), :] = g[k]
    # what reads the cotangent: the increments' operands
    for c in chunks:
        db = c.db
        us, vs = [], []
        for k in range(lay.blocks):
            gt = _terms(c.g[k])
            dxw = _dots([(c.bt, gt)], _NT)
            x32 = c.x[k].astype(_F32)
            dx = c.dx[k] + c.w[k] * dxw + c.d[k] * c.dy[k].astype(_F32)
            dx_ref[0, _rows(c.n), _block(k)] = dx.astype(dx_ref.dtype)
            db = db + _dots([(_terms(c.w[k] * x32), gt)])
            u = x32 * dxw * c.expo[k]
            us.append((u, ("block", k)))
            vs.append((u * c.dt_b[k], ("block", k)))
        db_ref[0, _rows(c.n), :] = db.astype(db_ref.dtype)
        dc_ref[0, _rows(c.n), :] = c.dc.astype(dc_ref.dtype)
        v = lay.reduce(vs)
        dlast = c.dlast + jnp.sum(v, axis=1, keepdims=True)
        dacs_ref[0, 0, 0, c.n] = c.dacs - v + jnp.where(lay.last, dlast, 0.0)
        ddt_ref[0, 0, 0, c.n] = c.ddt + lay.reduce(us)


def _call(name, x, b, c, acs, dt, d, heads, p, tile, interpret, states=None,
          dy=None):
    """Forward (``ssd_fwd``: ``y`` and the state entering each chunk) or
    backward (``ssd_bwd``: ``dx, dB, dC, dacs, ddt``). ``x`` (and ``dy``)
    ``(batch, seq, all heads x P)``, ``b``, ``c`` ``(batch, seq, groups x
    128)``, ``acs`` and ``dt`` ``(batch, groups, tiles, tile, heads,
    CHUNK)`` float32, ``d`` ``(groups, 1, heads x P)`` float32, ``states``
    ``(batch, groups, chunks, heads x P, 128)`` float32; ``heads`` a
    group's."""
    backward = name == BWD
    rows_, groups, tiles = acs.shape[:3]
    seq = x.shape[1]
    width = heads * p
    at = (lambda t: tiles - 1 - t) if backward else (lambda t: t)
    xs = pl.BlockSpec((1, tile * CHUNK, width), lambda i, j, t: (i, at(t), j))
    bs = pl.BlockSpec((1, tile * CHUNK, LANES), lambda i, j, t: (i, at(t), j))
    vec = pl.BlockSpec((1, 1, 1, tile, heads, CHUNK),
                       lambda i, j, t: (i, j, at(t), 0, 0, 0))
    ds = pl.BlockSpec((1, 1, width), lambda i, j, t: (j, 0, 0))
    state = pl.BlockSpec((1, 1, tile, width, LANES),
                         lambda i, j, t: (i, j, at(t), 0, 0))
    vec_like = jax.ShapeDtypeStruct(acs.shape, _F32)
    if backward:
        kernel, operands = _bwd_kernel, (x, b, c, acs, dt, d, states, dy)
        in_specs = [xs, bs, bs, vec, vec, ds, state, xs]
        out_specs = [xs, bs, bs, vec, vec]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                     jax.ShapeDtypeStruct(b.shape, b.dtype),
                     jax.ShapeDtypeStruct(c.shape, c.dtype),
                     vec_like, vec_like]
    else:
        kernel, operands = _fwd_kernel, (x, b, c, acs, dt, d)
        in_specs = [xs, bs, bs, vec, vec, ds]
        out_specs = [xs, state]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                     jax.ShapeDtypeStruct(
                         (rows_, groups, seq // CHUNK, width, LANES), _F32)]
    return pl.pallas_call(
        functools.partial(kernel, tile=tile, heads=heads, p=p),
        grid=(rows_, groups, tiles), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((width, LANES), _F32)],
        interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            # the state is carried over a row's tiles
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)


# jitted so that the layers of a model share one staged forward and one
# staged backward (a ``pallas_call`` costs the host a trace and a Mosaic
# lowering at every call site)
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _fwd(x, b, c, acs, dt, d, heads, p, tile, interpret):
    return _call(FWD, x, b, c, acs, dt, d, heads, p, tile, interpret)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _bwd_call(x, b, c, acs, dt, d, states, dy, heads, p, tile, interpret):
    return _call(BWD, x, b, c, acs, dt, d, heads, p, tile, interpret,
                 states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _scan(x, b, c, acs, dt, d, heads, p, tile, interpret):
    return _fwd(x, b, c, acs, dt, d, heads, p, tile, interpret)[0]


def _scan_fwd(x, b, c, acs, dt, d, heads, p, tile, interpret):
    y, states = _fwd(x, b, c, acs, dt, d, heads, p, tile, interpret)
    return y, (x, b, c, acs, dt, d, states)


def _scan_bwd(heads, p, tile, interpret, residuals, dy):
    x, *_, d, _ = residuals
    dx, db, dc, dacs, ddt = _bwd_call(*residuals, dy, heads, p, tile,
                                      interpret)
    # D's lanes: x * dy summed over rows and positions, a lane
    groups = d.shape[0]
    dd = jnp.sum(jnp.reshape(x.astype(_F32) * dy.astype(_F32),
                             (-1, groups, 1, heads * p)), axis=0)
    return dx, db, dc, dacs, ddt, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, A, B, C, D=None, tile=TILE, interpret=False):
    """``F.ssd_scan``'s chunked path through the kernels: ``x`` ``(batch,
    seq, heads, P)``, ``dt`` ``(batch, seq, heads)`` float32, ``A``
    ``(heads,)``, ``B``, ``C`` ``(batch, seq, groups, 128)`` in ``x``'s
    dtype, ``D`` ``(heads,)`` or None; ``y`` in ``x``'s dtype. The caller
    asks ``supported`` first. A sequence that is no whole number of chunks
    is padded with ``dt = 0`` and cut again. The sum of ``dt A`` from a
    chunk's start, and its transpose, stay XLA's. Differentiable in all
    six. ``tile`` is the module's constant for every caller but the tests,
    which take fewer chunks a grid step so that a short row crosses
    tiles."""
    b, seq, heads, p = x.shape
    groups = B.shape[2]
    r = heads // groups
    pad = -seq % CHUNK
    dt = dt.astype(_F32)
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                               * (v.ndim - 2)) for v in (x, dt, B, C))
    chunks = (seq + pad) // CHUNK
    tile = tile_chunks(chunks, tile)
    if tile is None:
        raise ValueError(f"{chunks} chunks are no whole number of tiles")

    def vector(v):
        """``(batch, seq, heads)`` as ``(batch, groups, tiles, tile,
        heads a group, CHUNK)``."""
        v = jnp.reshape(v, (b, chunks // tile, tile, CHUNK, groups, r))
        return jnp.transpose(v, (0, 4, 1, 2, 5, 3))

    acs = jnp.cumsum(vector(dt * A.astype(_F32)), axis=-1)
    d = jnp.zeros((heads,), _F32) if D is None else D.astype(_F32)
    d = jnp.reshape(jnp.repeat(d, p), (groups, 1, r * p))
    y = _scan(jnp.reshape(x, (b, seq + pad, heads * p)),
              jnp.reshape(B, (b, seq + pad, groups * LANES)),
              jnp.reshape(C, (b, seq + pad, groups * LANES)),
              acs, vector(dt), d, r, p, tile, bool(interpret))
    return jnp.reshape(y, (b, seq + pad, heads, p))[:, :seq]
