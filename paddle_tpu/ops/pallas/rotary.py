"""The per-head prologue of q and k as one pass: an optional RMSNorm over
the head width, then the rotary embedding, one read and one write of the
tensor forward and backward.

``rotary(x, cos, sin, half)`` takes ``x`` ``(batch, seq, heads, head_dim)``
and the float32 tables ``(seq, head_dim)`` that ``F.rotary_embedding``
builds (cos on the rotated lanes and 1 past them, sin and 0). The kernel
reads ``x`` as the projection wrote it, ``(batch, seq, heads x head_dim)``
(a free reshape), in blocks of ``(row tile, all heads)``, and writes the
result head-major, ``(batch, heads, seq, head_dim)``, which is how the
flash kernels take a head of width 128 and how XLA therefore lays q and k
out: the transposition rides on the block's DMA and no copy stands between
the two kernels. The backward reads ``dy`` head-major, as the flash
backward wrote it, and writes ``dx`` for the projection.

Between load and store everything is float32 in registers, ``ROWS`` rows
of one head at a time. The rotate-half is ``pltpu.roll`` along the lanes
with the signs and the pass-through lanes folded into the tables::

    out = y * C + roll(y, d - h) * S_lo + roll(y, h) * S_hi

``S_lo`` holds ``-sin`` on lanes ``< h`` and 0 elsewhere, ``S_hi`` ``sin``
on lanes ``h .. 2h - 1``; where ``2h = d`` the two rolls are one. The
tables' block index does not depend on the batch row, the grid's inner
dimension, so a row tile's tables are fetched once. With a norm weight
``y = x * rsqrt(mean(x^2) + eps) * w`` and the value stays float32 into
the rotation. The means over a head's lanes are products with a constant
on the otherwise idle MXU, the float32 value split into ``MEAN_TERMS``
bf16 terms and summed in float32 (``_kernel``'s ``mean``): float32
statistics as ``F.rms_norm`` has them, to 2**-18.

The backward without a norm is the transposed rotation of ``dy``: the same
kernel with the sine tables negated, and no residual. With a norm it reads
``x`` and ``dy``, recomputes the statistics, writes ``dx`` and accumulates
the weight's gradient in a float32 block that stays in VMEM over the whole
grid (so that grid runs in order). Forward and backward are staged once a
program, each behind one inner ``jax.jit`` (as ``flash_attention._fwd``):
``pallas_call`` names ``rope_fwd`` and ``rope_bwd``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
FWD, BWD = "rope_fwd", "rope_bwd"
# rows of one head between a load and its store: 8 float32 registers a
# value. (16 / 32 / 64 rows: the norm's forward over q of sdar-30b-a3b-chat
# took 2.63 / 1.85 / 1.06 ms before the reductions went to the MXU, 0.42 /
# 0.41 after; k's few heads gain from 64, 0.13 -> 0.08 ms; my chip runs,
# PR 32.)
ROWS = 64
# the blocks' double buffers; the compiler's scoped VMEM is 16 MiB on a v5e
VMEM_BUDGET = 8 * 1024 * 1024
MAX_TILE = 1024
# bf16 terms a float32 value is split into for the MXU's row means: two
# carry it to 2**-18 of its magnitude, the rounding a float32 sum over 128
# lanes has itself. A third doubled the backward's time (0.65 -> 1.36 ms
# over q of sdar-30b-a3b-chat; my chip run, PR 32).
MEAN_TERMS = 2


def row_tile(seq, heads, d, itemsize, tensors):
    """Rows a block: the largest power-of-two multiple of ``ROWS`` that
    divides ``seq`` and keeps ``tensors`` double-buffered blocks of all
    heads plus the three tables under ``VMEM_BUDGET``; None where not even
    ``ROWS`` rows do."""
    row_bytes = 2 * (tensors * heads * d * itemsize + 3 * d * 4)
    tile = None
    rows = ROWS
    while rows <= MAX_TILE and seq % rows == 0 \
            and rows * row_bytes <= VMEM_BUDGET:
        tile, rows = rows, rows * 2
    return tile


def supported(shape, dtype, half):
    """Whether the kernels take ``x`` of ``shape`` ``(batch, seq, heads,
    head_dim)``: whole 128-lane blocks a head, at most all lanes rotated, a
    row tile that fits (three tensors in the backward with a norm)."""
    _, seq, heads, d = shape
    return (d % LANES == 0 and 0 < 2 * half <= d
            and jnp.dtype(dtype).itemsize in (2, 4)
            and row_tile(seq, heads, d, jnp.dtype(dtype).itemsize, 3)
            is not None)


def _rotate(y, cos, sins, half, d):
    out = y * cos + pltpu.roll(y, d - half, 1) * sins[0]
    if len(sins) == 2:
        out = out + pltpu.roll(y, half, 1) * sins[1]
    return out


def _kernel(*refs, heads, d, half, epsilon, norm, backward, tile):
    """One block: ``tile`` rows of every head. Forward ``x`` (seq-major)
    -> ``out`` (head-major); backward ``dy`` (head-major) -> ``dx``
    (seq-major), with a norm also ``x`` in and the weight's gradient
    accumulated."""
    n_sin = 1 if 2 * half == d else 2
    x_ref = w_ref = dw_ref = None
    if backward and norm:
        in_ref, x_ref, cos_ref, *rest = refs
    else:
        in_ref, cos_ref, *rest = refs
    sin_refs, rest = rest[:n_sin], rest[n_sin:]
    if norm:
        w_ref, *rest = rest
    out_ref = rest[0]
    if backward and norm:
        dw_ref = rest[1]

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _init():
            dw_ref[:] = jnp.zeros_like(dw_ref)

    def seq_major(ref, j, rows):
        return ref[0, rows, j * d:(j + 1) * d].astype(jnp.float32)

    # 1 / d is a bf16 number where d is a power of two: the matrix then
    # divides as it adds
    folded = d & (d - 1) == 0
    ones = jnp.full((d, d), 1.0 / d if folded else 1.0,
                    jnp.bfloat16) if norm else None

    def mean(v):
        """The mean over a head's lanes, in every lane: on the MXU, which
        has nothing else to do, as ``v @ (1/d)`` with ``v`` split into
        bf16 terms that the products take exactly and the MXU adds in
        float32. (As ``jnp.mean`` over the lanes the kernels were bound by
        the reductions: PERF.md section 6, PR 32.)"""
        total = None
        for _ in range(MEAN_TERMS):
            term = v.astype(jnp.bfloat16)
            v = v - term.astype(jnp.float32)
            part = jnp.dot(term, ones, preferred_element_type=jnp.float32)
            total = part if total is None else total + part
        return total if folded else total * (1.0 / d)

    def chunk(c, dw):
        rows = pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS)
        cos = cos_ref[rows, :]
        sins = [ref[rows, :] for ref in sin_refs]
        w = w_ref[...] if norm else None

        def front(j):
            """Head ``j`` up to its last reduction over the lanes. It is
            written ahead of head ``j - 1``'s store: the reduction's
            latency then hides behind that head's arithmetic, where
            behind its own it cost about 100 cycles a head (the norm's
            forward over q of sdar-30b-a3b-chat 1.85 -> 1.02 ms; my chip
            run, PR 32)."""
            if not backward:
                y = seq_major(in_ref, j, rows)
                if not norm:
                    return y, None
                return y, jax.lax.rsqrt(mean(y * y) + epsilon)
            # the tables' sines come negated: the transposed rotation
            g = _rotate(in_ref[0, j, rows, :].astype(jnp.float32), cos, sins,
                        half, d)
            if not norm:
                return g, None
            x = seq_major(x_ref, j, rows)
            r = jax.lax.rsqrt(mean(x * x) + epsilon)
            xhat = x * r
            gw = g * w
            return gw, (xhat, r, mean(gw * xhat), g * xhat)

        ahead = front(0)
        for j in range(heads):
            value, stats = ahead
            if j + 1 < heads:
                ahead = front(j + 1)
            if not backward:
                if norm:
                    value = value * stats * w
                out_ref[0, j, rows, :] = _rotate(
                    value, cos, sins, half, d).astype(out_ref.dtype)
                continue
            if norm:
                xhat, r, m, dw_j = stats
                dw = dw + dw_j
                value = r * (value - xhat * m)
            out_ref[0, rows, j * d:(j + 1) * d] = value.astype(out_ref.dtype)
        return dw

    dw = jax.lax.fori_loop(0, tile // ROWS, chunk,
                           jnp.zeros((ROWS, d), jnp.float32))
    if dw_ref is not None:
        dw_ref[:] += dw


def _sine_tables(sin, half, d, sign):
    """``(S_lo, S_hi)`` from the formula's ``sin`` table (sin on the
    rotated lanes, 0 past them), or their sum where one roll does both."""
    lane = jnp.arange(d)
    lo = jnp.where(lane < half, -sign * sin, 0.0)
    hi = jnp.where(lane >= half, sign * sin, 0.0)
    return [lo + hi] if 2 * half == d else [lo, hi]


def _call(name, x, cos, sin, weight, residual, half, epsilon, interpret):
    """Forward (``rope_fwd``: ``x`` ``(b, s, heads, d)``, read seq-major,
    the result head-major ``(b, heads, s, d)``) or backward (``rope_bwd``:
    ``x`` is ``dy`` head-major, the result ``dx`` ``(b, s, heads, d)``
    written seq-major, and with a norm ``residual`` is the forward's ``x``
    and the second result the weight's gradient, ``(d,)`` float32)."""
    backward, norm = name == BWD, weight is not None
    with_dw = backward and norm
    if backward:
        b, heads, seq, d = x.shape
    else:
        b, seq, heads, d = x.shape
        x = jnp.reshape(x, (b, seq, heads * d))
    tile = row_tile(seq, heads, d, x.dtype.itemsize, 3 if with_dw else 2)
    head_major = pl.BlockSpec((1, heads, tile, d), lambda i, n: (n, 0, i, 0))
    seq_major = pl.BlockSpec((1, tile, heads * d), lambda i, n: (n, i, 0))
    table = pl.BlockSpec((tile, d), lambda i, n: (i, 0))

    def whole(rows):
        return pl.BlockSpec((rows, d), lambda i, n: (0, 0))

    sins = _sine_tables(sin, half, d, -1.0 if backward else 1.0)
    operands, in_specs = [x], [head_major if backward else seq_major]
    if with_dw:
        operands.append(jnp.reshape(residual, (b, seq, heads * d)))
        in_specs.append(seq_major)
    operands += [cos] + sins
    in_specs += [table] * (1 + len(sins))
    if norm:
        operands.append(jnp.reshape(weight.astype(jnp.float32), (1, d)))
        in_specs.append(whole(1))
    if backward:
        out_specs = [seq_major]
        out_shape = [jax.ShapeDtypeStruct((b, seq, heads * d), x.dtype)]
    else:
        out_specs = [head_major]
        out_shape = [jax.ShapeDtypeStruct((b, heads, seq, d), x.dtype)]
    if with_dw:
        out_specs.append(whole(ROWS))
        out_shape.append(jax.ShapeDtypeStruct((ROWS, d), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, d=d, half=half,
                          epsilon=epsilon, norm=norm, backward=backward,
                          tile=tile),
        grid=(seq // tile, b), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            # the weight's gradient is one block over the whole grid
            dimension_semantics=("arbitrary" if with_dw else "parallel",)
            * 2))(*operands)
    if backward:
        dx = jnp.reshape(out[0], (b, seq, heads, d))
        return dx, (jnp.sum(out[1], axis=0) if with_dw else None)
    return out[0]


# jitted so that the layers of a model share one staged forward and one
# staged backward (PERF.md section 6, PR 28: a ``pallas_call`` costs the
# host a trace and a Mosaic lowering at every call site)
@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _fwd(x, cos, sin, weight, half, epsilon, interpret):
    return jnp.swapaxes(
        _call(FWD, x, cos, sin, weight, None, half, epsilon, interpret), 1, 2)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _bwd_call(dy, x, cos, sin, weight, half, epsilon, interpret):
    return _call(BWD, jnp.swapaxes(dy, 1, 2), cos, sin, weight, x, half,
                 epsilon, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _rotary(x, cos, sin, weight, half, epsilon, interpret):
    return _fwd(x, cos, sin, weight, half, epsilon, interpret)


def _rotary_fwd(x, cos, sin, weight, half, epsilon, interpret):
    out = _fwd(x, cos, sin, weight, half, epsilon, interpret)
    # without a norm the rotation's transpose needs the tables alone
    return out, (None if weight is None else x, cos, sin, weight)


def _rotary_bwd(half, epsilon, interpret, residuals, dy):
    x, cos, sin, weight = residuals
    dx, dw = _bwd_call(dy, x, cos, sin, weight, half, epsilon, interpret)
    if weight is not None:
        dw = dw.astype(weight.dtype)
    # the tables hang on positions and constants: nothing reads these
    return dx, jnp.zeros_like(cos), jnp.zeros_like(sin), dw


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def rotary(x, cos, sin, half, weight=None, epsilon=1e-6, interpret=False):
    """``x`` ``(batch, seq, heads, head_dim)``, RMS-normalised over the
    head width and scaled by ``weight`` ``(head_dim,)`` where one is given,
    then rotated: ``y * cos + rotate_half(y) * sin`` over the first ``2 *
    half`` lanes with the float32 tables ``cos`` and ``sin`` ``(seq,
    head_dim)``. The caller asks ``supported`` first. Differentiable in
    ``x`` and ``weight``."""
    return _rotary(x, cos, sin, weight, int(half), float(epsilon),
                   bool(interpret))
