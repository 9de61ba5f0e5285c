"""Grouped matrix products over rows sorted by group, in tiles the widths
allow: ``gmm`` (rows ``(M, K)`` times their group's ``(K, N)``, or its
transpose) and ``tgmm`` (each group's ``(K, N)`` from its rows and their
cotangent), under one ``custom_vjp``.

The TPU compiler builds ``jax.lax.ragged_dot`` and its VJP from kernels
that tile ``K`` and ``N`` each by the largest of 512, 256 and 128 that
divides the width. At 2,688 (21 x 128) and 1,856 (14.5 x 128) that is 128
x 128 in every product and gradient: a 512-row tile of a product walks 21
x 15 grid steps of 85 ns of MXU time each, and the products are bound by
the grid's cost a step, not by the MXU (PERF.md section 7). Here ``K`` and
``N`` are each taken whole or in a multiple of 128 that divides them, the
fewest grid steps that fit the VMEM budget; a width no 128 divides is
taken whole, which is a block Mosaic accepts at any width, so nothing is
padded.

The grid walks only the (group, row tile) pairs that ``group_sizes`` makes
live, from tables handed to the kernels as scalar prefetch (as jax's
megablox does): a row tile two groups share is visited once by each, and
each writes its own rows. ``gmm``'s grid is ``(n tile, step, k tile)``:
with one ``k`` tile the weight block stays in VMEM over a group's row
tiles. Rows past ``sum(group_sizes)`` belong to no group: ``gmm`` leaves
them unwritten and ``tgmm`` selects them away, so whatever they hold, NaN
included, reaches nothing; the caller masks them in both directions.
``tgmm``'s grid is ``(n tile, k tile, step)``: the steps of a group add
into a float32 scratch, the group's block is written when its last step
ends, and an empty group is visited once and writes zeros.

Both accumulate in float32 and write the operands' dtype, as the
compiler's kernels do. Each is staged once a program behind one inner
``jax.jit``: ``pallas_call`` names ``gmm`` and ``tgmm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows a tile. One Nemotron expert layer (12,288 rows, 6,144 held over 8
# groups, 2,688 -> 1,856 -> 2,688) on a TPU v5e, forward / forward +
# backward: 128 rows 2.00 / 5.39 ms, 256 1.97-2.02 / 5.34, 512 2.19 /
# 5.79, 1,024 2.60 / 6.99; the compiler's ragged_dot 5.96 / 21.18 (PERF.md
# section 6)
ROW_TILE = 256
# the blocks' scoped VMEM, double-buffered operands and float32
# accumulator and product (``_vmem``), and the limit the kernels ask
# Mosaic for
VMEM_BUDGET = 40 * 2 ** 20
VMEM_LIMIT = 64 * 2 ** 20
GMM, TGMM = "gmm", "tgmm"

_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def compiler_tile(width):
    """The tile the compiler's ``ragged_dot`` kernels give a width: the
    largest of 512, 256 and 128 that divides it, 0 for none."""
    return next((t for t in (512, 256, 128) if width % t == 0), 0)


def supported(k, n, dtype):
    """Whether a layer's grouped products of widths ``k`` and ``n`` should
    take the kernels: bf16 operands, and a width where the compiler's
    kernels fall under 256-wide tiles (or a width no 128 divides)."""
    return (jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and min(compiler_tile(k), compiler_tile(n)) < 256)


def _widths(w):
    """A width's tiles, largest first: the whole, then the multiples of
    128 that divide it."""
    return [w] + [t for t in range(w // LANES * LANES, 0, -LANES)
                  if w % t == 0 and t < w]


def _vmem(kind, tm, tk, tn, itemsize):
    """Scoped VMEM a kernel's blocks ask for: each operand's and the
    result's block twice (the pipeline's two buffers), the float32
    accumulator and the float32 product."""
    blocks = tm * tk + tm * tn + tk * tn
    acc = tm * tn if kind == GMM else tk * tn
    return 2 * itemsize * blocks + 2 * 4 * acc


def tiling(kind, tm, k, n, itemsize):
    """``(tk, tn)`` for a kernel: the fewest grid steps a row tile whose
    blocks fit ``VMEM_BUDGET``, and of those the widest ``tk`` (one ``k``
    tile holds the weight block over a group's row tiles)."""
    fits = [(tk, tn) for tk in _widths(k) for tn in _widths(n)
            if _vmem(kind, tm, tk, tn, itemsize) <= VMEM_BUDGET]
    if not fits:
        raise ValueError(f"{kind}: no tiles of ({k}, {n}) fit "
                         f"{VMEM_BUDGET} bytes at {tm} rows")
    return min(fits, key=lambda t: ((k // t[0]) * (n // t[1]), -t[0]))


def schedule(group_sizes, rows, tm, visit_empty):
    """The grid's tables: ``(offsets, group, tile)`` and the number of
    steps. ``offsets`` ``(groups + 1,)`` are the groups' first rows and
    the end of the last; step ``i`` works on group ``group[i]`` in row
    tile ``tile[i]``. A group takes every row tile it has a row in;
    ``visit_empty`` gives an empty group one step."""
    groups = group_sizes.shape[0]
    tiles = rows // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    count = jnp.where(group_sizes > 0, -(-ends // tm) - starts // tm,
                      int(visit_empty)).astype(jnp.int32)
    # at most every tile once and a shared tile once more a group boundary
    length = tiles + groups - 1
    group = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), count,
                       total_repeat_length=length)
    before = jnp.cumsum(count) - count
    tile = starts[group] // tm + jnp.arange(length, dtype=jnp.int32) \
        - before[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               ends.astype(jnp.int32)])
    return ((offsets, group, jnp.clip(tile, 0, tiles - 1).astype(jnp.int32)),
            jnp.sum(count))


def _mine(offsets, group, tile, step, tm):
    """``(tm, 1)``: the rows of step ``step``'s tile its group holds."""
    g = group[step]
    row = tile[step] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _gmm_kernel(offsets, group, tile, x_ref, w_ref, out_ref, acc, *, tm,
                dims):
    step, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += lax.dot_general(x_ref[...], w_ref[...], dims,
                                preferred_element_type=_F32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        # a tile another group shares keeps that group's rows
        out_ref[...] = jnp.where(_mine(offsets, group, tile, step, tm),
                                 acc[...], out_ref[...].astype(_F32)
                                 ).astype(out_ref.dtype)


def _tgmm_kernel(offsets, group, tile, x_ref, dy_ref, out_ref, acc, *, tm):
    step, last = pl.program_id(2), pl.num_programs(2) - 1
    g = group[step]

    @pl.when((step == 0) | (group[jnp.maximum(step - 1, 0)] != g))
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(offsets[g + 1] > offsets[g])
    def _add():
        mine = _mine(offsets, group, tile, step, tm)
        x, dy = x_ref[...], dy_ref[...]
        acc[...] += lax.dot_general(
            jnp.where(mine, x, jnp.zeros_like(x)),
            jnp.where(mine, dy, jnp.zeros_like(dy)), _TN,
            preferred_element_type=_F32)

    @pl.when((step == last) | (group[jnp.minimum(step + 1, last)] != g))
    def _store():
        out_ref[...] = acc[...].astype(out_ref.dtype)


# jitted so that the layers of a model share one staged call a shape (a
# ``pallas_call`` costs the host a trace and a Mosaic lowering at every call
# site)
@functools.partial(jax.jit, static_argnames=("tm", "transpose", "interpret"))
def _gmm(x, w, group_sizes, tm, transpose, interpret):
    m, k = x.shape
    n = w.shape[1] if transpose else w.shape[2]
    tk, tn = tiling(GMM, tm, k, n, x.dtype.itemsize)
    tables, steps = schedule(group_sizes, m, tm, visit_empty=False)
    if transpose:
        w_spec = pl.BlockSpec((None, tn, tk),
                              lambda j, i, l, o, g, t: (g[i], j, l))
    else:
        w_spec = pl.BlockSpec((None, tk, tn),
                              lambda j, i, l, o, g, t: (g[i], l, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, dims=_NT if transpose else _NN),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, steps, k // tk),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda j, i, l, o, g, t: (t[i], l)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, l, o, g, t: (t[i], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), _F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=GMM,
    )(*tables, x, w)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _tgmm(x, dy, group_sizes, tm, interpret):
    m, k = x.shape
    n = dy.shape[1]
    tk, tn = tiling(TGMM, tm, k, n, x.dtype.itemsize)
    tables, steps = schedule(group_sizes, m, tm, visit_empty=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((group_sizes.shape[0], k, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, steps),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda j, l, i, o, g, t: (t[i], l)),
                      pl.BlockSpec((tm, tn),
                                   lambda j, l, i, o, g, t: (t[i], j))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda j, l, i, o, g, t: (g[i], l, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), _F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=TGMM,
    )(*tables, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(x, w, group_sizes, tm, interpret):
    return _gmm(x, w, group_sizes, tm, False, interpret)


def _grouped_fwd(x, w, group_sizes, tm, interpret):
    return _gmm(x, w, group_sizes, tm, False, interpret), (x, w, group_sizes)


def _grouped_bwd(tm, interpret, residuals, dy):
    x, w, group_sizes = residuals
    dx = _gmm(dy, w, group_sizes, tm, True, interpret)
    dw = _tgmm(x, dy, group_sizes, tm, interpret).astype(w.dtype)
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, group_sizes, tm=ROW_TILE, interpret=False):
    """``lax.ragged_dot(x, w, group_sizes)`` through the kernels: ``x``
    ``(M, K)`` sorted by group, ``w`` ``(groups, K, N)`` in ``x``'s dtype,
    ``group_sizes`` ``(groups,)`` int32 summing to at most ``M``; ``(M,
    N)`` in ``x``'s dtype, its rows past the last group unwritten.
    Differentiable in ``x`` and ``w``. Rows that are no whole number of
    ``tm``-row tiles are padded and cut again. ``tm`` is the module's
    constant for every caller but the tests, which take small tiles so
    that a toy row count crosses several."""
    m = x.shape[0]
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = _grouped(x, w, group_sizes.astype(jnp.int32), tm, bool(interpret))
    return out[:m] if pad else out
