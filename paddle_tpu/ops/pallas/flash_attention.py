"""Flash attention — Pallas TPU kernel.

Replaces the reference's fused attention CUDA kernels
(operators/fused/multihead_matmul_op.cu, math/bert_encoder_functor.cu), which
materialize the full S×S probability matrix (O(S²) HBM). This kernel is
blockwise-online-softmax: O(S) memory, MXU matmuls with fp32 accumulators,
causal block skipping. Forward + custom-VJP backward (dq and dk/dv passes) so
long-context training works end-to-end. The nine products multiply in the
dtype q/k/v arrive in: bf16 operands reach the MXU as bf16, its packed
format; ``p`` and ``ds`` are cast to that dtype where they enter a product;
the scores, the softmax statistics and the accumulators are float32 whatever
the operands are, and a float32 caller keeps float32 products. (An upcast
operand makes Mosaic issue the product in the MXU's f32 format, twice the
pushes and latches; at the default precision the chip rounds such operands
to bf16 in one pass, so the values were the same, and neither form sets the
kernels' time: PERF.md section 6, PR 26.)

Round-3 widening (verdict item 5):
- ragged tails: inputs are zero-padded to lane multiples and the padded key
  columns are masked in-kernel (padded query rows are harmless: their dout
  is zero, their outputs are sliced off, and their lse is pinned to 0 so
  the backward sees p = exp(-inf - 0) = 0);
- key-padding masks: per-batch valid KV lengths (``kv_lens``) mask columns
  >= len — the O(B) encoding of the (B,1,1,T) boolean padding mask, so real
  pretraining batches stay on the O(S) kernel;
- dropout: applied INSIDE the kernel with the TPU PRNG, seeded per
  (batch·head, q-block, k-block) so the backward regenerates bit-identical
  masks. Math: out = (m∘p)V with m = bernoulli/keep; then
  dv = (m∘p)ᵀdo, and ds = p∘(m∘dp − δ) where δ = do·out already
  absorbs the dropped normalizer term.

Grouped KV heads and a causal window (ISSUE 27): ``k``/``v`` may carry
fewer heads than ``q`` (``heads % kv_heads == 0``); query head ``i`` reads
KV head ``i // group`` through the K/V block index maps, and the dk/dv
kernel, whose grid runs over KV heads, walks the group's query heads in
its inner dimension, so no repeated K/V and no per-query-head dk/dv ever
reach HBM. ``window=w`` (causal only) lets query ``i`` see key ``j`` iff
``0 <= i - j < w``: the kernels mask by it, and the grid's inner dimension
runs over the band of blocks a window leaves (first block from the index
map) instead of over all of them, so blocks wholly outside the window cost
neither a grid step nor a DMA. Without a window and with equal head counts
the staged kernels are what they were.

Trace names: each ``pallas_call`` carries ``name=`` (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``). That names the kernel's op in a
device trace and stages it under a ``jax.named_scope`` of the same string
(``.../attn/sdpa/flash/flash_bwd_dq/pallas_call`` in the op's ``tf_op``),
so a reader finds the kernels whatever their operands are.

TPU layout notes: per-row stats (m, l, lse, delta) are carried at LANE=8
width (last dim equal to the array dim satisfies Mosaic's tiling rule);
VMEM scratch uses full (block, 128) tiles.

Public API: flash_attention(q, k, v, causal=False, sm_scale=None,
kv_lens=None, dropout_rate=0.0, dropout_seed=None, window=None)
with q: (batch, seq, heads, head_dim), k/v: (batch, seq, kv_heads, head_dim).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on a v5e at the cells' shape (PERF.md section 6, PR 26: bf16
# (16, 1024, 12, 64), causal, forward + backward of one layer, device ms of
# every op): (128, 128) 18.34, (256, 256) 8.90, (256, 512) 6.14,
# (512, 512) 5.01, (256, 1024) 4.90, (512, 1024) 4.15, (1024, 1024) 3.88.
# Nothing inside a tile moves those times (operand dtype, masks, exp):
# they follow the grid steps (about 0.26 us each) and the bytes the blocks
# bring from HBM in its padded tiles (head width 64 in 128 lanes, the
# 8-lane statistics in 128), K/V fetched again for each q block. So larger
# blocks are faster, and the tuning DB's row for bf16, width 64 and 513 to
# 1,024 positions says (1024, 1024): one tile a head. These defaults serve
# the calls no row covers; no run on record has measured them there.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
LANES = 128
STAT_LANES = 8
NEG_INF = -1e30
# The pallas_calls' name=: what a staged program and a device trace call
# the three kernels. The tuner, the analyzer's pallas-config-untuned rule
# and chip_smoke.py find them by these.
FWD, BWD_DQ, BWD_DKV = KERNEL_NAMES = (
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _scores(q, k, sm_scale):
    """sm_scale * q k^T in float32. The operands go to the MXU in the dtype
    they arrive in (a bf16 x bf16 product is exact in float32); the scale
    is applied to the float32 scores, never to a q rounded back to bf16."""
    return sm_scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _causal_mask(s, iq, ik, block_q, block_k, window=None):
    """Keep key ``col`` for query ``row`` iff ``0 <= row - col`` and, with
    a window, ``row - col < window``."""
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = rows >= cols
    if window is not None:
        keep = keep & (rows - cols < window)
    return jnp.where(keep, s, NEG_INF)


def _floor(x, lo):
    return max(x, lo) if isinstance(x, int) else jnp.maximum(x, lo)


def _cap(x, hi):
    return min(x, hi) if isinstance(x, int) else jnp.minimum(x, hi)


class _Band:
    """Which blocks of the other axis a block needs under a causal window.

    ``k_first(iq)``..``k_last(iq)`` are the key blocks q block ``iq`` sees
    (columns ``iq*bq - window + 1 .. (iq+1)*bq - 1``), ``q_first(ik)``..
    ``q_last(ik)`` the q blocks that see key block ``ik`` (rows ``ik*bk ..
    (ik+1)*bk + window - 2``). They take a Python int (for the static step
    counts ``k_steps`` and ``q_steps``, the widest band of any block) or a
    traced scalar (index maps, kernels) alike."""

    def __init__(self, window, block_q, block_k, nq, nk):
        self.window, self.bq, self.bk = window, block_q, block_k
        self.nq, self.nk = nq, nk
        self.k_steps = max(self.k_last(i) - self.k_first(i) + 1
                           for i in range(nq))
        self.q_steps = max(self.q_last(j) - self.q_first(j) + 1
                           for j in range(nk))

    def k_first(self, iq):
        return _floor(iq * self.bq - (self.window - 1), 0) // self.bk

    def k_last(self, iq):
        return _cap(((iq + 1) * self.bq - 1) // self.bk, self.nk - 1)

    def q_first(self, ik):
        return (ik * self.bk) // self.bq

    def q_last(self, ik):
        return _cap(((ik + 1) * self.bk + self.window - 2) // self.bq,
                    self.nq - 1)


def _kv_mask(s, ik, block_k, kv_len):
    """Mask key columns >= kv_len (padding tail or per-batch padding)."""
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    return jnp.where(cols < kv_len, s, NEG_INF)


def _dropout_mask(shape, rate, seed, b, iq, ik):
    """Deterministic per-block inverted-dropout multiplier in {0, 1/keep}.

    Counter-based hash PRNG (murmur3-style finalizer over
    (seed, block ids, element coords)) built from plain integer ops — the
    SAME bits on the CPU interpreter and on TPU, and trivially regenerated
    by the backward kernels (pltpu.prng_* has no CPU-interpret lowering)."""
    u32 = jnp.uint32

    def _u(x):
        # seed/block ids are non-negative int32: plain conversion is exact
        # (Mosaic cannot bitcast scalars)
        return jnp.asarray(x).astype(u32)

    rows = jax.lax.broadcasted_iota(u32, shape, 0)
    cols = jax.lax.broadcasted_iota(u32, shape, 1)
    h = (_u(seed) * u32(2654435761)
         ^ _u(b) * u32(0x9E3779B1)
         ^ _u(iq) * u32(0x85EBCA77)
         ^ _u(ik) * u32(0xC2B2AE3D))
    h = h ^ (rows * u32(0x27D4EB2F)) ^ (cols + u32(0x165667B1))
    h = h ^ jax.lax.shift_right_logical(h, u32(16))
    h = h * u32(0x85EBCA6B)
    h = h ^ jax.lax.shift_right_logical(h, u32(13))
    h = h * u32(0xC2B2AE35)
    h = h ^ jax.lax.shift_right_logical(h, u32(16))
    thresh = u32(int(min(rate, 1.0) * 4294967295.0))
    keep = h >= thresh
    return jnp.where(keep, 1.0 / (1.0 - rate), 0.0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(lens_ref, seed_ref,       # (1,STAT) i32, (1,STAT) i32
                q_ref, k_ref, v_ref,      # (1,Bq,D), (1,Bk,D), (1,Bk,D)
                o_ref, lse_ref,           # (1,Bq,D), (1,Bq,STAT_LANES)
                m_scr, l_scr, acc_scr,    # (Bq,LANES),(Bq,LANES),(Bq,D)
                *, sm_scale, causal, block_q, block_k, num_k_blocks,
                use_kv_mask, dropout_rate, band=None):
    b = pl.program_id(0)
    iq = pl.program_id(1)
    step = pl.program_id(2)
    # under a window the inner dimension walks the band's key blocks only
    ik = step if band is None else band.k_first(iq) + step
    window = None if band is None else band.window

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if band is not None:
        run = ik <= band.k_last(iq)
    else:
        run = (ik * block_k < (iq + 1) * block_q) if causal else True

    @pl.when(run)
    def _compute():
        s = _scores(q_ref[0], k_ref[0], sm_scale)
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, window)
        if use_kv_mask:
            s = _kv_mask(s, ik, block_k, lens_ref[b])
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if causal or use_kv_mask:
            # NEG_INF is finite, so a FULLY-masked row has m_new == s and
            # p == exp(0) == 1 — zero masked entries explicitly so l is 0
            # for such rows (out = 0, lse pinned to 0, no K/V grad leak)
            p = p * (s > NEG_INF * 0.5)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            # normalizer l uses the UNdropped p (softmax semantics); only
            # the value accumulation is dropped
            p = p * _dropout_mask(p.shape, dropout_rate, seed_ref[0],
                                  b, iq, ik)
        v = v_ref[0]
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(step == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # fully-masked rows (l == 0, e.g. padded queries) pin lse to 0 so
        # the backward's p = exp(NEG_INF - lse) is 0, not NaN
        lse = jnp.where(l == 0.0, 0.0, m_scr[:, :1] + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse, (block_q, STAT_LANES))


def _kv_index_maps(group, band):
    """The K/V block index map of the two kernels whose grid is (q heads,
    q blocks, key steps). Query head ``b`` reads KV head ``b // group``;
    under a window step ``j`` is key block ``k_first(i) + j``, held at the
    band's last block once past it so that the skipped steps fetch nothing
    new. Ungrouped and unwindowed it is the plain ``(b, j, 0)``."""
    def head(b):
        return b if group == 1 else b // group

    if band is None:
        return lambda b, i, j: (head(b), j, 0)
    return lambda b, i, j: (
        head(b), jnp.minimum(band.k_first(i) + j, band.k_last(i)), 0)


def _fwd(q, k, v, lens, seed, sm_scale, causal, block_q, block_k,
         use_kv_mask, dropout_rate, interpret=False, window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    group = bh // k.shape[0]
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    band = None if window is None else _Band(window, block_q, block_k, nq, nk)
    steps = nk if band is None else band.k_steps
    kv_map = _kv_index_maps(group, band)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=steps, use_kv_mask=use_kv_mask,
        dropout_rate=dropout_rate, band=band)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, STAT_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name=FWD,
    )(lens, seed, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr,
                   *, sm_scale, causal, block_q, block_k, num_k_blocks,
                   use_kv_mask, dropout_rate, band=None):
    b = pl.program_id(0)
    iq = pl.program_id(1)
    step = pl.program_id(2)
    ik = step if band is None else band.k_first(iq) + step
    window = None if band is None else band.window

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    if band is not None:
        run = ik <= band.k_last(iq)
    else:
        run = (ik * block_k < (iq + 1) * block_q) if causal else True

    @pl.when(run)
    def _compute():
        k = k_ref[0]
        s = _scores(q_ref[0], k, sm_scale)
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, window)
        if use_kv_mask:
            s = _kv_mask(s, ik, block_k, lens_ref[b])
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = dp * _dropout_mask(dp.shape, dropout_rate, seed_ref[0],
                                    b, iq, ik)
        ds = p * (dp - delta_ref[0][:, :1])
        dq_scr[:] += sm_scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale, causal, block_q, block_k, num_q_blocks,
                    use_kv_mask, dropout_rate, group=1, band=None):
    # the grid's first dimension is the KV head; the inner one walks the
    # group's query heads, and for each the q blocks (all of them, or the
    # band a window leaves): ``num_q_blocks`` steps a query head
    b = pl.program_id(0)
    ik = pl.program_id(1)
    step = pl.program_id(2)
    jq = step
    if group != 1:
        b = b * group + step // num_q_blocks
        jq = step % num_q_blocks
    iq = jq if band is None else band.q_first(ik) + jq
    window = None if band is None else band.window

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if band is not None:
        run = iq <= band.q_last(ik)
    else:
        run = ((iq + 1) * block_q > ik * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        s = _scores(q, k_ref[0], sm_scale)
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, window)
        if use_kv_mask:
            s = _kv_mask(s, ik, block_k, lens_ref[b])
        p = jnp.exp(s - lse_ref[0][:, :1])          # (Bq, Bk)
        if dropout_rate > 0.0:
            m = _dropout_mask(p.shape, dropout_rate, seed_ref[0],
                              b, iq, ik)
            p_drop = p * m
        else:
            m = None
            p_drop = p
        do = do_ref[0]                              # (Bq, D)
        dv_scr[:] += jax.lax.dot_general(p_drop.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if m is not None:
            dp = dp * m
        ds = p * (dp - delta_ref[0][:, :1])         # (Bq, Bk)
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == group * num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, use_kv_mask, dropout_rate,
         interpret, window, res, do):
    q, k, v, lens, seed, out, lse = res
    bh, sq, d = q.shape
    bkv, sk = k.shape[0], k.shape[1]
    group = bh // bkv
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    band = None if window is None else _Band(window, block_q, block_k, nq, nk)
    k_steps = nk if band is None else band.k_steps
    q_steps = nq if band is None else band.q_steps
    kv_map = _kv_index_maps(group, band)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)               # (bh, sq, 1)
    delta = jnp.broadcast_to(delta, (bh, sq, STAT_LANES))

    lens_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    stat_spec = pl.BlockSpec((1, block_q, STAT_LANES), lambda b, i, j: (b, i, 0))

    # the dk/dv kernel's view of what lives per query head (q, do, lse,
    # delta): KV head ``b``, key block ``j``, inner step ``t``
    if group == 1 and band is None:
        def q_map(b, j, t):
            return (b, t, 0)
    else:
        def q_map(b, j, t):
            jq = t if group == 1 else t % q_steps
            head = b if group == 1 else b * group + t // q_steps
            if band is not None:
                jq = jnp.minimum(band.q_first(j) + jq, band.q_last(j))
            return (head, jq, 0)
    stat_spec_kv = pl.BlockSpec((1, block_q, STAT_LANES), q_map)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_k_blocks=k_steps, use_kv_mask=use_kv_mask,
                          dropout_rate=dropout_rate, band=band),
        grid=(bh, nq, k_steps),
        in_specs=[
            lens_spec,
            seed_spec,
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            stat_spec,
            stat_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=BWD_DQ,
    )(lens, seed, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_q_blocks=q_steps, use_kv_mask=use_kv_mask,
                          dropout_rate=dropout_rate, group=group, band=band),
        grid=(bkv, nk, group * q_steps),
        in_specs=[
            lens_spec,
            seed_spec,
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            stat_spec_kv,
            stat_spec_kv,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name=BWD_DKV,
    )(lens, seed, q, k, v, do, lse, delta)
    # int-array inputs (lens, seed) take float0 cotangents
    return (dq, dk, dv, np.zeros(lens.shape, jax.dtypes.float0),
            np.zeros(seed.shape, jax.dtypes.float0))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_bhsd(q, k, v, lens, seed, sm_scale, causal, block_q, block_k,
                use_kv_mask, dropout_rate, interpret, window):
    out, _ = _fwd(q, k, v, lens, seed, sm_scale, causal, block_q, block_k,
                  use_kv_mask, dropout_rate, interpret, window)
    return out


def _flash_fwd_rule(q, k, v, lens, seed, sm_scale, causal, block_q, block_k,
                    use_kv_mask, dropout_rate, interpret, window):
    out, lse = _fwd(q, k, v, lens, seed, sm_scale, causal, block_q, block_k,
                    use_kv_mask, dropout_rate, interpret, window)
    return out, (q, k, v, lens, seed, out, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, use_kv_mask,
                    dropout_rate, interpret, window, res, do):
    return _bwd(sm_scale, causal, block_q, block_k, use_kv_mask,
                dropout_rate, interpret, window, res, do)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_supported(q, k, min_seq=128):
    """Single gate for flash-kernel eligibility, shared by every caller
    (scaled_dot_product_attention, ring attention). Ragged sequence
    lengths are fine (the wrapper pads and the kernel masks the tail)."""
    return (jax.default_backend() == "tpu" and
            q.shape[1] >= min_seq and
            q.shape[-1] in (64, 128, 256))


def _pad_seq(x, to_len):
    pad = to_len - x.shape[1]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))


def flash_attention(q, k, v, causal=False, sm_scale=None, kv_lens=None,
                    dropout_rate=0.0, dropout_seed=None,
                    block_q=None, block_k=None, interpret=False,
                    window=None):
    """q: (batch, seq, num_heads, head_dim), k/v: (batch, seq, kv_heads,
    head_dim) with ``num_heads % kv_heads == 0`` → output shaped like q.
    Query head ``i`` attends over KV head ``i // (num_heads // kv_heads)``.

    window: optional int, causal only — query ``i`` sees key ``j`` iff
    ``0 <= i - j < window`` (its own position and the ``window - 1``
    before it).

    kv_lens: optional (batch,) int32 — per-row count of VALID key/value
    positions (a trailing-padding key mask, the (B,1,1,T) boolean
    ``attn_mask`` of padded batches in O(B) form). dropout_rate/seed:
    attention-probability dropout inside the kernel (seed is an int or
    0-d array; vary it per step).

    block_q/block_k: ``None`` resolves from the tuning DB
    (``ops/pallas/tuner.py``: tuned entry → those blocks, miss → the
    compiled-in DEFAULT_BLOCK_Q/K, counted in
    ``pallas_config_resolved_total``), cut under a window to the power of
    two that holds it; explicit values bypass the DB.
    """
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    if h % h_kv or v.shape[2] != h_kv:
        raise ValueError(f"{h} query heads cannot share {h_kv} key and "
                         f"{v.shape[2]} value heads")
    if window is not None:
        window = int(window)
        if not causal or sq != sk or window < 1:
            raise ValueError("window needs causal self-attention "
                             f"(causal={causal}, sq={sq}, sk={sk}) and "
                             f"window >= 1, got {window}")
        if window >= sk:
            window = None               # it cuts nothing: plain causal
    # the kernels multiply in their operands' dtype: one dtype, q's
    k, v = k.astype(q.dtype), v.astype(q.dtype)
    if block_q is None or block_k is None:
        from .tuner import flash_dims, resolve, shape_bucket
        cfg, _ = resolve("flash_attention", q.dtype, flash_dims(d, sq, sk),
                         {"block_q": DEFAULT_BLOCK_Q,
                          "block_k": DEFAULT_BLOCK_K})
        if window is not None:
            # a block wider than the window computes mostly masked scores:
            # the band takes the row's blocks cut to the window's bucket
            cfg = {name: min(block, shape_bucket(window))
                   for name, block in cfg.items()}
        block_q = block_q or cfg["block_q"]
        block_k = block_k or cfg["block_k"]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if dropout_rate >= 1.0:
        # everything dropped (common.dropout's p == 1.0 semantics)
        return jnp.zeros_like(q)
    if dropout_rate < 0.0:
        raise ValueError(f"dropout_rate must be in [0, 1], got {dropout_rate}")

    # pad ragged tails to lane multiples; kernel masks padded key columns
    sq_pad = int(-(-sq // LANES) * LANES)
    sk_pad = int(-(-sk // LANES) * LANES)
    qp, kp, vp = _pad_seq(q, sq_pad), _pad_seq(k, sk_pad), _pad_seq(v, sk_pad)

    # clamp blocks for short sequences, keeping them LANES-aligned (a
    # non-128-multiple block like 200 would break Mosaic tiling); below one
    # lane tile, the whole sequence is the block
    def _clamp(block, seq):
        if seq < LANES:
            return seq
        bb = (min(block, seq) // LANES) * LANES
        while bb > LANES and seq % bb:
            bb -= LANES  # largest LANES-aligned block that divides seq
        return bb

    block_q = _clamp(block_q, sq_pad)
    block_k = _clamp(block_k, sk_pad)

    use_kv_mask = (sk_pad != sk) or (kv_lens is not None)
    if kv_lens is None:
        lens = jnp.full((b,), sk, dtype=jnp.int32)
    else:
        lens = jnp.minimum(jnp.asarray(kv_lens, jnp.int32).reshape(b), sk)
    # per-(batch*head) scalars live in SMEM (dynamically indexed by the
    # grid's b — the Mosaic-supported home for control scalars)
    lens_bh = jnp.repeat(lens, h)
    if dropout_seed is None:
        seed_arr = jnp.zeros((1,), jnp.int32)
    else:
        seed_arr = jnp.asarray(dropout_seed, jnp.int32).reshape((1,))

    def to_bhsd(x):
        return jnp.reshape(jnp.swapaxes(x, 1, 2),
                           (b * x.shape[2], x.shape[1], d))

    out = _flash_bhsd(to_bhsd(qp), to_bhsd(kp), to_bhsd(vp), lens_bh,
                      seed_arr, float(sm_scale), bool(causal), int(block_q),
                      int(block_k), bool(use_kv_mask), float(dropout_rate),
                      bool(interpret), window)
    out = jnp.swapaxes(jnp.reshape(out, (b, h, sq_pad, d)), 1, 2)
    return out[:, :sq]
