"""Fused LM-head cross entropy — Pallas TPU kernel.

Replaces the two-pass jnp scan in ``ops/chunked_ce.py`` (which still
materializes one (tokens, chunk) logits slab in HBM per scan step) with
a single Mosaic kernel: the LM-head matmul and the softmax-CE reduction
fused, blockwise-online logsumexp over vocab tiles (the flash-attention
trick applied along the class axis), fp32 accumulators in VMEM, and a
custom-VJP backward that RECOMPUTES each (block_tokens, block_vocab)
logits tile instead of saving any of them — peak memory is one logits
tile, never (tokens, vocab).

Forward, per token block, iterating vocab tiles innermost::

    logits = hid_f32 @ w_f32[:, tile]          # MXU, fp32 accumulate
    m, s   = online-logsumexp update(logits)   # m: running max, s: sum
    t     += logits[label] if label in tile    # target-logit pick
    loss   = sum(valid * (lse - t)) / max(#valid, 1)   # host-side epilogue

Backward (two kernels, mirroring the flash dq/dkv split)::

    d_logits = (exp(logits - lse) - onehot(label)) * g * valid / denom
    dh  += d_logits @ w[:, tile]^T             # grid (tokens, vocab)
    dw  += hid^T @ d_logits                    # grid (vocab, tokens)

``chunked_lm_ce`` is the parity oracle and the fallback for callers
(see ``nn.functional.fused_linear_cross_entropy``).  Block sizes resolve
from the tuning DB (``ops/pallas/tuner.py``) at trace time; explicit
``block_tokens``/``block_vocab`` arguments bypass the DB (that is how the
tuner itself sweeps candidates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES, NEG_INF, STAT_LANES

# interpret-validated defaults (see tuning_db.json for the swept seed
# entries); a v5e timing refresh only has to update the DB, not these
DEFAULT_BLOCK_TOKENS = 256
DEFAULT_BLOCK_VOCAB = 1024
# blocks are bounded to fit the v5e's default scoped-VMEM limit (16 MiB)
# with 1 MiB to spare; see vmem_bytes
VMEM_BUDGET = 15 << 20

__all__ = ["fused_lm_ce", "fused_ce_supported", "vmem_bytes",
           "DEFAULT_BLOCK_TOKENS", "DEFAULT_BLOCK_VOCAB", "VMEM_BUDGET"]


def _vocab_cols(j, shape, block_vocab):
    return j * block_vocab + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _ce_fwd_kernel(lbl_ref, hid_ref, w_ref,    # (Bt,STAT) i32,(Bt,H),(H,Bv)
                   lse_ref, tgt_ref,           # (Bt,STAT) f32 each
                   m_scr, s_scr, t_scr,        # (Bt,LANES) f32 each
                   *, vocab, block_vocab, num_v_blocks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        s_scr[:] = jnp.zeros_like(s_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    hid = hid_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(hid, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    cols = _vocab_cols(j, logits.shape, block_vocab)
    logits = jnp.where(cols < vocab, logits, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    p = jnp.exp(logits - m_new)
    # NEG_INF is finite: zero padded-vocab entries explicitly so they
    # never leak into the normalizer (cf. the flash kernel's mask note)
    p = p * (logits > NEG_INF * 0.5)
    alpha = jnp.exp(m_prev - m_new)
    s_new = alpha * s_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)

    # the label's logit lives in exactly one vocab tile; pick it with a
    # one-hot sum (ignore_index / padded rows never match any column)
    lbl = lbl_ref[:, :1]
    t_hit = jnp.sum(jnp.where(cols == lbl, logits, 0.0),
                    axis=1, keepdims=True)

    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    s_scr[:] = jnp.broadcast_to(s_new, s_scr.shape)
    t_scr[:] = t_scr[:] + jnp.broadcast_to(t_hit, t_scr.shape)

    @pl.when(j == num_v_blocks - 1)
    def _finalize():
        s = s_scr[:, :1]
        s_safe = jnp.where(s == 0.0, 1.0, s)
        lse = m_scr[:, :1] + jnp.log(s_safe)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        tgt_ref[...] = jnp.broadcast_to(t_scr[:, :1], tgt_ref.shape)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _ce_bwd_dh_kernel(lbl_ref, scale_ref, lse_ref,  # (Bt,STAT) i32/f32/f32
                      hid_ref, w_ref,               # (Bt,H), (H,Bv)
                      dh_ref,                       # (Bt,H)
                      dh_scr,                       # (Bt,H) f32
                      *, vocab, block_vocab, num_v_blocks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    hid = hid_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(hid, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    cols = _vocab_cols(j, logits.shape, block_vocab)
    logits = jnp.where(cols < vocab, logits, NEG_INF)
    p = jnp.exp(logits - lse_ref[:, :1])
    p = p * (logits > NEG_INF * 0.5)
    onehot = (cols == lbl_ref[:, :1]).astype(jnp.float32)
    dl = (p - onehot) * scale_ref[:, :1]            # (Bt, Bv)
    dh_scr[:] += jax.lax.dot_general(dl, w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(j == num_v_blocks - 1)
    def _finalize():
        dh_ref[...] = dh_scr[:].astype(dh_ref.dtype)


def _ce_bwd_dw_kernel(lbl_ref, scale_ref, lse_ref,  # (Bt,STAT) i32/f32/f32
                      hid_ref, w_ref,               # (Bt,H), (H,Bv)
                      dw_ref,                       # (H,Bv)
                      dw_scr,                       # (H,Bv) f32
                      *, vocab, block_vocab, num_t_blocks):
    j = pl.program_id(0)    # vocab tile (outer)
    i = pl.program_id(1)    # token block (inner)

    @pl.when(i == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    hid = hid_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(hid, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    cols = _vocab_cols(j, logits.shape, block_vocab)
    logits = jnp.where(cols < vocab, logits, NEG_INF)
    p = jnp.exp(logits - lse_ref[:, :1])
    p = p * (logits > NEG_INF * 0.5)
    onehot = (cols == lbl_ref[:, :1]).astype(jnp.float32)
    dl = (p - onehot) * scale_ref[:, :1]            # (Bt, Bv)
    dw_scr[:] += jax.lax.dot_general(hid, dl, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(i == num_t_blocks - 1)
    def _finalize():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)


# ---------------------------------------------------------------------------
# host wrappers
# ---------------------------------------------------------------------------
def _pad_to(x, rows, axis=0):
    pad = rows - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _stat(x, np_):
    """(n,) → (np_, STAT_LANES): the lane-tiled home of per-row stats."""
    return jnp.broadcast_to(_pad_to(x, np_)[:, None], (np_, STAT_LANES))


def _ce_shapes(n, v, block_tokens, block_vocab):
    np_ = int(-(-n // block_tokens) * block_tokens)
    vp = int(-(-v // block_vocab) * block_vocab)
    return np_, vp, np_ // block_tokens, vp // block_vocab


def _ce_fwd(hid, w, lbl, block_tokens, block_vocab, ignore_index,
            interpret):
    n, h = hid.shape
    v = w.shape[1]
    np_, vp, nt, nv = _ce_shapes(n, v, block_tokens, block_vocab)
    hid_p = _pad_to(hid, np_)
    w_p = _pad_to(w, vp, axis=1)
    # padded rows carry ignore_index: excluded from the loss mean below
    # and given zero scale in the backward
    lbl_p = jnp.full((np_,), ignore_index, jnp.int32).at[:n].set(lbl)
    lbl2 = jnp.broadcast_to(lbl_p[:, None], (np_, STAT_LANES))

    stat_spec = pl.BlockSpec((block_tokens, STAT_LANES), lambda i, j: (i, 0))
    lse_p, tgt_p = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, vocab=v, block_vocab=block_vocab,
                          num_v_blocks=nv),
        grid=(nt, nv),
        in_specs=[
            stat_spec,
            pl.BlockSpec((block_tokens, h), lambda i, j: (i, 0)),
            pl.BlockSpec((h, block_vocab), lambda i, j: (0, j)),
        ],
        out_specs=[stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((np_, STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((np_, STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_tokens, LANES), jnp.float32),
            pltpu.VMEM((block_tokens, LANES), jnp.float32),
            pltpu.VMEM((block_tokens, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(lbl2, hid_p, w_p)

    lse = lse_p[:n, 0]
    tgt = tgt_p[:n, 0]
    valid = (lbl != ignore_index).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    loss = jnp.sum(valid * (lse - tgt)) / denom
    return loss, (hid, w, lbl, lse, denom)


def _ce_bwd(hid, w, lbl, lse, denom, g, block_tokens, block_vocab,
            ignore_index, interpret):
    n, h = hid.shape
    v = w.shape[1]
    np_, vp, nt, nv = _ce_shapes(n, v, block_tokens, block_vocab)
    hid_p = _pad_to(hid, np_)
    w_p = _pad_to(w, vp, axis=1)
    lbl_p = jnp.full((np_,), ignore_index, jnp.int32).at[:n].set(lbl)
    lbl2 = jnp.broadcast_to(lbl_p[:, None], (np_, STAT_LANES))
    valid = (lbl != ignore_index).astype(jnp.float32)
    # d_loss/d_logit = (softmax - onehot) * scale; folding the upstream
    # cotangent and the mean's 1/denom in here makes padded rows exact
    # zeros (their lse pads to 0 so softmax is finite, scale kills it)
    scale2 = _stat((g.astype(jnp.float32) / denom) * valid, np_)
    lse2 = _stat(lse, np_)

    stat_spec = pl.BlockSpec((block_tokens, STAT_LANES), lambda i, j: (i, 0))
    dh_p = pl.pallas_call(
        functools.partial(_ce_bwd_dh_kernel, vocab=v,
                          block_vocab=block_vocab, num_v_blocks=nv),
        grid=(nt, nv),
        in_specs=[
            stat_spec,
            stat_spec,
            stat_spec,
            pl.BlockSpec((block_tokens, h), lambda i, j: (i, 0)),
            pl.BlockSpec((h, block_vocab), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_tokens, h), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, h), hid.dtype),
        scratch_shapes=[pltpu.VMEM((block_tokens, h), jnp.float32)],
        interpret=interpret,
    )(lbl2, scale2, lse2, hid_p, w_p)

    stat_spec_t = pl.BlockSpec((block_tokens, STAT_LANES),
                               lambda j, i: (i, 0))
    dw_p = pl.pallas_call(
        functools.partial(_ce_bwd_dw_kernel, vocab=v,
                          block_vocab=block_vocab, num_t_blocks=nt),
        grid=(nv, nt),
        in_specs=[
            stat_spec_t,
            stat_spec_t,
            stat_spec_t,
            pl.BlockSpec((block_tokens, h), lambda j, i: (i, 0)),
            pl.BlockSpec((h, block_vocab), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((h, block_vocab), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((h, vp), w.dtype),
        scratch_shapes=[pltpu.VMEM((h, block_vocab), jnp.float32)],
        interpret=interpret,
    )(lbl2, scale2, lse2, hid_p, w_p)

    return dh_p[:n], dw_p[:, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_ce(hid, w, lbl, block_tokens, block_vocab, ignore_index,
              interpret):
    loss, _ = _ce_fwd(hid, w, lbl, block_tokens, block_vocab,
                      ignore_index, interpret)
    return loss


def _fused_ce_fwd_rule(hid, w, lbl, block_tokens, block_vocab,
                       ignore_index, interpret):
    return _ce_fwd(hid, w, lbl, block_tokens, block_vocab, ignore_index,
                   interpret)


def _fused_ce_bwd_rule(block_tokens, block_vocab, ignore_index, interpret,
                       res, g):
    hid, w, lbl, lse, denom = res
    dh, dw = _ce_bwd(hid, w, lbl, lse, denom, g, block_tokens,
                     block_vocab, ignore_index, interpret)
    # int labels take a float0 cotangent
    return dh, dw, np.zeros(lbl.shape, jax.dtypes.float0)


_fused_ce.defvjp(_fused_ce_fwd_rule, _fused_ce_bwd_rule)


def fused_ce_supported(min_tokens=128):
    """Gate for the compiled (non-interpret) kernel path — mirrors
    ``flash_supported``. Interpret mode works everywhere; this is about
    whether running it compiled is worthwhile."""
    return jax.default_backend() == "tpu"


def vmem_bytes(h, block_tokens, block_vocab, dtype):
    """Upper estimate of the scoped VMEM the largest of the three kernels
    asks Mosaic for: double-buffered input/output blocks, the fp32
    accumulator, the fp32 copy of the weight tile the dh kernel keeps for
    its two matmuls, one (block_tokens, block_vocab) fp32 logits tile and
    the three lane-padded stat blocks. Fitted against the smallest
    ``vmem_limit_bytes`` each kernel compiles under for v5e (bf16 and
    fp32, h 128..4096): never below what the compiler needed, at most
    ~30% above."""
    it = jnp.dtype(dtype).itemsize
    a, b, c = block_tokens * h, h * block_vocab, block_tokens * block_vocab
    stats = 3 * 2 * block_tokens * LANES * 4
    dh = (4 * it + 4) * a + (2 * it + 4) * b + 4 * c + stats
    dw = 2 * it * a + (4 * it + 4) * b + 4 * c + stats
    return max(dh, dw)


def _clamp_blocks(n, v, h, dtype, block_tokens, block_vocab):
    """Shrink oversized blocks to the problem and to VMEM, keeping Mosaic
    tiling: token blocks on the sublane quantum (8), vocab blocks on the
    lane quantum (128). Padding rounds the problem UP to the block, so
    any aligned block is legal. The VMEM bound halves whichever block
    carries the larger tile until :func:`vmem_bytes` fits
    :data:`VMEM_BUDGET` — without it the dw kernel's (h, block_vocab)
    tiles outgrow the scoped limit as the hidden width rises."""
    bt = max(8, min(int(block_tokens), int(-(-n // 8) * 8)))
    bt = (bt // 8) * 8
    bv = max(LANES, min(int(block_vocab), int(-(-v // LANES) * LANES)))
    bv = (bv // LANES) * LANES
    while vmem_bytes(h, bt, bv, dtype) > VMEM_BUDGET:
        if bv > LANES and bv >= bt:
            bv = max(LANES, (bv // 2) // LANES * LANES)
        elif bt > 8:
            bt = max(8, (bt // 2) // 8 * 8)
        else:
            raise ValueError(
                f"fused_lm_ce: hidden width {h} does not fit VMEM even at "
                f"the smallest ({bt}, {bv}) blocks")
    return bt, bv


def fused_lm_ce(hidden, weight, labels, block_tokens=None,
                block_vocab=None, ignore_index=-100, interpret=None):
    """Fused LM-head softmax cross entropy (mean over valid labels).

    hidden: (..., H) activations; weight: (H, V) LM-head matrix;
    labels: (...,) int targets, ``ignore_index`` entries excluded from
    the mean. Returns a scalar fp32 loss; gradients flow to hidden and
    weight. Drop-in for ``chunked_lm_ce`` (its parity oracle in tests).

    block_tokens/block_vocab: ``None`` resolves from the tuning DB
    (tuned entry → those blocks, miss → module defaults, counted in
    ``pallas_config_resolved_total``); explicit values bypass the DB.
    interpret: ``None`` auto-selects interpret mode off-TPU.
    """
    hid = jnp.reshape(hidden, (-1, hidden.shape[-1]))
    lbl = jnp.reshape(jnp.asarray(labels, jnp.int32), (-1,))
    n, h = hid.shape
    v = weight.shape[1]
    if weight.shape[0] != h:
        raise ValueError(
            f"weight must be (H, V) with H={h}, got {weight.shape}")

    if block_tokens is None or block_vocab is None:
        from .tuner import ce_dims, resolve
        cfg, _ = resolve(
            "fused_ce", hid.dtype, ce_dims(h, v, n),
            {"block_tokens": DEFAULT_BLOCK_TOKENS,
             "block_vocab": DEFAULT_BLOCK_VOCAB})
        block_tokens = block_tokens or cfg["block_tokens"]
        block_vocab = block_vocab or cfg["block_vocab"]
    bt, bv = _clamp_blocks(n, v, h, hid.dtype, block_tokens, block_vocab)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _fused_ce(hid, weight, lbl, int(bt), int(bv),
                     int(ignore_index), bool(interpret))
