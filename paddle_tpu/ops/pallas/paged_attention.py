"""Paged (block-table) decode attention — XLA gather baseline + Pallas TPU
kernel.

Decode-time attention for the paged KV cache (``inference/kv_cache.py``):
each sequence's keys/values live scattered across fixed-size pool pages and
a per-sequence block table names the pages in order. Dense attention would
need the KV contiguous; here the gather happens through the table.

Two interchangeable paths (numerics asserted identical in tests):

- **XLA gather** (mandatory baseline, any backend): ``pool[tables]``
  advanced indexing → one rectangular (B, S, H, D) view per batch, masked
  to each row's true context length. Also provides the ragged
  *mixed-batch* path (:func:`paged_prefill_attention`) where prefill-chunk
  and decode-step rows share one flattened token axis.
- **Pallas kernel** (decode steps Tq == 1, and the ragged speculative-
  verify path 1 < Tq <= 32): the flash-attention streaming structure —
  grid ``(batch, heads, pages)``, online softmax in VMEM scratch — with
  the KV *block index maps reading the block table from scalar-prefetch
  SMEM* (``PrefetchScalarGridSpec``), so each grid step DMAs exactly one
  page and fully-masked pages are skipped. Page-tail masking reuses
  flash's ``kv_lens`` column-mask idiom (finite ``NEG_INF`` plus explicit
  ``p`` zeroing so fully-masked rows yield 0, not NaN). The kernel
  returns *unnormalized* (acc, m, l) running stats; the chunk tokens'
  self-attention term — a single score for a decode step, a causal
  (Tq, Tq) block for a verify chunk — is folded in a tiny jnp epilogue,
  so the new K/V never has to be scattered into the pool before
  attention reads it. The kernel body is row-wise: the verify path packs
  the Tq distinct queries into the sublane rows the decode path
  broadcasts one query across (``_paged_verify_kernel`` is the same body
  under its own name so the tuner/lint keying can tell the shapes apart).

Config (``q_pad`` — sublane rows holding the query/queries, 8 for f32
tiles / 16 for the bf16 tile shape; the verify path needs
``q_pad >= bucket(Tq)``) resolves through the tuning DB under kernel name
``"paged_attention"``; interpret-validated seeds ship in
``tuning_db.json`` — verify shapes carry an extra ``sq`` dim in the key.

Public API:
    paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           k_new=None, v_new=None, ...)
    paged_prefill_attention(q, k_new, v_new, row_id, positions, valid,
                            k_pool, v_pool, block_tables, context_lens)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, STAT_LANES, LANES

DEFAULT_Q_PAD = 8  # sublane rows the single decode query is broadcast to
MAX_VERIFY_TQ = 32  # widest speculative-verify chunk the kernel packs


def verify_rows(tq: int) -> int:
    """Sublane rows a Tq-query verify chunk occupies: Tq rounded up to
    the next power of two, floored at the f32 tile height. This — not
    the config's ``q_pad`` — keys the tuning DB for verify shapes, so
    the key is recoverable from the traced kernel's padded query shape."""
    from .tuner import shape_bucket
    return shape_bucket(int(tq), floor=8)


def paged_dims(d: int, page_size: int, num_pages: int, tq: int = 1) -> dict:
    """Tuning-DB dims for a paged decode call: head_dim and page size
    exact (hardware tiles), max context bucketed (one entry serves every
    block-table width whose capacity lands in the bucket). Verify calls
    (tq > 1) add the padded query-row bucket as ``sq``; decode keys stay
    exactly as the shipped seeds spell them."""
    from .tuner import shape_bucket
    dims = {"d": int(d), "ps": int(page_size),
            "sk": shape_bucket(int(page_size) * int(num_pages))}
    if int(tq) > 1:
        dims["sq"] = verify_rows(tq)
    return dims


def paged_decode_supported(q, k_pool, interpret: bool = False) -> bool:
    """Gate for the Pallas paged-decode/verify kernel: 1..MAX_VERIFY_TQ
    query tokens per row, tileable head_dim, sublane-aligned page size.
    Interpret mode lifts the backend requirement (CPU tests)."""
    return ((interpret or jax.default_backend() == "tpu") and
            q.ndim == 4 and 1 <= q.shape[1] <= MAX_VERIFY_TQ and
            q.shape[-1] in (32, 64, 128, 256) and
            k_pool.shape[2] % 8 == 0)


# ---------------------------------------------------------------------------
# XLA gather baseline
# ---------------------------------------------------------------------------

def _gather_ctx(pool, tables):
    """(P, H, ps, D) pool + (B, n) tables → (B, H, n*ps, D) context."""
    b, n = tables.shape
    p, h, ps, d = pool.shape
    return jnp.swapaxes(pool[tables], 1, 2).reshape(b, h, n * ps, d)


def _xla_paged_decode(q, k_pool, v_pool, tables, lens, k_new, v_new,
                      sm_scale):
    b, tq, h, d = q.shape
    kc = _gather_ctx(k_pool, tables).astype(jnp.float32)   # (B, H, S, D)
    vc = _gather_ctx(v_pool, tables).astype(jnp.float32)
    s_len = kc.shape[2]
    qf = q.astype(jnp.float32) * sm_scale
    s_ctx = jnp.einsum("bqhd,bhsd->bhqs", qf, kc)          # (B, H, Tq, S)
    cols = jnp.arange(s_len, dtype=jnp.int32)
    ctx_mask = cols[None, None, None, :] < \
        lens.astype(jnp.int32)[:, None, None, None]
    s_ctx = jnp.where(ctx_mask, s_ctx, NEG_INF)
    if k_new is not None:
        knf = k_new.astype(jnp.float32)
        s_new = jnp.einsum("bqhd,buhd->bhqu", qf, knf)     # (B, H, Tq, Tq)
        rows = jnp.arange(tq, dtype=jnp.int32)
        causal = rows[None, None, :, None] >= rows[None, None, None, :]
        s_new = jnp.where(causal, s_new, NEG_INF)
        s = jnp.concatenate([s_ctx, s_new], axis=-1)
    else:
        s = s_ctx
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * (s > NEG_INF * 0.5)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqs,bhsd->bqhd", p[..., :s_len], vc)
    if v_new is not None:
        out = out + jnp.einsum("bhqu,buhd->bqhd", p[..., s_len:],
                               v_new.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas decode kernel (Tq == 1)
# ---------------------------------------------------------------------------

def _paged_decode_kernel(tables_ref, lens_ref,      # scalar prefetch (SMEM)
                         q_ref,                     # (1, 1, q_pad, D)
                         k_ref, v_ref,              # (1, 1, ps, D)
                         o_ref,                     # (1, 1, q_pad, D) f32
                         m_ref, l_ref,              # (1, 1, q_pad, STAT)
                         m_scr, l_scr, acc_scr,     # VMEM running stats
                         *, sm_scale, page_size, num_pages):
    del tables_ref  # consumed by the index maps
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(i * page_size < lens_ref[b])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale     # (q_pad, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (ps, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cols = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < lens_ref[b], s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = p * (s > NEG_INF * 0.5)      # fully-masked rows stay at l == 0
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(i == num_pages - 1)
    def _finalize():
        # UNnormalized acc + (m, l): the wrapper epilogue folds the new
        # token's self-attention term before dividing
        o_ref[0, 0] = acc_scr[:].astype(o_ref.dtype)
        m_ref[0, 0] = jnp.broadcast_to(m_scr[:, :1],
                                       (m_scr.shape[0], STAT_LANES))
        l_ref[0, 0] = jnp.broadcast_to(l_scr[:, :1],
                                       (l_scr.shape[0], STAT_LANES))


def _paged_verify_kernel(*args, **kwargs):
    """Same body as :func:`_paged_decode_kernel`, under its own name:
    the sublane rows hold Tq DISTINCT queries (speculative verify)
    instead of one broadcast query, and the tuner/lint keying
    (``entry_for_traced_call``) recovers the ``sq`` dim from the traced
    query-row count only for this kernel name."""
    return _paged_decode_kernel(*args, **kwargs)


def _run_paged_kernel(kernel_fn, qhp, k_pool, v_pool, tables, lens,
                      sm_scale, interpret):
    """pallas_call plumbing shared by the decode and verify wrappers:
    qhp is (B, H, q_pad, D); returns unnormalized (acc, m, l)."""
    b, h, q_pad, d = qhp.shape
    num_pool_pages, _, ps, _ = k_pool.shape
    npages = tables.shape[1]
    # masked-out table slots may hold sentinel ids: the index map fetches
    # even skipped pages, so clamp every slot into the pool
    tables = jnp.clip(tables.astype(jnp.int32), 0, num_pool_pages - 1)
    lens = jnp.minimum(lens.astype(jnp.int32), npages * ps).reshape(b)

    kernel = functools.partial(
        kernel_fn, sm_scale=sm_scale, page_size=ps,
        num_pages=npages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, npages),
        in_specs=[
            pl.BlockSpec((1, 1, q_pad, d),
                         lambda bi, hi, i, tables, lens: (bi, hi, 0, 0)),
            # head-major pool: the block's last two dims are the array's
            # (ps, D), which is what the TPU lowering requires of a block
            pl.BlockSpec((1, 1, ps, d),
                         lambda bi, hi, i, tables, lens:
                         (tables[bi, i], hi, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bi, hi, i, tables, lens:
                         (tables[bi, i], hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q_pad, d),
                         lambda bi, hi, i, tables, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, q_pad, STAT_LANES),
                         lambda bi, hi, i, tables, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, q_pad, STAT_LANES),
                         lambda bi, hi, i, tables, lens: (bi, hi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_pad, LANES), jnp.float32),
            pltpu.VMEM((q_pad, LANES), jnp.float32),
            pltpu.VMEM((q_pad, d), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, q_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, q_pad, STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, q_pad, STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(tables, lens, qhp, k_pool, v_pool)
    return acc, m, l


def _pallas_paged_decode(q, k_pool, v_pool, tables, lens, k_new, v_new,
                         sm_scale, q_pad, interpret):
    b, tq, h, d = q.shape
    # (B, 1, H, D) → (B, H, q_pad, D): broadcast the single query row
    # across the sublane tile (all rows compute identical stats)
    qhp = jnp.broadcast_to(jnp.transpose(q, (0, 2, 1, 3)),
                           (b, h, q_pad, d))
    acc, m, l = _run_paged_kernel(_paged_decode_kernel, qhp, k_pool,
                                  v_pool, tables, lens, sm_scale,
                                  interpret)
    acc = acc[:, :, 0, :]                                   # (B, H, D)
    m = m[:, :, 0, 0]                                       # (B, H)
    l = l[:, :, 0, 0]
    if k_new is None:
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc / l_safe[..., None]
    else:
        # fold the new token's self-attention term (score vs itself is
        # always unmasked: a decode step attends to its own position)
        qf = q[:, 0].astype(jnp.float32)                    # (B, H, D)
        s_self = jnp.sum(qf * k_new[:, 0].astype(jnp.float32),
                         axis=-1) * sm_scale                # (B, H)
        m2 = jnp.maximum(m, s_self)
        alpha = jnp.exp(m - m2)       # finite NEG_INF → underflows to 0
        w_self = jnp.exp(s_self - m2)
        l2 = l * alpha + w_self
        out = (acc * alpha[..., None] +
               w_self[..., None] * v_new[:, 0].astype(jnp.float32)) / \
            l2[..., None]
    return out[:, None].astype(q.dtype)                     # (B, 1, H, D)


def _pallas_paged_verify(q, k_pool, v_pool, tables, lens, k_new, v_new,
                         sm_scale, q_pad, interpret):
    """Speculative-verify path (1 < Tq <= MAX_VERIFY_TQ): the Tq chunk
    queries ride the sublane rows the decode path broadcasts across —
    the kernel body is already row-wise, so each row streams the SAME
    cached context (every chunk token attends to the full prefix) with
    per-row online-softmax stats. The causal (Tq, Tq) self block over
    the chunk's own new K/V folds in the epilogue."""
    b, tq, h, d = q.shape
    rows = max(int(q_pad), verify_rows(tq))
    qt = jnp.transpose(q, (0, 2, 1, 3))                     # (B, H, Tq, D)
    qhp = jnp.pad(qt, ((0, 0), (0, 0), (0, rows - tq), (0, 0)))
    acc, m, l = _run_paged_kernel(_paged_verify_kernel, qhp, k_pool,
                                  v_pool, tables, lens, sm_scale,
                                  interpret)
    acc = acc[:, :, :tq, :]                                 # (B, H, Tq, D)
    m = m[:, :, :tq, 0]                                     # (B, H, Tq)
    l = l[:, :, :tq, 0]
    if k_new is None:
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc / l_safe[..., None]
    else:
        qf = q.astype(jnp.float32) * sm_scale
        s_self = jnp.einsum("bqhd,buhd->bhqu", qf,
                            k_new.astype(jnp.float32))      # (B,H,Tq,Tq)
        rng = jnp.arange(tq, dtype=jnp.int32)
        causal = rng[None, None, :, None] >= rng[None, None, None, :]
        s_self = jnp.where(causal, s_self, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(s_self, axis=-1))
        alpha = jnp.exp(m - m2)       # finite NEG_INF → underflows to 0
        p_self = jnp.exp(s_self - m2[..., None]) * (s_self > NEG_INF * 0.5)
        l2 = l * alpha + jnp.sum(p_self, axis=-1)
        out = (acc * alpha[..., None] +
               jnp.einsum("bhqu,buhd->bhqd", p_self,
                          v_new.astype(jnp.float32))) / l2[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B,Tq,H,D)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           k_new=None, v_new=None, sm_scale=None,
                           kernel="auto", q_pad=None, interpret=False):
    """Decode attention through a block table.

    q: (B, Tq, H, D) new-token queries (Tq == 1 for pure decode;
    Tq = 1 + K for a speculative-verify chunk — every query attends the
    full cached context plus the chunk's earlier tokens causally).
    k_pool/v_pool: (P, H, page_size, D) head-major page pools.
    block_tables: (B, n_pages) int32 page ids per row (padded slots may
    hold any value; only the first ceil(len/page_size) are read).
    context_lens: (B,) int32 valid cached tokens per row.
    k_new/v_new: optional (B, Tq, H, D) K/V of the query tokens
    themselves (not yet written to the pool); query i additionally
    attends causally to new tokens j <= i.

    kernel: "auto" (Pallas where supported, else XLA), "xla", "pallas".
    q_pad: Pallas sublane padding; ``None`` resolves from the tuning DB
    (kernel name ``"paged_attention"``).
    """
    b, tq, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    sm_scale = float(sm_scale)
    use_pallas = kernel == "pallas" or (
        kernel == "auto" and
        paged_decode_supported(q, k_pool, interpret=interpret))
    if kernel == "pallas" and \
            not paged_decode_supported(q, k_pool, interpret=interpret):
        raise ValueError("pallas paged decode unsupported for this "
                         f"shape/backend: q={q.shape} pool={k_pool.shape}")
    if use_pallas:
        from .tuner import resolve
        if q_pad is None:
            cfg, _ = resolve("paged_attention", q.dtype,
                             paged_dims(d, k_pool.shape[2],
                                        block_tables.shape[1], tq=tq),
                             {"q_pad": (DEFAULT_Q_PAD if tq == 1
                                        else verify_rows(tq))})
            q_pad = cfg["q_pad"]
        impl = _pallas_paged_decode if tq == 1 else _pallas_paged_verify
        return impl(q, k_pool, v_pool, block_tables,
                    context_lens, k_new, v_new, sm_scale,
                    int(q_pad), interpret)
    if kernel == "auto":
        from .tuner import record_fallback
        record_fallback("paged_attention")
    return _xla_paged_decode(q, k_pool, v_pool, block_tables, context_lens,
                             k_new, v_new, sm_scale)


def paged_prefill_attention(q, k_new, v_new, row_id, positions, valid,
                            k_pool, v_pool, block_tables, context_lens,
                            sm_scale=None):
    """Ragged mixed-batch attention (XLA): prefill chunks and decode
    steps flattened onto one token axis.

    q/k_new/v_new: (T, H, D) — chunk tokens of ALL rows concatenated.
    row_id: (T,) which batch row each token belongs to; positions: (T,)
    absolute position of each token in its sequence; valid: (T,) 1 for
    real tokens, 0 for padding. block_tables: (R, n_pages);
    context_lens: (R,) cached tokens per row. Each token attends to its
    row's cached context plus same-row chunk tokens at positions <= its
    own. Returns (T, H, D).
    """
    t, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    row_id = row_id.astype(jnp.int32)
    kc = _gather_ctx(k_pool, block_tables).astype(jnp.float32)  # (R,H,S,D)
    vc = _gather_ctx(v_pool, block_tables).astype(jnp.float32)
    s_len = kc.shape[2]
    kct = jnp.take(kc, row_id, axis=0)                      # (T, H, S, D)
    vct = jnp.take(vc, row_id, axis=0)
    qf = q.astype(jnp.float32) * sm_scale
    s_ctx = jnp.einsum("thd,thsd->ths", qf, kct)            # (T, H, S)
    cols = jnp.arange(s_len, dtype=jnp.int32)
    ctx_len_t = jnp.take(context_lens.astype(jnp.int32), row_id)
    s_ctx = jnp.where(cols[None, None, :] < ctx_len_t[:, None, None],
                      s_ctx, NEG_INF)
    s_new = jnp.einsum("thd,uhd->thu", qf,
                       k_new.astype(jnp.float32))           # (T, H, T)
    same_row = row_id[:, None] == row_id[None, :]
    causal = positions[None, :].astype(jnp.int32) <= \
        positions[:, None].astype(jnp.int32)
    ok = same_row & causal & (valid[None, :] > 0)
    s_new = jnp.where(ok[:, None, :], s_new, NEG_INF)
    s = jnp.concatenate([s_ctx, s_new], axis=-1)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * (s > NEG_INF * 0.5)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("ths,thsd->thd", p[..., :s_len], vct) + \
        jnp.einsum("thu,uhd->thd", p[..., s_len:],
                   v_new.astype(jnp.float32))
    return out.astype(q.dtype)
