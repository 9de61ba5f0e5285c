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
neither a grid step nor a DMA.

Hidden steps (ISSUE 38): without a window the grid of a ``causal`` call
still walks every (q block, key block) pair, and a pair over the diagonal
computes nothing (``run`` in the kernels). The pipeline copies an operand's
next block whenever its block index changes, computed or not, and a step
without arithmetic has nothing to hide a copy behind, so it used to cost
its DMA (1.0 to 1.5 MB, 1.3 to 1.9 us on a v5e). The index maps therefore
hold the index over such steps: the forward and dq kernels name the last
key block their q block sees for the steps past it, the dk/dv kernel the
first q block that sees its keys for the steps before it (q, do, lse and
delta alike), and a window's band its last block over a short row's tail.
One function says both whether a step is skipped and what its maps name
(``_Band.key_step``, ``_Band.query_step``), so a step is skipped exactly
where its fetch is held; no tile's arithmetic or order changes. What a
hidden step still costs is the grid's own overhead (about 0.35 us); its
number a lane block is ``flash_steps_held_total{kernel}``.

Tile kinds (ISSUE 30): ``_visible`` masks scores that the MXU has already
formed, and the two backward kernels are bound by the MXU's passes, so a
tile half of which the causal mask throws away pays for twice what it
uses. What can be visible in tile ``(iq, ik)`` follows from its position
and the static geometry (``_Tiles``), and each grid step takes the branch
of its tile's kind: a *dense* tile (every pair visible: all of a call
without ``causal`` and masked keys, and what lies strictly between a
window's first tile and the diagonal) is one product over the whole tile
with no mask; a *triangular* tile (the diagonal one, and the first tile
of a band whose window is a whole number of blocks) is computed by
``SUB_BLOCKS`` strips a side, static slices of the blocks already in VMEM,
each over the rows and columns that can be visible: (n + 1) / 2n of the
tile's products, the same sums without their exact zeros; every other
tile is *masked*, computed whole under a mask as before. The kinds are
known only for square blocks, ``sq == sk``, a window of whole blocks and
no masked keys (``kv_lens``, a ragged tail); anything else is the masked
path under ``_visible``, the general case of which the other two are what
the kernel can see to be special. A kernel cuts a triangle only where the
sub-blocks are of a size that pays in it (``_SUB_ROWS``, from the chip:
the dq kernel from 128 rows up, the dk/dv kernel from 256 up, the
forward, which the MXU does not bound, at 128 only); elsewhere the
triangle is one strip, the whole tile under the triangle's mask. The
tiles of each kind a staged kernel walks are counted in
``flash_tiles_staged_total{kernel, kind}``.

Block diffusion (ISSUE 31): ``block_diffusion=B`` is a third mask, over
rows that hold a sequence twice, ``[noised ; clean]``, ``L`` positions each
in blocks of ``B``: a noised query sees the noised keys of its own block and
the clean keys of the blocks before it, a clean query the clean keys of its
own block and of those before it, and no query a noised key of another
block (BD3-LM's training mask). Of the ``(2L / block)^2`` tiles the mask
leaves those on the noised half's diagonal, and the lower triangle, diagonal
included, of the two halves' clean keys (80 of 256 at ``L`` = 4,096 in
512-blocks). The grid's inner dimension walks exactly those: the tiles a
lane block computes are a static list (``_DiffusionTiles``), handed to the
kernels as scalar-prefetch tables that the index maps read, so a hidden tile
costs no grid step and no DMA. The tiles strictly under the diagonal are
dense; the three on a diagonal are cut into strips like a causal tile,
under masks on ``position // B``.

Trace names: each ``pallas_call`` carries ``name=`` (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``). That names the kernel's op in a
device trace and stages it under a ``jax.named_scope`` of the same string
(``.../attn/sdpa/flash/jit(_bwd_calls)/flash_bwd_dq/pallas_call`` in the
op's ``tf_op``), so a reader finds the kernels whatever their operands are.

TPU layout notes. The kernels' HBM operands are ``(batch x lane blocks,
seq, lanes)``: a lane block is one head at head width 128 or 256 and
``128 // head_dim`` neighbouring heads side by side below that (two at
width 64), so every row the kernels move is whole 128-lane tiles, and one
grid step does the heads of its block one after the other (``_Pack``). A
``(batch x heads, seq, 64)`` bf16 operand, what width 64 ran on before,
occupies 128 lanes in HBM: every copy into that form wrote, and every
block the kernels fetched read, twice its bytes. Measured on a v5e
(PERF.md section 6, PR 28; one attention layer of gpt2-small with its
projections, forward + backward, device ms): 5.341 with one head a block,
4.920 with two; with the operands left as the projections write them,
``(batch, seq, heads x head_dim)``, and the lane block found by the index
map: 5.131 (its blocks are 4 KB pieces a row apart; at head width 128,
where XLA already lays the projections' outputs out as ``(batch, heads,
seq, 128)`` so that the transposition costs nothing, its kernels read 4%
slower and it adds 3.3-4.0 ms of copies a layer).
Per-row stats (m, l, lse, delta) are carried at LANE=8 width, a row a head
(last dim equal to the array dim satisfies Mosaic's tiling rule); VMEM
scratch uses full (block, 128) tiles.

Public API: flash_attention(q, k, v, causal=False, sm_scale=None,
kv_lens=None, dropout_rate=0.0, dropout_seed=None, window=None)
with q: (batch, seq, heads, head_dim), k/v: (batch, seq, kv_heads, head_dim).
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on a v5e at the cells' shape (bf16 (16, 1024, 12, 64), causal,
# forward + backward of one layer, device ms of every op). One head a
# block, padded to 128 lanes (PERF.md section 6, PR 26): (128, 128) 18.34,
# (256, 256) 8.90, (256, 512) 6.14, (512, 512) 5.01, (256, 1024) 4.90,
# (512, 1024) 4.15, (1024, 1024) 3.88. Two heads a block (PR 28): (512,
# 512) 4.25, (512, 1024) 3.86, (1024, 1024) 3.54. Nothing inside a tile
# moved those times in PR 26's copies (operand dtype, masks, exp): they
# follow the grid steps (about 0.26 us each) and the bytes the blocks bring
# from HBM (the 8-lane statistics in 128-lane tiles), K/V fetched again for
# each q block. So larger blocks are faster, and the tuning DB's row for
# bf16, width 64 and 513 to 1,024 positions says (1024, 1024): one tile a
# lane block. These defaults serve the calls no row covers; no run on
# record has measured them there.
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


def _floor(x, lo):
    return max(x, lo) if isinstance(x, int) else jnp.maximum(x, lo)


def _cap(x, hi):
    return min(x, hi) if isinstance(x, int) else jnp.minimum(x, hi)


def _div(x, n):
    """``x // n`` for ``x >= 0``, where truncation is the floor: an integer
    ``//`` of a traced scalar stages two ``sign``s and a select (see
    ``_Pack._slot`` for what they cost the TPU lowering)."""
    return x // n if isinstance(x, int) else jax.lax.div(x, jnp.int32(n))


class _Band:
    """Which blocks of the other axis a block needs under the causal mask,
    with a window or (``window`` None) without one.

    ``k_first(iq)``..``k_last(iq)`` are the key blocks q block ``iq`` sees
    (columns ``iq*bq - window + 1 .. (iq+1)*bq - 1``; from column 0 without
    a window), ``q_first(ik)``..``q_last(ik)`` the q blocks that see key
    block ``ik`` (rows ``ik*bk .. (ik+1)*bk + window - 2``; to the last row
    without a window; ``q_first`` lies past the last q block where ``sq <
    sk`` leaves a key block unseen). They take a Python int or a traced
    scalar (index maps, kernels) alike.

    The grid's inner dimension walks ``k_steps`` key blocks from
    ``k_first`` on and ``q_steps`` q blocks from ``q_first`` on under a
    window (the widest band of any block), all of them from block 0
    without one. ``key_step`` and ``query_step`` say where a step of
    those walks is, whether its tile is computed, and which block its
    index map names: the step's own where it is computed, else the nearest
    computed one's, which is in VMEM already, so that a skipped step
    fetches nothing. The kernels' ``run`` and the index maps read the same
    two functions: a step is skipped exactly where its fetch is held."""

    def __init__(self, window, block_q, block_k, nq, nk):
        self.window, self.bq, self.bk = window, block_q, block_k
        self.nq, self.nk = nq, nk
        self.k_steps, self.q_steps = nk, nq
        if window is not None:
            self.k_steps = max(self.k_last(i) - self.k_first(i) + 1
                               for i in range(nq))
            self.q_steps = max(self.q_last(j) - self.q_first(j) + 1
                               for j in range(nk))

    def k_first(self, iq):
        if self.window is None:
            return 0
        return _div(_floor(iq * self.bq - (self.window - 1), 0), self.bk)

    def k_last(self, iq):
        return _cap(_div((iq + 1) * self.bq - 1, self.bk), self.nk - 1)

    def q_first(self, ik):
        return _div(ik * self.bk, self.bq)

    def q_last(self, ik):
        if self.window is None:
            return self.nq - 1
        return _cap(_div((ik + 1) * self.bk + self.window - 2, self.bq),
                    self.nq - 1)

    def key_step(self, iq, step):
        """Step ``step`` of q block ``iq``'s walk over the keys (forward
        and dq): ``(ik, run, held)``. The steps past the last visible key
        block, a row's blocks over the diagonal or a short band's tail,
        hold its index."""
        ik, last = self.k_first(iq) + step, self.k_last(iq)
        return ik, ik <= last, _cap(ik, last)

    def query_step(self, ik, step):
        """Step ``step`` of key block ``ik``'s walk over the q blocks
        (dk/dv, one query lane block of the group): ``(iq, run, held)``.
        Without a window the walk starts at q block 0, and the steps before
        the first q block that sees the keys hold that block's index (the
        last block's where none does); under a window it starts there, and
        a short band's tail holds the last."""
        first, last = self.q_first(ik), self.q_last(ik)
        if self.window is None:
            return step, step >= first, _cap(_floor(step, first), last)
        iq = first + step
        return iq, iq <= last, _cap(iq, last)


def _dropout_mask(shape, rate, seed, b, iq, ik, row0=0, col0=0):
    """Deterministic per-block inverted-dropout multiplier in {0, 1/keep}.

    Counter-based hash PRNG (murmur3-style finalizer over
    (seed, block ids, element coords)) built from plain integer ops — the
    SAME bits on the CPU interpreter and on TPU, and trivially regenerated
    by the backward kernels (pltpu.prng_* has no CPU-interpret lowering).
    ``shape`` is the whole tile ``(iq, ik)`` or the part of it that starts
    at row ``row0``, column ``col0``: an element's bits follow from its
    coordinates in the tile, however the tile is cut."""
    u32 = jnp.uint32

    def _u(x):
        # seed/block ids are non-negative int32: plain conversion is exact
        # (Mosaic cannot bitcast scalars)
        return jnp.asarray(x).astype(u32)

    rows = jax.lax.broadcasted_iota(u32, shape, 0) + u32(row0)
    cols = jax.lax.broadcasted_iota(u32, shape, 1) + u32(col0)
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
# heads in lane blocks
# ---------------------------------------------------------------------------
class _Pack:
    """How heads lie in the kernels' lane blocks.

    An operand is ``(batch x lane blocks, seq, lanes)`` with ``lanes =
    max(LANES, d)``: a lane block is one head at width 128 or 256, ``n =
    LANES // d`` heads side by side below that (two at width 64), so that no
    row of HBM is padded; one grid step does a block's heads one after the
    other (``each_head``). Lane block ``hb`` of q holds query heads ``hb*n
    .. hb*n + n - 1`` (``hb`` counts through the batch: ``batch * blocks +
    block``); they read KV lane block ``hb // group`` (a q block never
    straddles two), where query head ``hb*n + j`` finds its KV head in slot
    ``kv_slot(hb, j)``.

    A head inside a block is addressed without slicing: ``place(x, j, t)``
    keeps slot ``j``'s lanes of ``x``, zeros the others and, where ``t`` is
    another slot, moves them there. A product with such an operand
    contracts over all lanes (the zeros are exact) or comes out with the
    other slots' lanes zero; it costs the MXU what a ``d``-deep or
    ``d``-wide product costs, a full pass of its 128 x 128 array. With one
    head a block every method is the identity, and the staged kernels are
    what they were."""

    def __init__(self, d, group, heads=None, stored=None):
        self.d, self.group = d, group
        self.n = max(1, LANES // d)
        self.lanes = max(LANES, d)
        # query heads a batch row: the caller's, and as stored (zero heads
        # fill the last lane block)
        self.heads, self.stored = heads, stored

    def each_head(self, body, carry=None):
        """``carry = body(j, carry)`` for every slot ``j`` of a lane block.
        With more than one head a block the slots are the trips of one
        ``fori_loop``, so the kernel holds (and Mosaic compiles) one head's
        body whatever ``n`` is; ``j`` is then a traced scalar."""
        if self.n == 1:
            return body(0, carry)
        return jax.lax.fori_loop(0, self.n, body, carry)

    def head_id(self, hb, j):
        """``batch * heads + head`` of slot ``j`` of lane block ``hb``, in
        the caller's count of heads: what the dropout hash is fed. Stored
        head ``hb*n + j`` is head ``% stored`` of batch row ``// stored``;
        the zero heads past the caller's are sliced off, whatever id they
        get."""
        g = hb * self.n + j
        if self.stored == self.heads:
            return g
        return (g // self.stored) * self.heads + g % self.stored

    def kv_slot(self, hb, j):
        """The slot of its KV lane block that query head ``hb*n + j``
        reads: ``j`` itself with equal head counts."""
        if self.group == 1 or self.n == 1:
            return j
        return ((hb * self.n + j) // self.group) % self.n

    def _slot(self, shape):
        # lane >> log2(d): below a lane block d is a power of two. (An
        # integer ``//`` stages two ``sign``s, and each costs Pallas' TPU
        # lowering a traced helper: 1.9 s of host time over a step's 36
        # kernels; my chip run, PR 28.)
        return jax.lax.shift_right_logical(
            jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1),
            self.d.bit_length() - 1)

    def place(self, x, src, dst):
        if self.n == 1:
            return x
        slot = self._slot(x.shape)
        x = jnp.where(slot == src, x, jnp.zeros_like(x))
        if src is dst:
            return x
        # grouped heads: the KV head's slot is another than the query
        # head's. Copy the kept lanes into every slot (static rotations,
        # which Mosaic has for 32-bit lanes only: the widening is exact)
        # and keep ``dst``'s.
        wide = x.astype(jnp.float32)
        spread = wide
        for r in range(1, self.n):
            spread = spread + pltpu.roll(wide, r * self.d, axis=1)
        return jnp.where(slot == dst, spread, 0.0).astype(x.dtype)

    def where(self, j, x, other, shape):
        """``x`` in slot ``j``'s lanes of a ``shape`` tile, ``other`` in
        the rest."""
        if self.n == 1:
            return x
        return jnp.where(self._slot(shape) == j, x, other)

    def head_sums(self, x):
        """``(blocks, seq, lanes)`` summed over each head's lanes, in the
        statistics' row order: ``(blocks x n, seq)``. Every sum runs over
        whole lane blocks under a mask, so XLA reduces the minor axis as it
        lies and transposes nothing."""
        if self.n == 1:
            return jnp.sum(x, axis=-1)
        slot = self._slot((1, 1, x.shape[-1]))
        sums = [jnp.sum(jnp.where(slot == j, x, 0.0), axis=-1)
                for j in range(self.n)]
        return jnp.reshape(jnp.stack(sums, axis=1),
                           (x.shape[0] * self.n, x.shape[1]))


def _visible(iq, ik, block_q, block_k, causal, window, kv_len):
    """Which (query row, key column) pairs of tile ``(iq, ik)`` may meet,
    or None where all do: ``0 <= row - col`` under ``causal``, ``row - col
    < window`` with a window, ``col < kv_len`` with a key-padding mask
    (``kv_len`` None without). One tile serves every head of a step."""
    if not causal and kv_len is None:
        return None
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = None
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        keep = rows >= cols
        if window is not None:
            keep = keep & (rows - cols < window)
    if kv_len is not None:
        keep = (cols < kv_len) if keep is None else keep & (cols < kv_len)
    return keep


DENSE, TRIANGULAR, MASKED = TILE_KINDS = ("dense", "triangular", "masked")
# Sub-blocks a side of a triangular tile: 4 leave (4 + 1) / (2 x 4) = 62.5%
# of the tile's MXU passes (2: 75%, 8: 56%).
SUB_BLOCKS = 4
# The rows of a sub-block (``block // SUB_BLOCKS``) between which each kernel
# cuts a triangle; outside them it computes the tile whole under its mask.
# Measured on a v5e (PERF.md section 6, PR 30; device ms of one layer, bf16,
# (16, 1024, 12, 64) in (1024, 1024) tiles and laguna-xs2's window layer,
# (4, 4096, 64 over 8, 128) in (512, 512) tiles; whole tile -> cut):
# - flash_bwd_dq follows the MXU's passes: 0.834 -> 0.541 with 256 rows
#   (0.640 with 512, 0.535 with 128), 5.99 -> 5.49 with 128.
# - flash_bwd_dkv: 1.119 -> 0.900 with 256 rows and 0.891 with 512, but
#   1.480 with 128 and 7.94 -> 10.23 in the window layer: its two products
#   with a transposed left operand (dv, dk) want 256 rows a strip.
# - flash_fwd is not bound by the MXU, and cutting costs it more than the
#   passes give back wherever a strip's scores (sub x block float32)
#   overflow the 64 vector registers: 0.817 -> 0.996 with 256 rows, 0.948
#   with 512, 0.971 with 128; where they fit, 128 rows of a 512-row tile,
#   9.41 -> 6.75.
_SUB_ROWS = {FWD: (128, 128), BWD_DQ: (128, None), BWD_DKV: (256, None)}


class _Tiles:
    """What of each tile ``(iq, ik)`` of the grid can be visible, as far as
    the static geometry says, and so how ``kernel`` computes it:

    - ``dense``: every pair is visible, one product over the whole tile and
      no mask. Every tile of a call without ``causal`` and without masked
      keys; under ``causal`` the tiles strictly between the band's first
      tile and the diagonal.
    - ``triangular``: the tile on the diagonal (``ik == iq``, visible where
      ``row >= col``) and, under a window of ``reach`` whole blocks, the
      band's first tile (``ik == iq - reach``, visible where ``col >
      row``), computed by ``SUB_BLOCKS`` strips a side, each over the rows
      and columns that can be visible and masked in its one sub-block on
      the diagonal (``strips``): (n + 1) / 2n of the tile's products.
    - ``masked``: all of the tile is computed, under a mask. The general
      case: blocks that are not square, ``sq != sk``, a window that is no
      whole number of blocks, masked keys (``kv_lens``, a ragged tail),
      where what a tile shows cannot be told from its position and
      ``_visible`` says it; and a triangle whose sub-blocks are not whole
      lane tiles or not of a size that pays in this kernel (``_SUB_ROWS``):
      one strip, the whole tile under the triangle's mask.

    A tile no query of which sees any key is skipped (``run`` in the
    kernels, from ``band``) and has no kind. ``counts`` is the number of
    tiles of each kind one lane block's grid walks, ``held`` the number of
    its other steps: they compute nothing, and their index maps name the
    block of the nearest computed tile (``_Band``)."""

    # scalar-prefetch tables the grid is walked by: none, the grid's
    # indices say where a step is (``walk_keys``, ``walk_queries``)
    tables = ()

    def __init__(self, kernel, masked_keys, block_q, block_k, nq, nk, band,
                 square):
        # ``band``: the causal mask's geometry, None without ``causal``
        self.causal = causal = band is not None
        self.masked_keys, self.band = masked_keys, band
        self.bq, self.bk = block_q, block_k
        window = band.window if causal else None
        # the kinds are known from a tile's position
        self.exact = (causal and not masked_keys and square
                      and block_q == block_k
                      and (window is None or window % block_q == 0))
        # under a window the band's first tile is ``reach`` blocks before
        # the diagonal
        self.reach = window // block_q if self.exact and window else None
        # the rows of a strip: a sub-block where cutting pays, else the tile
        sub, (least, most) = block_q // SUB_BLOCKS, _SUB_ROWS[kernel]
        self.sub = sub if (sub % LANES == 0 and sub >= least
                           and (most is None or sub <= most)) else block_q
        self.counts = dict.fromkeys(TILE_KINDS, 0)
        for iq in range(nq):
            for ik in range(nk):
                kind = self.kind(iq, ik)
                if kind is not None:
                    self.counts[kind] += 1
        k_steps, q_steps = (band.k_steps, band.q_steps) if causal else (nk, nq)
        walked = nk * q_steps if kernel == BWD_DKV else nq * k_steps
        self.held = walked - sum(self.counts.values())

    def kind(self, iq, ik):
        """The kind of tile ``(iq, ik)`` (Python ints), None if skipped."""
        band = self.band
        if band is not None and not (band.k_first(iq) <= ik
                                     <= band.k_last(iq)):
            return None
        if not self.exact:
            return MASKED if self.causal or self.masked_keys else DENSE
        if ik == iq or (self.reach is not None and ik == iq - self.reach):
            return TRIANGULAR if self.sub < self.bq else MASKED
        return DENSE

    def branches(self, iq, ik, run):
        """``(predicate, shape)`` of each way the kernel computes a tile of
        this geometry, for traced ``iq``, ``ik`` and ``run`` (the tile is
        not skipped): ``shape`` is ``"diagonal"``, ``"edge"`` (the two
        triangles), ``DENSE`` or ``MASKED``. Only what the geometry has is
        staged."""
        if not self.exact:
            return [(run, MASKED if self.counts[MASKED] else DENSE)]
        out, below = [(ik == iq, "diagonal")], ik < iq
        if self.reach is not None:
            out.append((ik == iq - self.reach, "edge"))
            below = below & (ik > iq - self.reach)
        if self.counts[DENSE]:
            out.append((below, DENSE))
        return [(run & when, shape) for when, shape in out]

    def strips(self, shape, by):
        """``(row0, rows, col0, cols)`` of each product over a tile of
        ``shape``: the whole tile where it is dense or masked; of a
        triangle one strip a sub-block of query rows (``by="rows"``: the
        forward and dq kernels, which write rows) or of key columns
        (``by="cols"``: the dk/dv kernel), over the other axis' part that
        the strip can see."""
        b, sub = self.bq, self.sub
        if shape in (DENSE, MASKED):
            return [(0, self.bq, 0, self.bk)]
        # one strip, the whole triangle, where this kernel does not cut
        cut = [(i, sub) for i in range(0, b, sub)]
        if shape == "diagonal":         # visible: row >= col
            if by == "rows":
                return [(r0, n, 0, r0 + n) for r0, n in cut]
            return [(c0, b - c0, c0, n) for c0, n in cut]
        if by == "rows":                # the band's edge, visible: col > row
            return [(r0, n, r0, b - r0) for r0, n in cut]
        return [(0, c0 + n, c0, n) for c0, n in cut]

    def keep(self, shape, strip, iq, ik, kv_len):
        """Which pairs of a strip may meet, None where all do."""
        row0, rows, col0, cols = strip
        if shape == DENSE:
            return None
        if shape == MASKED:
            window = None if self.band is None else self.band.window
            return _visible(iq, ik, self.bq, self.bk, self.causal, window,
                            kv_len)
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        return row >= col if shape == "diagonal" else col > row

    # where a grid step is: ``(hb, iq, ik, first, last, branches)``, the
    # lane block of the queries, the tile, whether it is the first or the
    # last the step's accumulators see, and ``[(predicate, shape)]``
    def walk_keys(self, hb, i, step, steps, table):
        """The forward and dq kernels: grid (lane blocks, q blocks, key
        steps); under a window the steps walk the band's key blocks only."""
        ik, run = step, True
        if self.band is not None:
            ik, run, _ = self.band.key_step(i, step)
        return (hb, i, ik, step == 0, step == steps - 1,
                self.branches(i, ik, run))

    def walk_queries(self, hb, j, step, steps, group, table):
        """The dk/dv kernel: grid (KV lane blocks, key blocks, the group's
        query lane blocks x the q blocks, all of them or a window's
        band)."""
        jq = step
        if group != 1:
            hb = hb * group + step // steps
            jq = step % steps
        iq, run = jq, True
        if self.band is not None:
            iq, run, _ = self.band.query_step(j, jq)
        return (hb, iq, j, step == 0, step == group * steps - 1,
                self.branches(iq, j, run))


def block_diffusion_visible(rows, cols, half, block):
    """Which (query index, key index) pairs of a ``[noised ; clean]`` row of
    ``2 * half`` positions meet, from index arrays that broadcast (numpy or
    jax alike); ``block(p)`` is the diffusion block of position ``p``. A
    noised query (index below ``half``): the noised keys of its own block,
    the clean keys of the blocks before it. A clean query: the clean keys
    of its own block and of those before it. No noised key otherwise."""
    q_noised, k_noised = rows < half, cols < half
    qb = block(rows - half * (1 - q_noised.astype(rows.dtype)))
    kb = block(cols - half * (1 - k_noised.astype(cols.dtype)))
    return ((q_noised & k_noised & (qb == kb))
            | (q_noised & ~k_noised & (kb < qb))
            | (~q_noised & ~k_noised & (kb <= qb)))


ON_DIAGONAL, UNDER_DIAGONAL = "block_diagonal", "block_triangle"
# a block triangle as a kernel's branch sees it: ``strict`` (a traced 0 or
# 1) says whether a query's own block is hidden (noised x clean) or not
_Under = collections.namedtuple("_Under", "strict")


class _DiffusionTiles:
    """``_Tiles`` for the block-diffusion mask: which tiles of the ``2L x
    2L`` scores a lane block computes, in which order, and how.

    With square blocks ``b``, ``L % b == 0``, ``b % B == 0`` and no padded
    key (``exact``), ``n = L / b`` tiles a half a side, the mask leaves

    - noised x noised: the ``n`` tiles on the diagonal, visible where
      ``row // B == col // B`` (``block_diagonal``: strip ``r`` is the
      sub-block ``(r, r)`` alone);
    - noised x clean: the tiles under the diagonal, dense, and those on it,
      visible where ``col // B < row // B``;
    - clean x clean: the same with ``<=`` (both ``block_triangle``, cut like
      a causal tile's triangle; ``strict`` tells them apart at run time, so
      that one branch of the kernel serves both);
    - clean x noised: nothing.

    That is ``n + n (n + 1)`` tiles of ``4 n^2``: 80 of 256 at ``L`` = 4,096
    in 512-blocks. Anything else (a ragged ``2L``, blocks that do not divide
    ``L``) is every tile that shows a pair, whole, under
    ``block_diffusion_visible``.

    The kernels' grids walk the list and nothing else, q block by q block
    with its key blocks in order (forward and dq) or key block by key block
    and for each the group's query heads and for each the q blocks (dk/dv),
    as int32 scalar-prefetch ``tables`` of one entry a grid step: the q block, the key block, ``how`` (bit 0: the
    first tile of its accumulator, bit 1: the last, bit 2: ``strict``, the
    rest: the index of the shape in ``shapes``) and for dk/dv the head of
    the group. The index maps read the same tables, so a block that stays
    is not fetched again and a tile that is not listed is never touched."""

    def __init__(self, kernel, half, block_len, masked_keys, block_q, block_k,
                 nq, nk, group):
        self.half, self.block_len = half, block_len
        self.bq, self.bk = block_q, block_k
        b = block_q
        self.exact = (not masked_keys and block_q == block_k
                      and half % b == 0 and b % block_len == 0)
        sub, (least, most) = b // SUB_BLOCKS, _SUB_ROWS[kernel]
        self.sub = sub if (sub % LANES == 0 and sub % block_len == 0
                           and sub >= least
                           and (most is None or sub <= most)) else b
        if self.exact:
            self.shapes = (DENSE, ON_DIAGONAL, UNDER_DIAGONAL)
        else:
            self.shapes = (MASKED,)
        tiles = []                      # (iq, ik, shape, strict)
        for iq in range(nq):
            for ik in range(nk):
                found = self._tile(iq, ik)
                if found is not None:
                    tiles.append((iq, ik) + found)
        self.counts = dict.fromkeys(TILE_KINDS, 0)
        for _, _, shape, _ in tiles:
            self.counts[self._kind(shape)] += 1
        rows = [t + (0,) for t in tiles]                      # iq-major
        cols = [t + (g,) for ik in range(nk) for g in range(group)
                for t in tiles if t[1] == ik]
        walk = rows if kernel != BWD_DKV else cols
        # the accumulator a step adds to: the q block's, or the key block's
        owner = [t[0] if kernel != BWD_DKV else t[1] for t in walk]
        how = []
        for i, (_, _, shape, strict, _) in enumerate(walk):
            first = i == 0 or owner[i - 1] != owner[i]
            last = i == len(walk) - 1 or owner[i + 1] != owner[i]
            how.append(first | last << 1 | strict << 2
                       | self.shapes.index(shape) << 3)
        self.steps, self.held = len(walk), 0    # the walk is the tiles
        fields = [[t[0] for t in walk], [t[1] for t in walk], how]
        if kernel == BWD_DKV:
            fields.append([t[4] for t in walk])
        self.tables = tuple(np.asarray(f, np.int32) for f in fields)

    def _block(self, p):
        """The diffusion block of position ``p`` (a shift where the block
        length is a power of two: an integer ``//`` costs Pallas' TPU
        lowering a traced helper, see ``_Pack._slot``)."""
        n = self.block_len
        if isinstance(p, np.ndarray):
            return p // n
        if n & (n - 1) == 0:
            return jax.lax.shift_right_logical(p, n.bit_length() - 1)
        return _div(p, n)

    def _tile(self, iq, ik):
        """``(shape, strict)`` of tile ``(iq, ik)``, None where the mask
        hides all of it."""
        if self.exact:
            n = self.half // self.bq
            if iq < n and ik < n:
                return (ON_DIAGONAL, 0) if ik == iq else None
            if ik < n:
                return None
            r, c = iq % n, ik - n
            if c > r:
                return None
            return (DENSE, 0) if c < r else (UNDER_DIAGONAL, int(iq < n))
        rows = np.arange(iq * self.bq, (iq + 1) * self.bq)
        cols = np.arange(ik * self.bk, (ik + 1) * self.bk)
        cols = cols[cols < 2 * self.half]
        seen = block_diffusion_visible(rows[:, None], cols[None, :],
                                       self.half, self._block)
        return (MASKED, 0) if seen.any() else None

    def _kind(self, shape):
        if shape in (ON_DIAGONAL, UNDER_DIAGONAL):
            return TRIANGULAR if self.sub < self.bq else MASKED
        return shape

    def kind(self, iq, ik):
        """The kind of tile ``(iq, ik)`` (Python ints), None if hidden."""
        found = self._tile(iq, ik)
        return None if found is None else self._kind(found[0])

    def _walk(self, how):
        """``(first, last, branches)`` of a step from its ``how``."""
        code = jax.lax.shift_right_logical(how, 3)
        under = _Under(jax.lax.shift_right_logical(how, 2) & 1)
        return ((how & 1) == 1, (how & 2) == 2,
                [(code == i, under if shape == UNDER_DIAGONAL else shape)
                 for i, shape in enumerate(self.shapes)])

    def walk_keys(self, hb, i, step, steps, table):
        iq, ik, how = (ref[step] for ref in table)
        return (hb, iq, ik) + self._walk(how)

    def walk_queries(self, hb, j, step, steps, group, table):
        iq, ik, how, head = (ref[step] for ref in table)
        if group != 1:
            hb = hb * group + head
        return (hb, iq, ik) + self._walk(how)

    def strips(self, shape, by):
        b, sub = self.bq, self.sub
        if shape in (DENSE, MASKED):
            return [(0, self.bq, 0, self.bk)]
        cut = [(i, sub) for i in range(0, b, sub)]
        if shape == ON_DIAGONAL:
            return [(i, n, i, n) for i, n in cut]
        if by == "rows":                # a block triangle: as a causal one
            return [(r0, n, 0, r0 + n) for r0, n in cut]
        return [(c0, b - c0, c0, n) for c0, n in cut]

    def keep(self, shape, strip, iq, ik, kv_len):
        row0, rows, col0, cols = strip
        if shape == DENSE:
            return None
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        if shape == MASKED:
            col = ik * self.bk + col
            keep = block_diffusion_visible(iq * self.bq + row, col,
                                           self.half, self._block)
            return keep if kv_len is None else keep & (col < kv_len)
        # a tile on a diagonal starts at a whole block of both axes
        qb, kb = self._block(row), self._block(col)
        if shape == ON_DIAGONAL:
            return qb == kb
        return kb <= qb - shape.strict


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(lens_ref, seed_ref,       # (blocks,) i32, (1,) i32 in SMEM
                *refs, sm_scale, num_k_blocks, use_kv_mask, dropout_rate,
                pack, tiles):
    # the tiles' tables (none but under block diffusion), then
    # q_ref, k_ref, v_ref      (1,Bq,L), (1,Bk,L), (1,Bk,L)
    # o_ref, lse_ref           (1,Bq,L), (n,Bq,STAT_LANES)
    # m_scr, l_scr, acc_scr    (n,Bq,LANES) x 2, (Bq,L)
    table, refs = refs[:len(tiles.tables)], refs[len(tiles.tables):]
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    block_q = tiles.bq
    hb, iq, ik, first, last, branches = tiles.walk_keys(
        pl.program_id(0), pl.program_id(1), pl.program_id(2), num_k_blocks,
        table)

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute(shape):
        kv_len = lens_ref[hb] if use_kv_mask else None

        def head(j, _):
            t = pack.kv_slot(hb, j)
            for strip in tiles.strips(shape, "rows"):
                row0, nr, col0, nc = strip
                rows, cols = pl.ds(row0, nr), pl.ds(col0, nc)
                q, k, v = q_ref[0, rows], k_ref[0, cols], v_ref[0, cols]
                keep = tiles.keep(shape, strip, iq, ik, kv_len)
                s = _scores(pack.place(q, j, t), k, sm_scale)
                if keep is not None:
                    s = jnp.where(keep, s, NEG_INF)
                m_prev = m_scr[j, rows, :1]
                m_cur = jnp.max(s, axis=1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                p = jnp.exp(s - m_new)
                if keep is not None and shape != "diagonal":
                    # NEG_INF is finite, so a FULLY-masked row has m_new ==
                    # s and p == exp(0) == 1 — zero masked entries
                    # explicitly so l is 0 for such rows (out = 0, lse
                    # pinned to 0, no K/V grad leak). A row of the
                    # diagonal tile sees its own key at least: its m_new
                    # is a score and exp(NEG_INF - m_new) is 0 already
                    p = p * (s > NEG_INF * 0.5)
                alpha = jnp.exp(m_prev - m_new)
                l_new = alpha * l_scr[j, rows, :1] + jnp.sum(
                    p, axis=1, keepdims=True)
                if dropout_rate > 0.0:
                    # normalizer l uses the UNdropped p (softmax
                    # semantics); only the value accumulation is dropped
                    p = p * _dropout_mask(p.shape, dropout_rate, seed_ref[0],
                                          pack.head_id(hb, j), iq, ik,
                                          row0, col0)
                pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                acc = acc_scr[rows]
                acc_scr[rows] = (acc * pack.where(j, alpha, 1.0, acc.shape)
                                 + pack.place(pv, t, j))
                m_scr[j, rows] = jnp.broadcast_to(m_new, (nr, LANES))
                l_scr[j, rows] = jnp.broadcast_to(l_new, (nr, LANES))

        pack.each_head(head)

    for when, shape in branches:
        pl.when(when)(functools.partial(compute, shape))

    @pl.when(last)
    def _finalize():
        def head(j, l_all):
            l = l_scr[j, :, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            # fully-masked rows (l == 0, e.g. padded queries) pin lse to 0
            # so the backward's p = exp(NEG_INF - lse) is 0, not NaN
            lse = jnp.where(l == 0.0, 0.0, m_scr[j, :, :1] + jnp.log(l_safe))
            lse_ref[j] = jnp.broadcast_to(lse, (block_q, STAT_LANES))
            return pack.where(j, l_safe, l_all, acc_scr.shape)

        l_all = pack.each_head(head, None if pack.n == 1 else jnp.ones(
            acc_scr.shape, jnp.float32))
        o_ref[0] = (acc_scr[:] / l_all).astype(o_ref.dtype)


def _index_maps(group, band, tiles):
    """The q and the K/V block index maps of the two kernels whose grid is
    (q lane blocks, q blocks, key steps). Query lane block ``b`` reads KV
    lane block ``b // group``. Under ``causal`` step ``j`` of q block ``i``
    names the key block ``band.key_step`` holds for it: its own, ``k_first(i)
    + j``, up to the last one the q block sees (the diagonal's), and that
    one again for the steps past it, which the kernels skip: the pipeline
    copies a block only when its index changes, so a skipped step fetches
    nothing. Without ``causal`` every step runs and the map is the plain
    ``(b // group, j, 0)``. Where the grid walks a list of tiles (block
    diffusion) step ``j``'s blocks are the tables' entries."""
    def head(b):
        return b if group == 1 else b // group

    if tiles.tables:
        return (lambda b, i, j, lens, seed, tq, tk, how: (b, tq[j], 0),
                lambda b, i, j, lens, seed, tq, tk, how: (head(b), tk[j], 0))
    q_map = lambda b, i, j: (b, i, 0)  # noqa: E731
    if band is None:
        return q_map, lambda b, i, j: (head(b), j, 0)
    return q_map, lambda b, i, j: (head(b), band.key_step(i, j)[2], 0)


def _dkv_index_maps(group, band, q_steps, tiles):
    """The index maps of the dk/dv kernel, whose grid is (KV lane blocks,
    key blocks, the group's query lane blocks x ``q_steps`` q steps): of
    what lives per query lane block (q, do, lse, delta) and of the KV lane
    block's own (k, v, dk, dv). Inner step ``t`` is q step ``t % q_steps``
    of the group's query lane block ``t // q_steps``. Under ``causal`` a
    step the kernel skips names the q block ``band.query_step`` holds for
    it, the nearest one it computes, and so fetches nothing; at a change of
    head within the group the lane block changes and the blocks are
    fetched. Where the grid walks a list of tiles (block diffusion) step
    ``t``'s q block, key block and head are the tables' entries."""
    if tiles.tables:
        return (lambda b, j, t, lens, seed, tq, tk, how, head: (
                    b if group == 1 else b * group + head[t], tq[t], 0),
                lambda b, j, t, lens, seed, tq, tk, how, head: (b, tk[t], 0))

    def q_map(b, j, t):
        jq = t if group == 1 else t % q_steps
        block = b if group == 1 else b * group + t // q_steps
        if band is not None:
            jq = band.query_step(j, jq)[2]
        return (block, jq, 0)
    return q_map, lambda b, j, t: (b, j, 0)


def _geometry(q, k, d, heads, block_q, block_k, window, causal,
              use_kv_mask, diffusion):
    """What the three calls share, from the operands' shapes; ``heads`` is
    the pair (the caller's query heads, the stored ones), ``diffusion``
    None or the block-diffusion mask's ``(L, B)``."""
    blocks, sq, sk = q.shape[0], q.shape[1], k.shape[1]
    group = blocks // k.shape[0]
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    band = _Band(window, block_q, block_k, nq, nk) if causal else None
    if diffusion is None:
        tiles = {kernel: _Tiles(kernel, use_kv_mask, block_q, block_k, nq,
                                nk, band, sq == sk)
                 for kernel in KERNEL_NAMES}
    else:
        tiles = {kernel: _DiffusionTiles(kernel, *diffusion, use_kv_mask,
                                         block_q, block_k, nq, nk, group)
                 for kernel in KERNEL_NAMES}
    return blocks, sq, group, _Pack(d, group, *heads), nq, nk, band, tiles


def _call(kernel, name, tiles, grid, in_specs, out_specs, out_shape, scratch,
          interpret, lens, seed, *operands):
    """One of the three ``pallas_call``s. ``lens`` and ``seed`` are its
    first two operands either way (the benchmark finds the kernels by
    them): SMEM inputs, or, where the grid walks ``tiles.tables``, scalar
    prefetch with the tables after them, which the index maps then take as
    trailing arguments."""
    if not tiles.tables:
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        return pl.pallas_call(
            kernel, grid=grid, in_specs=[smem, smem] + in_specs,
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret, name=name)(lens, seed, *operands)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(tiles.tables), grid=grid,
        in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch)
    return pl.pallas_call(kernel, grid_spec=spec, out_shape=out_shape,
                          interpret=interpret, name=name)(
        lens, seed, *(jnp.asarray(t) for t in tiles.tables), *operands)


def _count_tiles(kernel, tiles, heads=1):
    """``flash_tiles_staged_total{kernel, kind}``: the tiles of each kind
    one lane block's grid walks in a kernel that is being staged, and
    ``flash_steps_held_total{kernel}``: the other steps of that grid, which
    compute nothing and fetch nothing (the dk/dv kernel's grid walks the
    ``heads`` query lane blocks of a KV lane block's group, and skips the
    same steps for each)."""
    from ... import telemetry
    if telemetry.enabled():
        counter = telemetry.counter(
            "flash_tiles_staged_total",
            "Tiles a lane block's grid walks in a staged flash kernel, by "
            "how they are computed")
        for kind, n in tiles.counts.items():
            counter.inc(n, kernel=kernel, kind=kind)
        telemetry.counter(
            "flash_steps_held_total",
            "Grid steps of a lane block in a staged flash kernel whose tile "
            "the mask hides: skipped, the block index held").inc(
                tiles.held * heads, kernel=kernel)


# ``_fwd`` and ``_bwd_calls`` are jitted so that the layers of a model share
# one staged forward and one staged backward: equal shapes and statics hit
# jit's cache, the kernels are traced once and lowered to Mosaic once a
# program (a private function the layers call), not once a layer. The call
# site's scopes stay in front of ``jit(_fwd)/flash_fwd`` in an op's name, and
# XLA inlines the calls: the compiled program is the same. (Set-up of
# gpt2-small.seq1024 from a warm compile cache, 36 call sites: staged a
# layer 38-44 s with one head a block and 49-55 s with two, staged once
# 32-35 s; PERF.md section 6, PR 28.)
@functools.partial(jax.jit, static_argnums=tuple(range(5, 16)))
def _fwd(q, k, v, lens, seed, sm_scale, causal, block_q, block_k,
         use_kv_mask, dropout_rate, interpret, window, d, heads, diffusion):
    blocks, sq, group, pack, nq, nk, band, tiles = _geometry(
        q, k, d, heads, block_q, block_k, window, causal, use_kv_mask,
        diffusion)
    lanes, n = pack.lanes, pack.n
    steps = nk if band is None else band.k_steps
    q_map, kv_map = _index_maps(group, band, tiles[FWD])
    grid = (blocks, nq, steps)
    if diffusion is not None:
        grid = (blocks, 1, tiles[FWD].steps)
    _count_tiles(FWD, tiles[FWD])
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, num_k_blocks=steps,
        use_kv_mask=use_kv_mask, dropout_rate=dropout_rate, pack=pack,
        tiles=tiles[FWD])
    out, lse = _call(
        kernel, FWD, tiles[FWD], grid,
        [
            pl.BlockSpec((1, block_q, lanes), q_map),
            pl.BlockSpec((1, block_k, lanes), kv_map),
            pl.BlockSpec((1, block_k, lanes), kv_map),
        ],
        [
            pl.BlockSpec((1, block_q, lanes), q_map),
            pl.BlockSpec((n, block_q, STAT_LANES), q_map),
        ],
        [
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((blocks * n, sq, STAT_LANES), jnp.float32),
        ],
        [
            pltpu.VMEM((n, block_q, LANES), jnp.float32),
            pltpu.VMEM((n, block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
        ],
        interpret, lens, seed, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(lens_ref, seed_ref, *refs, sm_scale, num_k_blocks,
                   use_kv_mask, dropout_rate, pack, tiles):
    table, refs = refs[:len(tiles.tables)], refs[len(tiles.tables):]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
    hb, iq, ik, first, last, branches = tiles.walk_keys(
        pl.program_id(0), pl.program_id(1), pl.program_id(2), num_k_blocks,
        table)

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(shape):
        kv_len = lens_ref[hb] if use_kv_mask else None

        def head(j, _):
            t = pack.kv_slot(hb, j)
            for strip in tiles.strips(shape, "rows"):
                row0, nr, col0, nc = strip
                rows, cols = pl.ds(row0, nr), pl.ds(col0, nc)
                q, do = q_ref[0, rows], do_ref[0, rows]
                k, v = k_ref[0, cols], v_ref[0, cols]
                keep = tiles.keep(shape, strip, iq, ik, kv_len)
                s = _scores(pack.place(q, j, t), k, sm_scale)
                if keep is not None:
                    s = jnp.where(keep, s, NEG_INF)
                p = jnp.exp(s - lse_ref[j, rows, :1])
                dp = jax.lax.dot_general(pack.place(do, j, t), v,
                                         (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                if dropout_rate > 0.0:
                    dp = dp * _dropout_mask(
                        dp.shape, dropout_rate, seed_ref[0],
                        pack.head_id(hb, j), iq, ik, row0, col0)
                ds = p * (dp - delta_ref[j, rows, :1])
                dq = jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dq_scr[rows] += sm_scale * pack.place(dq, t, j)

        pack.each_head(head)

    for when, shape in branches:
        pl.when(when)(functools.partial(compute, shape))

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(lens_ref, seed_ref, *refs, sm_scale, num_q_blocks,
                    use_kv_mask, dropout_rate, pack, tiles):
    # the grid's first dimension is the KV lane block; the inner one walks
    # the group's query lane blocks, and for each the q blocks (all of
    # them, or the band a window leaves): ``num_q_blocks`` steps each
    table, refs = refs[:len(tiles.tables)], refs[len(tiles.tables):]
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
     dk_scr, dv_scr) = refs
    hb, iq, ik, first, last, branches = tiles.walk_queries(
        pl.program_id(0), pl.program_id(1), pl.program_id(2), num_q_blocks,
        pack.group, table)

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute(shape):
        kv_len = lens_ref[hb] if use_kv_mask else None

        def head(j, _):
            t = pack.kv_slot(hb, j)
            for strip in tiles.strips(shape, "cols"):
                row0, nr, col0, nc = strip
                rows, cols = pl.ds(row0, nr), pl.ds(col0, nc)
                q, do = q_ref[0, rows], do_ref[0, rows]
                k, v = k_ref[0, cols], v_ref[0, cols]
                keep = tiles.keep(shape, strip, iq, ik, kv_len)
                # q and do of head j in its KV head's lanes, zeros
                # elsewhere: dv and dk come out with the other slots' lanes
                # zero
                q_j, do_j = pack.place(q, j, t), pack.place(do, j, t)
                s = _scores(q_j, k, sm_scale)
                if keep is not None:
                    s = jnp.where(keep, s, NEG_INF)
                p = jnp.exp(s - lse_ref[j, rows, :1])       # (rows, cols)
                if dropout_rate > 0.0:
                    m = _dropout_mask(p.shape, dropout_rate, seed_ref[0],
                                      pack.head_id(hb, j), iq, ik, row0, col0)
                    p_drop = p * m
                else:
                    m = None
                    p_drop = p
                dv_scr[cols] += jax.lax.dot_general(
                    p_drop.astype(do.dtype), do_j, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(do_j, v, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                if m is not None:
                    dp = dp * m
                ds = p * (dp - delta_ref[j, rows, :1])      # (rows, cols)
                dk_scr[cols] += sm_scale * jax.lax.dot_general(
                    ds.astype(q.dtype), q_j, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        pack.each_head(head)

    for when, shape in branches:
        pl.when(when)(functools.partial(compute, shape))

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, use_kv_mask, dropout_rate,
         interpret, window, d, heads, diffusion, res, do):
    lens, seed = res[3], res[4]
    dq, dk, dv = _bwd_calls(sm_scale, causal, block_q, block_k, use_kv_mask,
                            dropout_rate, interpret, window, d, heads,
                            diffusion, res, do)
    # int-array inputs (lens, seed) take float0 cotangents
    return (dq, dk, dv, np.zeros(lens.shape, jax.dtypes.float0),
            np.zeros(seed.shape, jax.dtypes.float0))


@functools.partial(jax.jit, static_argnums=tuple(range(11)))
def _bwd_calls(sm_scale, causal, block_q, block_k, use_kv_mask, dropout_rate,
               interpret, window, d, heads, diffusion, res, do):
    q, k, v, lens, seed, out, lse = res
    blocks, _, group, pack, nq, nk, band, tiles = _geometry(
        q, k, d, heads, block_q, block_k, window, causal, use_kv_mask,
        diffusion)
    lanes, n = pack.lanes, pack.n
    _count_tiles(BWD_DQ, tiles[BWD_DQ])
    _count_tiles(BWD_DKV, tiles[BWD_DKV], group)
    k_steps = nk if band is None else band.k_steps
    q_steps = nq if band is None else band.q_steps
    q_map_dq, kv_map = _index_maps(group, band, tiles[BWD_DQ])
    dq_grid, dkv_grid = (blocks, nq, k_steps), (k.shape[0], nk,
                                                group * q_steps)
    if diffusion is not None:
        dq_grid = (blocks, 1, tiles[BWD_DQ].steps)
        dkv_grid = (k.shape[0], 1, tiles[BWD_DKV].steps)
    # delta in the statistics' format, (batch x heads, seq, STAT_LANES)
    delta = jnp.broadcast_to(pack.head_sums(
        do.astype(jnp.float32) * out.astype(jnp.float32))[..., None],
        lse.shape)

    q_spec = pl.BlockSpec((1, block_q, lanes), q_map_dq)
    stat_spec = pl.BlockSpec((n, block_q, STAT_LANES), q_map_dq)
    common = dict(sm_scale=sm_scale, use_kv_mask=use_kv_mask,
                  dropout_rate=dropout_rate, pack=pack)

    dq = _call(
        functools.partial(_bwd_dq_kernel, num_k_blocks=k_steps,
                          tiles=tiles[BWD_DQ], **common),
        BWD_DQ, tiles[BWD_DQ], dq_grid,
        [
            q_spec,
            pl.BlockSpec((1, block_k, lanes), kv_map),
            pl.BlockSpec((1, block_k, lanes), kv_map),
            q_spec,
            stat_spec,
            stat_spec,
        ],
        q_spec, jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((block_q, lanes), jnp.float32)],
        interpret, lens, seed, q, k, v, do, lse, delta)

    q_map, kv_own = _dkv_index_maps(group, band, q_steps, tiles[BWD_DKV])
    q_spec_kv = pl.BlockSpec((1, block_q, lanes), q_map)
    stat_spec_kv = pl.BlockSpec((n, block_q, STAT_LANES), q_map)
    kv_spec = pl.BlockSpec((1, block_k, lanes), kv_own)

    dk, dv = _call(
        functools.partial(_bwd_dkv_kernel, num_q_blocks=q_steps,
                          tiles=tiles[BWD_DKV], **common),
        BWD_DKV, tiles[BWD_DKV], dkv_grid,
        [
            q_spec_kv,
            kv_spec,
            kv_spec,
            q_spec_kv,
            stat_spec_kv,
            stat_spec_kv,
        ],
        [kv_spec, kv_spec],
        [
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        [
            pltpu.VMEM((block_k, lanes), jnp.float32),
            pltpu.VMEM((block_k, lanes), jnp.float32),
        ],
        interpret, lens, seed, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=tuple(range(5, 16)))
def _flash(q, k, v, lens, seed, sm_scale, causal, block_q, block_k,
           use_kv_mask, dropout_rate, interpret, window, d, heads, diffusion):
    """q ``(batch x lane blocks, seq, lanes)``, k and v likewise over the
    KV heads' lane blocks (``_Pack``). ``d`` is the head width as stored,
    ``heads`` the pair (the caller's count of query heads, the stored one):
    the dropout hash is fed ``batch * heads + head`` in the caller's."""
    out, _ = _fwd(q, k, v, lens, seed, sm_scale, causal, block_q, block_k,
                  use_kv_mask, dropout_rate, interpret, window, d, heads,
                  diffusion)
    return out


def _flash_fwd_rule(q, k, v, lens, seed, *static):
    out, lse = _fwd(q, k, v, lens, seed, *static)
    return out, (q, k, v, lens, seed, out, lse)


_flash.defvjp(_flash_fwd_rule, _bwd)


def dims_of_call(eqn):
    """``(head_dim, seq_q, seq_k)`` of one of the three kernels' traced
    ``pallas_call`` equations, as stored: the one place outside the calls
    above that knows their operand format. q and k are the first two
    three-dimensional operands, ``(batch x lane blocks, seq, lanes)``; a
    lane block holds as many heads as the statistics, a row a head (``lse``:
    the forward's second result, the fifth such operand of the other two),
    have rows for each of q's."""
    # after lens, seed and, under block diffusion, the tiles' tables: all
    # one-dimensional
    tensors = [v.aval for v in eqn.invars if len(v.aval.shape) == 3]
    q, k = tensors[0], tensors[1]
    stats = eqn.outvars[1].aval if eqn.params["name"] == FWD else tensors[4]
    return q.shape[2] // (stats.shape[0] // q.shape[0]), q.shape[1], k.shape[1]


def flash_supported(q, k, min_seq=128):
    """Single gate for flash-kernel eligibility, shared by every caller
    (scaled_dot_product_attention, ring attention). Ragged sequence
    lengths are fine (the wrapper pads and the kernel masks the tail)."""
    return (jax.default_backend() == "tpu" and
            q.shape[1] >= min_seq and
            q.shape[-1] in (64, 128, 256))


def _stored_width(d):
    """The head width the kernels see: ``d`` where it divides a lane block
    or is a whole number of them, else the next width that does."""
    if d > LANES:
        return -(-d // LANES) * LANES
    return 1 << (d - 1).bit_length()


def _to_lane_blocks(x, seq, heads, d):
    """``(batch, s, h, w)``, zero-padded to ``(batch, seq, heads, d)``, as
    ``(batch x lane blocks, seq, lanes)``: neighbouring heads side by side
    in a block's lanes, the blocks ahead of the positions."""
    b, s, h, w = x.shape
    if (s, h, w) != (seq, heads, d):
        x = jnp.pad(x, ((0, 0), (0, seq - s), (0, heads - h), (0, d - w)))
    lanes = max(LANES, d)
    x = jnp.swapaxes(jnp.reshape(x, (b, seq, heads * d // lanes, lanes)), 1, 2)
    return jnp.reshape(x, (-1, seq, lanes))


def flash_attention(q, k, v, causal=False, sm_scale=None, kv_lens=None,
                    dropout_rate=0.0, dropout_seed=None,
                    block_q=None, block_k=None, interpret=False,
                    window=None, block_diffusion=None):
    """q: (batch, seq, num_heads, head_dim), k/v: (batch, seq, kv_heads,
    head_dim) with ``num_heads % kv_heads == 0`` → output shaped like q.
    Query head ``i`` attends over KV head ``i // (num_heads // kv_heads)``.

    The kernels see lane blocks (``_Pack``): one head at head width 128 or
    256, ``128 // head_dim`` neighbouring heads below that, so no padded
    row crosses HBM. Heads that do not fill whole lane blocks get zero
    heads padded on and sliced off again: KV heads up to a multiple of the
    heads a block holds (25 heads of width 64 run as 26), query heads with
    them. A head width that neither divides 128 nor is a multiple of it is
    zero-padded to the next that does; a ragged ``seq`` to a multiple of
    128.

    window: optional int, causal only — query ``i`` sees key ``j`` iff
    ``0 <= i - j < window`` (its own position and the ``window - 1``
    before it).

    How a tile is computed follows from what the kernels can observe
    (``_Tiles``), not from an argument: with ``causal``, square blocks,
    ``sq == sk``, no ``kv_lens`` or ragged tail and a window of whole
    blocks (or none), the tiles between a band's first tile and the
    diagonal are computed without a mask, and those two by sub-blocks
    over the keys that can be seen where the sub-blocks are of a size that
    pays in the kernel (``_SUB_ROWS``); any other call runs every tile
    whole under ``_visible``. The values are the same: only exact zeros
    leave the sums, and dropout draws the same bits.

    block_diffusion: optional int ``B``, the block-diffusion training mask
    over rows ``[noised ; clean]`` of ``2L`` positions (``seq = 2L``, ``L %
    B == 0``; ``block_diffusion_visible``): self-attention only, and not
    with ``causal``, ``window``, ``kv_lens`` or dropout. The grid walks the
    tiles the mask leaves and no other (``_DiffusionTiles``).

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
    diffusion = None
    if block_diffusion is not None:
        diffusion = (sq // 2, int(block_diffusion))
        if (causal or window is not None or kv_lens is not None
                or dropout_rate > 0.0 or sq != sk or sq % 2
                or diffusion[1] < 1 or diffusion[0] % diffusion[1]):
            raise ValueError(
                "block_diffusion=B masks self-attention over [noised ; "
                "clean] rows of 2L positions, L a multiple of B, and "
                "nothing else: no causal, window, kv_lens or dropout "
                f"(sq={sq}, sk={sk}, B={block_diffusion}, causal={causal}, "
                f"window={window}, kv_lens given={kv_lens is not None}, "
                f"dropout_rate={dropout_rate})")
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

    # ragged tails go to lane multiples (the kernels mask the padded key
    # columns), heads to whole lane blocks
    sq_pad = int(-(-sq // LANES) * LANES)
    sk_pad = int(-(-sk // LANES) * LANES)
    d_st = _stored_width(d)
    per_block = _Pack(d_st, h // h_kv).n
    kv_st = -(-h_kv // per_block) * per_block
    h_st = kv_st * (h // h_kv)
    qp = _to_lane_blocks(q, sq_pad, h_st, d_st)
    kp = _to_lane_blocks(k, sk_pad, kv_st, d_st)
    vp = _to_lane_blocks(v, sk_pad, kv_st, d_st)

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
    # per-(batch*lane block) scalars live in SMEM (dynamically indexed by
    # the grid's b — the Mosaic-supported home for control scalars)
    lens_blocks = jnp.repeat(lens, qp.shape[0] // b)
    if dropout_seed is None:
        seed_arr = jnp.zeros((1,), jnp.int32)
    else:
        seed_arr = jnp.asarray(dropout_seed, jnp.int32).reshape((1,))

    out = _flash(qp, kp, vp, lens_blocks, seed_arr, float(sm_scale),
                 bool(causal), int(block_q), int(block_k), bool(use_kv_mask),
                 float(dropout_rate), bool(interpret), window, d_st, (h, h_st),
                 diffusion)
    out = jnp.swapaxes(jnp.reshape(out, (b, -1, sq_pad, out.shape[-1])), 1, 2)
    return jnp.reshape(out, (b, sq_pad, h_st, d_st))[:, :sq, :h, :d]
