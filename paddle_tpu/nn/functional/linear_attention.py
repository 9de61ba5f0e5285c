"""Linear attention: the gated delta rule and the short causal convolution
that feeds it (Gated DeltaNet, Yang et al. 2024).

Per head, with a state ``S`` of ``d_k x d_v`` that starts at 0, position
``t`` of the sequence does::

    S <- exp(g_t) S                      the state decays (g_t <= 0)
    u  = beta_t (v_t - S^T k_t)          what the key retrieves is corrected
    S <- S + k_t u^T                     by a step of size beta_t
    o_t = S^T q_t

``gated_delta_rule`` computes it. The recurrence over positions
(``path="recurrent"``, one ``lax.scan`` step a position) is the
definition and what the tests hold everything else to. The path a layer
takes is the chunked one (the WY form): the sequence is cut into chunks of
``chunk`` positions, inside a chunk the ``chunk`` dependent updates become
one unit lower-triangular system

    (I + tril(diag(beta) (K K^T * decay), -1)) [W | U] = diag(beta) [K e^g | V]

and only the state is carried from chunk to chunk: ``seq / chunk``
dependent steps where the recurrence has ``seq``. Two implementations of
that one algorithm, chosen from what the call can observe (backend, head
widths, chunk, dtype, sequence), never by the caller:

- on a TPU, at head widths of one 128-lane block and the layer's chunk of
  64, two Pallas kernels a (row, head) (``ops/pallas/gated_delta.py``:
  ``gated_delta_fwd`` / ``gated_delta_bwd``): a chunk's system, its
  inverse, ``W``, ``U`` and the outputs stay in VMEM and the state is
  carried in a scratch; HBM sees the operands, ``o`` and one state a chunk.
  No head groups and no checkpoint: the grid is a (row, head) at a time;
- everywhere else (the CPU, narrow toy heads, other chunk sizes) XLA's
  ``_chunked`` below: the systems of all chunks of ``GROUP_HEADS`` heads
  solved at once as batched matrix products (``inverse_unit_lower``), the
  state carried by a ``lax.scan`` of two products a step
  (``_chunk_states``), and what reads the states (the outputs) and what
  their cotangents feed again products over all chunks at once. It is the
  yardstick the kernels are tested against, beside the recurrence.

Decays, the triangular system and the state are float32 whatever the
operands are; a product with a float32 operand runs at ``PRECISION`` (the
kernels split such an operand into two bf16 terms and keep the cross
terms: the same three passes). The backward pass keeps one state a chunk
(``d_k x d_v`` float32 a head), never one a position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# float32 products (the system's inverse, everything the state touches) on
# XLA's paths, chunked and recurrent: three bf16 passes on a TPU's MXU, an
# error of 2**-16 of a term or less; the CPU computes float32 as it is. The
# kernels ask for the same three passes themselves (``gated_delta._dot``)
# and do not read this value. On one layer's rule at 8,192 positions one
# pass (the TPU's default, which rounds the state to bf16 in every product)
# lay 3.7e-3 of the six-pass output's norm away, three passes 2.3e-4; over
# the whole training step six passes (HIGHEST) cost 0.8% of the tokens a
# second (PERF.md section 6, PR 33). The benchmark's comparison does not
# tell a bf16-rounded state from a sound one under bf16 activations
# (PERF.md section 7); ``chip_smoke.py``'s ``kernels`` phase does, against
# this path at HIGHEST (``check_gated_delta_precision``), and the CPU tests
# hold both implementations to the recurrence in float32.
PRECISION = lax.Precision.HIGH
# heads XLA's chunked path works on at once (the kernels have no groups). A
# (row, head) pair's chunks hold about 70 MB of float32 at 8,192 positions
# (the systems, W, U, the states) and three times that in the backward
# pass; all 32 heads of two rows at once took a training step past a 16 GB
# chip (16.08 GB compiled, 13.35 GB in two groups of 16 heads; PERF.md
# section 6, PR 33). The rule reads the heads and not the batch, so that
# one row takes the path two rows take.
GROUP_HEADS = 16


def causal_conv1d(x, w, bias=None):
    """Depthwise causal convolution over the sequence: ``x`` ``(batch,
    seq, channels)``, ``w`` ``(channels, kernel)``, ``bias`` ``(channels,)``
    or None::

        out[t] = sum_j w[:, j] * x[t - (kernel - 1) + j]  [+ bias]

    with zeros to the left of position 0, so position ``t`` reads
    ``t - kernel + 1 .. t`` and never ``t + 1`` (``w[:, -1]`` weighs the
    position itself: a PyTorch ``Conv1d(groups=channels, padding=kernel -
    1)`` cut to ``seq``). ``kernel`` shifted products in float32; the
    result has ``x``'s dtype. Staged under the scope ``causal_conv``."""
    with jax.named_scope("causal_conv"):
        seq, kernel = x.shape[1], w.shape[-1]
        padded = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
        w = w.astype(jnp.float32)
        out = sum(padded[:, j:j + seq].astype(jnp.float32) * w[:, j]
                  for j in range(kernel))
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out.astype(x.dtype)


def _mm(spec, a, b):
    """A product with a float32 operand, accumulated in float32."""
    return jnp.einsum(spec, a, b, precision=PRECISION,
                      preferred_element_type=jnp.float32)


# -- the unit lower-triangular system ----------------------------------------

def _merge_blocks(a, t, s):
    """``t`` holds the inverses of ``I + a``'s diagonal blocks of ``s``
    rows; returns those of its diagonal blocks of all rows, doubling ``s``
    a step. A block of ``2s`` rows ``[[X, 0], [B, Y]]`` has the inverse
    ``[[X', 0], [-Y' B X', Y']]``: with ``B`` the part of ``a`` below the
    first half and left of the second, in every block of ``2s`` rows at
    once, that is ``t - t B t``."""
    size = a.shape[-1]
    i = jnp.arange(size)
    row, col = i[:, None], i[None, :]
    while s < size:
        below = (row // (2 * s) == col // (2 * s)) \
            & (row // s % 2 == 1) & (col // s % 2 == 0)
        t = t - _mm("...ij,...jk->...ik",
                    _mm("...ij,...jk->...ik", t, jnp.where(below, a, 0)), t)
        s *= 2
    return t


# rows of the diagonal blocks that are inverted on their own first: the
# early steps then pass over a quarter of a 64-row system's entries. The
# steps are bound by memory, not by the MXU (a 64 x 64 x 64 product is 11
# operations a byte of its operands).
_BASE = 16


def _inverse_unit_lower(a):
    size = a.shape[-1]
    if size & (size - 1):
        raise ValueError(f"a system of {size} rows: a power of two is needed")
    if size <= _BASE:
        return _merge_blocks(a, jnp.broadcast_to(
            jnp.eye(size, dtype=a.dtype), a.shape), 1)
    n = size // _BASE
    blocks = jnp.reshape(a, a.shape[:-2] + (n, _BASE, n, _BASE))
    diagonal = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)
    small = _merge_blocks(diagonal, jnp.broadcast_to(
        jnp.eye(_BASE, dtype=a.dtype), diagonal.shape), 1)
    t = jnp.einsum("...nij,nm->...nimj", small, jnp.eye(n, dtype=a.dtype))
    return _merge_blocks(a, jnp.reshape(t, a.shape), _BASE)


@jax.custom_vjp
def inverse_unit_lower(a):
    """``(I + a)^-1`` for ``a`` ``(..., n, n)`` strictly lower triangular,
    ``n`` a power of two, as ``2 log2(n)`` batched matrix products: blocks
    of 1, 2, 4, ... rows are inverted from the inverses of their halves
    (twelve products at ``n`` = 64, where forward substitution has 63
    dependent row updates). It is the substitution's arithmetic in another
    order, so it stays as accurate where keys repeat and ``a`` is far from
    small, which the shorter ``prod_k (I + (-a)^(2^k))`` does not (its
    powers reach 1e17 before they cancel, for 64 equal keys). What lies on
    or above the diagonal of ``a`` is not read. The cotangent is two
    products, ``-T^T dT T^T``."""
    return _inverse_unit_lower(a)


def _inverse_fwd(a):
    t = _inverse_unit_lower(a)
    return t, t


def _inverse_bwd(t, dt):
    n = t.shape[-1]
    da = -_mm("...ji,...jk->...ik", t, _mm("...ij,...kj->...ik", dt, t))
    return (jnp.where(jnp.tril(jnp.ones((n, n), bool), -1), da, 0),)


inverse_unit_lower.defvjp(_inverse_fwd, _inverse_bwd)


# -- the state, chunk to chunk -----------------------------------------------

def _chunks_first(x):
    """``(batch, heads, chunks, ...)`` with the chunks in front, as a scan
    walks them."""
    return jnp.moveaxis(x, 2, 0)


def _states_fwd_scan(w, u, k, dte, a):
    def step(s, x):
        w_i, u_i, k_i, dte_i, a_i = x
        v_i = u_i - _mm("bhck,bhkv->bhcv", w_i, s)
        nxt = a_i[..., None, None] * s \
            + _mm("bhck,bhcv->bhkv", k_i, dte_i[..., None] * v_i)
        return nxt, (s, v_i)

    b, h, _, _, dk = w.shape
    s0 = jnp.zeros((b, h, dk, u.shape[-1]), jnp.float32)
    _, (s, v) = lax.scan(step, s0,
                         tuple(map(_chunks_first, (w, u, k, dte, a))))
    return jnp.moveaxis(s, 0, 2), jnp.moveaxis(v, 0, 2)


@jax.custom_vjp
def _chunk_states(w, u, k, dte, a):
    """The one dependent loop: ``(S, V)`` with ``S[n]`` the state entering
    chunk ``n`` ``(batch, heads, chunks, d_k, d_v)`` and ``V[n] = U[n] -
    W[n] S[n]`` the chunk's corrected values, from ``S[0] = 0`` and ``S[n +
    1] = a[n] S[n] + K[n]^T (dte[n] * V[n])`` (``dte``: a position's decay
    to the end of its chunk, ``a`` the whole chunk's). A ``lax.scan`` of two
    products a step forward and two backward; whatever else the cotangents
    need (``dW``, ``dK``, ``ddte``, ``da``) is products over all chunks at
    once, from the states the forward returned: nothing else is kept."""
    return _states_fwd_scan(w, u, k, dte, a)


def _states_fwd(w, u, k, dte, a):
    s, v = _states_fwd_scan(w, u, k, dte, a)
    return (s, v), (w, k, dte, a, s, v)


def _states_bwd(res, cts):
    w, k, dte, a, s, v = res
    ds, dv = cts

    def step(c, x):
        """``c``: the cotangent of the state LEAVING this chunk."""
        w_i, k_i, dte_i, a_i, ds_i, dv_i = x
        dv_i = dv_i + dte_i[..., None] * _mm("bhck,bhkv->bhcv", k_i, c)
        before = ds_i + a_i[..., None, None] * c \
            - _mm("bhck,bhcv->bhkv", w_i, dv_i)
        return before, (c, dv_i)

    _, (c, dv) = lax.scan(
        step, jnp.zeros_like(s[:, :, 0]),
        tuple(map(_chunks_first, (w, k, dte, a, ds, dv))), reverse=True)
    c, dv = jnp.moveaxis(c, 0, 2), jnp.moveaxis(dv, 0, 2)
    dw = -_mm("bhncv,bhnkv->bhnck", dv, s)
    dk = _mm("bhncv,bhnkv->bhnck", dte[..., None] * v, c).astype(k.dtype)
    ddte = jnp.sum(v * _mm("bhnck,bhnkv->bhncv", k, c), axis=-1)
    da = jnp.sum(c * s, axis=(-1, -2))
    return dw, dv, dk, ddte, da


_chunk_states.defvjp(_states_fwd, _states_bwd)


# -- the two paths -----------------------------------------------------------

def _recurrent(q, k, v, g, beta):
    f32 = jnp.float32

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - _mm("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, _mm("bhkv,bhk->bhv", s, q_t)

    b, _, h, dk = q.shape
    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def _head_groups(heads, group_heads):
    """The groups ``heads`` heads are worked on in: ``group_heads`` a
    group, or one group where that does not divide them."""
    return heads // group_heads if heads % group_heads == 0 else 1


def _chunked(q, k, v, g, beta, chunk, group_heads):
    """``_chunked_heads`` over all heads, ``group_heads`` of them at once:
    heads are independent, so a group at a time, each recomputed in the
    backward pass, and one group's intermediates exist at once."""
    groups = _head_groups(q.shape[2], group_heads)
    if groups == 1:
        return _chunked_heads(q, k, v, g, beta, chunk)

    def split(x):
        """``(batch, seq, heads, ...)`` with the groups in front."""
        x = jnp.reshape(x, x.shape[:2] + (groups, -1) + x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    o = lax.map(jax.checkpoint(lambda xs: _chunked_heads(*xs, chunk)),
                tuple(map(split, (q, k, v, g, beta))))
    return jnp.reshape(jnp.moveaxis(o, 0, 2), v.shape)


def _chunked_heads(q, k, v, g, beta, chunk):
    f32 = jnp.float32
    b, seq, h, _ = q.shape
    pad = -seq % chunk
    if pad:
        # beta = 0 writes nothing and g = 0 decays nothing: the positions
        # past the end leave the state as it is
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    n = (seq + pad) // chunk

    def chunks(x):
        """``(batch, seq, heads, ...)`` as ``(batch, heads, chunks, chunk,
        ...)``."""
        x = jnp.reshape(x, (b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    bc = chunks(beta).astype(f32)
    gc = jnp.cumsum(chunks(g).astype(f32), axis=-1)       # within a chunk
    i = jnp.arange(chunk)
    seen = i[:, None] >= i[None, :]
    # exp(g_j+1 + .. + g_i) for i >= j, 0 above the diagonal: the exponent
    # is masked, not the result, so that nothing overflows on the way
    decay = jnp.exp(jnp.where(seen, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    # the operands' own products, accumulated in float32
    kk = jnp.einsum("bhnck,bhnjk->bhncj", kc, kc, preferred_element_type=f32)
    qk = jnp.einsum("bhnck,bhnjk->bhncj", qc, kc, preferred_element_type=f32)
    a = jnp.where(i[:, None] > i[None, :], bc[..., None] * kk * decay, 0)
    t = inverse_unit_lower(a)
    # the columns' factors go to the system's inverse and the rows' to the
    # products' results, so that q, k and v enter the products as they are
    # and no scaled float32 copy of them is made
    w = _mm("bhncj,bhnjk->bhnck", t * (bc * jnp.exp(gc))[..., None, :], kc)
    u = _mm("bhncj,bhnjv->bhncv", t * bc[..., None, :], vc)
    last = gc[..., -1]
    s, v_new = _chunk_states(w, u, kc, jnp.exp(last[..., None] - gc),
                             jnp.exp(last))
    o = jnp.exp(gc)[..., None] * _mm("bhnck,bhnkv->bhncv", qc, s) \
        + _mm("bhncj,bhnjv->bhncv", qk * decay, v_new)
    o = jnp.reshape(jnp.moveaxis(o, 1, 3), (b, seq + pad, h, o.shape[-1]))
    return o[:, :seq].astype(v.dtype)


def _count_staged(path: str, chunks: int):
    """``linear_attn_calls_staged_total{path}`` and
    ``gated_delta_chunks_total``: one call of ``gated_delta_rule`` being
    staged, and the dependent chunk steps a row of it has."""
    from ... import telemetry
    if telemetry.enabled():
        telemetry.counter(
            "linear_attn_calls_staged_total",
            "Staged calls of gated_delta_rule, by the path taken").inc(
                1, path=path)
        telemetry.counter(
            "gated_delta_chunks_total",
            "Chunk states a row walks one after another, summed over the "
            "staged calls of gated_delta_rule").inc(chunks)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     path: str = "chunked"):
    """The gated delta rule over a sequence (the module's opening lines):
    ``q``, ``k`` ``(batch, seq, heads, d_k)``, ``v`` ``(batch, seq, heads,
    d_v)``, ``g`` (log decay, ``<= 0``) and ``beta`` (step size) ``(batch,
    seq, heads)``; returns ``o`` ``(batch, seq, heads, d_v)`` in ``v``'s
    dtype. ``q`` and ``k`` arrive normalised and scaled as the model wants
    them; a key head that serves several value heads arrives repeated.
    Operands are read in the dtype they come in; decays, the triangular
    system and the state are float32.

    ``path="chunked"`` (what a layer calls) works in chunks of ``chunk``
    positions, a power of two; a sequence that is no multiple of it is
    padded with ``beta = 0, g = 0`` and cut again. Which implementation
    runs is read from the input (the module's opening lines): the Pallas
    kernels on a TPU where ``gated_delta.supported`` takes the shapes, else
    XLA's batched products, ``GROUP_HEADS`` heads at once, the groups one
    after another and each recomputed in the backward pass, which bounds
    what a call holds. ``path="recurrent"`` is the definition, a step a
    position: the yardstick of the tests, too slow and too large in the
    backward pass for a real row. Staged under the scope
    ``gated_delta_rule``; ``linear_attn_calls_staged_total{path=pallas|
    chunked|recurrent}`` counts the staged calls by what was decided
    (``chunked``: XLA's) and ``gated_delta_chunks_total`` their chunk
    steps."""
    if path not in ("chunked", "recurrent"):
        raise ValueError(f"unknown path {path!r}")
    from ...ops.pallas import gated_delta as kernel
    seq = q.shape[1]
    if path == "chunked" and jax.default_backend() == "tpu" \
            and q.dtype == k.dtype == v.dtype \
            and kernel.supported(q.shape, v.shape, v.dtype, chunk):
        path = "pallas"
    _count_staged(path, seq if path == "recurrent" else -(-seq // chunk))
    with jax.named_scope("gated_delta_rule"):
        if path == "recurrent":
            return _recurrent(q, k, v, g, beta)
        if path == "pallas":
            return kernel.gated_delta(q, k, v, g, beta)
        return _chunked(q, k, v, g, beta, chunk, GROUP_HEADS)
