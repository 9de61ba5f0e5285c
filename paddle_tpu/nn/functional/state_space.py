"""The state-space duality scan of Mamba-2 (Dao and Gu 2024).

Per head ``h``, with ``B`` and ``C`` read from the head's group ``g`` and a
state ``S`` of ``P x N`` that starts at 0, position ``t`` does::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

In the gated delta rule's terms (``linear_attention.py``) this is ``q =
C``, ``k = B``, ``v = dt x`` and ``g = dt A`` with no delta correction, so
its chunked form needs no triangular system. ``ssd_scan`` computes it. The
recurrence over positions (``path="recurrent"``, one ``lax.scan`` step a
position) is the definition and what the tests hold the other path to. The
path a layer takes is the chunked one: the sequence is cut into chunks of
``chunk`` positions; inside a chunk the outputs are one masked product a
head, ``(C B^T * L) (dt x)`` with ``L_ij = exp(a_j+1 + .. + a_i)`` for ``i
>= j``; what a chunk adds to the state is one product a head; and the state
is carried from chunk to chunk by a ``lax.scan`` that multiplies and adds
and forms no product (``_carry``). Every product is batched over all
chunks. ``C B^T`` is formed once a group, for all of the group's heads.

Decays and the state are float32 whatever the operands are; a product
with a float32 operand runs at ``linear_attention.PRECISION``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .linear_attention import _mm


def _carry(increments, decays):
    """``S[n]``, the state entering chunk ``n``, from ``S[0] = 0`` and
    ``S[n + 1] = decays[n] S[n] + increments[n]``: ``increments`` ``(chunks,
    ...)`` float32, ``decays`` ``(chunks, ...)`` broadcast against them. The
    one dependent loop; it multiplies and adds. Its autodiff keeps the
    states it returns and nothing else.

    A twin of ``linear_attention._chunk_states`` and not a call of it:
    that scan corrects each chunk's values by the state (``V = U - W S``),
    a product a step and its cotangent, which ``W = 0`` would spend on
    zeros; and its keys are a head's where ``B`` is a group's."""
    def step(s, inc):
        d, a = inc
        return a * s + d, s

    _, states = lax.scan(step, jnp.zeros_like(increments[0]),
                         (increments, decays))
    return states


def _recurrent(x, dt, a, b, c):
    """``y`` without ``D``, a ``lax.scan`` step a position: ``x`` ``(batch,
    seq, groups, r, P)``, ``dt``, ``a`` ``(batch, seq, groups, r)``, ``b``,
    ``c`` ``(batch, seq, groups, N)``."""
    f32 = jnp.float32

    def step(s, inp):
        x_t, dt_t, a_t, b_t, c_t = inp
        s = jnp.exp(a_t)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[:, :, None, None, :]
        return s, _mm("bgrpn,bgn->bgrp", s, c_t)

    batch, _, groups, r, p = x.shape
    xs = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, a, b, c))
    s0 = jnp.zeros((batch, groups, r, p, b.shape[-1]), f32)
    return jnp.moveaxis(lax.scan(step, s0, xs)[1], 0, 1)


def _chunked(x, dt, a, b, c, chunk):
    """``y`` without ``D`` in chunks of ``chunk`` positions, shapes as
    ``_recurrent`` takes them."""
    f32 = jnp.float32
    batch, seq, groups, r, p = x.shape
    pad = -seq % chunk
    if pad:
        # dt = 0 writes nothing and decays nothing: the positions past the
        # end leave the state as it is
        x, dt, a, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                  * (v.ndim - 2)) for v in (x, dt, a, b, c))
    n = (seq + pad) // chunk

    def chunks(v):
        """``(batch, seq, ...)`` as ``(batch, chunks, chunk, ...)``."""
        return jnp.reshape(v, (batch, n, chunk) + v.shape[2:])

    xc, bc, cc = chunks(x), chunks(b), chunks(c)
    dtc = chunks(dt).astype(f32)
    acs = jnp.cumsum(chunks(a).astype(f32), axis=2)   # within a chunk
    i = jnp.arange(chunk)
    seen = i[:, None] >= i[None, :]
    # a group's C B^T, once for its heads: the operands' own product
    cb = jnp.einsum("bnigk,bnjgk->bngij", cc, bc, preferred_element_type=f32)
    # exp(a_j+1 + .. + a_i) for i >= j, 0 above the diagonal: the exponent
    # is masked, not the result, so that nothing overflows on the way
    acs_h = jnp.moveaxis(acs, 2, -1)                   # (b, n, g, r, chunk)
    decay = jnp.exp(jnp.where(seen, acs_h[..., :, None] - acs_h[..., None, :],
                              -jnp.inf))
    scores = cb[:, :, :, None] * decay * jnp.moveaxis(dtc, 2, -1)[..., None, :]
    y = _mm("bngrij,bnjgrp->bnigrp", scores, xc)
    # what a chunk adds to the state, each position decayed to the chunk's
    # end: one product a head
    last = acs[:, :, -1:]
    to_end = (jnp.exp(last - acs) * dtc)[..., None] * xc
    increments = _mm("bncgrp,bncgk->bngrpk", to_end, bc)
    states = _carry(jnp.moveaxis(increments, 1, 0),
                    jnp.moveaxis(jnp.exp(last[:, :, 0]), 1, 0)[..., None, None])
    y = y + jnp.exp(acs)[..., None] * _mm(
        "bncgk,nbgrpk->bncgrp", cc, states)
    return jnp.reshape(y, (batch, n * chunk, groups, r, p))[:, :seq]


def _count_staged(path: str, chunks: int):
    """``ssd_scan_calls_staged_total{path}`` and ``ssd_chunks_total``: one
    call of ``ssd_scan`` being staged, and the dependent steps a row of it
    has."""
    from ... import telemetry
    if telemetry.enabled():
        telemetry.counter(
            "ssd_scan_calls_staged_total",
            "Staged calls of ssd_scan, by the path taken").inc(1, path=path)
        telemetry.counter(
            "ssd_chunks_total",
            "Chunk states a row walks one after another, summed over the "
            "staged calls of ssd_scan").inc(chunks)


def ssd_scan(x, dt, A, B, C, D=None, chunk: int = 128,
             path: str = "chunked"):
    """Mamba-2's scan (the module's opening lines): ``x`` ``(batch, seq,
    heads, P)``, ``dt`` ``(batch, seq, heads)`` the step, positive (the
    caller's softplus is done), ``A`` ``(heads,)`` negative, ``B``, ``C``
    ``(batch, seq, groups, N)`` with ``heads / groups`` neighbouring heads
    a group, ``D`` ``(heads,)`` or None; returns ``y`` ``(batch, seq,
    heads, P)`` in ``x``'s dtype. Operands are read in the dtype they come
    in; decays and the state are float32.

    ``path="chunked"`` (what a layer calls) works in chunks of ``chunk``
    positions; a sequence that is no multiple of it is padded with ``dt =
    0`` and cut again. ``path="recurrent"`` is the definition, a step a
    position: the yardstick of the tests, too slow and too large in the
    backward pass for a real row. Staged under the scope ``ssd_scan``;
    ``ssd_scan_calls_staged_total{path=chunked|recurrent}`` counts the
    staged calls and ``ssd_chunks_total`` their dependent steps (``seq``
    on the recurrent path)."""
    if path not in ("chunked", "recurrent"):
        raise ValueError(f"unknown path {path!r}")
    batch, seq, heads, p = x.shape
    groups = B.shape[2]
    if heads % groups or B.shape != C.shape:
        raise ValueError(f"{heads} heads over B {B.shape} and C {C.shape}")
    _count_staged(path, seq if path == "recurrent" else -(-seq // chunk))
    with jax.named_scope("ssd_scan"):
        f32 = jnp.float32
        dt = dt.astype(f32)
        a = dt * A.astype(f32)
        shape = (batch, seq, groups, heads // groups)
        args = (jnp.reshape(x, shape + (p,)), jnp.reshape(dt, shape),
                jnp.reshape(a, shape), B, C)
        if path == "recurrent":
            y = _recurrent(*args)
        else:
            y = _chunked(*args, chunk)
        y = jnp.reshape(y, x.shape)
        if D is not None:
            y = y + x.astype(f32) * D.astype(f32)[:, None]
        return y.astype(x.dtype)
