"""Rotary position embeddings.

``rope_frequencies`` turns one layer's parameters (theta, how many lanes
rotate, plain or YaRN) into the per-pair inverse frequencies and the factor
on cos and sin; ``rotary_embedding`` applies them to a query or key tensor
in the rotate-half convention (lane ``i`` pairs with lane ``i + r/2`` of
the ``r`` rotated lanes; the lanes past ``r`` pass through). A model with
two kinds of layer calls the first once per kind and the second per layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rope_frequencies(theta: float, rotary_dim: int, yarn=None):
    """``(inv_freq float32[rotary_dim // 2], scale)``.

    Plain: ``inv_freq[i] = theta ** (-2i / rotary_dim)``, scale 1.
    ``yarn`` is a dict with ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow`` and optionally ``attention_factor`` (Peng et
    al. 2023, as the published configs spell it): pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn less than ``beta_slow`` times have it divided by
    ``factor``, a linear ramp over the pair index joins the two, and cos and
    sin are multiplied by ``attention_factor`` (``0.1 ln(factor) + 1`` where
    the config gives none).
    """
    if rotary_dim % 2:
        raise ValueError(f"rotary_dim must be even, got {rotary_dim}")
    half = rotary_dim // 2
    pos_freqs = float(theta) ** (np.arange(half, dtype=np.float64) / half)
    if yarn is None:
        return np.asarray(1.0 / pos_freqs, np.float32), 1.0
    factor = float(yarn["factor"])
    original = yarn["original_max_position_embeddings"]

    def correction_dim(rotations):
        # the pair index whose wavelength fits `rotations` times into the
        # original context
        return rotary_dim * math.log(
            original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp                      # 1 where the frequency is kept
    inv_freq = (1.0 / (factor * pos_freqs)) * (1.0 - keep) \
        + (1.0 / pos_freqs) * keep
    scale = yarn.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return np.asarray(inv_freq, np.float32), float(scale)


def _rotate_half_matrix(half: int, d: int) -> np.ndarray:
    """``P`` with ``(x @ P)[l] = -x[l + half]`` for ``l < half``, ``x[l -
    half]`` for ``half <= l < 2 * half`` and 0 past the rotated lanes: the
    rotate-half of the first ``2 * half`` lanes as a signed permutation."""
    p = np.zeros((d, d), np.float32)
    lanes = np.arange(half)
    p[lanes + half, lanes] = -1.0
    p[lanes, lanes + half] = 1.0
    return p


def _rotate_xla(x, cos, sin, half: int):
    """``x * C + (x @ P) * S`` over the heads of ``x`` (batch, seq, heads,
    head_dim), the product exact (one +-1 a column) into float32."""
    rotated = jnp.matmul(
        x, jnp.asarray(_rotate_half_matrix(half, x.shape[-1]), x.dtype),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    out = x.astype(jnp.float32) * cos[..., None, :] \
        + rotated * sin[..., None, :]                         # over heads
    return out.astype(x.dtype)


def rope_tables(inv_freq, scale, positions, seq: int, d: int):
    """``(C, S)`` float32 ``(seq, d)``: cos of a position's angles times
    ``scale`` on the rotated lanes (lane ``i`` and ``i + r/2`` share pair
    ``i``'s angle) and 1 past them, sin and 0. ``positions`` ``(seq,)``
    integers, or None for ``arange(seq)``."""
    half = len(inv_freq)
    if positions is None:
        positions = jnp.arange(seq)
    angles = jnp.asarray(positions).astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)              # (seq, r/2)
    rest = angles.shape[:-1] + (d - 2 * half,)
    cos = jnp.concatenate(
        [jnp.cos(angles) * scale] * 2 + [jnp.ones(rest)], axis=-1)
    sin = jnp.concatenate(
        [jnp.sin(angles) * scale] * 2 + [jnp.zeros(rest)], axis=-1)
    return cos, sin


def _count_staged(path: str, norm: bool):
    """``rope_calls_staged_total{path, norm}``: one call of
    ``rotary_embedding`` being staged, by the path its input took."""
    from ... import telemetry
    if telemetry.enabled():
        telemetry.counter(
            "rope_calls_staged_total",
            "Staged calls of rotary_embedding, by the path taken and "
            "whether the QK norm is folded in").inc(
                1, path=path, norm=int(norm))


def rotary_path(x, inv_freq) -> str:
    """``"pallas"`` or ``"xla"``: the path ``rotary_embedding`` takes for
    ``x`` (batch, seq, heads, head_dim), read from the input and the
    backend as the docstring there says."""
    from ...ops.pallas import rotary as kernel
    pallas = jax.default_backend() == "tpu" and x.ndim == 4 \
        and kernel.supported(x.shape, x.dtype, len(inv_freq))
    return "pallas" if pallas else "xla"


def rotary_embedding(x, inv_freq, scale: float = 1.0, positions=None,
                     norm_weight=None, epsilon: float = 1e-6):
    """Rotate the first ``2 * len(inv_freq)`` lanes of ``x`` (batch, seq,
    heads, head_dim) by its positions, ``arange(seq)`` or the ``(seq,)``
    integers ``positions`` (a row that holds a sequence twice gives both
    copies of token ``i`` position ``i``); the other lanes pass through.
    Angles, cos and sin are float32; the result has ``x``'s dtype. With
    ``norm_weight`` ``(head_dim,)``, ``x`` is first RMS-normalised over the
    head width with ``epsilon`` and scaled by it (``F.rms_norm``): the
    per-head prologue of q and k in one call.

    ``y * C + rotate_half(y) * S`` over whole head vectors: ``C`` holds cos
    on the rotated lanes and 1 on the others, ``S`` sin and 0. Which path
    computes it is read from the input. On a TPU, with a head of whole
    128-lane blocks and a sequence a row tile divides, norm and rotation
    are one Pallas pass forward and one backward
    (``ops/pallas/rotary.py``): the rotate-half is a lane roll in
    registers, the tensor is read once and written once. Everywhere else
    (the CPU, narrow heads, a decode step) XLA computes ``F.rms_norm`` and
    then ``y * C + (y @ P) * S`` with ``P`` the rotate-half as a signed
    permutation matrix, exact at ``Precision.HIGHEST``. That product goes
    through HBM in float32: 35.81 ms of a 541.88 ms step of
    ``laguna-xs2.seq4096`` at 2.7x the tensor's bytes (ledger, PR 31),
    which is why the TPU no longer takes it; slicing a 128-lane vector
    into halves and joining them again took 89 ms there (PERF.md section
    6, PR 27).

    Staged under the scope ``rope``, which the benchmark's
    ``rope_ms_per_step`` reads; ``rope_calls_staged_total{path, norm}``
    counts the staged calls by path."""
    from ...ops.pallas import rotary as kernel
    from .norm import _unwrap, rms_norm
    norm_weight = _unwrap(norm_weight)
    with jax.named_scope("rope"):
        half, d = len(inv_freq), x.shape[-1]
        cos, sin = rope_tables(inv_freq, scale, positions, x.shape[1], d)
        path = rotary_path(x, inv_freq)
        _count_staged(path, norm_weight is not None)
        if path == "pallas":
            return kernel.rotary(x, cos, sin, half, norm_weight, epsilon)
        if norm_weight is not None:
            # a float32 scale (a zero-centred norm's 1 + w) on bf16 heads:
            # the result keeps the heads' dtype, as the kernel's does
            x = rms_norm(x, norm_weight, epsilon).astype(x.dtype)
        return _rotate_xla(x, cos, sin, half)
