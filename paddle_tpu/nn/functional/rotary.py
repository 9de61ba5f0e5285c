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


def rotary_embedding(x, inv_freq, scale: float = 1.0, positions=None):
    """Rotate the first ``2 * len(inv_freq)`` lanes of ``x`` (batch, seq,
    heads, head_dim) by its positions, ``arange(seq)`` or the ``(seq,)``
    integers ``positions`` (a row that holds a sequence twice gives both
    copies of token ``i`` position ``i``); the other lanes pass through.
    Angles, cos and sin are float32; the result has ``x``'s dtype.

    ``x * C + (x @ P) * S`` over whole head vectors: ``C`` holds cos on the
    rotated lanes and 1 on the others, ``S`` sin and 0, and ``P`` is the
    rotate-half as a signed permutation matrix. The product is exact (one
    +-1 a column) and costs the MXU next to nothing, where slicing a
    128-lane vector into halves and joining them again took 89 ms of a 641
    ms step of ``laguna-xs2.seq4096`` (PERF.md section 6, PR 27).

    Staged under the scope ``rope``, which the benchmark's
    ``rope_ms_per_step`` reads."""
    with jax.named_scope("rope"):
        half, d = len(inv_freq), x.shape[-1]
        if positions is None:
            positions = jnp.arange(x.shape[1])
        angles = jnp.asarray(positions).astype(jnp.float32)[:, None] \
            * jnp.asarray(inv_freq, jnp.float32)              # (seq, r/2)
        rest = angles.shape[:-1] + (d - 2 * half,)
        cos = jnp.concatenate(
            [jnp.cos(angles) * scale] * 2 + [jnp.ones(rest)], axis=-1)
        sin = jnp.concatenate(
            [jnp.sin(angles) * scale] * 2 + [jnp.zeros(rest)], axis=-1)
        rotated = jnp.matmul(
            x, jnp.asarray(_rotate_half_matrix(half, d), x.dtype),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        out = x.astype(jnp.float32) * cos[..., None, :] \
            + rotated * sin[..., None, :]                     # over heads
        return out.astype(x.dtype)
