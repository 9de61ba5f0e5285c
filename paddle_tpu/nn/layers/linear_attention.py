"""Linear-attention layers: a sequence mixer whose state is a fixed-size
matrix a head, not a cache that grows with the sequence."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import functional as F
from ..initializer import Constant, Initializer, Uniform
from ..layer import Layer
from .common import Linear
from .norm import GatedRMSNorm


# positions a chunk of the rule: the published kernels' chunk. It changes no
# value (``F.gated_delta_rule``), only how many steps depend on each other.
CHUNK = 64


class _LogUniform(Initializer):
    """``log(U(low, high))``: a decay rate ``A = exp(A_log)`` uniform over
    ``(low, high)``."""

    def __init__(self, low, high):
        self.low, self.high = low, high

    def __call__(self, shape, dtype):
        return jnp.log(Uniform(self.low, self.high)(
            shape, jnp.float32)).astype(dtype)


class GatedDeltaNet(Layer):
    """The Gated DeltaNet mixer (Yang et al. 2024, as Qwen3-Next builds
    it): ``key_heads`` key heads of width ``d_k`` serving ``value_heads``
    value heads of width ``d_v`` (a key head serves ``value_heads /
    key_heads`` neighbouring value heads). For ``x`` ``(batch, seq,
    hidden)``:

    1. ``[q | k | v | z] = in_proj_qkvz(x)`` (``2 key_heads d_k + 2
       value_heads d_v`` columns in that order, a head's lanes together),
       ``[b | a] = in_proj_ba(x)`` (``2 value_heads``);
    2. ``[q | k | v] <- silu(causal_conv1d([q | k | v], conv_weight))``:
       depthwise over the sequence, ``conv_kernel`` taps, no bias;
    3. ``beta = sigmoid(b)``, or ``2 sigmoid(b)`` with ``allow_neg_eigval``
       (the step's transition ``I - beta k k^T`` then has the eigenvalue
       ``1 - beta`` in ``(-1, 1)``: Olmo-Hybrid's layers), ``g = -exp(A_log)
       * softplus(a + dt_bias)`` in float32, one a value head a position;
    4. ``q <- q / sqrt(sum q^2 + eps) / sqrt(d_k)``, ``k <- k / sqrt(sum
       k^2 + eps)`` over a head;
    5. ``o = F.gated_delta_rule(q, k, v, g, beta)`` in chunks of ``CHUNK``
       positions;
    6. ``y = norm(o, z)`` (``GatedRMSNorm`` over ``d_v``, one weight for
       all heads), then ``out_proj``.

    Sublayers ``in_proj_qkvz``, ``in_proj_ba``, ``norm``, ``out_proj`` and
    the scopes ``causal_conv`` and ``gated_delta_rule`` (opened by the
    functions) name every op in a device trace. ``A_log`` starts as
    ``log(U(0, 16))``, ``dt_bias`` as 1, the convolution's taps as
    ``U(-1/sqrt(kernel), 1/sqrt(kernel))``, the norm's weight as 1.
    """

    def __init__(self, hidden_size, key_heads, value_heads, d_k, d_v,
                 conv_kernel=4, epsilon=1e-6, allow_neg_eigval=False):
        super().__init__()
        if value_heads % key_heads:
            raise ValueError(f"{value_heads} value heads over {key_heads} "
                             f"key heads")
        self.key_heads, self.value_heads = key_heads, value_heads
        self.d_k, self.d_v = d_k, d_v
        self.epsilon = epsilon
        self.allow_neg_eigval = allow_neg_eigval
        self.key_dim, self.value_dim = key_heads * d_k, value_heads * d_v
        self.in_proj_qkvz = Linear(hidden_size,
                                   2 * self.key_dim + 2 * self.value_dim,
                                   bias_attr=False)
        self.in_proj_ba = Linear(hidden_size, 2 * value_heads,
                                 bias_attr=False)
        bound = 1.0 / math.sqrt(conv_kernel)
        self.conv_weight = self.create_parameter(
            (2 * self.key_dim + self.value_dim, conv_kernel),
            initializer=Uniform(-bound, bound))
        self.A_log = self.create_parameter(
            (value_heads,), initializer=_LogUniform(0.0, 16.0))
        self.dt_bias = self.create_parameter((value_heads,),
                                             initializer=Constant(1.0))
        self.norm = GatedRMSNorm(d_v, epsilon)
        self.out_proj = Linear(self.value_dim, hidden_size, bias_attr=False)

    def _unit(self, x):
        """``x / sqrt(sum x^2 + eps)`` over a head, in float32."""
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + self.epsilon)

    def forward(self, x):
        b, s, _ = x.shape
        f32 = jnp.float32
        kd, hv = self.key_dim, self.value_heads
        qkvz, ba = self.in_proj_qkvz(x), self.in_proj_ba(x)
        conv_dim = 2 * kd + self.value_dim
        mixed = F.silu(F.causal_conv1d(qkvz[..., :conv_dim],
                                       self.conv_weight.value))
        z = jnp.reshape(qkvz[..., conv_dim:], (b, s, hv, self.d_v))
        q = jnp.reshape(mixed[..., :kd], (b, s, self.key_heads, self.d_k))
        k = jnp.reshape(mixed[..., kd:2 * kd],
                        (b, s, self.key_heads, self.d_k))
        v = jnp.reshape(mixed[..., 2 * kd:], (b, s, hv, self.d_v))
        beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(self.A_log.value.astype(f32)) * jax.nn.softplus(
            ba[..., hv:].astype(f32) + self.dt_bias.value.astype(f32))
        group = hv // self.key_heads
        q = jnp.repeat((self._unit(q) / math.sqrt(self.d_k)).astype(x.dtype),
                       group, axis=2)
        k = jnp.repeat(self._unit(k).astype(x.dtype), group, axis=2)
        o = F.gated_delta_rule(q, k, v, g, beta, chunk=CHUNK)
        return self.out_proj(jnp.reshape(self.norm(o, z),
                                         (b, s, self.value_dim)))
