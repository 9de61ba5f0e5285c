"""State-space layers: a sequence mixer whose state is a fixed-size matrix a
head, carried along the sequence by a decay and an input of its own."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import functional as F
from ..initializer import Constant, Initializer, Uniform
from ..layer import Layer
from .common import Linear
from .norm import GatedRMSNorm


class _LogArange(Initializer):
    """``log(1), log(2), .., log(n)``: decay rates ``A = exp(A_log)`` of 1
    to ``n``, one a head."""

    def __call__(self, shape, dtype):
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
                       ).astype(dtype)


class _StepBias(Initializer):
    """``softplus^-1(dt)``, ``dt`` log-uniform over ``(low, high)`` and
    floored at ``floor``: ``softplus(dt_bias)`` is then that step."""

    def __init__(self, low, high, floor):
        self.low, self.high, self.floor = low, high, floor

    def __call__(self, shape, dtype):
        dt = jnp.exp(Uniform(math.log(self.low), math.log(self.high))(
            shape, jnp.float32))
        dt = jnp.maximum(dt, self.floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class Mamba2Mixer(Layer):
    """The Mamba-2 mixer (Dao and Gu 2024, as Nemotron-H builds it):
    ``num_heads`` heads of width ``head_dim`` (``P``) over ``n_groups``
    groups of ``B`` and ``C`` of width ``state_size`` (``N``). For ``x``
    ``(batch, seq, hidden)``, with ``I = num_heads P`` and ``G N`` the
    groups' width:

    1. ``[z | xBC | dt] = in_proj(x)`` (``I``, ``I + 2 G N``, ``num_heads``
       columns in that order; ``xBC`` is ``[x | B | C]``);
    2. ``xBC <- silu(causal_conv1d(xBC, conv_weight, conv_bias))``:
       depthwise over the sequence, ``conv_kernel`` taps, with a bias;
    3. ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` in float32;
    4. ``y = F.ssd_scan(x, dt, A, B, C, D)`` in chunks of ``chunk``
       positions (head ``h`` reads group ``h // (num_heads / n_groups)``);
    5. ``y = norm(y, z)``: the gate first, ``rms_norm(y * silu(z)) * w``
       over ``n_groups`` groups of ``I / n_groups`` lanes
       (``GatedRMSNorm(norm_before_gate=False)``), then ``out_proj``.

    Sublayers ``in_proj``, ``norm``, ``out_proj``, and the scopes
    ``causal_conv`` and ``ssd_scan`` (opened by the functions) and
    ``gated_norm`` (around the norm) name every op in a device trace. The
    initialisers are the published ones: ``A_log = log(1 .. num_heads)``,
    ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform over
    ``(time_step_min, time_step_max)`` floored at ``time_step_floor``, ``D
    = 1``, the convolution's taps and bias ``U(-1/sqrt(kernel),
    1/sqrt(kernel))`` (PyTorch's ``Conv1d`` default), the norm's weight 1.
    """

    def __init__(self, hidden_size, num_heads, head_dim, n_groups,
                 state_size, conv_kernel=4, chunk=128, epsilon=1e-5,
                 time_step_min=1e-3, time_step_max=0.1,
                 time_step_floor=1e-4):
        super().__init__()
        if num_heads % n_groups:
            raise ValueError(f"{num_heads} heads over {n_groups} groups")
        self.num_heads, self.head_dim = num_heads, head_dim
        self.n_groups, self.state_size = n_groups, state_size
        self.chunk = chunk
        self.inner = num_heads * head_dim
        self.conv_dim = self.inner + 2 * n_groups * state_size
        self.in_proj = Linear(hidden_size, self.inner + self.conv_dim
                              + num_heads, bias_attr=False)
        bound = 1.0 / math.sqrt(conv_kernel)
        self.conv_weight = self.create_parameter(
            (self.conv_dim, conv_kernel), initializer=Uniform(-bound, bound))
        self.conv_bias = self.create_parameter(
            (self.conv_dim,), initializer=Uniform(-bound, bound))
        self.A_log = self.create_parameter((num_heads,),
                                           initializer=_LogArange())
        self.D = self.create_parameter((num_heads,),
                                       initializer=Constant(1.0))
        self.dt_bias = self.create_parameter(
            (num_heads,), initializer=_StepBias(
                time_step_min, time_step_max, time_step_floor))
        self.norm = GatedRMSNorm(self.inner, epsilon,
                                 group_size=self.inner // n_groups,
                                 norm_before_gate=False)
        self.out_proj = Linear(self.inner, hidden_size, bias_attr=False)

    def forward(self, x):
        b, s, _ = x.shape
        f32 = jnp.float32
        h, g, n = self.num_heads, self.n_groups, self.state_size
        zxbcdt = self.in_proj(x)
        z = zxbcdt[..., :self.inner]
        xbc = F.silu(F.causal_conv1d(
            zxbcdt[..., self.inner:self.inner + self.conv_dim],
            self.conv_weight.value, self.conv_bias.value))
        dt = jax.nn.softplus(zxbcdt[..., -h:].astype(f32)
                             + self.dt_bias.value.astype(f32))
        xs = jnp.reshape(xbc[..., :self.inner], (b, s, h, self.head_dim))
        B = jnp.reshape(xbc[..., self.inner:self.inner + g * n], (b, s, g, n))
        C = jnp.reshape(xbc[..., self.inner + g * n:], (b, s, g, n))
        y = F.ssd_scan(xs, dt, -jnp.exp(self.A_log.value.astype(f32)), B, C,
                       self.D.value, chunk=self.chunk)
        with jax.named_scope("gated_norm"):
            y = self.norm(jnp.reshape(y, (b, s, self.inner)), z)
        return self.out_proj(y)
