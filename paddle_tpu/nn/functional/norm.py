"""Normalization functionals (reference: python/paddle/nn/functional/norm.py;
operators/batch_norm_op.*, layer_norm_op.*, group_norm_op.*, instance_norm_op.*).

XLA fuses these fully on TPU; a Pallas fused layer_norm for the residual+LN
pattern lives in paddle_tpu/ops/pallas/layer_norm.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _unwrap(p):
    return p.value if hasattr(p, "value") else p


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None, name=None):
    """Returns (out, new_running_mean, new_running_var) when training else out.

    Note: unlike the reference's in-place stat mutation
    (operators/batch_norm_op.cu), the functional form returns updated stats;
    the BatchNorm layer handles the buffer write-back so that jit-staging sees
    a pure function.
    """
    weight, bias = _unwrap(weight), _unwrap(bias)
    running_mean, running_var = _unwrap(running_mean), _unwrap(running_var)
    channel_axis = x.ndim - 1 if data_format[-1] == "C" else 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != channel_axis)
    use_batch_stats = training and not use_global_stats
    if use_batch_stats:
        mean = jnp.mean(x, axis=reduce_axes)
        var = jnp.var(x, axis=reduce_axes)
        new_rm = momentum * running_mean + (1.0 - momentum) * mean
        new_rv = momentum * running_var + (1.0 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    shape = [1] * x.ndim
    shape[channel_axis] = x.shape[channel_axis]
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + epsilon).astype(x.dtype)
    out = (x - jnp.reshape(mean, shape)) * jnp.reshape(inv, shape)
    if weight is not None:
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        out = out + jnp.reshape(bias, shape)
    if training:
        return out, new_rm, new_rv
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    weight, bias = _unwrap(weight), _unwrap(bias)
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.var(x32, axis=axes, keepdims=True)
    out = ((x32 - mean) * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def rms_norm(x, weight=None, epsilon=1e-6, name=None, offset=0.0,
             axis_name=None):
    """``x / sqrt(mean(x**2) + epsilon) * weight`` over the last axis: no
    mean is subtracted and there is no bias. The statistics are float32
    whatever ``x`` is. With ``offset`` the scale is ``offset + weight`` (a
    zero-centred weight under ``offset=1``: it starts at 0 and weight decay
    pulls the scale to 1), formed and applied in float32 before the result
    takes ``x``'s dtype. ``axis_name``: the last axis is this member's share
    of a wider one split over the named axis (a tensor-parallel layer's
    lanes), and the mean runs over all members' lanes (two ``psum``s)."""
    weight = _unwrap(weight)
    x32 = x.astype(jnp.float32)
    if axis_name is None:
        mean = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    else:
        mean = jax.lax.psum(
            jnp.sum(jnp.square(x32), axis=-1, keepdims=True), axis_name) \
            / jax.lax.psum(x.shape[-1], axis_name)
    out = x32 * jax.lax.rsqrt(mean + epsilon)
    if offset:
        return (out * (offset + weight.astype(jnp.float32))).astype(x.dtype)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * weight
    return out


def gated_rms_norm(x, z, weight, epsilon=1e-6, group_size=None,
                   norm_before_gate=True):
    """``rms_norm(x) * weight * silu(z)`` over the last axis, ``z`` of
    ``x``'s shape: the norm on a linear-attention head's output with the
    gate the input projection made beside it. ``norm_before_gate=False``
    gates first, ``rms_norm(x * silu(z)) * weight`` (Mamba-2's order), and
    ``group_size`` takes the statistics over groups of that many lanes of
    the last axis, not over all of it. All in float32; the result has
    ``x``'s dtype."""
    weight = _unwrap(weight)
    x32 = x.astype(jnp.float32)
    if not norm_before_gate:
        x32 = x32 * jax.nn.silu(z.astype(jnp.float32))
    out = x32 if group_size is None else jnp.reshape(
        x32, x32.shape[:-1] + (-1, group_size))
    out = out * jax.lax.rsqrt(
        jnp.mean(jnp.square(out), axis=-1, keepdims=True) + epsilon)
    if group_size is not None:
        out = jnp.reshape(out, x32.shape)
    out = out * weight.astype(jnp.float32)
    if norm_before_gate:
        out = out * jax.nn.silu(z.astype(jnp.float32))
    return out.astype(x.dtype)


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    weight, bias = _unwrap(weight), _unwrap(bias)
    channel_axis = x.ndim - 1 if data_format[-1] == "C" else 1
    spatial = tuple(i for i in range(2, x.ndim)) if channel_axis == 1 \
        else tuple(i for i in range(1, x.ndim - 1))
    mean = jnp.mean(x, axis=spatial, keepdims=True)
    var = jnp.var(x, axis=spatial, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[channel_axis] = x.shape[channel_axis]
    if weight is not None:
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        out = out + jnp.reshape(bias, shape)
    return out


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    weight, bias = _unwrap(weight), _unwrap(bias)
    channel_last = data_format[-1] == "C"
    if channel_last:
        x_cf = jnp.moveaxis(x, -1, 1)
    else:
        x_cf = x
    n, c = x_cf.shape[0], x_cf.shape[1]
    g = num_groups
    grouped = jnp.reshape(x_cf, (n, g, c // g) + x_cf.shape[2:])
    axes = tuple(range(2, grouped.ndim))
    mean = jnp.mean(grouped, axis=axes, keepdims=True)
    var = jnp.var(grouped, axis=axes, keepdims=True)
    out = jnp.reshape((grouped - mean) * jax.lax.rsqrt(var + epsilon), x_cf.shape)
    shape = [1, c] + [1] * (x_cf.ndim - 2)
    if weight is not None:
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        out = out + jnp.reshape(bias, shape)
    if channel_last:
        out = jnp.moveaxis(out, 1, -1)
    return out


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    if p == 2:
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    else:
        n = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(n, epsilon)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    channel_axis = x.ndim - 1 if data_format[-1] == "C" else 1
    sq = jnp.square(x)
    half = size // 2
    pad_cfg = [(0, 0, 0)] * x.ndim
    pad_cfg[channel_axis] = (half, size - 1 - half, 0)
    padded = jax.lax.pad(sq, jnp.array(0.0, sq.dtype), pad_cfg)
    window = [1] * x.ndim
    window[channel_axis] = size
    acc = jax.lax.reduce_window(padded, 0.0, jax.lax.add, tuple(window),
                                (1,) * x.ndim, [(0, 0)] * x.ndim)
    return x / jnp.power(k + alpha * acc, beta)
