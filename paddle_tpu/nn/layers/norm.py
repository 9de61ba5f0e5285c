"""Normalization layers (reference: python/paddle/nn/layer/norm.py).

BatchNorm running stats are Layer buffers: training forward reassigns them,
which the functionalization bridge captures as pure outputs under jit
(see paddle_tpu/jit/functionalization.py) — the TPU-native version of the
reference's in-place stat mutation in operators/batch_norm_op.cu.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import functional as F
from ..initializer import Constant, _to_initializer
from ..layer import Layer


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = data_format
        self.use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr,
                initializer=_to_initializer(weight_attr, None) or Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (num_features,), attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", jnp.zeros((num_features,), dtype=jnp.float32))
        self.register_buffer("_variance", jnp.ones((num_features,), dtype=jnp.float32))

    def forward(self, x):
        if self.training and not self.use_global_stats:
            out, new_rm, new_rv = F.batch_norm(
                x, self._mean, self._variance, self.weight, self.bias,
                training=True, momentum=self.momentum, epsilon=self.epsilon,
                data_format=self.data_format,
                use_global_stats=self.use_global_stats)
            self._mean = new_rm
            self._variance = new_rv
            return out
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=False, momentum=self.momentum, epsilon=self.epsilon,
            data_format=self.data_format, use_global_stats=self.use_global_stats)

    def extra_repr(self):
        return f"num_features={self.num_features}, momentum={self.momentum}"


class BatchNorm(_BatchNormBase):
    """Legacy paddle.nn.BatchNorm(num_channels) (reference: fluid/dygraph/nn.py)."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5,
                 param_attr=None, bias_attr=None, dtype="float32",
                 data_layout="NCHW", in_place=False, use_global_stats=False,
                 **kw):
        super().__init__(num_channels, momentum, epsilon, param_attr, bias_attr,
                         data_layout, use_global_stats or None)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCL", name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, "NCW" if data_format in ("NCL", "NCW") else "NWC")


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCDHW", name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BatchNorm (reference: nn/layer/norm.py SyncBatchNorm +
    operators/sync_batch_norm_op.cu).

    When running inside shard_map/pmap with a data-parallel axis named
    ``axis_name`` (default "data"), batch statistics are averaged over that
    axis with lax.pmean — the XLA collective replacing the reference's NCCL
    allreduce of partial sums.
    """

    axis_name = "data"

    def forward(self, x):
        import jax

        if not self.training or self.use_global_stats:
            return super().forward(x)
        try:
            jax.lax.axis_index(self.axis_name)  # raises if axis not bound
            in_spmd = True
        except Exception:
            in_spmd = False
        if not in_spmd:
            return super().forward(x)
        channel_axis = x.ndim - 1 if self.data_format[-1] == "C" else 1
        reduce_axes = tuple(i for i in range(x.ndim) if i != channel_axis)
        mean = jax.lax.pmean(jnp.mean(x, axis=reduce_axes), self.axis_name)
        mean_sq = jax.lax.pmean(jnp.mean(jnp.square(x), axis=reduce_axes),
                                self.axis_name)
        var = mean_sq - jnp.square(mean)
        self._mean = self.momentum * self._mean + (1 - self.momentum) * mean
        self._variance = self.momentum * self._variance + (1 - self.momentum) * var
        shape = [1] * x.ndim
        shape[channel_axis] = x.shape[channel_axis]
        import jax.lax as lax
        inv = lax.rsqrt(var + self.epsilon)
        out = (x - jnp.reshape(mean, shape)) * jnp.reshape(inv, shape)
        if self.weight is not None:
            out = out * jnp.reshape(self.weight.value, shape)
        if self.bias is not None:
            out = out + jnp.reshape(self.bias.value, shape)
        return out

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        """Recursively convert BatchNorm layers to SyncBatchNorm."""
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            new = SyncBatchNorm(layer.num_features, layer.momentum, layer.epsilon,
                                data_format=layer.data_format)
            if layer.weight is not None:
                new.weight = layer.weight
            if layer.bias is not None:
                new.bias = layer.bias
            new._buffers["_mean"] = layer._mean
            new._buffers["_variance"] = layer._variance
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer.add_sublayer(name, cls.convert_sync_batchnorm(sub))
        return layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                self.normalized_shape, attr=weight_attr,
                initializer=_to_initializer(weight_attr, None) or Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                self.normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self.normalized_shape, self.weight, self.bias,
                            self.epsilon)

    def extra_repr(self):
        return f"normalized_shape={self.normalized_shape}"


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis with a learned scale
    (``F.rms_norm``). ``offset=1.0`` makes the weight zero-centred: the
    scale is ``1 + weight`` and the weight starts at 0. ``axis_name``: the
    layer holds a share of a wider axis split over that named axis, and the
    statistic runs over every share's lanes."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None,
                 name=None, offset=0.0, axis_name=None):
        super().__init__()
        self.hidden_size = hidden_size
        self.epsilon = epsilon
        self.offset = offset
        self.axis_name = axis_name
        self.weight = self.create_parameter(
            (hidden_size,), attr=weight_attr,
            initializer=_to_initializer(weight_attr, None)
            or Constant(1.0 - offset))

    def scale(self):
        """What the normalised value is multiplied by: the weight, or with
        an offset ``offset + weight`` in float32."""
        if not self.offset:
            return self.weight.value
        return self.offset + self.weight.value.astype(jnp.float32)

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.epsilon, offset=self.offset,
                          axis_name=self.axis_name)

    def extra_repr(self):
        return f"hidden_size={self.hidden_size}"


class GatedRMSNorm(Layer):
    """``rms_norm(x) * weight * silu(z)`` over the last axis
    (``F.gated_rms_norm``): ``forward(x, z)``. ``norm_before_gate=False``
    gates first; ``group_size`` takes the statistics over groups of that
    many lanes."""

    def __init__(self, hidden_size, epsilon=1e-6, name=None, group_size=None,
                 norm_before_gate=True):
        super().__init__()
        self.hidden_size = hidden_size
        self.epsilon = epsilon
        self.group_size, self.norm_before_gate = group_size, norm_before_gate
        self.weight = self.create_parameter((hidden_size,),
                                            initializer=Constant(1.0))

    def forward(self, x, z):
        return F.gated_rms_norm(x, z, self.weight, self.epsilon,
                                self.group_size, self.norm_before_gate)

    def extra_repr(self):
        return f"hidden_size={self.hidden_size}"


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self.num_groups = num_groups
        self.num_channels = num_channels
        self.epsilon = epsilon
        self.data_format = data_format
        self.weight = None if weight_attr is False else self.create_parameter(
            (num_channels,), attr=weight_attr,
            initializer=_to_initializer(weight_attr, None) or Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            (num_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self.num_groups, self.epsilon, self.weight,
                            self.bias, self.data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self.num_features = num_features
        self.epsilon = epsilon
        self.data_format = data_format
        if weight_attr is False:
            self.weight, self.bias = None, None
        else:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr,
                initializer=_to_initializer(weight_attr, None) or Constant(1.0))
            self.bias = None if bias_attr is False else self.create_parameter(
                (num_features,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self.epsilon, data_format=self.data_format)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k
        self.data_format = data_format

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta,
                                     self.k, self.data_format)


class SpectralNorm(Layer):
    """Spectral norm of a weight (reference: operators/spectral_norm_op.cc),
    power iteration on buffers u/v."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12, name=None):
        super().__init__()
        self.dim = dim
        self.power_iters = power_iters
        self.eps = eps
        h = weight_shape[dim]
        w = 1
        for i, s in enumerate(weight_shape):
            if i != dim:
                w *= s
        from ...framework.random import get_rng_key
        import jax
        self.register_buffer("weight_u", jax.random.normal(get_rng_key(), (h,)))
        self.register_buffer("weight_v", jax.random.normal(get_rng_key(), (w,)))

    def forward(self, weight):
        import jax.numpy as jnp
        w = jnp.moveaxis(weight, self.dim, 0)
        h = w.shape[0]
        mat = jnp.reshape(w, (h, -1))
        u, v = self.weight_u, self.weight_v
        for _ in range(self.power_iters):
            v = mat.T @ u
            v = v / (jnp.linalg.norm(v) + self.eps)
            u = mat @ v
            u = u / (jnp.linalg.norm(u) + self.eps)
        self.weight_u, self.weight_v = u, v
        sigma = u @ mat @ v
        return weight / sigma
