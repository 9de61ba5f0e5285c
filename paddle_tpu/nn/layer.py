"""Layer: the imperative module system.

TPU-native re-design of the reference's dygraph Layer
(reference: python/paddle/fluid/dygraph/layers.py — parameters, sublayers,
buffers, hooks, state_dict) on top of JAX. A Layer owns mutable
``Parameter`` boxes and buffer entries; eager forward just computes with
jax ops on the current values. For compiled execution, ``functional_call``
(see paddle_tpu/jit/functionalization.py) swaps traced values in, making any
Layer a pure function of its state — the dygraph/static duality of the
reference (dygraph_to_static/) collapses into this single bridge.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtype as dtype_mod
from ..framework.naming import unique_name


class Parameter:
    """A mutable box holding a jax.Array leaf of a Layer.

    Equivalent of the reference's ``framework.Parameter``
    (python/paddle/fluid/framework.py) without the Program machinery.
    ``pspec`` optionally carries a ``jax.sharding.PartitionSpec`` used by the
    distributed engine to shard this parameter over the mesh (the TPU-native
    analogue of the reference's per-parameter ``is_distributed`` /
    ``split``-ed vars in fleet/meta_parallel/parallel_layers/mp_layers.py).
    """

    __slots__ = ("value", "name", "trainable", "grad", "pspec", "optimize_attr")

    def __init__(self, value, name: Optional[str] = None, trainable: bool = True):
        self.value = value
        self.name = name or unique_name("param")
        self.trainable = trainable
        self.grad = None
        self.pspec = None  # PartitionSpec for distributed sharding
        self.optimize_attr = {"learning_rate": 1.0}

    # -- array-ish conveniences -------------------------------------------
    @property
    def shape(self):
        return tuple(self.value.shape)

    @property
    def dtype(self):
        return self.value.dtype

    @property
    def ndim(self):
        return self.value.ndim

    @property
    def stop_gradient(self):
        return not self.trainable

    @stop_gradient.setter
    def stop_gradient(self, v):
        self.trainable = not v

    def numpy(self):
        return np.asarray(self.value)

    def set_value(self, v):
        self.value = jnp.asarray(v, dtype=self.value.dtype)

    def clear_grad(self):
        self.grad = None

    def astype(self, dt):
        self.value = self.value.astype(dtype_mod.convert_dtype_to_jax(dt))
        return self

    def __jax_array__(self):
        return self.value

    def __repr__(self):
        return (f"Parameter(name={self.name}, shape={self.shape}, "
                f"dtype={self.dtype}, trainable={self.trainable})")


class HookRemoveHelper:
    def __init__(self, hooks: OrderedDict, hook_id: int):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    """Base class for all network modules (reference: dygraph/layers.py Layer)."""

    def __init__(self, name_scope: Optional[str] = None, dtype=None):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        self.training = True
        self._dtype = dtype_mod.convert_dtype_to_jax(dtype) or dtype_mod.get_default_dtype()
        self._full_name = unique_name(name_scope or type(self).__name__.lower())
        # what forward is staged under (jax.named_scope): the name a parent
        # registers this layer by, the class name for a root. Unlike
        # _full_name it carries no counter, so it is the same in every
        # process and for every model built in one.
        self._scope_name = type(self).__name__.lower()
        self._forward_pre_hooks: OrderedDict = OrderedDict()
        self._forward_post_hooks: OrderedDict = OrderedDict()
        self._hook_id = 0

    # -- construction ------------------------------------------------------
    def create_parameter(self, shape, dtype=None, initializer=None,
                         is_bias: bool = False, attr=None, trainable: bool = True,
                         name: Optional[str] = None) -> Parameter:
        from .initializer import (Constant, XavierUniform, _global_default,
                                  _to_initializer)
        dt = dtype_mod.convert_dtype_to_jax(dtype) or self._dtype
        # precedence (reference set_global_initializer semantics): an
        # attr-specified initializer wins; otherwise the global default
        # overrides the layer's own default passed via `initializer`.
        init = _to_initializer(attr, None)
        if init is None:
            init = _global_default(is_bias)
        if init is None:
            init = initializer
        if init is None:
            init = Constant(0.0) if is_bias else XavierUniform()
        value = init(shape, dt)
        p = Parameter(value, name=name, trainable=trainable)
        if attr is not None and getattr(attr, "learning_rate", None) is not None:
            p.optimize_attr["learning_rate"] = attr.learning_rate
        return p

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        self._name_sublayer(name, sublayer)
        return sublayer

    def _name_sublayer(self, name: str, sublayer: Optional["Layer"]):
        """The one place a registered sublayer learns its scope name. A
        layer registered twice stages under the later name; what only
        reads a model (a container's slice) registers nothing."""
        if sublayer is not None:
            sublayer._set_scope_name(name)

    def _set_scope_name(self, name: str):
        self._scope_name = name

    def register_buffer(self, name: str, tensor, persistable: bool = True):
        self._buffers[name] = tensor if tensor is None else jnp.asarray(tensor)
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return self._buffers.get(name)

    # -- attribute routing -------------------------------------------------
    def __setattr__(self, name: str, value: Any):
        if isinstance(value, Parameter):
            self.__dict__.pop(name, None)
            self._parameters[name] = value
        elif isinstance(value, Layer):
            self.__dict__.pop(name, None)
            self._sub_layers[name] = value
            self._name_sublayer(name, value)
        elif "_buffers" in self.__dict__ and name in self._buffers:
            self._buffers[name] = value if value is None else jnp.asarray(value)
        elif "_parameters" in self.__dict__ and name in self._parameters and value is None:
            self._parameters[name] = None
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        d = self.__dict__
        if "_parameters" in d and name in d["_parameters"]:
            return d["_parameters"][name]
        if "_buffers" in d and name in d["_buffers"]:
            return d["_buffers"][name]
        if "_sub_layers" in d and name in d["_sub_layers"]:
            return d["_sub_layers"][name]
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        for store in (self._parameters, self._buffers, self._sub_layers):
            if name in store:
                del store[name]
                return
        object.__delattr__(self, name)

    # -- iteration ---------------------------------------------------------
    def named_sublayers(self, prefix: str = "", include_self: bool = False,
                        layers_set=None) -> Iterator[Tuple[str, "Layer"]]:
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from sub.named_sublayers(prefix=sub_prefix, include_self=True,
                                           layers_set=layers_set)

    def sublayers(self, include_self: bool = False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items() if l is not None)

    def named_parameters(self, prefix: str = "", include_sublayers: bool = True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        layers = self.named_sublayers(prefix=prefix, include_self=True) \
            if include_sublayers else [(prefix, self)]
        for lp, layer in layers:
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (lp + ("." if lp else "") + name, p)

    def parameters(self, include_sublayers: bool = True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        layers = self.named_sublayers(prefix=prefix, include_self=True) \
            if include_sublayers else [(prefix, self)]
        for lp, layer in layers:
            for name, b in layer._buffers.items():
                if b is None:
                    continue
                yield (lp + ("." if lp else "") + name, b)

    def buffers(self, include_sublayers: bool = True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    # -- mode / dtype ------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def apply(self, fn: Callable[["Layer"], None]):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self.astype(dtype)
        if device is not None:
            dev = device if not isinstance(device, str) else _resolve_device(device)
            for l in self.sublayers(include_self=True):
                for p in l._parameters.values():
                    if p is not None:
                        p.value = jax.device_put(p.value, dev)
                for k, b in l._buffers.items():
                    if b is not None:
                        l._buffers[k] = jax.device_put(b, dev)
        return self

    def astype(self, dt):
        dt = dtype_mod.convert_dtype_to_jax(dt)
        for l in self.sublayers(include_self=True):
            l._dtype = dt
            for p in l._parameters.values():
                if p is not None and dtype_mod.is_floating(p.dtype):
                    p.value = p.value.astype(dt)
            for k, b in l._buffers.items():
                if b is not None and dtype_mod.is_floating(b.dtype):
                    l._buffers[k] = b.astype(dt)
        return self

    def float(self):
        return self.astype(jnp.float32)

    def bfloat16(self):
        return self.astype(jnp.bfloat16)

    # -- state dict --------------------------------------------------------
    def state_dict(self, include_sublayers: bool = True, structured_name_prefix: str = ""):
        out = OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix,
                                             include_sublayers=include_sublayers):
            out[name] = p.value
        layers = self.named_sublayers(prefix=structured_name_prefix, include_self=True) \
            if include_sublayers else [(structured_name_prefix, self)]
        for lp, layer in layers:
            for name, b in layer._buffers.items():
                if b is None or name in layer._non_persistable_buffer_names:
                    continue
                out[lp + ("." if lp else "") + name] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        missing, unexpected = [], list(state_dict.keys())
        own = self.state_dict()
        param_map = {n: p for n, p in self.named_parameters()}
        buf_owners = {}
        for lp, layer in self.named_sublayers(include_self=True):
            for name in layer._buffers:
                buf_owners[lp + ("." if lp else "") + name] = (layer, name)
        for name in own:
            if name not in state_dict:
                missing.append(name)
                continue
            unexpected.remove(name)
            v = jnp.asarray(state_dict[name])
            if name in param_map:
                p = param_map[name]
                if tuple(v.shape) != tuple(p.shape):
                    raise ValueError(
                        f"shape mismatch for {name}: got {tuple(v.shape)}, "
                        f"expected {tuple(p.shape)}")
                p.value = v.astype(p.dtype)
            else:
                layer, bname = buf_owners[name]
                layer._buffers[bname] = v.astype(layer._buffers[bname].dtype)
        return missing, unexpected

    load_dict = set_state_dict

    # -- hooks -------------------------------------------------------------
    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call --------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            res = hook(self, args)
            if res is not None:
                args = res if isinstance(res, tuple) else (res,)
        with jax.named_scope(self._scope_name):
            out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, args, out)
            if res is not None:
                out = res
        return out

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            body = repr(sub).split("\n")
            body = [body[0]] + ["  " + l for l in body[1:]]
            lines.append(f"  ({name}): " + "\n".join(body))
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

    def full_name(self):
        return self._full_name

    def clear_gradients(self):
        for p in self.parameters():
            p.grad = None


def _resolve_device(name: str):
    import jax
    if name in ("cpu",):
        return jax.devices("cpu")[0]
    if name.startswith(("gpu", "tpu", "cuda")):
        plat = "tpu" if name.startswith("tpu") else "gpu"
        idx = int(name.split(":")[1]) if ":" in name else 0
        return jax.devices(plat)[idx]
    return jax.devices()[0]
