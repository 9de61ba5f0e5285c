"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
the reference PaddlePaddle tree (see SURVEY.md), designed from scratch on
JAX/XLA/Pallas/pjit.

Top-level namespace mirrors the reference's ``paddle`` module
(reference: python/paddle/__init__.py): tensor ops, nn, optimizer, amp, io,
distributed, vision, metric, jit, static-free.
"""
from __future__ import annotations

__version__ = "0.1.0"

import jax as _jax

# -- core types --------------------------------------------------------------
Tensor = _jax.Array

from .framework import dtype as _dtype_mod  # noqa: E402
from .framework.dtype import (  # noqa: F401,E402
    bfloat16, bool_, complex64, complex128, float16, float32, float64,
    get_default_dtype, int8, int16, int32, int64, set_default_dtype, uint8)
from .framework import (  # noqa: F401,E402
    get_device, is_compiled_with_cuda, is_compiled_with_npu,
    is_compiled_with_tpu, is_compiled_with_xpu, set_device)
from .framework.random import get_rng_state_tracker, seed  # noqa: F401,E402

# -- tensor ops at top level (paddle.add, paddle.reshape, ...) ---------------
from .tensor import *  # noqa: F401,F403,E402
from .tensor import linalg, logic, manipulation, math, random, stat  # noqa: F401,E402

# -- subpackages -------------------------------------------------------------
from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import reader  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import rec  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import telemetry  # noqa: F401,E402
from . import monitor  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import analysis  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from .framework.flags import get_flags, set_flags  # noqa: F401,E402
from .framework_io import load, save  # noqa: F401,E402
from .autograd import grad, no_grad  # noqa: F401,E402
from .nn.layer import Parameter  # noqa: F401,E402
from .nn.initializer import ParamAttr  # noqa: F401,E402
from .hapi.model import Model  # noqa: F401,E402
from .hapi import callbacks, model_summary  # noqa: F401,E402
from .hapi.model_summary import flops, summary  # noqa: F401,E402


def is_tensor(x):
    return isinstance(x, _jax.Array)


def numpy(x):
    import numpy as _np
    return _np.asarray(x)


def in_dynamic_mode() -> bool:
    """Eager-by-default: True outside jit tracing and outside the
    enable_static() compat mode (the reference's dygraph/static switch
    collapses; reference fluid/framework.py:185)."""
    if _static_mode:
        return False
    import jax.core as _core
    try:
        return not isinstance(_jax.numpy.zeros(()), _core.Tracer)
    except Exception:
        return True


_static_mode = False


def disable_static(place=None):
    global _static_mode
    _static_mode = False


def enable_static():
    """Source-compat switch (reference paddle.enable_static). There is no
    global graph mode here — jax.jit staging replaces it — so this only flips
    the flag read by ``in_dynamic_mode`` and routes users to the
    ``paddle_tpu.static`` facade (Program.trace / Executor)."""
    global _static_mode
    _static_mode = True


def in_dygraph_mode() -> bool:
    return not _static_mode


enable_dygraph = disable_static
disable_dygraph = enable_static


# -- source-compat aliases (reference python/paddle/__init__.py) -------------
VarBase = Tensor                      # fluid core.VarBase → jax.Array
dtype = _jax.numpy.dtype              # paddle.dtype (VarType enum → np dtype)
bool = bool_                          # noqa: A001  (dtype alias, like paddle)
from .device import (  # noqa: F401,E402
    CPUPlace, CUDAPinnedPlace, CUDAPlace, NPUPlace, TPUPlace, XPUPlace)
from .distributed.parallel import DataParallel  # noqa: F401,E402
from .batch import batch  # noqa: F401,E402
from .autograd import set_grad_enabled  # noqa: F401,E402


def get_cudnn_version():
    """No cuDNN on TPU (reference device.py:62); None = not available."""
    return None


def is_compiled_with_rocm() -> bool:
    return False


def get_cuda_rng_state():
    """Map the reference's CUDA generator state onto the global JAX PRNG key
    (framework/random.py); returned value round-trips via set_cuda_rng_state."""
    from .framework import random as _rnd
    return [_rnd._state.key]


def set_cuda_rng_state(state_list):
    from .framework import random as _rnd
    _rnd._state.key = state_list[0]


def monkey_patch_math_varbase():
    """No-op: jax.Array already carries operator overloads (the reference
    patches VarBase with math dunders at import; ours need no patching)."""


def monkey_patch_variable():
    """No-op: see monkey_patch_math_varbase."""


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    """Standalone parameter factory (reference framework create_parameter)."""
    from .nn.initializer import Constant, XavierNormal
    from .nn.layer import Parameter
    init = default_initializer or (Constant(0.0) if is_bias else XavierNormal())
    return Parameter(init(shape, dtype), trainable=True, name=name)
