"""Test support: a test of a function's math stages it once.

Called eagerly, a function runs op by op, and each ``lax.scan``,
``checkpoint`` or ``pallas_call`` in it is traced and compiled as a program
of its own on every call. Tests of eager behaviour, of the counters a trace
bumps, or of globals patched at trace time keep their eager calls."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.jit.functionalization import functional_call, state_of


def run(f, *args, **static):
    """``f(*args, **static)`` as one program, the keywords held static."""
    return jax.jit(functools.partial(f, **static))(*args)


def forward(layer, **static):
    """``layer`` as a staged function of its inputs, the keywords held
    static, through ``functional_call`` so that nothing traced stays in the
    layer."""
    params, buffers = (dict(state) for state in state_of(layer))
    call = jax.jit(lambda p, b, *x: functional_call(layer, p, b, *x,
                                                    **static)[0])
    return functools.partial(call, params, buffers)


def _argnums(args, argnums):
    """Every argument where none are named: a tuple where there are several."""
    if argnums is not None:
        return argnums
    return 0 if len(args) == 1 else tuple(range(len(args)))


def grads(f, *args, argnums=None):
    """``jax.grad(f, argnums)(*args)`` as one program."""
    return jax.jit(jax.grad(f, _argnums(args, argnums)))(*args)


def value_and_grads(f, *args, argnums=None, has_aux=False):
    """``jax.value_and_grad(f, argnums, has_aux)(*args)`` as one program."""
    return jax.jit(jax.value_and_grad(f, _argnums(args, argnums),
                                      has_aux=has_aux))(*args)


def out_and_grads(f, *args, ct=None, argnums=None):
    """``f(*args)`` and the gradients of ``sum(f(*args) * ct)``, or of
    ``sum(f(*args) ** 2)`` without ``ct``, in float32, as one program."""
    def loss(*a):
        out = f(*a)
        y = out.astype(jnp.float32)
        return jnp.sum(y ** 2 if ct is None else y * ct), out
    (_, out), g = value_and_grads(loss, *args, argnums=argnums, has_aux=True)
    return out, g


def checkpoints(jaxpr):
    """The ``jax.checkpoint``s staged at any depth, not those inside one."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "remat2":
            n += 1
        else:
            n += sum(map(checkpoints, jax.core.jaxprs_in_params(eqn.params)))
    return n


def check_grad(f, x, rtol, atol, scalarize=jnp.sum, eps=1e-3):
    """The gradient of ``scalarize(f(x))`` against central finite
    differences, element by element, the loss staged once for all."""
    loss = jax.jit(lambda v: scalarize(f(v)))
    analytic = jax.jit(jax.grad(loss))(jnp.asarray(x, jnp.float32))
    x = np.asarray(x, dtype=np.float64)
    numeric = np.zeros_like(x)
    flat, out = x.reshape(-1), numeric.reshape(-1)
    for i in range(flat.size):
        orig = flat[i]
        flat[i] = orig + eps
        f1 = float(loss(jnp.asarray(x, jnp.float32)))
        flat[i] = orig - eps
        f0 = float(loss(jnp.asarray(x, jnp.float32)))
        flat[i] = orig
        out[i] = (f1 - f0) / (2 * eps)
    np.testing.assert_allclose(np.asarray(analytic, np.float64), numeric,
                               rtol=rtol, atol=atol)
