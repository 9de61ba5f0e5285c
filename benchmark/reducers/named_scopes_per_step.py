"""``scope_per_step`` for scopes given by name: device time per step of
the step program's ops whose ``tf_op`` holds any of ``scopes`` (``a|b``)
as a whole component, with ``by_scope_ms`` for the names in ``split``. The
pattern is built by ``scope_per_step.scope``, so a metric's file names its
scopes and writes no regular expression. A ``tf_op`` that is nothing but a
name (the ``ragged-dot-none`` the TPU compiler gives the kernels it builds
from ``jax.lax.ragged_dot``, whatever scope they were staged under) is a
whole component too."""
from benchmark.reducers import scope_per_step


def reduce(reading, scopes: str, split=(), module: str = "jit_train_step("):
    return scope_per_step.reduce(reading, scope_per_step.scope(scopes),
                                 module=module, split=split)
