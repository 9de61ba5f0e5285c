"""Stateful-looking RNG over JAX's functional PRNG.

The reference exposes a global stateful generator (``paddle.seed``,
reference: python/paddle/framework/random.py) consumed implicitly by dropout /
initializers. JAX PRNG is functional, so we keep a process-global key that is
split on every draw in eager mode, and a *scoped* key stack so that jitted
training steps can inject an explicit key (making the step a pure function):

    with rng_guard(key):           # inside a jitted step
        y = dropout(x, 0.1)        # consumes folds of `key`, fully traceable

Also hosts RNGStatesTracker for tensor-parallel dropout (reference:
fleet/meta_parallel/parallel_layers/random.py:24): "global" vs "local" states
so that dropout masks agree or differ across the model-parallel axis as needed.
"""
from __future__ import annotations

import contextlib
import threading

import jax


class _RNGState(threading.local):
    """Global PRNG key holder. The key is created LAZILY: materializing it
    in __init__ would initialize the jax backend at ``import paddle_tpu``
    time (it claims the chip, and is wrong for launcher subprocesses that
    only read env vars)."""

    def __init__(self):
        self._key = None
        self.scoped: list = []  # stack of (key, counter) for rng_guard scopes

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.key(0)
        return self._key

    @key.setter
    def key(self, v):
        self._key = v


_state = _RNGState()


def seed(s: int):
    """Seed the global generator (paddle.seed equivalent)."""
    _state.key = jax.random.key(int(s))
    return _state


def get_rng_key():
    """Draw a fresh key.

    Inside an ``rng_guard`` scope, keys are derived deterministically from the
    scope key by fold_in of a counter (trace-safe). Outside, the global key is
    split statefully (eager convenience).
    """
    if _state.scoped:
        key, counter = _state.scoped[-1]
        _state.scoped[-1] = (key, counter + 1)
        return jax.random.fold_in(key, counter)
    _state.key, sub = jax.random.split(_state.key)
    return sub


@contextlib.contextmanager
def rng_guard(key):
    """Scope in which get_rng_key() derives from `key` (pure under jit)."""
    _state.scoped.append((key, 0))
    try:
        yield
    finally:
        _state.scoped.pop()


class RNGStatesTracker:
    """Named RNG states for tensor-parallel dropout.

    Reference: fleet/meta_parallel/parallel_layers/random.py:24 — model-parallel
    ranks must use identical dropout masks for replicated activations and
    different masks for sharded ones.
    """

    def __init__(self):
        self.states_ = {}

    def add(self, name: str, s: int):
        if name in self.states_:
            raise ValueError(f"state {name!r} already exists")
        self.states_[name] = (jax.random.key(int(s)), 0)

    def reset(self):
        self.states_ = {}

    @contextlib.contextmanager
    def rng_state(self, name: str = "model_parallel_rng"):
        if name not in self.states_:
            raise ValueError(f"state {name!r} not added")
        key, counter = self.states_[name]
        _state.scoped.append((key, counter))
        try:
            yield
        finally:
            k, c = _state.scoped.pop()
            self.states_[name] = (k, c)


_rng_tracker = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _rng_tracker
