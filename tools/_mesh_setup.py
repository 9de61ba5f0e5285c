"""Shared setup for the repo's CLI tools (chip_smoke.py,
bench_collectives, lint_program): repo-root path handling,
forced-host-device env, the persistent compile cache, and the plain data
mesh every tool was rebuilding by hand.

Import order matters: ``force_host_devices`` touches XLA_FLAGS /
JAX_PLATFORMS and must run BEFORE the first ``import jax`` anywhere in
the process (both only set defaults, so an operator's explicit env wins).
"""
from __future__ import annotations

import os
import sys

__all__ = ["repo_root", "ensure_repo_on_path", "force_host_devices",
           "use_compile_cache", "data_mesh"]


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ensure_repo_on_path() -> str:
    """Make ``import paddle_tpu`` work when a tool runs as a script
    (sys.path[0] is then tools/, not the repo root)."""
    root = repo_root()
    if root not in sys.path:
        sys.path.insert(0, root)
    return root


def force_host_devices(n: int, platform: str = "cpu") -> None:
    """Default the process to ``n`` virtual host devices (no-op for any
    var the operator already set, so real-TPU runs are unaffected)."""
    if "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n}")
    os.environ.setdefault("JAX_PLATFORMS", platform)


def use_compile_cache() -> str:
    """Place jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set in code, so whoever runs the program decides where
    compiled code survives. Otherwise ``<checkout>/.jax_cache`` — one
    fixed path, never a temp name, pid or time: the directory is part of
    what a process looks entries up by, so a cache that moves never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(repo_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def data_mesh(n: int = 1):
    """Build the plain data-parallel mesh over exactly ``n`` devices.
    ``build_mesh`` raises when the backend has fewer: a benchmark that
    quietly shrank to one device would report it as ``n``."""
    from paddle_tpu.distributed.mesh import build_mesh

    return build_mesh({"data": n})
