"""``ops/pallas/rotary.py``: QK norm and rotary embedding as one pass, in
interpret mode against the XLA formula and ``F.rms_norm`` (what the CPU
runs and the parent ran everywhere), and the rule by which
``F.rotary_embedding`` reads its path from its input. That the kernels
lower and fit on a v5e is ``tests/test_pallas_tpu_compile.py``'s; their
values on the chip are ``chip_smoke.py``'s ``kernels`` phase.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional.rotary import rope_tables
from paddle_tpu.ops.pallas import rotary as kernel

YARN = {"factor": 64.0, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1}
EPSILON = 1e-6

# name: (shape, rotated lanes, yarn, a row held twice, norm weight)
CASES = {
    "all_128_lanes": ((2, 64, 3, 128), 128, None, False, False),
    "64_of_128_yarn": ((2, 64, 3, 128), 64, YARN, False, False),
    "row_held_twice": ((1, 128, 2, 128), 128, None, True, False),
    "norm": ((2, 64, 3, 128), 128, None, False, True),
    "norm_row_held_twice": ((1, 128, 2, 128), 128, None, True, True),
    "norm_64_of_128_yarn": ((1, 64, 2, 128), 64, YARN, False, True),
    "norm_head_of_256_lanes": ((1, 64, 2, 256), 128, None, False, True),
    "norm_head_of_384_lanes": ((1, 64, 1, 384), 128, None, False, True),
    "float32": ((1, 64, 2, 128), 128, None, False, True),
}


@pytest.fixture(scope="module")
def readings():
    """Per case, ``{what: (kernel's, formula's)}`` for the values, ``dx``
    and, with a norm, the weight's gradient."""
    cache = {}

    def read(name):
        if name in cache:
            return cache[name]
        shape, rotated, yarn, twice, norm = CASES[name]
        dtype = jnp.float32 if name == "float32" else jnp.bfloat16
        _, seq, _, d = shape
        inv_freq, scale = F.rope_frequencies(10000.0, rotated, yarn)
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 3)
        x = (3.0 * jax.random.normal(keys[0], shape)).astype(dtype)
        ct = jax.random.normal(keys[1], shape)
        weight = (1.0 + 0.2 * jax.random.normal(keys[2], (d,))).astype(
            dtype) if norm else None
        positions = jnp.concatenate([jnp.arange(seq // 2)] * 2) \
            if twice else None
        cos, sin = rope_tables(inv_freq, scale, positions, seq, d)

        def through_kernel(x, weight):
            return kernel.rotary(x, cos, sin, len(inv_freq), weight, EPSILON,
                                 interpret=True)

        def through_formula(x, weight):       # the CPU takes the XLA path
            return F.rotary_embedding(x, inv_freq, scale, positions, weight,
                                      EPSILON)

        got = {}
        for i, fn in enumerate((through_kernel, through_formula)):
            # the float32 sum's cotangent reaches ``out`` as ct in its dtype
            out, (dx, dw) = _staged.out_and_grads(fn, x, weight, ct=ct)
            assert out.dtype == dtype and dx.dtype == dtype
            for what, value in (("values", out), ("dx", dx), ("dw", dw)):
                if value is not None:
                    got.setdefault(what, [None, None])[i] = np.asarray(
                        value, np.float32)
        cache[name] = got
        return got
    return read


@pytest.mark.parametrize("name, what", [
    (name, what) for name, case in CASES.items()
    for what in ("values", "dx") + (("dw",) if case[4] else ())])
def test_kernel_matches_the_formula(readings, name, what):
    """To bf16 tolerances: the kernel rounds once, at its store, where
    the formula after ``F.rms_norm`` rounds the normalised value, its
    product with the weight and the result."""
    mine, ref = readings(name)[what]
    # two bf16 roundings of a value are 2**-8 of it apart at most; dw sums
    # seq x heads products, each as far off
    tol = 1e-5 if name == "float32" else 2.0 ** -6
    scale = np.max(np.abs(ref))
    assert np.all(np.isfinite(mine))
    np.testing.assert_allclose(mine, ref, rtol=tol, atol=tol * scale)


def test_norm_then_rotation_is_what_the_entry_point_folds():
    """``rotary_embedding(norm_weight=)`` on the XLA path is ``F.rms_norm``
    and then the rotation, digit for digit."""
    inv_freq, scale = F.rope_frequencies(10000.0, 16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 3, 16), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    folded = F.rotary_embedding(x, inv_freq, scale, None, w, 1e-5)
    apart = F.rotary_embedding(F.rms_norm(x, w, 1e-5), inv_freq, scale)
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(apart))


@pytest.mark.parametrize("shape, half, takes", [
    ((4, 4096, 64, 128), 64, True),      # laguna-xs2, a sliding layer's q
    ((4, 4096, 8, 128), 32, True),       # its k in a full layer
    ((2, 8192, 32, 128), 64, True),      # sdar-30b-a3b-chat
    ((2, 64, 4, 256), 64, True),         # two lane blocks a head
    ((2, 64, 4, 16), 8, False),          # the tests' head widths
    ((2, 64, 4, 64), 32, False),         # half a lane block
    ((8, 1, 32, 128), 64, False),        # a decode step
    ((2, 96, 4, 128), 64, False),        # no row tile divides 96
    ((2, 64, 4, 128), 0, False),         # nothing rotates
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_which_shapes_the_kernels_take(shape, half, takes):
    assert kernel.supported(shape, jnp.bfloat16, half) is takes


@pytest.mark.parametrize("seq, heads, tensors, tile", [
    (4096, 64, 2, 64), (4096, 64, 3, 64), (4096, 8, 2, 512),
    (8192, 32, 3, 128), (8192, 4, 3, 512), (192, 4, 2, 64), (96, 4, 2, None),
])
def test_row_tile_follows_the_vmem_budget(seq, heads, tensors, tile):
    """The largest power-of-two multiple of ``ROWS`` that divides the
    sequence with the blocks' double buffers under the budget."""
    got = kernel.row_tile(seq, heads, 128, 2, tensors)
    assert got == tile
    if tile:
        assert 2 * tile * (tensors * heads * 128 * 2 + 3 * 128 * 4) \
            <= kernel.VMEM_BUDGET


@pytest.fixture
def staged_counter():
    from paddle_tpu import telemetry
    from paddle_tpu.telemetry.metrics import Registry
    prev, reg = telemetry.get_registry(), Registry()
    telemetry._set_registry(reg)
    telemetry.enable()
    yield lambda **labels: int(
        reg.get("rope_calls_staged_total").value(**labels))
    telemetry.disable()
    telemetry._set_registry(prev)


@pytest.mark.parametrize("backend, d, path", [
    ("cpu", 128, "xla"), ("tpu", 16, "xla"), ("tpu", 128, "pallas")])
def test_path_is_read_from_the_input(monkeypatch, staged_counter, backend, d,
                                     path):
    """A CPU backend or a head width of 16 takes the XLA formula, a TPU
    with whole lane blocks the kernels (traced here, not run), and
    ``rope_calls_staged_total{path, norm}`` says which."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    inv_freq, scale = F.rope_frequencies(10000.0, d)
    x = jax.ShapeDtypeStruct((2, 64, 4, d), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((d,), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda x, w: (F.rotary_embedding(x, inv_freq, scale),
                      F.rotary_embedding(x, inv_freq, scale, None, w)))(x, w)
    assert ("pallas_call" in str(jaxpr)) is (path == "pallas")
    other = "xla" if path == "pallas" else "pallas"
    assert [staged_counter(path=path, norm=n) for n in (0, 1)] == [1, 1]
    assert [staged_counter(path=other, norm=n) for n in (0, 1)] == [0, 0]


def test_the_backward_is_staged_once_and_keeps_no_residual_without_a_norm():
    """Two layers' calls share ``jit(_fwd)`` and ``jit(_bwd_call)``; the
    rotation's transpose needs the tables alone, the norm's also ``x``."""
    cos, sin = rope_tables(F.rope_frequencies(1e4, 128)[0], 1.0, None, 64,
                           128)
    x = jnp.ones((1, 64, 2, 128), jnp.bfloat16)
    w = jnp.ones((128,), jnp.bfloat16)

    def two_layers(x, w):
        for _ in range(2):
            x = kernel.rotary(x, cos, sin, 64, w, interpret=True)
        return jnp.sum(x.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(two_layers, argnums=(0, 1)))(x, w))
    assert text.count("name=_fwd") == 2 and text.count("name=_bwd_call") == 2
    _, residuals = kernel._rotary_fwd(x, cos, sin, None, 64, 1e-6, True)
    assert residuals[0] is None and residuals[3] is None
    _, residuals = kernel._rotary_fwd(x, cos, sin, w, 64, 1e-6, True)
    assert residuals[0] is x
