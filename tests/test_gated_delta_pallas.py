"""ISSUE 34: the chunked gated delta rule as two Pallas kernels a (row,
head), interpreted on the CPU at the kernels' head width (128) and chunk
(64). The recurrence over positions (``path="recurrent"``) and XLA's chunked
path are the yardsticks, in values and in all five gradients; two chunks a
grid step here, so that the carried state and its cotangent cross a tile's
edge."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from paddle_tpu import telemetry
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import linear_attention as la
from paddle_tpu.ops.pallas import gated_delta as kernel

# name -> (rows, positions, heads, dtype, what is special about the inputs)
CASES = {
    "float32_two_rows_padded": (2, 200, 2, jnp.float32, None),
    "bf16_one_row": (1, 256, 1, jnp.bfloat16, None),
    "beta_zero": (2, 200, 2, jnp.float32, "beta_zero"),
    "repeated_keys": (2, 200, 2, jnp.float32, "repeated_keys"),
}
# the kernels keep three bf16 passes of a float32 product (2**-16 of a
# term); bf16 results are rounded to 2**-9 of their value
TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2e-2}


def inputs(name):
    b, seq, h, dtype, special = CASES[name]
    d = kernel.LANES
    ks = jax.random.split(jax.random.key(len(name)), 6)
    q = jax.random.normal(ks[0], (b, seq, h, d))
    k = jax.random.normal(ks[1], (b, seq, h, d))
    if special == "repeated_keys":
        # every key of a chunk the same: ``a`` is far from small and the
        # inverse's entries reach 2**63 by the shorter product form
        k = jnp.broadcast_to(k[:, :1], k.shape)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, seq, h, d))
    g = -2.0 * jax.random.uniform(ks[3], (b, seq, h))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, seq, h)))
    if special == "beta_zero":
        beta = jnp.where(jnp.arange(seq)[None, :, None] % 3 == 0, 0.0, beta)
    ct = jax.random.normal(ks[5], v.shape)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), ct


@functools.lru_cache(maxsize=None)
def results(name, path):
    """``(o,) + the five gradients`` in float32. ``"pallas"``: the kernels
    interpreted, two chunks a grid step. The yardsticks read the operands
    in float32 (the same numbers: what was bf16 stays a bf16 value)."""
    args, ct = inputs(name)
    if path == "pallas":
        def rule(*a):
            return kernel.gated_delta(*a, tile=2, interpret=True)
    else:
        args = tuple(x.astype(jnp.float32) for x in args)
        if path == "chunked":
            def rule(*a):
                return la._chunked(*a, kernel.CHUNK, la.GROUP_HEADS)
        else:
            def rule(*a):
                return F.gated_delta_rule(*a, path="recurrent")

    out, grads = _staged.out_and_grads(rule, *args, ct=ct)
    return tuple(np.asarray(x, np.float32) for x in (out,) + grads)


@pytest.mark.parametrize("yardstick", ["recurrent", "chunked"])
@pytest.mark.parametrize("name", list(CASES))
def test_kernels_equal_the_yardsticks_in_values_and_all_gradients(
        name, yardstick):
    """Each of ``o, dq, dk, dv, dg, dbeta`` to ``TOL`` of the yardstick's
    largest magnitude: 200 positions are padded to four chunks with ``beta
    = 0, g = 0`` and cut again, and the four chunks are two tiles."""
    dtype = CASES[name][3]
    got, want = results(name, "pallas"), results(name, yardstick)
    for which, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got,
                           want):
        assert a.shape == b.shape, which
        assert np.isfinite(a).all(), which
        np.testing.assert_allclose(a, b, atol=TOL[dtype] * np.abs(b).max(),
                                   rtol=0, err_msg=which)


def test_with_beta_zero_a_position_writes_nothing():
    """The state only decays over a position whose ``beta`` is 0: its value
    has no gradient."""
    (_, _, _, _, beta), _ = inputs("beta_zero")
    dv = results("beta_zero", "pallas")[3]
    assert np.abs(dv[np.asarray(beta) == 0.0]).max() == 0.0
    assert np.abs(dv[np.asarray(beta) > 0.0]).max() > 0.0


@pytest.mark.parametrize("tile", [1, 2, 4])
def test_tiles_change_nothing(tile):
    """One, two or four chunks a grid step: the same arithmetic, the state
    carried in the scratch or in registers."""
    args, _ = inputs("bf16_one_row")
    want = kernel.gated_delta(*args, tile=4, interpret=True)
    got = kernel.gated_delta(*args, tile=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("q_shape, v_shape, dtype, chunk, ok", [
    ((2, 8192, 32, 128), (2, 8192, 32, 128), jnp.bfloat16, 64, True),
    ((1, 8192, 32, 128), (1, 8192, 32, 128), jnp.float32, 64, True),
    ((2, 8191, 4, 128), (2, 8191, 4, 128), jnp.bfloat16, 64, True),
    ((2, 200, 4, 128), (2, 200, 4, 128), jnp.bfloat16, 64, True),
    ((2, 8192 + 64, 4, 128), (2, 8192 + 64, 4, 128), jnp.bfloat16, 64,
     False),
    ((2, 8192, 32, 64), (2, 8192, 32, 128), jnp.bfloat16, 64, False),
    ((2, 8192, 32, 128), (2, 8192, 32, 256), jnp.bfloat16, 64, False),
    ((2, 8192, 32, 16), (2, 8192, 32, 24), jnp.float32, 64, False),
    ((2, 8192, 32, 128), (2, 8192, 32, 128), jnp.bfloat16, 32, False),
    ((2, 8192, 32, 128), (2, 8192, 32, 128), jnp.float16, 64, False),
], ids=["the_cell", "one_row_float32", "padded_to_whole_tiles",
        "shorter_than_a_tile", "no_whole_tiles", "narrow_keys",
        "wide_values", "toy_heads", "another_chunk", "float16"])
def test_supported(q_shape, v_shape, dtype, chunk, ok):
    assert kernel.supported(q_shape, v_shape, dtype, chunk) is ok
    chunks = -(-q_shape[1] // 64)
    assert (kernel.tile_chunks(chunks) is not None) or not ok


@pytest.mark.parametrize("backend, d, chunk, path", [
    ("cpu", 128, 64, "chunked"), ("tpu", 16, 64, "chunked"),
    ("tpu", 128, 32, "chunked"), ("tpu", 128, 64, "pallas")])
def test_path_is_read_from_the_input(monkeypatch, backend, d, chunk, path):
    """A CPU backend, toy heads or another chunk size take XLA's chunked
    path, a TPU with whole lane blocks the kernels (traced here, not run),
    and ``linear_attn_calls_staged_total{path}`` says which; a caller
    cannot ask for the kernels by name."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x = jax.ShapeDtypeStruct((2, 512, 32, d), jnp.bfloat16)
    vec = jax.ShapeDtypeStruct((2, 512, 32), jnp.float32)
    with telemetry.scope(profile=False) as tel:
        jaxpr = jax.make_jaxpr(lambda *a: F.gated_delta_rule(
            *a, chunk=chunk))(x, x, x, vec, vec)
        calls = tel.registry.get("linear_attn_calls_staged_total")
        other = "chunked" if path == "pallas" else "pallas"
        assert calls.value(path=path) == 1 and calls.value(path=other) == 0
        assert tel.registry.get("gated_delta_chunks_total").value() \
            == 512 // chunk
    assert ("pallas_call" in str(jaxpr)) is (path == "pallas")
    # no head groups and no checkpoint around the kernels
    assert ("remat" in str(jaxpr)) is (path == "chunked")
    with pytest.raises(ValueError, match="unknown path"):
        F.gated_delta_rule(x, x, x, vec, vec, path="pallas")


def test_the_backward_is_staged_once_and_keeps_no_chunk_by_chunk_residual():
    """Two layers' calls share ``jit(_fwd)`` and ``jit(_bwd_call)``; what
    the forward keeps for the backward is its operands and the state
    entering each chunk, nothing of size ``chunk x chunk``."""
    (q, k, v, g, beta), _ = inputs("bf16_one_row")

    def two_layers(q, k, v, g, beta):
        for _ in range(2):
            v = kernel.gated_delta(q, k, v, g, beta, tile=2, interpret=True)
        return jnp.sum(v.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(two_layers, argnums=(0, 1, 2, 3, 4)))(
        q, k, v, g, beta))
    assert text.count("name=_fwd") == 2 and text.count("name=_bwd_call") == 2
    rows = jnp.reshape(v, (1, 256, 128))
    vec = jnp.zeros((1, 1, 2, 2, 64), jnp.float32)
    _, residuals = kernel._rule_fwd(rows, rows, rows, vec, vec, 2, True)
    shapes = sorted(tuple(r.shape) for r in residuals)
    assert shapes == sorted([(1, 256, 128)] * 3 + [(1, 1, 2, 2, 64)] * 2
                            + [(1, 1, 4, 128, 128)])
    assert not any(s[-2:] == (64, 64) for s in shapes)
