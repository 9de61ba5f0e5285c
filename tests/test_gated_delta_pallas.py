"""The chunked gated delta rule as two Pallas kernels a (row, head),
interpreted on the CPU at the kernels' chunk (64), at heads of one 128-lane
block and at Olmo-Hybrid's 96 key and 192 value lanes (padded with zeros to
128 and 256) with step sizes up to 2. The recurrence over positions
(``path="recurrent"``) and XLA's chunked path are the yardsticks, in values
and in all five gradients; two chunks a grid step here, so that the carried
state and its cotangent cross a tile's edge."""
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

# name -> (rows, positions, heads, dtype, what is special about the inputs,
# (d_k, d_v), the largest step size)
CASES = {
    "float32_two_rows_padded": (2, 200, 2, jnp.float32, None, (128, 128), 1),
    "bf16_one_row": (1, 256, 1, jnp.bfloat16, None, (128, 128), 1),
    "beta_zero": (2, 200, 2, jnp.float32, "beta_zero", (128, 128), 1),
    "repeated_keys": (2, 200, 2, jnp.float32, "repeated_keys", (128, 128),
                      1),
    # Olmo-Hybrid's heads: zero lanes to whole blocks, steps up to 2
    "olmo_heads_negative_eigenvalues": (1, 200, 2, jnp.float32, None,
                                        (96, 192), 2),
    "olmo_heads_near_parallel_keys": (1, 200, 2, jnp.float32,
                                      "near_parallel_keys", (96, 192), 2),
}
# the kernels keep three bf16 passes of a float32 product (2**-16 of a
# term); bf16 results are rounded to 2**-9 of their value
TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2e-2}
# near-parallel keys under steps near 2 and a slow decay: a transition's
# eigenvalue along the keys is near -1 and nothing damps the rounding of the
# earlier chunks, which adds up along the row (dg 1.1e-4 of its largest
# magnitude, the rest 3e-5 to 6e-5; XLA's float32 chunked path 4.5e-6)
CASE_TOL = {"olmo_heads_near_parallel_keys": 3e-4}


def inputs(name):
    b, seq, h, dtype, special, (dk, dv), most = CASES[name]
    ks = jax.random.split(jax.random.key(len(name)), 6)
    q = jax.random.normal(ks[0], (b, seq, h, dk))
    k = jax.random.normal(ks[1], (b, seq, h, dk))
    if special == "repeated_keys":
        # every key of a chunk the same: ``a`` is far from small and the
        # inverse's entries reach 2**63 by the shorter product form
        k = jnp.broadcast_to(k[:, :1], k.shape)
    if special == "near_parallel_keys":
        # keys a hundredth apart: with steps near 2 every transition ``I -
        # beta k k^T`` turns the state's component along them by nearly -1
        k = k[:, :1] + 0.01 * k
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, seq, h, dv))
    g = -2.0 * jax.random.uniform(ks[3], (b, seq, h))
    if special == "near_parallel_keys":
        g = g / 20.0                  # a state that forgets slowly
    beta = most * jax.nn.sigmoid(jax.random.normal(ks[4], (b, seq, h)))
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
    tol = CASE_TOL.get(name, TOL[CASES[name][3]])
    got, want = results(name, "pallas"), results(name, yardstick)
    for which, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got,
                           want):
        assert a.shape == b.shape, which
        assert np.isfinite(a).all(), which
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max(),
                                   rtol=0, err_msg=which)


def test_with_beta_zero_a_position_writes_nothing():
    """The state only decays over a position whose ``beta`` is 0: its value
    has no gradient."""
    (_, _, _, _, beta), _ = inputs("beta_zero")
    dv = results("beta_zero", "pallas")[3]
    assert np.abs(dv[np.asarray(beta) == 0.0]).max() == 0.0
    assert np.abs(dv[np.asarray(beta) > 0.0]).max() > 0.0


def test_olmo_heads_are_padded_and_cut_again():
    """A 96-lane key head and a 192-lane value head enter the kernels as 128
    and 256 lanes, the rows ``(batch, seq, heads x width)``, and ``o`` comes
    back at 192; the staged call counts its data and zero lanes."""
    from paddle_tpu.telemetry import scope
    (q, k, v, g, beta), _ = inputs("olmo_heads_negative_eigenvalues")
    assert kernel.pad_lanes(96) == 128 and kernel.pad_lanes(192) == 256
    text = str(jax.make_jaxpr(lambda *a: kernel.gated_delta(
        *a, tile=2, interpret=True))(q, k, v, g, beta))
    assert "f32[1,256,256]" in text and "f32[1,256,512]" in text
    assert kernel.gated_delta(q, k, v, g, beta, tile=2,
                              interpret=True).shape == v.shape
    with scope(profile=False) as tel:
        la._count_staged("pallas", 4, {"qk": (96, 128), "v": (192, 256)})
        lanes = tel.registry.get("gated_delta_lanes_staged_total")
        assert (lanes.value(operand="qk", kind="data"),
                lanes.value(operand="qk", kind="pad"),
                lanes.value(operand="v", kind="data"),
                lanes.value(operand="v", kind="pad")) == (96, 32, 192, 64)


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
    ((2, 8192, 32, 128), (2, 8192, 32, 256), jnp.bfloat16, 64, True),
    ((2, 8192, 32, 16), (2, 8192, 32, 24), jnp.float32, 64, False),
    ((2, 8192, 32, 128), (2, 8192, 32, 128), jnp.bfloat16, 32, False),
    ((2, 8192, 32, 128), (2, 8192, 32, 128), jnp.float16, 64, False),
    ((4, 8192, 15, 96), (4, 8192, 15, 192), jnp.bfloat16, 64, True),
    ((2, 8192, 32, 128), (2, 8192, 32, 384), jnp.bfloat16, 64, False),
], ids=["the_cell", "one_row_float32", "padded_to_whole_tiles",
        "shorter_than_a_tile", "no_whole_tiles", "narrow_keys",
        "wide_values", "toy_heads", "another_chunk", "float16",
        "olmo_hybrid_cell", "values_past_two_blocks"])
def test_supported(q_shape, v_shape, dtype, chunk, ok):
    assert kernel.supported(q_shape, v_shape, dtype, chunk) is ok
    chunks = -(-q_shape[1] // 64)
    assert (kernel.tile_chunks(chunks) is not None) or not ok


@pytest.mark.parametrize("backend, d, chunk, path", [
    ("cpu", 128, 64, "chunked"), ("tpu", 16, 64, "chunked"),
    ("tpu", 128, 32, "chunked"), ("tpu", 128, 64, "pallas"),
    ("tpu", 96, 64, "pallas")])
def test_path_is_read_from_the_input(monkeypatch, backend, d, chunk, path):
    """A CPU backend, toy heads or another chunk size take XLA's chunked
    path, a TPU with whole lane blocks, or heads of 96 and 192 lanes padded
    to them, the kernels (traced here, not run), and
    ``linear_attn_calls_staged_total{path}`` says which (and on the
    kernels' path ``gated_delta_lanes_staged_total`` the zero lanes); a
    caller cannot ask for the kernels by name."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x = jax.ShapeDtypeStruct((2, 512, 32, d), jnp.bfloat16)
    xv = jax.ShapeDtypeStruct((2, 512, 32, 2 * d if d == 96 else d),
                              jnp.bfloat16)
    vec = jax.ShapeDtypeStruct((2, 512, 32), jnp.float32)
    with telemetry.scope(profile=False) as tel:
        jaxpr = jax.make_jaxpr(lambda *a: F.gated_delta_rule(
            *a, chunk=chunk))(x, x, xv, vec, vec)
        calls = tel.registry.get("linear_attn_calls_staged_total")
        other = "chunked" if path == "pallas" else "pallas"
        assert calls.value(path=path) == 1 and calls.value(path=other) == 0
        assert tel.registry.get("gated_delta_chunks_total").value() \
            == 512 // chunk
        lanes = tel.registry.get("gated_delta_lanes_staged_total")
        if path == "pallas":
            pad = 32 if d == 96 else 0
            assert lanes.value(operand="qk", kind="pad") == pad
            assert lanes.value(operand="v", kind="pad") == 2 * pad
        else:
            assert lanes is None or lanes.value(operand="qk",
                                                kind="data") == 0
    assert ("pallas_call" in str(jaxpr)) is (path == "pallas")
    # no head groups and no checkpoint around the kernels
    assert ("remat" in str(jaxpr)) is (path == "chunked")
    with pytest.raises(ValueError, match="unknown path"):
        F.gated_delta_rule(x, x, xv, vec, vec, path="pallas")


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
