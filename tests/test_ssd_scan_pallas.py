"""Mamba-2's state-space scan as two Pallas kernels a (row, group
of heads), interpreted on the CPU at the kernels' widths (``P`` 64, ``N``
128, chunks of 128). The recurrence over positions (``path="recurrent"``)
and XLA's chunked path are the yardsticks, in values and in the gradients
of all six inputs; two chunks a grid step here, so that the carried state
and its cotangent cross a tile's edge."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from paddle_tpu import telemetry
from paddle_tpu.jit.functionalization import functional_call, state_of
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas import ssd_scan as kernel
from paddle_tpu.text.models import MixedDecoderForPretraining

# name -> (rows, positions, heads, groups, P, dtype, with D)
CASES = {
    # four chunks, the last one padded, in two tiles; two groups of four
    # heads, two heads a lane block
    "float32_two_rows_padded": (2, 456, 8, 2, 64, jnp.float32, True),
    "bf16_one_row": (1, 512, 8, 2, 64, jnp.bfloat16, True),
    # one head a lane block, two a group, no D
    "float32_width_128_no_d": (1, 250, 4, 2, 128, jnp.float32, False),
}
# the kernels keep three bf16 passes of a float32 product (2**-16 of a
# term); bf16 results are rounded to 2**-9 of their value
TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2e-2}
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def inputs(name):
    b, seq, heads, groups, p, dtype, with_d = CASES[name]
    ks = jax.random.split(jax.random.key(len(name)), 7)
    x = jax.random.normal(ks[0], (b, seq, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, seq, heads)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    B = jax.random.normal(ks[3], (b, seq, groups, kernel.LANES)) / 4
    C = jax.random.normal(ks[4], (b, seq, groups, kernel.LANES)) / 4
    D = jax.random.normal(ks[5], (heads,))
    ct = jax.random.normal(ks[6], x.shape)
    args = (x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype), D)
    return args, ct, with_d


@functools.lru_cache(maxsize=None)
def results(name, path):
    """``(y,) + the six gradients`` in float32. ``"pallas"``: the kernels
    interpreted, two chunks a grid step. The yardsticks read the operands
    in float32 (the same numbers: what was bf16 stays a bf16 value)."""
    args, ct, with_d = inputs(name)
    if path == "pallas":
        def scan(x, dt, A, B, C, D):
            return kernel.ssd_scan(x, dt, A, B, C, D if with_d else None,
                                   tile=2, interpret=True)
    else:
        args = tuple(v.astype(jnp.float32) for v in args)

        def scan(x, dt, A, B, C, D):
            return F.ssd_scan(x, dt, A, B, C, D if with_d else None,
                              chunk=kernel.CHUNK, path=path)

    out, grads = _staged.out_and_grads(scan, *args, ct=ct)
    return tuple(np.asarray(v, np.float32) for v in (out,) + grads)


@pytest.mark.parametrize("yardstick", ["recurrent", "chunked"])
@pytest.mark.parametrize("name", list(CASES))
def test_kernels_equal_the_yardsticks_in_values_and_all_gradients(
        name, yardstick):
    """Each of ``y, dx, ddt, dA, dB, dC, dD`` to ``TOL`` of the yardstick's
    largest magnitude."""
    dtype = CASES[name][5]
    got, want = results(name, "pallas"), results(name, yardstick)
    for which, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape, which
        assert np.isfinite(a).all(), which
        np.testing.assert_allclose(a, b, atol=TOL[dtype] * np.abs(b).max(),
                                   rtol=0, err_msg=which)
    if not CASES[name][6]:
        assert np.abs(got[-1]).max() == 0.0


def test_padded_positions_write_and_decay_nothing():
    """The first 400 positions of a row give the same outputs as those 400
    alone: the padding to whole chunks (``dt = 0``) reaches nothing."""
    (x, dt, A, B, C, D), _, _ = inputs("float32_two_rows_padded")
    short = kernel.ssd_scan(x[:, :400], dt[:, :400], A, B[:, :400],
                            C[:, :400], D, tile=2, interpret=True)
    whole = kernel.ssd_scan(x, dt, A, B, C, D, tile=2, interpret=True)
    np.testing.assert_allclose(short, whole[:, :400], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("tile", [1, 2, 4])
def test_tiles_change_nothing(tile):
    """One, two or four chunks a grid step: the same arithmetic, the state
    carried in the scratch or from one chunk to the next."""
    (x, dt, A, B, C, D), _, _ = inputs("bf16_one_row")
    want = kernel.ssd_scan(x, dt, A, B, C, D, tile=4, interpret=True)
    got = kernel.ssd_scan(x, dt, A, B, C, D, tile=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("x_shape, b_shape, dtype, chunk, ok", [
    ((2, 8192, 64, 64), (2, 8192, 8, 128), jnp.bfloat16, 128, True),
    ((1, 8192, 64, 64), (1, 8192, 8, 128), jnp.float32, 128, True),
    ((2, 8191, 64, 64), (2, 8191, 8, 128), jnp.bfloat16, 128, True),
    ((2, 200, 64, 64), (2, 200, 8, 128), jnp.bfloat16, 128, True),
    ((2, 8192, 16, 128), (2, 8192, 8, 128), jnp.bfloat16, 128, True),
    ((2, 384, 64, 64), (2, 384, 8, 128), jnp.bfloat16, 128, False),
    ((2, 8192, 64, 48), (2, 8192, 8, 128), jnp.bfloat16, 128, False),
    ((2, 8192, 16, 32), (2, 8192, 8, 128), jnp.bfloat16, 128, False),
    ((2, 8192, 64, 64), (2, 8192, 8, 64), jnp.bfloat16, 128, False),
    ((2, 8192, 64, 64), (2, 8192, 8, 128), jnp.bfloat16, 64, False),
    ((2, 8192, 64, 64), (2, 8192, 8, 128), jnp.float16, 128, False),
    ((2, 80, 6, 4), (2, 80, 2, 8), jnp.float32, 16, False),
], ids=["the_cell", "one_row_float32", "padded_to_whole_tiles",
        "shorter_than_a_tile", "one_head_a_block", "no_whole_tiles",
        "head_width_48", "group_under_a_block", "narrow_state",
        "another_chunk", "float16", "toy_widths"])
def test_supported(x_shape, b_shape, dtype, chunk, ok):
    assert kernel.supported(x_shape, b_shape, dtype, chunk) is ok


@pytest.mark.parametrize("backend, p, n, chunk, path", [
    ("cpu", 64, 128, 128, "chunked"), ("tpu", 48, 128, 128, "chunked"),
    ("tpu", 64, 64, 128, "chunked"), ("tpu", 64, 128, 64, "chunked"),
    ("tpu", 64, 128, 128, "pallas")])
def test_path_is_read_from_the_input(monkeypatch, backend, p, n, chunk,
                                     path):
    """A CPU backend, other widths or another chunk take XLA's chunked
    path, a TPU at the kernels' widths the kernels (traced here, not run),
    and ``ssd_scan_calls_staged_total{path}`` says which; a caller cannot
    ask for the kernels by name."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x = jax.ShapeDtypeStruct((2, 512, 16, p), jnp.bfloat16)
    vec = jax.ShapeDtypeStruct((2, 512, 16), jnp.float32)
    head = jax.ShapeDtypeStruct((16,), jnp.float32)
    bc = jax.ShapeDtypeStruct((2, 512, 2, n), jnp.bfloat16)
    with telemetry.scope(profile=False) as tel:
        jaxpr = jax.make_jaxpr(lambda *a: F.ssd_scan(*a, chunk=chunk))(
            x, vec, head, bc, bc, head)
        calls = tel.registry.get("ssd_scan_calls_staged_total")
        other = "chunked" if path == "pallas" else "pallas"
        assert calls.value(path=path) == 1 and calls.value(path=other) == 0
        assert tel.registry.get("ssd_chunks_total").value() == 512 // chunk
    assert ("pallas_call" in str(jaxpr)) is (path == "pallas")
    with pytest.raises(ValueError, match="unknown path"):
        F.ssd_scan(x, vec, head, bc, bc, path="pallas")


def test_the_backward_is_staged_once_and_keeps_one_state_a_chunk():
    """Two layers' calls share ``jit(_fwd)`` and ``jit(_bwd_call)``; what
    the forward keeps for the backward is its operands and the state
    entering each chunk, nothing of size ``chunk x chunk``."""
    (x, dt, A, B, C, D), _, _ = inputs("bf16_one_row")

    def two_layers(x, dt, A, B, C, D):
        for _ in range(2):
            x = kernel.ssd_scan(x, dt, A, B, C, D, tile=2, interpret=True)
        return jnp.sum(x.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(two_layers, argnums=tuple(
        range(6))))(x, dt, A, B, C, D))
    assert text.count("name=_fwd") == 2 and text.count("name=_bwd_call") == 2
    rows = jnp.reshape(x, (1, 512, 512))
    bc = jnp.reshape(B, (1, 512, 256))
    vec = jnp.zeros((1, 2, 2, 2, 4, 128), jnp.float32)
    d = jnp.zeros((2, 1, 256), jnp.float32)
    _, residuals = kernel._scan_fwd(rows, bc, bc, vec, vec, d, 4, 64, 2,
                                    True)
    shapes = sorted(tuple(r.shape) for r in residuals)
    assert shapes == sorted([(1, 512, 512)] + [(1, 512, 256)] * 2
                            + [(1, 2, 2, 2, 4, 128)] * 2 + [(2, 1, 256)]
                            + [(1, 2, 4, 256, 128)])


MAMBA = dict(num_heads=4, head_dim=64, n_groups=2, state_size=128,
             conv_kernel=4, chunk=128)


def test_a_step_at_the_kernels_widths_stages_them_once_a_mamba_layer(
        monkeypatch):
    """A decoder of the nemotron cell's kind at its mixers' widths (heads
    of 64, a state of 128, chunks of 128) and a toy hidden size, traced as
    on a TPU: every Mamba layer's scan is one staged call of the kernels,
    and the checkpoint's recomputation and the backward are transformations
    of that one (four a staging at the cell's four Mamba layers)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = MixedDecoderForPretraining(
        vocab_size=128, hidden_size=32, layer_types=["mamba", None] * 3
        + ["mamba"], heads_per_layer=[4] * 7,
        mlp_layer_types=[None, "sparse"] * 3 + [None],
        kv_heads=2, head_dim=16, rope={"full_attention": None},
        sliding_window=None, intermediate_size=0, num_experts=8,
        experts_per_token=2, expert_size=16, shared_expert_size=24,
        held_experts=(2, 4), routed_scaling_factor=2.5,
        router_scoring="sigmoid", router_selection_bias=True,
        expert_activation="relu2", mamba=MAMBA, epsilon=1e-5,
        checkpoint_blocks=True)
    params, buffers = state_of(model)
    ids = jnp.zeros((2, 256), jnp.int32)

    def loss(p):
        logits = functional_call(model, p, buffers, ids)[0]
        return jnp.mean(logits.astype(jnp.float32))

    with telemetry.scope(profile=False) as tel:
        text = str(jax.make_jaxpr(jax.grad(loss))(dict(params)))
        calls = tel.registry.get("ssd_scan_calls_staged_total")
        assert calls.value(path="pallas") == 4
        assert calls.value(path="chunked") == 0
    # each layer's forward, again under its checkpoint, and its backward
    assert text.count("name=_fwd") == 8
    assert text.count("name=_bwd_call") == 4

