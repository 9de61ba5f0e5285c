"""ISSUE 39: Mamba-2's state-space scan, the causal convolution's bias, the
gated norm in groups and gate first, and the ``Mamba2Mixer`` layer, at
small sizes on the CPU in float32. The recurrence over positions
(``path="recurrent"``) is the definition; the chunked path is held to it in
values and in the gradients of all six inputs."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from paddle_tpu import nn, telemetry
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import state_space


def inputs(seed, b=2, seq=80, heads=6, groups=2, p=4, n=8):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, seq, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, seq, heads)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    B = jax.random.normal(ks[3], (b, seq, groups, n))
    C = jax.random.normal(ks[4], (b, seq, groups, n))
    D = jax.random.normal(ks[5], (heads,))
    return x, dt, A, B, C, D


scan = functools.partial(_staged.run, F.ssd_scan)


def plain_loop(x, dt, A, B, C, D):
    """The module's opening lines in numpy, a position at a time."""
    x, dt, A, B, C = (np.asarray(v, np.float64) for v in (x, dt, A, B, C))
    b, seq, heads, p = x.shape
    groups, n = B.shape[2:]
    y = np.zeros(x.shape)
    for i in range(b):
        for h in range(heads):
            g = h // (heads // groups)
            s = np.zeros((p, n))
            for t in range(seq):
                s = np.exp(dt[i, t, h] * A[h]) * s \
                    + dt[i, t, h] * np.outer(x[i, t, h], B[i, t, g])
                y[i, t, h] = s @ C[i, t, g]
                if D is not None:
                    y[i, t, h] += float(D[h]) * x[i, t, h]
    return y


def test_recurrent_path_is_the_definition():
    args = inputs(0, b=1, seq=19)
    for D in (args[5], None):
        got = scan(*args[:5], D, path="recurrent")
        np.testing.assert_allclose(got, plain_loop(*args[:5], D), atol=2e-5,
                                   rtol=1e-5)


@pytest.mark.parametrize("with_d", [True, False], ids=["D", "no_D"])
@pytest.mark.parametrize("chunk,seq", [(16, 80), (16, 75), (32, 33), (64, 40)])
def test_chunked_equals_recurrent_in_values_and_all_gradients(chunk, seq,
                                                              with_d):
    """Three heads a group; a sequence that is a multiple of the chunk, one
    that is not and one shorter than a chunk."""
    args = inputs(1, seq=seq)[:6 if with_d else 5]      # D = None without
    ct = jax.random.normal(jax.random.key(9), args[0].shape)
    want, g_want = _staged.out_and_grads(functools.partial(
        F.ssd_scan, chunk=chunk, path="recurrent"), *args, ct=ct)
    got, g_got = _staged.out_and_grads(functools.partial(
        F.ssd_scan, chunk=chunk), *args, ct=ct)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * scale + 1e-6, name


def test_padding_positions_write_and_decay_nothing():
    """A row and the same row with positions after it: the first ``seq``
    outputs are the same, so the padding the chunked path adds (dt = 0)
    cannot reach them either."""
    x, dt, A, B, C, D = inputs(2, seq=40)
    short = scan(x[:, :37], dt[:, :37], A, B[:, :37], C[:, :37], D,
                 chunk=16)
    np.testing.assert_allclose(short, scan(x, dt, A, B, C, D,
                                           chunk=16)[:, :37], atol=1e-5)


def test_one_group_product_serves_its_heads():
    """``C B^T`` is formed once a group: the chunked path's jaxpr holds a
    product with the chunk's two position axes over the 2 groups, and none
    over the 8 heads."""
    x, dt, A, B, C, _ = inputs(3, seq=64, heads=8, groups=2)
    shapes = [tuple(e.outvars[0].aval.shape)
              for e in jax.make_jaxpr(lambda *a: state_space._chunked(
                  *a, 16))(*_grouped(x, dt, A, B, C)).jaxpr.eqns
              if e.primitive.name == "dot_general"]
    # (batch, chunks, groups, chunk, chunk): C B^T, once a group
    assert (2, 4, 2, 16, 16) in shapes
    assert not any(s[-2:] == (16, 16) and 8 in s for s in shapes)


def _grouped(x, dt, A, B, C):
    b, seq, heads, p = x.shape
    groups = B.shape[2]
    shape = (b, seq, groups, heads // groups)
    return (jnp.reshape(x, shape + (p,)), jnp.reshape(dt, shape),
            jnp.reshape(dt * A, shape), B, C)


def test_state_is_carried_from_chunk_to_chunk():
    """Without the state a chunk brings, the chunked path is the sum of
    independent chunks and differs from the recurrence: the carry does the
    work."""
    x, dt, A, B, C, D = inputs(4, seq=64)
    dt = dt * 0.05                              # slow decays: long memory
    want = scan(x, dt, A, B, C, D, path="recurrent")
    got = scan(x, dt, A, B, C, D, chunk=16)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    recurrent = jax.jit(functools.partial(F.ssd_scan, path="recurrent"))
    alone = jnp.concatenate([recurrent(x[:, i:i + 16], dt[:, i:i + 16], A,
                                       B[:, i:i + 16], C[:, i:i + 16], D)
                             for i in range(0, 64, 16)], axis=1)
    assert float(jnp.max(jnp.abs(alone - want))) > 0.1


def test_bf16_operands_stay_inside_their_rounding():
    x, dt, A, B, C, D = inputs(5, seq=64)
    want = scan(x, dt, A, B, C, D, path="recurrent")
    low = [v.astype(jnp.bfloat16) for v in (x, B, C)]
    got = scan(low[0], dt, A, low[1], low[2], D, chunk=16)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.linalg.norm((got.astype(jnp.float32) - want).ravel())
                / jnp.linalg.norm(want.ravel()))
    assert err < 2e-2, err


def test_staged_calls_are_counted_by_path(monkeypatch):
    args = inputs(6, seq=100)
    with telemetry.scope(profile=False) as tel:
        F.ssd_scan(*args, chunk=32)
        F.ssd_scan(*args, path="recurrent")
        calls = tel.registry.get("ssd_scan_calls_staged_total")
        assert calls.value(path="chunked") == 1
        assert calls.value(path="recurrent") == 1
        assert calls.value(path="pallas") == 0
        # 4 chunks of 32 cover 100 positions; the recurrence walks all 100
        assert tel.registry.get("ssd_chunks_total").value() == 104
    with pytest.raises(ValueError, match="unknown path"):
        F.ssd_scan(*args, path="pallas")
    # on a TPU the kernels' widths (heads of 64, a state of 128, chunks of
    # 128) stage the Pallas kernels, and the toy widths still XLA's path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = inputs(6, b=1, seq=256, heads=4, groups=2, p=64, n=128)
    with telemetry.scope(profile=False) as tel:
        jax.make_jaxpr(lambda *a: F.ssd_scan(*a))(*wide)
        jax.make_jaxpr(lambda *a: F.ssd_scan(*a, chunk=32))(*args)
        calls = tel.registry.get("ssd_scan_calls_staged_total")
        assert calls.value(path="pallas") == 1
        assert calls.value(path="chunked") == 1
        assert calls.value(path="recurrent") == 0


# -- the convolution's bias and the gated norm --------------------------------

def test_causal_conv_bias_is_added_once_a_channel():
    x = jax.random.normal(jax.random.key(0), (2, 30, 5))
    w = jax.random.normal(jax.random.key(1), (5, 4))
    bias = jax.random.normal(jax.random.key(2), (5,))
    got = F.causal_conv1d(x, w, bias)
    for c in range(5):
        want = np.convolve(np.asarray(x[1, :, c]),
                           np.asarray(w[c, ::-1]))[:30] + float(bias[c])
        np.testing.assert_allclose(got[1, :, c], want, atol=1e-5)
    np.testing.assert_allclose(F.causal_conv1d(x, w) + bias, got, atol=1e-6)


def test_gated_norm_gate_first_in_groups():
    x = jax.random.normal(jax.random.key(0), (2, 3, 12))
    z = jax.random.normal(jax.random.key(1), (2, 3, 12))
    w = jax.random.normal(jax.random.key(2), (12,))
    got = F.gated_rms_norm(x, z, w, 1e-5, group_size=4,
                           norm_before_gate=False)
    y = np.asarray(x * jax.nn.silu(z)).reshape(2, 3, 3, 4)
    want = y / np.sqrt(np.mean(y * y, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(2, 3, 12) * np.asarray(w),
                               rtol=1e-5, atol=1e-6)
    # the default is the norm over all lanes, then the gate
    plain = np.asarray(x) / np.sqrt(np.mean(np.asarray(x) ** 2, -1,
                                            keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        F.gated_rms_norm(x, z, w, 1e-5),
        plain * np.asarray(w) * np.asarray(jax.nn.silu(z)), rtol=1e-5,
        atol=1e-6)
    # one group of all lanes, gate after: the default again
    np.testing.assert_allclose(F.gated_rms_norm(x, z, w, 1e-5, group_size=12),
                               F.gated_rms_norm(x, z, w, 1e-5), atol=1e-6)


# -- the layer -----------------------------------------------------------------

SIZES = dict(num_heads=4, head_dim=6, n_groups=2, state_size=5,
             conv_kernel=4, chunk=8)


def mixer(seed=0):
    from paddle_tpu.framework.random import rng_guard
    with rng_guard(jax.random.key(seed)):
        return nn.Mamba2Mixer(16, epsilon=1e-5, **SIZES)


def test_initialisers_are_the_published_ones():
    m = mixer()
    p = {k: np.asarray(v.value) for k, v in m.named_parameters()}
    assert set(p) == {"in_proj.weight", "conv_weight", "conv_bias", "A_log",
                      "D", "dt_bias", "norm.weight", "out_proj.weight"}
    conv_dim = 4 * 6 + 2 * 2 * 5
    assert p["in_proj.weight"].shape == (16, 4 * 6 + conv_dim + 4)
    assert p["conv_weight"].shape == (conv_dim, 4)
    assert p["conv_bias"].shape == (conv_dim,)
    assert np.abs(p["conv_weight"]).max() <= 0.5
    assert np.abs(p["conv_bias"]).max() <= 0.5
    np.testing.assert_allclose(p["A_log"], np.log([1, 2, 3, 4]), rtol=1e-6)
    np.testing.assert_array_equal(p["D"], 1.0)
    np.testing.assert_array_equal(p["norm.weight"], 1.0)
    # softplus(dt_bias) is the step: in [1e-3, 0.1] and at least 1e-4
    big = nn.Mamba2Mixer(16, num_heads=4096, head_dim=1, n_groups=1,
                         state_size=1)
    step = np.asarray(jax.nn.softplus(big.dt_bias.value))
    assert step.min() >= 1e-3 * (1 - 1e-5) and step.max() <= 0.1 * (1 + 1e-5)
    # log-uniform: the median step lies near the geometric mean
    assert abs(math.log(np.median(step)) - math.log(1e-2)) < 0.15


def test_the_layer_is_its_equations():
    """The mixer against a loop over positions in numpy, from the same
    weights: projection, convolution with its bias, the scan, the gate
    first, the norm in two groups, the output projection."""
    m = mixer(1)
    p = {k: np.asarray(v.value, np.float64) for k, v in m.named_parameters()}
    x = np.asarray(jax.random.normal(jax.random.key(3), (2, 21, 16)))
    got = np.asarray(_staged.run(m, jnp.asarray(x, jnp.float32)))
    h, d, g, n = 4, 6, 2, 5
    inner = h * d
    proj = x @ p["in_proj.weight"]
    z, xbc, dt = proj[..., :inner], proj[..., inner:-h], proj[..., -h:]
    conv = np.zeros_like(xbc)
    for t in range(21):
        for j in range(4):
            src = t - 3 + j
            if src >= 0:
                conv[:, t] += xbc[:, src] * p["conv_weight"][:, j]
    conv = conv + p["conv_bias"]
    conv = conv / (1 + np.exp(-conv))
    xs = conv[..., :inner].reshape(2, 21, h, d)
    B = conv[..., inner:inner + g * n].reshape(2, 21, g, n)
    C = conv[..., inner + g * n:].reshape(2, 21, g, n)
    step = np.log1p(np.exp(dt + p["dt_bias"]))
    y = plain_loop(xs, step, -np.exp(p["A_log"]), B, C, p["D"])
    y = y.reshape(2, 21, inner) * (z / (1 + np.exp(-z)))
    y = y.reshape(2, 21, g, inner // g)
    y = y / np.sqrt(np.mean(y * y, -1, keepdims=True) + 1e-5)
    want = (y.reshape(2, 21, inner) * p["norm.weight"]) @ p["out_proj.weight"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_scopes_name_every_part_of_the_mixer():
    m = mixer()
    text = jax.jit(lambda x: m(x)).lower(
        jnp.zeros((1, 16, 16))).as_text(debug_info=True)
    for scope in ("in_proj", "causal_conv", "ssd_scan", "gated_norm/norm",
                  "out_proj"):
        assert "mamba2mixer/" + scope in text, scope
