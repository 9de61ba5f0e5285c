"""ISSUE 33: the gated delta rule and the short causal convolution, at small
sizes on the CPU with seeded inputs. The recurrence over positions
(``path="recurrent"``) is the definition; the chunked path, its triangular
systems and its scan over chunk states are held to it, in values and in all
five gradients."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from paddle_tpu import nn, telemetry
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import linear_attention as la


def inputs(seed, b=2, seq=128, h=3, dk=16, dv=24, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, seq, h, dk))
    k = jax.random.normal(ks[1], (b, seq, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, seq, h, dv))
    g = -2.0 * jax.random.uniform(ks[3], (b, seq, h))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, seq, h)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


rule = functools.partial(_staged.run, F.gated_delta_rule)


def out_and_grads(args, ct, **kw):
    """The rule's output and its five gradients, as one program."""
    return _staged.out_and_grads(functools.partial(F.gated_delta_rule, **kw),
                                 *args, ct=ct)


def plain_loop(q, k, v, g, beta):
    """The module's opening lines in numpy, a position at a time."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    b, seq, h, dk = q.shape
    out = np.zeros(v.shape)
    for i in range(b):
        for j in range(h):
            s = np.zeros((dk, v.shape[-1]))
            for t in range(seq):
                s = np.exp(g[i, t, j]) * s
                u = beta[i, t, j] * (v[i, t, j] - s.T @ k[i, t, j])
                s = s + np.outer(k[i, t, j], u)
                out[i, t, j] = s.T @ q[i, t, j]
    return out


def test_recurrent_path_is_the_definition():
    args = inputs(0, b=1, seq=24, h=2, dk=4, dv=5)
    got = rule(*args, path="recurrent")
    np.testing.assert_allclose(got, plain_loop(*args), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("seq", [128, 100], ids=["whole_chunks", "padded"])
def test_chunked_equals_recurrent_in_values_and_all_gradients(chunk, seq):
    """float32 to 1e-5 of the largest value; a sequence of 100 positions is
    padded with beta = 0, g = 0 and cut again."""
    args = inputs(1, seq=seq)
    ct = jax.random.normal(jax.random.key(9), args[2].shape)
    want, g_want = out_and_grads(args, ct, path="recurrent")
    got, g_got = out_and_grads(args, ct, chunk=chunk)
    assert got.shape == want.shape == args[2].shape
    np.testing.assert_allclose(got, want, atol=1e-5 * float(
        jnp.max(jnp.abs(want))), rtol=0)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5 * float(
            jnp.max(jnp.abs(b))), rtol=0)


def test_chunk_sizes_agree_with_each_other():
    args = inputs(2, seq=192)
    outs = [rule(*args, chunk=c) for c in (16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-6)


def test_bf16_operands_stay_inside_their_rounding():
    """q, k, v in bf16 are read as they are (exact products, float32
    sums); decays, systems and states are float32 whatever the operands
    are. Against the same bf16 inputs through the float32 recurrence the
    result differs by its own rounding to bf16, 2**-8 of a value; the
    bound stated is 1% of the largest value, and 2% for a gradient."""
    args = inputs(3, dtype=jnp.bfloat16)
    ct = jax.random.normal(jax.random.key(9), args[2].shape)
    f32 = tuple(x.astype(jnp.float32) for x in args)
    got, g_got = out_and_grads(args, ct)
    assert got.dtype == jnp.bfloat16
    want, g_want = out_and_grads(f32, ct, path="recurrent")
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
        < 0.01 * scale
    for a, b in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) \
            < 0.02 * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("rows", [1, 2])
def test_head_groups_change_nothing(monkeypatch, rows):
    """``GROUP_HEADS`` heads at once, whatever the batch: four heads one at
    a time and two at a time, in values and gradients, for one row (what
    the benchmark's comparison samples) as for two (what it times)."""
    args = inputs(4, b=rows, h=4)
    ct = jax.random.normal(jax.random.key(9), args[2].shape)
    assert [la._head_groups(4, n) for n in (64, 8, 4, 3, 2, 1)] \
        == [1, 1, 1, 1, 2, 4]
    assert la._head_groups(32, la.GROUP_HEADS) == 2
    whole, whole_grads = out_and_grads(args, ct)
    for group_heads in (1, 2):
        # a program of its own after the patch: the global is read as the
        # rule is traced
        monkeypatch.setattr(la, "GROUP_HEADS", group_heads)
        out, out_grads = out_and_grads(args, ct)
        np.testing.assert_allclose(out, whole, atol=1e-6)
        for a, b in zip(out_grads, whole_grads):
            np.testing.assert_allclose(a, b, atol=2e-6)


def test_without_gate_and_at_full_steps_it_is_the_plain_delta_rule():
    """g = 0, beta = 1: S <- S + k (v - S^T k)^T. A key seen once is then
    retrieved exactly: with orthonormal keys o_t = v_t for q_t = k_t."""
    seq, dk = 8, 8
    k = jnp.eye(dk)[None, :, None, :]                       # (1, 8, 1, 8)
    v = jax.random.normal(jax.random.key(0), (1, seq, 1, 5))
    zeros, ones = jnp.zeros((1, seq, 1)), jnp.ones((1, seq, 1))
    for kw in ({"path": "recurrent"}, {"chunk": 4}):
        np.testing.assert_allclose(
            rule(k, k, v, zeros, ones, **kw), v, atol=1e-6)
    # and a key written twice is overwritten, not added to
    k2 = jnp.concatenate([k[:, :1]] * 2, axis=1)
    out = rule(k2, k2, v[:, :2], zeros[:, :2], ones[:, :2], chunk=2)
    np.testing.assert_allclose(out[:, 1], v[:, 1], atol=1e-6)


def test_with_beta_zero_the_state_only_decays():
    """One write at position 0, then beta = 0: what q reads back is the
    write times the decay since."""
    seq, dk, dv = 16, 4, 3
    k = jnp.broadcast_to(jnp.eye(dk)[0], (1, seq, 1, dk))
    v = jax.random.normal(jax.random.key(1), (1, seq, 1, dv))
    beta = jnp.zeros((1, seq, 1)).at[:, 0].set(1.0)
    g = jnp.full((1, seq, 1), -0.25).at[:, 0].set(0.0)
    want = v[:, :1] * jnp.exp(-0.25 * jnp.arange(seq))[None, :, None, None]
    for kw in ({"path": "recurrent"}, {"chunk": 4}, {"chunk": 16}):
        np.testing.assert_allclose(
            rule(k, k, v, g, beta, **kw), want, rtol=1e-5)


@pytest.mark.parametrize("n", [4, 16, 64])
def test_inverse_by_products_against_linalg_inv(n):
    a = jnp.tril(jax.random.normal(jax.random.key(n), (3, 2, n, n)), -1) * 0.3
    want = jnp.linalg.inv(jnp.eye(n) + a)
    got = la.inverse_unit_lower(a)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(
        jnp.max(jnp.abs(want))), rtol=1e-4)
    # what lies on or above the diagonal is not read
    np.testing.assert_array_equal(
        la.inverse_unit_lower(a + jnp.triu(jnp.ones((n, n)))), got)
    ct = jax.random.normal(jax.random.key(1), a.shape)
    g = _staged.grads(lambda a: jnp.sum(la.inverse_unit_lower(a) * ct), a)
    g_ref = _staged.grads(lambda a: jnp.sum(jnp.linalg.inv(
        jnp.eye(n) + jnp.tril(a, -1)) * ct), a)
    np.testing.assert_allclose(g, g_ref, atol=1e-4 * float(
        jnp.max(jnp.abs(g_ref))))


def test_inverse_stays_exact_where_keys_repeat():
    """64 equal keys at beta = 1 and no decay: ``a`` is all ones under the
    diagonal and the inverse is 1 on it, -1 just under it. The block
    inversion gives it exactly; the product of ``I + (-a)^(2^k)`` meets
    powers of 1e17 on the way."""
    a = jnp.tril(jnp.ones((64, 64)), -1)
    want = jnp.eye(64) - jnp.eye(64, k=-1)
    np.testing.assert_array_equal(la.inverse_unit_lower(a), want)
    with pytest.raises(ValueError, match="power of two"):
        la.inverse_unit_lower(jnp.zeros((48, 48)))


def test_causal_conv_against_numpy_convolve():
    x = jax.random.normal(jax.random.key(0), (2, 40, 6))
    w = jax.random.normal(jax.random.key(1), (6, 4))
    got = F.causal_conv1d(x, w)
    assert got.shape == x.shape and got.dtype == x.dtype
    for b in range(2):
        for c in range(6):
            want = np.convolve(np.asarray(x[b, :, c]),
                               np.asarray(w[c, ::-1]))[:40]
            np.testing.assert_allclose(got[b, :, c], want, atol=1e-5)
    assert F.causal_conv1d(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


def test_causal_conv_never_reads_the_next_position():
    x = jax.random.normal(jax.random.key(0), (1, 20, 3))
    w = jax.random.normal(jax.random.key(1), (3, 4))
    changed = x.at[:, 11:].add(5.0)
    a, b = F.causal_conv1d(x, w), F.causal_conv1d(changed, w)
    np.testing.assert_array_equal(a[:, :11], b[:, :11])
    assert not np.allclose(a[:, 11], b[:, 11])
    # position t reads t - 3 .. t: the gradient of out[t] reaches 4 inputs
    reach = jax.grad(lambda x: F.causal_conv1d(x, w)[0, 10, 0])(x)
    assert np.flatnonzero(np.asarray(reach[0, :, 0])).tolist() == [7, 8, 9,
                                                                   10]


def test_norms_of_the_linear_layer():
    x = jax.random.normal(jax.random.key(0), (2, 5, 16))
    z = jax.random.normal(jax.random.key(1), (2, 5, 16))
    w = jax.random.normal(jax.random.key(2), (16,)) * 0.1
    unit = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(F.rms_norm(x, w, 1e-6, offset=1.0),
                               unit * (1 + w), rtol=1e-5)
    np.testing.assert_allclose(F.gated_rms_norm(x, z, w, 1e-6),
                               unit * w * z / (1 + np.exp(-z)), rtol=1e-5,
                               atol=1e-6)
    zero_centred = nn.RMSNorm(16, 1e-6, offset=1.0)
    assert float(jnp.abs(zero_centred.weight.value).max()) == 0.0
    np.testing.assert_allclose(zero_centred(x), unit, rtol=1e-5)
    np.testing.assert_allclose(zero_centred.scale(), np.ones(16))
    plain = nn.RMSNorm(16, 1e-6)
    assert plain.scale() is plain.weight.value
    gated = nn.GatedRMSNorm(16, 1e-6)
    np.testing.assert_allclose(gated(x, z), unit * z / (1 + np.exp(-z)),
                               rtol=1e-5, atol=1e-6)
    half = F.rms_norm(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), 1e-6,
                      offset=1.0)
    assert half.dtype == jnp.bfloat16


def test_the_layer_is_its_equations():
    """nn.GatedDeltaNet against its docstring written out with the
    recurrent path, and the parameters it starts from."""
    layer = nn.GatedDeltaNet(32, 2, 4, 8, 8, conv_kernel=4)
    p = dict(layer.named_parameters())
    assert {k: tuple(v.shape) for k, v in p.items()} == {
        "conv_weight": (64, 4), "A_log": (4,), "dt_bias": (4,),
        "in_proj_qkvz.weight": (32, 96), "in_proj_ba.weight": (32, 8),
        "norm.weight": (8,), "out_proj.weight": (32, 32)}
    assert float(jnp.exp(p["A_log"].value).max()) < 16
    assert float(jnp.abs(p["conv_weight"].value).max()) <= 0.5
    np.testing.assert_array_equal(p["dt_bias"].value, np.ones(4))
    x = jax.random.normal(jax.random.key(0), (2, 48, 32))
    qkvz = x @ p["in_proj_qkvz.weight"].value
    ba = x @ p["in_proj_ba.weight"].value
    mixed = jax.nn.silu(F.causal_conv1d(qkvz[..., :64],
                                        p["conv_weight"].value))
    q = mixed[..., :16].reshape(2, 48, 2, 8)
    k = mixed[..., 16:32].reshape(2, 48, 2, 8)
    v = mixed[..., 32:].reshape(2, 48, 4, 8)
    z = qkvz[..., 64:].reshape(2, 48, 4, 8)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(8)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(p["A_log"].value) * jax.nn.softplus(
        ba[..., 4:] + p["dt_bias"].value)
    o = rule(jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v, g,
             jax.nn.sigmoid(ba[..., :4]), path="recurrent")
    y = F.gated_rms_norm(o, z, p["norm.weight"].value, 1e-6)
    want = y.reshape(2, 48, 32) @ p["out_proj.weight"].value
    np.testing.assert_allclose(_staged.run(layer, x), want, atol=2e-5)
    with pytest.raises(ValueError, match="value heads"):
        nn.GatedDeltaNet(32, 3, 4, 8, 8)


@pytest.mark.parametrize("rows", [1, 2])
def test_a_sampled_row_takes_the_path_a_step_takes(rows):
    """The benchmark compares one row and times two: at the cell's 32 heads
    both stage one loop over two groups of 16 heads, forward, and a loop
    over the same two groups in the backward pass, each with the scans over
    the chunk states inside it."""
    args = inputs(3, b=rows, seq=32, h=32, dk=4, dv=4)

    def loops(jaxpr, depth=0):
        """(depth, trips) of every scan, outermost first."""
        found = []
        for eqn in jaxpr.eqns:
            inner = depth
            if eqn.primitive.name == "scan":
                found.append((depth, eqn.params["length"]))
                inner += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += loops(sub, inner)
        return found

    fwd = loops(jax.make_jaxpr(
        lambda *a: F.gated_delta_rule(*a, chunk=16))(*args).jaxpr)
    assert [trips for depth, trips in fwd if depth == 0] == [2]
    assert (1, 2) in fwd                        # 32 positions: two chunks
    ct = jnp.ones(args[2].shape)
    bwd = loops(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        F.gated_delta_rule(*a, chunk=16) * ct), (0, 1, 2, 3, 4)))(
            *args).jaxpr)
    assert [trips for depth, trips in bwd if depth == 0] == [2, 2]


def test_staged_calls_are_counted_by_path():
    args = inputs(5, seq=100)
    with telemetry.scope(profile=False) as tel:
        F.gated_delta_rule(*args, chunk=32)
        F.gated_delta_rule(*args, path="recurrent")
        calls = tel.registry.get("linear_attn_calls_staged_total")
        assert calls.value(path="chunked") == 1
        assert calls.value(path="recurrent") == 1
        # 4 chunks of 32 cover 100 positions; the recurrence walks all 100
        assert tel.registry.get("gated_delta_chunks_total").value() == 104
    with pytest.raises(ValueError, match="unknown path"):
        F.gated_delta_rule(*args, path="pallas")
