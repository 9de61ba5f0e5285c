"""OpTest-style numeric-gradient harness (reference:
fluid/tests/unittests/op_test.py:270 OpTest — check_output vs reference impl,
check_grad vs finite differences :110,:1409)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _staged


check_grad = functools.partial(_staged.check_grad, rtol=5e-2, atol=5e-3)


class TestActivationGrads:
    @pytest.mark.parametrize("name", ["relu", "gelu", "sigmoid", "tanh",
                                      "softplus", "silu", "mish", "hardswish",
                                      "elu", "selu"])
    def test_grad_matches_numeric(self, name):
        from paddle_tpu.nn import functional as F
        fn = getattr(F, name)
        x = np.random.RandomState(0).randn(4, 5).astype(np.float32) + 0.3
        check_grad(fn, x)


class TestLossGrads:
    def test_cross_entropy_grad(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(1)
        logits = rs.randn(6, 4).astype(np.float32)
        label = rs.randint(0, 4, (6,))
        check_grad(lambda v: F.cross_entropy(v, jnp.asarray(label)), logits)

    def test_mse_matches_numpy(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(2)
        a, b = rs.randn(8, 3), rs.randn(8, 3)
        got = float(F.mse_loss(jnp.asarray(a, dtype=jnp.float32),
                               jnp.asarray(b, dtype=jnp.float32)))
        np.testing.assert_allclose(got, ((a - b) ** 2).mean(), rtol=1e-5)


class TestConvAgainstReference:
    def test_conv2d_matches_manual(self):
        """conv2d vs direct im2col computation."""
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(3)
        x = rs.randn(2, 3, 8, 8).astype(np.float32)
        w = rs.randn(4, 3, 3, 3).astype(np.float32)
        out = np.asarray(F.conv2d(jnp.asarray(x), jnp.asarray(w), padding=1))
        # manual reference
        xp = np.pad(x, [(0, 0), (0, 0), (1, 1), (1, 1)])
        ref = np.zeros((2, 4, 8, 8), dtype=np.float32)
        for n in range(2):
            for o in range(4):
                for i in range(8):
                    for j in range(8):
                        ref[n, o, i, j] = np.sum(
                            xp[n, :, i:i + 3, j:j + 3] * w[o])
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_conv2d_grad(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(4)
        x = rs.randn(1, 2, 5, 5).astype(np.float32)
        w = jnp.asarray(rs.randn(3, 2, 3, 3).astype(np.float32))
        check_grad(lambda v: F.conv2d(v, w, padding=1), x)

    def test_conv2d_transpose_shape_inverts(self):
        from paddle_tpu.nn import functional as F
        x = jnp.ones((2, 4, 7, 7))
        w = jnp.ones((4, 5, 3, 3))  # (in, out, kh, kw)
        y = F.conv2d_transpose(x, w, stride=2, padding=1, output_padding=1)
        assert y.shape == (2, 5, 14, 14)


class TestNormOps:
    def test_layer_norm_stats(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(5)
        x = rs.randn(4, 16).astype(np.float32)
        y = np.asarray(F.layer_norm(jnp.asarray(x), 16))
        np.testing.assert_allclose(y.mean(-1), 0, atol=1e-5)
        np.testing.assert_allclose(y.std(-1), 1, atol=1e-2)

    def test_batch_norm_train_updates_stats(self):
        import paddle_tpu as paddle
        bn = paddle.nn.BatchNorm2D(3, momentum=0.5)
        x = jnp.asarray(np.random.RandomState(6).randn(4, 3, 5, 5),
                        dtype=jnp.float32)
        bn.train()
        _ = bn(x)
        assert not np.allclose(np.asarray(bn._mean), 0.0)

    def test_group_norm(self):
        from paddle_tpu.nn import functional as F
        x = jnp.asarray(np.random.RandomState(7).randn(2, 8, 4, 4),
                        dtype=jnp.float32)
        y = F.group_norm(x, num_groups=4)
        grouped = np.asarray(y).reshape(2, 4, 2, 4, 4)
        np.testing.assert_allclose(grouped.mean(axis=(2, 3, 4)), 0, atol=1e-4)


class TestPooling:
    def test_max_pool2d(self):
        from paddle_tpu.nn import functional as F
        x = jnp.arange(16.0).reshape(1, 1, 4, 4)
        y = F.max_pool2d(x, 2, 2)
        np.testing.assert_allclose(np.asarray(y)[0, 0],
                                   [[5, 7], [13, 15]])

    def test_adaptive_avg_pool(self):
        from paddle_tpu.nn import functional as F
        x = jnp.ones((2, 3, 8, 8))
        y = F.adaptive_avg_pool2d(x, 1)
        assert y.shape == (2, 3, 1, 1)
        np.testing.assert_allclose(np.asarray(y), 1.0)


class TestRNN:
    def test_lstm_forward_shapes(self):
        import paddle_tpu as paddle
        lstm = paddle.nn.LSTM(4, 8, num_layers=2)
        x = jnp.ones((3, 5, 4))
        out, (h, c) = lstm(x)
        assert out.shape == (3, 5, 8)
        assert h.shape == (2, 3, 8)
        assert c.shape == (2, 3, 8)

    def test_bidirectional_gru(self):
        import paddle_tpu as paddle
        gru = paddle.nn.GRU(4, 6, direction="bidirect")
        x = jnp.ones((2, 7, 4))
        out, h = gru(x)
        assert out.shape == (2, 7, 12)
        assert h.shape == (2, 2, 6)

    def test_lstm_grad_flows(self):
        import paddle_tpu as paddle
        from paddle_tpu.jit.functionalization import state_of, functional_call
        lstm = paddle.nn.LSTM(3, 4)
        params, buffers = state_of(lstm)
        x = jnp.ones((2, 5, 3))

        def loss(p):
            (out, _), _ = functional_call(lstm, p, buffers, x)
            return jnp.sum(out)

        g = jax.grad(loss)(params)
        assert all(np.isfinite(np.asarray(v)).all() for v in g.values())


class TestNewOpGrads:
    """Numeric-grad coverage for ops added in the parity sweeps (SURVEY §4:
    every op gets analytic-vs-finite-difference checking)."""

    def test_hsigmoid_loss_grad(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(0)
        w = rs.randn(5, 6).astype("float32")
        label = jnp.asarray([0, 3, 5])
        x0 = rs.randn(3, 6).astype("float32")
        check_grad(lambda x: F.hsigmoid_loss(x, label, 6, w, None), x0)

    def test_dice_loss_grad(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(1)
        probs = jax.nn.softmax(jnp.asarray(rs.randn(4, 3), jnp.float32))
        label = jnp.asarray(rs.randint(0, 3, (4, 1)))
        check_grad(lambda x: F.dice_loss(jax.nn.softmax(x), label),
                   rs.randn(4, 3).astype("float32"))

    def test_diag_embed_grad(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(2)
        check_grad(lambda x: F.diag_embed(x, offset=1),
                   rs.randn(2, 4).astype("float32"))

    def test_temporal_shift_grad(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(3)
        check_grad(lambda x: F.temporal_shift(x, seg_num=2, shift_ratio=0.25),
                   rs.randn(4, 4, 2, 2).astype("float32"))

    def test_cross_entropy_fast_path_grad(self):
        # the lse-gather hard-label fast path must match finite differences
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(4)
        label = jnp.asarray(rs.randint(0, 7, (5,)))
        check_grad(lambda x: F.cross_entropy(x, label),
                   rs.randn(5, 7).astype("float32"))

    def test_cross_entropy_fast_path_matches_log_softmax(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(6, 9), jnp.float32)
        label = jnp.asarray(rs.randint(0, 9, (6,)))
        fast = F.cross_entropy(x, label)
        ref = -jnp.take_along_axis(jax.nn.log_softmax(x, -1),
                                   label[:, None], axis=-1).mean()
        np.testing.assert_allclose(float(fast), float(ref), rtol=1e-5)

    def test_cross_entropy_ignore_index_fast_path(self):
        from paddle_tpu.nn import functional as F
        rs = np.random.RandomState(6)
        x = jnp.asarray(rs.randn(4, 5), jnp.float32)
        label = jnp.asarray([1, -100, 3, -100])
        out = F.cross_entropy(x, label)
        ref = -jnp.take_along_axis(jax.nn.log_softmax(x, -1),
                                   jnp.asarray([[1], [0], [3], [0]]),
                                   axis=-1)[:, 0]
        expect = (ref[0] + ref[2]) / 2
        np.testing.assert_allclose(float(out), float(expect), rtol=1e-5)

    def test_sequence_conv_grad(self):
        from paddle_tpu import static
        rs = np.random.RandomState(7)
        from paddle_tpu.static import Scope, scope_guard
        with scope_guard(Scope()):
            # create deterministic params once
            x0 = rs.randn(2, 5, 3).astype("float32")
            static.nn.sequence_conv(jnp.asarray(x0), 4, filter_size=3,
                                    name="sconv_g")
            check_grad(lambda x: static.nn.sequence_conv(
                x, 4, filter_size=3, name="sconv_g"), x0)

    def test_row_conv_grad(self):
        from paddle_tpu import static
        from paddle_tpu.static import Scope, scope_guard
        rs = np.random.RandomState(8)
        with scope_guard(Scope()):
            x0 = rs.randn(2, 4, 3).astype("float32")
            static.nn.row_conv(jnp.asarray(x0), 2, name="rc_g")
            check_grad(lambda x: static.nn.row_conv(x, 2, name="rc_g"), x0)

    def test_adadelta_matches_reference_formula(self):
        import paddle_tpu as paddle
        opt = paddle.optimizer.Adadelta(learning_rate=1.0, rho=0.9,
                                        epsilon=1e-6)
        p = jnp.asarray([1.0, 2.0])
        g = jnp.asarray([0.5, -0.5])
        slots = opt.init_slots(p)
        new_p, new_slots = opt.update(p, g, slots, 1.0, jnp.asarray(1))
        asg = 0.1 * 0.25
        upd = 0.5 * np.sqrt(1e-6) / np.sqrt(asg + 1e-6)
        np.testing.assert_allclose(np.asarray(new_p)[0], 1.0 - upd, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(new_slots["avg_squared_grad"]),
                                   [asg, asg], rtol=1e-5)
