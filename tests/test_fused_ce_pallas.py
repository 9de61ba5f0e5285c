"""Fused LM-head cross-entropy Pallas kernel (ISSUE 6): the blockwise
online-logsumexp kernel must match ``chunked_lm_ce`` (itself verified
against dense logits) in loss AND grads, across swept block configs, via
the Pallas interpreter on CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _staged
from paddle_tpu.nn.functional import fused_linear_cross_entropy
from paddle_tpu.nn.functional.attention import _xla_attention
from paddle_tpu.ops.chunked_ce import chunked_lm_ce
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.fused_ce import fused_ce_supported, fused_lm_ce


def _data(n=128, h=64, v=512, seed=0, ignore_frac=0.0):
    rs = np.random.RandomState(seed)
    hid = jnp.asarray(rs.randn(n, h), jnp.float32)
    w = jnp.asarray(rs.randn(h, v) * 0.05, jnp.float32)
    y = rs.randint(0, v, n).astype("i4")
    if ignore_frac:
        y[rs.rand(n) < ignore_frac] = -100
    return hid, w, jnp.asarray(y)


def _both(hid, w, y, bt, bv):
    """(loss, (dh, dw)) for fused kernel and chunked reference."""
    fu = _staged.value_and_grads(lambda a, b: fused_lm_ce(
        a, b, y, block_tokens=bt, block_vocab=bv, interpret=True), hid, w)
    ref = _staged.value_and_grads(lambda a, b: chunked_lm_ce(a, b, y), hid, w)
    return fu, ref


class TestFusedCeParity:
    @pytest.mark.parametrize("bt,bv", [(128, 512), (64, 256), (8, 128)])
    def test_loss_and_grads_match_chunked(self, bt, bv):
        hid, w, y = _data()
        (lf, (dhf, dwf)), (lr, (dhr, dwr)) = _both(hid, w, y, bt, bv)
        assert float(lf) == pytest.approx(float(lr), abs=1e-3)
        np.testing.assert_allclose(np.asarray(dhf), np.asarray(dhr),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dwf), np.asarray(dwr),
                                   rtol=1e-4, atol=1e-5)

    def test_non_divisible_shapes(self):
        """Token and vocab counts that divide into NEITHER block size:
        the padded tail must not leak into loss or grads."""
        hid, w, y = _data(n=200, h=32, v=500, seed=1)
        (lf, (dhf, dwf)), (lr, (dhr, dwr)) = _both(hid, w, y, 64, 256)
        assert float(lf) == pytest.approx(float(lr), abs=1e-3)
        np.testing.assert_allclose(np.asarray(dhf), np.asarray(dhr),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dwf), np.asarray(dwr),
                                   rtol=1e-4, atol=1e-5)

    def test_ignore_index_rows_drop_out(self):
        hid, w, y = _data(n=96, seed=2, ignore_frac=0.4)
        (lf, (dhf, _)), (lr, (dhr, _)) = _both(hid, w, y, 32, 256)
        assert float(lf) == pytest.approx(float(lr), abs=1e-3)
        ignored = np.asarray(y) == -100
        assert ignored.any()
        np.testing.assert_array_equal(np.asarray(dhf)[ignored], 0.0)
        np.testing.assert_allclose(np.asarray(dhf), np.asarray(dhr),
                                   rtol=1e-4, atol=1e-5)

    def test_all_ignored_is_zero_loss_zero_grads(self):
        hid, w, _ = _data(n=32)
        y = jnp.full((32,), -100, jnp.int32)
        loss, (dh, dw) = _staged.value_and_grads(
            lambda a, b: fused_lm_ce(a, b, y, block_tokens=16,
                                     block_vocab=256, interpret=True), hid, w)
        assert float(loss) == 0.0
        np.testing.assert_array_equal(np.asarray(dh), 0.0)
        np.testing.assert_array_equal(np.asarray(dw), 0.0)

    def test_db_resolved_blocks_default_path(self):
        """block_tokens/block_vocab=None resolves from the tuning DB at
        trace time — must still be numerically correct."""
        hid, w, y = _data()
        lf = fused_lm_ce(hid, w, y, interpret=True)
        lr = chunked_lm_ce(hid, w, y)
        assert float(lf) == pytest.approx(float(lr), abs=1e-3)

    def test_not_supported_on_cpu(self):
        assert jax.default_backend() != "tpu"
        assert not fused_ce_supported()


class TestFusedLinearCrossEntropyDispatch:
    def test_pallas_equals_chunked_kernel(self):
        hid, w, y = _data()
        a = fused_linear_cross_entropy(hid, w, y, kernel="pallas",
                                       interpret=True)
        b = fused_linear_cross_entropy(hid, w, y, kernel="chunked")
        assert float(a) == pytest.approx(float(b), abs=1e-3)

    def test_auto_falls_back_on_cpu(self):
        from paddle_tpu import telemetry
        from paddle_tpu.telemetry.metrics import Registry
        hid, w, y = _data(n=32)
        prev = telemetry.get_registry()
        reg = Registry()
        telemetry._set_registry(reg)
        telemetry.enable()
        try:
            out = fused_linear_cross_entropy(hid, w, y, kernel="auto")
            assert np.isfinite(float(out))
            assert reg.get("pallas_config_resolved_total").value(
                kernel="fused_ce", source="fallback") == 1
        finally:
            telemetry.disable()
            telemetry._set_registry(prev)

    def test_unknown_kernel_raises(self):
        hid, w, y = _data(n=32)
        with pytest.raises(ValueError, match="kernel"):
            fused_linear_cross_entropy(hid, w, y, kernel="nope")


class TestFlashSweptConfigs:
    """Flash attention at the tuner's candidate block configs (the sweep
    the DB entries come from) — parity with the XLA reference."""

    @pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128)])
    def test_forward_parity(self, bq, bk):
        rs = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rs.randn(1, 256, 2, 64), jnp.float32)
                   for _ in range(3))
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        ref = _xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_grad_parity_nondefault_blocks(self):
        rs = np.random.RandomState(4)
        q, k, v = (jnp.asarray(rs.randn(1, 256, 1, 64), jnp.float32)
                   for _ in range(3))
        gf = _staged.grads(lambda a, b, c: jnp.sum(flash_attention(
            a, b, c, block_q=128, block_k=128, interpret=True) ** 2), q, k, v)
        gr = _staged.grads(lambda a, b, c: jnp.sum(
            _xla_attention(a, b, c) ** 2), q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)
