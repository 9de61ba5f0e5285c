"""Chunked LM-head CE: forward and grads must match the dense
logits-materializing path exactly (fp32 accumulation both sides)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _staged
from paddle_tpu.ops.chunked_ce import chunked_lm_ce

N, H, V = 24, 16, 1000   # V deliberately not a multiple of chunk


def _data(seed=0, ignore_frac=0.0):
    rs = np.random.RandomState(seed)
    hid = rs.randn(N, H).astype("f4")
    w = (rs.randn(H, V) * 0.05).astype("f4")
    y = rs.randint(0, V, N).astype("i4")
    if ignore_frac:
        y[rs.rand(N) < ignore_frac] = -100
    return jnp.asarray(hid), jnp.asarray(w), jnp.asarray(y)


def _dense_ce(hid, w, y, ignore_index=-100):
    logits = hid.astype(jnp.float32) @ w.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    valid = y != ignore_index
    safe = jnp.where(valid, y, 0)
    tgt = jnp.take_along_axis(logits, safe[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    per = jnp.where(valid, lse - tgt, 0.0)
    return per.sum() / jnp.maximum(valid.sum().astype(jnp.float32), 1.0)


@pytest.mark.parametrize("chunk", [128, 256, 1000, 4096])
def test_forward_matches_dense(chunk):
    hid, w, y = _data()
    a = float(chunked_lm_ce(hid, w, y, chunk))
    b = float(_dense_ce(hid, w, y))
    assert a == pytest.approx(b, rel=1e-6)


def test_grads_match_dense():
    hid, w, y = _data(1)
    ga = _staged.grads(lambda h, w: chunked_lm_ce(h, w, y, 256), hid, w)
    gb = _staged.grads(lambda h, w: _dense_ce(h, w, y), hid, w)
    np.testing.assert_allclose(np.asarray(ga[0]), np.asarray(gb[0]),
                               rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ga[1]), np.asarray(gb[1]),
                               rtol=2e-5, atol=1e-7)


def test_ignore_index_and_bf16():
    hid, w, y = _data(2, ignore_frac=0.3)
    a = float(chunked_lm_ce(hid, w, y, 300))
    b = float(_dense_ce(hid, w, y))
    assert a == pytest.approx(b, rel=1e-6)
    # bf16 inputs: fp32 accumulation inside, grads in bf16
    hb, wb = hid.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    gh, gw = _staged.grads(lambda h, w: chunked_lm_ce(h, w, y, 256), hb, wb)
    assert gh.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
    ref = float(_dense_ce(hb, wb, y))
    assert float(chunked_lm_ce(hb, wb, y, 256)) == \
        pytest.approx(ref, rel=1e-3)


def test_under_jit_and_all_ignored():
    hid, w, y = _data(3)
    f = jax.jit(lambda h, w, y: chunked_lm_ce(h, w, y, 256))
    assert np.isfinite(float(f(hid, w, y)))
    y_all = jnp.full_like(y, -100)
    assert float(f(hid, w, y_all)) == 0.0


def test_gpt_fused_head_loss_matches_logits_path():
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.text.models import GPTForPretraining

    paddle.seed(0)
    m = GPTForPretraining(tensor_parallel=False, vocab_size=512,
                          hidden_size=64, num_layers=2, num_heads=4,
                          max_position_embeddings=64, attn_dropout=0.0,
                          hidden_dropout=0.0)
    m.eval()
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 512, (2, 32)), jnp.int32)
    y = jnp.asarray(rs.randint(0, 512, (2, 32)), jnp.int32)
    dense = float(nn.functional.cross_entropy(m(ids), y))
    fused = float(m.fused_head_loss(ids, y, chunk=128))
    assert fused == pytest.approx(dense, rel=1e-5)
