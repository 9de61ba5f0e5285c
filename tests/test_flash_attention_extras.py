"""Round-3 flash-attention widening (verdict item 5): ragged tails,
per-batch KV padding masks, and in-kernel dropout — all checked against the
XLA reference via the Pallas interpreter on CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.nn.functional.attention import _xla_attention
from paddle_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(rs, b=2, s=256, h=2, d=64):
    return (jnp.asarray(rs.randn(b, s, h, d), jnp.float32),
            jnp.asarray(rs.randn(b, s, h, d), jnp.float32),
            jnp.asarray(rs.randn(b, s, h, d), jnp.float32))


class TestKvLensMask:
    def test_kv_lens_matches_xla_boolean_mask(self):
        rs = np.random.RandomState(0)
        q, k, v = _qkv(rs)
        lens = jnp.asarray([150, 256], jnp.int32)
        mask = (jnp.arange(256)[None, None, None, :] <
                lens.reshape(-1, 1, 1, 1))
        for causal in (False, True):
            out = flash_attention(q, k, v, causal=causal, kv_lens=lens,
                                  interpret=True)
            ref = _xla_attention(q, k, v, mask=mask, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-3, atol=2e-3)

    def test_kv_lens_grads_match_xla(self):
        rs = np.random.RandomState(1)
        q, k, v = _qkv(rs, b=1, s=128, h=1)
        lens = jnp.asarray([100], jnp.int32)
        mask = (jnp.arange(128)[None, None, None, :] <
                lens.reshape(-1, 1, 1, 1))
        gf = jax.grad(lambda a, b_, c: jnp.sum(flash_attention(
            a, b_, c, kv_lens=lens, interpret=True,
            block_q=128, block_k=128) ** 2), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b_, c: jnp.sum(_xla_attention(
            a, b_, c, mask=mask) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-3, atol=2e-3)

    def test_fully_masked_row_zero_output_and_grads(self):
        """kv_lens == 0: output must be zero and NO gradient may leak into
        the masked K/V (review regression: NEG_INF is finite, so a fully
        masked row used to produce mean-of-V with nonzero dk/dv)."""
        rs = np.random.RandomState(8)
        q, k, v = _qkv(rs, b=2, s=128, h=1)
        lens = jnp.asarray([0, 128], jnp.int32)
        out = flash_attention(q, k, v, kv_lens=lens, interpret=True)
        np.testing.assert_array_equal(np.asarray(out[0]), 0.0)

        def loss(k_, v_):
            return jnp.sum(flash_attention(q, k_, v_, kv_lens=lens,
                                           interpret=True) ** 2)

        dk, dv = jax.grad(loss, argnums=(0, 1))(k, v)
        np.testing.assert_array_equal(np.asarray(dk[0]), 0.0)
        np.testing.assert_array_equal(np.asarray(dv[0]), 0.0)
        assert np.any(np.asarray(dv[1]) != 0.0)

    def test_dropout_rate_one_returns_zeros(self):
        rs = np.random.RandomState(9)
        q, k, v = _qkv(rs, b=1, s=128, h=1)
        out = flash_attention(q, k, v, dropout_rate=1.0, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_ragged_grads_match_xla(self):
        """Padded tail must contribute ZERO gradient."""
        rs = np.random.RandomState(2)
        q, k, v = _qkv(rs, b=1, s=200, h=1)
        gf = jax.grad(lambda a, b_, c: jnp.sum(flash_attention(
            a, b_, c, causal=True, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b_, c: jnp.sum(_xla_attention(
            a, b_, c, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-3, atol=2e-3)


class TestKernelDropout:
    @pytest.mark.slow
    def test_dropout_statistics_and_scaling(self):
        """Kernel dropout: output is a valid inverted-dropout sample —
        mean close to the undropped output, exact zeros pattern applied at
        the p level (checked statistically: E[out] == out_nodrop)."""
        rs = np.random.RandomState(3)
        q, k, v = _qkv(rs, b=1, s=256, h=1)
        base = flash_attention(q, k, v, interpret=True)
        outs = [flash_attention(q, k, v, dropout_rate=0.3, dropout_seed=i,
                                interpret=True) for i in range(24)]
        mean = jnp.mean(jnp.stack(outs), axis=0)
        # stderr ~ |v|·p/sqrt(n): loose tolerance, checks the 1/keep
        # scaling and that masks differ per seed
        np.testing.assert_allclose(np.asarray(mean), np.asarray(base),
                                   rtol=0.35, atol=0.35)
        assert not np.allclose(np.asarray(outs[0]), np.asarray(outs[1]))

    def test_dropout_deterministic_per_seed(self):
        rs = np.random.RandomState(4)
        q, k, v = _qkv(rs, b=1, s=128, h=1)
        a = flash_attention(q, k, v, dropout_rate=0.2, dropout_seed=7,
                            interpret=True)
        b = flash_attention(q, k, v, dropout_rate=0.2, dropout_seed=7,
                            interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dropout_grads_are_consistent(self):
        """The backward must regenerate the SAME mask: finite-difference
        check of the jitted loss (any fwd/bwd mask mismatch shows up as a
        gradient error far beyond fd tolerance)."""
        rs = np.random.RandomState(5)
        q, k, v = _qkv(rs, b=1, s=128, h=1)

        def loss(a):
            return jnp.sum(flash_attention(
                a, k, v, dropout_rate=0.25, dropout_seed=11,
                interpret=True, block_q=128, block_k=128) ** 2)

        g = jax.grad(loss)(q)
        rs2 = np.random.RandomState(6)
        for _ in range(4):
            d = jnp.asarray(rs2.randn(*q.shape), jnp.float32)
            eps = 1e-3
            fd = (loss(q + eps * d) - loss(q - eps * d)) / (2 * eps)
            an = jnp.sum(g * d)
            np.testing.assert_allclose(float(fd), float(an), rtol=5e-2)


class TestDispatch:
    def test_sdpa_kv_lens_xla_fallback_matches(self):
        """Off-TPU, kv_lens routes through the XLA mask fallback."""
        from paddle_tpu.nn.functional.attention import \
            scaled_dot_product_attention
        rs = np.random.RandomState(7)
        q, k, v = _qkv(rs, b=2, s=64, h=1, d=64)
        lens = jnp.asarray([40, 64], jnp.int32)
        out = scaled_dot_product_attention(q, k, v, kv_lens=lens)
        mask = (jnp.arange(64)[None, None, None, :] <
                lens.reshape(-1, 1, 1, 1))
        ref = _xla_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# bf16 operands: the products run in the dtype the operands arrive in
# ---------------------------------------------------------------------------
def _kernel_dropout_mask(bh, s, block, rate, seed):
    """The multiplier the kernels apply, rebuilt tile by tile:
    ``_xla_attention`` draws its mask from another generator, the kernels'
    is a pure function of (seed, batch*head, block ids)."""
    from paddle_tpu.ops.pallas.flash_attention import _dropout_mask
    n = s // block
    return jnp.stack([jnp.block([[
        _dropout_mask((block, block), rate, seed, i, iq, ik)
        for ik in range(n)] for iq in range(n)]) for i in range(bh)])


def _dense_dropout_attention(q, k, v, mult):
    """Float32 (m∘softmax(qkᵀ/√d))v with a given multiplier m (B,H,S,T)."""
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(logits, axis=-1) * mult
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _out_and_grads(fn, *qkv):
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2), out
    grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(*qkv)
    return (out,) + grads


def _assert_bf16_close(got, want):
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16, name
        a, w = np.asarray(a, np.float32), np.asarray(w)
        assert np.abs(a - w).max() <= 1e-2 * np.abs(w).max(), name


_BF16_CASES = {
    "causal": dict(causal=True),
    "non_causal": dict(),
    "kv_lens": dict(kv_lens=(150, 256)),
    "dropout": dict(dropout_rate=0.1, dropout_seed=1234),
}


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("case", list(_BF16_CASES))
def test_bf16_operands_match_float32_reference(case, d):
    """bf16 q/k/v through the kernels (bf16 products, float32 accumulation)
    against float32 attention of the same, upcast, inputs.

    Tolerance: the largest error of each tensor within 1% of the tensor's
    largest magnitude. Why: the inputs are equal on both sides, so the
    error is the kernels' own roundings to bf16, up to 2**-9 = 0.2% each:
    ``p`` or ``ds`` where it enters a product, ``out``, the cotangent
    ``do = 2 out``, and each result as it is stored. Four of them lie
    along a gradient's chain, 0.8%, and a sum of rounded terms errs by a
    share of its terms, not of itself, hence the tensor's scale and not
    each element's. The readings: 0.51% at worst (dq, dropout, d 128),
    0.23% for ``out``; the float32 products this replaced read 0.30% and
    0.23%, so most of it is the storing, which both have. A product that
    lost an operand or a mask reads 10% and more."""
    kw = dict(_BF16_CASES[case])
    b, s, h, block = 2, 256, 2, 128
    rs = np.random.RandomState(17)
    q, k, v = (jnp.asarray(rs.randn(b, s, h, d), jnp.bfloat16)
               for _ in range(3))
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    if "kv_lens" in kw:
        kw["kv_lens"] = jnp.asarray(kw["kv_lens"], jnp.int32)
        mask = (jnp.arange(s)[None, None, None, :] <
                kw["kv_lens"].reshape(-1, 1, 1, 1))

        def ref(q, k, v):
            return _xla_attention(q, k, v, mask=mask)
    elif "dropout_rate" in kw:
        mult = _kernel_dropout_mask(b * h, s, block, kw["dropout_rate"],
                                    kw["dropout_seed"]).reshape(b, h, s, s)
        assert 0.05 < float(jnp.mean(mult == 0.0)) < 0.15

        def ref(q, k, v):
            return _dense_dropout_attention(q, k, v, mult)
    else:
        def ref(q, k, v):
            return _xla_attention(q, k, v, causal=kw.get("causal", False))

    def flash(q, k, v):
        return flash_attention(q, k, v, interpret=True, block_q=block,
                               block_k=block, **kw)

    _assert_bf16_close(_out_and_grads(flash, q, k, v),
                       _out_and_grads(ref, q32, k32, v32))


def test_cells_bucket_takes_the_measured_row(tmp_path, monkeypatch):
    """bf16, head width 64, 513 to 1,024 positions (every ``seq1024``
    cell and ``chip_smoke.py``'s GPT-base) resolve to the tuning DB's
    (1024, 1024), the fastest of the ten shapes PR 26 measured on the
    v5e (PERF.md section 6), and one such tile a head keeps the values:
    the same tolerance as above, for the same reason."""
    from paddle_tpu.ops.pallas import tuner
    # an absent overlay, so a developer's ~/.cache row cannot answer
    monkeypatch.setenv("PADDLE_TPU_TUNING_DB", str(tmp_path / "none.json"))
    tuner.clear_cache()
    try:
        for seq in (1024, 640):
            cfg, source = tuner.resolve(
                "flash_attention", jnp.bfloat16,
                tuner.flash_dims(64, seq, seq), {"block_q": 256,
                                                 "block_k": 512})
            assert (cfg, source) == ({"block_q": 1024, "block_k": 1024},
                                     "db")
        rs = np.random.RandomState(5)
        q, k, v = (jnp.asarray(rs.randn(1, 1024, 2, 64), jnp.bfloat16)
                   for _ in range(3))
        got = _out_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True), q, k, v)
    finally:
        tuner.clear_cache()
    want = _out_and_grads(
        lambda q, k, v: _xla_attention(q, k, v, causal=True),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    _assert_bf16_close(got, want)


def _flash_grad_jaxpr(dtype, **kw):
    q = jnp.zeros((1, 256, 1, 64), dtype)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, block_q=128, block_k=128, **kw
        ).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)
    return jax.make_jaxpr(f)(q, q, q)


@pytest.mark.parametrize("kw", [
    dict(causal=True), dict(dropout_rate=0.1, dropout_seed=3),
    dict(kv_lens=np.asarray([200], np.int32))],
    ids=["causal", "dropout", "kv_lens"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kernel_products_take_the_operand_dtype(dtype, kw):
    """Walks the three kernels' jaxprs through their ``pallas_call``s:
    all nine ``dot_general``s multiply in the dtype q/k/v arrive in (bf16
    stays bf16, the MXU's packed format; float32 callers keep float32
    products), accumulate in float32, and no operand is an upcast to
    float32 (directly or through a chain of casts): the guard that keeps
    ``x_ref[0].astype(jnp.float32)`` from coming back."""
    from paddle_tpu.analysis import walker
    from paddle_tpu.ops.pallas.flash_attention import KERNEL_NAMES

    dots = {name: 0 for name in KERNEL_NAMES}
    for path, jaxpr in walker.iter_jaxprs(_flash_grad_jaxpr(dtype, **kw)):
        kernel = next((p.split(":", 1)[1] for p in path
                       if p.startswith("pallas_call:")), None)
        producer = {v: e for e in jaxpr.eqns for v in e.outvars}
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "dot_general":
                continue
            assert kernel in dots, f"a product outside the kernels: {path}"
            dots[kernel] += 1
            assert eqn.outvars[0].aval.dtype == jnp.float32
            for x in eqn.invars:
                assert x.aval.dtype == dtype, (kernel, x.aval)
                src = producer.get(x)
                while src is not None and \
                        src.primitive.name == "convert_element_type":
                    assert src.params["new_dtype"] != jnp.float32, kernel
                    src = producer.get(src.invars[0])
    assert dots == {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
