"""Round-3 flash-attention widening (verdict item 5): ragged tails,
per-batch KV padding masks, and in-kernel dropout — all checked against the
XLA reference via the Pallas interpreter on CPU."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _staged
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
            out, ref = _staged.run(lambda q, k, v: (
                flash_attention(q, k, v, causal=causal, kv_lens=lens,
                                interpret=True),
                _xla_attention(q, k, v, mask=mask, causal=causal)), q, k, v)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-3, atol=2e-3)

    def test_kv_lens_grads_match_xla(self):
        rs = np.random.RandomState(1)
        q, k, v = _qkv(rs, b=1, s=128, h=1)
        lens = jnp.asarray([100], jnp.int32)
        mask = (jnp.arange(128)[None, None, None, :] <
                lens.reshape(-1, 1, 1, 1))
        gf = _staged.grads(lambda a, b_, c: jnp.sum(flash_attention(
            a, b_, c, kv_lens=lens, interpret=True,
            block_q=128, block_k=128) ** 2), q, k, v)
        gr = _staged.grads(lambda a, b_, c: jnp.sum(_xla_attention(
            a, b_, c, mask=mask) ** 2), q, k, v)
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
        out, (dk, dv) = _staged.out_and_grads(
            lambda k_, v_: flash_attention(q, k_, v_, kv_lens=lens,
                                           interpret=True), k, v)
        np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
        np.testing.assert_array_equal(np.asarray(dk[0]), 0.0)
        np.testing.assert_array_equal(np.asarray(dv[0]), 0.0)
        assert np.any(np.asarray(dv[1]) != 0.0)

    def test_dropout_rate_one_returns_zeros(self):
        rs = np.random.RandomState(9)
        q, k, v = _qkv(rs, b=1, s=128, h=1)
        out = _staged.run(flash_attention, q, k, v, dropout_rate=1.0,
                          interpret=True)
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_ragged_grads_match_xla(self):
        """Padded tail must contribute ZERO gradient."""
        rs = np.random.RandomState(2)
        q, k, v = _qkv(rs, b=1, s=200, h=1)
        gf = _staged.grads(lambda a, b_, c: jnp.sum(flash_attention(
            a, b_, c, causal=True, interpret=True) ** 2), q, k, v)
        gr = _staged.grads(lambda a, b_, c: jnp.sum(_xla_attention(
            a, b_, c, causal=True) ** 2), q, k, v)
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

        @jax.jit
        def loss(a):
            return jnp.sum(flash_attention(
                a, k, v, dropout_rate=0.25, dropout_seed=11,
                interpret=True, block_q=128, block_k=128) ** 2)

        g = _staged.grads(loss, q)
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
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
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


def _assert_bf16_close(got, want):
    """``_staged.out_and_grads``' ``(out, (dq, dk, dv))`` of bf16 operands
    against those of float32 ones."""
    for name, a, w in zip(("out", "dq", "dk", "dv"), (got[0],) + got[1],
                          (want[0],) + want[1]):
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

    _assert_bf16_close(_staged.out_and_grads(flash, q, k, v),
                       _staged.out_and_grads(ref, q32, k32, v32))


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
        got = _staged.out_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True), q, k, v)
    finally:
        tuner.clear_cache()
    want = _staged.out_and_grads(
        lambda q, k, v: _xla_attention(q, k, v, causal=True),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    _assert_bf16_close(got, want)


def _flash_grad_jaxpr(dtype, d=64, **kw):
    q = jnp.zeros((1, 256, 128 // d, d), dtype)

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
@pytest.mark.parametrize("d", [64, 128])
def test_kernel_products_take_the_operand_dtype(d, dtype, kw):
    """Walks the three kernels' jaxprs through their ``pallas_call``s:
    all nine ``dot_general``s of each way a tile is computed (staged once
    whatever the heads a lane block:
    two heads at width 64 are the trips of one loop) multiply in the dtype
    q/k/v arrive in (bf16
    stays bf16, the MXU's packed format; float32 callers keep float32
    products), accumulate in float32, and no operand is an upcast to
    float32 (directly or through a chain of casts): the guard that keeps
    ``x_ref[0].astype(jnp.float32)`` from coming back."""
    from paddle_tpu.analysis import walker
    from paddle_tpu.ops.pallas.flash_attention import KERNEL_NAMES

    dots = {name: 0 for name in KERNEL_NAMES}
    for path, jaxpr in walker.iter_jaxprs(_flash_grad_jaxpr(dtype, d, **kw)):
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
    # once a way a tile is computed: a causal call of two blocks a side has
    # the diagonal tile (whole here: 128 rows are too few to cut) and the
    # dense one before it
    ways = 2 if kw.get("causal") else 1
    assert dots == {"flash_fwd": 2 * ways, "flash_bwd_dq": 3 * ways,
                    "flash_bwd_dkv": 4 * ways}


# ---------------------------------------------------------------------------
# heads in lane blocks: the kernels' operands are (batch x lane blocks, seq,
# lanes), 128 // head_dim neighbouring heads side by side below width 128
# ---------------------------------------------------------------------------
def _layout_case(seed, b, s, h, h_kv, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, d), dtype),
            jax.random.normal(ks[1], (b, s, h_kv, d), dtype),
            jax.random.normal(ks[2], (b, s, h_kv, d), dtype),
            jax.random.normal(ks[3], (b, s, h, d), dtype))


@pytest.mark.parametrize("b, s, h, h_kv, d, window, kv_lens, blocks", [
    (1, 256, 12, 12, 64, None, None, (128, 128)),   # two heads a lane block
    (1, 256, 25, 25, 64, None, None, (128, 128)),   # a zero head padded on
    (2, 256, 8, 2, 64, None, None, (128, 128)),     # groups of 4 at width 64
    (1, 256, 6, 2, 64, 100, None, (128, 128)),      # odd groups, a window
    (1, 256, 3, 1, 64, None, None, (128, 256)),     # KV heads padded too
    (1, 1024, 6, 1, 128, None, None, (512, 512)),   # laguna's full layers
    (1, 1024, 8, 1, 128, 512, None, (512, 512)),    # and its window layers
    (1, 1024, 6, 1, 128, 512, None, (256, 512)),
    (1, 1024, 8, 1, 128, None, None, (512, 256)),
    (2, 300, 4, 4, 64, None, (150, 300), (128, 128)),   # ragged, kv_lens
    (2, 200, 6, 2, 128, None, (200, 77), (128, 128)),
    (1, 256, 2, 2, 32, None, None, (128, 128)),     # four heads a block
    (1, 256, 3, 3, 80, None, None, (128, 128)),     # a width padded to 128
    (1, 256, 2, 1, 256, None, None, (128, 128)),    # two lane blocks a head
    # tiles computed by sub-blocks (ISSUE 30): one causal tile, two heads a
    # lane block; four tiles a side, 6 heads a KV head; a window of one
    # block, 8 heads a KV head: both triangles, no tile between them
    (1, 1024, 2, 2, 64, None, None, (1024, 1024)),
    (1, 2048, 6, 1, 128, None, None, (512, 512)),
    (1, 2048, 8, 1, 128, 512, None, (512, 512)),
    (1, 2048, 2, 1, 128, 1024, None, (512, 512)),   # and one dense between
    # what falls to the masked path: a ragged length and a kv_lens that
    # cuts a diagonal tile; blocks that are not square
    (2, 1000, 2, 2, 64, None, (1000, 700), (512, 512)),
    (1, 1024, 2, 2, 64, None, None, (512, 1024)),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_lane_blocks_match_xla(b, s, h, h_kv, d, window, kv_lens, blocks):
    """Forward and the three gradients of the kernels (interpret mode,
    float32 callers) against ``_xla_attention`` for every way heads meet
    lane blocks: ``128 // d`` heads side by side below width 128 (with the
    zero heads that fill the last block, and grouped heads finding their
    KV head's slot), one head a block at 128, several blocks a head above."""
    q, k, v, do = _layout_case(3, b, s, h, h_kv, d)
    kw, mask = {}, None
    if kv_lens is not None:
        kw["kv_lens"] = jnp.asarray(kv_lens, jnp.int32)
        mask = (jnp.arange(s)[None, None, None, :] <
                kw["kv_lens"].reshape(-1, 1, 1, 1))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=blocks[0], block_k=blocks[1],
                               interpret=True, **kw)

    def xla(q, k, v):
        return _xla_attention(q, k, v, causal=True, window=window, mask=mask)

    out, got = _staged.out_and_grads(flash, q, k, v, ct=do)
    ref, want = _staged.out_and_grads(xla, q, k, v, ct=do)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == w.shape, name
        np.testing.assert_allclose(a, w, rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("h, h_kv", [(12, 12), (25, 25), (8, 2)],
                         ids=["12", "25_padded", "8_over_2"])
def test_lane_blocks_bf16_match_float32_reference(h, h_kv):
    """The cells' dtype at width 64: bf16 operands through a lane block of
    two heads, under the tolerance of the test above it was derived for."""
    q, k, v, _ = _layout_case(5, 1, 256, h, h_kv, 64, jnp.bfloat16)
    got = _staged.out_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, block_q=128, block_k=128),
        q, k, v)
    want = _staged.out_and_grads(
        lambda q, k, v: _xla_attention(q, k, v, causal=True),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    _assert_bf16_close(got, want)


@pytest.mark.parametrize("h, h_kv", [(2, 2), (3, 3), (3, 1), (6, 3)], ids=[
    "two_heads", "three_padded_to_four", "3_over_1_kv_padded",
    "6_over_3_kv_padded"])
def test_dropout_masks_bit_equal_for_every_head_of_a_lane_block(h, h_kv):
    """The multiplier each head's probabilities meet is the one the hash
    gives ``(seed, batch * heads + head, q block, k block)``, as before the
    layout changed, with ``heads`` the caller's count where zero heads are
    padded on: one query head, or under grouping a KV head and with it a
    whole group of query heads (3 over 1 is stored as 6 over 2, 6 over 3
    as 8 over 4), in the second batch row too. Read off the kernel itself:
    with q = 0 every probability is 1/S, and with the rows of V an
    identity's, ``out * S`` is the multiplier, exactly (rate 0.5: 0 or
    2)."""
    b, s, d, block, rate, seed = 2, 128, 64, 128, 0.5, 77
    q = jnp.zeros((b, s, h, d), jnp.float32)
    want = _kernel_dropout_mask(b * h, s, block, rate, seed).reshape(
        b, h, s, s)
    assert len({np.asarray(m).tobytes() for m in want.reshape(-1, s, s)}) \
        == b * h                                # a mask of its own a head
    for half in range(s // d):
        v = jnp.zeros((s, d), jnp.float32).at[
            half * d + jnp.arange(d), jnp.arange(d)].set(1.0)
        v = jnp.broadcast_to(v[None, :, None, :], (b, s, h_kv, d))
        out = flash_attention(q, q[:, :, :h_kv], v, dropout_rate=rate,
                              dropout_seed=seed, block_q=block,
                              block_k=block, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(jnp.swapaxes(out, 1, 2)) * s,
            np.asarray(want[..., half * d:(half + 1) * d]))
    assert 0.4 < float(jnp.mean(want == 0.0)) < 0.6


def _transposes_and_kernels(jaxpr, scope, stack=""):
    """Under name-stack ``scope``: the operand shapes of every ``transpose``
    and the ``pallas_call`` equations, nested jaxprs included (an inner
    jaxpr's name stacks continue its equation's)."""
    from paddle_tpu.analysis import walker
    transposes, calls = [], []
    for eqn in walker.unwrap(jaxpr)[0].eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if scope in here and eqn.primitive.name == "transpose":
            transposes.append(eqn.invars[0].aval.shape)
        elif scope in here and eqn.primitive.name == "pallas_call":
            calls.append(eqn)
        for sub in walker.subjaxprs(eqn):
            t, c = _transposes_and_kernels(sub.jaxpr, scope, here)
            transposes += t
            calls += c
    return transposes, calls


def _gpt_block():
    from paddle_tpu.text.models.gpt import GPTBlock
    block = GPTBlock(256, 4, attn_dropout=0.0, resid_dropout=0.0,
                     tensor_parallel=False)
    return block, (2, 512, 256), 4, 64


def _laguna_block():
    from paddle_tpu import nn
    from paddle_tpu.text.models.mixed_decoder import (GroupedQueryAttention,
                                                      MixedDecoderBlock)
    attn = GroupedQueryAttention(
        256, 4, 2, 128, {"theta": 10000.0, "rotary_dim": 128}, window=256,
        gate="head")
    block = MixedDecoderBlock(attn, nn.GatedSiluFFN(256, 512), False, 256,
                              1e-6)
    return block, (2, 512, 256), 4, 128


@pytest.mark.parametrize("make", [_gpt_block, _laguna_block],
                         ids=["gpt_block", "laguna_block"])
def test_a_block_on_the_flash_path_moves_no_padded_row(make, monkeypatch):
    """The staged forward + backward of one block with the flash gate held
    open: under ``sdpa/flash`` three ``pallas_call``s (by ``name=``), each
    with ``lens`` int32 ``(n,)`` and ``seed`` int32 ``(1,)`` as its first two
    operands (what the benchmark's readers match: ``custom-call(s32[N],
    s32[1], ...``); every other operand and result is whole 128-lane rows,
    ``(batch x lane blocks, seq, lanes)``, or the 8-lane statistics with a
    row a head; and what is transposed around them (q, k, v in, the
    output back, and their four cotangents) is such rows too, never a
    64-wide one that HBM would pad to 128."""
    import importlib

    from paddle_tpu.jit.functionalization import functional_call, state_of
    from paddle_tpu.ops.pallas.flash_attention import (KERNEL_NAMES, LANES,
                                                       STAT_LANES)
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "flash_supported", lambda q, k, min_seq=128: True)
    block, shape, heads, d = make()
    params, buffers = state_of(block)

    def loss(params, x):
        return jnp.sum(functional_call(block, params, buffers, x)[0]
                       .astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        params, jnp.zeros(shape, jnp.bfloat16))
    transposes, calls = _transposes_and_kernels(jaxpr, "sdpa/flash")
    assert sorted(c.params["name"] for c in calls) == sorted(KERNEL_NAMES)
    b, s, _ = shape
    blocks = b * heads * d // LANES
    for call in calls:
        lens, seed = (v.aval for v in call.invars[:2])
        assert (lens.dtype, lens.shape) == (jnp.int32, (blocks,)), call
        assert (seed.dtype, seed.shape) == (jnp.int32, (1,)), call
        for v in list(call.invars[2:]) + list(call.outvars):
            if v.aval.shape[-1] == STAT_LANES:
                assert v.aval.shape == (b * heads, s, STAT_LANES)
            else:
                assert v.aval.shape[1:] == (s, LANES), v.aval
    assert len(transposes) <= 8 and all(
        t[-1] == LANES and len(t) == 4 for t in transposes), transposes


# ---------------------------------------------------------------------------
# tile kinds (ISSUE 30): a tile whose visible part is a triangle is computed
# by sub-blocks over the rows and columns that can be visible
# ---------------------------------------------------------------------------
# name: (b, s, h, h_kv, d, kw of the call, blocks, the (dense, triangular,
#        masked) tiles a lane block's grid walks in flash_fwd, flash_bwd_dq
#        and flash_bwd_dkv, or one triple for all three). A triangle is
#        cut by the forward where a sub-block has 128 rows, by the dq kernel
#        from 128 up, by the dk/dv kernel from 256 up (``_SUB_ROWS``); where
#        it is not cut it is computed whole under its mask: ``masked``
_TILE_CASES = {
    "one_causal_tile_two_heads_a_block": (
        1, 1024, 2, 2, 64, dict(causal=True), (1024, 1024),
        ((0, 0, 1), (0, 1, 0), (0, 1, 0))),
    "four_tiles_a_side_6_over_1": (
        1, 2048, 6, 1, 128, dict(causal=True), (512, 512),
        ((6, 4, 0), (6, 4, 0), (6, 0, 4))),
    "window_of_a_block_8_over_1": (
        1, 2048, 8, 1, 128, dict(causal=True, window=512), (512, 512),
        ((0, 7, 0), (0, 7, 0), (0, 0, 7))),
    "window_of_two_blocks": (
        1, 2048, 2, 1, 128, dict(causal=True, window=1024), (512, 512),
        ((3, 6, 0), (3, 6, 0), (3, 0, 6))),
    "two_tiles_a_side_of_1024_rows": (
        1, 2048, 1, 1, 128, dict(causal=True), (1024, 1024),
        ((1, 0, 2), (1, 2, 0), (1, 2, 0))),
    "dropout_draws_the_same_bits": (
        1, 1024, 2, 2, 64, dict(causal=True, dropout_rate=0.1,
                                dropout_seed=77), (512, 512),
        ((1, 2, 0), (1, 2, 0), (1, 0, 2))),
    "dropout_in_a_tile_of_1024_rows": (
        1, 1024, 2, 2, 64, dict(causal=True, dropout_rate=0.1,
                                dropout_seed=78), (1024, 1024),
        ((0, 0, 1), (0, 1, 0), (0, 1, 0))),
    "ragged_and_kv_lens_cut_a_diagonal_tile": (
        2, 1000, 2, 2, 64, dict(causal=True, kv_lens=(1000, 700)),
        (512, 512), (0, 0, 3)),
    "blocks_not_square": (
        1, 1024, 2, 2, 64, dict(causal=True), (512, 1024), (0, 0, 2)),
    "window_no_whole_number_of_blocks": (
        1, 1024, 2, 1, 128, dict(causal=True, window=700), (512, 512),
        (0, 0, 3)),
    "block_too_small_to_cut": (
        1, 512, 2, 2, 64, dict(causal=True), (256, 256), (1, 0, 2)),
    "non_causal": (
        1, 1024, 2, 2, 64, dict(), (512, 512), (4, 0, 0)),
}


@pytest.mark.parametrize("case", list(_TILE_CASES))
def test_tile_kinds_match_the_masked_path(case, flash_staged):
    """Output and the three gradients of each geometry against
    ``_xla_attention`` (where the reference can say it) and against the same
    call forced onto the masked dense path, which a ``kv_lens`` of the full
    length does without masking a key: equal to float32 rounding, with
    dropout too, so the sub-blocks draw the bits the whole tile draws. The
    counter reads the tiles of each kind for each of the three kernels."""
    from paddle_tpu.ops.pallas.flash_attention import KERNEL_NAMES, TILE_KINDS
    b, s, h, h_kv, d, kw, blocks, kinds = _TILE_CASES[case]
    kw = dict(kw)
    q, k, v, do = _layout_case(11, b, s, h, h_kv, d)
    mask = None
    if "kv_lens" in kw:
        kw["kv_lens"] = jnp.asarray(kw["kv_lens"], jnp.int32)
        mask = (jnp.arange(s)[None, None, None, :] <
                kw["kv_lens"].reshape(-1, 1, 1, 1))

    def run(**more):
        def loss(q, k, v):
            out = flash_attention(q, k, v, block_q=blocks[0],
                                  block_k=blocks[1], interpret=True,
                                  **dict(kw, **more))
            return jnp.sum(do * out), out
        grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    if not isinstance(kinds[0], tuple):
        kinds = (kinds,) * 3        # the same in all three kernels
    got = run()
    counter = flash_staged("flash_tiles_staged_total")

    def staged():
        return tuple(tuple(int(counter.value(kernel=kernel, kind=kind))
                           for kind in TILE_KINDS) for kernel in KERNEL_NAMES)

    assert staged() == kinds
    names = ("out", "dq", "dk", "dv")
    if "kv_lens" not in kw:
        forced = run(kv_lens=jnp.full((b,), s, jnp.int32))
        assert staged() == tuple(
            (dense, cut, masked + dense + cut + masked)
            for dense, cut, masked in kinds)
        for name, a, w in zip(names, got, forced):
            np.testing.assert_allclose(a, w, rtol=1e-5, atol=1e-5,
                                       err_msg=name)
    if "dropout_rate" not in kw:
        def xla(q, k, v):         # staged: no flash kernel, nothing counted
            return _xla_attention(q, k, v, mask=mask,
                                  causal=kw.get("causal", False),
                                  window=kw.get("window"))
        ref, want = _staged.out_and_grads(xla, q, k, v, ct=do)
        for name, a, w in zip(names, got, (ref,) + want):
            np.testing.assert_allclose(a, w, rtol=5e-4, atol=5e-4,
                                       err_msg=name)


@pytest.mark.parametrize("row0, rows, col0, cols", [
    (0, 256, 0, 256), (256, 256, 0, 512), (768, 256, 0, 1024),
    (256, 768, 256, 256), (128, 128, 128, 384)])
def test_dropout_bits_of_a_part_are_the_tiles(row0, rows, col0, cols):
    """The multiplier of a part of a tile is that part of the tile's
    multiplier, bit for bit: the hash is fed an element's coordinates in
    the tile, not in the array it is drawn for."""
    from paddle_tpu.ops.pallas.flash_attention import _dropout_mask
    whole = _dropout_mask((1024, 1024), 0.1, 1234, 5, 2, 1)
    part = _dropout_mask((rows, cols), 0.1, 1234, 5, 2, 1, row0, col0)
    np.testing.assert_array_equal(
        np.asarray(part),
        np.asarray(whole[row0:row0 + rows, col0:col0 + cols]))
    assert 0.05 < float(jnp.mean(part == 0.0)) < 0.15
