"""Olmo-Hybrid through the mixed decoder: norms after the sublayers
(``norm_placement="post"``), the whole-width QK norm, Gated DeltaNet at key
and value heads of different widths with step sizes up to 2
(``allow_neg_eigval``), against the benchmark's float32 reference
(``benchmark/reference/olmohybrid.py``, which scans position by position);
and the tensor-parallel share: two shares of a layer, their partial sums and
the QK norm's statistic reduced over a named axis, are the uncut layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import olmohybrid as family
from benchmark.reference import olmohybrid as reference
from paddle_tpu import nn
from paddle_tpu.jit.functionalization import functional_call, state_of
from paddle_tpu.text.models import MixedDecoderForPretraining
from paddle_tpu.text.models.mixed_decoder import (GroupedQueryAttention,
                                                  MixedDecoderBlock)


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """Rows of 40 positions: chunks of 16, so that a layer still carries
    its state over three of them."""
    from paddle_tpu.nn.layers import linear_attention
    monkeypatch.setattr(linear_attention, "CHUNK", 16)


LINEAR = dict(key_heads=2, value_heads=2, d_k=12, d_v=24, conv_kernel=4,
              allow_neg_eigval=True)
ARCH = {"layers": ["linear_attention"] * 3 + ["full_attention"], "heads": 2,
        "kv_heads": 2, "head_dim": 16,
        "linear": {"heads": 2, "d_k": 12, "d_v": 24, "conv_kernel": 4},
        "allow_neg_eigval": True, "ffn_columns": 48}


def toy_model(**over):
    kwargs = dict(
        vocab_size=128, hidden_size=64, layer_types=ARCH["layers"],
        heads_per_layer=[2] * 4, mlp_layer_types=["dense"] * 4, kv_heads=2,
        head_dim=16, rope={"full_attention": None}, sliding_window=None,
        intermediate_size=48, qk_norm="whole", norm_placement="post",
        linear_attention=LINEAR)
    kwargs.update(over)
    return MixedDecoderForPretraining(**kwargs)


def perturbed(model, seed=7):
    """The model's parameters with the ones that start at a constant moved
    off it, so that a norm's weight or a bias that is dropped shows."""
    params = dict(state_of(model)[0])
    keys = jax.random.split(jax.random.key(seed), len(params))
    return {name: v + 0.1 * jax.random.normal(k, v.shape)
            if name.endswith(("norm.weight", "dt_bias")) else v
            for (name, v), k in zip(sorted(params.items()), keys)}


def reference_params(params):
    shim = type("B", (), {"config": {"n_head": ARCH}})()
    tree = family.Built.to_reference(shim, params)
    tree["blocks"] = [tree["blocks"][i] for i in sorted(tree["blocks"])]
    return tree


def test_toy_model_equals_the_reference():
    """Logits, loss and every gradient leaf, float32 on both sides and each
    side staged once: the chunked rule with steps up to 2 against the
    reference's scan over positions, the three convolutions, the gated norm,
    the whole-width QK norm without rotation, the reordered norms, and the
    program's fused projections cut into the published tensors."""
    model = toy_model(checkpoint_blocks=True)
    params = perturbed(model)
    ids = jax.random.randint(jax.random.key(0), (2, 40), 0, 128)
    labels = jnp.roll(ids, -1, axis=1)

    def ours(p):
        logits = functional_call(model, p, {}, ids, rng=jax.random.key(0))[0]
        return nn.functional.cross_entropy(logits, labels), logits

    def theirs(p):
        with jax.default_matmul_precision("highest"):
            logits = reference.hidden_states(p, ids, ARCH, 1e-6) \
                @ p["lm_head"]
        return reference.loss(p, ids, labels, n_head=ARCH, eps=1e-6), logits

    (loss, logits), grads = jax.jit(jax.value_and_grad(
        ours, has_aux=True))(params)
    (want, want_logits), want_grads = jax.jit(jax.value_and_grad(
        theirs, has_aux=True))(reference_params(params))
    np.testing.assert_allclose(logits, want_logits, atol=2e-4, rtol=0)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got = reference_params(grads)

    def close(a, b, name):
        """As the harness compares a leaf: ``|g - g_ref| / |g_ref|``. The
        model's gradients amplify float32 rounding a thousandfold and more
        (steps near 2 undamped by the decay, un-normed mixer inputs): a leaf
        reads 1e-4 to 7e-4 by the order the CPU sums in, a wrong term 1e-2
        and more."""
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert a.shape == b.shape and rel < 2e-3, (name, rel)

    blocks = want_grads.pop("blocks")
    for i, block in enumerate(blocks):
        assert set(block) == set(got["blocks"][i])
        for name, g in block.items():
            close(got["blocks"][i][name], g, f"{i}.{name}")
    for name, g in want_grads.items():
        close(got[name], g, name)
    # every published tensor of a layer was compared
    assert set(blocks[0]) == set(reference.LINEAR_PARAM_NAMES
                                 + reference.BLOCK_PARAM_NAMES)
    assert set(blocks[3]) == set(reference.FULL_PARAM_NAMES
                                 + reference.BLOCK_PARAM_NAMES)


def test_blocks_norm_their_sublayers_outputs():
    """Names and scopes: ``post_mixer_norm`` and ``post_ffn_norm`` in every
    block and no norm on a sublayer's input; the whole-width QK norm's
    weights as wide as q and k, under the scope ``qk_norm`` with no rotation
    inside; the pre-norm block keeps its names."""
    model = toy_model()
    p = dict(model.named_parameters())
    for i in range(4):
        for norm in ("post_mixer_norm", "post_ffn_norm"):
            assert p[f"decoder.h.{i}.{norm}.weight"].shape == (64,)
        assert f"decoder.h.{i}.input_norm.weight" not in p
        assert f"decoder.h.{i}.post_attn_norm.weight" not in p
    assert p["decoder.h.3.attn.q_norm.weight"].shape == (2 * 16,)
    assert p["decoder.h.3.attn.k_norm.weight"].shape == (2 * 16,)
    assert p["decoder.norm.weight"].shape == (64,)
    text = jax.jit(lambda x: model(x)).lower(
        jnp.zeros((1, 32), jnp.int32)).as_text(debug_info=True)
    for i in range(4):
        for norm in ("post_mixer_norm", "post_ffn_norm"):
            assert f"decoder/h.{i}/{norm}" in text
    assert "decoder/h.3/attn/qk_norm" in text
    assert "rope" not in text
    pre = MixedDecoderBlock(nn.Linear(8, 8), nn.Linear(8, 8), False, 8, 1e-6)
    assert set(dict(pre.named_parameters())) == {
        "input_norm.weight", "attn.weight", "attn.bias",
        "post_attn_norm.weight", "mlp.weight", "mlp.bias"}
    with pytest.raises(ValueError, match="norm placement"):
        MixedDecoderBlock(nn.Linear(8, 8), None, False, 8, 1e-6,
                          norm_placement="sandwich")
    with pytest.raises(ValueError, match="elementwise gate"):
        GroupedQueryAttention(64, 2, 2, 16, None, gate="elementwise",
                              qk_norm_epsilon=1e-6, qk_norm_over="whole")


# -- the tensor-parallel share -------------------------------------------------

D, HEADS, CHIPS = 32, 4, 2


def _heads(v, share, width, parts=1, axis=-1):
    """Share ``share``'s heads of ``v``: along ``axis`` ``parts`` parts side
    by side, each ``HEADS`` heads of ``width``."""
    n = v.shape[axis] // parts
    per = HEADS // CHIPS * width
    idx = np.concatenate([np.arange(share * per, (share + 1) * per) + p * n
                          for p in range(parts)])
    return jnp.take(v, idx, axis=axis)


def _shared_from(params, cut):
    """``params`` cut into the shares' (``cut(name, value, share)``),
    stacked along a new first axis."""
    return {name: jnp.stack([cut(name, v, i) for i in range(CHIPS)])
            for name, v in params.items()}


def _uncut(layer, params, x):
    return jax.jit(lambda p: functional_call(layer, p, {}, x)[0])(params)


def _reduced(layer_of_share, params, x, axis_name="chips"):
    """The shares' outputs summed over the named axis (the all-reduce of the
    row-parallel products' partial sums), their layer built with
    ``axis_name``, each share's own parameters."""
    def one(p):
        y = functional_call(layer_of_share, p, {}, x)[0]
        return jax.lax.psum(y, axis_name)
    return jax.jit(jax.vmap(one, axis_name=axis_name))(params)[0]


def test_two_shares_add_up_to_the_uncut_layers():
    """The guide's share test for a layer shared by two chips: each share
    holds half the heads of the mixers (q, k, v, z, a, b and the
    convolution's channels by columns, out and o by rows) and half the
    feed-forward columns (gate and up by columns, down by rows). With the
    partial sums all-reduced, and the whole-width QK norm's mean of squares
    reduced over the same axis, two shares are the uncut Gated DeltaNet,
    full-attention layer and feed-forward block; with the norm's statistic
    left on a share's own lanes (``axis_name=None``, one chip) the attention
    is not."""
    x = jax.random.normal(jax.random.key(1), (2, 24, D))
    half = HEADS // CHIPS

    # Gated DeltaNet: [q | k | v | z], [b | a], conv over [q | k | v]
    whole = nn.GatedDeltaNet(D, HEADS, HEADS, 8, 16, allow_neg_eigval=True)
    share = nn.GatedDeltaNet(D, half, half, 8, 16, allow_neg_eigval=True)

    def cut_linear(name, v, i):
        if name == "in_proj_qkvz.weight":
            qk, vz = v[:, :2 * HEADS * 8], v[:, 2 * HEADS * 8:]
            return jnp.concatenate([_heads(qk, i, 8, 2),
                                    _heads(vz, i, 16, 2)], axis=-1)
        if name == "conv_weight":
            return jnp.concatenate([_heads(v[:2 * HEADS * 8], i, 8, 2, 0),
                                    _heads(v[2 * HEADS * 8:], i, 16, 1, 0)])
        if name == "in_proj_ba.weight":
            return _heads(v, i, 1, 2)
        if name in ("A_log", "dt_bias"):
            return _heads(v, i, 1)
        if name == "out_proj.weight":
            return _heads(v, i, 16, 1, 0)
        return v                                    # the gated norm's weight
    params = dict(state_of(whole)[0])
    params["norm.weight"] = params["norm.weight"] + 0.1
    np.testing.assert_allclose(
        _reduced(share, _shared_from(params, cut_linear), x),
        _uncut(whole, params, x), atol=2e-5, rtol=1e-5)

    # full attention with the whole-width QK norm, no rotation
    def attention(heads, axis_name=None):
        return GroupedQueryAttention(D, heads, heads, 8, None,
                                     qk_norm_epsilon=1e-6,
                                     qk_norm_over="whole",
                                     axis_name=axis_name)

    def cut_attention(name, v, i):
        if name.endswith(("q_norm.weight", "k_norm.weight")):
            return _heads(v, i, 8)
        if name == "o_proj.weight":
            return _heads(v, i, 8, 1, 0)
        return _heads(v, i, 8)                      # q, k, v by columns
    params = dict(state_of(attention(HEADS))[0])
    params = {k: v + 0.1 if "norm" in k else v for k, v in params.items()}
    want = _uncut(attention(HEADS), params, x)
    shares = _shared_from(params, cut_attention)
    np.testing.assert_allclose(
        _reduced(attention(half, "chips"), shares, x), want, atol=2e-5,
        rtol=1e-5)
    alone = _reduced(attention(half), shares, x)
    assert float(jnp.abs(alone - want).max()) > 1e-3

    # the feed-forward block: gate and up by columns, down by rows
    ffn = nn.GatedSiluFFN(D, 48)

    def cut_ffn(name, v, i):
        rows = slice(i * 24, (i + 1) * 24)
        return v[rows] if name.startswith("down") else v[:, rows]
    params = dict(state_of(ffn)[0])
    np.testing.assert_allclose(
        _reduced(nn.GatedSiluFFN(D, 24), _shared_from(params, cut_ffn), x),
        _uncut(ffn, params, x), atol=2e-5, rtol=1e-5)
