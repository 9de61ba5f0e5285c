"""ISSUE 37: multi-head latent attention (``text/models/mixed_decoder.py``
``MultiHeadLatentAttention``) at small sizes on the CPU with seeded float32
weights, held to a plain ``jax.numpy`` statement of the published equations
(``benchmark/reference/glm4moelite.py`` ``attention``, a head at a time in
the published lane order)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from benchmark.families.glm4moelite import published_columns
from benchmark.reference import glm4moelite as reference
from paddle_tpu import telemetry
from paddle_tpu.jit.functionalization import functional_call, state_of
from paddle_tpu.nn import functional as F
from paddle_tpu.text.models import (MixedDecoderModel,
                                    MultiHeadLatentAttention)

SIZES = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
             qk_rope_head_dim=4, v_head_dim=16)
NAMES = {"q_a_proj.weight": "q_a_w", "q_a_norm.weight": "q_a_norm_g",
         "q_b_proj.weight": "q_b_w", "kv_a_proj.weight": "kv_a_w",
         "kv_a_norm.weight": "kv_a_norm_g", "kv_b_proj.weight": "kv_b_w",
         "o_proj.weight": "o_w"}


def layer(heads=4, hidden=32, seed=0, theta=1e4, **sizes):
    sizes = {**SIZES, **sizes}
    import paddle_tpu as paddle
    paddle.seed(seed)
    attn = MultiHeadLatentAttention(hidden, heads, rope={"theta": theta},
                                    epsilon=1e-5, **sizes)
    # norms away from their initial ones, so that a missing one shows
    for i, norm in enumerate((attn.q_a_norm, attn.kv_a_norm)):
        norm.weight.value = 1.0 + 0.3 * jax.random.normal(
            jax.random.key(7 + i), norm.weight.value.shape)
    arch = {"heads": heads, "d_nope": sizes["qk_nope_head_dim"],
            "d_rope": sizes["qk_rope_head_dim"], "d_v": sizes["v_head_dim"],
            "kv_lora_rank": sizes["kv_lora_rank"], "rope_theta": theta}
    return attn, arch


def as_reference(leaves, arch):
    """The layer's leaves under the reference's names, ``q_b_proj``'s
    columns in the published ``[nope | rope]`` order."""
    cols = published_columns(arch["heads"], arch["d_nope"], arch["d_rope"])
    out = {NAMES[k]: v for k, v in leaves.items()}
    out["q_b_w"] = out["q_b_w"][..., cols]
    return out


@pytest.mark.parametrize("heads, seq, sizes", [
    (4, 24, {}), (3, 40, dict(qk_nope_head_dim=8, qk_rope_head_dim=8)),
    (2, 16, dict(q_lora_rank=8, kv_lora_rank=40))],
    ids=["four_heads", "half_the_lanes_rotate", "wide_kv_latent"])
def test_layer_equals_the_plain_statement(heads, seq, sizes):
    """Forward and the gradient of every one of the seven tensors and of
    the input."""
    attn, arch = layer(heads, seed=heads, **sizes)
    x = jax.random.normal(jax.random.key(1), (2, seq, 32))
    params = dict(state_of(attn)[0])
    assert sorted(params) == sorted(NAMES)

    def ours(p, x):
        return functional_call(attn, p, {}, x)[0]

    def theirs(p, x):
        return reference.attention(x, as_reference(p, arch), arch, 1e-5,
                                   False)

    weight = jax.random.normal(jax.random.key(2), (2, seq, 32))
    out, got = _staged.out_and_grads(ours, params, x, ct=weight)
    ref, want = _staged.out_and_grads(theirs, params, x, ct=weight)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_scale_is_of_the_whole_query_width():
    """``1 / sqrt(d_nope + d_rope)``: the plain statement with ``1 /
    sqrt(d_nope)`` disagrees."""
    attn, arch = layer()
    x = jax.random.normal(jax.random.key(1), (1, 16, 32))
    params = dict(state_of(attn)[0])
    ref = as_reference(params, arch)
    # scaling q_b by sqrt(16 / 12) is the other scale
    other = dict(ref, q_b_w=ref["q_b_w"] * np.sqrt(16 / 12))
    got = _staged.forward(attn)(x)
    plain = jax.jit(lambda x, p: reference.attention(x, p, arch, 1e-5, False))
    np.testing.assert_allclose(got, plain(x, ref), rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(got - plain(x, other)).max()) > 1e-3


@pytest.mark.parametrize("without", ["both", "q_a_norm", "kv_a_norm"])
def test_the_two_latent_norms_are_there(without, monkeypatch):
    """The plain statement without a latent's norm disagrees: the norms
    stand between two products, on the 768- and the 512-wide latent."""
    attn, arch = layer()
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    ref = as_reference(dict(state_of(attn)[0]), arch)
    right = reference.latent_norm

    def norm(c, g, eps):
        skipped = {"both": (24, 16), "q_a_norm": (24,), "kv_a_norm": (16,)}
        return c if c.shape[-1] in skipped[without] else right(c, g, eps)

    monkeypatch.setattr(reference, "latent_norm", norm)
    # staged after the patch: the reference reads it as it is traced
    wrong = _staged.run(reference.attention, x, ref, arch=arch, eps=1e-5,
                        remat=False)
    assert float(jnp.abs(_staged.forward(attn)(x) - wrong).max()) > 1e-2


def test_the_rotary_key_is_one_head_rotated_once(monkeypatch):
    """``F.rotary_embedding`` sees q with all heads and ``k_r`` as ONE head
    of ``d_rope`` lanes; every key head then reads those lanes."""
    attn, arch = layer()
    seen = []
    right = F.rotary_embedding

    def spy(x, *args, **kw):
        seen.append(x.shape)
        return right(x, *args, **kw)

    monkeypatch.setattr(F, "rotary_embedding", spy)
    keys = []
    sdpa = F.scaled_dot_product_attention
    monkeypatch.setattr(
        F, "scaled_dot_product_attention",
        lambda q, k, v, **kw: (keys.append(k), sdpa(q, k, v, **kw))[1])
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    attn(x)
    assert seen == [(2, 16, 4, 16), (2, 16, 1, 4)]
    k = keys[0]
    assert k.shape == (2, 16, 4, 16)
    for head in range(1, 4):
        np.testing.assert_array_equal(k[:, :, head, :4], k[:, :, 0, :4])
        assert float(jnp.abs(k[:, :, head, 4:] - k[:, :, 0, 4:]).max()) > 0.01
    # and the shared lanes are the rotated k_r: position 0 is not turned
    kv_a = x @ attn.kv_a_proj.weight.value
    np.testing.assert_allclose(k[:, 0, 0, :4], kv_a[:, 0, 16:], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.abs(k[:, 5, 0, :4] - kv_a[:, 5, 16:]).max()) > 1e-3


def test_explicit_positions_turn_the_rotary_lanes():
    attn, _ = layer()
    attend = _staged.forward(attn)
    x = jax.random.normal(jax.random.key(1), (1, 8, 32))
    np.testing.assert_allclose(attend(x, jnp.arange(8)), attend(x),
                               rtol=1e-6)
    assert float(jnp.abs(attend(x, jnp.arange(8) * 3) - attend(x)).max()) \
        > 1e-4


def test_what_is_not_built_is_refused():
    kw = dict(hidden_size=32, num_heads=4, rope={"theta": 1e4})
    with pytest.raises(ValueError, match="unequal key and value widths"):
        MultiHeadLatentAttention(**kw, **dict(SIZES, v_head_dim=12))
    with pytest.raises(ValueError, match="sliding window is not built"):
        MultiHeadLatentAttention(**kw, **SIZES, window=8)
    attn, _ = layer()
    with pytest.raises(ValueError, match="block-diffusion"):
        attn(jnp.zeros((1, 8, 32)), None, 4)
    with pytest.raises(ValueError, match="unknown layer kinds"):
        model(layer_types=["latent"])


def model(layer_types=("latent_attention", "latent_attention"), **kw):
    n = len(layer_types)
    return MixedDecoderModel(
        vocab_size=64, hidden_size=32, layer_types=list(layer_types),
        heads_per_layer=[4] * n, mlp_layer_types=["dense"] * n,
        kv_heads=None, head_dim=None,
        rope={"latent_attention": {"theta": 1e4}}, sliding_window=None,
        intermediate_size=48, latent_attention=SIZES, epsilon=1e-5, **kw)


def test_the_decoder_builds_it_where_a_layer_asks():
    m = model()
    assert [type(b.attn) for b in m.h] == [MultiHeadLatentAttention] * 2
    ids = jnp.zeros((1, 16), jnp.int32)
    text = str(jax.make_jaxpr(lambda ids: functional_call(
        m, dict(state_of(m)[0]), {}, ids)[0])(ids).pretty_print(
            name_stack=True))
    for scope in ("h.1", "attn", "latent_q", "latent_kv", "q_a_proj",
                  "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm",
                  "kv_b_proj", "rope", "sdpa", "o_proj"):
        assert scope in text, scope
    # the latents' products and norms are inside the two scopes
    assert "latent_q/q_a_norm" in text and "latent_kv/kv_a_norm" in text
    assert "latent_q/rope" in text and "latent_kv/rope" in text
    # a model with other layer kinds beside it
    mixed = MixedDecoderModel(
        vocab_size=64, hidden_size=32,
        layer_types=["full_attention", "latent_attention"],
        heads_per_layer=[4, 4], mlp_layer_types=["dense", "dense"],
        kv_heads=2, head_dim=8,
        rope={"full_attention": {"theta": 1e4, "rotary_dim": 8},
              "latent_attention": {"theta": 1e4}},
        sliding_window=None, intermediate_size=48, latent_attention=SIZES)
    assert mixed(ids).shape == (1, 16, 32)


@pytest.mark.parametrize("backend, lanes, path", [
    ("cpu", (64, 192), "xla"), ("tpu", (64, 192), "pallas"),
    ("tpu", (4, 12), "xla")])
def test_staged_calls_are_counted_by_the_queries_rotation(monkeypatch,
                                                          backend, lanes,
                                                          path):
    """``latent_attn_calls_staged_total{rope}``: once a staged call, by the
    path q's rotation takes (a TPU and whole 128-lane heads: the kernel);
    ``k_r``'s one narrow head is XLA's everywhere. Traced, not run."""
    from paddle_tpu.telemetry.metrics import Registry
    d_rope, d_nope = lanes
    attn, _ = layer(heads=2, qk_rope_head_dim=d_rope,
                    qk_nope_head_dim=d_nope, v_head_dim=d_rope + d_nope)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    # the flash gate asks the backend too: keep attention on XLA's path
    import importlib
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.pallas.flash_attention"),
        "flash_supported", lambda *a, **k: False)
    prev, reg = telemetry.get_registry(), Registry()
    telemetry._set_registry(reg)
    telemetry.enable()
    try:
        x = jax.ShapeDtypeStruct((1, 128, 32), jnp.bfloat16)
        attn.astype("bfloat16")
        text = str(jax.make_jaxpr(lambda x: functional_call(
            attn, dict(state_of(attn)[0]), {}, x)[0])(x))
        calls = reg.get("latent_attn_calls_staged_total")
        rope = reg.get("rope_calls_staged_total")
        other = "xla" if path == "pallas" else "pallas"
        assert calls.value(rope=path) == 1 and calls.value(rope=other) == 0
        assert ("pallas_call" in text) is (path == "pallas")
        assert rope.value(path="xla", norm=0) == (1 if path == "pallas" else 2)
        assert rope.value(path="pallas", norm=0) == (path == "pallas")
    finally:
        telemetry.disable()
        telemetry._set_registry(prev)
