"""ISSUE 39: what the mixed decoder gained for Nemotron-H, at small sizes on
the CPU in float32: squared-ReLU experts and shared expert, layers of one
sublayer (a Mamba-2 mixer, an expert layer or attention alone), and
grouped-query attention without rotation."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from paddle_tpu import telemetry
from paddle_tpu.incubate.moe import (DroplessMoELayer, GroupedExperts,
                                     route_top_k)
from paddle_tpu.jit.functionalization import functional_call, state_of
from paddle_tpu.text.models import MixedDecoderForPretraining
from paddle_tpu.text.models.mixed_decoder import GroupedQueryAttention

MAMBA = dict(num_heads=4, head_dim=8, n_groups=2, state_size=8,
             conv_kernel=4, chunk=16)
PATTERN = ["mamba", None, "mamba", None, "full_attention", None]
FFN = [None, "sparse", None, "sparse", None, "sparse"]


def toy_model(**over):
    kwargs = dict(
        vocab_size=128, hidden_size=32, layer_types=PATTERN,
        heads_per_layer=[4] * 6, mlp_layer_types=FFN, kv_heads=2,
        head_dim=16, rope={"full_attention": None}, sliding_window=None,
        intermediate_size=0, num_experts=8, experts_per_token=2,
        expert_size=16, shared_expert_size=24, held_experts=(2, 4),
        routed_scaling_factor=2.5, router_scoring="sigmoid",
        router_selection_bias=True, expert_activation="relu2", mamba=MAMBA,
        epsilon=1e-5)
    kwargs.update(over)
    return MixedDecoderForPretraining(**kwargs)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def dense_relu2_moe(layer, x, p):
    """The layer's equation with squared-ReLU experts, a dense loop over
    the held experts."""
    tokens = jnp.reshape(x, (-1, x.shape[-1]))
    ids, weights = route_top_k(tokens @ p["router.weight"], layer.top_k,
                               layer.scoring, layer.routed_scaling_factor)
    out = relu2(tokens @ p["shared_expert.up_proj.weight"]) \
        @ p["shared_expert.down_proj.weight"]
    for j in range(layer.count):
        w = jnp.sum(jnp.where(ids == layer.first + j, weights, 0.0), -1)
        h = relu2(tokens @ p["experts.up_proj"][j])
        out = out + w[:, None] * (h @ p["experts.down_proj"][j])
    return jnp.reshape(out, x.shape)


def test_relu2_experts_and_shared_expert_against_a_dense_loop():
    layer = DroplessMoELayer(16, 8, 12, 3, held=(4, 6),
                             routed_scaling_factor=2.5, d_shared=20,
                             activation="relu2")
    params, buffers = state_of(layer)
    params = dict(params)
    # two products an expert, no gate; the shared expert the same
    assert set(params) == {"router.weight", "shared_expert.up_proj.weight",
                           "shared_expert.down_proj.weight",
                           "experts.up_proj", "experts.down_proj"}
    x = jax.random.normal(jax.random.key(0), (2, 20, 16))
    out, grad = _staged.out_and_grads(lambda p: functional_call(
        layer, p, buffers, x)[0], params)
    dense, want = _staged.out_and_grads(lambda p: dense_relu2_moe(
        layer, x, p), params)
    np.testing.assert_allclose(out, dense, rtol=1e-5, atol=1e-5)
    for name in want:
        np.testing.assert_allclose(grad[name], want[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)


def test_grouped_experts_relu2_by_hand_and_unknown_activations():
    experts = GroupedExperts(2, 3, 4, activation="relu2")
    up = jnp.arange(24.0).reshape(2, 3, 4) / 10 - 1
    down = jnp.arange(24.0).reshape(2, 4, 3) / 10
    experts.up_proj.value, experts.down_proj.value = up, down
    rows = jnp.asarray([[1.0, 0.0, 0.0], [0.0, 1.0, -1.0], [1.0, 1.0, 1.0]])
    got = experts(rows, jnp.asarray([1, 2], jnp.int32))
    want = [relu2(rows[0] @ up[0]) @ down[0]] \
        + [relu2(r @ up[1]) @ down[1] for r in rows[1:]]
    np.testing.assert_allclose(got, jnp.stack(want), rtol=1e-6)
    with pytest.raises(ValueError, match="activation"):
        GroupedExperts(2, 3, 4, activation="gelu")


def test_one_sublayer_blocks_are_named_by_what_they_hold():
    model = toy_model()
    names = set(dict(model.named_parameters()))
    for i, (mixer, ffn) in enumerate(zip(PATTERN, FFN)):
        block = {n.split(".", 3)[3] for n in names
                 if n.startswith(f"decoder.h.{i}.")}
        assert "input_norm.weight" in block
        assert not any(n.startswith("post_attn_norm") for n in block)
        part = "mamba" if mixer == "mamba" else "attn" if mixer else "moe"
        assert {n.split(".")[0] for n in block} == {"input_norm", part}
    assert "decoder.h.0.mamba.conv_bias" in names
    assert "decoder.h.1.moe.experts.up_proj" in names
    assert not any("gate_proj" in n for n in names)
    assert not any("rope" in n or "q_norm" in n for n in names)
    # a layer of one sublayer: x + sublayer(norm(x))
    block = model.decoder.h[1]
    x = jax.random.normal(jax.random.key(1), (1, 10, 32))
    np.testing.assert_allclose(
        _staged.forward(block)(x), x + _staged.forward(block.moe)(
            _staged.forward(block.input_norm)(x)), rtol=1e-6, atol=1e-6)


def test_a_block_needs_a_sublayer():
    with pytest.raises(ValueError, match="mixer or a feed-forward"):
        toy_model(layer_types=[None], mlp_layer_types=[None],
                  heads_per_layer=[4])


def test_checkpoint_blocks_is_one_checkpoint_a_sublayer_and_changes_nothing():
    ids = jax.random.randint(jax.random.key(2), (2, 40), 0, 128)
    plain = toy_model()
    params, buffers = state_of(plain)
    params = dict(params)

    def loss(model):
        def f(p):
            logits = functional_call(model, p, buffers, ids)[0]
            return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])
        return f

    checked = toy_model(checkpoint_blocks=True)
    jaxpr = jax.make_jaxpr(loss(checked))(params).jaxpr
    assert _staged.checkpoints(jaxpr) == 6     # one a block of one sublayer
    assert _staged.checkpoints(
        jax.make_jaxpr(loss(plain))(params).jaxpr) == 0
    want, g_want = _staged.value_and_grads(loss(plain), params)
    got, g_got = _staged.value_and_grads(loss(checked), params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for name in g_want:
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=1e-5,
                                   atol=1e-7, err_msg=name)


def test_attention_without_rotation_is_plain_softmax_attention():
    attn = GroupedQueryAttention(32, 4, 2, 16, rope=None)
    assert attn.inv_freq is None
    x = jax.random.normal(jax.random.key(3), (2, 12, 32))
    p = {k: v.value for k, v in attn.named_parameters()}
    q = jnp.reshape(x @ p["q_proj.weight"], (2, 12, 4, 16))
    k = jnp.repeat(jnp.reshape(x @ p["k_proj.weight"], (2, 12, 2, 16)), 2, 2)
    v = jnp.repeat(jnp.reshape(x @ p["v_proj.weight"], (2, 12, 2, 16)), 2, 2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(16)
    mask = np.tril(np.ones((12, 12), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    o = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(2, 12, 64)
    attend = _staged.forward(attn)
    out = attend(x)
    np.testing.assert_allclose(out, o @ p["o_proj.weight"], rtol=1e-5,
                               atol=1e-5)
    # no positional encoding: the last position's output does not depend on
    # the order of the positions before it
    perm = jnp.concatenate([jnp.arange(10, -1, -1), jnp.asarray([11])])
    np.testing.assert_allclose(attend(x[:, perm])[:, -1],
                               out[:, -1], rtol=1e-5, atol=1e-5)


def test_mamba_layers_refuse_positions_and_count_their_scans():
    model = toy_model()
    ids = jnp.zeros((1, 40), jnp.int32)
    with pytest.raises(ValueError, match="recurrence"):
        model.decoder.blocks(ids, positions=jnp.arange(40))
    with telemetry.scope(profile=False) as tel:
        model(ids)
        calls = tel.registry.get("ssd_scan_calls_staged_total")
        assert calls.value(path="chunked") == 2
        # 40 positions in chunks of 16: three chunk states a layer
        assert tel.registry.get("ssd_chunks_total").value() == 6
    text = jax.jit(lambda x: model(x)).lower(ids).as_text(debug_info=True)
    root = "mixeddecoderforpretraining/decoder/h.0/mamba/"
    for scope in ("in_proj", "causal_conv", "ssd_scan", "gated_norm",
                  "out_proj"):
        assert root + scope in text, scope
    assert "decoder/h.4/attn/sdpa" in text and "h.4/attn/rope" not in text
