"""ISSUE 33: the mixed decoder's third layer kind. A toy model with
``linear_attention`` layers three to one with gated full attention, the
zero-centred norms, the gate inside ``q_proj``, the gated shared expert,
against the benchmark's float32 reference
(``benchmark/reference/qwen3next.py``, which scans position by position)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from benchmark.families import qwen3next as family
from benchmark.reference import qwen3next as reference
from paddle_tpu import nn, telemetry
from paddle_tpu.incubate.moe import DroplessMoELayer
from paddle_tpu.jit.functionalization import functional_call, state_of
from paddle_tpu.text.models import MixedDecoderForPretraining
from paddle_tpu.text.models.mixed_decoder import (GroupedQueryAttention,
                                                  MixedDecoderModel)


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """The toy rows have 40 positions: chunks of 16, so that a layer still
    carries its state over three of them."""
    from paddle_tpu.nn.layers import linear_attention
    monkeypatch.setattr(linear_attention, "CHUNK", 16)


LINEAR = dict(key_heads=2, value_heads=4, d_k=16, d_v=16, conv_kernel=4)
ARCH = {"layers": ["linear_attention"] * 3 + ["full_attention"], "heads": 4,
        "kv_heads": 2, "head_dim": 32, "rope_theta": 1e7, "rotary_dim": 8,
        "linear": LINEAR, "top_k": 2, "held": (8, 8)}


def toy_model(**over):
    kwargs = dict(
        vocab_size=128, hidden_size=64, layer_types=ARCH["layers"],
        heads_per_layer=[4] * 4, mlp_layer_types=["sparse"] * 4, kv_heads=2,
        head_dim=32,
        rope={"full_attention": {"theta": 1e7, "rotary_dim": 8}},
        sliding_window=None, intermediate_size=128, num_experts=16,
        experts_per_token=2, expert_size=32, shared_expert_size=32,
        held_experts=(8, 8), router_scoring="softmax", qk_norm=True,
        attention_gate="elementwise", shared_expert_gate=True,
        linear_attention=LINEAR, norm_offset=1.0)
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


@pytest.mark.parametrize("checkpoint", [False, True])
def test_toy_model_equals_the_reference(checkpoint):
    """Loss and every gradient leaf, float32 on both sides: the chunked rule
    against the reference's scan over positions, the convolution, both
    norms, the elementwise gate, partial rotation, the gated shared expert,
    and the program's column order mapped onto the published grouping."""
    model = toy_model(checkpoint_blocks=checkpoint)
    params = perturbed(model)
    ids = jax.random.randint(jax.random.key(0), (2, 80), 0, 128)
    labels = jnp.roll(ids, -1, axis=1)

    def ours(p):
        logits = functional_call(model, p, {}, ids, rng=jax.random.key(0))[0]
        return nn.functional.cross_entropy(logits, labels)

    def theirs(p):
        return reference.loss(p, ids, labels, n_head=ARCH, eps=1e-6,
                              remat=bool(checkpoint))

    loss, grads = jax.jit(jax.value_and_grad(ours))(params)
    want, want_grads = jax.jit(jax.value_and_grad(theirs))(
        reference_params(params))
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got = reference_params(grads)

    def close(a, b, name):
        """As the harness compares a leaf: ``|g - g_ref| / |g_ref|``."""
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert a.shape == b.shape and rel < 5e-4, (name, rel)

    for i, block in enumerate(want_grads.pop("blocks")):
        assert set(block) == set(got["blocks"][i])
        for name, g in block.items():
            close(got["blocks"][i][name], g, f"{i}.{name}")
    for name, g in want_grads.items():
        close(got[name], g, name)


def test_blocks_are_named_by_their_mixer():
    model = toy_model()
    names = set(dict(model.named_parameters()))
    for i in range(3):
        assert f"decoder.h.{i}.linear_attn.in_proj_qkvz.weight" in names
        assert not any(n.startswith(f"decoder.h.{i}.attn.") for n in names)
    assert "decoder.h.3.attn.q_proj.weight" in names
    assert not any("h.3.linear_attn" in n for n in names)
    # the doubled q_proj, no g_proj, zero-centred norms, the shared gate
    p = dict(model.named_parameters())
    assert p["decoder.h.3.attn.q_proj.weight"].shape == (64, 4 * 2 * 32)
    assert not any("g_proj" in n for n in names)
    for n in ("decoder.norm.weight", "decoder.h.0.input_norm.weight",
              "decoder.h.3.attn.q_norm.weight"):
        assert float(jnp.abs(p[n].value).max()) == 0.0
    assert float(p["decoder.h.0.linear_attn.norm.weight"].value.min()) == 1.0
    assert p["decoder.h.0.moe.shared_expert_gate.weight"].shape == (64, 1)
    text = str(jax.make_jaxpr(lambda x: model(x))(
        jnp.zeros((1, 32), jnp.int32)))
    assert text.count("scan[") >= 3          # a scan a linear layer


def test_scopes_name_every_part_of_the_linear_mixer():
    model = toy_model()
    lowered = jax.jit(lambda x: model(x)).lower(jnp.zeros((1, 32), jnp.int32))
    text = lowered.as_text(debug_info=True)
    root = "mixeddecoderforpretraining/decoder/h.1/linear_attn/"
    for scope in ("in_proj_qkvz", "in_proj_ba", "causal_conv",
                  "gated_delta_rule", "norm", "out_proj"):
        assert root + scope in text, scope
    assert "decoder/h.3/attn/qk_norm/rope" in text
    assert "decoder/h.3/linear_attn" not in text


def test_positions_and_block_diffusion_are_refused():
    decoder = toy_model().decoder
    ids = jnp.zeros((1, 32), jnp.int32)
    with pytest.raises(ValueError, match="recurrence"):
        decoder(ids, positions=jnp.arange(32))
    with pytest.raises(ValueError, match="recurrence"):
        decoder.blocks(ids, block_diffusion=4)
    with pytest.raises(ValueError, match="unknown layer kinds"):
        toy_model(layer_types=["linear", "full_attention"] * 2)
    with pytest.raises(ValueError, match="unknown attention gate"):
        toy_model(attention_gate="lane")
    # a model without linear layers still takes both
    plain = MixedDecoderModel(
        vocab_size=64, hidden_size=32, layer_types=["full_attention"],
        heads_per_layer=[2], mlp_layer_types=["dense"], kv_heads=1,
        head_dim=16, rope={"full_attention": {"theta": 1e4,
                                              "rotary_dim": 16}},
        sliding_window=None, intermediate_size=64)
    assert plain(ids, positions=jnp.arange(32)).shape == (1, 32, 32)


@pytest.mark.parametrize("gate, width", [(None, 1), ("head", 1),
                                         ("elementwise", 2)])
def test_attention_gates(gate, width):
    """None, one sigmoid a head from g_proj (``gated_attention=True`` as
    before), or one a lane from the second half of a head's q_proj
    columns."""
    rope = {"theta": 1e4, "rotary_dim": 16}
    attn = GroupedQueryAttention(32, 4, 2, 16, rope, gate=gate)
    assert attn.q_proj.weight.shape == (32, 4 * 16 * width)
    assert (attn.g_proj is not None) == (gate == "head")
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    out = attn(x)
    # the same layer with the gate taken out, then applied by hand
    plain = GroupedQueryAttention(32, 4, 2, 16, rope)
    w = attn.q_proj.weight.value.reshape(32, 4, -1)
    plain.q_proj.weight.value = w[..., :16].reshape(32, 64)
    for name in ("k_proj", "v_proj"):
        getattr(plain, name).weight.value = getattr(attn, name).weight.value
    plain.o_proj.weight.value = jnp.eye(64)
    heads = plain(x).reshape(2, 24, 4, 16)
    if gate == "head":
        heads = heads * jax.nn.sigmoid(x @ attn.g_proj.weight.value)[
            ..., None]
    elif gate == "elementwise":
        heads = heads * jax.nn.sigmoid(jnp.einsum(
            "bsh,hnd->bsnd", x, w[..., 16:]))
    np.testing.assert_allclose(
        out, heads.reshape(2, 24, 64) @ attn.o_proj.weight.value, atol=1e-5)
    if gate == "head":
        model = MixedDecoderModel(
            vocab_size=64, hidden_size=32, layer_types=["full_attention"],
            heads_per_layer=[4], mlp_layer_types=["dense"], kv_heads=2,
            head_dim=16, rope={"full_attention": rope}, sliding_window=None,
            intermediate_size=64, gated_attention=True)
        assert model.h[0].attn.gate == "head"


def test_shared_expert_gate():
    layer = DroplessMoELayer(16, 8, 8, 2, scoring="softmax", d_shared=8,
                             shared_expert_gate=True)
    plain = DroplessMoELayer(16, 8, 8, 2, scoring="softmax", d_shared=8)
    assert plain.shared_expert_gate is None
    for name, p in plain.named_parameters():
        p.value = dict(layer.named_parameters())[name].value
    x = jax.random.normal(jax.random.key(0), (3, 10, 16))
    shared = layer.shared_expert(x)
    gate = jax.nn.sigmoid(x @ layer.shared_expert_gate.weight.value)
    np.testing.assert_allclose(layer(x), plain(x) - shared + gate * shared,
                               atol=1e-5)
    assert DroplessMoELayer(16, 8, 8, 2, shared_expert_gate=True) \
        .shared_expert_gate is None          # nothing to gate


def test_the_sixteen_shares_add_up():
    """The guide's share test at a toy size: 16 experts in 4 shares of 4.
    The routed parts of all shares plus the gated shared expert ONCE are the
    uncut reference's layer; and each share's layer is the reference's given
    the same share."""
    d, f, experts, chips, k = 32, 16, 16, 4, 3
    ks = jax.random.split(jax.random.key(2), 9)
    p = {"router_w": jax.random.normal(ks[0], (d, experts)) * 0.3,
         "shared_gate_w": jax.random.normal(ks[1], (d, f)) * 0.2,
         "shared_up_w": jax.random.normal(ks[2], (d, f)) * 0.2,
         "shared_down_w": jax.random.normal(ks[3], (f, d)) * 0.2,
         "shared_expert_gate_w": jax.random.normal(ks[4], (d, 1)),
         "experts_gate_w": jax.random.normal(ks[5], (experts, d, f)) * 0.2,
         "experts_up_w": jax.random.normal(ks[6], (experts, d, f)) * 0.2,
         "experts_down_w": jax.random.normal(ks[7], (experts, f, d)) * 0.2}
    u = jax.random.normal(ks[8], (2, 40, d))
    whole, _ = reference.moe(u, p, {"top_k": k, "held": (0, experts)})
    shared = jax.nn.sigmoid(u @ p["shared_expert_gate_w"]) \
        * reference.gated_ffn(u, p["shared_gate_w"], p["shared_up_w"],
                              p["shared_down_w"])
    total = 0.0
    for chip in range(chips):
        first, count = chip * experts // chips, experts // chips
        layer = DroplessMoELayer(d, f, experts, k, held=(first, count),
                                 scoring="softmax", d_shared=f,
                                 shared_expert_gate=True)
        layer.router.weight.value = p["router_w"]
        layer.shared_expert_gate.weight.value = p["shared_expert_gate_w"]
        for name in ("gate", "up", "down"):
            getattr(layer.shared_expert, f"{name}_proj").weight.value = \
                p[f"shared_{name}_w"]
            getattr(layer.experts, f"{name}_proj").value = \
                p[f"experts_{name}_w"][first:first + count]
        got = layer(u)
        part, _ = reference.moe(
            u, dict(p, **{f"experts_{n}_w": p[f"experts_{n}_w"][
                first:first + count] for n in ("gate", "up", "down")}),
            {"top_k": k, "held": (first, count)})
        np.testing.assert_allclose(got, part, rtol=1e-4, atol=1e-5)
        total = total + got
    np.testing.assert_allclose(total - (chips - 1) * shared, whole,
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(whole - shared).mean()) > 0.01


def test_counters_of_a_forward_pass():
    model = toy_model()
    ids = jnp.zeros((1, 40), jnp.int32)
    with telemetry.scope(profile=False) as tel:
        model(ids)
        calls = tel.registry.get("linear_attn_calls_staged_total")
        assert calls.value(path="chunked") == 3
        # 40 positions in chunks of 16: three chunk states a layer
        assert tel.registry.get("gated_delta_chunks_total").value() == 9
        rope = tel.registry.get("rope_calls_staged_total")
        assert rope.value(path="xla", norm=1) == 2       # q and k, on the CPU
    for _, m in model.named_sublayers():
        if isinstance(m, DroplessMoELayer):
            assert int(m.tokens_routed) == 40


@pytest.mark.parametrize("ffn", ["sparse", "dense"])
def test_checkpoint_blocks_recomputes_a_blocks_halves_apart(ffn):
    """``checkpoint_blocks``: two checkpoints a block, the mixer's half and
    the feed-forward half, so that the two halves' intermediates never
    exist together; without it none."""
    ids = jnp.zeros((1, 40), jnp.int32)
    for checkpoint, want in ((False, 0), (True, 2 * 4)):
        model = toy_model(checkpoint_blocks=checkpoint,
                          mlp_layer_types=[ffn] * 4)
        params = dict(state_of(model)[0])
        jaxpr = jax.make_jaxpr(
            lambda p: functional_call(model, p, None, ids)[0])(params)
        assert _staged.checkpoints(jaxpr.jaxpr) == want, checkpoint
