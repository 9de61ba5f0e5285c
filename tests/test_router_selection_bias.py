"""ISSUE 37: the router's selection bias (``topk_method: noaux_tc``): the
experts are chosen by ``scores + bias`` and weighted by ``scores`` alone;
the bias is a persistable buffer that no gradient and no optimizer reaches
and that a trainer's step and a checkpoint keep; without one the router
stages what it staged before; and the shares of an expert-parallel layout
add up to the uncut layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.incubate.moe import DroplessMoELayer, route_top_k
from paddle_tpu.jit.functionalization import functional_call, state_of


def test_a_large_bias_chooses_and_does_not_weigh():
    """Expert 2 has the smallest score and a large bias: it is chosen, and
    its weight is its unbiased score over the unbiased sum x the factor."""
    logits = jnp.asarray([[0.0, 2.0, -1.0, 1.0]])
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0])
    ids, w = route_top_k(logits, 2, "sigmoid", 1.8, selection_bias=bias)
    assert ids.tolist() == [[2, 1]] and ids.dtype == jnp.int32
    s = 1 / (1 + np.exp(-np.asarray([-1.0, 2.0])))
    np.testing.assert_allclose(w[0], 1.8 * s / s.sum(), rtol=1e-6)
    assert w.dtype == jnp.float32
    # without it the two largest scores are chosen
    assert route_top_k(logits, 2, "sigmoid", 1.8)[0].tolist() == [[1, 3]]
    # a zero bias chooses and weighs as none does
    for a, b in zip(route_top_k(logits, 2, "sigmoid", 1.8),
                    route_top_k(logits, 2, "sigmoid", 1.8,
                                selection_bias=jnp.zeros(4))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_no_gradient_reaches_the_bias(scoring):
    logits = jax.random.normal(jax.random.key(0), (6, 8))
    bias = jax.random.normal(jax.random.key(1), (8,))

    def f(logits, bias):
        return jnp.sum(route_top_k(logits, 3, scoring, 1.8, bias)[1] ** 2)

    g_logits, g_bias = jax.grad(f, argnums=(0, 1))(logits, bias)
    assert float(jnp.abs(g_bias).max()) == 0.0
    # top-2 of 3 normalised weights move with the logits all the same
    assert float(jnp.abs(g_logits).max()) > 0.0


def _route_top_k_before(logits, top_k, scoring="sigmoid", scaling_factor=1.0):
    """``route_top_k`` as it stood before it took a bias (PR 36)."""
    logits = logits.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    top, ids = lax.top_k(scores, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), top * scaling_factor


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_without_a_bias_the_router_stages_what_it_staged(scoring):
    logits = jax.random.normal(jax.random.key(0), (16, 32)).astype(
        jnp.bfloat16)
    now = jax.make_jaxpr(lambda x: route_top_k(x, 4, scoring, 2.5))(logits)
    before = jax.make_jaxpr(
        lambda x: _route_top_k_before(x, 4, scoring, 2.5))(logits)
    assert str(now) == str(before)
    for a, b in zip(route_top_k(logits, 4, scoring, 2.5),
                    _route_top_k_before(logits, 4, scoring, 2.5)):
        np.testing.assert_array_equal(a, b)
    # and a layer built without the option has no such buffer
    layer = DroplessMoELayer(8, 4, 6, 2, scoring=scoring)
    assert "e_score_correction_bias" not in dict(layer.named_buffers())
    assert "e_score_correction_bias" not in layer.state_dict()


def test_the_layer_registers_a_persistable_buffer_and_routes_by_it():
    paddle.seed(0)
    layer = DroplessMoELayer(8, 4, 6, 2, routed_scaling_factor=1.8,
                             selection_bias=True)
    params, buffers = state_of(layer)
    assert "e_score_correction_bias" not in params
    bias = buffers["e_score_correction_bias"]
    assert bias.shape == (6,) and bias.dtype == jnp.float32
    assert float(jnp.abs(bias).max()) == 0.0
    assert "e_score_correction_bias" in layer.state_dict()
    # the counters beside it are not persistable and are not saved
    assert "tokens_routed" not in layer.state_dict()
    tokens = jax.random.normal(jax.random.key(5), (10, 8))
    plain = layer.route(tokens)[0]
    layer.e_score_correction_bias = jnp.zeros(6).at[4].set(9.0)
    ids, weights = layer.route(tokens)
    assert bool(jnp.all(jnp.any(ids == 4, axis=-1)))
    assert not bool(jnp.all(jnp.any(plain == 4, axis=-1)))
    s = jax.nn.sigmoid(tokens @ layer.router.weight.value)
    top = jnp.take_along_axis(s, ids, axis=-1)
    np.testing.assert_allclose(
        weights, 1.8 * top / top.sum(-1, keepdims=True), rtol=1e-5)
    # a functional call reads the bias it is handed, not the layer's own
    x = jnp.reshape(tokens, (2, 5, 8))
    handed = dict(buffers)
    out_zero, _ = functional_call(layer, dict(params), handed, x)
    out_own, new = functional_call(layer, dict(params), None, x)
    assert float(jnp.abs(out_zero - out_own).max()) > 1e-4
    np.testing.assert_array_equal(new["e_score_correction_bias"],
                                  layer.e_score_correction_bias)


def _model(**kw):
    from paddle_tpu.text.models import MixedDecoderForPretraining
    return MixedDecoderForPretraining(
        vocab_size=64, hidden_size=32,
        layer_types=["full_attention"] * 2, heads_per_layer=[4, 4],
        mlp_layer_types=["dense", "sparse"], kv_heads=2, head_dim=8,
        rope={"full_attention": {"theta": 1e4, "rotary_dim": 8}},
        sliding_window=None, intermediate_size=48, num_experts=8,
        experts_per_token=2, expert_size=16, shared_expert_size=16,
        held_experts=(0, 4), routed_scaling_factor=1.8,
        checkpoint_blocks=True, **kw)


def test_the_bias_survives_a_trainers_step_and_has_no_optimizer_slot():
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.engine import ParallelTrainer

    before = mesh_mod.get_mesh()
    try:
        mesh = mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
        paddle.seed(0)
        model = _model(router_selection_bias=True)
        name = "decoder.h.1.moe.e_score_correction_bias"
        bias = jnp.asarray([0., 3., 0., 0., -2., 0., 0., 1.])
        model.decoder.h[1].moe.e_score_correction_bias = bias
        opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
        trainer = ParallelTrainer(
            model, opt, lambda logits, labels: nn.functional.cross_entropy(
                logits, labels), mesh=mesh)
        assert name in trainer.state["buffers"]
        assert name not in trainer.state["params"]
        assert not any("e_score_correction_bias" in k
                       for k in jax.tree_util.tree_leaves(
                           jax.tree_util.tree_map_with_path(
                               lambda path, _: jax.tree_util.keystr(path),
                               trainer.state["opt"])))
        ids = jax.random.randint(jax.random.key(0), (2, 16), 0, 64)
        router = np.asarray(trainer.state["params"][
            "decoder.h.1.moe.router.weight"])
        losses = [float(trainer.train_step(ids, jnp.roll(ids, -1, 1)))
                  for _ in range(3)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        np.testing.assert_array_equal(trainer.state["buffers"][name], bias)
        # the router beside it did move
        assert float(np.abs(np.asarray(trainer.state["params"][
            "decoder.h.1.moe.router.weight"]) - router).max()) > 0
        # and a checkpoint of the model carries it, a fresh model loads it
        saved = model.state_dict()
        np.testing.assert_array_equal(saved[name], bias)
        fresh = _model(router_selection_bias=True)
        fresh.set_state_dict(saved)
        np.testing.assert_array_equal(
            fresh.decoder.h[1].moe.e_score_correction_bias, bias)
    finally:
        mesh_mod.set_mesh(before)


def test_the_model_passes_the_option_on_and_leaves_it_off_by_default():
    assert "decoder.h.1.moe.e_score_correction_bias" not in dict(
        _model().named_buffers())
    assert "decoder.h.1.moe.e_score_correction_bias" in dict(
        _model(router_selection_bias=True).named_buffers())


def test_shares_add_up_to_the_whole_layer_under_a_bias():
    """The guide's share test: four layers holding experts 0-3, 4-7, 8-11,
    12-15 of 16, given the same weights and the same non-zero bias as the
    uncut reference, add up, with the shared expert counted once, to the
    reference's whole layer."""
    from benchmark.reference import glm4moelite as reference

    d, f, experts, k, chips = 32, 16, 16, 4, 4
    ks = jax.random.split(jax.random.key(4), 9)
    p = {"router_w": jax.random.normal(ks[0], (d, experts)) * 0.3,
         "shared_gate_w": jax.random.normal(ks[1], (d, f)) * 0.2,
         "shared_up_w": jax.random.normal(ks[2], (d, f)) * 0.2,
         "shared_down_w": jax.random.normal(ks[3], (f, d)) * 0.2,
         "experts_gate_w": jax.random.normal(ks[4], (experts, d, f)) * 0.2,
         "experts_up_w": jax.random.normal(ks[5], (experts, d, f)) * 0.2,
         "experts_down_w": jax.random.normal(ks[6], (experts, f, d)) * 0.2}
    u = jax.random.normal(ks[7], (2, 48, d))
    bias = jax.random.normal(ks[8], (experts,)) * 0.3
    arch = {"top_k": k, "routed_scaling_factor": 1.8, "held": (0, experts)}
    whole, chosen = reference.moe(u, p, bias, arch)
    # the bias does change the choice on these tokens
    unbiased = reference.moe(u, p, jnp.zeros(experts), arch)[1]
    assert float(jnp.mean(jnp.sort(chosen, -1) != jnp.sort(unbiased, -1))) \
        > 0.05

    held = experts // chips
    total = 0.0
    for share in range(chips):
        first = held * share
        layer = DroplessMoELayer(d, f, experts, k, held=(first, held),
                                 routed_scaling_factor=1.8, d_shared=f,
                                 selection_bias=True)
        layer.router.weight.value = p["router_w"]
        layer.e_score_correction_bias = bias
        for name in ("gate", "up", "down"):
            getattr(layer.shared_expert, f"{name}_proj").weight.value = \
                p[f"shared_{name}_w"]
            getattr(layer.experts, f"{name}_proj").value = \
                p[f"experts_{name}_w"][first:first + held]
        # the reference given the same share computes the same part
        part, _ = reference.moe(
            u, dict(p, **{f"experts_{n}_w": p[f"experts_{n}_w"][
                first:first + held] for n in ("gate", "up", "down")}),
            bias, dict(arch, held=(first, held)))
        got = layer(u)
        np.testing.assert_allclose(got, part, rtol=1e-4, atol=1e-5)
        total = total + got
    shared = reference.gated_ffn(u, p["shared_gate_w"], p["shared_up_w"],
                                 p["shared_down_w"])
    np.testing.assert_allclose(total - (chips - 1) * shared, whole,
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(whole - shared).mean()) > 0.01
