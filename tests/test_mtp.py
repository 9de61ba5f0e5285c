"""ISSUE 37: the multi-token-prediction module of
``MixedDecoderForPretraining(mtp_layers=1)``: two loss terms, the second
over ``L - 1`` positions, through the trunk's own embedding and head; at
small sizes on the CPU with seeded float32 weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.jit.functionalization import functional_call, state_of
from paddle_tpu.nn import functional as F
from paddle_tpu.text.models import (MixedDecoderForPretraining,
                                    MultiTokenPrediction)

LATENT = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
              qk_rope_head_dim=4, v_head_dim=16)


def model(mtp_layers=1, seed=0, **kw):
    paddle.seed(seed)
    args = dict(
        vocab_size=96, hidden_size=32,
        layer_types=["latent_attention"] * 2, heads_per_layer=[4, 4],
        mlp_layer_types=["dense", "sparse"], kv_heads=None, head_dim=None,
        rope={"latent_attention": {"theta": 1e4}}, sliding_window=None,
        intermediate_size=48, num_experts=8, experts_per_token=2,
        expert_size=16, shared_expert_size=16, held_experts=(0, 4),
        routed_scaling_factor=1.8, epsilon=1e-5, latent_attention=LATENT,
        router_selection_bias=True)
    return MixedDecoderForPretraining(mtp_layers=mtp_layers, **{**args, **kw})


def batch(rows=2, seq=16, seed=0):
    tokens = jax.random.randint(jax.random.key(seed), (rows, seq + 1), 0, 96)
    return tokens[:, :-1], tokens[:, 1:]


def per_token_ce(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def test_the_loss_has_two_terms_and_the_second_is_over_l_minus_1():
    m = model()
    ids, labels = batch()
    total = m((ids, labels))
    main, ahead = float(m.mtp_main_loss), float(m.mtp_next_loss)
    assert float(total) == pytest.approx(main + 0.3 * ahead, rel=1e-6)
    # by hand, from the model's own parts
    hidden = m.decoder(ids)
    assert main == pytest.approx(float(jnp.mean(per_token_ce(
        m.lm_head(hidden), labels))), rel=1e-5)
    z = m.mtp(m.decoder.embed_tokens(labels), hidden)
    assert z.shape == hidden.shape                  # all L positions run
    each = per_token_ce(m.lm_head(z), jnp.roll(labels, -1, axis=1))
    # position i predicts labels[i + 1]; the last has no such token
    assert ahead == pytest.approx(float(jnp.mean(each[:, :-1])), rel=1e-5)
    assert ahead != pytest.approx(float(jnp.mean(each)), rel=1e-4)
    # another weight
    other = model(mtp_loss_weight=1.0)
    assert float(other((ids, labels))) == pytest.approx(main + ahead,
                                                        rel=1e-6)


def test_the_module_reads_the_normed_trunk_and_the_next_tokens_embedding():
    m = model()
    ids, labels = batch()
    hidden = m.decoder(ids)
    embedded = m.decoder.embed_tokens(labels)
    mtp = m.mtp
    assert isinstance(mtp, MultiTokenPrediction)
    z = mtp.eh_proj(jnp.concatenate(
        [mtp.enorm(embedded), mtp.hnorm(hidden)], axis=-1))
    assert mtp.eh_proj.weight.value.shape == (64, 32)
    np.testing.assert_allclose(mtp(embedded, hidden),
                               mtp.norm(mtp.block(z)), rtol=1e-5, atol=1e-6)
    # the block is one more of the model's last kind, causal over the row
    assert type(mtp.block.attn).__name__ == "MultiHeadLatentAttention"
    assert "moe" in mtp.block._sub_layers
    changed = mtp(embedded.at[:, 9].add(1.0), hidden)
    same = jnp.abs(changed - mtp(embedded, hidden)).max(axis=-1)
    assert float(same[:, :9].max()) == 0.0 and float(same[:, 9].min()) > 0.0


def test_embedding_and_head_are_the_trunks_and_get_both_gradients():
    m = model()
    names = list(state_of(m)[0])
    assert not any("embed" in n or "lm_head" in n for n in names
                   if n.startswith("mtp."))
    assert sum("embed_tokens" in n for n in names) == 1
    assert sum(n.startswith("lm_head") for n in names) == 1
    ids = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]])
    labels = jnp.asarray([[2, 3, 4, 90, 6, 7, 8, 9]])  # 90: a label only
    params = dict(state_of(m)[0])

    def term(name):
        def f(p):
            out, new = functional_call(m, p, None, (ids, labels))
            return out if name == "total" else new[name]
        return jax.grad(f)(params)

    total, main, ahead = (term(n) for n in (
        "total", "mtp_main_loss", "mtp_next_loss"))
    for leaf in ("decoder.embed_tokens.weight", "lm_head.weight"):
        assert float(jnp.abs(main[leaf]).max()) > 0
        assert float(jnp.abs(ahead[leaf]).max()) > 0
        np.testing.assert_allclose(total[leaf],
                                   main[leaf] + 0.3 * ahead[leaf],
                                   rtol=1e-4, atol=1e-7)
    # token 90 is never an input: its embedding's row moves only through
    # the module's lookup of the labels
    assert float(jnp.abs(main["decoder.embed_tokens.weight"][90]).max()) == 0
    assert float(jnp.abs(ahead["decoder.embed_tokens.weight"][90]).max()) > 0
    # the module's own tensors belong to the second term alone
    for leaf in ("mtp.eh_proj.weight", "mtp.enorm.weight",
                 "mtp.hnorm.weight", "mtp.norm.weight",
                 "mtp.block.attn.q_b_proj.weight",
                 "mtp.block.moe.router.weight"):
        assert float(jnp.abs(main[leaf]).max()) == 0
        assert float(jnp.abs(ahead[leaf]).max()) > 0


def test_without_the_option_the_model_stages_what_it_staged():
    m = model(mtp_layers=0)
    params, buffers = state_of(m)
    assert not any(n.startswith("mtp") for n in list(params) + list(buffers))
    assert m.mtp is None
    ids, _ = batch()
    now = jax.make_jaxpr(lambda p, x: functional_call(m, p, {}, x)[0])(
        dict(params), ids)

    def before(p, x):
        """``forward`` as it stood before the option (PR 36)."""
        def forward(x):
            return m.lm_head(m.decoder(x))
        was, type(m).forward = type(m).forward, lambda self, x: forward(x)
        try:
            return functional_call(m, p, {}, x)[0]
        finally:
            type(m).forward = was

    assert str(now) == str(jax.make_jaxpr(before)(dict(params), ids))
    assert m(ids).shape == (2, 16, 96)
    with pytest.raises(ValueError, match="mtp_layers=2"):
        model(mtp_layers=2)


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint_blocks"])
def test_scopes_and_the_checkpointed_module(checkpoint):
    m = model(checkpoint_blocks=checkpoint)
    ids, labels = batch()
    params = dict(state_of(m)[0])

    def loss(p):
        return functional_call(m, p, None, (ids, labels))[0]

    text = str(jax.make_jaxpr(jax.grad(loss))(params).pretty_print(
        name_stack=True))
    root = "jvp(mixeddecoderforpretraining)/"
    for scope in ("mtp/enorm", "mtp/hnorm", "mtp/eh_proj",
                  "mtp/block/attn/latent_q", "mtp/block/attn/latent_kv",
                  "mtp/block/moe/router", "mtp/norm", "lm_head", "loss",
                  "embed_tokens", "decoder/embed_tokens"):
        assert root + scope in text, scope
    assert "mtp/lm_head" not in text and "mtp/loss" not in text
    plain = model(checkpoint_blocks=False)
    got, want = jax.value_and_grad(loss)(params), jax.value_and_grad(
        lambda p: functional_call(plain, p, None, (ids, labels))[0])(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for k in params:
        np.testing.assert_allclose(got[1][k], want[1][k], rtol=2e-4,
                                   atol=1e-6, err_msg=k)


def test_it_trains_through_the_trainer_and_publishes_its_terms():
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.engine import ParallelTrainer

    before = mesh_mod.get_mesh()
    try:
        mesh = mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
        m = model(checkpoint_blocks=True)
        opt = paddle.optimizer.AdamW(3e-3, parameters=m.parameters())
        trainer = ParallelTrainer(m, opt, lambda out, _labels: out,
                                  mesh=mesh)
        ids, labels = batch(rows=4)
        losses = [float(trainer.train_step((ids, labels), 0.0))
                  for _ in range(5)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05
        # the step moved the module and the tensors it shares
        for leaf in ("mtp.eh_proj.weight", "lm_head.weight",
                     "decoder.embed_tokens.weight"):
            assert float(jnp.abs(trainer.state["params"][leaf]
                                 - dict(state_of(m)[0])[leaf]).max()) > 0
    finally:
        mesh_mod.set_mesh(before)
    # the two terms leave a jitted call in the buffers and reach telemetry
    _, new = jax.jit(lambda p: functional_call(m, p, None, (ids, labels)))(
        dict(state_of(m)[0]))
    assert float(m.mtp_main_loss) == 0.0     # nothing stayed in the layer
    prev = telemetry.get_registry()
    telemetry._set_registry(telemetry.Registry())
    try:
        m.publish_losses(new, cell="toy")
        got = telemetry.get_registry().to_dict()
    finally:
        telemetry._set_registry(prev)
    assert list(got["mtp_main_loss"]["series"].values()) == [
        pytest.approx(float(new["mtp_main_loss"]))]
    assert list(got["mtp_next_loss"]["series"].values()) == [
        pytest.approx(float(new["mtp_next_loss"]))]
    assert "mtp_main_loss" not in m.state_dict()
