"""Block-diffusion training in the program (ISSUE 31): the mask as the
flash kernels' static tile geometry and on the XLA path, the noise and the
loss, explicit rotary positions, QK norm, and the model that puts them
together. Small sizes on the CPU; the kernels run in interpret mode."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _staged
from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional.attention import _xla_attention
from paddle_tpu.text import block_diffusion as bd
from paddle_tpu.text.models import (GroupedQueryAttention,
                                    MixedDecoderForBlockDiffusion)

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def four_rules(half, block):
    """The mask from its four rules, query by query: nothing shared with
    the program's statement of it."""
    seen = np.zeros((2 * half, 2 * half), bool)
    for i in range(2 * half):
        for j in range(2 * half):
            bi, bj = (i % half) // block, (j % half) // block
            if i < half:
                seen[i, j] = (bj == bi) if j < half else (bj < bi)
            else:
                seen[i, j] = j >= half and bj <= bi
    return seen


def plain_attention(q, k, v, seen):
    """Softmax attention under a boolean matrix, grouped heads repeated."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(jnp.asarray(seen), scores, -jnp.inf)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, -1), v)


def qkv(half, heads, kv_heads, d, batch=1, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    shape = (batch, 2 * half)
    return (jax.random.normal(keys[0], shape + (heads, d)),
            jax.random.normal(keys[1], shape + (kv_heads, d)),
            jax.random.normal(keys[2], shape + (kv_heads, d)),
            jax.random.normal(keys[3], shape + (heads, d)))


# -- the mask ----------------------------------------------------------------

@pytest.mark.parametrize("half, block", [(8, 2), (12, 4), (12, 3), (16, 16)])
def test_mask_is_the_four_rules(half, block):
    got = np.asarray(F.block_diffusion_mask(2 * half, block))
    np.testing.assert_array_equal(got, four_rules(half, block))
    rows = np.arange(2 * half)[:, None]
    cols = np.arange(2 * half)[None, :]
    np.testing.assert_array_equal(
        fa.block_diffusion_visible(rows, cols, half, lambda p: p // block),
        got)
    # a query sees (half + block) / 2 keys on average, noised or clean
    assert got[:half].sum() == got[half:].sum() == half * (half + block) / 2


TILE_CASES = [  # half, block length, tile, exact
    (256, 4, 128, True), (512, 8, 256, True), (512, 4, 512, True),
    (1024, 16, 512, True), (192, 6, 128, False), (320, 4, 128, False),
    (384, 32, 128, True)]


@pytest.mark.parametrize("half, block, tile, exact", TILE_CASES)
@pytest.mark.parametrize("kernel", fa.KERNEL_NAMES)
def test_tiles_are_the_masks(kernel, half, block, tile, exact):
    """Every tile's kind against the boolean matrix: hidden iff no pair is
    visible, dense iff all are; the walk lists each other tile once (dk/dv:
    once a query head of the group), in the order its accumulator needs."""
    seq = -(-2 * half // 128) * 128
    n, group = seq // tile, 2
    tiles = fa._DiffusionTiles(kernel, half, block, seq != 2 * half, tile,
                               tile, n, n, group)
    assert tiles.exact == exact
    seen = np.zeros((seq, seq), bool)
    seen[:2 * half, :2 * half] = np.asarray(
        F.block_diffusion_mask(2 * half, block))
    run = []
    for iq in range(n):
        for ik in range(n):
            part = seen[iq * tile:(iq + 1) * tile, ik * tile:(ik + 1) * tile]
            kind = tiles.kind(iq, ik)
            assert (kind is None) == (not part.any()), (iq, ik)
            if kind is not None:
                run.append((iq, ik))
                if exact:
                    assert (kind == fa.DENSE) == bool(part.all()), (iq, ik)
                else:
                    assert kind == fa.MASKED
    assert sum(tiles.counts.values()) == len(run)
    table = [np.asarray(t) for t in tiles.tables]
    walked = list(zip(table[0].tolist(), table[1].tolist()))
    first, last = table[2] & 1, (table[2] >> 1) & 1
    if kernel == fa.BWD_DKV:
        assert sorted(walked) == sorted(run * group)
        owner, heads = table[1], table[3]
        assert sorted(set(heads.tolist())) == list(range(group))
    else:
        assert walked == run
        owner = table[0]
    # an accumulator's tiles are one run of steps, opened and closed once
    changes = np.flatnonzero(np.diff(owner)) + 1
    np.testing.assert_array_equal(np.flatnonzero(first),
                                  np.concatenate([[0], changes]))
    np.testing.assert_array_equal(np.flatnonzero(last), np.concatenate(
        [changes - 1, [len(owner) - 1]]))
    assert len(set(owner.tolist())) == n == len(changes) + 1


def test_the_cells_geometry_runs_80_tiles_of_256():
    """L = 4,096 in 512-blocks: 56 dense tiles and 24 on a diagonal, which
    the forward and dq kernels cut into strips and the dk/dv kernel, whose
    strips would be under 256 rows, computes whole under the mask. With
    1,024-blocks 24 of 64, and only the forward keeps its diagonals whole."""
    want = {512: {fa.FWD: (56, 24, 0), fa.BWD_DQ: (56, 24, 0),
                  fa.BWD_DKV: (56, 0, 24)},
            1024: {fa.FWD: (12, 0, 12), fa.BWD_DQ: (12, 12, 0),
                   fa.BWD_DKV: (12, 12, 0)}}
    for tile, by_kernel in want.items():
        n = 8192 // tile
        for kernel, kinds in by_kernel.items():
            tiles = fa._DiffusionTiles(kernel, 4096, 4, False, tile, tile, n,
                                       n, 8)
            assert tuple(tiles.counts[k] for k in fa.TILE_KINDS) == kinds
            assert tiles.steps == sum(kinds) * (
                8 if kernel == fa.BWD_DKV else 1)
    assert sum(want[512][fa.FWD]) == 80 and (8192 // 512) ** 2 == 256


def test_staging_counts_the_tiles(flash_staged):
    """``flash_tiles_staged_total{kernel, kind}`` of one staged call at the
    cell's geometry (traced, not run): 80 tiles a kernel."""
    q = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, block_diffusion=4, block_q=512, block_k=512,
            interpret=True).astype(jnp.float32))

    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)
    counter = flash_staged("flash_tiles_staged_total")
    got = {kernel: tuple(int(counter.value(kernel=kernel, kind=kind))
                         for kind in fa.TILE_KINDS)
           for kernel in fa.KERNEL_NAMES}
    assert got == {fa.FWD: (56, 24, 0), fa.BWD_DQ: (56, 24, 0),
                   fa.BWD_DKV: (56, 0, 24)}


# -- the kernels and the XLA path against plain softmax ----------------------

KERNEL_CASES = {  # half, block length, tile, heads, kv heads, head width
    "one_tile_a_half": (128, 4, 128, 2, 1, 128),
    "uncut_diagonals": (256, 8, 128, 4, 2, 64),
    "strips_fwd_dq": (512, 4, 512, 2, 1, 128),
    "strips_dq_dkv": (1024, 4, 1024, 1, 1, 128),
    "ragged": (24, 4, None, 2, 2, 64),
    "tile_straddles_the_halves": (192, 6, 128, 2, 1, 128),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_flash_kernels_under_the_mask(case):
    """Forward and the three gradients, interpreted, against plain softmax
    under the four rules' matrix, with grouped KV heads."""
    half, block, tile, heads, kv_heads, d = KERNEL_CASES[case]
    q, k, v, do = qkv(half, heads, kv_heads, d)
    seen = four_rules(half, block) if half <= 32 else np.asarray(
        F.block_diffusion_mask(2 * half, block))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_diffusion=block,
                                  block_q=tile, block_k=tile, interpret=True)

    def both(fn):
        out, grads = _staged.out_and_grads(fn, q, k, v, ct=do)
        return (out,) + grads

    got = both(flash)
    want = both(lambda q, k, v: plain_attention(q, k, v, seen))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5)


@pytest.mark.parametrize("half, block, heads, kv_heads", [
    (8, 2, 2, 2), (12, 4, 4, 2), (16, 4, 8, 1)])
def test_xla_path_under_the_mask(half, block, heads, kv_heads):
    q, k, v, do = qkv(half, heads, kv_heads, 16, batch=2)
    seen = four_rules(half, block)

    def both(fn):
        out, grads = _staged.out_and_grads(fn, q, k, v, ct=do)
        return (out,) + grads

    got = both(lambda q, k, v: _xla_attention(q, k, v, block_diffusion=block))
    sdpa = both(lambda q, k, v: F.scaled_dot_product_attention(
        q, k, v, block_diffusion=block))
    want = both(lambda q, k, v: plain_attention(q, k, v, seen))
    for a, b, c in zip(got, sdpa, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)
        np.testing.assert_allclose(np.asarray(b), np.asarray(c), atol=1e-5)


REFUSED = {
    "window": dict(is_causal=True, window=4),
    "is_causal": dict(is_causal=True),
    "kv_lens": dict(kv_lens=jnp.asarray([8], jnp.int32)),
    "dropout": dict(dropout_p=0.1),
    "attn_mask": dict(attn_mask=jnp.ones((16, 16), bool)),
    "odd_seq": dict(_seq=15),
    "keys_not_the_queries": dict(_keys=32),
    "half_not_whole_blocks": dict(_block=3),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_sdpa_refuses_what_the_mask_does_not_define(case):
    kw = dict(REFUSED[case])
    seq, keys = kw.pop("_seq", 16), kw.pop("_keys", None)
    block = kw.pop("_block", 4)
    q = jnp.ones((1, seq, 2, 16))
    k = jnp.ones((1, keys or seq, 2, 16))
    with pytest.raises(ValueError, match="block_diffusion|blocks of"):
        F.scaled_dot_product_attention(q, k, k, block_diffusion=block, **kw)


@pytest.mark.parametrize("kw", [
    dict(causal=True), dict(kv_lens=jnp.asarray([8], jnp.int32)),
    dict(dropout_rate=0.1), dict(block_diffusion=3)],
    ids=["causal", "kv_lens", "dropout", "half_not_whole_blocks"])
def test_flash_refuses_what_the_mask_does_not_define(kw):
    q = jnp.ones((1, 16, 2, 64))
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention(q, q, q, **dict(dict(block_diffusion=4), **kw),
                           interpret=True)


def test_the_tuner_reads_a_walked_calls_dims():
    """``dims_of_call`` and the tuner's lookup find q behind the tables."""
    from paddle_tpu.ops.pallas import tuner
    q = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, block_diffusion=4, block_q=256, block_k=256,
            interpret=True).astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    found = {}

    def visit(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                visit(sub)
    visit(jaxpr.jaxpr)
    assert set(found) == set(fa.KERNEL_NAMES)
    for name, eqn in found.items():
        assert fa.dims_of_call(eqn) == (128, 1024, 1024)
        key, _ = tuner.entry_for_traced_call(name, eqn)
        assert "bfloat16" in key and "d128,sk1024,sq1024" in key


# -- noise and loss ----------------------------------------------------------

def test_noise_is_a_function_of_tokens_and_key():
    tokens = jax.random.randint(jax.random.key(1), (4, 64), 0, 99)
    a = bd.noise(tokens, jax.random.key(7), 4, 99)
    b = bd.noise(tokens, jax.random.key(7), 4, 99)
    c = bd.noise(tokens, jax.random.key(8), 4, 99)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[1]), np.asarray(c[1]))
    noised, masked, t = (np.asarray(x) for x in a)
    assert (noised[masked] == 99).all()
    np.testing.assert_array_equal(noised[~masked], np.asarray(tokens)[~masked])
    # one level a block, inside [t_min, 1]
    blocks = t.reshape(4, 16, 4)
    assert (blocks == blocks[..., :1]).all()
    assert 1e-3 <= t.min() and t.max() <= 1.0 and len(np.unique(blocks)) > 32


@pytest.mark.parametrize("t_min", [1e-3, 0.5])
def test_masked_share_is_the_mean_level(t_min):
    """E[masked] = E[t] = (1 + t_min) / 2, within the sampling error of
    16,384 blocks (a block's share has variance under 1/3)."""
    tokens = jnp.zeros((16, 4096), jnp.int32)
    _, masked, t = bd.noise(tokens, jax.random.key(3), 4, 1, t_min)
    want = (1 + t_min) / 2
    assert abs(float(jnp.mean(t)) - want) < 4 * (1 / 12 / 16384) ** 0.5
    assert abs(float(jnp.mean(masked)) - want) < 4 * (1 / 3 / 16384) ** 0.5


def test_noise_needs_whole_blocks():
    with pytest.raises(ValueError, match="whole number of blocks"):
        bd.noise(jnp.zeros((1, 10), jnp.int32), jax.random.key(0), 4, 1)


def test_loss_is_the_weighted_masked_cross_entropy():
    key = jax.random.key(2)
    logits = jax.random.normal(key, (2, 8, 11))
    tokens = jax.random.randint(jax.random.key(3), (2, 8), 0, 11)
    masked = jax.random.bernoulli(jax.random.key(4), 0.5, (2, 8))
    t = jnp.repeat(jnp.asarray([[0.25, 0.5], [1.0, 0.125]]), 4, axis=1)
    logp = np.asarray(jax.nn.log_softmax(logits, -1), np.float64)
    want = 0.0
    for r in range(2):
        for i in range(8):
            if masked[r, i]:
                want -= logp[r, i, int(tokens[r, i])] / float(t[r, i])
    got = bd.loss(logits, tokens, masked, t)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(float(got), want / 16, rtol=1e-6)
    ids, positions = bd.model_inputs(jnp.where(masked, 10, tokens), tokens)
    assert ids.shape == (2, 16)
    np.testing.assert_array_equal(np.asarray(positions),
                                  np.tile(np.arange(8), 2))


# -- positions, QK norm, the model -------------------------------------------

def test_rotary_takes_positions():
    x = jax.random.normal(jax.random.key(0), (2, 12, 3, 16))
    inv_freq, scale = F.rope_frequencies(1e6, 16)
    plain = F.rotary_embedding(x, inv_freq, scale)
    np.testing.assert_array_equal(
        np.asarray(F.rotary_embedding(x, inv_freq, scale,
                                      positions=jnp.arange(12))),
        np.asarray(plain))
    twice = F.rotary_embedding(jnp.concatenate([x[:, :6], x[:, :6]], 1),
                               inv_freq, scale,
                               positions=jnp.tile(jnp.arange(6), 2))
    np.testing.assert_allclose(np.asarray(twice[:, 6:]),
                               np.asarray(plain[:, :6]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(twice[:, :6]),
                               np.asarray(plain[:, :6]), atol=1e-6)


ROPE = {"theta": 1e6, "rotary_dim": 16}


def test_qk_norm_is_off_by_default_and_a_norm_over_the_head_width():
    plain = GroupedQueryAttention(32, 4, 2, 16, ROPE)
    assert plain.q_norm is None and not any(
        "norm" in name for name, _ in plain.named_parameters())
    normed = GroupedQueryAttention(32, 4, 2, 16, ROPE, qk_norm_epsilon=1e-6)
    shapes = {name: tuple(p.value.shape)
              for name, p in normed.named_parameters()}
    assert shapes["q_norm.weight"] == shapes["k_norm.weight"] == (16,)
    for name, p in plain.named_parameters():
        dict(normed.named_parameters())[name].value = p.value
    x = jax.random.normal(jax.random.key(0), (1, 8, 32))
    # scaling q's and k's projections leaves a normed layer where it was
    before = normed(x)
    normed.q_proj.weight.value = normed.q_proj.weight.value * 3.0
    normed.k_proj.weight.value = normed.k_proj.weight.value * 0.5
    np.testing.assert_allclose(np.asarray(normed(x)), np.asarray(before),
                               atol=1e-5)
    assert not np.allclose(np.asarray(plain(x)), np.asarray(before),
                           atol=1e-3)


def tiny_model(**kw):
    return MixedDecoderForBlockDiffusion(
        block_length=4, vocab_size=64, hidden_size=32,
        layer_types=["full_attention"] * 2, heads_per_layer=[4, 4],
        mlp_layer_types=["sparse"] * 2, kv_heads=2, head_dim=8,
        rope={"full_attention": {"theta": 1e6, "rotary_dim": 8}},
        sliding_window=None, intermediate_size=64, num_experts=8,
        experts_per_token=2, expert_size=16, shared_expert_size=0,
        router_scoring="softmax", qk_norm=True, **kw)


@pytest.mark.parametrize("checkpoint", [False, True])
def test_model_draws_its_noise_from_the_steps_key(checkpoint):
    from paddle_tpu.framework.random import rng_guard
    model = tiny_model(checkpoint_blocks=checkpoint)
    assert model.mask_token_id == 63
    assert all(b.moe.shared_expert is None and b.moe.scoring == "softmax"
               for b in model.decoder.h)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 63)
    seen = []
    hook = model.lm_head.register_forward_post_hook(
        lambda layer, args, out: seen.append(args[0].shape))

    def loss(key):
        with rng_guard(key):
            return float(model(tokens))

    a, b, c = loss(jax.random.key(5)), loss(jax.random.key(5)), \
        loss(jax.random.key(6))
    hook.remove()
    assert a == b and a != c and np.isfinite(a)
    # the head meets the noised half alone: 16 of the 32 positions
    assert set(seen) == {(2, 16, 32)}
    assert 0.0 < float(model.masked_share) < 1.0


def test_model_publishes_the_masked_share():
    from paddle_tpu import telemetry
    from paddle_tpu.telemetry.metrics import Registry
    model = tiny_model()
    model(jax.random.randint(jax.random.key(1), (2, 16), 0, 63))
    prev, reg = telemetry.get_registry(), Registry()
    telemetry._set_registry(reg)
    try:
        model.publish_noise(step="last")
        gauge = reg.get("block_diffusion_masked_share")
        assert gauge.value(step="last") == pytest.approx(
            float(model.masked_share))
    finally:
        telemetry._set_registry(prev)


def test_positions_and_mask_reach_every_layer():
    """The trunk under the block-diffusion mask with explicit positions:
    the clean half's outputs do not depend on the noised half (no clean
    query sees a noised key), and a noised block's do not depend on later
    clean blocks."""
    model = tiny_model()
    decoder = model.decoder
    clean = jax.random.randint(jax.random.key(1), (1, 16), 0, 63)
    positions = jnp.tile(jnp.arange(16), 2)
    decode = _staged.forward(decoder, block_diffusion=4)  # one program

    def run(noised, clean):
        return decode(jnp.concatenate([noised, clean], 1), positions)

    a = run(jnp.full((1, 16), 63), clean)
    b = run(jax.random.randint(jax.random.key(2), (1, 16), 0, 63), clean)
    np.testing.assert_allclose(np.asarray(a[:, 16:]), np.asarray(b[:, 16:]),
                               atol=1e-6)
    assert not np.allclose(np.asarray(a[:, :16]), np.asarray(b[:, :16]))
    later = clean.at[:, 8:].set(0)
    c = run(jnp.full((1, 16), 63), later)
    # noised blocks 0-2 (positions 0-11) see clean blocks 0 and 1 at most
    np.testing.assert_allclose(np.asarray(a[:, :12]), np.asarray(c[:, :12]),
                               atol=1e-6)
    assert not np.allclose(np.asarray(a[:, 12:16]), np.asarray(c[:, 12:16]))


# -- the routers' learning-rate multiplier ------------------------------------

def test_trainer_honours_a_parameters_learning_rate_multiplier():
    """``ParamAttr(learning_rate=)`` reaches the staged update of
    ``ParallelTrainer`` as it reaches ``Optimizer.step``: a router at
    multiplier 0 stands still while the rest of the model moves, and at 0.1
    AdamW's first step moves it a tenth of what it moves the others."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.engine import ParallelTrainer

    before = mesh_mod.get_mesh()
    mesh = mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    try:
        moved = {}
        for scale in (0.0, 0.1, 1.0):
            model = tiny_model(router_attr=nn.ParamAttr(learning_rate=scale))
            start = {n: np.asarray(p.value)
                     for n, p in model.named_parameters()}
            opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters(),
                                         weight_decay=0.0)
            trainer = ParallelTrainer(model, opt, lambda out, _: out,
                                      mesh=mesh)
            assert (trainer.lr_scales is None) == (scale == 1.0)
            tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 63)
            assert np.isfinite(float(trainer.train_step(tokens, 0.0)))
            after = trainer.state["params"]
            moved[scale] = {n: float(np.abs(np.asarray(after[n])
                                            - start[n]).max())
                            for n in start}
        router, other = "decoder.h.0.moe.router.weight", "lm_head.weight"
        assert moved[0.0][router] == 0.0 and moved[0.0][other] > 5e-4
        assert moved[0.1][router] == pytest.approx(
            0.1 * moved[1.0][router], rel=1e-3)
        assert moved[0.1][other] == pytest.approx(moved[1.0][other])
    finally:
        mesh_mod.set_mesh(before)
