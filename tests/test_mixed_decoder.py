"""ISSUE 27: window and grouped-head attention, the two RoPEs, RMSNorm, the
dropless expert layer and the decoder built from them, at small sizes on
the CPU with seeded weights. The float32 reference of the benchmark
(``benchmark/reference/laguna.py``) is what the model is held to."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import nn, telemetry
from paddle_tpu.incubate.moe import DroplessMoELayer, route_top_k
from paddle_tpu.jit.functionalization import functional_call, state_of
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional.attention import _xla_attention
from paddle_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(seed, b, s, h, h_kv, d=64):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, d)),
            jax.random.normal(ks[1], (b, s, h_kv, d)),
            jax.random.normal(ks[2], (b, s, h_kv, d)),
            jax.random.normal(ks[3], (b, s, h, d)))


def _explicit_attention(q, k, v, window):
    """Softmax attention under an explicit [S, S] mask, K and V repeated
    for the query heads that share them."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = (i >= j) & (i - j < window if window else True)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


# -- attention ----------------------------------------------------------------

@pytest.mark.parametrize("h, h_kv, window", [
    (4, 4, None), (4, 2, None), (6, 2, 70), (4, 1, 128), (2, 2, 1)])
def test_xla_attention_window_and_grouped_heads(h, h_kv, window):
    q, k, v, _ = _qkv(0, 2, 192, h, h_kv, d=16)
    got = _xla_attention(q, k, v, causal=True, window=window)
    want = _explicit_attention(q, k, v, window)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s, h, h_kv, window, bq, bk", [
    (512, 4, 2, None, 128, 128),     # grouped heads alone
    (512, 4, 2, 128, 128, 128),      # the window ends on a block's edge
    (512, 4, 1, 100, 128, 256),      # it cuts through a block; bq != bk
    (512, 2, 2, 200, 256, 128),      # no groups, a window over two blocks
    (300, 6, 2, 70, 128, 128),       # a ragged tail under a window
], ids=lambda x: str(x))
def test_flash_window_grouped_heads_match_xla(s, h, h_kv, window, bq, bk):
    """Forward and the three gradients of the kernels (interpret mode)
    against ``_xla_attention`` under an explicit mask."""
    q, k, v, do = _qkv(1, 2, s, h, h_kv)

    def flash(q, k, v):
        return jnp.sum(do * flash_attention(
            q, k, v, causal=True, window=window, block_q=bq, block_k=bk,
            interpret=True))

    def xla(q, k, v):
        return jnp.sum(do * _xla_attention(q, k, v, causal=True,
                                           window=window))

    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, window=window, block_q=bq,
                        block_k=bk, interpret=True),
        _explicit_attention(q, k, v, window), rtol=2e-5, atol=2e-5)
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_window_band_walks_only_the_blocks_it_needs():
    from paddle_tpu.ops.pallas.flash_attention import _Band
    band = _Band(512, 512, 512, nq=8, nk=8)      # the cell's sliding layers
    assert (band.k_steps, band.q_steps) == (2, 2)
    assert [band.k_first(i) for i in range(8)] == [0, 0, 1, 2, 3, 4, 5, 6]
    assert [band.q_last(j) for j in range(8)] == [1, 2, 3, 4, 5, 6, 7, 7]
    band = _Band(100, 128, 256, nq=4, nk=2)      # a window inside a block
    assert [(band.k_first(i), band.k_last(i)) for i in range(4)] == \
        [(0, 0), (0, 0), (0, 1), (1, 1)]


def test_window_needs_causal_and_heads_must_divide():
    q, k, v, _ = _qkv(2, 1, 128, 4, 4)
    with pytest.raises(ValueError, match="causal"):
        F.scaled_dot_product_attention(q, k, v, window=16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=16, interpret=True)
    with pytest.raises(ValueError, match="cannot share"):
        flash_attention(q, k[:, :, :3], v[:, :, :3], interpret=True)


def test_sdpa_takes_window_and_grouped_heads():
    q, k, v, _ = _qkv(3, 1, 96, 4, 2, d=16)
    got = F.scaled_dot_product_attention(q, k, v, is_causal=True, window=20)
    np.testing.assert_allclose(got, _explicit_attention(q, k, v, 20),
                               rtol=1e-5, atol=1e-5)


# -- RoPE, RMSNorm, the gated FFN ---------------------------------------------

def test_rope_plain_hand_values():
    """theta 10,000 over all 4 lanes: pairs (0, 2) and (1, 3) turn by
    ``pos`` and ``pos / 100``."""
    inv_freq, scale = F.rope_frequencies(10000.0, 4)
    np.testing.assert_allclose(inv_freq, [1.0, 0.01])
    assert scale == 1.0
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(1, 1, 1, 4)
    x = jnp.tile(x, (1, 3, 1, 1))
    got = np.asarray(F.rotary_embedding(x, inv_freq))[0, :, 0]
    for pos in range(3):
        a, b = float(pos), pos / 100.0
        want = [1 * math.cos(a) - 3 * math.sin(a),
                2 * math.cos(b) - 4 * math.sin(b),
                3 * math.cos(a) + 1 * math.sin(a),
                4 * math.cos(b) + 2 * math.sin(b)]
        np.testing.assert_allclose(got[pos], want, rtol=1e-6, atol=1e-6)


def test_rope_yarn_partial_hand_values():
    """The published full-attention group at head width 128: 64 lanes
    rotate, pairs 0-5 keep their frequency, pairs 16-31 have it divided by
    64, a ramp between; cos and sin carry the attention factor; lanes 64
    on pass through."""
    yarn = dict(factor=64, original_max_position_embeddings=4096,
                beta_fast=64, beta_slow=1,
                attention_factor=1.4158883083359672)
    inv_freq, scale = F.rope_frequencies(500000.0, 64, yarn)
    plain = 500000.0 ** (-np.arange(32) / 32)
    # floor(64 ln(4096 / (64 * 2 pi)) / (2 ln 5e5)) = 5, ceil(.. beta 1) = 16
    np.testing.assert_allclose(inv_freq[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[16:], plain[16:] / 64, rtol=1e-6)
    ramp = (10 - 5) / (16 - 5)
    np.testing.assert_allclose(
        inv_freq[10], plain[10] / 64 * ramp + plain[10] * (1 - ramp),
        rtol=1e-6)
    assert scale == pytest.approx(0.1 * math.log(64) + 1, rel=1e-6)
    assert F.rope_frequencies(500000.0, 64, dict(yarn, attention_factor=None)
                              )[1] == pytest.approx(scale, rel=1e-6)
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 128))
    got = np.asarray(F.rotary_embedding(x, inv_freq, scale))
    np.testing.assert_array_equal(got[..., 64:], np.asarray(x)[..., 64:])
    pos, lane = 3, 7
    a = pos * inv_freq[lane]
    want = scale * (x[0, pos, 1, lane] * math.cos(a)
                    - x[0, pos, 1, lane + 32] * math.sin(a))
    assert got[0, pos, 1, lane] == pytest.approx(float(want), rel=1e-5)
    # position 0 turns nothing: the rotated lanes are scaled only
    np.testing.assert_allclose(got[0, 0, :, :64],
                               scale * np.asarray(x)[0, 0, :, :64], rtol=1e-6)


def test_rms_norm_and_gated_ffn():
    x = jax.random.normal(jax.random.key(1), (2, 3, 8))
    norm = nn.RMSNorm(8, epsilon=1e-6)
    norm.weight.value = jnp.arange(1.0, 9.0)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6) \
        * np.arange(1.0, 9.0)
    np.testing.assert_allclose(norm(x), want, rtol=1e-5)
    ffn = nn.GatedSiluFFN(8, 16)
    g, u, d = (ffn.gate_proj.weight.value, ffn.up_proj.weight.value,
               ffn.down_proj.weight.value)
    np.testing.assert_allclose(
        ffn(x), (jax.nn.silu(x @ g) * (x @ u)) @ d, rtol=1e-5, atol=1e-6)
    assert [n for n, _ in ffn.named_parameters()] == [
        "gate_proj.weight", "up_proj.weight", "down_proj.weight"]


# -- the dropless expert layer -------------------------------------------------

def _dense_moe(layer, x, p=None):
    """The layer's equation as a dense loop over the held experts, a pure
    function of the parameters ``p`` (the layer's own by default)."""
    p = dict(state_of(layer)[0]) if p is None else p
    tokens = jnp.reshape(x, (-1, x.shape[-1]))
    ids, weights = route_top_k(
        tokens @ p["router.weight"], layer.top_k, layer.scoring,
        layer.routed_scaling_factor)
    out = jnp.zeros_like(tokens)
    if layer.shared_expert is not None:
        out = (jax.nn.silu(tokens @ p["shared_expert.gate_proj.weight"])
               * (tokens @ p["shared_expert.up_proj.weight"])) \
            @ p["shared_expert.down_proj.weight"]
    for j in range(layer.count):
        w = jnp.sum(jnp.where(ids == layer.first + j, weights, 0.0), -1)
        h = jax.nn.silu(tokens @ p["experts.gate_proj"][j]) \
            * (tokens @ p["experts.up_proj"][j])
        out = out + w[:, None] * (h @ p["experts.down_proj"][j])
    return jnp.reshape(out, x.shape)


def test_route_top_k_scores_and_normalises():
    logits = jnp.asarray([[0.0, 2.0, -1.0, 1.0]])
    ids, w = route_top_k(logits, 2, "sigmoid", scaling_factor=2.5)
    assert ids.tolist() == [[1, 3]] and ids.dtype == jnp.int32
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0])))
    np.testing.assert_allclose(w[0], 2.5 * s / s.sum(), rtol=1e-6)
    _, w = route_top_k(logits, 2, "softmax")
    p = np.exp([2.0, 1.0])          # the softmax's own sum cancels
    np.testing.assert_allclose(w[0], p / p.sum(), rtol=1e-6)
    assert w.dtype == jnp.float32
    assert route_top_k(logits.astype(jnp.bfloat16), 2)[1].dtype == jnp.float32
    with pytest.raises(ValueError, match="scoring"):
        route_top_k(logits, 2, "tanh")


@pytest.mark.parametrize("scoring, score", [
    ("sigmoid", lambda z: 1 / (1 + np.exp(-z))),
    ("softmax", lambda z: np.exp(z) / np.exp(z).sum(-1, keepdims=True))])
def test_the_layer_routes_by_its_scoring_rule(scoring, score):
    """The two rules choose differently where a logit's rank and its share
    of the row part ways; each layer follows its own, by hand values."""
    layer = DroplessMoELayer(8, 4, 6, 2, scoring=scoring,
                             routed_scaling_factor=2.5)
    tokens = jax.random.normal(jax.random.key(5), (10, 8))
    ids, weights = layer.route(tokens)
    s = score(np.asarray(tokens, np.float64)
              @ np.asarray(layer.router.weight.value, np.float64))
    want = np.argsort(-s, axis=-1)[:, :2]
    np.testing.assert_array_equal(ids, want)
    top = np.take_along_axis(s, want, axis=-1)
    np.testing.assert_allclose(
        weights, 2.5 * top / top.sum(-1, keepdims=True), rtol=1e-5)
    x = jnp.reshape(tokens, (2, 5, 8))
    np.testing.assert_allclose(layer(x), _dense_moe(layer, x), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("tile", [512, 8], ids=["one_part", "two_parts"])
@pytest.mark.parametrize("held", [None, (8, 8), (0, 3)])
def test_dropless_layer_equals_the_dense_loop(held, tile, monkeypatch):
    """Forward and every gradient; with a tile of 8 rows the buffer is more
    than its first chunk, so the conditional second part is staged too."""
    from paddle_tpu.incubate import moe
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    layer = DroplessMoELayer(16, 8, 32, 4, held=held,
                             routed_scaling_factor=2.5, d_shared=8)
    two_parts = layer.buffer_rows(48) > layer.chunk_rows(48)
    assert two_parts == (tile == 8 and held is not None)
    x = jax.random.normal(jax.random.key(0), (2, 24, 16))
    np.testing.assert_allclose(layer(x), _dense_moe(layer, x), rtol=1e-5,
                               atol=1e-5)
    params, buffers = state_of(layer)
    grad = jax.grad(lambda p: jnp.sum(functional_call(
        layer, p, buffers, x)[0] ** 2))(dict(params))
    want = jax.grad(lambda p: jnp.sum(_dense_moe(layer, x, p) ** 2))(
        dict(params))
    assert set(grad) == set(want) and len(grad) == 7
    for name in want:
        np.testing.assert_allclose(grad[name], want[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)


@pytest.mark.parametrize("tile", [512, 8], ids=["one_part", "two_parts"])
def test_no_assignment_is_dropped_when_every_token_goes_to_one_expert(
        tile, monkeypatch):
    """The worst imbalance: a router that sends every token to the same
    held experts. Every assignment is computed, none cut: with a tile of 8
    rows the first chunk holds 240 of the 480, the rest of the buffer the
    others, four times the expected load."""
    from paddle_tpu.incubate import moe
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    layer = DroplessMoELayer(16, 8, 32, 4, held=(4, 8), d_shared=None)
    assert layer.chunk_rows(120) == (240 if tile == 8 else 480)
    w = np.zeros((16, 32), np.float32)
    w[:, [4, 5, 6, 7]] = 5.0            # all four choices held here
    layer.router.weight.value = jnp.asarray(w)
    x = jnp.abs(jax.random.normal(jax.random.key(2), (3, 40, 16))) + 0.1
    out = layer(x)
    assert int(layer.tokens_routed) == 120
    assert int(layer.held_assignments) == 480 == layer.buffer_rows(120)
    assert float(layer.max_load_over_mean) == pytest.approx(8 * 120 / 480)
    np.testing.assert_allclose(out, _dense_moe(layer, x), rtol=1e-5,
                               atol=1e-5)
    w[:, [5, 6, 7]] = 0.0
    w[:, 4] = 50.0                      # one held expert takes every token
    layer.router.weight.value = jnp.asarray(w)
    out = layer(x)
    ids, _ = layer.route(jnp.reshape(x, (-1, 16)))
    assert bool(jnp.all(jnp.any(ids == 4, axis=-1)))
    assert int(layer.held_assignments) >= 120
    assert float(layer.max_load_over_mean) >= 8 * 120 / 480
    np.testing.assert_allclose(out, _dense_moe(layer, x), rtol=1e-5,
                               atol=1e-5)


def test_rows_of_no_group_may_hold_anything(monkeypatch):
    """On the TPU the grouped-product kernels do not write the rows past
    the last assignment, in the result or in its gradient. With NaN put
    there on both ways, the layer's output and every gradient are still
    the dense loop's."""
    from paddle_tpu.incubate import moe
    monkeypatch.setattr(moe, "ROW_TILE", 8)

    @jax.custom_vjp
    def taint(rows, keep):
        return rows
    taint.defvjp(lambda rows, keep: (rows, keep),
                 lambda keep, g: (jnp.where(keep > 0, g, jnp.nan),
                                  jnp.zeros_like(keep)))

    layer = DroplessMoELayer(16, 8, 32, 4, held=(8, 8),
                             routed_scaling_factor=2.5, d_shared=8)
    real = layer.experts.forward

    def unwritten(rows, sizes):
        keep = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
        out = real(taint(rows, keep.astype(rows.dtype)), sizes)
        return jnp.where(keep, out, jnp.nan)

    object.__setattr__(layer.experts, "forward", unwritten)
    x = jax.random.normal(jax.random.key(0), (2, 24, 16))
    assert layer.buffer_rows(48) > layer.chunk_rows(48) > 48 * 4 * 8 / 32
    got = layer(x)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, _dense_moe(layer, x), rtol=1e-5,
                               atol=1e-5)
    params, buffers = state_of(layer)
    grad = jax.grad(lambda p: jnp.sum(functional_call(
        layer, p, buffers, x)[0] ** 2))(dict(params))
    want = jax.grad(lambda p: jnp.sum(_dense_moe(layer, x, p) ** 2))(
        dict(params))
    for name in want:
        assert bool(jnp.all(jnp.isfinite(grad[name]))), name
        np.testing.assert_allclose(grad[name], want[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)


def test_the_router_scores_in_float32():
    layer = DroplessMoELayer(16, 8, 32, 4, held=(0, 8), d_shared=8)
    layer.astype("bfloat16")
    x = jax.random.normal(jax.random.key(3), (1, 8, 16), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda t: layer.route(t))(
        jnp.reshape(x, (-1, 16)))
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 1
    assert dots[0].params["preferred_element_type"] == jnp.float32
    assert all(v.aval.dtype == jnp.float32 for e in jaxpr.jaxpr.eqns
               if e.primitive.name in ("logistic", "top_k")
               for v in e.invars)


def test_shares_add_up_to_the_whole_layer():
    """The guide's share test: eight layers holding experts 0-31, 32-63,
    ... of 256, given the same weights as the uncut reference, add up,
    with the shared expert counted once, to the reference's whole layer."""
    from benchmark.reference import laguna as reference

    d, f, experts, k = 32, 16, 256, 8
    ks = jax.random.split(jax.random.key(4), 8)
    p = {"router_w": jax.random.normal(ks[0], (d, experts)) * 0.3,
         "shared_gate_w": jax.random.normal(ks[1], (d, f)) * 0.2,
         "shared_up_w": jax.random.normal(ks[2], (d, f)) * 0.2,
         "shared_down_w": jax.random.normal(ks[3], (f, d)) * 0.2,
         "experts_gate_w": jax.random.normal(ks[4], (experts, d, f)) * 0.2,
         "experts_up_w": jax.random.normal(ks[5], (experts, d, f)) * 0.2,
         "experts_down_w": jax.random.normal(ks[6], (experts, f, d)) * 0.2}
    u = jax.random.normal(ks[7], (2, 48, d))
    arch = {"top_k": k, "routed_scaling_factor": 2.5, "held": (0, experts)}
    whole, _ = reference.sparse_ffn(u, p, arch)

    total = 0.0
    for share in range(8):
        first = 32 * share
        layer = DroplessMoELayer(d, f, experts, k, held=(first, 32),
                                 routed_scaling_factor=2.5, d_shared=f)
        layer.router.weight.value = p["router_w"]
        for name in ("gate", "up", "down"):
            getattr(layer.shared_expert, f"{name}_proj").weight.value = \
                p[f"shared_{name}_w"]
            getattr(layer.experts, f"{name}_proj").value = \
                p[f"experts_{name}_w"][first:first + 32]
        # the reference given the same share computes the same part
        part, _ = reference.sparse_ffn(
            u, dict(p, **{f"experts_{n}_w": p[f"experts_{n}_w"][
                first:first + 32] for n in ("gate", "up", "down")}),
            dict(arch, held=(first, 32)))
        got = layer(u)
        np.testing.assert_allclose(got, part, rtol=1e-4, atol=1e-5)
        total = total + got
    shared = reference.gated_ffn(u, p["shared_gate_w"], p["shared_up_w"],
                                 p["shared_down_w"])
    np.testing.assert_allclose(total - 7 * shared, whole, rtol=1e-4,
                               atol=1e-5)


def test_routing_counters_reach_telemetry():
    layer = DroplessMoELayer(16, 8, 32, 4, held=(8, 8), d_shared=8)
    x = jax.random.normal(jax.random.key(5), (2, 10, 16))
    params, buffers = state_of(layer)
    _, new = jax.jit(lambda p, b, t: functional_call(layer, p, b, t))(
        dict(params), dict(buffers), x)
    ids, _ = layer.route(jnp.reshape(x, (-1, 16)))
    held = int(jnp.sum((ids >= 8) & (ids < 16)))
    assert int(new["tokens_routed"]) == 20
    assert int(new["held_assignments"]) == held
    loads = np.bincount(np.asarray(ids).ravel(), minlength=32)[8:16]
    assert float(new["max_load_over_mean"]) == pytest.approx(
        loads.max() * 8 / max(held, 1))
    # nothing of the jitted call stayed in the layer
    assert int(layer.tokens_routed) == 0
    before = telemetry.get_registry()
    telemetry._set_registry(telemetry.Registry())
    try:
        layer.publish_routing(new, layer="h.1")
        layer.publish_routing(new, layer="h.1")
        got = telemetry.get_registry().to_dict()
    finally:
        telemetry._set_registry(before)
    assert list(got["moe_tokens_routed_total"]["series"].values()) == [40]
    assert list(got["moe_held_assignments_total"]["series"].values()) == \
        [2 * held]
    assert list(got["moe_max_load_over_mean"]["series"].values()) == [
        pytest.approx(loads.max() * 8 / max(held, 1))]


# -- the decoder ---------------------------------------------------------------

def _toy():
    from benchmark import manifest
    from benchmark.families import laguna
    return laguna, laguna.toy(manifest.Manifest().config("laguna-xs2"))


@pytest.fixture
def one_device_mesh():
    from paddle_tpu.distributed import mesh as mesh_mod
    before = mesh_mod.get_mesh()
    yield mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    mesh_mod.set_mesh(before)


def test_decoder_names_scopes_and_layer_kinds(one_device_mesh):
    laguna, config = _toy()
    built = laguna.build(config, dict(config["run"], param_dtype="float32"),
                         seed=0, mesh=one_device_mesh)
    model = built.model
    blocks = list(model.decoder.h)
    assert [("moe" in b._sub_layers, b.attn.num_heads, b.attn.window)
            for b in blocks] == [(False, 6, None), (True, 8, 32),
                                 (True, 8, 32)]
    assert blocks[0].attn.inv_freq.shape == (4,)       # half of 16 lanes
    assert blocks[1].attn.inv_freq.shape == (8,)       # all of them
    assert blocks[0].attn.rope_scale == pytest.approx(1.4158883083359672)
    assert (blocks[1].moe.first, blocks[1].moe.count,
            blocks[1].moe.num_experts, blocks[1].moe.top_k) == (4, 4, 16, 2)
    text = str(jax.make_jaxpr(
        lambda ids: functional_call(
            model, dict(state_of(model)[0]), {}, ids)[0])(
                jnp.zeros((1, 64), jnp.int32)).pretty_print(
                    name_stack=True))
    for scope in ("mixeddecoderforpretraining", "decoder", "h.1", "attn",
                  "rope", "sdpa", "moe", "router", "dispatch", "experts",
                  "combine", "shared_expert", "mlp", "lm_head"):
        assert scope in text, scope


@pytest.mark.parametrize("std", [1.0, 0.25])
def test_embedding_starts_at_the_scale_the_recipe_gives(one_device_mesh, std):
    """N(0, ``run.embedding_std``): the configuration's 1.0 is the scale the
    blocks write at. Under ``nn.Embedding``'s Xavier default (0.09 at the
    toy's sizes, 0.012 at the cell's) a row's tokens reach a fresh router
    nearly alike and the held experts' load goes by the seed."""
    laguna, config = _toy()
    assert config["run"]["embedding_std"] == 1.0
    built = laguna.build(
        config, dict(config["run"], param_dtype="float32", embedding_std=std),
        seed=3, mesh=one_device_mesh)
    table = built.model.decoder.embed_tokens.weight.value
    assert table.shape == (512, 64)
    assert float(jnp.std(table)) == pytest.approx(std, rel=0.02)
    assert abs(float(jnp.mean(table))) < 0.02 * std


def test_the_model_alone_keeps_the_embeddings_default():
    from paddle_tpu.text.models import MixedDecoderModel
    rope = {"full_attention": {"theta": 1e4, "rotary_dim": 8}}
    model = MixedDecoderModel(
        vocab_size=512, hidden_size=64, layer_types=["full_attention"],
        heads_per_layer=[2], mlp_layer_types=["dense"], kv_heads=1,
        head_dim=8, rope=rope, sliding_window=None, intermediate_size=32)
    default = nn.Embedding(512, 64).weight.value
    assert float(jnp.std(model.embed_tokens.weight.value)) == pytest.approx(
        float(jnp.std(default)), rel=0.1)


def test_decoder_trains_through_the_trainer(one_device_mesh):
    from benchmark import traffic_gen
    laguna, config = _toy()
    built = laguna.build(config, dict(config["run"], param_dtype="float32"),
                         seed=1, mesh=one_device_mesh)
    mix = dict(seq=64, pool_batches=1, zipf_exponent=1.1,
               follow_probability=0.5, doc_length_median=12,
               doc_length_sigma=1.0, doc_length_min=2)
    ids, labels = traffic_gen.make_pool(mix, config["vocab_used"],
                                        config["eos_token_id"], 2, seed=2)
    losses = [float(built.trainer.train_step(ids[0], labels[0]))
              for _ in range(4)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
