"""The grouped-product kernels (``ops/pallas/grouped_matmul.py``) in
interpret mode against ``lax.ragged_dot``, their schedule, the gate that
sends a layer's products to them, and the counter of that choice."""
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import _staged
from paddle_tpu import telemetry
from paddle_tpu.framework.random import rng_guard
from paddle_tpu.incubate import moe
from paddle_tpu.incubate.moe import DroplessMoELayer, GroupedExperts
from paddle_tpu.jit.functionalization import functional_call
from paddle_tpu.ops.pallas import grouped_matmul as gm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _grads(product, x, w, sizes, ct):
    """The product, and the gradients of ``sum(y * ct)`` over the held
    rows in ``x`` and ``w``; the rows past the last group masked on the
    way out, as the expert layer masks them."""
    held = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
    y, (dx, dw) = _staged.out_and_grads(
        lambda x, w: jnp.where(held, product(x, w), 0), x, w, ct=ct)
    return y, jnp.where(held, dx, 0), dw


CASES = {
    "whole_tiles": ((16, 16, 16, 16), 64, 256, 384, 16),
    # an empty group, boundaries inside tiles, rows past the last group
    "empty_group_and_boundaries": ((5, 0, 30, 13), 64, 256, 384, 16),
    "one_group": ((0, 0, 40, 0), 48, 128, 256, 16),
    # widths no 128 divides, taken whole (1,856 / 8 and twice that)
    "widths_not_of_128": ((7, 9, 3, 1), 32, 232, 464, 16),
    "nothing_held": ((0, 0, 0, 0), 32, 128, 128, 16),
}


@pytest.mark.parametrize("case", [(n, t) for n in CASES for t in (0, 1)] + [
    ("ragged_rows", 0)], ids=lambda c: f"{c[0]}_{'nt' if c[1] else 'nn'}")
def test_kernels_match_ragged_dot(case):
    """``gmm`` (forward and, transposed, the rows' gradient) and ``tgmm``
    (the weights') against ``lax.ragged_dot`` and its VJP, float32. The
    rows past the last group hold NaN on the way in and the cotangent
    holds NaN there on the way back: neither reaches a held row or a
    weight's gradient."""
    name, transpose = case
    # rows no whole number of tiles: the wrapper pads them
    sizes, rows, k, n, tm = CASES.get(name, ((3, 20, 10, 2), 50, 256, 232, 8))
    ks = jax.random.split(jax.random.key(rows + k + n), 3)
    sizes = jnp.asarray(sizes, jnp.int32)
    held = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    x = jnp.where(held, jax.random.normal(ks[0], (rows, k)), jnp.nan)
    groups = sizes.shape[0]
    w = jax.random.normal(ks[1], (groups, n, k) if transpose else
                          (groups, k, n))
    ct = jax.random.normal(ks[2], (rows, n))

    def kernel(x, w):
        if transpose:       # the rows' gradient's form of the product
            return gm._gmm(x, w, sizes, tm, True, True)
        return gm.grouped_matmul(x, w, sizes, tm=tm, interpret=True)

    def reference(x, w):
        return lax.ragged_dot(x, jnp.swapaxes(w, 1, 2) if transpose else w,
                              sizes, precision=lax.Precision.HIGHEST)

    x0 = jnp.where(held, x, 0)
    want = _grads(reference, x0, w, sizes, ct)
    if transpose:
        got = jnp.where(held, kernel(x, w), 0)
        np.testing.assert_allclose(got, want[0], rtol=1e-5, atol=1e-4)
        return
    got = _grads(kernel, x, w, sizes, ct)
    for name, a, b in zip(("y", "dx", "dw"), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4, err_msg=name)


def test_tgmm_writes_an_empty_group_as_zeros():
    sizes = jnp.asarray([0, 24, 0, 8], jnp.int32)
    x = jax.random.normal(jax.random.key(0), (32, 128))
    dy = jax.random.normal(jax.random.key(1), (32, 256))
    dw = gm._tgmm(x, dy, sizes, 16, True)
    assert dw.shape == (4, 128, 256)
    assert not bool(jnp.any(dw[0])) and not bool(jnp.any(dw[2]))
    np.testing.assert_allclose(dw[1], x[:24].T @ dy[:24], rtol=1e-5,
                               atol=1e-4)


def test_schedule_walks_each_groups_tiles_once():
    """Step ``i`` works on ``group[i]`` in row tile ``tile[i]``: a tile
    two groups share is visited once by each, in order; an empty group has
    a step only where ``tgmm`` asks for one."""
    sizes = jnp.asarray([5, 0, 30, 13], jnp.int32)
    (offsets, group, tile), steps = gm.schedule(sizes, 64, 16, False)
    assert offsets.tolist() == [0, 5, 5, 35, 48]
    assert int(steps) == 5
    assert list(zip(group.tolist(), tile.tolist()))[:5] == [
        (0, 0), (2, 0), (2, 1), (2, 2), (3, 2)]
    (_, group, tile), steps = gm.schedule(sizes, 64, 16, True)
    assert int(steps) == 6
    assert list(zip(group.tolist(), tile.tolist()))[:6] == [
        (0, 0), (1, 0), (2, 0), (2, 1), (2, 2), (3, 2)]


def test_tiles_the_cells_widths_allow():
    """At the Nemotron cell's widths every kernel takes a whole width no
    128 divides and as much of the other as VMEM holds: one grid step a
    row tile in the products, three in the weights' gradients, where the
    compiler's kernels take 21 x 15."""
    d, e, tm = 2688, 1856, gm.ROW_TILE
    assert gm.tiling(gm.GMM, tm, d, e, 2) == (d, e)
    assert gm.tiling(gm.GMM, tm, e, d, 2) == (e, d)
    assert gm.tiling(gm.TGMM, tm, d, e, 2) == (896, e)
    assert gm.tiling(gm.TGMM, tm, e, d, 2) == (e, 896)
    for kind, k, n in ((gm.GMM, d, e), (gm.GMM, e, d), (gm.TGMM, d, e)):
        assert gm._vmem(kind, tm, *gm.tiling(kind, tm, k, n, 2), 2) \
            <= gm.VMEM_BUDGET < gm.VMEM_LIMIT
    assert gm.compiler_tile(d) == 128 and gm.compiler_tile(e) == 0


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# the five sparse configurations and their experts' activation
SPARSE = {"laguna-xs2": "silu", "sdar-30b-a3b-chat": "silu",
          "qwen3-next-80b-a3b-instruct": "silu", "glm-4.7-flash": "silu",
          "nemotron-3-nano-30b-a3b": "relu2"}


@pytest.mark.parametrize("name", sorted(SPARSE))
def test_each_sparse_configuration_takes_its_path(name):
    """On a TPU (the backend faked: the layer is traced, nothing runs) the
    expert layer of each sparse configuration, at its published widths in
    bf16 over a step's 16,384 tokens, stages its grouped products on the
    kernels where the compiler's would fall to 128-wide tiles (Nemotron's
    2,688 / 1,856) and on ``lax.ragged_dot`` elsewhere (2,048 with 512,
    768 or 1,536: 512 x 512 or 512 x 256): two staged calls, the part of
    the buffer that always runs and the rest, counted by path."""
    config = _config(name)
    activation = SPARSE[name]
    d_model, d_expert = config["hidden_size"], config["moe_intermediate_size"]
    # the experts a chip holds, of a router sixteen times as wide
    held = config.get("num_experts", config.get("n_routed_experts"))
    want = "pallas" if name.startswith("nemotron") else "xla"
    assert moe.grouped_path(d_model, d_expert, jnp.bfloat16) == "xla"
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert moe.grouped_path(d_model, d_expert, jnp.bfloat16) == want
        assert moe.grouped_path(d_model, d_expert, jnp.float32) == "xla"

        def step(key, x):
            # made inside the trace: no weight exists at full size
            with rng_guard(key):
                layer = DroplessMoELayer(d_model, d_expert, held * 16,
                                         config["num_experts_per_tok"],
                                         held=(0, held),
                                         activation=activation)
            return layer(x)

        with telemetry.scope(profile=False) as tel:
            text = str(jax.make_jaxpr(step)(jax.random.key(0),
                                            jax.ShapeDtypeStruct(
                                                (2, 8192, d_model),
                                                jnp.bfloat16)))
            calls = tel.registry.get("moe_grouped_calls_staged_total")
            assert calls.value(path=want) == 2
            assert calls.value(path="xla" if want == "pallas" else
                               "pallas") == 0
    products = 2 if activation == "relu2" else 3
    if want == "pallas":
        # a forward product a call, each one staged gmm
        assert text.count("name=gmm") == 2 * products
        assert "ragged_dot" not in text
    else:
        assert text.count("ragged_dot") >= 2 * products
        assert "pallas_call" not in text


@pytest.mark.parametrize("activation", ["relu2", "silu"])
def test_grouped_experts_on_the_kernels_match_ragged_dot(activation):
    """An expert layer at toy widths no 128 divides (the gate's other
    case), bf16, its products on the interpreted kernels against the same
    layer on ``lax.ragged_dot``: output and every gradient, with rows past
    the last group masked by the caller."""
    layer = GroupedExperts(3, 96, 232, activation)
    params = {n: p.value for n, p in layer.named_parameters()}
    ks = jax.random.split(jax.random.key(1), 2)
    sizes = jnp.asarray([200, 0, 170], jnp.int32)
    held = (jnp.arange(512) < 370)[:, None]
    rows = jnp.where(held, jax.random.normal(ks[0], (512, 96)), 0).astype(
        jnp.bfloat16)
    ct = jax.random.normal(ks[1], (512, 96))

    def run(interpret):
        layer.interpret = interpret
        with telemetry.scope(profile=False) as tel:
            out = _staged.out_and_grads(lambda p, rows: jnp.where(
                held, functional_call(layer, p, {}, rows, sizes)[0], 0),
                params, rows, ct=ct)
            path = "pallas" if interpret else "xla"
            assert tel.registry.get("moe_grouped_calls_staged_total").value(
                path=path) == 1
        return out

    got_y, (got_p, got_x) = run(True)
    want_y, (want_p, want_x) = run(False)
    np.testing.assert_allclose(got_y.astype(jnp.float32),
                               want_y.astype(jnp.float32), rtol=2e-2,
                               atol=2e-2)
    for name in want_p:
        a, b = got_p[name].astype(jnp.float32), want_p[name].astype(
            jnp.float32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-2, \
            name
    a, b = (jnp.where(held, v, 0).astype(jnp.float32) for v in (got_x, want_x))
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-2
