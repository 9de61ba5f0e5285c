"""The ``sdar`` family, its reference and the cell
``sdar-30b-a3b-chat.seq4096`` without a chip: the configuration keeps every
published width, the counts are the shapes', the reference is the program's
mathematics in float32 (noise, mask, QK norm, router and loss), the eight
chips' expert shares add up to the uncut layer, and the new cost function
gives values worked out by hand."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (compare, kernel_costs_block_diffusion, manifest,
                       traffic_gen)
from benchmark.families import sdar
from benchmark.reducers import roofline_share_of
from benchmark.reference import sdar as reference
from paddle_tpu.distributed import mesh as mesh_mod

MAN = manifest.Manifest()
CELL = "sdar-30b-a3b-chat.seq4096"
CONFIG = MAN.config("sdar-30b-a3b-chat")
TOY = sdar.toy(CONFIG)
MIX = dict(seq=128, pool_batches=1, zipf_exponent=1.1, follow_probability=0.5,
           doc_length_median=12, doc_length_sigma=1.0, doc_length_min=2)
SPEC = dict(grad_leaves="all", loss_rtol=1e-5, grad_rel_l2=1e-4,
            grad_median_rel_l2=1e-4)
# the catalog row's config (model-configs guide, architectures.jsonl)
PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=32768, max_window_layers=48, mlp_only_layers=[],
    model_type="sdar_moe", moe_intermediate_size=768, norm_topk_prob=True,
    num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=48, num_key_value_heads=4, rms_norm_eps=1e-06,
    rope_scaling=None, rope_theta=1000000, sliding_window=None,
    tie_word_embeddings=False, use_sliding_window=False, vocab_size=151936)


@pytest.fixture
def one_device_mesh():
    before = mesh_mod.get_mesh()
    yield mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    mesh_mod.set_mesh(before)


@pytest.fixture
def built(one_device_mesh):
    return sdar.build(TOY, TOY["run"], seed=3, mesh=one_device_mesh)


def rows(seed=5):
    ids, labels = traffic_gen.make_pool(MIX, TOY["vocab_used"],
                                        TOY["eos_token_id"], 2, seed=seed)
    return ids[0], labels[0]


def test_the_cell_resolves():
    assert MAN.problems() == []
    cell = MAN.cell(CELL)
    assert cell["entry"]["chips"] == 1 and cell["traffic"]["seq"] == 4096
    w = cell["workload"]
    assert w["kind"] == "train" and w["mesh"] == {"data": 1}
    assert w["rows_per_chip"] == 2 and w["rows_ladder"] == [1, 2, 4]
    assert (w["sync_every"], w["warmup_steps"], w["trace_steps"]) == (4, 3, 8)
    names = {m["name"] for m in cell["per_layer"]}
    new = {"flash_block_diffusion_roofline", "noise_ms_per_step",
           "qk_norm_ms_per_step"}
    assert new | {"flash_attn_ms_per_step", "attn_path_ms_per_step",
                  "lm_head_loss_ms_per_step"} <= names
    assert not names & {"flash_attn_roofline", "flash_window_roofline",
                        "moe_ms_per_step", "rope_ms_per_step"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gb", "setup_s"}
    for other in sorted(MAN.workloads):
        if other != CELL:
            assert not new & {m["name"]
                              for m in MAN.cell(other)["per_layer"]}
    # the kernels are found as flash_attn_ms_per_step finds them
    assert MAN.layer_metric("flash_block_diffusion_roofline")["args"][
        "pattern"] == MAN.layer_metric("flash_attn_ms_per_step")["args"][
            "pattern"]


def test_no_width_differs_from_the_published_config():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CONFIG["reduced"] == MAN.configs["sdar-30b-a3b-chat"]["reduced"]
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in CONFIG["reduced"]}
    assert set(CONFIG["changed"]) == set(CONFIG["reduced"])
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert CONFIG["deployment"]["held_experts"] == [0, 128 // 8]
    assert CONFIG["vocab_size"] == 151936 // 8 and CONFIG[
        "num_hidden_layers"] >= 4
    # [MASK] is the one row of the slice the traffic never draws
    objective = CONFIG["block_diffusion"]
    assert objective == {"block_length": 4, "t_min": 0.001,
                         "mask_token_id": CONFIG["vocab_size"] - 1}
    assert 0 <= CONFIG["eos_token_id"] < CONFIG["vocab_used"] \
        == objective["mask_token_id"]
    for key in ("block_length", "noise_schedule", "t_min",
                "loss_weight_and_normalisation", "no_shift", "qk_norm",
                "mask_and_eos_ids", "initialisers", "router"):
        assert key in CONFIG["assumed"]


def test_mask_token_is_never_in_the_traffic():
    mix = dict(MAN.cell(CELL)["traffic"], seq=512, pool_batches=4)
    ids, _ = traffic_gen.make_pool(mix, CONFIG["vocab_used"],
                                   CONFIG["eos_token_id"], 2, seed=2**31 + 7)
    assert ids.max() < CONFIG["block_diffusion"]["mask_token_id"]
    assert (ids == CONFIG["eos_token_id"]).any()


def test_shapes_give_the_counts_the_file_states():
    assert sdar.layer_params(CONFIG) == {
        "attention": 2 * 8_388_608 + 2 * 1_048_576, "norms": 4_352,
        "router": 262_144, "experts": 16 * 4_718_592}
    assert sum(sdar.layer_params(CONFIG).values()) == 94_638_336
    assert sdar.param_count(CONFIG) == CONFIG["flops"]["N"] == 645_623_296 \
        == 6 * 94_638_336 + 77_791_232 + 2_048
    f = sdar.model_flops_per_token(CONFIG, 4096)
    # met at a position in a layer: q, o, k, v, the router and one expert
    met = 2 * 8_388_608 + 2 * 1_048_576 + 262_144 + 4_718_592
    assert met == CONFIG["flops"]["per_layer_met"] == 23_855_104
    assert f["six_n"] == 6 * (2 * 6 * met + 18_992 * 2_048) \
        == CONFIG["flops"]["six_n_per_token_at_seq4096"]
    # a query sees (4096 + 4) / 2 keys, at both of a token's positions
    assert kernel_costs_block_diffusion.visible_keys(4096, 4) == 2050
    assert f["attention"] == 12 * 32 * 128 * (2 * 2050) * 6 \
        == CONFIG["flops"]["attention_per_token_at_seq4096"]
    assert f["total"] == f["six_n"] + f["attention"] \
        == CONFIG["flops"]["per_token_at_seq4096"] == 3_160_080_384


def test_cost_function_against_hand_values():
    """Two rows of 4,096 clean tokens on the published widths."""
    got = kernel_costs_block_diffusion.flash_block_diffusion(CONFIG, 2, 4096)
    # one product: 8,192 positions x 2,050 visible keys x 128 lanes x 2
    assert got["flops"] == 6 * 2 * 32 * 9 * (2 * 8192 * 2050 * 128)
    tensor, stat = 8192 * 128 * 2, 8192 * 8 * 4
    # per query head: q o | q do dq | q do, and 1 + 2 + 2 statistics; per
    # KV head: k v | k v | k v dk dv
    assert got["bytes"] == 6 * 2 * (32 * (7 * tensor + 5 * stat)
                                    + 4 * 8 * tensor)
    one = kernel_costs_block_diffusion.flash_block_diffusion(CONFIG, 1, 4096)
    assert got["flops"] == 2 * one["flops"]
    # nothing follows the tiles: a longer block moves the visible keys only
    longer = dict(CONFIG, block_diffusion=dict(CONFIG["block_diffusion"],
                                               block_length=32))
    assert kernel_costs_block_diffusion.flash_block_diffusion(
        longer, 1, 4096)["flops"] == one["flops"] * 2064 / 2050
    reading = types.SimpleNamespace(
        config=CONFIG, rows_per_chip=2, seq=4096,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    roofline_share_of.sum_per_step.reduce, kept = (
        lambda r, pattern: 150.0), roofline_share_of.sum_per_step.reduce
    try:
        value, note = roofline_share_of.reduce(
            reading, **MAN.layer_metric("flash_block_diffusion_roofline")[
                "args"])
    finally:
        roofline_share_of.sum_per_step.reduce = kept
    assert value == pytest.approx(100 * got["flops"] / 197e12 / 0.150)
    assert note["bound"] == "compute" and value < 100


def test_param_count_is_what_the_program_builds(built):
    n = sum(int(np.prod(v.shape))
            for v in built.trainer.state["params"].values())
    assert n == sdar.param_count(TOY)
    # 3 top leaves; 12 a block: 2 norms, q k v o, 2 QK norms, router, 3 experts
    assert len(built.leaf_names("all")) == 3 + 2 * 12


def test_reference_noise_is_the_programs(built):
    """The reference states the noise itself; given the comparison's key it
    draws what the program's function draws."""
    from paddle_tpu.text import block_diffusion as bd
    arch = built.config["n_head"]
    ids, _ = rows()
    key = jax.random.wrap_key_data(jnp.asarray(arch["noise_key"], jnp.uint32))
    ours = bd.noise(jnp.asarray(ids), key, arch["block_length"],
                    arch["mask_token_id"], arch["t_min"])
    for a, b in zip(ours, reference.noise(jnp.asarray(ids), arch)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert arch["noise_key"] == sdar.check_noise_key()


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint_blocks"])
def test_reference_equals_program(one_device_mesh, checkpoint):
    """Loss and every gradient leaf, float32 on both sides, through the
    harness's own comparison."""
    recipe = dict(TOY["run"], checkpoint_blocks=checkpoint)
    built = sdar.build(TOY, recipe, seed=3, mesh=one_device_mesh)
    params = dict(built.trainer.state["params"])
    got = compare.against_reference(built, reference, params, *rows(),
                                    dict(SPEC, reference_remat=checkpoint))
    assert got["ok"], got
    assert got["grad_leaves"] == 3 + 2 * 12


WRONG = ("sigmoid_router", "no_qk_norm", "positions_run_on", "token_causal",
         "noised_sees_its_clean_block", "no_weight", "unnormalised_top_k")


@pytest.mark.parametrize("wrong", WRONG)
def test_comparison_sees_a_wrong_term(built, wrong, monkeypatch):
    """Not vacuous: each assumed or easily mistaken term, changed in the
    reference, is out of tolerance."""
    if wrong == "sigmoid_router":
        def route(u, w, a):
            top, ids_ = jax.lax.top_k(jax.nn.sigmoid(u @ w), a["top_k"])
            return ids_, top / top.sum(-1, keepdims=True)
        monkeypatch.setattr(reference, "route", route)
    elif wrong == "unnormalised_top_k":
        def route(u, w, a):
            return tuple(reversed(jax.lax.top_k(jax.nn.softmax(u @ w),
                                                a["top_k"])))
        monkeypatch.setattr(reference, "route", route)
    elif wrong == "no_qk_norm":
        right = reference.rms_norm
        monkeypatch.setattr(
            reference, "rms_norm",
            lambda x, g, eps: x if x.ndim == 4 else right(x, g, eps))
    elif wrong == "positions_run_on":
        right = reference.apply_rope
        monkeypatch.setattr(
            reference, "apply_rope", lambda x, positions, theta: right(
                x, jnp.arange(x.shape[1]), theta))
    elif wrong == "token_causal":
        def visible(length, block):
            return reference_visible(length, 1)
        reference_visible = reference.visible
        monkeypatch.setattr(reference, "visible", visible)
    elif wrong == "noised_sees_its_clean_block":
        def visible(length, block):
            seen = right(length, block)
            i = jnp.arange(2 * length)
            own = ((i[:, None] % length) // block
                   == (i[None, :] % length) // block)
            return seen | (own & (i[:, None] < length)
                           & (i[None, :] >= length))
        right = reference.visible
        monkeypatch.setattr(reference, "visible", visible)
    elif wrong == "no_weight":
        right_noise = reference.noise
        monkeypatch.setattr(
            reference, "noise", lambda x0, arch: right_noise(x0, arch)[:2]
            + (jnp.ones(x0.shape, jnp.float32),))
    got = compare.against_reference(
        built, reference, dict(built.trainer.state["params"]), *rows(), SPEC)
    assert not got["ok"], got


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The eight chips' expert parts (experts 0-1, ..., 14-15 of a toy's
    16; 0-15, ..., 112-127 in the deployment), each through the program's
    layer that is told what it holds, summed: the uncut reference's layer
    output. The router is every chip's alike and is counted once (it adds
    nothing to the output); there is no shared expert."""
    from paddle_tpu.incubate.moe import DroplessMoELayer
    d, f, experts, chips, k = 32, 16, 16, 8, 4
    keys = jax.random.split(jax.random.key(0), 5)
    params = {"router_w": jax.random.normal(keys[0], (d, experts)),
              "experts_gate_w": jax.random.normal(keys[1], (experts, d, f)),
              "experts_up_w": jax.random.normal(keys[2], (experts, d, f)),
              "experts_down_w": jax.random.normal(keys[3], (experts, f, d))}
    u = jax.random.normal(keys[4], (2, 24, d))
    with jax.default_matmul_precision("highest"):
        whole, chosen = reference.moe(u, params, {"top_k": k,
                                                  "held": (0, experts)})
        parts = []
        for chip in range(chips):
            held = (chip * experts // chips, experts // chips)
            layer = DroplessMoELayer(d, f, experts, k, held=held,
                                     scoring="softmax")
            assert layer.shared_expert is None
            layer.router.weight.value = params["router_w"]
            lo, hi = held[0], held[0] + held[1]
            layer.experts.gate_proj.value = params["experts_gate_w"][lo:hi]
            layer.experts.up_proj.value = params["experts_up_w"][lo:hi]
            layer.experts.down_proj.value = params["experts_down_w"][lo:hi]
            parts.append(layer(u))
            # and the reference's share is the program's
            share, _ = reference.moe(
                u, dict(params, **{name: params[name][lo:hi] for name in (
                    "experts_gate_w", "experts_up_w", "experts_down_w")}),
                {"top_k": k, "held": held})
            np.testing.assert_allclose(np.asarray(parts[-1]),
                                       np.asarray(share), atol=1e-4)
    assert chosen.shape == (2, 24, k)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=2e-4, rtol=1e-5)
    assert float(jnp.abs(whole).mean()) > 0.1
    # one share alone is not the layer
    assert not np.allclose(np.asarray(parts[0]), np.asarray(whole), atol=0.1)


@pytest.mark.parametrize("chips, experts", [(8, 128), (2, 16), (4, 32)])
def test_routers_start_with_every_chips_load_at_its_expectation(chips,
                                                                experts):
    """The recipe's initialiser: a chip's columns, the same on every chip.
    Whatever the token, its ``chips`` best experts are one on every chip."""
    from paddle_tpu.framework.random import rng_guard
    with rng_guard(jax.random.key(4)):
        w = sdar.tied_across_chips(chips)((64, experts), jnp.float32)
    slots = experts // chips
    assert w.shape == (64, experts) and float(jnp.abs(w).max()) > 0
    np.testing.assert_array_equal(np.asarray(w[:, :slots]),
                                  np.asarray(w[:, -slots:]))
    assert len(np.unique(np.asarray(w[0, :slots]))) == slots
    u = jax.random.normal(jax.random.key(5), (200, 64))
    _, chosen = jax.lax.top_k(jax.nn.softmax(u @ w, axis=-1), chips)
    on_chip = np.sort(np.asarray(chosen) // slots, axis=-1)
    np.testing.assert_array_equal(on_chip, np.tile(np.arange(chips), (200, 1)))
    with pytest.raises(ValueError, match="chips"):
        sdar.tied_across_chips(3)((64, 16), jnp.float32)


def test_the_family_reports_routing_load_and_noise(built, capsys):
    from paddle_tpu import telemetry
    ids, _ = rows()
    params = dict(built.trainer.state["params"])
    before = telemetry.get_registry()
    telemetry._set_registry(telemetry.Registry())
    try:
        built.report_routing(params, ids)
        counters = telemetry.get_registry().to_dict()
    finally:
        telemetry._set_registry(before)
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    # two rows of 128 clean tokens are 512 positions through every router
    assert line["event"] == "routing_agreement" and line["positions"] == 512
    assert line["assignments_chosen_differently_by_layer"] == [0.0, 0.0]
    names = [name for name, _ in built.sparse_layers()]
    assert names == ["decoder.h.0.moe", "decoder.h.1.moe"]
    chosen, buffers = built.chosen_experts(params, ids)
    for i, got in enumerate(chosen):
        assert got.shape == (512, 2)
        held = int((got >= 8).sum())                  # experts 8-15 of 16
        # expected: 512 positions x 2 a position x 8 of 16, and met to the
        # assignment: the routers start tied across the chips
        assert line["held_assignments_over_expected_by_layer"][i] == held / 512
        assert held == 512 and not line["second_part_ran_by_layer"][i]
        series = counters["moe_held_assignments_total"]["series"]
        assert [v for k, v in series.items() if names[i] in k] == [held]
    assert 0.3 < line["masked_share"] < 1.0
    assert line["masked_share"] == pytest.approx(
        float(buffers["masked_share"]))
    gauge = counters["block_diffusion_masked_share"]["series"]
    assert list(gauge.values()) == [pytest.approx(line["masked_share"])]


def test_a_step_takes_clean_rows_alone(built):
    ids, labels = rows()
    assert built.step_args(ids, labels)[0] is ids
    a = float(built.trainer.train_step(*built.step_args(ids, labels)))
    b = float(built.trainer.train_step(*built.step_args(ids, labels)))
    # another step, another key: another noise on the same rows
    assert np.isfinite(a) and np.isfinite(b) and a != b


def test_new_metrics_read_nothing_from_a_trace_without_their_scopes(tmp_path):
    """On a trace of the GPT program, which opens neither new scope, the
    new scope readers find no time and do not raise; the roofline's reader
    finds that trace's flash kernels."""
    import gzip
    import os
    import shutil

    from benchmark import trace_reduce, xplane_scopes

    name = "trace_1chip_scoped.xplane.pb"
    path = str(tmp_path / name)
    with gzip.open(os.path.join(manifest.HERE, "selftest", "data",
                                name + ".gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    reading = types.SimpleNamespace(
        trace=trace_reduce.load(path), steps=4, counters={}, config=CONFIG,
        rows_per_chip=1, seq=4096, peaks=manifest.peaks("TPU v5 lite"))
    reading._scopes = xplane_scopes.Scopes(path)
    for metric in ("noise_ms_per_step", "qk_norm_ms_per_step"):
        spec = MAN.layer_metric(metric)
        value, note = manifest.plugin("reducers", spec["reducer"]).reduce(
            reading, **spec["args"])
        assert value == 0.0 and note["scoped_share"] > 0.9
    spec = MAN.layer_metric("flash_block_diffusion_roofline")
    value, note = roofline_share_of.reduce(reading, **spec["args"])
    assert value > 0 and note["measured_ms"] > 0
