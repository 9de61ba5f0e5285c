"""The ``olmohybrid`` family, its reference and the cell
``olmo-hybrid-7b.seq8192`` without a chip: the configuration keeps every
published width, ``full_attention_interval`` is ``layer_types``' pattern, the
counts are the shapes' worked out by hand, the reference's segmented scan is
its plain scan, the three controls the cell's limits are placed against are
refused, and the new metrics read nothing where their scopes are absent."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, kernel_costs_linear, manifest, traffic_gen
from benchmark.families import olmohybrid
from benchmark.reducers import roofline_share_of
from benchmark.reference import olmohybrid as reference
from paddle_tpu.distributed import mesh as mesh_mod

MAN = manifest.Manifest()
CELL = "olmo-hybrid-7b.seq8192"
NAME = "olmo-hybrid-7b"
CONFIG = MAN.config(NAME)
TOY = olmohybrid.toy(CONFIG)
MIX = dict(seq=64, pool_batches=1, zipf_exponent=1.1, follow_probability=0.5,
           doc_length_median=12, doc_length_sigma=1.0, doc_length_min=2)
NEW_METRICS = {"delta_mixer_ms_per_step", "delta_rule_ms_per_step",
               "delta_rule_roofline", "whole_qk_norm_ms_per_step",
               "post_norm_ms_per_step"}
FULL, LINEAR = "full_attention", "linear_attention"


def catalog_row():
    """The catalog row's ``config`` (model-configs guide,
    ``architectures.jsonl``)."""
    return dict(
        model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
        intermediate_size=11008, num_hidden_layers=32, num_attention_heads=30,
        num_key_value_heads=30, hidden_act="silu",
        max_position_embeddings=65536, attention_bias=False,
        rms_norm_eps=1e-06, tie_word_embeddings=False,
        layer_types=([LINEAR] * 3 + [FULL]) * 8, linear_num_key_heads=30,
        linear_num_value_heads=30, linear_key_head_dim=96,
        linear_value_head_dim=192, linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})


@pytest.fixture
def built():
    before = mesh_mod.get_mesh()
    mesh = mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    yield olmohybrid.build(TOY, TOY["run"], seed=3, mesh=mesh)
    mesh_mod.set_mesh(before)


def rows(seed=5):
    ids, labels = traffic_gen.make_pool(MIX, TOY["vocab_used"],
                                        TOY["eos_token_id"], 1, seed=seed)
    return ids[0], labels[0]


def test_the_cell_resolves():
    assert MAN.problems() == []
    cell = MAN.cell(CELL)
    assert cell["entry"]["chips"] == 1 and cell["traffic"]["seq"] == 8192
    w = cell["workload"]
    assert w["kind"] == "train" and w["mesh"] == {"data": 1}
    assert w["rows_per_chip"] in w["rows_ladder"] == [1, 2, 4]
    # the largest rung that compiled under the 14 GB every cell keeps to
    assert w["rows_per_chip"] == max(
        r for r in w["rows_ladder"]
        if w["compiled_peak_gb"][str(r)] + 1.03 < 14)
    names = {m["name"] for m in cell["per_layer"]}
    assert NEW_METRICS | {"flash_attn_ms_per_step", "attn_path_ms_per_step",
                          "lm_head_loss_ms_per_step"} <= names
    # the accepted linear-attention and QK-norm metrics keep their lists
    assert not names & {"linear_attn_ms_per_step",
                        "gated_delta_rule_ms_per_step",
                        "gated_delta_rule_roofline", "qk_norm_ms_per_step",
                        "qk_norm_rope_ms_per_step", "moe_ms_per_step"}
    for other in sorted(MAN.workloads):
        if other != CELL:
            assert not NEW_METRICS & {m["name"]
                                      for m in MAN.cell(other)["per_layer"]}
    for name in NEW_METRICS:
        assert MAN.per_layer[name]["workloads"] == [CELL]
    # the twins read the accepted metrics' scopes
    for twin, accepted in (("delta_mixer_ms_per_step",
                            "linear_attn_ms_per_step"),
                           ("delta_rule_ms_per_step",
                            "gated_delta_rule_ms_per_step"),
                           ("delta_rule_roofline",
                            "gated_delta_rule_roofline"),
                           ("whole_qk_norm_ms_per_step",
                            "qk_norm_ms_per_step")):
        assert MAN.layer_metric(twin)["args"] == \
            MAN.layer_metric(accepted)["args"]
        assert MAN.per_layer[twin]["layer"] == \
            MAN.per_layer[accepted]["layer"]
    assert cell["traffic"] == MAN.cell(
        "qwen3-next-80b-a3b-instruct.seq8192")["traffic"]


def test_no_width_differs_from_the_published_config():
    published = catalog_row()
    changed = {k for k, v in published.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "linear_num_key_heads", "linear_num_value_heads", "vocab_size"}
    assert CONFIG["reduced"] == MAN.configs[NAME]["reduced"]
    assert MAN.configs[NAME]["source"] == CONFIG["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
        "config.json")
    assert CONFIG["published"] == {k: published[k] for k in CONFIG["reduced"]}
    deployment = CONFIG["deployment"]
    assert deployment["chips_sharing_a_layer"] == 2
    for key in olmohybrid.HEAD_COUNTS:
        assert CONFIG[key] == 30 // 2
    # the feed-forward width stays published; its columns held are the share
    assert CONFIG["intermediate_size"] == 11008
    assert deployment["ffn_columns_held"] == 11008 // 2
    assert CONFIG["vocab_size"] == 100352 // 8
    assert 0 <= CONFIG["eos_token_id"] < CONFIG["vocab_used"] \
        == CONFIG["vocab_size"]
    for key in ("reordered_norms", "whole_width_qk_norm", "no_rotation",
                "head_dim", "gated_delta_net", "fused_projections", "chunk",
                "initialisers", "eos_token_id"):
        assert key in CONFIG["assumed"]
    a = olmohybrid.arch(CONFIG)
    assert a["layers"] == [LINEAR] * 3 + [FULL]
    assert (a["heads"], a["kv_heads"], a["head_dim"]) == (15, 15, 128)
    assert a["linear"] == {"heads": 15, "d_k": 96, "d_v": 192,
                           "conv_kernel": 4}
    assert a["allow_neg_eigval"] and a["ffn_columns"] == 5504


def test_full_attention_interval_is_the_pattern_of_layer_types():
    """``kernel_costs_linear`` counts the linear layers by the interval; the
    published ``layer_types`` says the same of all 32 layers, and the family
    refuses a file where the two disagree."""
    every = CONFIG["full_attention_interval"]
    assert every == 4
    assert [t == FULL for t in CONFIG["layer_types"]] == [
        (i + 1) % every == 0 for i in range(32)]
    assert kernel_costs_linear.linear_layers(CONFIG) == 3
    wrong = dict(CONFIG, layer_types=[LINEAR, FULL] * 16)
    with pytest.raises(ValueError, match="full_attention_interval"):
        olmohybrid.arch(wrong)


def test_shapes_give_the_counts_the_file_states():
    h, key, value = 3840, 15 * 96, 15 * 192
    linear = olmohybrid.layer_params(CONFIG, LINEAR)
    assert linear == {"in_proj_qkvz": h * (2 * key + 2 * value),
                      "in_proj_ba": h * 30, "conv": (2 * key + value) * 4,
                      "decay": 30, "mixer_norms": 192, "out_proj": value * h,
                      "norms": 2 * h, "mlp": 3 * h * 5504}
    # mixer 44,375,262 and feed-forward block 63,406,080
    assert sum(linear.values()) == 44_375_262 + 63_406_080 + 7_680 \
        == 107_789_022
    full = olmohybrid.layer_params(CONFIG, FULL)
    assert sum(full.values()) == 3 * h * 1920 + 1920 * h + 2 * 1920 \
        + 63_406_080 + 7_680 == 92_908_800
    embed_and_head = 2 * 12_544 * h
    assert embed_and_head == 96_337_920
    assert olmohybrid.param_count(CONFIG) == CONFIG["flops"]["N"] \
        == 3 * 107_789_022 + 92_908_800 + 96_337_920 + h == 512_617_626
    f = olmohybrid.model_flops_per_token(CONFIG, 8192)
    met = 3 * (33_177_600 + 115_200 + 11_059_200 + 63_406_080) \
        + (22_118_400 + 7_372_800 + 63_406_080) + 12_544 * h
    assert met == CONFIG["flops"]["met_per_token"] == 464_340_480
    assert f["six_n"] == 6 * met == CONFIG["flops"]["six_n"] \
        == 2_786_042_880
    assert f["attention"] == 12 * 15 * 128 * 8193 / 2 \
        == CONFIG["flops"]["attention_at_seq_8192"] == 94_383_360
    assert f["recurrence"] == 18 * 96 * 192 * 15 * 3 \
        == CONFIG["flops"]["recurrence"] == 14_929_920
    assert f["total"] == CONFIG["flops"]["total_at_seq_8192"] \
        == 2_895_356_160
    # how the program recomputes is its business: no count follows it
    other = dict(CONFIG, run=dict(CONFIG["run"], checkpoint_blocks=False))
    assert olmohybrid.model_flops_per_token(other, 8192) == f


def test_cost_function_against_hand_values():
    """Four rows of 8,192 positions, three linear layers of 15 heads at
    ``d_k = 96``, ``d_v = 192``: the cost function the accepted roofline
    reads, unchanged, at this cell's widths; no zero lane is counted."""
    got = kernel_costs_linear.gated_delta_rule(CONFIG, 4, 8192)
    calls = 4 * 8192 * 15 * 3
    assert calls == 1_474_560
    # nine d_k x d_v products a head a position, 2 operations a term
    assert got["flops"] == calls * 9 * 2 * 96 * 192 == 489_223_618_560
    # q, k (96) and v, o (192) and their gradients in bf16, g and beta and
    # theirs in float32: 2 x 576 x 2 + 16 bytes
    assert got["bytes"] == calls * 2320 == 3_420_979_200
    reading = types.SimpleNamespace(
        config=CONFIG, rows_per_chip=4, seq=8192,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    spec = MAN.layer_metric("delta_rule_roofline")
    scoped = roofline_share_of.named_scopes_per_step.reduce
    roofline_share_of.named_scopes_per_step.reduce = \
        lambda r, scopes: (120.0, {})
    try:
        value, note = roofline_share_of.reduce(reading, **spec["args"])
    finally:
        roofline_share_of.named_scopes_per_step.reduce = scoped
    assert note["bound"] == "memory"
    assert note["least_ms"] == pytest.approx(3_420_979_200 / 819e9 * 1e3)
    assert value == pytest.approx(100 * 3_420_979_200 / 819e9 / 0.120)


def test_reference_segments_change_nothing(monkeypatch):
    """The checkpointed segments of the reference's scan are the plain
    scan, steps up to 2 and all."""
    ks = jax.random.split(jax.random.key(0), 5)
    q, k = (jax.random.normal(s, (1, 48, 2, 4)) for s in ks[:2])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, 48, 2, 6))
    g = -jax.random.uniform(ks[3], (1, 48, 2))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, 48, 2)))
    whole = reference.delta_rule(q, k, v, g, beta, False)
    monkeypatch.setattr(reference, "SEGMENT", 16)
    np.testing.assert_allclose(reference.delta_rule(q, k, v, g, beta, True),
                               whole, atol=1e-5)


def as_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def pre_norm_block(x, p, kind, arch, eps, remat):
    """The block with its norms on the sublayers' inputs: what Olmo-Hybrid
    does NOT do."""
    mixer = reference.gated_delta_net if kind == LINEAR \
        else reference.attention
    h = x + mixer(reference.norm(x, p["post_mixer_norm_g"], eps), p, arch,
                  eps, remat)
    return h + reference.ffn(reference.norm(h, p["post_ffn_norm_g"], eps), p)


CONTROLS = {
    "float8_operands": ("_mm", lambda a, b: as_fp8(a) @ as_fp8(b)),
    "beta_without_its_factor_2": ("step_size",
                                  lambda b, arch: jax.nn.sigmoid(b)),
    "pre_norm_blocks": ("block", pre_norm_block)}


def test_the_cells_limits_refuse_the_three_controls(built, monkeypatch):
    """The three controls the cell's limits are placed against, each refused
    under the limits the workload file commits where the sound reference
    passes them: every product's operands rounded to ``float8_e4m3``, the
    step size ``sigmoid(b)`` without its factor 2, and the norms on the
    sublayers' inputs."""
    check = MAN.cell(CELL)["workload"]["check"]
    spec = {k: check[k] for k in ("grad_leaves", "loss_rtol", "grad_rel_l2",
                                  "grad_median_rel_l2")}
    params = dict(built.trainer.state["params"])
    sound = compare.against_reference(built, reference, params, *rows(),
                                      spec)
    assert sound["ok"], sound
    # the program's side is the same in every comparison: staged once
    ours = built.loss_and_grads(params, built.leaf_names("all"), *rows())
    monkeypatch.setattr(built, "loss_and_grads", lambda *_: ours)
    for name, (attr, wrong) in CONTROLS.items():
        with monkeypatch.context() as m:
            m.setattr(reference, attr, wrong)
            got = compare.against_reference(built, reference, params,
                                            *rows(), spec)
        assert not got["ok"], (name, got)
    for key in ("loss_rtol", "grad_rel_l2", "grad_median_rel_l2",
                "loss_margin", "sample_rows", "controls"):
        assert len(check["why"][key]) > 100, key
    assert "float8_e4m3" in json.dumps(check["why"])


def test_new_metrics_read_nothing_from_a_trace_without_their_scopes(tmp_path):
    """On a trace of the GPT program, which opens none of the new metrics'
    scopes, their readers find no time and do not raise: a program that
    lacks what this configuration added leaves the metrics out."""
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
        rows_per_chip=4, seq=8192, peaks=manifest.peaks("TPU v5 lite"))
    reading._scopes = xplane_scopes.Scopes(path)
    for metric in sorted(NEW_METRICS):
        spec = MAN.layer_metric(metric)
        reducer = manifest.plugin("reducers", spec["reducer"])
        value = reducer.reduce(reading, **spec["args"])
        if isinstance(value, tuple):
            value = value[0]
        assert not value, metric
