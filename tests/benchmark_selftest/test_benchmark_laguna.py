"""The ``laguna`` family, its reference and the cell ``laguna-xs2.seq4096``
without a chip: the configuration keeps every published width, the counts
are the shapes', the reference is the program's mathematics in float32, and
the new cost functions and reducer give values worked out by hand."""
import json
import types

import jax
import pytest

from benchmark import compare, kernel_costs_mixed, manifest, traffic_gen
from benchmark.families import laguna
from benchmark.reducers import roofline_share_of
from benchmark.reference import laguna as reference
from paddle_tpu.distributed import mesh as mesh_mod

MAN = manifest.Manifest()
CONFIG = MAN.config("laguna-xs2")
TOY = laguna.toy(CONFIG)
MIX = dict(seq=64, pool_batches=1, zipf_exponent=1.1, follow_probability=0.5,
           doc_length_median=12, doc_length_sigma=1.0, doc_length_min=2)
SPEC = dict(grad_leaves="all", loss_rtol=1e-5, grad_rel_l2=1e-4,
            grad_median_rel_l2=1e-4)
# the catalog row's config (model-configs guide), the numbers at its top
# level; the per-layer lists and the rope group are checked below
PUBLISHED = dict(
    vocab_size=100352, hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=40, num_attention_heads=48, num_key_value_heads=8,
    head_dim=128, max_position_embeddings=262144, rms_norm_eps=1e-06,
    num_experts=256, num_experts_per_tok=8, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, sliding_window=512,
    partial_rotary_factor=0.5, moe_routed_scaling_factor=2.5)


@pytest.fixture
def one_device_mesh():
    before = mesh_mod.get_mesh()
    yield mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    mesh_mod.set_mesh(before)


def test_the_cell_resolves():
    cell = MAN.cell("laguna-xs2.seq4096")
    assert cell["entry"]["chips"] == 1 and cell["traffic"]["seq"] == 4096
    assert cell["workload"]["kind"] == "train"
    assert cell["workload"]["rows_per_chip"] in \
        cell["workload"]["rows_ladder"] == [1, 2, 4, 8]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"moe_ms_per_step", "moe_experts_roofline",
            "flash_window_roofline", "rope_ms_per_step",
            "flash_attn_ms_per_step", "attn_path_ms_per_step"} <= names
    assert "flash_attn_roofline" not in names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gb", "setup_s"}
    for other in ("gpt2-small.seq1024", "gpt2-small.seq256"):
        assert not names - {"flash_attn_roofline"} <= {
            m["name"] for m in MAN.cell(other)["per_layer"]}


def test_no_width_differs_from_the_published_config():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in CONFIG["reduced"]}
    assert set(CONFIG["changed"]) == set(CONFIG["reduced"])
    # the per-layer lists keep their published length and pattern
    assert CONFIG["layer_types"] == 10 * (
        ["full_attention"] + 3 * ["sliding_attention"])
    assert CONFIG["num_attention_heads_per_layer"] == 10 * [48, 64, 64, 64]
    assert CONFIG["mlp_layer_types"] == ["dense"] + 39 * ["sparse"]
    full = CONFIG["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["rope_theta"], full["factor"],
            full["partial_rotary_factor"]) == ("yarn", 500000, 64, 0.5)
    assert CONFIG["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert CONFIG["deployment"]["held_experts"] == [0, 32]
    assert 0 <= CONFIG["eos_token_id"] < CONFIG["vocab_used"] \
        == CONFIG["vocab_size"] == 100352 // 8
    for key in ("gating", "router_scoring", "router_normalisation",
                "hidden_act"):
        assert key in CONFIG["assumed"]


def test_shapes_give_the_counts_the_file_states():
    a = laguna.arch(CONFIG)
    assert [(x["attention"][:4], x["heads"], x["ffn"]) for x in a["layers"]] \
        == [("full", 48, "dense"), ("slid", 64, "sparse"),
            ("slid", 64, "sparse"), ("slid", 64, "sparse"),
            ("full", 48, "sparse")]
    # attention of a full layer: q and o 2048 x 6144 each, k and v
    # 2048 x 1024 each, the gate 2048 x 48
    assert laguna.layer_params(CONFIG, a["layers"][0]) == {
        "attention": 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48,
        "norms": 4096, "mlp": 3 * 2048 * 8192}
    assert laguna.layer_params(CONFIG, a["layers"][1])["experts"] == \
        32 * 3 * 2048 * 512
    assert laguna.param_count(CONFIG) == CONFIG["flops"]["N"] == 691_623_936
    f = laguna.model_flops_per_token(CONFIG, 4096)
    # what a token meets: everything but the embedding's lookup and 31 of
    # the 32 held experts a sparse layer (8 x 32 / 256 = 1 expected)
    met = 691_623_936 - 12544 * 2048 - 2048 - 5 * 4096 \
        - 4 * 31 * 3 * 2048 * 512
    assert f["six_n"] == 6 * met == CONFIG["flops"]["six_n"]
    # keys seen: (4096 + 1) / 2 in full layers; in sliding ones
    # (512 * 513 / 2 + 3584 * 512) / 4096 = 480.0625
    assert laguna.visible_keys(4096) == 2048.5
    assert laguna.visible_keys(4096, 512) == 480.0625
    assert f["attention"] == 12 * 128 * (2 * 48 * 2048.5 + 3 * 64 * 480.0625)
    assert f["attention"] == CONFIG["flops"]["attention_at_seq_4096"]
    assert f["total"] == f["six_n"] + f["attention"]


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint_blocks"])
def test_reference_equals_program(one_device_mesh, checkpoint):
    """Loss and every gradient leaf, float32 on both sides, through the
    harness's own comparison."""
    recipe = dict(TOY["run"], param_dtype="float32",
                  checkpoint_blocks=checkpoint)
    built = laguna.build(TOY, recipe, seed=3, mesh=one_device_mesh)
    ids, labels = traffic_gen.make_pool(MIX, TOY["vocab_used"],
                                        TOY["eos_token_id"], 2, seed=5)
    params = dict(built.trainer.state["params"])
    got = compare.against_reference(
        built, reference, params, ids[0], labels[0],
        dict(SPEC, reference_remat=checkpoint))
    assert got["ok"], got
    # 3 top leaves; 7 a block, 3 for a dense FFN, 7 for a sparse one
    assert got["grad_leaves"] == 3 + 3 * 7 + 3 + 2 * 7
    assert len(built.leaf_names("all")) == got["grad_leaves"]


def test_the_family_reports_routing_and_load(one_device_mesh, capsys):
    """The comparison's row: how often program and reference chose another
    expert, and what the program's expert layers held of it, from the
    buffers that also feed the telemetry counters."""
    from paddle_tpu import telemetry
    built = laguna.build(TOY, dict(TOY["run"], param_dtype="float32"),
                         seed=3, mesh=one_device_mesh)
    ids, _ = traffic_gen.make_pool(MIX, TOY["vocab_used"],
                                   TOY["eos_token_id"], 2, seed=5)
    params = dict(built.trainer.state["params"])
    before = telemetry.get_registry()
    telemetry._set_registry(telemetry.Registry())
    try:
        built.report_routing(params, ids[0])
        counters = telemetry.get_registry().to_dict()
    finally:
        telemetry._set_registry(before)
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert line["event"] == "routing_agreement" and line["tokens"] == 128
    # float32 on both sides: the same choices
    assert line["assignments_chosen_differently_by_layer"] == [0.0, 0.0]
    chosen, _ = built.chosen_experts(params, ids[0])
    names = [name for name, _ in built.sparse_layers()]
    assert names == ["decoder.h.1.moe", "decoder.h.2.moe"]
    for i, got in enumerate(chosen):
        held = int(((got >= 4) & (got < 8)).sum())     # experts 4-7 of 16
        # expected: 128 tokens x 2 a token x 4 of 16
        assert line["held_assignments_over_expected_by_layer"][i] == held / 64
        layer = built.sparse_layers()[i][1]
        assert line["second_part_ran_by_layer"][i] == (
            held > layer.chunk_rows(128))
        assert line["max_load_over_mean_by_layer"][i] >= 1.0
        series = counters["moe_held_assignments_total"]["series"]
        assert [v for k, v in series.items() if names[i] in k] == [held]


@pytest.mark.parametrize("wrong", ["softmax_router", "no_gate",
                                   "window_off_by_one", "plain_rope"])
def test_comparison_sees_a_wrong_term(one_device_mesh, wrong):
    """Not vacuous: each assumed or easily mistaken term, changed in the
    reference, is out of tolerance."""
    def loss(params, ids, labels, *, n_head, **kw):
        arch = json.loads(json.dumps(n_head))
        arch["held"] = tuple(arch["held"])
        right = reference.route
        if wrong == "softmax_router":
            def route(u, w, a):
                top, ids_ = jax.lax.top_k(jax.nn.softmax(u @ w), a["top_k"])
                return ids_, a["routed_scaling_factor"] * top / top.sum(
                    -1, keepdims=True)
            reference.route = route
        elif wrong == "no_gate":
            arch["gated_attention"] = False
        elif wrong == "window_off_by_one":
            arch["sliding_window"] += 1
        else:
            arch["rope"]["full_attention"] = arch["rope"]["sliding_attention"]
        try:
            return reference.loss(params, ids, labels, n_head=arch, **kw)
        finally:
            reference.route = right

    recipe = dict(TOY["run"], param_dtype="float32")
    built = laguna.build(TOY, recipe, seed=3, mesh=one_device_mesh)
    ids, labels = traffic_gen.make_pool(MIX, TOY["vocab_used"],
                                        TOY["eos_token_id"], 2, seed=5)
    got = compare.against_reference(
        built, types.SimpleNamespace(loss=loss),
        dict(built.trainer.state["params"]), ids[0], labels[0], SPEC)
    assert not got["ok"], got


def test_cost_functions_against_hand_values():
    """One row of 4,096 on the published widths."""
    got = kernel_costs_mixed.flash_window(CONFIG, 1, 4096)
    unit_full = 2 * 4096 * 2048.5 * 128
    unit_slid = 2 * 4096 * 480.0625 * 128
    assert got["flops"] == 9 * (2 * 48 * unit_full + 3 * 64 * unit_slid)
    tensor, stat = 4096 * 128 * 2, 4096 * 8 * 4
    # per query head: q o | q do dq | q do, and 1 + 2 + 2 statistics; per
    # KV head: k v | k v | k v dk dv
    per_layer = lambda h: h * (7 * tensor + 5 * stat) + 8 * 8 * tensor  # noqa: E731
    assert got["bytes"] == 2 * per_layer(48) + 3 * per_layer(64)
    assert kernel_costs_mixed.flash_window(CONFIG, 4, 4096)["flops"] \
        == 4 * got["flops"]

    got = kernel_costs_mixed.moe_experts(CONFIG, 4, 4096)
    rows = 4 * 4096 * 8 * 32 / 256                  # 16,384 assignments
    assert got["flops"] == 4 * 9 * 2 * rows * 2048 * 512
    x, mid, w = rows * 2048 * 2, rows * 512 * 2, 32 * 2048 * 512 * 2
    # nine products: each reads two operands and writes one result; over
    # them x appears 9 times, the intermediate 9 times, the weights 9 times
    assert got["bytes"] == 4 * 9 * (x + mid + w)


def test_roofline_share_of_reads_costs_and_either_time(monkeypatch):
    reading = types.SimpleNamespace(
        config=CONFIG, rows_per_chip=4, seq=4096,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(roofline_share_of.sum_per_step, "reduce",
                        lambda r, pattern: 50.0)
    monkeypatch.setattr(roofline_share_of.named_scopes_per_step, "reduce",
                        lambda r, scopes: (20.0, {"scoped_share": 1.0}))
    cost = kernel_costs_mixed.flash_window(CONFIG, 4, 4096)
    value, note = roofline_share_of.reduce(
        reading, "kernel_costs_mixed", "flash_window", pattern="x")
    assert value == pytest.approx(100 * cost["flops"] / 197e12 / 0.050)
    assert note["bound"] == "compute" and note["measured_ms"] == 50.0
    cost = kernel_costs_mixed.moe_experts(CONFIG, 4, 4096)
    value, note = roofline_share_of.reduce(
        reading, "kernel_costs_mixed", "moe_experts", scopes="x")
    assert value == pytest.approx(100 * max(
        cost["flops"] / 197e12, cost["bytes"] / 819e9) / 0.020)
    # nothing ran, or the program has no such scope: no value, no error
    monkeypatch.setattr(roofline_share_of.named_scopes_per_step, "reduce",
                        lambda r, scopes: (None, {"stale_metadata": True}))
    assert roofline_share_of.reduce(
        reading, "kernel_costs_mixed", "moe_experts", scopes="x") is None
    monkeypatch.setattr(roofline_share_of.sum_per_step, "reduce",
                        lambda r, pattern: 0.0)
    assert roofline_share_of.reduce(
        reading, "kernel_costs_mixed", "flash_window", pattern="x") is None
    with pytest.raises(ValueError):
        roofline_share_of.reduce(reading, "kernel_costs_mixed", "moe_experts")


def test_new_metrics_read_nothing_from_a_trace_without_their_scopes(tmp_path):
    """On a trace of the GPT program, which opens none of the new scopes
    and runs no grouped product, the new readers find no time and do not
    raise; ``roofline_share_of`` then reports nothing."""
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
    for metric in ("moe_ms_per_step", "rope_ms_per_step"):
        spec = MAN.layer_metric(metric)
        value, note = manifest.plugin("reducers", spec["reducer"]).reduce(
            reading, **spec["args"])
        assert value == 0.0 and note["scoped_share"] > 0.9
    spec = MAN.layer_metric("moe_experts_roofline")
    assert roofline_share_of.reduce(reading, **spec["args"]) is None
    # the flash kernels of that trace are there to be read
    spec = MAN.layer_metric("flash_window_roofline")
    value, note = roofline_share_of.reduce(reading, **spec["args"])
    assert value > 0 and note["measured_ms"] > 0
