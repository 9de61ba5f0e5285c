"""The ``qwen3next`` family, its reference and the cell
``qwen3-next-80b-a3b-instruct.seq8192`` without a chip: the configuration
keeps every published width, the counts are the shapes', the reference's
recurrence is the definition worked out by hand, the reference is the
program's mathematics in float32, and the new cost function gives values
worked out by hand."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, kernel_costs_linear, manifest, traffic_gen
from benchmark.families import qwen3next
from benchmark.reducers import roofline_share_of
from benchmark.reference import qwen3next as reference
from paddle_tpu.distributed import mesh as mesh_mod

MAN = manifest.Manifest()
CELL = "qwen3-next-80b-a3b-instruct.seq8192"
NAME = "qwen3-next-80b-a3b-instruct"
CONFIG = MAN.config(NAME)
TOY = qwen3next.toy(CONFIG)
MIX = dict(seq=96, pool_batches=1, zipf_exponent=1.1, follow_probability=0.5,
           doc_length_median=12, doc_length_sigma=1.0, doc_length_min=2)
SPEC = dict(grad_leaves="all", loss_rtol=1e-5, grad_rel_l2=1e-3,
            grad_median_rel_l2=1e-4)
# the catalog row's config (model-configs guide, architectures.jsonl)
PUBLISHED = dict(
    decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
    hidden_act="silu", hidden_size=2048, intermediate_size=5120,
    linear_conv_kernel_dim=4, linear_key_head_dim=128,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_value_head_dim=128, max_position_embeddings=262144,
    mlp_only_layers=[], model_type="qwen3_next", moe_intermediate_size=512,
    norm_topk_prob=True, num_attention_heads=16, num_experts=512,
    num_experts_per_tok=10, num_hidden_layers=48, num_key_value_heads=2,
    partial_rotary_factor=0.25, rms_norm_eps=1e-06, rope_scaling=None,
    rope_theta=10000000, shared_expert_intermediate_size=512,
    tie_word_embeddings=False, use_sliding_window=False, vocab_size=151936)
NEW_METRICS = {"linear_attn_ms_per_step", "gated_delta_rule_ms_per_step",
               "gated_delta_rule_roofline"}


@pytest.fixture
def one_device_mesh():
    before = mesh_mod.get_mesh()
    yield mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    mesh_mod.set_mesh(before)


@pytest.fixture
def built(one_device_mesh):
    return qwen3next.build(TOY, TOY["run"], seed=3, mesh=one_device_mesh)


def rows(seed=5):
    ids, labels = traffic_gen.make_pool(MIX, TOY["vocab_used"],
                                        TOY["eos_token_id"], 2, seed=seed)
    return ids[0], labels[0]


def test_the_cell_resolves():
    assert MAN.problems() == []
    cell = MAN.cell(CELL)
    assert cell["entry"]["chips"] == 1 and cell["traffic"]["seq"] == 8192
    w = cell["workload"]
    assert w["kind"] == "train" and w["mesh"] == {"data": 1}
    assert w["rows_per_chip"] in w["rows_ladder"] == [1, 2, 4]
    assert (w["sync_every"], w["warmup_steps"], w["trace_steps"]) == (4, 3, 8)
    names = {m["name"] for m in cell["per_layer"]}
    # the expert layers are read by the accepted metric of their scope, the
    # cell appended to its list; the QK norm with its rotation by a metric
    # of the cell's own (sdar's selftest keeps qk_norm_ms_per_step to sdar)
    assert NEW_METRICS | {"flash_attn_ms_per_step", "attn_path_ms_per_step",
                          "lm_head_loss_ms_per_step", "moe_ms_per_step",
                          "qk_norm_rope_ms_per_step"} <= names
    assert not names & {"flash_attn_roofline", "flash_window_roofline",
                        "moe_experts_roofline", "rope_ms_per_step",
                        "qk_norm_ms_per_step"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gb", "setup_s"}
    for other in sorted(MAN.workloads):
        if other != CELL:
            assert not NEW_METRICS & {m["name"]
                                      for m in MAN.cell(other)["per_layer"]}
    for name in NEW_METRICS:
        assert MAN.per_layer[name]["layer"] == "linear attention"
    # the traffic is seq4096's at 8,192 positions
    other = MAN.cell("laguna-xs2.seq4096")["traffic"]
    assert {k: v for k, v in cell["traffic"].items()
            if k not in ("seq", "why")} == {
                k: v for k, v in other.items() if k not in ("seq", "why")}


def test_no_width_differs_from_the_published_config():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CONFIG["reduced"] == MAN.configs[NAME]["reduced"]
    assert MAN.configs[NAME]["source"] == CONFIG["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in CONFIG["reduced"]}
    assert set(CONFIG["changed"]) == set(CONFIG["reduced"])
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 16
    assert CONFIG["deployment"]["held_experts"] == [0, 512 // 16]
    assert CONFIG["vocab_size"] == 151936 // 8
    assert CONFIG["num_hidden_layers"] == CONFIG["full_attention_interval"]
    assert 0 <= CONFIG["eos_token_id"] < CONFIG["vocab_used"] \
        == CONFIG["vocab_size"]
    for key in ("zero_centred_norms", "attention_gate",
                "qk_normalisation_in_the_rule", "decay", "chunk",
                "convolution", "gated_norm", "multi_token_prediction",
                "router", "eos_token_id", "initialisers"):
        assert key in CONFIG["assumed"]
    assert qwen3next.arch(CONFIG)["layers"] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert qwen3next.arch(CONFIG)["rotary_dim"] == 64


def test_shapes_give_the_counts_the_file_states():
    linear = qwen3next.layer_params(CONFIG, "linear_attention")
    full = qwen3next.layer_params(CONFIG, "full_attention")
    assert linear == {
        "in_proj_qkvz": 2048 * 12288, "in_proj_ba": 2048 * 64,
        "conv": 8192 * 4, "decay": 64, "mixer_norms": 128,
        "out_proj": 4096 * 2048, "norms": 4096, "router": 2048 * 512,
        "shared": 3 * 2048 * 512, "shared_gate": 2048,
        "experts": 32 * 3_145_728}
    ffn = 4_196_352 + 4_096 + 32 * 3_145_728
    assert sum(linear.values()) == 33_718_464 + ffn
    assert sum(full.values()) == 27_263_488 + ffn
    assert qwen3next.param_count(CONFIG) == CONFIG["flops"]["N"] \
        == 3 * 33_718_464 + 27_263_488 + 4 * ffn + 2 * 18_992 * 2_048 + 2_048 \
        == 625_667_136
    f = qwen3next.model_flops_per_token(CONFIG, 8192)
    # met in a product: the mixers' projections, and a layer's router,
    # shared expert, its gate and 10 x 32 / 512 of an expert
    met_ffn = 1_048_576 + 3_145_728 + 2_048 + 0.625 * 3_145_728
    met = 3 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) \
        + (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048) + 4 * met_ffn \
        + 18_992 * 2_048
    assert met == CONFIG["flops"]["met_per_token"] == 191_864_832
    assert f["six_n"] == 6 * met
    assert f["attention"] == 12 * 16 * 256 * 8193 / 2 \
        == CONFIG["flops"]["attention_at_seq_8192"]
    assert f["recurrence"] == 3 * 32 * 3 * (6 * 128 * 128) \
        == CONFIG["flops"]["recurrence"] == 28_311_552
    assert f["total"] == f["six_n"] + f["attention"] + f["recurrence"] \
        == CONFIG["flops"]["total_at_seq_8192"] == 1_380_851_712
    # how the program recomputes is its business: no count follows it
    other = dict(CONFIG, run=dict(CONFIG["run"], checkpoint_blocks=True))
    assert qwen3next.model_flops_per_token(other, 8192) == f


def test_cost_function_against_hand_values():
    """Two rows of 8,192 positions on the published widths: 3 linear
    layers, 32 value heads, d_k = d_v = 128."""
    got = kernel_costs_linear.gated_delta_rule(CONFIG, 2, 8192)
    calls = 2 * 8192 * 32 * 3
    assert kernel_costs_linear.linear_layers(CONFIG) == 3
    # nine d_k x d_v products a head a position, 2 operations a term
    assert got["flops"] == calls * 9 * 2 * 128 * 128 == 463_856_467_968
    # q k v o and their gradients in bf16: 8 x 128 x 2; g beta and theirs
    # in float32: 4 x 4
    assert got["bytes"] == calls * (8 * 128 * 2 + 16) == 3_246_391_296
    one = kernel_costs_linear.gated_delta_rule(CONFIG, 1, 8192)
    assert got == {k: 2 * v for k, v in one.items()}
    other = dict(CONFIG, run=dict(CONFIG["run"], checkpoint_blocks=True))
    assert kernel_costs_linear.gated_delta_rule(other, 2, 8192) == got
    reading = types.SimpleNamespace(
        config=CONFIG, rows_per_chip=2, seq=8192,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    spec = MAN.layer_metric("gated_delta_rule_roofline")
    assert spec["args"]["scopes"] == MAN.layer_metric(
        "gated_delta_rule_ms_per_step")["args"]["scopes"]
    scoped, kept = roofline_share_of.named_scopes_per_step.reduce, None
    roofline_share_of.named_scopes_per_step.reduce = \
        lambda r, scopes: (90.0, {})
    try:
        value, note = roofline_share_of.reduce(reading, **spec["args"])
        roofline_share_of.named_scopes_per_step.reduce = \
            lambda r, scopes: (None, {})
        assert roofline_share_of.reduce(reading, **spec["args"]) is None
    finally:
        roofline_share_of.named_scopes_per_step.reduce = scoped
    assert note["bound"] == "memory"
    assert value == pytest.approx(100 * got["bytes"] / 819e9 / 0.090)
    assert 0 < value < 100 and kept is None


def test_reference_recurrence_by_hand():
    """Three positions, one head, a 2 x 2 state, worked out on paper.

    t=1: g=0, beta=1, k=(1,0), v=(2,4): S=[[2,4],[0,0]]; q=(1,0): o=(2,4).
    t=2: g=ln(1/2), beta=1/2, k=(0,1), v=(6,2): S decays to [[1,2],[0,0]];
         S^T k = 0, u = (3,1): S=[[1,2],[3,1]]; q=(1,1): o=(4,3).
    t=3: g=0, beta=1, k=(1,0), v=(5,5): S^T k=(1,2), u=(4,3):
         S=[[5,5],[3,1]] (the key's row is overwritten); q=(1,-1): o=(2,4)."""
    q = jnp.asarray([[1., 0.], [1., 1.], [1., -1.]])[None, :, None, :]
    k = jnp.asarray([[1., 0.], [0., 1.], [1., 0.]])[None, :, None, :]
    v = jnp.asarray([[2., 4.], [6., 2.], [5., 5.]])[None, :, None, :]
    g = jnp.asarray([0., np.log(0.5), 0.])[None, :, None]
    beta = jnp.asarray([1., 0.5, 1.])[None, :, None]
    want = np.asarray([[2., 4.], [4., 3.], [2., 4.]])[None, :, None, :]
    for remat in (False, True):
        got = reference.delta_rule(q, k, v, g, beta, remat)
        np.testing.assert_allclose(got, want, atol=1e-6)
    # and the program's two paths say the same
    from paddle_tpu.nn import functional as F
    for kw in ({"path": "recurrent"}, {"chunk": 2}, {"chunk": 4}):
        np.testing.assert_allclose(
            F.gated_delta_rule(q, k, v, g, beta, **kw), want, atol=1e-5)


def test_reference_scans_positions_and_segments_change_nothing():
    ks = jax.random.split(jax.random.key(0), 5)
    q, k = (jax.random.normal(x, (2, 160, 3, 8)) * 0.3 for x in ks[:2])
    v = jax.random.normal(ks[2], (2, 160, 3, 6))
    g = -jax.random.uniform(ks[3], (2, 160, 3))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, 160, 3)))
    plain = reference.delta_rule(q, k, v, g, beta, False)
    np.testing.assert_allclose(reference.delta_rule(q, k, v, g, beta, True),
                               plain, atol=1e-6)
    text = str(jax.make_jaxpr(lambda *a: reference.delta_rule(*a, False))(
        q, k, v, g, beta))
    assert "length=160" in text          # a step a position, not a chunk


def test_reference_convolution_is_four_shifted_products():
    x = jax.random.normal(jax.random.key(0), (2, 30, 5))
    w = jax.random.normal(jax.random.key(1), (5, 4))
    got = reference.causal_conv(x, w)
    for c in range(5):
        want = np.convolve(np.asarray(x[1, :, c]), np.asarray(w[c, ::-1]))[:30]
        np.testing.assert_allclose(got[1, :, c], want, atol=1e-5)


def test_columns_map_the_programs_order_onto_the_published_grouping():
    lin = dict(key_heads=2, value_heads=4, d_k=3, d_v=2, conv_kernel=4)
    cols = qwen3next.grouped_columns(lin)
    # program: q0 q1 | k0 k1 | v0 v1 v2 v3 | z0 z1 z2 z3 (3, 3, 2, 2 lanes)
    # reference, per key head: q | k | its two value heads' v | their z
    q, k, v, z = 0, 6, 12, 20
    assert cols["qkvz_w"].tolist() == (
        [q, q + 1, q + 2, k, k + 1, k + 2, v, v + 1, v + 2, v + 3,
         z, z + 1, z + 2, z + 3]
        + [q + 3, q + 4, q + 5, k + 3, k + 4, k + 5, v + 4, v + 5, v + 6,
           v + 7, z + 4, z + 5, z + 6, z + 7])
    # program: b0..b3 | a0..a3; reference per key head: b b | a a
    assert cols["ba_w"].tolist() == [0, 1, 4, 5, 2, 3, 6, 7]
    full = qwen3next.grouped_columns(qwen3next.arch(CONFIG)["linear"])
    assert sorted(full["qkvz_w"].tolist()) == list(range(12288))
    assert sorted(full["ba_w"].tolist()) == list(range(64))


def test_param_count_is_what_the_program_builds(built):
    n = sum(int(np.prod(v.shape))
            for v in built.trainer.state["params"].values())
    assert n == qwen3next.param_count(TOY)
    # 3 top leaves; a block: 2 norms + 8 of the expert layer + 7 (linear)
    # or 6 (full)
    assert len(built.leaf_names("all")) == 3 + 3 * 17 + 16


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint_blocks"])
def test_reference_equals_program(one_device_mesh, checkpoint):
    """Loss and every gradient leaf, float32 on both sides, through the
    harness's own comparison."""
    recipe = dict(TOY["run"], checkpoint_blocks=checkpoint)
    built = qwen3next.build(TOY, recipe, seed=3, mesh=one_device_mesh)
    params = dict(built.trainer.state["params"])
    got = compare.against_reference(built, reference, params, *rows(),
                                    dict(SPEC, reference_remat=bool(
                                        checkpoint)))
    assert got["ok"], got
    assert got["grad_leaves"] == 3 + 3 * 17 + 16


WRONG = ("plain_norms", "no_decay", "no_beta", "no_l2_norm", "conv_reads_ahead",
         "gate_a_head", "no_shared_gate", "rope_on_all_lanes",
         "state_in_bf16")


@pytest.mark.parametrize("wrong", WRONG)
def test_comparison_sees_a_wrong_term(built, wrong, monkeypatch):
    """Not vacuous: each assumed or easily mistaken term, changed in the
    reference, is out of tolerance."""
    if wrong == "plain_norms":
        monkeypatch.setattr(reference, "norm", lambda x, w, eps: x
                            * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                            + eps) * w)
    elif wrong in ("no_decay", "no_beta", "no_l2_norm"):
        right = reference.delta_rule

        def rule(q, k, v, g, beta, remat):
            if wrong == "no_decay":
                g = jnp.zeros_like(g)
            elif wrong == "no_beta":
                beta = jnp.ones_like(beta)
            else:
                q, k = q * 1.5, k * 1.5
            return right(q, k, v, g, beta, remat)
        monkeypatch.setattr(reference, "delta_rule", rule)
    elif wrong == "conv_reads_ahead":
        right_conv = reference.causal_conv
        monkeypatch.setattr(reference, "causal_conv", lambda x, w: right_conv(
            jnp.roll(x, -1, axis=1), w))
    elif wrong == "gate_a_head":
        right_attention = reference.attention

        def attention(u, p, arch, eps, remat):
            """One gate a head: the mean of its lanes' gates."""
            d = arch["head_dim"]
            w = jnp.reshape(p["q_w"], (-1, arch["heads"], 2 * d))
            gate = jnp.broadcast_to(jnp.mean(w[..., d:], -1, keepdims=True),
                                    w[..., d:].shape)
            w = jnp.concatenate([w[..., :d], gate], axis=-1)
            return right_attention(
                u, dict(p, q_w=jnp.reshape(w, p["q_w"].shape)), arch, eps,
                remat)
        monkeypatch.setattr(reference, "attention", attention)
    elif wrong == "no_shared_gate":
        right_moe = reference.moe
        monkeypatch.setattr(
            reference, "moe", lambda u, p, arch, remat=False: right_moe(
                u, dict(p, shared_expert_gate_w=jnp.zeros_like(
                    p["shared_expert_gate_w"])), arch, remat))
    elif wrong == "rope_on_all_lanes":
        right_rope = reference.apply_rope
        monkeypatch.setattr(reference, "apply_rope", lambda x, theta, r:
                            right_rope(x, theta, x.shape[-1]))
    elif wrong == "state_in_bf16":
        monkeypatch.setattr(reference, "_state", lambda s: s.astype(
            jnp.bfloat16).astype(jnp.float32))
    got = compare.against_reference(
        built, reference, dict(built.trainer.state["params"]), *rows(), SPEC)
    assert not got["ok"], got


def test_the_family_reports_routing_and_load(built, capsys):
    import json

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
    assert line["event"] == "routing_agreement" and line["tokens"] == 192
    assert line["assignments_chosen_differently_by_layer"] == [0.0] * 4
    names = [name for name, _ in built.sparse_layers()]
    assert names == [f"decoder.h.{i}.moe" for i in range(4)]
    chosen, _ = built.chosen_experts(params, ids)
    for i, got in enumerate(chosen):
        assert got.shape == (192, 2)
        held = int((got >= 8).sum())                  # experts 8-15 of 16
        assert line["held_assignments_over_expected_by_layer"][i] \
            == held / 192
        series = counters["moe_held_assignments_total"]["series"]
        assert [v for k, v in series.items() if names[i] in k] == [held]
    assert {"moe_tokens_routed_total", "moe_max_load_over_mean"} \
        <= set(counters)


def test_new_metrics_read_nothing_from_a_trace_without_their_scopes(tmp_path):
    """On a trace of the GPT program, which opens neither new scope, the
    three new readers find no time and do not raise: a program that lacks
    what this configuration added leaves the metrics out."""
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
        rows_per_chip=1, seq=8192, peaks=manifest.peaks("TPU v5 lite"))
    reading._scopes = xplane_scopes.Scopes(path)
    for metric in sorted(NEW_METRICS):
        spec = MAN.layer_metric(metric)
        reducer = manifest.plugin("reducers", spec["reducer"])
        value = reducer.reduce(reading, **spec["args"])
        if isinstance(value, tuple):
            value = value[0]
        assert not value, metric
