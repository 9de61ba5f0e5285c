"""The ``nemotronh`` family, its reference and the cell
``nemotron-3-nano-30b-a3b.seq8192`` without a chip: the configuration keeps
every published width, the counts are the shapes', the reference's scan is
the definition worked out by hand, the reference is the program's
mathematics in float32, the two controls the cell's limits are placed
against are refused, and the new cost function gives values worked out by
hand."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, kernel_costs_ssd, manifest, traffic_gen
from benchmark.families import nemotronh
from benchmark.reducers import roofline_share_of
from benchmark.reference import nemotronh as reference
from paddle_tpu import telemetry
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.incubate.moe import DroplessMoELayer

MAN = manifest.Manifest()
CELL = "nemotron-3-nano-30b-a3b.seq8192"
NAME = "nemotron-3-nano-30b-a3b"
CONFIG = MAN.config(NAME)
TOY = nemotronh.toy(CONFIG)
MIX = dict(seq=96, pool_batches=1, zipf_exponent=1.1, follow_probability=0.5,
           doc_length_median=12, doc_length_sigma=1.0, doc_length_min=2)
SPEC = dict(grad_leaves="all", loss_rtol=1e-5, grad_rel_l2=1e-3,
            grad_median_rel_l2=1e-4)
NEW_METRICS = {"mamba_ms_per_step", "ssd_scan_ms_per_step",
               "ssd_scan_roofline"}
# 3 top leaves; a Mamba layer 9 (its norm and 8 of the mixer), an expert
# layer 6 (norm, router, shared up and down, experts up and down), the
# attention layer 5
LEAVES = 3 + 4 * 9 + 4 * 6 + 5


def catalog_row():
    """The catalog row's ``config`` (model-configs guide,
    ``architectures.jsonl``), as ISSUE 39 quotes it."""
    return dict(
        attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
        head_dim=128, hidden_size=2688,
        hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                                "EMEMEMEME",
        intermediate_size=1856, layer_norm_epsilon=1e-05, mamba_head_dim=64,
        mamba_hidden_act="silu", mamba_num_heads=64, mamba_proj_bias=False,
        max_position_embeddings=262144, mlp_bias=False,
        mlp_hidden_act="relu2", model_type="nemotron_h",
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_group=1, n_groups=8, n_routed_experts=128, n_shared_experts=1,
        norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32,
        num_experts_per_tok=6, num_hidden_layers=52, num_key_value_heads=2,
        num_logits_to_keep=1, partial_rotary_factor=1,
        rescale_prenorm_residual=True, residual_in_fp32=False,
        rope_theta=10000, routed_scaling_factor=2.5, sliding_window=None,
        ssm_state_size=128, tie_word_embeddings=False, time_step_floor=0.0001,
        time_step_max=0.1, time_step_min=0.001, topk_group=1, use_bias=False,
        use_conv_bias=True, use_mamba_kernels=True, vocab_size=131072)


@pytest.fixture
def one_device_mesh():
    before = mesh_mod.get_mesh()
    yield mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    mesh_mod.set_mesh(before)


@pytest.fixture
def built(one_device_mesh):
    return nemotronh.build(TOY, TOY["run"], seed=3, mesh=one_device_mesh)


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
    # cell appended to its list
    assert NEW_METRICS | {"flash_attn_ms_per_step", "attn_path_ms_per_step",
                          "lm_head_loss_ms_per_step",
                          "moe_ms_per_step"} <= names
    assert not names & {"flash_attn_roofline", "moe_experts_roofline",
                        "rope_ms_per_step", "qk_norm_ms_per_step",
                        "linear_attn_ms_per_step"}
    for other in sorted(MAN.workloads):
        if other != CELL:
            assert not NEW_METRICS & {m["name"]
                                      for m in MAN.cell(other)["per_layer"]}
    for name in NEW_METRICS:
        assert MAN.per_layer[name]["layer"] == "state-space mixer"
    # the traffic is qwen3-next's, shared
    assert cell["traffic"] == MAN.cell(
        "qwen3-next-80b-a3b-instruct.seq8192")["traffic"]


def test_no_width_differs_from_the_published_config():
    published = catalog_row()
    changed = {k for k, v in published.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert CONFIG["reduced"] == MAN.configs[NAME]["reduced"]
    assert MAN.configs[NAME]["source"] == CONFIG["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert CONFIG["published"] == {k: published[k] for k in CONFIG["reduced"]}
    assert set(CONFIG["changed"]) == set(CONFIG["reduced"])
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 16
    assert CONFIG["deployment"]["held_experts"] == [0, 128 // 16]
    assert CONFIG["vocab_size"] == 131072 // 8
    assert 0 <= CONFIG["eos_token_id"] < CONFIG["vocab_used"] \
        == CONFIG["vocab_size"]
    for key in ("no_rotation", "initialisers", "rescale_prenorm_residual",
                "learning_rate", "state_across_documents", "router",
                "experts"):
        assert key in CONFIG["assumed"]
    a = nemotronh.arch(CONFIG)
    assert a["layers"] == ["mamba", "moe", "mamba", "moe", "mamba",
                           "attention", "moe", "mamba", "moe"]
    assert a["mamba"] == {"heads": 64, "head_dim": 64, "groups": 8,
                          "state": 128, "conv_kernel": 4}


def test_shapes_give_the_counts_the_file_states():
    mamba = nemotronh.layer_params(CONFIG, "mamba")
    assert mamba == {"in_proj": 2688 * 10304, "conv": 6144 * 5,
                     "heads": 3 * 64, "mixer_norm": 4096,
                     "out_proj": 4096 * 2688, "norm": 2688}
    assert sum(mamba.values()) == 38_742_208 + 2688
    attention = nemotronh.layer_params(CONFIG, "attention")
    assert sum(attention.values()) == 23_396_352 + 2688
    moe = nemotronh.layer_params(CONFIG, "moe")
    assert moe == {"router": 2688 * 128, "shared": 2 * 2688 * 3712,
                   "experts": 8 * 2 * 2688 * 1856, "norm": 2688}
    assert nemotronh.param_count(CONFIG) == CONFIG["flops"]["N"] \
        == 4 * (38_742_208 + 2688) + (23_396_352 + 2688) \
        + 4 * sum(moe.values()) + 2 * 16_384 * 2688 + 2688 == 666_962_944
    f = nemotronh.model_flops_per_token(CONFIG, 8192)
    # met in a product: the mixers' two projections, q k v o, and an expert
    # layer's router, shared expert and 6 x 8 / 128 of an expert
    met = 4 * (2688 * 10304 + 4096 * 2688) + 23_396_352 \
        + 4 * (2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856) \
        + 16_384 * 2688
    assert met == CONFIG["flops"]["met_per_token"] == 318_431_232
    assert f["six_n"] == 6 * met == CONFIG["flops"]["six_n"]
    assert f["attention"] == 12 * 32 * 128 * 8193 / 2 \
        == CONFIG["flops"]["attention_at_seq_8192"]
    assert f["scan"] == 4 * 64 * 12 * 64 * 128 == CONFIG["flops"]["scan"] \
        == 25_165_824
    assert f["total"] == f["six_n"] + f["attention"] + f["scan"] \
        == CONFIG["flops"]["total_at_seq_8192"] == 2_137_104_384
    # how the program recomputes is its business: no count follows it
    other = dict(CONFIG, run=dict(CONFIG["run"], checkpoint_blocks=False))
    assert nemotronh.model_flops_per_token(other, 8192) == f


def test_cost_function_against_hand_values():
    """Two rows of 8,192 positions on the published widths: 4 Mamba layers,
    64 heads of P = 64 over 8 groups of N = 128."""
    got = kernel_costs_ssd.ssd_scan(CONFIG, 2, 8192)
    positions = 2 * 8192 * 4
    assert kernel_costs_ssd.mamba_layers(CONFIG) == 4
    # six P x N products a head a position, 2 operations a term
    assert got["flops"] == positions * 64 * 6 * 2 * 64 * 128 \
        == 412_316_860_416
    # a head: x y dx dy in bf16 (4 x 64 x 2) and dt ddt in float32 (2 x 4);
    # a group: B C dB dC in bf16 (4 x 128 x 2)
    assert got["bytes"] == positions * (64 * 520 + 8 * 1024) \
        == 2_717_908_992
    one = kernel_costs_ssd.ssd_scan(CONFIG, 1, 8192)
    assert got == {k: 2 * v for k, v in one.items()}
    reading = types.SimpleNamespace(
        config=CONFIG, rows_per_chip=2, seq=8192,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    spec = MAN.layer_metric("ssd_scan_roofline")
    assert spec["args"]["scopes"] == MAN.layer_metric(
        "ssd_scan_ms_per_step")["args"]["scopes"]
    scoped = roofline_share_of.named_scopes_per_step.reduce
    roofline_share_of.named_scopes_per_step.reduce = \
        lambda r, scopes: (40.0, {})
    try:
        value, note = roofline_share_of.reduce(reading, **spec["args"])
        roofline_share_of.named_scopes_per_step.reduce = \
            lambda r, scopes: (None, {})
        assert roofline_share_of.reduce(reading, **spec["args"]) is None
    finally:
        roofline_share_of.named_scopes_per_step.reduce = scoped
    assert note["bound"] == "memory"
    assert value == pytest.approx(100 * got["bytes"] / 819e9 / 0.040)
    assert 0 < value < 100


def test_reference_scan_by_hand():
    """Three positions, one head, P = 1, N = 2, worked out on paper.

    t=1: dt=1, A=ln(1/2) -> decay 1/2; x=2, B=(1,0): S = (2, 0); C=(1,1):
         y = 2.
    t=2: dt=1: S decays to (1, 0); x=4, B=(0,1): S = (1, 4); C=(0,1): y = 4.
    t=3: dt=2: decay 1/4, S = (1/4, 1); x=1, B=(1,1): S = (2.25, 3);
         C=(1,0): y = 2.25."""
    x = jnp.asarray([2.0, 4.0, 1.0])[None, :, None, None]
    dt = jnp.asarray([1.0, 1.0, 2.0])[None, :, None]
    a = jnp.asarray([np.log(0.5)], jnp.float32)
    b = jnp.asarray([[1., 0.], [0., 1.], [1., 1.]])[None, :, None, :]
    c = jnp.asarray([[1., 1.], [0., 1.], [1., 0.]])[None, :, None, :]
    want = np.asarray([2.0, 4.0, 2.25])[None, :, None, None]
    for remat in (False, True):
        got = reference.scan(x, dt, a, b, c, remat)
        np.testing.assert_allclose(got, want, atol=1e-6)
    # and the program's two paths say the same
    from paddle_tpu.nn import functional as F
    for kw in ({"path": "recurrent"}, {"chunk": 2}, {"chunk": 4}):
        np.testing.assert_allclose(
            F.ssd_scan(x, dt, a, b, c, **kw), want, atol=1e-5)


def test_reference_scans_positions_and_segments_change_nothing(monkeypatch):
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (2, 150, 3, 4))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 150, 3)))
    a = -jnp.asarray([0.5, 1.0, 2.0])
    b, c = (jax.random.normal(k, (2, 150, 3, 5)) for k in ks[2:])
    whole = reference.scan(x, dt, a, b, c, False)
    monkeypatch.setattr(reference, "SEGMENT", 32)
    for remat in (False, True):
        np.testing.assert_allclose(reference.scan(x, dt, a, b, c, remat),
                                   whole, atol=1e-5)
    text = str(jax.make_jaxpr(lambda *v: reference.scan(*v, False))(
        x, dt, a, b, c))
    assert "length=32" in text           # a step a position in a segment


def test_param_count_is_what_the_program_builds(built):
    n = sum(int(np.prod(v.shape))
            for v in built.trainer.state["params"].values())
    assert n == nemotronh.param_count(TOY)
    assert len(built.leaf_names("all")) == LEAVES
    names = set(built.leaf_names("all"))
    assert "decoder.h.0.mamba.conv_bias" in names
    assert "decoder.h.5.attn.q_proj.weight" in names
    assert not any("post_attn_norm" in n or "gate_proj" in n for n in names)


def test_staging_the_cut_counts_four_chunked_scans(built):
    """The cut's forward at toy widths: one chunked scan a Mamba layer,
    staged anew on every staging."""
    ids, _ = rows()
    params = dict(built.trainer.state["params"])
    with telemetry.scope(profile=False) as tel:
        for staging in (1, 2):
            jax.jit(lambda p: built._loss(p, ids, ids)).lower(params)
            calls = tel.registry.get("ssd_scan_calls_staged_total")
            assert calls.value(path="chunked") == 4 * staging
        assert calls.value(path="recurrent") == 0
        # 96 positions in chunks of 16: six chunk states a layer
        assert tel.registry.get("ssd_chunks_total").value() == 2 * 4 * 6


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint_blocks"])
def test_reference_equals_program(one_device_mesh, checkpoint):
    """Loss and every gradient leaf, float32 on both sides, through the
    harness's own comparison, with the routers' biases away from zero."""
    recipe = dict(TOY["run"], checkpoint_blocks=checkpoint)
    built = nemotronh.build(TOY, recipe, seed=3, mesh=one_device_mesh)
    for i, (_, m) in enumerate(built.sparse_layers()):
        m.e_score_correction_bias = 0.05 * jax.random.normal(
            jax.random.key(i), (16,))
    params = dict(built.trainer.state["params"])
    got = compare.against_reference(built, reference, params, *rows(),
                                    dict(SPEC, reference_remat=bool(
                                        checkpoint)))
    assert got["ok"], got
    assert got["grad_leaves"] == LEAVES


def test_the_eight_shares_add_up():
    """The guide's share test: 16 experts in 8 shares of 2. The routed
    parts of all shares plus the shared expert ONCE are the uncut
    reference's layer; and each share's layer is the reference's given the
    same share."""
    d, f, fs, experts, chips, k = 32, 16, 24, 16, 8, 3
    ks = jax.random.split(jax.random.key(2), 7)
    p = {"router_w": jax.random.normal(ks[0], (d, experts)) * 0.3,
         "shared_up_w": jax.random.normal(ks[1], (d, fs)) * 0.2,
         "shared_down_w": jax.random.normal(ks[2], (fs, d)) * 0.2,
         "experts_up_w": jax.random.normal(ks[3], (experts, d, f)) * 0.2,
         "experts_down_w": jax.random.normal(ks[4], (experts, f, d)) * 0.2}
    bias = 0.1 * jax.random.normal(ks[5], (experts,))
    u = jax.random.normal(ks[6], (2, 40, d))
    arch = {"top_k": k, "routed_scaling_factor": 2.5}
    whole, _ = reference.moe(u, p, dict(arch, held=(0, experts)), bias)
    shared = reference.relu2(u @ p["shared_up_w"]) @ p["shared_down_w"]
    total = 0.0
    for chip in range(chips):
        first, count = chip * experts // chips, experts // chips
        layer = DroplessMoELayer(d, f, experts, k, held=(first, count),
                                 routed_scaling_factor=2.5, d_shared=fs,
                                 selection_bias=True, activation="relu2")
        layer.e_score_correction_bias = bias
        layer.router.weight.value = p["router_w"]
        for name in ("up", "down"):
            getattr(layer.shared_expert, f"{name}_proj").weight.value = \
                p[f"shared_{name}_w"]
            getattr(layer.experts, f"{name}_proj").value = \
                p[f"experts_{name}_w"][first:first + count]
        got = layer(u)
        part, _ = reference.moe(
            u, dict(p, **{f"experts_{n}_w": p[f"experts_{n}_w"][
                first:first + count] for n in ("up", "down")}),
            dict(arch, held=(first, count)), bias)
        np.testing.assert_allclose(got, part, rtol=1e-4, atol=1e-5)
        total = total + got
    np.testing.assert_allclose(total - (chips - 1) * shared, whole,
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(whole - shared).mean()) > 0.01


WRONG = ("rotation", "norm_before_gate", "one_norm_group", "no_conv_bias",
         "no_d", "silu_experts", "softmax_router", "no_scaling",
         "bias_weighs")


@pytest.mark.parametrize("wrong", WRONG)
def test_comparison_sees_a_wrong_term(built, wrong, monkeypatch):
    """Not vacuous: each assumed or easily mistaken term, changed in the
    reference, is out of tolerance."""
    if wrong == "rotation":
        monkeypatch.setattr(reference, "attention", rotated_attention)
    elif wrong == "norm_before_gate":
        def gated_norm(y, z, w, groups, eps):
            g = jnp.reshape(y, y.shape[:-1] + (groups, -1))
            g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
            return jnp.reshape(g, y.shape) * w * reference.silu(z)
        monkeypatch.setattr(reference, "gated_norm", gated_norm)
    elif wrong == "one_norm_group":
        right_norm = reference.gated_norm
        monkeypatch.setattr(reference, "gated_norm",
                            lambda y, z, w, groups, eps: right_norm(
                                y, z, w, 1, eps))
    elif wrong == "no_conv_bias":
        right_conv = reference.causal_conv
        monkeypatch.setattr(reference, "causal_conv",
                            lambda x, w, b: right_conv(x, w, 0.0 * b))
    elif wrong == "no_d":
        right_mamba = reference.mamba
        monkeypatch.setattr(reference, "mamba", lambda u, p, *a: right_mamba(
            u, dict(p, d=0.0 * p["d"]), *a))
    elif wrong == "silu_experts":
        monkeypatch.setattr(reference, "relu2", reference.silu)
    else:
        right_route = reference.route

        def route(u, router_w, arch, bias):
            if wrong == "no_scaling":
                return right_route(u, router_w,
                                   dict(arch, routed_scaling_factor=1.0),
                                   bias)
            if wrong == "bias_weighs":
                s = jax.nn.sigmoid(u @ router_w) + bias
            else:
                s = jax.nn.softmax(u @ router_w, -1)
            top, ids = jax.lax.top_k(s, arch["top_k"])
            return ids, top / jnp.sum(top, -1, keepdims=True) \
                * arch["routed_scaling_factor"]
        monkeypatch.setattr(reference, "route", route)
        for i, (_, m) in enumerate(built.sparse_layers()):
            m.e_score_correction_bias = 0.3 * jnp.abs(jax.random.normal(
                jax.random.key(i), (16,)))
    got = compare.against_reference(
        built, reference, dict(built.trainer.state["params"]), *rows(), SPEC)
    assert not got["ok"], got


def rotated_attention(u, p, arch, remat):
    """The reference's attention with rotate-half RoPE at theta 1e4 on q and
    k: what the published layer does NOT do."""
    b, s, _ = u.shape
    d, kv, heads = arch["head_dim"], arch["kv_heads"], arch["heads"]
    inv = 1e4 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    angles = jnp.concatenate([angles, angles], -1)[:, None]

    def rope(y):
        half = jnp.concatenate([-y[..., d // 2:], y[..., :d // 2]], -1)
        return y * jnp.cos(angles) + half * jnp.sin(angles)

    q = rope(jnp.reshape(u @ p["q_w"], (b, s, heads, d)))
    k = jnp.repeat(rope(jnp.reshape(u @ p["k_w"], (b, s, kv, d))),
                   heads // kv, 2)
    v = jnp.repeat(jnp.reshape(u @ p["v_w"], (b, s, kv, d)), heads // kv, 2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(1.0 * d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    o = jnp.einsum("bhst,bthd->bshd",
                   jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1), v)
    return jnp.reshape(o, (b, s, heads * d)) @ p["o_w"]


def as_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def test_the_cells_limits_refuse_both_controls(built, monkeypatch):
    """The two controls the cell's limits are placed against, each refused
    under the limits the workload file commits where the sound reference
    passes them: every product's operands rounded to ``float8_e4m3``, and
    the state NOT carried from segment to segment of the scan (each of the
    published chunk's 128 positions starting from zero; 16 at this toy
    size, the program's own chunk)."""
    check = MAN.cell(CELL)["workload"]["check"]
    spec = {k: check[k] for k in ("grad_leaves", "loss_rtol", "grad_rel_l2",
                                  "grad_median_rel_l2")}
    params = dict(built.trainer.state["params"])
    sound = compare.against_reference(built, reference, params, *rows(),
                                      spec)
    assert sound["ok"], sound
    with monkeypatch.context() as m:
        m.setattr(reference, "_mm", lambda a, b: as_fp8(a) @ as_fp8(b))
        got = compare.against_reference(built, reference, params, *rows(),
                                        spec)
        assert not got["ok"], got
    with monkeypatch.context() as m:
        m.setattr(reference, "SEGMENT", 16)
        m.setattr(reference, "_entering", jnp.zeros_like)
        got = compare.against_reference(built, reference, params, *rows(),
                                        spec)
        assert not got["ok"], got
    for key in ("loss_rtol", "grad_rel_l2", "grad_median_rel_l2",
                "loss_margin", "sample_rows"):
        assert len(check["why"][key]) > 100, key
    assert "float8_e4m3" in json.dumps(check["why"])
    assert "chunk" in check["why"]["controls"]


def test_the_family_reports_routing_and_load(built, capsys):
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
    assert names == [f"decoder.h.{i}.moe" for i in (1, 3, 6, 8)]
    chosen, _ = built.chosen_experts(params, ids)
    for i, got in enumerate(chosen):
        assert got.shape == (192, 2)
        held = int(((got >= 4) & (got < 8)).sum())      # experts 4-7 of 16
        assert line["held_assignments_over_expected_by_layer"][i] \
            == held * 16 / (192 * 2 * 4)
    assert {"moe_tokens_routed_total", "moe_max_load_over_mean",
            "moe_held_assignments_total"} <= set(counters)


def test_the_routers_biases_start_balanced(one_device_mesh):
    """``balanced_biases``: with the recipe's balancing (at toy size on two
    rows of ``seq1024``) every layer's held experts take their expected
    load of another batch of that traffic to within 15% on two seeds, where the zero biases of fresh routers miss
    it by 30% in one layer; the trainer's state and the layers hold the
    same biases."""
    def held_load(recipe, seed):
        built = nemotronh.build(TOY, recipe, seed=seed, mesh=one_device_mesh)
        for (name, _), b in zip(built.sparse_layers(),
                                built.selection_biases()):
            np.testing.assert_array_equal(
                built.trainer.state["buffers"][
                    name + ".e_score_correction_bias"], b)
        ids, _ = traffic_gen.make_pool(
            dict(MAN.cell("gpt2-small.seq1024")["traffic"], pool_batches=2),
            TOY["vocab_used"], TOY["eos_token_id"], 2, seed)
        chosen, _ = built.chosen_experts(
            dict(built.trainer.state["params"]), ids[1])
        # experts 4-7 of 16 held, 2 a token: a quarter of the assignments
        return np.asarray([float(((c >= 4) & (c < 8)).mean()) * 4
                           for c in chosen])

    for seed in (0, 1):
        assert np.abs(held_load(TOY["run"], seed) - 1).max() < 0.15
    fresh = dict(TOY["run"], router_bias_balanced_on=None)
    assert np.abs(held_load(fresh, 1) - 1).max() > 0.3


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
