"""The ``glm4moelite`` family, its reference and the cell
``glm-4.7-flash.seq4096`` without a chip: the configuration keeps every
published width, the counts are the shapes', the reference's router, rotary
key and loss terms are hand values, the reference is the program's
mathematics in float32, a lower precision is refused, and the new cost
function gives values worked out by hand."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, kernel_costs_latent, manifest, traffic_gen
from benchmark.families import glm4moelite
from benchmark.reducers import roofline_share_of
from benchmark.reference import glm4moelite as reference
from paddle_tpu.distributed import mesh as mesh_mod

MAN = manifest.Manifest()
CELL = "glm-4.7-flash.seq4096"
NAME = "glm-4.7-flash"
CONFIG = MAN.config(NAME)
TOY = glm4moelite.toy(CONFIG)
MIX = dict(seq=96, pool_batches=1, zipf_exponent=1.1, follow_probability=0.5,
           doc_length_median=12, doc_length_sigma=1.0, doc_length_min=2)
SPEC = dict(grad_leaves="all", loss_rtol=1e-5, grad_rel_l2=1e-3,
            grad_median_rel_l2=1e-4)
# the catalog row's config (model-configs guide, architectures.jsonl)
PUBLISHED = dict(
    attention_bias=False, hidden_act="silu", hidden_size=2048,
    intermediate_size=10240, max_position_embeddings=202752,
    model_type="glm4_moe_lite", moe_intermediate_size=1536,
    topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
    n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
    routed_scaling_factor=1.8, num_experts_per_tok=4,
    first_k_dense_replace=1, num_hidden_layers=47, num_key_value_heads=20,
    num_nextn_predict_layers=1, partial_rotary_factor=1, rms_norm_eps=1e-05,
    rope_scaling=None, rope_theta=1000000, tie_word_embeddings=False,
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, vocab_size=154880)
NEW_METRICS = {"latent_attn_ms_per_step", "latent_qkv_ms_per_step",
               "mtp_ms_per_step", "flash_latent_roofline"}
# a block's two norms and seven attention tensors, three of a dense MLP or
# seven of an expert layer; the module's four beside its block; three at
# the top
LEAVES = 3 + 12 + 2 * 16 + 20


@pytest.fixture
def one_device_mesh():
    before = mesh_mod.get_mesh()
    yield mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    mesh_mod.set_mesh(before)


@pytest.fixture
def built(one_device_mesh):
    return glm4moelite.build(TOY, TOY["run"], seed=3, mesh=one_device_mesh)


def rows(seed=5):
    ids, labels = traffic_gen.make_pool(MIX, TOY["vocab_used"],
                                        TOY["eos_token_id"], 2, seed=seed)
    return ids[0], labels[0]


def with_biases(built, scale=0.2):
    """Non-zero biases in the program's routers (the cell's stay zero)."""
    for i, (_, m) in enumerate(built.sparse_layers()):
        m.e_score_correction_bias = scale * jax.random.normal(
            jax.random.key(40 + i), (m.num_experts,))
    return built


def test_the_cell_resolves():
    assert MAN.problems() == []
    cell = MAN.cell(CELL)
    assert cell["entry"]["chips"] == 1 and cell["traffic"]["seq"] == 4096
    assert MAN.workloads[CELL]["traffic"] == "seq4096"
    w = cell["workload"]
    assert w["kind"] == "train" and w["mesh"] == {"data": 1}
    assert w["rows_per_chip"] in w["rows_ladder"] == [1, 2, 4]
    assert (w["sync_every"], w["warmup_steps"], w["trace_steps"]) == (4, 3, 8)
    names = {m["name"] for m in cell["per_layer"]}
    # the expert layers are read by the accepted metric of their scope, the
    # cell appended to its list
    assert NEW_METRICS | {"flash_attn_ms_per_step", "attn_path_ms_per_step",
                          "lm_head_loss_ms_per_step", "moe_ms_per_step"} \
        <= names
    assert not names & {"flash_attn_roofline", "flash_window_roofline",
                        "moe_experts_roofline", "rope_ms_per_step",
                        "qk_norm_ms_per_step", "linear_attn_ms_per_step"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gb", "setup_s"}
    for other in sorted(MAN.workloads):
        if other != CELL:
            assert not NEW_METRICS & {m["name"]
                                      for m in MAN.cell(other)["per_layer"]}
    assert {MAN.per_layer[n]["layer"] for n in NEW_METRICS} == {
        "attention", "objective"}
    assert MAN.per_layer["mtp_ms_per_step"]["layer"] == "objective"
    # the benchmark gained one configuration and one cell: 8 of 24, and the
    # one four-chip cell it had
    assert len(MAN.workloads) == 8 and len(MAN.configs) == 6
    assert sum(w["chips"] == 4 for w in MAN.workloads.values()) == 1


def test_no_width_differs_from_the_published_config():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert CONFIG["reduced"] == MAN.configs[NAME]["reduced"]
    assert MAN.configs[NAME]["source"] == CONFIG["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert CONFIG["published"] == {k: PUBLISHED[k] for k in CONFIG["reduced"]}
    assert set(CONFIG["changed"]) == set(CONFIG["reduced"])
    dep = CONFIG["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["held_experts"] == [0, 64 // 8]
    assert dep["vocab_rows"] == [0, 154880 // 8]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 8, 19360)
    assert 0 <= CONFIG["eos_token_id"] < CONFIG["vocab_used"] \
        == CONFIG["vocab_size"]
    for key in ("latent_attention", "rope_pairing", "lane_order",
                "multi_token_prediction", "mtp_loss_weight",
                "e_score_correction_bias", "router", "hidden_act",
                "eos_token_id", "sequence_length", "initialisers"):
        assert key in CONFIG["assumed"]
    assert CONFIG["mtp_loss_weight"] == 0.3
    a = glm4moelite.arch(CONFIG)
    assert a["ffn"] == ["dense"] + ["sparse"] * 4
    assert (a["heads"], a["q_lora_rank"], a["kv_lora_rank"], a["d_nope"],
            a["d_rope"], a["d_v"]) == (20, 768, 512, 192, 64, 256)
    assert (a["top_k"], a["router_width"], a["held"],
            a["routed_scaling_factor"], a["mtp_layers"]) == (
                4, 64, (0, 8), 1.8, 1)
    with pytest.raises(ValueError, match="group-limited"):
        glm4moelite.arch(dict(CONFIG, n_group=4))


def test_shapes_give_the_counts_the_file_states():
    attention = 1_572_864 + 768 + 3_932_160 + 1_179_648 + 512 + 4_587_520 \
        + 10_485_760
    assert attention == 21_759_232
    dense = glm4moelite.layer_params(CONFIG, "dense")
    sparse = glm4moelite.layer_params(CONFIG, "sparse")
    assert sum(dense.values()) == 62_914_560 + attention + 4_096 \
        == 84_677_888
    assert sparse["router"] == 131_072 and sparse["shared"] == 9_437_184
    assert sparse["experts"] == 8 * 9_437_184
    assert sum(sparse.values()) - sparse["experts"] == 31_331_584
    assert sum(sparse.values()) == 106_829_056
    assert sum(glm4moelite.mtp_params(CONFIG).values()) \
        == 106_829_056 + 8_388_608 + 6_144 == 115_223_808
    assert glm4moelite.param_count(CONFIG) == CONFIG["flops"]["N"] \
        == 84_677_888 + 4 * 106_829_056 + 115_223_808 + 79_298_560 + 2_048 \
        == 706_518_528
    f = glm4moelite.model_flops_per_token(CONFIG, 4096)
    # met in a product: six layers' projections, the dense MLP, five sparse
    # layers' router, shared expert and 4 x 8 / 64 of an expert, eh_proj,
    # the head's slice twice
    met = 6 * (attention - 1_280) + 62_914_560 \
        + 5 * (131_072 + 9_437_184 + 0.5 * 9_437_184) + 8_388_608 \
        + 2 * 19_360 * 2_048
    assert met == CONFIG["flops"]["met_per_token"] == 352_583_680
    assert f["six_n"] == 6 * met
    assert f["attention"] == 6 * 12 * 20 * 256 * 4097 / 2 \
        == CONFIG["flops"]["attention_at_seq_4096"] == 755_159_040
    assert f["total"] == f["six_n"] + f["attention"] \
        == CONFIG["flops"]["total_at_seq_4096"] == 2_870_661_120
    assert round(f["total"] / 1e6, 1) == 2870.7
    # how the program recomputes is its business: no count follows it
    other = dict(CONFIG, run=dict(CONFIG["run"], checkpoint_blocks=False))
    assert glm4moelite.model_flops_per_token(other, 4096) == f


def test_cost_function_against_hand_values():
    """Four rows of 4,096 positions on the published widths: six layers,
    20 heads of width 256, nothing grouped."""
    got = kernel_costs_latent.flash_latent(CONFIG, 4, 4096)
    calls = 4 * 20 * 6
    assert kernel_costs_latent.latent_layers(CONFIG) == 6
    # nine products over the causal half, 2 operations a term
    assert got["flops"] == calls * 9 * 2 * 4096 * 4097 / 2 * 256 \
        == 18_558_788_567_040
    # q k v o and their gradients once a head in bf16 (4 + 5 + 6 tensors
    # over the three kernels), five float32 statistics in 8 lanes
    assert got["bytes"] == calls * (15 * 4096 * 256 * 2 + 5 * 4096 * 8 * 4) \
        == 15_414_067_200
    one = kernel_costs_latent.flash_latent(CONFIG, 1, 4096)
    assert got == {k: 4 * v for k, v in one.items()}
    # the same as the mixed decoder's count where no head is grouped
    from benchmark import kernel_costs_mixed
    as_gqa = dict(num_hidden_layers=6, layer_types=["full_attention"] * 6,
                  num_attention_heads_per_layer=[20] * 6,
                  mlp_layer_types=["sparse"] * 6, head_dim=256,
                  num_key_value_heads=20, sliding_window=None)
    assert kernel_costs_mixed.flash_window(as_gqa, 4, 4096) == got
    with pytest.raises(ValueError, match="one head width"):
        kernel_costs_latent.flash_latent(dict(CONFIG, v_head_dim=128), 4,
                                         4096)
    reading = types.SimpleNamespace(
        config=CONFIG, rows_per_chip=4, seq=4096,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    spec = MAN.layer_metric("flash_latent_roofline")
    assert spec["args"]["pattern"] == MAN.layer_metric(
        "flash_attn_ms_per_step")["args"]["pattern"]
    summed = roofline_share_of.sum_per_step.reduce
    roofline_share_of.sum_per_step.reduce = lambda r, pattern: 150.0
    try:
        value, note = roofline_share_of.reduce(reading, **spec["args"])
        roofline_share_of.sum_per_step.reduce = lambda r, pattern: 0.0
        assert roofline_share_of.reduce(reading, **spec["args"]) is None
    finally:
        roofline_share_of.sum_per_step.reduce = summed
    assert note["bound"] == "compute"
    assert value == pytest.approx(100 * got["flops"] / 197e12 / 0.150)
    assert 0 < value < 100


def test_reference_router_by_hand():
    """The bias chooses, the scores weigh: four experts, two a token."""
    u = jnp.eye(4)[:1]                                   # reads row 0
    router_w = jnp.zeros((4, 4)).at[0].set(jnp.asarray([0., 2., -1., 1.]))
    arch = {"top_k": 2, "routed_scaling_factor": 1.8}
    ids, w = reference.route(u, router_w, jnp.zeros(4), arch)
    assert ids.tolist() == [[1, 3]]
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0])))
    np.testing.assert_allclose(w[0], 1.8 * s / s.sum(), rtol=1e-6)
    ids, w = reference.route(u, router_w, jnp.asarray([0., 0., 5., 0.]), arch)
    assert ids.tolist() == [[2, 1]]
    s = 1 / (1 + np.exp(-np.asarray([-1.0, 2.0])))
    np.testing.assert_allclose(w[0], 1.8 * s / s.sum(), rtol=1e-6)


def test_reference_rope_and_attention_by_hand():
    """Rotate-half over four lanes at theta 100: pair i turns by ``pos *
    100 ** (-i / 2)``. With ``W_uq = 0`` every score is 0, so a head's
    output is the running mean of its values: the causal mask, the split
    ``[c_kv | k_r]``, the latent's norm and a head's ``[k_nope | v]``."""
    x = jnp.asarray([1., 2., 3., 4.])[None, None, None, :]
    x = jnp.broadcast_to(x, (1, 3, 1, 4))
    got = reference.apply_rope(x, 100.0)
    for pos in range(3):
        a, b = pos * 1.0, pos * 0.1
        want = [1 * np.cos(a) - 3 * np.sin(a), 2 * np.cos(b) - 4 * np.sin(b),
                3 * np.cos(a) + 1 * np.sin(a), 4 * np.cos(b) + 2 * np.sin(b)]
        np.testing.assert_allclose(got[0, pos, 0], want, rtol=1e-5)
    arch = {"heads": 2, "d_nope": 1, "d_rope": 2, "d_v": 3, "kv_lora_rank": 2,
            "rope_theta": 100.0}
    u = jnp.asarray([[[3., 4.], [0., 5.], [6., 8.]]])           # [1, 3, 2]
    p = {"q_a_w": jnp.ones((2, 2)), "q_a_norm_g": jnp.ones(2),
         "q_b_w": jnp.zeros((2, 2 * 3)),
         # c_kv = u (the first two columns), k_r = anything
         "kv_a_w": jnp.concatenate([jnp.eye(2), jnp.ones((2, 2))], axis=1),
         "kv_a_norm_g": jnp.asarray([1., 2.]),
         # head 0: k_nope, then v = (c0, c1, c0 + c1); head 1: v = 2 c0, 0, 0
         "kv_b_w": jnp.asarray([[9., 1., 0., 1., 9., 2., 0., 0.],
                                [9., 0., 1., 1., 9., 0., 0., 0.]]),
         "o_w": jnp.eye(6)}
    got = reference.attention(u, p, arch, 0.0, False)
    # the normed latent: u / rms(u) * g
    c = np.asarray([[3., 4.], [0., 5.], [6., 8.]])
    c = c / np.sqrt((c ** 2).mean(-1, keepdims=True)) * np.asarray([1., 2.])
    v0 = np.stack([c[:, 0], c[:, 1], c[:, 0] + c[:, 1]], axis=-1)
    v1 = np.stack([2 * c[:, 0], 0 * c[:, 0], 0 * c[:, 0]], axis=-1)
    v = np.concatenate([v0, v1], axis=-1)
    want = np.cumsum(v, axis=0) / np.arange(1, 4)[:, None]
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)
    for remat in (False, True):
        np.testing.assert_allclose(
            reference.attention(u, p, arch, 0.0, remat), got, rtol=1e-6)


def test_reference_loss_terms_by_hand(built):
    """With a zero head every logit is 0 and both terms are ``ln V``; with
    the module's second loss term weighted 0.3 the loss is ``1.3 ln V``;
    and the second term reads ``L - 1`` positions: a label changed at the
    last position moves the first term's inputs only."""
    params = dict(built.trainer.state["params"])
    ref = built.to_reference(params)
    ref["blocks"] = [ref["blocks"][i] for i in sorted(ref["blocks"])]
    arch, eps = built.config["n_head"], built.config["layer_norm_epsilon"]
    ids, labels = rows()
    zero = dict(ref, lm_head=jnp.zeros_like(ref["lm_head"]))
    main, ahead = reference.loss_terms(zero, ids, labels, n_head=arch,
                                       eps=eps)
    assert float(main) == pytest.approx(np.log(512), rel=1e-6)
    assert float(ahead) == pytest.approx(np.log(512), rel=1e-6)
    assert float(reference.loss(zero, ids, labels, n_head=arch, eps=eps)) \
        == pytest.approx(1.3 * np.log(512), rel=1e-6)
    main, ahead = reference.loss_terms(ref, ids, labels, n_head=arch, eps=eps)
    assert float(reference.loss(ref, ids, labels, n_head=arch, eps=eps)) \
        == pytest.approx(float(main) + 0.3 * float(ahead), rel=1e-6)
    # without the module: the trunk alone, one term
    trunk = dict(ref, blocks=ref["blocks"][:-1])
    alone = reference.loss(trunk, ids, labels, n_head=dict(arch, mtp_layers=0),
                           eps=eps)
    assert float(alone) == pytest.approx(float(main), rel=1e-6)


def test_columns_map_the_programs_lanes_onto_the_published_order():
    # two heads, nope 3, rope 2: program [r r n n n | r r n n n]
    cols = glm4moelite.published_columns(2, 3, 2)
    assert cols.tolist() == [2, 3, 4, 0, 1, 7, 8, 9, 5, 6]
    full = glm4moelite.published_columns(20, 192, 64)
    assert sorted(full.tolist()) == list(range(20 * 256))
    assert full[:3].tolist() == [64, 65, 66] and full[192] == 0


def test_param_count_is_what_the_program_builds(built):
    n = sum(int(np.prod(v.shape))
            for v in built.trainer.state["params"].values())
    assert n == glm4moelite.param_count(TOY)
    assert len(built.leaf_names("all")) == LEAVES
    # the biases are state, not parameters: in the trainer's buffers and
    # in a checkpoint, float32 whatever the parameters train in
    names = [n for n in built.trainer.state["buffers"]
             if n.endswith("e_score_correction_bias")]
    assert names == [f"decoder.h.{i}.moe.e_score_correction_bias"
                     for i in (1, 2)] + [
                         "mtp.block.moe.e_score_correction_bias"]
    assert all(built.trainer.state["buffers"][n].dtype == jnp.float32
               and n in built.model.state_dict() for n in names)
    assert not any("e_score" in n for n in built.trainer.state["params"])
    assert built.step_args("ids", "labels") == (("ids", "labels"), 0.0)


@pytest.mark.parametrize("checkpoint, biased", [
    (False, False), (True, False), (True, True)],
    ids=["plain", "checkpoint_blocks", "checkpoint_blocks_and_biases"])
def test_reference_equals_program(one_device_mesh, checkpoint, biased):
    """Loss and every gradient leaf, float32 on both sides, through the
    harness's own comparison; with non-zero biases in the routers too."""
    recipe = dict(TOY["run"], checkpoint_blocks=checkpoint)
    built = glm4moelite.build(TOY, recipe, seed=3, mesh=one_device_mesh)
    if biased:
        with_biases(built)
    params = dict(built.trainer.state["params"])
    got = compare.against_reference(built, reference, params, *rows(),
                                    dict(SPEC, reference_remat=bool(
                                        checkpoint)))
    assert got["ok"], got
    assert got["grad_leaves"] == LEAVES
    given = built.config["n_head"]["selection_bias"]
    assert len(given) == 3
    assert all((float(np.abs(b).max()) > 0) == biased for b in given)


WRONG = ("no_latent_norms", "rope_on_the_nope_lanes", "k_r_a_head",
         "scale_of_the_nope_width", "bias_weighs", "no_bias",
         "no_scaling_factor", "module_reads_before_the_final_norm",
         "hidden_then_embedding", "second_term_over_all_positions",
         "weight_one", "no_module")


@pytest.mark.parametrize("wrong", WRONG)
def test_comparison_sees_a_wrong_term(built, wrong, monkeypatch):
    """Not vacuous: each assumed or easily mistaken term, changed in the
    reference, is out of tolerance."""
    with_biases(built)
    arch = built.config["n_head"]
    if wrong == "no_latent_norms":
        monkeypatch.setattr(reference, "latent_norm", lambda c, g, eps: c)
    elif wrong == "rope_on_the_nope_lanes":
        right = reference.attention

        def attention(u, p, arch, eps, remat):
            """A head's first lanes turned in place of its last."""
            dn, dr = arch["d_nope"], arch["d_rope"]
            w = jnp.reshape(p["q_b_w"], (-1, arch["heads"], dn + dr))
            w = jnp.concatenate([w[..., dr:], w[..., :dr]], axis=-1)
            return right(u, dict(p, q_b_w=jnp.reshape(w, p["q_b_w"].shape)),
                         arch, eps, remat)
        monkeypatch.setattr(reference, "attention", attention)
    elif wrong == "k_r_a_head":
        right_rope = reference.apply_rope

        def rope(x, theta):
            """Keys' rotary head turned twice as far: not the queries'."""
            return right_rope(x, theta if x.shape[2] > 1 else theta ** 0.5)
        monkeypatch.setattr(reference, "apply_rope", rope)
    elif wrong == "scale_of_the_nope_width":
        right = reference.attention
        monkeypatch.setattr(
            reference, "attention", lambda u, p, arch, eps, remat: right(
                u, dict(p, q_b_w=p["q_b_w"] * np.sqrt(16 / 12)), arch, eps,
                remat))
    elif wrong == "bias_weighs":
        def route(u, router_w, bias, arch):
            scores = jax.nn.sigmoid(reference._mm(u, router_w)) + bias
            top, ids = jax.lax.top_k(scores, arch["top_k"])
            return ids, arch["routed_scaling_factor"] * top / jnp.sum(
                top, axis=-1, keepdims=True)
        monkeypatch.setattr(reference, "route", route)
    elif wrong == "no_bias":
        right_route = reference.route
        monkeypatch.setattr(
            reference, "route", lambda u, w, bias, arch: right_route(
                u, w, jnp.zeros_like(bias), arch))
    elif wrong == "no_scaling_factor":
        arch["routed_scaling_factor"] = 1.0
    elif wrong in ("module_reads_before_the_final_norm",
                   "hidden_then_embedding"):
        right_states = reference.hidden_states

        def hidden_states(params, ids, labels, arch, eps, remat=False):
            blocks = list(params["blocks"])
            p = dict(blocks[-1])
            if wrong == "hidden_then_embedding":
                half = p["eh_w"].shape[0] // 2
                p["eh_w"] = jnp.concatenate([p["eh_w"][half:],
                                             p["eh_w"][:half]])
            else:
                # undo the final norm's weight for the module's input: as
                # if it read the last block's output under hnorm alone
                p["hnorm_g"] = p["hnorm_g"] * (1.0 + params["norm_g"])
            blocks[-1] = p
            return right_states(dict(params, blocks=blocks), ids, labels,
                                arch, eps, remat)
        monkeypatch.setattr(reference, "hidden_states", hidden_states)
    elif wrong == "second_term_over_all_positions":
        def loss_terms(params, ids, labels, *, n_head, eps, remat=False):
            with jax.default_matmul_precision("highest"):
                hidden, z, _ = reference.hidden_states(
                    params, ids, labels, n_head, eps, remat)
                main = jnp.mean(reference._cross_entropy(
                    hidden @ params["lm_head"], labels))
                ahead = jnp.mean(reference._cross_entropy(
                    z @ params["lm_head"], jnp.roll(labels, -1, axis=1)))
                return main, ahead
        monkeypatch.setattr(reference, "loss_terms", loss_terms)
    elif wrong == "weight_one":
        arch["mtp_loss_weight"] = 1.0
    elif wrong == "no_module":
        right_terms = reference.loss_terms
        monkeypatch.setattr(
            reference, "loss_terms", lambda *a, **kw: (
                right_terms(*a, **kw)[0], None))
    params = dict(built.trainer.state["params"])
    if wrong == "module_reads_before_the_final_norm":
        # a final norm away from its initial ones, so that skipping it shows
        params["decoder.norm.weight"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.key(9), params["decoder.norm.weight"].shape)
    got = compare.against_reference(built, reference, params, *rows(), SPEC)
    assert not got["ok"], got


def as_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def test_the_cells_limits_refuse_the_reference_in_fp8(built, monkeypatch):
    """The control the cell's limits are placed against: the reference with
    every product's operands rounded to ``float8_e4m3`` is refused under
    the limits the workload file commits, by at least one of them, where
    the sound reference passes them with room."""
    check = MAN.cell(CELL)["workload"]["check"]
    spec = {k: check[k] for k in ("grad_leaves", "loss_rtol", "grad_rel_l2",
                                  "grad_median_rel_l2")}
    params = dict(built.trainer.state["params"])
    sound = compare.against_reference(built, reference, params, *rows(),
                                      spec)
    assert sound["ok"], sound
    monkeypatch.setattr(reference, "_mm",
                        lambda a, b: as_fp8(a) @ as_fp8(b))
    got = compare.against_reference(built, reference, params, *rows(), spec)
    assert not got["ok"], got
    over = [got["loss_rel_diff"] > spec["loss_rtol"],
            got["grad_worst_rel_l2"] > spec["grad_rel_l2"],
            got["grad_median_rel_l2"] > spec["grad_median_rel_l2"]]
    assert any(over), got
    # each limit is written with its two readings
    for key in ("loss_rtol", "grad_rel_l2", "grad_median_rel_l2",
                "loss_margin", "sample_rows"):
        assert "float8_e4m3" in check["why"][key] \
            or key in ("loss_margin", "sample_rows")
        assert len(check["why"][key]) > 100


def test_the_family_reports_routing_and_the_two_terms(built, capsys):
    import json

    from paddle_tpu import telemetry
    ids, labels = rows()
    params = dict(built.trainer.state["params"])
    before = telemetry.get_registry()
    telemetry._set_registry(telemetry.Registry())
    try:
        built.report_routing(params, ids, labels)
        counters = telemetry.get_registry().to_dict()
    finally:
        telemetry._set_registry(before)
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert line["event"] == "routing_agreement" and line["tokens"] == 192
    assert line["assignments_chosen_differently_by_layer"] == [0.0] * 3
    names = [name for name, _ in built.sparse_layers()]
    assert names == ["decoder.h.1.moe", "decoder.h.2.moe", "mtp.block.moe"]
    chosen, buffers = built.chosen_experts(params, (ids, labels))
    for i, got in enumerate(chosen):
        assert got.shape == (192, 2)
        held = int(((got >= 4) & (got < 8)).sum())      # experts 4-7 of 16
        assert line["held_assignments_over_expected_by_layer"][i] \
            == held * 16 / (192 * 2 * 4)
        series = counters["moe_held_assignments_total"]["series"]
        assert [v for k, v in series.items() if names[i] in k] == [held]
    assert {"moe_tokens_routed_total", "moe_max_load_over_mean",
            "mtp_main_loss", "mtp_next_loss"} <= set(counters)
    assert line["mtp_main_loss"] == pytest.approx(
        float(buffers["mtp_main_loss"]))
    assert line["mtp_next_loss"] > 0


def test_new_metrics_read_nothing_from_a_trace_without_their_scopes(tmp_path):
    """On a trace of the GPT program, which opens none of the new scopes,
    the new readers find no time and do not raise: a program that lacks
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
        rows_per_chip=1, seq=4096, peaks=manifest.peaks("TPU v5 lite"))
    reading._scopes = xplane_scopes.Scopes(path)
    for metric in sorted(NEW_METRICS - {"flash_latent_roofline"}):
        spec = MAN.layer_metric(metric)
        reducer = manifest.plugin("reducers", spec["reducer"])
        value, note = reducer.reduce(reading, **spec["args"])
        if metric == "latent_attn_ms_per_step":
            # GPT's blocks call their attention attn too; what is the
            # latent layer's own inside it reads nothing
            assert note["by_scope_ms"]["latent_q"] == 0.0
            assert note["by_scope_ms"]["latent_kv"] == 0.0
        else:
            assert not value, metric
    # the roofline's reader finds the GPT program's flash kernels (the
    # pattern is the accepted one) and counts this configuration's shapes
    spec = MAN.layer_metric("flash_latent_roofline")
    value = roofline_share_of.reduce(reading, **spec["args"])
    assert value is None or value[0] > 0
