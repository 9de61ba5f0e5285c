"""benchmark/reference/gpt.py is the program's mathematics: at a tiny
size on the CPU, in float32 on both sides, its loss and every gradient
leaf equal ``GPTForPretraining``'s through the family's own comparison -
both loss paths, with and without recomputation."""
import jax
import numpy as np
import pytest

from benchmark import compare, manifest, traffic_gen
from benchmark.families import gpt
from benchmark.reference import gpt as reference
from paddle_tpu.distributed import mesh as mesh_mod

CONFIG = gpt.toy(manifest.Manifest().config("gpt2-small"))
MIX = dict(seq=32, pool_batches=1, zipf_exponent=1.1, follow_probability=0.5,
           doc_length_median=12, doc_length_sigma=1.0, doc_length_min=2)
# float32 against float32 under the suite's "highest" matmul precision:
# what is left is the order of summation
SPEC = dict(grad_leaves="all", loss_rtol=1e-5, grad_rel_l2=1e-4,
            grad_median_rel_l2=1e-4)


@pytest.fixture
def one_device_mesh():
    before = mesh_mod.get_mesh()
    yield mesh_mod.build_mesh({"data": 1}, devices=jax.devices()[:1])
    mesh_mod.set_mesh(before)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("loss_path", ["dense", "fused_chunked"])
def test_reference_equals_program(one_device_mesh, loss_path, remat):
    recipe = dict(CONFIG["run"], param_dtype="float32", loss_path=loss_path,
                  loss_chunk=128, remat=remat)
    built = gpt.build(CONFIG, recipe, seed=3, mesh=one_device_mesh)
    ids, labels = traffic_gen.make_pool(MIX, CONFIG["vocab_used"],
                                        CONFIG["eos_token_id"], 2, seed=5)
    params = dict(built.trainer.state["params"])
    got = compare.against_reference(built, reference, params, ids[0],
                                    labels[0], SPEC)
    assert got["ok"], got
    assert got["grad_leaves"] == 4 + 12 * CONFIG["n_layer"]


def test_comparison_sees_a_wrong_term(one_device_mesh):
    """The comparison is not vacuous: a reference with ReLU in place of
    GELU is out of tolerance."""
    class Wrong:
        @staticmethod
        def loss(params, ids, labels, **kw):
            right, reference.gelu_new = reference.gelu_new, jax.nn.relu
            try:
                return reference.loss(params, ids, labels, **kw)
            finally:
                reference.gelu_new = right

    recipe = dict(CONFIG["run"], param_dtype="float32")
    built = gpt.build(CONFIG, recipe, seed=3, mesh=one_device_mesh)
    ids, labels = traffic_gen.make_pool(MIX, CONFIG["vocab_used"],
                                        CONFIG["eos_token_id"], 2, seed=5)
    got = compare.against_reference(
        built, Wrong, dict(built.trainer.state["params"]), ids[0], labels[0],
        SPEC)
    assert not got["ok"], got


def test_qkv_columns_map_onto_gpt2_order(one_device_mesh):
    built = gpt.build(CONFIG, dict(CONFIG["run"], param_dtype="float32"),
                      seed=0, mesh=one_device_mesh)
    h, n = CONFIG["n_embd"], CONFIG["n_head"]
    d = h // n
    cols = np.arange(3 * h)
    ref = built.to_reference({"gpt.h.0.attn.qkv_proj.bias": cols})[
        "blocks"][0]["qkv_b"]
    # reference column (part, head, j) holds program column head*3d+part*d+j
    for part in range(3):
        for head in range(n):
            assert list(ref[part * h + head * d:part * h + (head + 1) * d]) \
                == list(range(head * 3 * d + part * d,
                              head * 3 * d + (part + 1) * d))


def test_shapes_give_the_published_counts():
    man = manifest.Manifest()
    small, medium = man.config("gpt2-small"), man.config("gpt2-medium")
    assert gpt.param_count(small) == small["flops"]["N"] == 124_475_904
    assert gpt.param_count(medium) == medium["flops"]["N"] == 354_871_296
    f = gpt.model_flops_per_token(small, 1024)
    assert f["total"] == 6 * 124_475_904 + 6 * 12 * 1024 * 768
    assert round(f["total"] / f["six_n"], 4) == \
        small["flops"]["factor_to_6N_only_at_seq_1024"]
