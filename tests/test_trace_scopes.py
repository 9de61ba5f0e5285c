"""The names the program gives its work for a device trace: module scopes
from nn/layer.py, the scopes opened by name in attention, the flash kernels
and the engine's staged step, and the host spans on the profiler's clock.

Scopes are metadata of the staged program, so they are read here from the
jaxpr's name stacks on the CPU; what a trace of the chip shows of them is
held by tests/benchmark_selftest/test_benchmark_scopes.py."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, profiler
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.engine import ParallelTrainer
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.nn.functional.attention import scaled_dot_product_attention
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.text.models import GPTForPretraining


def name_stacks(jaxpr, out=None):
    """``Counter`` of the name stacks of every equation, nested ones too."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        out[str(eqn.source_info.name_stack)] += 1
        for value in eqn.params.values():
            for x in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(x, "jaxpr", x)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    name_stacks(inner, out)
    return out


def holds(stack: str, scope: str) -> bool:
    """``scope`` (one or more components) as whole components of a name
    stack: between ``/``, ``(``, ``)`` and the ends."""
    return re.search(rf"(?:^|[/(]){re.escape(scope)}(?:[/)]|$)",
                     stack) is not None


def tiny_gpt():
    return GPTForPretraining(
        tensor_parallel=False, vocab_size=128, hidden_size=32, num_layers=2,
        num_heads=4, max_position_embeddings=32, attn_dropout=0.0,
        hidden_dropout=0.0)


def staged(mesh_shape, **kw):
    before = mesh_mod.get_mesh()
    try:
        model = tiny_gpt()
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        n = int(np.prod(list(mesh_shape.values())))
        mesh = build_mesh(mesh_shape, devices=jax.devices()[:n])
        trainer = ParallelTrainer(
            model, opt, lambda lo, la: nn.functional.cross_entropy(lo, la),
            mesh=mesh, **kw)
        ids = np.zeros((4, 16), np.int32)
        return name_stacks(trainer.staged_jaxpr(ids, ids).jaxpr)
    finally:
        mesh_mod.set_mesh(before)


@pytest.fixture(scope="module")
def one_chip():
    return staged({"data": 1})


@pytest.fixture(scope="module")
def two_chips():
    return staged({"data": 2})


@pytest.fixture(scope="module")
def two_chips_bucketed():
    return staged({"data": 2}, grad_sync_buckets=2)


@pytest.mark.parametrize("scope", [
    "gptforpretraining", "gpt/embeddings/word_embeddings", "gpt/h.0/attn",
    "gpt/h.1/attn/qkv_proj", "gpt/h.1/mlp/fc_out", "gpt/ln_f", "sdpa",
    "sdpa/xla", "lm_head", "loss", "update"])
def test_staged_step_names_its_work(one_chip, scope):
    assert any(holds(s, scope) for s in one_chip), sorted(one_chip)


@pytest.mark.parametrize("scope", ["gpt/h.0/attn/sdpa", "lm_head", "loss"])
def test_forward_and_backward_are_told_apart_by_jax(one_chip, scope):
    """jax writes jvp( around what it stages for the forward pass and
    transpose(jvp( for the backward pass; the module scopes sit inside."""
    with_scope = [s for s in one_chip if holds(s, scope)]
    assert any(s.startswith("jvp(") for s in with_scope)
    assert any(s.startswith("transpose(jvp(") for s in with_scope)


def test_update_and_guard_are_outside_autodiff(one_chip):
    update = [s for s in one_chip if holds(s, "update")]
    assert update and not any("jvp(" in s for s in update)
    # most of the step's equations outside autodiff are the update
    assert one_chip["update"] > 100


def test_no_grad_exchange_is_staged_on_one_chip(one_chip):
    assert not any(holds(s, "grad_exchange") for s in one_chip)


def test_grad_exchange_after_the_backward_pass(two_chips):
    exchange = [s for s in two_chips if holds(s, "grad_exchange")]
    assert exchange == ["grad_exchange"]
    assert any(holds(s, "update") for s in two_chips)


def test_bucketed_grad_exchange_inside_the_backward_pass(two_chips_bucketed):
    exchange = [s for s in two_chips_bucketed if holds(s, "grad_exchange")]
    assert exchange and all("transpose(" in s for s in exchange)


def test_scope_names_are_equal_in_two_models_of_one_process():
    def scopes(model):
        return [(name, sub._scope_name)
                for name, sub in model.named_sublayers()]

    first, second = tiny_gpt(), tiny_gpt()
    assert scopes(first) == scopes(second)
    # unlike _full_name, which carries a process-wide counter
    assert first.full_name() != second.full_name()
    assert first._scope_name == second._scope_name == "gptforpretraining"


def test_a_sublayer_is_named_as_its_parent_registers_it():
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.first = nn.Linear(2, 2)
            self.add_sublayer("second", nn.Linear(2, 2))

    net = Net()
    assert net._scope_name == "net"
    assert net.first._scope_name == "first"
    assert net.second._scope_name == "second"


@pytest.mark.parametrize("how", ["constructor", "append", "setitem", "insert",
                                 "renamed_parent"])
def test_a_layer_list_passes_its_name_on(how):
    class Net(nn.Layer):
        def __init__(self, blocks):
            super().__init__()
            self.h = blocks

    if how == "constructor":
        net = Net(nn.LayerList([nn.Linear(2, 2), nn.Linear(2, 2)]))
    elif how == "append":
        net = Net(nn.LayerList([nn.Linear(2, 2)]))
        net.h.append(nn.Linear(2, 2))
    elif how == "setitem":
        net = Net(nn.LayerList([nn.Linear(2, 2), nn.Linear(2, 2)]))
        net.h[1] = nn.Linear(2, 2)
    elif how == "insert":
        net = Net(nn.LayerList([nn.Linear(2, 2)]))
        net.h.insert(0, nn.Linear(2, 2))
    else:
        net = Net(nn.LayerList([nn.Linear(2, 2), nn.Linear(2, 2)]))
        net.blocks = net.h
    prefix = "blocks" if how == "renamed_parent" else "h"
    assert [sub._scope_name for sub in net.h] == [f"{prefix}.0", f"{prefix}.1"]
    # a list that no parent holds keeps its class name
    assert [sub._scope_name for sub in nn.LayerList([nn.Linear(2, 2)])] == \
        ["layerlist.0"]


@pytest.mark.parametrize("container, prefix", [
    (nn.LayerList, "h."), (lambda layers: nn.Sequential(*layers), "")])
def test_a_slice_is_a_view_that_renames_nothing(container, prefix):
    """A read of a model must not change what its step is staged under."""
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.h = container([nn.Linear(2, 2) for _ in range(3)])

    net = Net()
    view = net.h[1:]
    assert [sub._scope_name for sub in net.h] == \
        [f"{prefix}{i}" for i in range(3)]
    assert type(view) is type(net.h) and list(view) == list(net.h)[1:]
    assert list(view.state_dict()) == [
        "0.weight", "0.bias", "1.weight", "1.bias"]


def test_a_call_stages_forward_under_the_scope():
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.h = nn.LayerList([nn.Linear(2, 2)])

        def forward(self, x):
            return self.h[0](x)

    net = Net()
    stacks = name_stacks(jax.make_jaxpr(net)(jnp.ones((1, 2))).jaxpr)
    assert set(stacks) == {"net/h.0"}


@pytest.mark.parametrize("kernel, transform", [
    ("flash_fwd", "jvp("), ("flash_bwd_dq", "transpose(jvp("),
    ("flash_bwd_dkv", "transpose(jvp(")])
def test_flash_kernels_have_a_name_and_a_scope(kernel, transform):
    q = jnp.ones((1, 512, 2, 64), jnp.float32)

    def loss(q):
        with jax.named_scope("attn"):
            return flash_attention(q, q, q, causal=True,
                                   interpret=True).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss))(q).jaxpr
    # every layer of a model shares one staged forward and one staged
    # backward (an inner jit each, traced and lowered once a program): the
    # kernels' calls lie in those, under the scope they were called in
    calls = {f"{e.source_info.name_stack}/{inner.source_info.name_stack}":
             inner.params["name"]
             for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")
             for inner in e.params["jaxpr"].jaxpr.eqns
             if inner.primitive.name == "pallas_call"}
    stack = next(s for s in calls if holds(s, kernel))
    # the backward kernels nest under the scope the forward was staged in
    assert stack.startswith(transform + "attn)") and calls[stack] == kernel


@pytest.fixture(scope="module")
def smoke_kernels():
    """The Pallas calls chip_smoke.py's train phase counts, from its own
    trainer at a toy size with the flash gate held open: the CPU rehearsal
    routes attention to XLA and skips the count."""
    import importlib

    import chip_smoke
    from tools._mesh_setup import data_mesh

    # the package exports the function under the module's name
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    cfg = dict(vocab=128, seq=512, layers=2, hidden=128, heads=2, batch=1)
    before, gate = mesh_mod.get_mesh(), fa.flash_supported
    fa.flash_supported = lambda q, k, min_seq=128: True
    try:
        trainer = chip_smoke._gpt_trainer(cfg, data_mesh(1))
        return chip_smoke._pallas_kernels(
            trainer.staged_jaxpr(*chip_smoke._batch(cfg, cfg["batch"])))
    finally:
        fa.flash_supported = gate
        mesh_mod.set_mesh(before)


def test_chip_smoke_counts_the_flash_kernels_by_their_names(smoke_kernels):
    import chip_smoke

    assert smoke_kernels == {"flash_fwd": 2, "flash_bwd_dq": 2,
                             "flash_bwd_dkv": 2}
    chip_smoke._check_flash_calls(smoke_kernels, layers=2)


@pytest.mark.parametrize("kernels", [
    {}, {"flash_fwd": 2, "flash_bwd_dq": 2},
    {"_fwd_kernel": 2, "_bwd_dq_kernel": 2, "_bwd_dkv_kernel": 2}])
def test_chip_smoke_fails_a_step_without_its_flash_kernels(kernels):
    import chip_smoke

    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke._check_flash_calls(kernels, layers=2)


def test_sdpa_scope_holds_the_path_it_took():
    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q: scaled_dot_product_attention(q, q, q, is_causal=True))(q)
    # the top level's equations; a nested jaxpr's stacks are relative to it
    stacks = {str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns}
    assert stacks and all(s.startswith("sdpa/xla") for s in stacks)


# -- host spans ----------------------------------------------------------------

def test_record_event_opens_no_named_scope():
    """A host range is not a staging scope: one that is open while a
    program is traced must not write itself into that program's op names."""
    profiler.start_profiler("CPU")
    try:
        with profiler.RecordEvent("host_range"):
            stacks = name_stacks(jax.make_jaxpr(lambda x: x * 2)(1.0).jaxpr)
    finally:
        profiler.stop_profiler(profile_path="", verbose=False)
    assert set(stacks) == {""}


def host_spans(trace_dir, prefix="paddle_tpu."):
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def test_trainer_spans_and_record_event_land_in_the_jax_trace(tmp_path):
    """The trainer's two spans are always on and need neither the
    program's profiler nor telemetry; a RecordEvent shows up beside them
    as paddle_tpu.<name> while the program's profiler is on."""
    before = mesh_mod.get_mesh()
    try:
        model = tiny_gpt()
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        trainer = ParallelTrainer(
            model, opt, lambda lo, la: nn.functional.cross_entropy(lo, la),
            mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]))
        ids = np.zeros((2, 16), np.int32)
        trainer.train_step(ids, ids)                 # compile outside
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for _ in range(3):
                trainer.train_step(ids, ids)
            profiler.start_profiler("CPU")
            with profiler.RecordEvent("checkpoint_save"):
                pass
            profiler.stop_profiler(profile_path="", verbose=False)
        finally:
            jax.profiler.stop_trace()
    finally:
        mesh_mod.set_mesh(before)
    spans = collections.Counter(host_spans(tmp_path))
    assert spans == {"paddle_tpu.trainer.stage": 3,
                     "paddle_tpu.trainer.launch": 3,
                     "paddle_tpu.checkpoint_save": 1}
