"""``ParallelTrainer`` stages one program of its step: the state enters
the first ``train_step`` call as the pytree every later call passes (the
containers the staged step returns), whatever built or rebuilt it."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.amp import GradScaler
from paddle_tpu.distributed.engine import ParallelTrainer
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.telemetry import staging

PARTS = ("params", "buffers", "opt", "comm_err", "guard")


@pytest.fixture(autouse=True)
def own_record():
    # the record is the process's: each test reads its own trainer's alone
    staging.reset()
    yield
    staging.reset()


def _trainer(mesh=None, scaler=False, **kw):
    paddle.seed(7)

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(16, 32)
            self.bn = nn.BatchNorm1D(32)        # buffers in the state
            self.l2 = nn.Linear(32, 4)

        def forward(self, x):
            return self.l2(nn.functional.relu(self.bn(self.l1(x))))

    model = MLP()
    opt = paddle.optimizer.Momentum(0.05, momentum=0.9,
                                    parameters=model.parameters())
    if scaler:
        kw["scaler"] = GradScaler(enable=True, init_loss_scaling=8.0)
    return ParallelTrainer(model, opt,
                           lambda out, y: jnp.mean((out - y) ** 2),
                           mesh=build_mesh(mesh or {"data": 2}), **kw)


def _batch(seed=3):
    rng = np.random.RandomState(seed)
    return (rng.randn(8, 16).astype(np.float32),
            rng.randn(8, 4).astype(np.float32))


def _node_types(tree):
    """The type of every container of a pytree, by key path."""
    found = {}

    def walk(node, path):
        if isinstance(node, dict):
            found[path] = type(node)
            for k, v in node.items():
                walk(v, path + (k,))
        elif isinstance(node, (list, tuple)):
            found[path] = type(node)
            for i, v in enumerate(node):
                walk(v, path + (i,))

    walk(tree, ())
    return found


SHAPES = {
    "plain": dict(),
    "accumulate": dict(accumulate_steps=2),
    "zero1": dict(mesh={"sharding": 2}, zero_stage=1),
    "zero2": dict(mesh={"sharding": 2}, zero_stage=2),
    "zero3": dict(mesh={"sharding": 2}, zero_stage=3),
    "scaler": dict(scaler=True),
    "int8": dict(grad_sync="int8", grad_sync_block=8),
    "int8_zero2": dict(mesh={"data": 2, "sharding": 2}, zero_stage=2,
                       grad_sync="int8", grad_sync_block=8),
    "int8_buckets": dict(grad_sync="int8", grad_sync_block=8,
                         grad_sync_buckets=2),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_three_calls_stage_one_program(shape):
    trainer = _trainer(**SHAPES[shape])
    if shape == "scaler":
        assert "amp" in trainer.state["guard"]
    if shape.startswith("int8"):
        assert trainer.state["comm_err"]
    made = _node_types(trainer.state)
    assert set(made.values()) == {dict}
    x, y = _batch()
    for _ in range(3):
        trainer.train_step(x, y)
    assert staging.programs("train_step") == 1
    summary = trainer.staging_summary()["train_step"]
    assert summary["programs"] == 1
    assert summary["staged_in_steps"] == [1]
    # the step hands back what it was given
    assert _node_types(trainer.state) == made


def test_the_integrity_check_is_a_second_program_staged_where_it_first_runs():
    trainer = _trainer(integrity_check_every=2)
    x, y = _batch()
    trainer.train_step(x, y)
    assert staging.programs("train_step") == 1
    trainer.train_step(x, y)                # the first call that checks
    assert staging.programs("train_step") == 2
    for _ in range(3):                      # plain, check, plain
        trainer.train_step(x, y)
    assert staging.programs("train_step") == 2
    assert trainer.staging_summary()["train_step"]["staged_in_steps"] == \
        [1, 2]


def test_a_later_check_cadence_stages_its_program_in_that_call():
    trainer = _trainer(integrity_check_every=3)
    x, y = _batch()
    for _ in range(4):
        trainer.train_step(x, y)
    assert trainer.staging_summary()["train_step"]["staged_in_steps"] == \
        [1, 3]


@pytest.mark.parametrize("shape", ["plain", "scaler", "int8", "zero2"])
def test_a_loaded_state_runs_the_program_a_fresh_state_runs(shape, tmp_path):
    x, y = _batch()
    saved = _trainer(**SHAPES[shape])
    saved.train_step(x, y)
    saved.save_checkpoint(str(tmp_path / "ckpt"))
    want = {k: np.asarray(v) for k, v in saved.state["params"].items()}

    staging.reset()
    fresh = _trainer(**SHAPES[shape])
    made = _node_types(fresh.state)
    fresh.train_step(x, y)
    fresh.load_checkpoint(str(tmp_path / "ckpt"))
    assert _node_types(fresh.state) == made
    assert set(fresh.state) == set(PARTS)
    for k, v in fresh.state["params"].items():
        np.testing.assert_array_equal(np.asarray(v), want[k])
    fresh.train_step(x, y)
    assert staging.programs("train_step") == 1

    # and a load before the first step: that step's program is the one
    staging.reset()
    resumed = _trainer(**SHAPES[shape])
    resumed.load_checkpoint(str(tmp_path / "ckpt"))
    assert _node_types(resumed.state) == made
    for _ in range(2):
        resumed.train_step(x, y)
    assert staging.programs("train_step") == 1
    assert resumed.staging_summary()["train_step"]["staged_in_steps"] == [1]


def test_the_setters_keep_the_program():
    trainer = _trainer()
    x, y = _batch()
    trainer.train_step(x, y)
    name = next(iter(trainer.state["params"]))
    trainer.set_param(name, jnp.zeros_like(trainer.get_param(name)))
    slot = trainer.opt_slot_names(name)[0]
    trainer.set_opt_slot(name, slot,
                         jnp.ones_like(trainer.get_opt_slot(name, slot)))
    trainer.train_step(x, y)
    assert staging.programs("train_step") == 1


def test_a_resumed_run_stages_one_program(tmp_path):
    from paddle_tpu.distributed.checkpoint import CheckpointManager
    from paddle_tpu.resilience.runner import run_resilient
    loader = [_batch(seed) for seed in range(4)]
    mgr = CheckpointManager(str(tmp_path), use_async=False)
    run_resilient(_trainer(), loader, steps=3, manager=mgr,
                  handle_signals=False)
    staging.reset()
    resumed = _trainer()                    # "a new process"
    made = _node_types(resumed.state)
    res = run_resilient(resumed, loader, steps=6, manager=mgr,
                        handle_signals=False)
    assert mgr.last_restored_step == 2 and res.steps_done == 6
    assert _node_types(resumed.state) == made
    assert staging.programs("train_step") == 1


@pytest.mark.parametrize("param_sync", ["fp32", "bf16", "int8"])
def test_localsgd_stages_each_of_its_two_programs_once(param_sync):
    from paddle_tpu.distributed.meta_parallel.localsgd import LocalSGDTrainer
    build_mesh({"data": 2})
    paddle.seed(1)
    net = nn.Linear(16, 4)
    opt = paddle.optimizer.SGD(0.05, parameters=net.parameters())
    trainer = LocalSGDTrainer(net, opt,
                              lambda out, y: jnp.mean((out - y) ** 2),
                              k_steps=2, param_sync=param_sync)
    made = _node_types(trainer.state)
    assert set(made.values()) == {dict}
    x, y = _batch()
    trainer.train_step(x, y)                # the program without collectives
    assert staging.programs("train_step") == 1
    for _ in range(4):                      # sync, local, sync, local
        trainer.train_step(x, y)
    assert staging.programs("train_step") == 2
    assert _node_types(trainer.state) == made


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_a_pipelined_step_is_one_program(schedule):
    from paddle_tpu.distributed.mesh import (CommunicateTopology,
                                             HybridCommunicateGroup)
    from paddle_tpu.distributed.meta_parallel import (PipelineLayer,
                                                      PipelineParallel)
    from paddle_tpu.text.models import gpt_pipeline_descs

    class Strategy:
        pipeline_configs = {"accumulate_steps": 2, "schedule": schedule}

    def loss_fn(logits, labels):
        return jnp.mean(nn.functional.cross_entropy(
            logits.reshape(-1, logits.shape[-1]),
            labels.reshape(-1).astype("int64")))

    build_mesh({"data": 2, "pipe": 2})
    paddle.seed(7)
    layer = PipelineLayer(
        gpt_pipeline_descs(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            max_position_embeddings=16, dropout=0.0, tensor_parallel=False,
            tie_embeddings=True),
        num_stages=2, seg_method="layer:GPTBlock")
    topo = CommunicateTopology(("data", "pipe", "sharding", "model"),
                               (2, 2, 1, 1))
    model = PipelineParallel(layer, HybridCommunicateGroup(topo, 0),
                             Strategy())
    opt = paddle.optimizer.SGD(0.05, parameters=model.parameters())
    trainer = ParallelTrainer(model, opt, loss_fn, micro_batches=2)
    made = _node_types(trainer.state)
    assert set(made.values()) == {dict}
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 64, (8, 16)).astype("int32")
    for _ in range(3):
        trainer.train_step(ids, ids)
    assert staging.programs("train_step") == 1
    assert trainer.staging_summary()["train_step"]["staged_in_steps"] == [1]
    assert _node_types(trainer.state) == made
