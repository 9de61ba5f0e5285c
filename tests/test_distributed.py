"""Distributed tests on the 8-device virtual CPU mesh — the SURVEY.md §4
translation of the reference's TestDistBase subprocess simulation
(tests/unittests/test_dist_base.py:744): verify DP/TP/PP/sharding logic
without real TPUs, asserting parallel == single-device numerics.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.mesh import (CommunicateTopology,
                                         HybridCommunicateGroup, build_mesh)


def make_mesh(**degrees):
    return build_mesh(degrees)


class TestTopology:
    def test_communicate_topology(self):
        topo = CommunicateTopology(("data", "pipe", "sharding", "model"),
                                   (2, 2, 1, 2))
        assert topo.world_size() == 8
        assert topo.get_dim("model") == 2
        coord = topo.get_coord(5)
        assert topo.get_rank(data=coord[0], pipe=coord[1],
                             sharding=coord[2], model=coord[3]) == 5
        groups = topo.get_comm_list("model")
        assert len(groups) == 4 and all(len(g) == 2 for g in groups)

    def test_hybrid_group_queries(self):
        topo = CommunicateTopology(("data", "pipe", "sharding", "model"),
                                   (2, 2, 1, 2))
        hcg = HybridCommunicateGroup(topo, global_rank=3)
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_pipe_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 2
        nxt = hcg.get_p2p_next_rank()
        assert nxt != 3


class TestCollectives:
    def test_allreduce_psum_in_shard_map(self):
        from paddle_tpu.distributed import all_reduce
        mesh = Mesh(np.array(jax.devices()), ("data",))
        x = jnp.arange(8.0)

        f = jax.shard_map(lambda v: all_reduce(v),
                          mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                          check_vma=False)
        out = f(x)
        assert float(out[0]) == float(jnp.sum(x))

    def test_allgather_and_reduce_scatter(self):
        from paddle_tpu.distributed.collective import (all_gather_concat,
                                                       reduce_scatter)
        mesh = Mesh(np.array(jax.devices()), ("data",))
        x = jnp.arange(8.0)
        g = jax.shard_map(lambda v: all_gather_concat(v),
                          mesh=mesh, in_specs=P("data"),
                          out_specs=P(None), check_vma=False)
        out = g(x)
        np.testing.assert_allclose(np.asarray(out[:8]), np.asarray(x))
        rs = jax.shard_map(lambda v: reduce_scatter(v),
                           mesh=mesh, in_specs=P(None), out_specs=P("data"),
                           check_vma=False)
        out2 = rs(jnp.ones(8))
        np.testing.assert_allclose(np.asarray(out2), 8.0)

    def test_alltoall(self):
        from paddle_tpu.distributed.collective import alltoall
        mesh = Mesh(np.array(jax.devices()), ("data",))
        x = jnp.arange(64.0 * 8).reshape(64, 8)
        f = jax.shard_map(lambda v: alltoall(v, split_axis=0, concat_axis=0),
                          mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                          check_vma=False)
        out = f(x)
        # all_to_all of row-shards = transpose of the block structure
        assert out.shape == (64, 8)


class TestTPLayers:
    def test_column_row_equivalence_with_dense(self):
        """Col+Row parallel MLP inside shard_map == dense MLP."""
        from paddle_tpu.distributed.meta_parallel import (ColumnParallelLinear,
                                                          RowParallelLinear)
        from paddle_tpu.jit.functionalization import functional_call, state_of
        paddle.seed(0)
        make_mesh(model=8)
        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 8, input_is_parallel=True)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.col, self.row = col, row

            def forward(self, x):
                return self.row(nn.functional.relu(self.col(x)))

        net = Net()
        x = jnp.asarray(np.random.RandomState(0).randn(4, 16),
                        dtype=jnp.float32)
        # dense reference using the same (full) weights
        h = nn.functional.relu(x @ col.weight.value)
        if col.bias is not None:
            h = nn.functional.relu(x @ col.weight.value + col.bias.value)
        ref = h @ row.weight.value + row.bias.value

        params, buffers = state_of(net)
        mesh = Mesh(np.array(jax.devices()), ("model",))
        specs = {"col.weight": P(None, "model"), "col.bias": P("model"),
                 "row.weight": P("model", None), "row.bias": P()}

        def f(params, x):
            out, _ = functional_call(net, params, {}, x)
            return out

        fm = jax.shard_map(f, mesh=mesh, in_specs=(specs, P()),
                           out_specs=P(), check_vma=False)
        out = fm(dict(params), x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_vocab_parallel_embedding(self):
        from paddle_tpu.distributed.meta_parallel import VocabParallelEmbedding
        from paddle_tpu.jit.functionalization import functional_call, state_of
        paddle.seed(1)
        make_mesh(model=8)
        emb = VocabParallelEmbedding(64, 16)
        ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (4, 7)))
        ref = jnp.take(emb.weight.value, ids, axis=0)
        params, _ = state_of(emb)
        mesh = Mesh(np.array(jax.devices()), ("model",))

        def f(params, ids):
            out, _ = functional_call(emb, params, {}, ids)
            return out

        fm = jax.shard_map(f, mesh=mesh,
                           in_specs=({"weight": P("model", None)}, P()),
                           out_specs=P(), check_vma=False)
        out = fm(dict(params), ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_parallel_cross_entropy(self):
        from paddle_tpu.distributed.meta_parallel import ParallelCrossEntropy
        paddle.seed(2)
        make_mesh(model=8)
        rs = np.random.RandomState(2)
        logits = jnp.asarray(rs.randn(6, 64), dtype=jnp.float32)
        labels = jnp.asarray(rs.randint(0, 64, (6,)))
        ref = nn.functional.cross_entropy(logits, labels, reduction="none")
        pce = ParallelCrossEntropy()
        mesh = Mesh(np.array(jax.devices()), ("model",))
        fm = jax.shard_map(lambda lg, lb: pce(lg, lb), mesh=mesh,
                           in_specs=(P(None, "model"), P()),
                           out_specs=P(), check_vma=False)
        out = fm(logits, labels)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestEngine:
    def _data(self):
        rs = np.random.RandomState(0)
        x = rs.randn(64, 16).astype("float32")
        y = (x.sum(1) > 0).astype("int64") * 2
        return x, y

    def _net(self):
        paddle.seed(0)
        return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))

    def test_dp_matches_single_device(self):
        from paddle_tpu.distributed.engine import ParallelTrainer
        x, y = self._data()
        loss_fn = lambda o, l: nn.functional.cross_entropy(o, l)  # noqa: E731

        # single device
        make_mesh(data=1)
        net1 = self._net()
        tr1 = ParallelTrainer(net1, paddle.optimizer.SGD(
            0.1, parameters=net1.parameters()), loss_fn)
        # 8-way DP
        make_mesh(data=8)
        paddle.seed(0)
        net8 = self._net()
        net8.set_state_dict(net1.state_dict())
        tr8 = ParallelTrainer(net8, paddle.optimizer.SGD(
            0.1, parameters=net8.parameters()), loss_fn)
        for _ in range(5):
            l1 = float(tr1.train_step(x, y))
            l8 = float(tr8.train_step(x, y))
        np.testing.assert_allclose(l1, l8, rtol=1e-4)

    def test_remat_matches_no_remat(self):
        """remat trades FLOPs for memory; the trajectory must be
        IDENTICAL (round-4 regression: the remat wrapper forwarded the
        (out, buffers) pair to loss_fn instead of the model output)."""
        from paddle_tpu.distributed.engine import ParallelTrainer
        x, y = self._data()
        loss_fn = lambda o, l: nn.functional.cross_entropy(o, l)  # noqa: E731
        make_mesh(data=1)
        net_a = self._net()
        tr_a = ParallelTrainer(net_a, paddle.optimizer.SGD(
            0.1, parameters=net_a.parameters()), loss_fn)
        paddle.seed(0)
        net_b = self._net()
        net_b.set_state_dict(net_a.state_dict())
        tr_b = ParallelTrainer(net_b, paddle.optimizer.SGD(
            0.1, parameters=net_b.parameters()), loss_fn, remat=True)
        for _ in range(4):
            la = float(tr_a.train_step(x, y))
            lb = float(tr_b.train_step(x, y))
        np.testing.assert_allclose(la, lb, rtol=1e-6)
        assert la < 1.5  # it actually trained

    def test_fp16_allreduce_tracks_fp32(self):
        """fp16_allreduce (reference fp16_allreduce_optimizer.py): grads
        cross the DP pmean as bf16. Trajectory must track the fp32
        allreduce closely — same data on every replica makes the pmean a
        near-identity, so divergence can only come from the bf16
        round-trip (~1e-2)."""
        from paddle_tpu.distributed.engine import ParallelTrainer
        x, y = self._data()
        loss_fn = lambda o, l: nn.functional.cross_entropy(o, l)  # noqa: E731
        make_mesh(data=8)
        net_a = self._net()
        tr_a = ParallelTrainer(net_a, paddle.optimizer.SGD(
            0.1, parameters=net_a.parameters()), loss_fn)
        paddle.seed(0)
        net_b = self._net()
        net_b.set_state_dict(net_a.state_dict())
        tr_b = ParallelTrainer(net_b, paddle.optimizer.SGD(
            0.1, parameters=net_b.parameters()), loss_fn,
            fp16_allreduce=True)
        la = lb = first_b = None
        for i in range(8):
            la = float(tr_a.train_step(x, y))
            lb = float(tr_b.train_step(x, y))
            if i == 0:
                first_b = lb
        assert abs(la - lb) < 2e-2, (la, lb)
        assert lb < first_b  # it actually trained

    def test_zero_sharding_specs(self):
        from paddle_tpu.distributed.meta_parallel.sharding_parallel import (
            shard_spec_for)
        v = jnp.zeros((64, 128))
        spec = shard_spec_for(v, n_shards=8, min_size=16)
        assert "sharding" in str(spec)
        tiny = jnp.zeros((4,))
        assert shard_spec_for(tiny, n_shards=8, min_size=1024) == P()

    def test_pp_loss_matches_single_device(self):
        from paddle_tpu.distributed.engine import ParallelTrainer
        from paddle_tpu.distributed.meta_parallel import (LayerDesc,
                                                          PipelineLayer,
                                                          PipelineParallel)
        paddle.seed(3)
        x, y = self._data()
        loss_fn = lambda o, l: nn.functional.cross_entropy(o, l)  # noqa: E731
        descs = [LayerDesc(nn.Linear, 16, 16) for _ in range(3)] + \
            [LayerDesc(nn.Linear, 16, 4)]
        pl_ = PipelineLayer(descs, num_stages=4)
        # single-device forward loss
        out_ref = pl_(jnp.asarray(x))
        ref_loss = float(loss_fn(out_ref, jnp.asarray(y)))

        make_mesh(pipe=4, data=2)
        topo = CommunicateTopology(("data", "pipe", "sharding", "model"),
                                   (2, 4, 1, 1))
        hcg = HybridCommunicateGroup(topo, 0)

        class Strat:
            pipeline_configs = {"accumulate_steps": 4}

        pp = PipelineParallel(pl_, hcg, Strat())
        tr = ParallelTrainer(pp, paddle.optimizer.SGD(
            0.0, parameters=pp.parameters()), loss_fn, micro_batches=4)
        l = float(tr.train_step(x, y))
        np.testing.assert_allclose(l, ref_loss, rtol=1e-4)

    @staticmethod
    def _resnet50_bf16():
        from paddle_tpu.distributed.engine import ParallelTrainer
        from paddle_tpu.vision.models import resnet50
        model = resnet50(num_classes=10)
        model.bfloat16()
        opt = paddle.optimizer.Momentum(0.01, momentum=0.9,
                                        parameters=model.parameters())
        tr = ParallelTrainer(
            model, opt, lambda o, y: nn.functional.cross_entropy(o, y))
        rng = np.random.RandomState(0)
        # XLA convolutions want the input in the weights' dtype
        imgs = jnp.asarray(rng.randn(4, 3, 32, 32), jnp.bfloat16)
        return tr, imgs, rng.randint(0, 10, (4,)).astype("int32")

    @staticmethod
    def _bert_bf16_grad_scaler():
        from paddle_tpu.amp import GradScaler
        from paddle_tpu.distributed.engine import ParallelTrainer
        from paddle_tpu.text.models import BertForPretraining
        model = BertForPretraining(
            tensor_parallel=False, vocab_size=128, hidden_size=32,
            num_layers=1, num_heads=4, max_position_embeddings=64,
            attn_dropout=0.0, hidden_dropout=0.0)
        model.bfloat16()
        opt = paddle.optimizer.AdamW(5e-3, parameters=model.parameters())
        tr = ParallelTrainer(
            model, opt, lambda out, lbl: model.loss(*out, *lbl),
            scaler=GradScaler(enable=True, init_loss_scaling=1024.0))
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (4, 16)).astype("int32")
        mlm = np.full((4, 16), -100, dtype="int32")
        mlm[:, ::4] = rng.randint(0, 128, (4, 4))
        return tr, ids, (mlm, rng.randint(0, 2, (4,)).astype("int32"))

    @staticmethod
    def _gpt_fused_ce_remat_bf16_moments():
        from paddle_tpu.distributed.engine import ParallelTrainer
        from paddle_tpu.text.models import GPTForPretraining
        model = GPTForPretraining(
            tensor_parallel=False, vocab_size=256, hidden_size=32,
            num_layers=2, num_heads=2, max_position_embeddings=32,
            attn_dropout=0.0, hidden_dropout=0.0)
        model.bfloat16()

        class FusedLoss(nn.Layer):
            """(ids, labels) -> loss: the logits never materialize."""

            def __init__(self, inner):
                super().__init__()
                self.inner = inner

            def forward(self, batch):
                return self.inner.fused_head_loss(*batch, chunk=64)

        opt = paddle.optimizer.AdamW(5e-3, parameters=model.parameters(),
                                     slot_dtype="bfloat16")
        tr = ParallelTrainer(FusedLoss(model), opt,
                             lambda out, _lbl: out, remat=True)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 256, (4, 32)).astype("int32")
        labels = rng.randint(0, 256, (4, 32)).astype("int32")
        return tr, (ids, labels), 0.0

    @pytest.mark.slow  # 70 s, 13 s, 15 s: each compiles its step twice
    @pytest.mark.parametrize("build", ["_resnet50_bf16",
                                       "_bert_bf16_grad_scaler",
                                       "_gpt_fused_ce_remat_bf16_moments"])
    def test_model_walks_through_trainer(self, build):
        """The model families no benchmark cell trains still meet
        ParallelTrainer: five steps on one fixed batch, loss finite and
        falling, one program staged in the first step and none after."""
        from paddle_tpu import telemetry
        make_mesh(data=1)
        paddle.seed(0)
        with telemetry.scope(profile=False) as tel:
            tr, inputs, labels = getattr(self, build)()
            losses = [float(tr.train_step(inputs, labels))]
            assert tel.registry.get("recompiles_total").value() == 1
            losses += [float(tr.train_step(inputs, labels))
                       for _ in range(4)]
            assert tel.registry.get("recompiles_total").value() == 1
        assert np.all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        if tr.scaler is not None:
            amp = tr.state["guard"]["amp"]
            assert int(amp["good"]) == 5 and int(amp["bad"]) == 0


class TestRingAttention:
    def test_matches_full_attention(self):
        from paddle_tpu.ops.ring_attention import ring_flash_attention
        from paddle_tpu.nn.functional.attention import _xla_attention
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(2, 64, 2, 16), dtype=jnp.float32)
        k = jnp.asarray(rs.randn(2, 64, 2, 16), dtype=jnp.float32)
        v = jnp.asarray(rs.randn(2, 64, 2, 16), dtype=jnp.float32)
        mesh = Mesh(np.array(jax.devices()), ("sep",))
        for causal in (False, True):
            f = jax.shard_map(
                lambda a, b, c: ring_flash_attention(a, b, c, causal=causal),
                mesh=mesh, in_specs=(P(None, "sep"),) * 3,
                out_specs=P(None, "sep"), check_vma=False)
            out = f(q, k, v)
            ref = _xla_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)


class TestMoE:
    def test_expert_parallel_matches_local(self):
        from paddle_tpu.incubate import MoELayer
        from paddle_tpu.jit.functionalization import functional_call, state_of
        paddle.seed(0)
        moe = MoELayer(d_model=16, d_hidden=32, num_experts=8,
                       axis_name="model")
        x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16),
                        dtype=jnp.float32)
        y_local = moe(x)
        params, _ = state_of(moe)
        mesh = Mesh(np.array(jax.devices()), ("model",))

        def f(p, xx):
            out, _ = functional_call(moe, p, {}, xx)
            return out

        fm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                           check_vma=False)
        y_ep = fm(dict(params), x)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_local),
                                   rtol=1e-5, atol=1e-5)


class TestFlashAttentionInterpret:
    """Kernel correctness via the pallas interpreter (runs on CPU)."""

    def test_fwd_matches_xla(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        from paddle_tpu.nn.functional.attention import _xla_attention
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 256, 2, 64), dtype=jnp.float32)
        k = jnp.asarray(rs.randn(1, 256, 2, 64), dtype=jnp.float32)
        v = jnp.asarray(rs.randn(1, 256, 2, 64), dtype=jnp.float32)
        for causal in (False, True):
            out = flash_attention(q, k, v, causal=causal, interpret=True,
                                  block_q=128, block_k=128)
            ref = _xla_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)

    def test_grads_match_xla(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        from paddle_tpu.nn.functional.attention import _xla_attention
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(1, 128, 1, 64), dtype=jnp.float32)
        k = jnp.asarray(rs.randn(1, 128, 1, 64), dtype=jnp.float32)
        v = jnp.asarray(rs.randn(1, 128, 1, 64), dtype=jnp.float32)
        gf = jax.grad(lambda a, b, c: jnp.sum(
            flash_attention(a, b, c, causal=True, interpret=True, block_q=128, block_k=128) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: jnp.sum(
            _xla_attention(a, b, c, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)


class TestGPTHybridSmoke:
    def test_gpt_tp_forward(self):
        from paddle_tpu.jit.functionalization import functional_call, state_of
        from paddle_tpu.text.models import GPTForPretraining, gpt_tiny
        paddle.seed(0)
        make_mesh(model=8)
        model = GPTForPretraining(tensor_parallel=True,
                                  **gpt_tiny(hidden_size=64, num_heads=8))
        model.eval()
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 1024, (2, 32)))
        ref = model(ids)  # single-device (no axis bound → dense fallbacks)
        params, buffers = state_of(model)
        specs = {n: (p.pspec if p.pspec is not None else P())
                 for n, p in model.named_parameters()}
        mesh = Mesh(np.array(jax.devices()).reshape(1, 1, 1, 1, 8),
                    ("data", "pipe", "sharding", "sep", "model"))

        def f(params, ids):
            out, _ = functional_call(model, params, buffers, ids)
            return out

        fm = jax.shard_map(f, mesh=mesh,
                           in_specs=(specs, P()),
                           out_specs=P(None, None, "model"), check_vma=False)
        out = fm(dict(params), ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)


class TestReviewRegressions:
    """Fixes from code review: aux-loss through jit, ragged flash raise."""

    def test_moe_aux_loss_flows_through_jit(self):
        from paddle_tpu.incubate import MoELayer
        from paddle_tpu.jit.functionalization import functional_call, state_of
        paddle.seed(0)
        moe = MoELayer(d_model=16, d_hidden=32, num_experts=4)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16),
                        dtype=jnp.float32)
        y_eager = moe(x)
        aux_eager = float(moe.aux_loss)
        params, buffers = state_of(moe)

        @jax.jit
        def f(p, b, xx):
            out, nb = functional_call(moe, p, b, xx)
            return out, nb["aux_loss"]

        out, aux = f(dict(params), dict(buffers), x)
        assert abs(float(aux) - aux_eager) < 1e-6
        np.testing.assert_allclose(np.asarray(out), np.asarray(y_eager),
                                   rtol=1e-5, atol=1e-5)

    def test_flash_attention_ragged_seq_supported(self):
        """Round 3: ragged (non-128-multiple) sequences run the kernel via
        tail padding + in-kernel column masking (previously a ValueError)."""
        from paddle_tpu.nn.functional.attention import _xla_attention
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        rs = np.random.RandomState(2)
        q = jnp.asarray(rs.randn(1, 200, 2, 64), jnp.float32)
        for causal in (False, True):
            out = flash_attention(q, q, q, causal=causal, interpret=True)
            ref = _xla_attention(q, q, q, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-3, atol=2e-3)


class TestZeroStage3:
    """ZeRO-3 parameter sharding: storage is 1/n per device, numerics match
    dense training exactly (reference sharding_optimizer.py:43 stage p_g_os)."""

    def _make(self, stage, degrees):
        make_mesh(**degrees)
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 64), nn.ReLU(),
                            nn.Linear(64, 64), nn.ReLU(), nn.Linear(64, 4))
        opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters())
        from paddle_tpu.distributed.engine import ParallelTrainer
        return ParallelTrainer(
            net, opt, lambda o, y: nn.functional.cross_entropy(o, y),
            zero_stage=stage)

    def test_stage3_matches_dense(self):
        rng = np.random.RandomState(0)
        xs = rng.randn(8, 16).astype("float32")
        ys = rng.randint(0, 4, (8,)).astype("int64")
        tr0 = self._make(0, {"data": 4})
        l0 = [float(tr0.train_step(xs, ys)) for _ in range(5)]
        tr3 = self._make(3, {"data": 2, "sharding": 2})
        l3 = [float(tr3.train_step(xs, ys)) for _ in range(5)]
        np.testing.assert_allclose(l0, l3, rtol=5e-4)

    def test_stage3_param_storage_is_sharded(self):
        tr3 = self._make(3, {"sharding": 4})
        p = tr3.state["params"]["2.weight"]  # (64, 64) -> divisible
        assert p.addressable_shards[0].data.size * 4 == p.size

    def test_group_sharded_api_records_stage(self):
        from paddle_tpu.distributed.sharding import (get_group_sharded_stage,
                                                     group_sharded_parallel)
        make_mesh(sharding=4)
        paddle.seed(0)
        net = nn.Linear(8, 8)
        opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters())
        m, o, _ = group_sharded_parallel(net, opt, "p_g_os")
        assert get_group_sharded_stage(m) == 3


class TestFlashDefaultBlocks:
    """Numeric coverage for the shipped default (256, 512) blocks and the
    LANES-aligned clamp path (a regression specific to the default geometry
    must not ship untested)."""

    def test_default_blocks_match_xla_s512(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        from paddle_tpu.nn.functional.attention import _xla_attention
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 512, 2, 64), jnp.float32)
        out = flash_attention(q, q, q, causal=True, interpret=True)
        ref = _xla_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_clamped_blocks_match_xla_s384(self):
        # 384 forces the clamp: block_q 256->128 (divisor), block_k 512->384
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        from paddle_tpu.nn.functional.attention import _xla_attention
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(1, 384, 1, 64), jnp.float32)
        for causal in (False, True):
            out = flash_attention(q, q, q, causal=causal, interpret=True)
            ref = _xla_attention(q, q, q, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-3, atol=2e-3)


class TestZeroStage2:
    """ZeRO-2: gradients reduce-scattered over the sharding axis (sharded
    accumulation buffers under gradient merge), numerics equal to dense."""

    def _make(self, stage, degrees, K=2):
        make_mesh(**degrees)
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 64), nn.ReLU(),
                            nn.Linear(64, 64), nn.ReLU(), nn.Linear(64, 4))
        opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters())
        from paddle_tpu.distributed.engine import ParallelTrainer
        return ParallelTrainer(
            net, opt, lambda o, y: nn.functional.cross_entropy(o, y),
            zero_stage=stage, accumulate_steps=K)

    def test_stage2_matches_dense_with_accumulation(self):
        rng = np.random.RandomState(0)
        xs = rng.randn(8, 16).astype("float32")
        ys = rng.randint(0, 4, (8,)).astype("int64")
        tr0 = self._make(0, {"data": 4})
        l0 = [float(tr0.train_step(xs, ys)) for _ in range(5)]
        tr2 = self._make(2, {"data": 2, "sharding": 2})
        l2 = [float(tr2.train_step(xs, ys)) for _ in range(5)]
        np.testing.assert_allclose(l0, l2, rtol=5e-4)

    def test_stage2_skips_tp_sharded_params(self):
        # TP param keeps its 'model' axis; only replicated params get
        # zero-2 grad sharding, and TP x zero-2 matches TP dense exactly
        from paddle_tpu.distributed.meta_parallel.parallel_layers.mp_layers \
            import ColumnParallelLinear, RowParallelLinear
        from paddle_tpu.distributed.engine import ParallelTrainer

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.c = ColumnParallelLinear(16, 64)
                self.r = RowParallelLinear(64, 4)
                self.plain = nn.Linear(16, 16)

            def forward(self, x):
                return self.r(nn.functional.relu(self.c(self.plain(x))))

        rng = np.random.RandomState(0)
        xs = rng.randn(8, 16).astype("float32")
        ys = rng.randint(0, 4, (8,)).astype("int64")

        def run(stage, degrees):
            make_mesh(**degrees)
            paddle.seed(0)
            net = Net()
            opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters())
            tr = ParallelTrainer(
                net, opt, lambda o, y: nn.functional.cross_entropy(o, y),
                zero_stage=stage, accumulate_steps=2)
            if stage == 2:
                assert "c.weight" not in tr.zero2_dims
                assert "r.weight" not in tr.zero2_dims
            return [float(tr.train_step(xs, ys)) for _ in range(5)]

        l0 = run(0, {"data": 2, "model": 2})
        l2 = run(2, {"data": 2, "sharding": 2, "model": 2})
        np.testing.assert_allclose(l0, l2, rtol=5e-4)


class TestSequenceParallelTraining:
    """End-to-end context parallelism: GPT trained with its sequence split
    over the "sep" axis (ring attention rotating K/V chunks) must produce
    the SAME loss trajectory as dense training (SURVEY §5 long-context
    capability, exceeding the reference)."""

    @pytest.mark.slow
    def test_gpt_sep2_matches_dense(self):
        from paddle_tpu.distributed.engine import ParallelTrainer
        from paddle_tpu.text.models import GPTForPretraining
        cfg = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                   max_position_embeddings=64, attn_dropout=0.0,
                   hidden_dropout=0.0)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (4, 64)).astype("int32")
        lbl = rng.randint(0, 128, (4, 64)).astype("int32")

        def run(degrees):
            make_mesh(**degrees)
            paddle.seed(0)
            m = GPTForPretraining(tensor_parallel=False, **cfg)
            opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
            tr = ParallelTrainer(m, opt, lambda lg, lb: m.loss(lg, lb))
            return [float(tr.train_step(ids, lbl)) for _ in range(4)]

        l_dense = run({"data": 2})
        l_sep = run({"data": 2, "sep": 2})
        np.testing.assert_allclose(l_dense, l_sep, rtol=1e-3)

    @pytest.mark.slow
    def test_gpt_sep_with_tp_composition(self):
        from paddle_tpu.distributed.engine import ParallelTrainer
        from paddle_tpu.text.models import GPTForPretraining
        cfg = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
                   max_position_embeddings=64, attn_dropout=0.0,
                   hidden_dropout=0.0)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (4, 64)).astype("int32")
        lbl = rng.randint(0, 128, (4, 64)).astype("int32")

        def run(degrees, tp):
            make_mesh(**degrees)
            paddle.seed(0)
            m = GPTForPretraining(tensor_parallel=tp, **cfg)
            opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
            tr = ParallelTrainer(m, opt, lambda lg, lb: m.loss(lg, lb))
            return [float(tr.train_step(ids, lbl)) for _ in range(4)]

        l_dense = run({"data": 2}, False)
        l_hybrid = run({"data": 2, "sep": 2, "model": 2}, True)
        np.testing.assert_allclose(l_dense, l_hybrid, rtol=2e-3)

    @pytest.mark.slow
    def test_sep_with_pytree_rank1_labels(self):
        """sep>1 with a label PYTREE containing a rank-1 leaf: the engine
        must pick per-leaf data specs (rank-1 leaves have no sequence dim to
        split over "sep") instead of crashing with a rank-2 spec on a rank-1
        array (round-2 advisor finding, engine.py per-leaf specs)."""
        from paddle_tpu.distributed.engine import ParallelTrainer
        from paddle_tpu.text.models import GPTForPretraining
        cfg = dict(vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
                   max_position_embeddings=32, attn_dropout=0.0,
                   hidden_dropout=0.0)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (4, 32)).astype("int32")
        lbl = rng.randint(0, 64, (4, 32)).astype("int32")
        wgt = np.ones((4,), "float32")  # rank-1 per-row weight leaf

        def loss_fn(logits, labels):
            tok, w = labels
            per_tok = nn.functional.cross_entropy(
                logits.reshape(-1, logits.shape[-1]), tok.reshape(-1),
                reduction="none")
            per_row = per_tok.reshape(tok.shape).mean(axis=1)
            return (per_row * w).sum() / w.sum()

        def run(degrees):
            make_mesh(**degrees)
            paddle.seed(0)
            m = GPTForPretraining(tensor_parallel=False, **cfg)
            opt = paddle.optimizer.SGD(1e-2, parameters=m.parameters())
            tr = ParallelTrainer(m, opt, loss_fn)
            return [float(tr.train_step(ids, (lbl, wgt)))
                    for _ in range(3)]

        l_dense = run({"data": 2})
        l_sep = run({"data": 2, "sep": 2})
        np.testing.assert_allclose(l_dense, l_sep, rtol=1e-3)
