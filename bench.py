"""Benchmark suite: training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
The primary metric is GPT-base (124M) bf16 tokens/sec/chip (best variant
of a small batch-size x loss-path sweep); "extra" carries the additional
BASELINE.md configs (ResNet-50 images/sec, BERT-base AMP samples/sec,
Wide&Deep CTR samples/sec, GPT-1.3B tokens/sec + peak HBM) so the perf
story is not a single model.

Process architecture: the parent process NEVER imports jax, because a
chip belongs to one process at a time. Every benchmark config runs in a
fresh subprocess, one after the other, with a wall-clock timeout; a
failure records {"error": ...} for that config instead of ending the
whole bench.

FLOPs convention (stated per round-2 verdict): MFU uses the 6N
approximation — 6 FLOPs per parameter per token (fwd 2N + bwd 4N),
EXCLUDING attention score/context FLOPs (the PaLM-appendix convention
without the 12*L*H*Q*T term). Peak is the published bf16 figure of the
device kind the child ran on (telemetry.PUBLISHED_PEAKS).

The reference publishes no in-repo numbers (BASELINE.md), so vs_baseline
is 1.0 on success; the absolute numbers are the tracked quantity.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback

_MARK = "##BENCHJSON## "
_HERE = os.path.dirname(os.path.abspath(__file__))

# per-config child wall-clock budgets (compile + warmup + timed iters);
# the sweep configs compile several step variants
CHILD_TIMEOUT = {"numerics": 300, "op_pallas": 420,
                 "gpt_base": 1200, "gpt_1p3b": 900, "heter_ctr": 600}
CHILD_TIMEOUT_DEFAULT = 600
GLOBAL_BUDGET_S = 2700  # stop launching new configs past this

# numerics first: the on-chip kernel-vs-dense validation (r3 item 10) is
# cheap and must not be starved by the budget; heter_ctr last (r3 item
# 2's 10x A/B — informative, not the headline)
CONFIG_ORDER = ("numerics", "op_pallas", "gpt_base", "resnet50",
                "bert_base_amp", "widedeep_ctr", "gpt_1p3b", "heter_ctr")


# --------------------------------------------------------------------------
# child side: one process = one backend init = one config
# --------------------------------------------------------------------------

def _child_setup():
    """Backend init inside the child: the compile cache shared with the
    other children and with later runs, then the first device query."""
    import jax

    from tools._mesh_setup import use_compile_cache

    use_compile_cache()
    jax.devices()
    return jax


def _bf16_peak(jax):
    from paddle_tpu import telemetry
    return telemetry.published_peak(
        jax.devices()[0].device_kind)["bf16_flops_per_sec"]


def _emit(payload: dict):
    sys.stdout.write(_MARK + json.dumps(payload) + "\n")
    sys.stdout.flush()


def _timed_steps(trainer, inputs, labels, warmup: int, iters: int):
    """Run warmup + timed steps; the host fetch of the loss is the sync
    point that ends the timed region."""
    for _ in range(warmup):
        loss = trainer.train_step(inputs, labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = trainer.train_step(inputs, labels)
    final_loss = float(loss)
    return time.perf_counter() - t0, final_loss


def _hbm_peak_gb(jax):
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        return round(peak / 2**30, 3) if peak else None
    except Exception:
        return None


def _make_fused_loss(inner, chunk, ce_kernel="chunked"):
    """Wrap a model exposing fused_head_loss as a (ids, labels) -> loss
    Layer, so ParallelTrainer drives a fused head+CE path (the (B*S,
    vocab) logits never materialize). ce_kernel: "chunked" =
    ops/chunked_ce.py jnp scan, "pallas" = the Mosaic kernel in
    ops/pallas/fused_ce.py (interpret mode off-TPU)."""
    from paddle_tpu import nn

    class FusedLoss(nn.Layer):
        def __init__(self, inner_):
            super().__init__()
            self.inner = inner_

        def forward(self, batch_):
            ids, lbl = batch_
            return self.inner.fused_head_loss(ids, lbl, chunk=chunk,
                                              ce_kernel=ce_kernel)

    return FusedLoss(inner)


def _gpt_variant(jax, on_tpu, batch, seq, vocab, cfg, fused, chunk=8192,
                 remat=False, grad_sync=None, ce_kernel="chunked"):
    """Measure one (batch, loss-path, remat, grad-sync) GPT-base variant.

    fused=True routes through GPTForPretraining.fused_head_loss
    (ops/chunked_ce.py) so the (B*S, vocab) logits never materialize;
    fused=False is the dense-logits + lse-gather CE path. grad_sync
    ("int8"/"int4"/"bf16") compresses the DP gradient exchange
    (distributed/compressed.py) — over all local devices on TPU, a
    single-device mesh otherwise (measures the quantize overhead)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, telemetry
    from paddle_tpu.distributed.engine import ParallelTrainer
    from tools._mesh_setup import data_mesh
    from paddle_tpu.text.models import GPTForPretraining

    paddle.seed(0)
    ndev = len(jax.devices()) if (on_tpu and grad_sync) else 1
    data_mesh(ndev)
    # fresh per-variant registry, no run_dir/profiler: the per-step sync
    # telemetry adds is the loss fetch _timed_steps does anyway
    with telemetry.scope(profile=False) as tel:
        model = GPTForPretraining(
            tensor_parallel=False, vocab_size=vocab, hidden_size=cfg["h"],
            num_layers=cfg["l"], num_heads=cfg["n"],
            max_position_embeddings=seq, attn_dropout=0.0,
            hidden_dropout=0.0)
        model.bfloat16()
        opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters())

        sync_kw = dict(grad_sync=grad_sync) if grad_sync else {}
        if fused:
            trainer = ParallelTrainer(
                _make_fused_loss(model, chunk, ce_kernel), opt,
                lambda out, _lbl: out, remat=remat, **sync_kw)
        else:
            trainer = ParallelTrainer(
                model, opt,
                # bf16 logits straight into the fused lse-gather CE fast
                # path (fp32 accumulation inside; astype here would
                # materialize a full fp32 (b, s, vocab) tensor)
                lambda logits, lbl: nn.functional.cross_entropy(logits, lbl),
                remat=remat, **sync_kw)

        rng = np.random.RandomState(0)
        ids = rng.randint(0, vocab, (batch, seq)).astype("int32")
        labels = rng.randint(0, vocab, (batch, seq)).astype("int32")
        iters = 16 if on_tpu else 3
        warmup = 8 if on_tpu else 2
        inputs = (ids, labels) if fused else ids
        lbls = 0.0 if fused else labels
        dt, final_loss = _timed_steps(trainer, inputs, lbls, warmup, iters)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    out = {"tokens_per_sec": round(batch * seq * iters / dt, 1),
           "params": n_params, "final_loss": round(final_loss, 4),
           "telemetry": _harvest_telemetry(tel.registry),
           # predicted-vs-measured step time of this variant's last step
           # (engine._record_step_telemetry pairs the overlap model's
           # makespan with the wall clock; telemetry.calibration)
           "calibration": telemetry.calibration.pair("step_time")}
    if on_tpu:
        # memory_stats peak is process-cumulative: attributable to THIS
        # variant only while the sweep runs smallest-footprint-first
        out["hbm_peak_so_far_gb"] = _hbm_peak_gb(jax)
    return out


def _harvest_telemetry(reg):
    """Registry -> the compact telemetry dict appended to bench JSON."""
    def val(name, default=None):
        m = reg.get(name)
        return m.value() if m is not None else default
    return {
        "mfu": round(val("mfu", 0.0), 6),
        "recompiles": int(val("recompiles_total", 0)),
        "wire_bytes": val("grad_sync_bytes_total", 0.0),
        "compression_x": round(val("grad_sync_compression_x", 0.0), 3),
        "step_time_avg_s": round(val("step_time_seconds", 0.0), 6),
    }


def bench_gpt(jax, on_tpu):
    """GPT-base 124M with a batch x loss-path sweep (round-3 verdict:
    the b=8 dense-only number sat at the bottom of the MFU band and the
    chunked-CE op was built but never measured). The best variant is the
    headline; every variant is recorded."""
    vocab, seq = (50304, 1024) if on_tpu else (1024, 128)
    cfg = {"h": 768, "l": 12, "n": 12} if on_tpu else \
        {"h": 128, "l": 2, "n": 4}
    # ordered smallest HBM footprint first (fused before dense at each
    # batch) so per-variant hbm_peak_so_far_gb increments are
    # attributable; remat trades FLOPs for memory — measured once at the
    # largest batch (the only place it could pay on this 124M model)
    variants = ([("fused_b8", dict(batch=8, fused=True)),
                 ("dense_b8", dict(batch=8, fused=False)),
                 ("fused_b16", dict(batch=16, fused=True)),
                 ("dense_b16", dict(batch=16, fused=False)),
                 ("fused_b32", dict(batch=32, fused=True)),
                 ("fused_b32_remat", dict(batch=32, fused=True,
                                          remat=True)),
                 ("dense_b32", dict(batch=32, fused=False)),
                 # compressed DP grad exchange over all chips (per-chip
                 # batch 8): same model, 4x (int8) / 7x (int4) fewer
                 # gradient bytes on wire
                 ("fused_b8_int8dp", dict(batch=8, fused=True,
                                          grad_sync="int8")),
                 ("fused_b8_int4dp", dict(batch=8, fused=True,
                                          grad_sync="int4")),
                 # Pallas fused-CE kernel (ops/pallas/fused_ce.py):
                 # head matmul + softmax-CE in one Mosaic kernel, block
                 # configs from the tuning DB
                 ("fused_b8_pallas_ce", dict(batch=8, fused=True,
                                             ce_kernel="pallas"))]
                if on_tpu else
                [("fused_b4", dict(batch=4, fused=True)),
                 ("dense_b4", dict(batch=4, fused=False)),
                 ("fused_b4_int8dp", dict(batch=4, fused=True,
                                          grad_sync="int8")),
                 ("fused_b4_int4dp", dict(batch=4, fused=True,
                                          grad_sync="int4")),
                 # interpret-mode on CPU: correctness + plumbing only
                 ("fused_b4_pallas_ce", dict(batch=4, fused=True,
                                             ce_kernel="pallas"))])
    sweep, best, best_name = {}, None, None
    out = None
    for name, kw in variants:
        try:
            r = _gpt_variant(jax, on_tpu, seq=seq, vocab=vocab, cfg=cfg,
                             **kw)
            sweep[name] = r
            if best is None or \
                    r["tokens_per_sec"] > best["tokens_per_sec"]:
                best, best_name = r, name
        except Exception as e:  # OOM etc.: record, keep sweeping
            sweep[name] = {"error": f"{type(e).__name__}: {e}"}
        if best is None:
            continue
        # interim emit: a child killed at its timeout later in the sweep
        # must not discard the variants already measured (the parent
        # keeps the LAST mark line)
        out = dict(best)
        out["variant"] = best_name
        out["sweep"] = dict(sweep)
        if on_tpu:
            out["mfu_6N"] = round(
                out["tokens_per_sec"] * 6 * out["params"] / _bf16_peak(jax),
                4)
        out["on_tpu"] = on_tpu
        out["backend"] = jax.default_backend()
        out["partial"] = name != variants[-1][0]
        _emit(out)
    if best is None:
        raise RuntimeError(f"all GPT-base variants failed: {sweep}")
    # auto-parallel planner over the same shape: search the config
    # space on the calibrated cost model, RUN the chosen config, and
    # record planned-vs-measured step time so the drift lands in
    # calibration_drift_ratio{key=planner_step_time}
    try:
        out["planner"] = _gpt_planner(jax, on_tpu, vocab, seq, cfg)
    except Exception as e:
        out["planner"] = {"error": f"{type(e).__name__}: {e}"}
    out.pop("partial", None)
    return out


def _gpt_planner(jax, on_tpu, vocab, seq, cfg):
    """plan_search at the bench GPT shape + a measured run of its pick.

    Closes the planner's own calibration loop: predicted step time (the
    search's scoring model under the calibrated constants) vs the
    measured step time of actually running the chosen ParallelTrainer
    config, recorded under the planner_step_time key."""
    from paddle_tpu import telemetry
    from tools import bench_plan

    spec = dict(vocab=vocab, h=cfg["h"], layers=cfg["l"], heads=cfg["n"],
                seq=seq, batch_per_device=8 if on_tpu else 4)
    n = len(jax.devices()) if on_tpu else 1
    builder = bench_plan.make_gpt_builder(
        spec, spec["batch_per_device"] * n)
    ranked, baselines, n_params = bench_plan.search(
        spec, n, stage_top_k=1, builder=builder)
    pick = ranked[0]
    predicted = pick.predicted.total
    trainer, ids, labels = builder(pick)
    iters = 16 if on_tpu else 2
    warmup = 8 if on_tpu else 1
    dt, final_loss = _timed_steps(trainer, ids, labels, warmup, iters)
    measured = dt / iters
    telemetry.calibration.record("planner_step_time", predicted, measured)
    return {"pick": pick.to_dict(), "baselines": baselines,
            "predicted_s": predicted, "measured_s": measured,
            "final_loss": round(final_loss, 4),
            "calibration": telemetry.calibration.pair(
                "planner_step_time")}


def bench_gpt_1p3b(jax, on_tpu):
    """BASELINE configs[3]: GPT-3 1.3B on ONE chip — proves the memory
    machinery (remat + bf16 moments + chunked CE) at real scale. The
    hybrid multi-chip layout for the same model is exercised by
    __graft_entry__.dryrun_multichip; this measures what a single 16 GB
    v5e can hold: params bf16 2.6 GB + AdamW m/v bf16 5.3 GB + rematted
    activations, with the (B*S, 50304) logits never materialized."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.engine import ParallelTrainer
    from tools._mesh_setup import data_mesh
    from paddle_tpu.text.models import GPTForPretraining

    if on_tpu:
        vocab, h, layers, heads, seq, batch = 50304, 2048, 24, 16, 1024, 8
        iters, warmup = 10, 4
    else:
        vocab, h, layers, heads, seq, batch = 1024, 256, 4, 4, 128, 2
        iters, warmup = 2, 1

    paddle.seed(0)
    data_mesh(1)
    model = GPTForPretraining(
        tensor_parallel=False, vocab_size=vocab, hidden_size=h,
        num_layers=layers, num_heads=heads, max_position_embeddings=seq,
        attn_dropout=0.0, hidden_dropout=0.0)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(2e-4, parameters=model.parameters(),
                                 slot_dtype="bfloat16")
    trainer = ParallelTrainer(_make_fused_loss(model, 8192), opt,
                              lambda out, _lbl: out, remat=True)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq)).astype("int32")
    labels = rng.randint(0, vocab, (batch, seq)).astype("int32")
    dt, final_loss = _timed_steps(trainer, (ids, labels), 0.0,
                                  warmup, iters)
    tokens_per_sec = batch * seq * iters / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    out = {"tokens_per_sec": round(tokens_per_sec, 1), "params": n_params,
           "final_loss": round(final_loss, 4)}
    if on_tpu:
        out["mfu_6N"] = round(
            tokens_per_sec * 6 * n_params / _bf16_peak(jax), 4)
        out["peak_hbm_gb"] = _hbm_peak_gb(jax)
    return out


def bench_resnet50(jax, on_tpu):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.engine import ParallelTrainer
    from tools._mesh_setup import data_mesh
    from paddle_tpu.vision.models import resnet50, resnet18

    paddle.seed(0)
    data_mesh(1)
    if on_tpu:
        model, batch, size, iters, warmup = resnet50(), 128, 224, 20, 8
    else:
        model, batch, size, iters, warmup = resnet18(), 4, 32, 2, 1
    model.bfloat16()  # TPU AMP O2 equivalent: bf16 params + compute
    opt = paddle.optimizer.Momentum(0.1, momentum=0.9,
                                    parameters=model.parameters())
    trainer = ParallelTrainer(
        model, opt, lambda o, y: nn.functional.cross_entropy(o, y))
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    # inputs must match the bf16 conv weights (XLA convs are same-dtype)
    imgs = jnp.asarray(rng.randn(batch, 3, size, size), dtype=jnp.bfloat16)
    lbls = rng.randint(0, 1000, (batch,)).astype("int32")
    dt, final_loss = _timed_steps(trainer, imgs, lbls, warmup, iters)
    return {"images_per_sec": round(batch * iters / dt, 1),
            "final_loss": round(final_loss, 4)}


def bench_widedeep(jax, on_tpu):
    """BASELINE configs[4]: sparse recommender throughput (Criteo-shaped
    synthetic CTR: 26 categorical fields + 13 dense)."""
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed.engine import ParallelTrainer
    from tools._mesh_setup import data_mesh
    from paddle_tpu.rec import WideDeep

    paddle.seed(0)
    data_mesh(1)
    if on_tpu:
        fields, batch, iters, warmup = [100_000] * 26, 4096, 20, 8
        hidden = (400, 400, 400)
    else:
        fields, batch, iters, warmup = [1000] * 8, 256, 2, 1
        hidden = (64, 32)
    model = WideDeep(fields, dense_dim=13, embedding_dim=16,
                     hidden_sizes=hidden)
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())

    def bce(logit, y):
        return jnp.mean(jnp.maximum(logit, 0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    trainer = ParallelTrainer(model, opt, bce)
    rng = np.random.RandomState(0)
    ids = np.stack([rng.randint(0, d, batch) for d in fields], 1) \
        .astype("int64")
    dense = rng.randn(batch, 13).astype("float32")
    label = rng.randint(0, 2, batch).astype("float32")
    dt, final_loss = _timed_steps(trainer, (ids, dense), label,
                                  warmup, iters)
    return {"samples_per_sec": round(batch * iters / dt, 1),
            "final_loss": round(final_loss, 4)}


def bench_bert_amp(jax, on_tpu):
    """BERT-base MLM+NSP, bf16 (the TPU AMP: reference fp16_utils.py:322
    cast_model_to_fp16 O2 maps to whole-model bf16 on TPU)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.engine import ParallelTrainer
    from tools._mesh_setup import data_mesh
    from paddle_tpu.text.models import BertForPretraining

    paddle.seed(0)
    data_mesh(1)
    if on_tpu:
        cfg = dict(vocab_size=30528, hidden_size=768, num_layers=12,
                   num_heads=12, max_position_embeddings=512)
        batch, seq, iters, warmup = 16, 128, 20, 8
    else:
        cfg = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                   num_heads=4, max_position_embeddings=128)
        batch, seq, iters, warmup = 4, 64, 2, 1
    model = BertForPretraining(tensor_parallel=False, attn_dropout=0.0,
                               hidden_dropout=0.0, **cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(outputs, labels):
        mlm_logits, nsp_logits = outputs
        mlm_labels, nsp_labels = labels
        return model.loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels)

    trainer = ParallelTrainer(model, opt, loss_fn)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg["vocab_size"], (batch, seq)).astype("int32")
    mlm = np.full((batch, seq), -100, dtype="int32")
    mlm[:, ::8] = rng.randint(0, cfg["vocab_size"], (batch, seq // 8))
    nsp = rng.randint(0, 2, (batch,)).astype("int32")
    dt, final_loss = _timed_steps(trainer, ids, (mlm, nsp), warmup, iters)
    return {"samples_per_sec": round(batch * iters / dt, 1),
            "final_loss": round(final_loss, 4)}


def bench_numerics(jax, on_tpu):
    """On-chip numerics smoke (r3 verdict item 10): flash-attention
    fwd/bwd, chunked CE, bf16 matmul vs dense fp32 references on the
    LIVE backend — the tolerances that CPU-interpret testing cannot
    validate. Reuses tools/numerics_smoke.py's checks in-process."""
    sys.path.insert(0, os.path.join(_HERE, "tools"))
    import numerics_smoke as ns

    checks = []
    interpret = not on_tpu
    for fn in (lambda: ns.check_flash_attention(interpret),
               ns.check_chunked_ce, ns.check_bf16_matmul):
        checks.extend(fn())
    return {"numerics_ok": all(c.get("ok") for c in checks),
            "checks": checks}


def bench_heter_ctr(jax, on_tpu):
    """Heter device-tier vs host-PS embedding A/B on the Wide&Deep CTR
    shape (r3 verdict item 2's 10x target), overlapped prepare mode."""
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed.engine import ParallelTrainer
    from tools._mesh_setup import data_mesh
    from paddle_tpu.rec import WideDeep

    if on_tpu:
        fields, batch, steps, warmup = [100_000] * 26, 4096, 12, 4
        hidden, cap = (400, 400, 400), 1_000_000
    else:
        fields, batch, steps, warmup = [1000] * 8, 256, 3, 1
        hidden, cap = (64, 32), 4096
    rng = np.random.RandomState(0)

    def draw_ids():
        u = rng.zipf(1.3, size=(batch, len(fields)))
        return (u % np.asarray(fields)[None, :]).astype("int64")

    batches = [(draw_ids(), rng.randn(batch, 13).astype("float32"),
                rng.randint(0, 2, batch).astype("float32"))
               for _ in range(steps + warmup)]

    def bce(logit, y):
        return jnp.mean(jnp.maximum(logit, 0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    out = {}
    for mode in ("heter", True):
        paddle.seed(0)
        data_mesh(1)
        model = WideDeep(fields, dense_dim=13, embedding_dim=16,
                         hidden_sizes=hidden, sparse=mode,
                         heter_capacity=cap)
        opt = paddle.optimizer.Adagrad(0.05, epsilon=1e-8,
                                       parameters=model.parameters())
        tr = ParallelTrainer(model, opt, bce)

        def run(bs):
            if mode != "heter":
                for ids, dense, y in bs:
                    loss = tr.train_step((ids, dense), y)
                return loss
            fut = model.prepare_batch_async(bs[0][0])
            for i, (ids, dense, y) in enumerate(bs):
                slots = fut.result()
                loss = tr.train_step((slots, dense), y)
                if i + 1 < len(bs):
                    fut = model.prepare_batch_async(bs[i + 1][0])
            return loss

        float(run(batches[:warmup]))
        t0 = time.perf_counter()
        float(run(batches[warmup:]))
        dt = time.perf_counter() - t0
        name = "heter_overlapped" if mode == "heter" else "host_ps"
        out[name + "_samples_per_sec"] = round(batch * steps / dt, 1)
        if mode == "heter":
            out["hot_hit_rate"] = round(model.ctr_table.hit_rate, 4)
    out["speedup_x"] = round(out["heter_overlapped_samples_per_sec"]
                             / out["host_ps_samples_per_sec"], 2)
    return out


def bench_op_pallas(jax, on_tpu):
    """Pallas kernel tier via tools/op_bench.py's pallas suite: tuned-vs-
    default block configs for flash attention + fused CE and the
    chunked-CE baseline. On TPU this is the autotuner's perf surface
    (run `python -m paddle_tpu.ops.pallas.tuner --suite bench` first to
    refresh the DB); on CPU the kernels run in interpret mode, so the
    value is plumbing + config-resolution coverage, not perf."""
    from paddle_tpu import telemetry
    from tools.op_bench import pallas_suite

    with telemetry.scope(profile=False) as tel:
        recs = pallas_suite(iters=20 if on_tpu else 2, smoke=not on_tpu)
    reg = tel.registry
    resolved = {}
    m = reg.get("pallas_config_resolved_total")
    if m is not None:
        for key, v in m.series().items():
            resolved[",".join(f"{k}={val}" for k, val in key)] = int(v)
    return {"ops": {r["op"]: {k: v for k, v in r.items() if k != "op"}
                    for r in recs},
            "config_resolutions": resolved}


CHILD_FNS = {"gpt_base": bench_gpt, "resnet50": bench_resnet50,
             "bert_base_amp": bench_bert_amp, "widedeep_ctr": bench_widedeep,
             "gpt_1p3b": bench_gpt_1p3b, "numerics": bench_numerics,
             "heter_ctr": bench_heter_ctr, "op_pallas": bench_op_pallas}


def child_main(name: str) -> int:
    try:
        jax = _child_setup()
        on_tpu = jax.default_backend() == "tpu"
        result = CHILD_FNS[name](jax, on_tpu)
        result["on_tpu"] = on_tpu
        result["backend"] = jax.default_backend()
        _emit(result)
        return 0
    except Exception as e:
        sys.stderr.write(traceback.format_exc())
        _emit({"error": f"{type(e).__name__}: {e}"})
        return 1


# --------------------------------------------------------------------------
# parent side: orchestration, no jax
# --------------------------------------------------------------------------

def _run_child(name: str, timeout: float):
    """One fresh subprocess; returns (payload|None, err|None)."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", name],
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, f"timed out after {timeout:.0f}s (killed)"
    except Exception as e:  # spawn failure
        return None, f"spawn failed: {e}"
    payload = None
    for line in (proc.stdout or "").splitlines():
        if line.startswith(_MARK):
            try:
                payload = json.loads(line[len(_MARK):])
            except ValueError:
                pass
    if payload is None:
        tail = (proc.stderr or "").strip().splitlines()[-6:]
        return None, (f"exit {proc.returncode}, no result; "
                      f"stderr tail: {' | '.join(tail)}")
    if proc.stderr:
        sys.stderr.write(proc.stderr)
    return payload, None


def main():
    t_start = time.monotonic()
    extra = {}
    for name in CONFIG_ORDER:
        elapsed = time.monotonic() - t_start
        if elapsed > GLOBAL_BUDGET_S:
            extra[name] = {"error": "skipped: global bench budget "
                                    f"exhausted ({elapsed:.0f}s)"}
            continue
        payload, err = _run_child(
            name, CHILD_TIMEOUT.get(name, CHILD_TIMEOUT_DEFAULT))
        extra[name] = payload if payload is not None else {"error": err}

    gpt = extra.get("gpt_base", {})
    ok = "tokens_per_sec" in gpt
    result = {
        "schema_version": 2,
        "metric": "gpt_base_train_tokens_per_sec_per_chip",
        "value": gpt.get("tokens_per_sec", 0.0),
        "unit": "tokens/sec",
        "vs_baseline": 1.0 if ok else 0.0,
        "backend": gpt.get("backend"),
        "flops_convention": "6N per token (no attention term)",
        # best-variant {predicted, measured, drift} step-time triple
        # (telemetry.calibration; schema_version 2)
        "calibration": gpt.get("calibration"),
        "extra": extra,
    }
    if "mfu_6N" in gpt:
        result["mfu"] = gpt["mfu_6N"]
        result["params"] = gpt["params"]
        result["final_loss"] = gpt["final_loss"]
    if "telemetry" in gpt:
        # best-variant registry harvest (mfu from the cost model,
        # recompiles, wire bytes) — see paddle_tpu/telemetry
        result["telemetry"] = gpt["telemetry"]
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", default=None)
    args = ap.parse_args()
    sys.exit(child_main(args.child) if args.child else main())
