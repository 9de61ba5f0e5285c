#!/usr/bin/env python
"""Bench the auto-parallel planner end to end: search, pick, run, drift.

Runs ``distributed/auto.plan_search`` over the bench-config GPT at the
host's device count, compares the pick's calibrated predicted step time
against the two baselines the planner must beat (the naive all-data-
parallel layout and ``auto.plan()``'s memory-ordered pick), then —
unless ``--plan-only`` — builds the chosen config for real via
``ParallelTrainer.from_plan`` and measures it, recording the
predicted/measured pair under the ``planner_step_time`` calibration key
so the drift between planned and actual step time lands in
``calibration_drift_ratio{key=planner_step_time}``.

The runnable search space here is the subspace the plain
``GPTForPretraining`` builder can realize (data x sharding
factorizations, grad_sync policy / dcn gating / buckets, remat; TP when
the hidden size supports it): pipe and sep need the model-side wrappers
(`PipelineParallel`, sep-aware attention) that this flat builder does
not construct, so ``--max-pipe/--max-sep`` default to 1. The FULL
five-axis space is exercised by ``plan_search``'s own tests.

Output: ONE JSON line on stdout (schema_version 2), like every bench
tool. ``--smoke`` shrinks shapes/steps for CI; ``--plan-only`` skips
building/measuring entirely (the two-process determinism test diffs the
ranked plan list of two such runs).
"""
import argparse
import json
import sys
import time


def _args():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 2 steps (CI)")
    ap.add_argument("--plan-only", action="store_true",
                    help="search + rank only; no staging, no measuring")
    ap.add_argument("--devices", type=int, default=8,
                    help="simulated host devices (XLA_FLAGS, default 8)")
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--stage-top-k", type=int, default=2,
                    help="analytic top-k re-scored from their staged "
                         "step (0 = analytic only)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--max-pipe", type=int, default=1)
    ap.add_argument("--max-sep", type=int, default=1)
    ap.add_argument("--zero-stage", type=int, default=1)
    return ap.parse_args()


def _gpt_spec(smoke: bool):
    if smoke:
        return dict(vocab=256, h=64, layers=1, heads=2, seq=32,
                    batch_per_device=4)
    # the toy GPT shape bench_collectives and lint_program share
    return dict(vocab=1024, h=128, layers=2, heads=4, seq=128,
                batch_per_device=4)


def make_gpt_builder(spec: dict, global_batch: int):
    """``builder(plan) -> (trainer, inputs, labels)`` over the plain
    bench GPT — used for plan_search's staged tier AND to build the
    winning config for measurement (same construction path both ways,
    so the staged score prices exactly what gets run)."""
    def build(plan):
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.engine import ParallelTrainer
        from paddle_tpu.text.models import GPTForPretraining

        paddle.seed(0)
        mesh = plan.build_mesh()
        model = GPTForPretraining(
            tensor_parallel=plan.degrees.get("model", 1) > 1,
            vocab_size=spec["vocab"], hidden_size=spec["h"],
            num_layers=spec["layers"], num_heads=spec["heads"],
            max_position_embeddings=spec["seq"], attn_dropout=0.0,
            hidden_dropout=0.0)
        model.bfloat16()
        opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters())
        trainer = ParallelTrainer.from_plan(
            plan, model, opt,
            lambda logits, lbl: nn.functional.cross_entropy(logits, lbl),
            mesh=mesh)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, spec["vocab"],
                          (global_batch, spec["seq"])).astype("int32")
        labels = rng.randint(0, spec["vocab"],
                             (global_batch, spec["seq"])).astype("int32")
        return trainer, ids, labels
    return build


def count_gpt_params(spec: dict) -> int:
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTForPretraining

    paddle.seed(0)
    model = GPTForPretraining(
        tensor_parallel=False, vocab_size=spec["vocab"],
        hidden_size=spec["h"], num_layers=spec["layers"],
        num_heads=spec["heads"], max_position_embeddings=spec["seq"],
        attn_dropout=0.0, hidden_dropout=0.0)
    return sum(int(np.prod(p.shape)) for p in model.parameters())


def search(spec: dict, n_devices: int, *, top_k=8, stage_top_k=0,
           builder=None, max_pipe=1, max_sep=1, zero_stage=1,
           hbm_bytes=16e9):
    """plan_search over the bench GPT spec; returns (ranked, baselines).

    ``baselines`` prices the naive all-DP layout and ``auto.plan()``'s
    memory-ordered pick with the SAME analytic calibrated model, plus
    the strict-beat verdicts the acceptance criterion asks for —
    compared on the analytic tier so all three share one scale."""
    from paddle_tpu.distributed import auto

    n_params = count_gpt_params(spec)
    global_batch = spec["batch_per_device"] * n_devices
    kw = dict(layers=spec["layers"], hidden=spec["h"],
              seq_len=spec["seq"], global_batch=global_batch,
              batch_per_device=spec["batch_per_device"],
              hbm_bytes=hbm_bytes, param_bytes=2, zero_stage=zero_stage,
              max_pipe=max_pipe, max_sep=max_sep,
              micro_choices=(1,), top_k=top_k)
    ranked = auto.plan_search(n_params, n_devices, **kw)
    score_kw = dict(layers=spec["layers"], hidden=spec["h"],
                    seq_len=spec["seq"], global_batch=global_batch,
                    param_bytes=2)

    all_dp = auto.Plan(
        degrees={"data": n_devices, "sharding": 1, "model": 1,
                 "pipe": 1, "sep": 1},
        per_device=auto._estimate(
            n_params, {"data": n_devices, "sharding": 1, "model": 1,
                       "pipe": 1, "sep": 1},
            layers=spec["layers"], hidden=spec["h"], seq_len=spec["seq"],
            batch_per_device=spec["batch_per_device"], param_bytes=2,
            zero_stage=zero_stage, remat=False),
        hbm_bytes=hbm_bytes, zero_stage=zero_stage)
    auto.score_plan(all_dp, n_params, **score_kw)
    mem_pick = auto.plan(
        n_params, n_devices, layers=spec["layers"], hidden=spec["h"],
        seq_len=spec["seq"], batch_per_device=spec["batch_per_device"],
        hbm_bytes=hbm_bytes, param_bytes=2, zero_stage=zero_stage,
        max_model=max(1, spec["h"] // 128))
    auto.score_plan(mem_pick, n_params, **score_kw)

    pick_t = ranked[0].predicted.total
    baselines = {
        "pick_predicted_s": pick_t,
        "all_dp_predicted_s": all_dp.predicted.total,
        "memory_pick_predicted_s": mem_pick.predicted.total,
        "memory_pick_degrees": {k: mem_pick.degrees[k]
                                for k in sorted(mem_pick.degrees)},
        "pick_beats_all_dp": pick_t < all_dp.predicted.total,
        "pick_beats_memory_pick": pick_t < mem_pick.predicted.total,
    }
    if stage_top_k > 0 and builder is not None:
        ranked = auto.plan_search(n_params, n_devices, builder=builder,
                                  stage_top_k=stage_top_k, **kw)
    return ranked, baselines, n_params


def main():
    args = _args()
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    from _mesh_setup import ensure_repo_on_path, force_host_devices
    force_host_devices(args.devices)
    ensure_repo_on_path()
    import jax

    from paddle_tpu import telemetry

    t0 = time.perf_counter()
    n_devices = len(jax.devices())
    spec = _gpt_spec(args.smoke)
    global_batch = spec["batch_per_device"] * n_devices
    builder = make_gpt_builder(spec, global_batch)
    stage_k = 0 if args.plan_only else args.stage_top_k
    ranked, baselines, n_params = search(
        spec, n_devices, top_k=args.top_k, stage_top_k=stage_k,
        builder=builder, max_pipe=args.max_pipe, max_sep=args.max_sep,
        zero_stage=args.zero_stage)
    pick = ranked[0]
    predicted_s = pick.predicted.total

    out = {
        "schema_version": 2,
        "bench": "plan",
        "metric": "planner_step_time_ms",
        "unit": "ms",
        "value": round(predicted_s * 1e3, 6),
        "devices": n_devices,
        "params": n_params,
        "smoke": bool(args.smoke),
        "plan_only": bool(args.plan_only),
        "pick": pick.to_dict(),
        "plans": [p.to_dict() for p in ranked],
        "baselines": baselines,
        "calibration": None,
        "search_ms": round((time.perf_counter() - t0) * 1e3, 3),
    }
    if not args.plan_only:
        trainer, ids, labels = builder(pick)
        steps = max(1, 1 if args.smoke else args.steps)
        warmup = max(1, 1 if args.smoke else args.warmup)
        for _ in range(warmup):
            loss = trainer.train_step(ids, labels)
        float(loss)
        t1 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.train_step(ids, labels)
        final_loss = float(loss)
        measured_s = (time.perf_counter() - t1) / steps
        telemetry.calibration.record("planner_step_time", predicted_s,
                                     measured_s)
        out["value"] = round(measured_s * 1e3, 6)
        out["predicted_ms"] = round(predicted_s * 1e3, 6)
        out["measured_ms"] = round(measured_s * 1e3, 6)
        out["final_loss"] = round(final_loss, 4)
        # predicted/measured/drift triple from the calibration registry
        out["calibration"] = telemetry.calibration.pair(
            "planner_step_time")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
