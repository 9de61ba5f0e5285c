"""Collective-exchange micro-benchmark: fp32 / bf16 / int8 / int4 grad sync.

Measures the bucketed compressed exchange (distributed/compressed.py) over
a forced-host-device mesh (or real TPU devices when present) and prints ONE
JSON line:

    {"metric": "int8_vs_fp32_bytes_x", "value": ..., "unit": "x",
     "extra": {per-policy: {wire_bytes_per_rank, ms_per_exchange,
                            buckets, rel_err},
               "int4_vs_fp32_bytes_x": ...,          # expect >= 7
               "per_axis_int4_dcn": {...}}}          # DCN-gated case

Bytes-on-wire come from the analytic ring model in
``compressed.wire_bytes_per_rank`` (what each rank moves for one mean:
all-reduce counts 2(n-1)/n payloads, the quantized figures count both
phases plus every scale exchange — int4 moves nibbles plus bf16 scales).
The per-axis case splits the devices into a 2-axis mesh, marks the outer
axis "dcn" and runs int4 there with an exact fp32 pre-reduction on the
inner ("ici") axis — the DCN-gating deployment shape. Latency is
wall-clock on whatever backend runs — on forced host devices it measures
the code path, not ICI; on TPUs it is the real exchange time.

The ``overlap`` suite instead stages the bench-GPT train step through
``ParallelTrainer`` at bucket counts K=1 and K=``--buckets`` on the data
mesh and reports the analysis overlap model's ``overlap_efficiency`` —
the fraction of collective wire time hidden under backward/optimizer
compute. Bucketed (K>=2) must strictly beat monolithic.

The ``calibrate`` suite is the fitting sweep behind
``telemetry.calibration``: it times a psum size ladder and real
train-step walltimes, runs ``calibration.fit()`` to regress corrected
per-link bandwidth/latency constants and an effective
``peak_flops_per_sec``, persists them to the calibration-DB overlay
(``--calibration-db`` / ``PADDLE_TPU_CALIBRATION_DB``), and reports the
predicted-vs-measured step-time drift before and after the fit (after
must shrink; ``--smoke`` asserts it).

Every suite prints one JSON line at ``schema_version`` 2: the
``calibration`` block carries the run's ``{predicted, measured, drift}``
triples so BENCH_*.json files double as model-accuracy evidence.

Usage:
    python tools/bench_collectives.py                     # defaults
    python tools/bench_collectives.py --numel 4194304 --devices 4 \
        --block 256 --int4-block 64 --bucket-mb 4 --iters 20
    python tools/bench_collectives.py --smoke   # tiny shapes + telemetry
                                                # self-check (CI)
    python tools/bench_collectives.py --suite overlap --json
    python tools/bench_collectives.py --suite calibrate --smoke
"""
from __future__ import annotations

import argparse
import json
import time


def _overlap_trainer(buckets: int, smoke: bool, devices: int, policy: str):
    """The lint_program/bench GPT configuration on a data mesh with the
    given grad-sync bucket count."""
    import numpy as np

    import paddle_tpu as paddle
    from _mesh_setup import data_mesh
    from paddle_tpu import nn
    from paddle_tpu.distributed.engine import ParallelTrainer
    from paddle_tpu.text.models import GPTForPretraining

    if smoke:
        vocab, h, layers, heads, seq, batch = 256, 64, 1, 2, 32, 4
    else:  # the toy GPT shape bench_plan and lint_program share
        vocab, h, layers, heads, seq, batch = 1024, 128, 2, 4, 128, 4
    paddle.seed(0)
    model = GPTForPretraining(
        tensor_parallel=False, vocab_size=vocab, hidden_size=h,
        num_layers=layers, num_heads=heads, max_position_embeddings=seq,
        attn_dropout=0.0, hidden_dropout=0.0)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters())
    trainer = ParallelTrainer(
        model, opt,
        lambda logits, lbl: nn.functional.cross_entropy(logits, lbl),
        mesh=data_mesh(devices), grad_sync=policy,
        grad_sync_buckets=buckets)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq)).astype("int32")
    labels = rng.randint(0, vocab, (batch, seq)).astype("int32")
    return trainer, ids, labels


def overlap_case(buckets: int, smoke: bool, devices: int,
                 policy: str) -> dict:
    """Stage one train step and run the overlap model over its jaxpr."""
    from paddle_tpu.analysis import cost

    trainer, ids, labels = _overlap_trainer(buckets, smoke, devices, policy)
    closed = trainer.staged_jaxpr(ids, labels)
    out = cost.overlap_summary(closed, trainer.mesh)
    out["buckets"] = [len(b) for b in trainer.grad_sync_bucket_keys]
    return out


def _timed_trainer_steps(trainer, ids, labels, warmup: int,
                         iters: int) -> float:
    """Median per-step wall seconds of real train steps (loss fetch is
    the sync point)."""
    for _ in range(max(1, warmup)):
        loss = trainer.train_step(ids, labels)
    float(loss)
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        loss = trainer.train_step(ids, labels)
        float(loss)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def run_overlap(args) -> None:
    from paddle_tpu.telemetry import calibration

    k = max(2, args.buckets)
    base = overlap_case(1, args.smoke, args.devices, args.policy)
    bucketed = overlap_case(k, args.smoke, args.devices, args.policy)
    eff1 = base["overlap_efficiency"]
    effk = bucketed["overlap_efficiency"]
    if args.smoke:
        assert effk is not None and effk > 0, bucketed
        assert eff1 is None or effk > eff1, (base, bucketed)
    # predicted-vs-measured: the bucketed schedule's modeled makespan vs
    # a couple of real steps of the same trainer configuration
    trainer, ids, labels = _overlap_trainer(k, args.smoke, args.devices,
                                            args.policy)
    dt = _timed_trainer_steps(trainer, ids, labels, warmup=2, iters=3)
    calibration.record("step_time", bucketed["makespan"], dt)
    extra = {"k": k, "devices": args.devices, "policy": args.policy,
             "smoke": bool(args.smoke),
             "overlap_efficiency_k1": eff1,
             "hidden_wire_seconds": (
                 None if effk is None
                 else effk * bucketed["collective_time"])}
    if args.json:
        extra["k1"] = base
        extra[f"k{k}"] = bucketed
    print(json.dumps({
        "schema_version": 2,
        "metric": "grad_sync_overlap_efficiency",
        "value": effk,
        "unit": "frac",
        "vs_baseline": eff1,
        "calibration": calibration.pair("step_time"),
        "extra": extra,
    }))


def run_calibrate(args) -> None:
    """The fitting sweep: measured collectives + measured train steps ->
    calibration.fit() -> overlay DB -> drift before/after."""
    import math
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from _mesh_setup import data_mesh
    from paddle_tpu.analysis import cost
    from paddle_tpu.telemetry import calibration

    db_path = args.calibration_db
    if not db_path and args.smoke:
        # keep the smoke self-test hermetic: never write ~/.cache
        db_path = os.path.join(tempfile.mkdtemp(prefix="paddle_calib_"),
                               "calibration_db.json")
    if db_path:
        os.environ["PADDLE_TPU_CALIBRATION_DB"] = db_path
    calibration.clear_cache()

    mesh = data_mesh(args.devices)
    n = mesh.devices.size

    # -- collective ladder: psum wall time across payload sizes ---------
    sizes = ([1 << 12, 1 << 14, 1 << 16] if args.smoke
             else [1 << 14, 1 << 16, 1 << 18, 1 << 20, 1 << 22])
    coll_samples = []
    for numel in sizes:
        xd = jax.device_put(
            jnp.ones((n, numel), jnp.float32),
            NamedSharding(mesh, P("data", None)))
        jfn = jax.jit(jax.shard_map(
            lambda xs: jax.lax.psum(xs, "data"), mesh=mesh,
            in_specs=P("data", None), out_specs=P(None, None),
            check_vma=False))
        jfn(xd).block_until_ready()
        times = []
        for _ in range(max(2, args.iters)):
            t0 = time.perf_counter()
            jfn(xd).block_until_ready()
            times.append(time.perf_counter() - t0)
        times.sort()
        dt = times[len(times) // 2]
        # the cost model's ring figure for psum: 2(n-1)/n payload bytes
        wire = 2.0 * (n - 1) / n * numel * 4
        coll_samples.append({"link": "ici", "wire_bytes": wire,
                             "seconds": dt})

    # -- compute samples: real steps of the bench-GPT trainer -----------
    trainer, ids, labels = _overlap_trainer(
        max(2, args.buckets), args.smoke, args.devices, args.policy)
    closed = trainer.staged_jaxpr(ids, labels)
    ov_before = cost.overlap_summary(closed, trainer.mesh)
    flops = ov_before["compute_time"] * ov_before["peak_flops"]
    steps = 3 if args.smoke else max(5, args.iters)
    dts = []
    _timed_trainer_steps(trainer, ids, labels, warmup=1, iters=1)
    for _ in range(steps):
        dts.append(_timed_trainer_steps(trainer, ids, labels,
                                        warmup=0, iters=1))
    dts.sort()
    measured = dts[len(dts) // 2]
    compute_samples = [{"flops": flops, "seconds": d} for d in dts]

    drift_before = measured / ov_before["makespan"]
    fitted = calibration.fit(collective_samples=coll_samples,
                             compute_samples=compute_samples,
                             save=True, db_path=db_path)
    # every consumer reads the fitted constants through the same choke
    # points, so re-pricing the identical jaxpr shows the correction
    ov_after = cost.overlap_summary(closed, trainer.mesh)
    drift_after = measured / ov_after["makespan"]
    calibration.record("step_time", ov_after["makespan"], measured)
    links = fitted["entry"].get("links", {}).get("ici", {})
    if links.get("bandwidth_bps"):
        for s in coll_samples:
            calibration.record(
                "collective_ici",
                s["wire_bytes"] / links["bandwidth_bps"]
                + links.get("latency_s", 0.0),
                s["seconds"])
    if args.smoke:
        assert abs(math.log(drift_after)) < abs(math.log(drift_before)), (
            drift_before, drift_after, fitted)
    print(json.dumps({
        "schema_version": 2,
        "metric": "calibration_step_time_drift",
        "value": drift_after,
        "unit": "x",
        "vs_baseline": drift_before,
        "calibration": {
            "step_time": calibration.pair("step_time"),
            "collective_ici": calibration.pair("collective_ici"),
        },
        "extra": {
            "devices": n, "smoke": bool(args.smoke),
            "db_path": fitted["path"],
            "predicted_before_s": ov_before["makespan"],
            "predicted_after_s": ov_after["makespan"],
            "measured_s": measured,
            "n_collective_samples": len(coll_samples),
            "n_compute_samples": len(compute_samples),
            "fitted": fitted["entry"],
        },
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", choices=("exchange", "overlap", "calibrate"),
                    default="exchange",
                    help="exchange: wire bytes/latency per policy; "
                         "overlap: staged-step overlap_efficiency at "
                         "K=1 vs K=--buckets; calibrate: fit corrected "
                         "wire/peak constants into the calibration DB "
                         "from measured collectives + train steps")
    ap.add_argument("--calibration-db", default=None,
                    help="calibrate suite: overlay DB path to write "
                         "(default: PADDLE_TPU_CALIBRATION_DB or the "
                         "user cache overlay; --smoke uses a tempdir)")
    ap.add_argument("--numel", type=int, default=1 << 22,
                    help="total gradient elements (fp32)")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced host device count when no accelerator")
    ap.add_argument("--block", type=int, default=256,
                    help="int8 quantization block")
    ap.add_argument("--int4-block", type=int, default=64,
                    help="int4 quantization block (smaller: 4-bit steps "
                         "are coarse)")
    ap.add_argument("--bucket-mb", type=int, default=4,
                    help="flat bucket size in MiB")
    ap.add_argument("--buckets", type=int, default=4,
                    help="overlap suite: grad-sync bucket count K")
    ap.add_argument("--policy", default="fp32",
                    help="overlap suite: grad_sync policy")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--json", action="store_true",
                    help="overlap suite: include the full per-K overlap "
                         "summaries in the JSON line")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + telemetry self-check; asserts the "
                         "registry saw the per-policy wire-byte counters "
                         "and (overlap) that K>=2 hides wire time")
    args = ap.parse_args()
    if args.smoke:
        args.numel, args.devices, args.block = 4096, 2, 64
        args.int4_block = 64
        args.iters, args.warmup = 2, 1

    from _mesh_setup import (data_mesh, ensure_repo_on_path,
                             force_host_devices)
    force_host_devices(args.devices)
    ensure_repo_on_path()
    if args.suite == "overlap":
        return run_overlap(args)
    if args.suite == "calibrate":
        return run_calibrate(args)

    import math

    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import telemetry
    from paddle_tpu.distributed.compressed import (
        QUANTIZED_POLICIES, bucket_sizes, compressed_tree_mean,
        init_residuals, wire_bytes_per_rank)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = data_mesh(args.devices)
    n = mesh.devices.size
    bucket_bytes = args.bucket_mb << 20
    blocks = {"fp32": args.block, "bf16": args.block, "int8": args.block,
              "int4": args.int4_block}
    # one numel for every policy: align to the lcm of the block sizes
    align = n * math.lcm(args.block, args.int4_block)
    numel = ((args.numel + align - 1) // align) * align
    nbuckets = len(bucket_sizes(numel, max(bucket_bytes // 4, align), align))

    rng = np.random.RandomState(0)
    # per-rank distinct gradients, replica-major then sharded over "data"
    g = rng.randn(n, numel).astype(np.float32)
    g_dev = jax.device_put(jnp.asarray(g),
                           NamedSharding(mesh, P("data", None)))
    exact = g.mean(axis=0)

    tel_cm = telemetry.scope(profile=False)
    tel = tel_cm.__enter__()
    reg = tel.registry
    extra = {}

    def run_case(run_mesh, axis, policy, block):
        residuals = ({"g": jnp.zeros((n, numel), jnp.float32)}
                     if (policy in QUANTIZED_POLICIES
                         or (isinstance(policy, dict)
                             and any(p in QUANTIZED_POLICIES
                                     for p in policy.values())))
                     else None)
        dspec = P(tuple(a for a in run_mesh.axis_names), None) \
            if len(run_mesh.axis_names) > 1 else P("data", None)

        def exchange(x, res):
            def f(xs, rs):
                tree = {"g": xs[0]}
                r = {"g": rs["g"][0]} if rs else None
                mean, r = compressed_tree_mean(
                    tree, axis, policy=policy, block=block,
                    bucket_bytes=bucket_bytes, residuals=r)
                out_r = {"g": r["g"][None]} if rs else {}
                return mean["g"][None], out_r

            return jax.shard_map(
                f, mesh=run_mesh,
                in_specs=(dspec, {"g": dspec} if res else {}),
                out_specs=(dspec, {"g": dspec} if res else {}),
                check_vma=False)(x, res if res else {})

        jfn = jax.jit(exchange)
        res_in = residuals if residuals is not None else {}
        gd = jax.device_put(jnp.asarray(g), NamedSharding(run_mesh, dspec))
        out, _ = jfn(gd, res_in)
        for _ in range(args.warmup):
            out, _ = jfn(gd, res_in)
        np.asarray(out)  # sync
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out, _ = jfn(gd, res_in)
        np.asarray(out)
        dt = (time.perf_counter() - t0) / args.iters
        got = np.asarray(out)[0]
        rel = float(np.abs(got - exact).max() /
                    (np.abs(exact).max() + 1e-12))
        return dt, rel

    for policy in ("fp32", "bf16", "int8", "int4"):
        dt, rel = run_case(mesh, "data", policy, blocks[policy])
        wire = wire_bytes_per_rank(numel, n, policy, block=blocks[policy])
        if policy == "fp32":
            # predicted-vs-measured wire time of the plain exchange: the
            # ring model over link_bandwidth/link_latency vs wall clock
            # (on forced host devices this measures the code path, not
            # ICI — still the honest drift of the model on this backend)
            from paddle_tpu.distributed.mesh import (link_bandwidth,
                                                     link_latency)
            from paddle_tpu.telemetry import calibration
            calibration.record(
                "collective_ici",
                wire / link_bandwidth("ici") + link_latency("ici"), dt)
        telemetry.counter(
            "grad_sync_bytes_total",
            "logical wire bytes per rank of the bucketed grad "
            "exchange").inc(wire * args.iters, policy=policy)
        telemetry.histogram(
            "grad_sync_exchange_seconds",
            "one compressed_tree_mean wall time").observe(dt, policy=policy)
        extra[policy] = {
            "wire_bytes_per_rank": wire,
            "ms_per_exchange": round(dt * 1e3, 3),
            "ms_per_bucket": round(dt * 1e3 / nbuckets, 3),
            "buckets": nbuckets,
            "rel_err": rel,
        }

    # per-axis policy (DCN gating): outer "data" axis quantizes int4 (the
    # slow cross-slice hop), inner "model" axis pre-reduces exact fp32
    # (the fast ICI hop). Wire model = the two sequential group exchanges.
    if n >= 4 and n % 2 == 0:
        mesh2 = Mesh(np.asarray(jax.devices()[:n]).reshape(n // 2, 2),
                     ("data", "model"))
        per_axis = {"data": "int4", "model": "fp32"}
        dt, rel = run_case(mesh2, ("data", "model"), per_axis, None)
        wire = (wire_bytes_per_rank(numel, n // 2, "int4",
                                    block=args.int4_block)
                + wire_bytes_per_rank(numel, 2, "fp32"))
        extra["per_axis_int4_dcn"] = {
            "policy": per_axis,
            "wire_bytes_per_rank": wire,
            "ms_per_exchange": round(dt * 1e3, 3),
            "rel_err": rel,
        }

    ratio = (extra["fp32"]["wire_bytes_per_rank"] /
             max(extra["int8"]["wire_bytes_per_rank"], 1e-9))
    ratio4 = (extra["fp32"]["wire_bytes_per_rank"] /
              max(extra["int4"]["wire_bytes_per_rank"], 1e-9))
    extra["int4_vs_fp32_bytes_x"] = round(ratio4, 3)
    extra["telemetry"] = {
        "wire_bytes": {p: reg.get("grad_sync_bytes_total").value(policy=p)
                       for p in ("fp32", "bf16", "int8", "int4")},
        "prometheus_bytes": len(telemetry.prometheus_text(reg)),
    }
    tel_cm.__exit__(None, None, None)
    if args.smoke:
        prom = telemetry.prometheus_text(reg)
        wb = extra["telemetry"]["wire_bytes"]
        assert "grad_sync_bytes_total" in prom, "telemetry missing metric"
        assert wb["int8"] > 0 and wb["fp32"] > wb["int8"], wb
        assert wb["int4"] > 0 and wb["int8"] > wb["int4"], wb
        assert ratio4 >= 7.0, f"int4 must beat fp32 by >=7x, got {ratio4}"
        # the bucketed exchange must hide wire time: K=2 on the CPU mesh
        # shows a strictly positive overlap_efficiency in the schedule
        # model of the staged train step
        ov = overlap_case(2, smoke=True, devices=args.devices,
                          policy="fp32")
        assert ov["overlap_efficiency"] is not None \
            and ov["overlap_efficiency"] > 0, ov
        extra["overlap_smoke"] = {
            "overlap_efficiency": ov["overlap_efficiency"],
            "n_collectives": ov["n_collectives"],
            "buckets": ov["buckets"]}
    from paddle_tpu.telemetry import calibration as _calibration
    print(json.dumps({
        "schema_version": 2,
        "metric": "int8_vs_fp32_bytes_x",
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": 1.0,
        "calibration": _calibration.pair("collective_ici"),
        "extra": {"numel": numel, "devices": n, "block": args.block,
                  "int4_block": args.int4_block,
                  "bucket_mb": args.bucket_mb, "smoke": bool(args.smoke),
                  **extra},
    }))


if __name__ == "__main__":
    main()
