"""Standalone op micro-benchmark harness.

Reference: paddle/fluid/operators/benchmark/op_tester.cc (C64 in SURVEY.md §2)
— runs a single op from a config N times and reports latency. TPU
translation: jit-compile the op once, time steady-state iterations with a
device sync per batch, report op name / shapes / mean latency / achieved
GB/s + GFLOP/s where derivable.

``--json`` switches each line to the bench_collectives.py convention
(``{"metric": "<op>_mean_us", "value": ..., "unit": "us", "extra": {...}}``)
so the driver's bench orchestration can diff runs.  ``--suite pallas``
times the Pallas kernel tier (flash attention + fused CE, fwd+bwd) at
both tuning-DB-resolved and compiled-in-default block configs, plus the
chunked-CE baseline — the tuned-vs-default surface the autotuner
(``paddle_tpu/ops/pallas/tuner.py``) optimizes; the tuner reuses this
module's ``time_op`` loop so its timings are the same measurement.

Usage:
    python tools/op_bench.py                      # built-in suite
    python tools/op_bench.py matmul --m 1024 --n 1024 --k 1024 --dtype bf16
    python tools/op_bench.py --suite pallas --json --smoke   # CPU-safe CI
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable from anywhere: the repo root (paddle_tpu's parent) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(x):
    import jax
    leaves = jax.tree_util.tree_leaves(x)
    if leaves:
        np.asarray(leaves[0])  # host fetch = reliable sync (see bench.py)


def time_op(fn, args, iters=50, warmup=5):
    import jax
    jfn = jax.jit(fn)
    for _ in range(warmup):
        out = jfn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def bench_case(name, fn, args, flops=None, bytes_moved=None, iters=50,
               json_mode=False, extra=None):
    dt = time_op(fn, args, iters=iters)
    rec = {"op": name, "mean_us": round(dt * 1e6, 2)}
    if flops:
        rec["gflops"] = round(flops / dt / 1e9, 1)
    if bytes_moved:
        rec["gbps"] = round(bytes_moved / dt / 1e9, 1)
    if extra:
        rec.update(extra)
    if json_mode:
        line = {"metric": f"{name}_mean_us", "value": rec["mean_us"],
                "unit": "us",
                "extra": {k: v for k, v in rec.items()
                          if k not in ("op", "mean_us")}}
    else:
        line = rec
    print(json.dumps(line), flush=True)
    return rec


def default_suite(dtype="bfloat16", iters=50, json_mode=False):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn

    rng = np.random.RandomState(0)
    dt = jnp.dtype(dtype)
    results = []

    m = k = n = 2048
    a = jnp.asarray(rng.randn(m, k), dt)
    b = jnp.asarray(rng.randn(k, n), dt)
    results.append(bench_case(
        f"matmul_{m}x{k}x{n}_{dtype}", jnp.matmul, (a, b),
        flops=2 * m * k * n, bytes_moved=(m * k + k * n + m * n) * dt.itemsize,
        iters=iters, json_mode=json_mode))

    x = jnp.asarray(rng.randn(8, 3, 224, 224), dt)
    w = jnp.asarray(rng.randn(64, 3, 7, 7), dt)
    results.append(bench_case(
        "conv2d_resnet_stem", lambda x, w: nn.functional.conv2d(
            x, w, stride=2, padding=3), (x, w), iters=iters,
        json_mode=json_mode))

    h = jnp.asarray(rng.randn(8, 1024, 1024), dt)
    wln = jnp.ones((1024,), dt)
    bln = jnp.zeros((1024,), dt)
    results.append(bench_case(
        "layer_norm_8x1024x1024",
        lambda h, w, b: nn.functional.layer_norm(h, (1024,), w, b),
        (h, wln, bln), bytes_moved=2 * h.size * dt.itemsize, iters=iters,
        json_mode=json_mode))

    q = jnp.asarray(rng.randn(4, 1024, 8, 64), dt)
    results.append(bench_case(
        "flash_attention_s1024",
        lambda q: nn.functional.scaled_dot_product_attention(
            q, q, q, is_causal=True, training=False), (q,),
        # causal: only the lower triangle is computed -> half the dense count
        flops=4 * 4 * 8 * 1024 * 1024 * 64 // 2, iters=iters,
        json_mode=json_mode))

    e = jnp.asarray(rng.randn(50304, 768), dt)
    ids = jnp.asarray(rng.randint(0, 50304, (8, 1024)), jnp.int32)
    results.append(bench_case(
        "embedding_50k", lambda e, i: jnp.take(e, i, axis=0), (e, ids),
        bytes_moved=8 * 1024 * 768 * dt.itemsize, iters=iters,
        json_mode=json_mode))

    sm_x = jnp.asarray(rng.randn(8192, 50304), dt)
    results.append(bench_case(
        "softmax_8192x50304", lambda x: paddle.nn.functional.softmax(x, -1),
        (sm_x,), bytes_moved=2 * sm_x.size * dt.itemsize, iters=iters,
        json_mode=json_mode))
    return results


def pallas_suite(dtype=None, iters=50, smoke=False, json_mode=False):
    """The Pallas kernel tier as a tracked perf surface: flash attention
    and fused CE, each measured fwd+bwd at (a) tuning-DB-resolved blocks
    and (b) the compiled-in defaults, plus the chunked-CE jnp baseline
    the fused kernel replaces.  Off-TPU the kernels run in interpret
    mode — the numbers are then plumbing/correctness signals, not perf
    (the record says ``interpret: true``)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.chunked_ce import chunked_lm_ce
    from paddle_tpu.ops.pallas import flash_attention, fused_lm_ce
    from paddle_tpu.ops.pallas import tuner
    from paddle_tpu.ops.pallas.flash_attention import (DEFAULT_BLOCK_K,
                                                       DEFAULT_BLOCK_Q)
    from paddle_tpu.ops.pallas.fused_ce import (DEFAULT_BLOCK_TOKENS,
                                                DEFAULT_BLOCK_VOCAB)

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    if dtype is None:
        dtype = "bfloat16" if on_tpu else "float32"
    dt = jnp.dtype(dtype)
    if smoke or not on_tpu:
        iters = min(iters, 3)
    rng = np.random.RandomState(0)
    results = []

    # -- flash attention (fwd+bwd) ------------------------------------------
    b, h, s, d = (1, 2, 128, 64) if (smoke or not on_tpu) else \
        (4, 8, 1024, 64)
    q = jnp.asarray(rng.randn(b, s, h, d), dt)
    fl_dims = tuner.flash_dims(d, s, s)
    fl_cfg, fl_src = tuner.resolve(
        "flash_attention", dt, fl_dims,
        {"block_q": DEFAULT_BLOCK_Q, "block_k": DEFAULT_BLOCK_K})

    def flash_step(bq, bk):
        def f(q):
            return jnp.sum(flash_attention(
                q, q, q, causal=True, block_q=bq, block_k=bk,
                interpret=interpret) ** 2)
        return lambda q: jax.grad(f)(q)

    fl_flops = 3 * 4 * b * h * s * s * d // 2  # fwd+bwd causal, ~3x fwd
    results.append(bench_case(
        f"pallas_flash_attn_s{s}_{dtype}_tuned",
        flash_step(fl_cfg["block_q"], fl_cfg["block_k"]), (q,),
        flops=fl_flops, iters=iters, json_mode=json_mode,
        extra={"config": fl_cfg, "source": fl_src, "interpret": interpret}))
    results.append(bench_case(
        f"pallas_flash_attn_s{s}_{dtype}_default",
        flash_step(DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K), (q,),
        flops=fl_flops, iters=iters, json_mode=json_mode,
        extra={"config": {"block_q": DEFAULT_BLOCK_Q,
                          "block_k": DEFAULT_BLOCK_K},
               "interpret": interpret}))

    # -- fused CE (fwd+bwd) vs the chunked-scan baseline --------------------
    tok, hd, v = (128, 64, 512) if (smoke or not on_tpu) else \
        (8192, 768, 50304)
    hid = jnp.asarray(rng.randn(tok, hd) * 0.1, dt)
    w = jnp.asarray(rng.randn(hd, v) * 0.1, dt)
    lbl = jnp.asarray(rng.randint(0, v, (tok,)), jnp.int32)
    ce_dims = tuner.ce_dims(hd, v, tok)
    ce_cfg, ce_src = tuner.resolve(
        "fused_ce", dt, ce_dims,
        {"block_tokens": DEFAULT_BLOCK_TOKENS,
         "block_vocab": DEFAULT_BLOCK_VOCAB})

    def ce_step(bt, bv):
        def f(hid, w):
            return fused_lm_ce(hid, w, lbl, block_tokens=bt, block_vocab=bv,
                               interpret=interpret)
        return lambda hid, w: jax.grad(f, argnums=(0, 1))(hid, w)

    ce_flops = 3 * 2 * tok * hd * v  # fwd+bwd ~3x the head matmul
    results.append(bench_case(
        f"pallas_fused_ce_t{tok}_v{v}_{dtype}_tuned",
        ce_step(ce_cfg["block_tokens"], ce_cfg["block_vocab"]), (hid, w),
        flops=ce_flops, iters=iters, json_mode=json_mode,
        extra={"config": ce_cfg, "source": ce_src, "interpret": interpret}))
    results.append(bench_case(
        f"pallas_fused_ce_t{tok}_v{v}_{dtype}_default",
        ce_step(DEFAULT_BLOCK_TOKENS, DEFAULT_BLOCK_VOCAB), (hid, w),
        flops=ce_flops, iters=iters, json_mode=json_mode,
        extra={"config": {"block_tokens": DEFAULT_BLOCK_TOKENS,
                          "block_vocab": DEFAULT_BLOCK_VOCAB},
               "interpret": interpret}))
    results.append(bench_case(
        f"chunked_ce_t{tok}_v{v}_{dtype}_baseline",
        lambda hid, w: jax.grad(
            lambda hid, w: chunked_lm_ce(hid, w, lbl, min(8192, v)),
            argnums=(0, 1))(hid, w),
        (hid, w), flops=ce_flops, iters=iters, json_mode=json_mode,
        extra={"interpret": False}))
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("op", nargs="?", help="matmul | suite (default)")
    ap.add_argument("--m", type=int, default=1024)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--k", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--dtype", default=None,
                    choices=["float32", "bfloat16", "float16"])
    ap.add_argument("--suite", default="default",
                    choices=["default", "pallas"],
                    help="which suite to run when no single op is named")
    ap.add_argument("--json", action="store_true",
                    help="one bench_collectives-style JSON line per op "
                         '({"metric", "value", "unit", "extra"})')
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + few iters (CI plumbing check; "
                         "CPU-safe)")
    args = ap.parse_args()
    from tools._mesh_setup import use_compile_cache
    use_compile_cache()
    if args.op in (None, "suite"):
        if args.suite == "pallas":
            pallas_suite(args.dtype, iters=args.iters, smoke=args.smoke,
                         json_mode=args.json)
        else:
            default_suite(args.dtype or "bfloat16", iters=args.iters,
                          json_mode=args.json)
        return
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    dt = jnp.dtype(args.dtype or "bfloat16")
    if args.op == "matmul":
        a = jnp.asarray(rng.randn(args.m, args.k), dt)
        b = jnp.asarray(rng.randn(args.k, args.n), dt)
        bench_case(f"matmul_{args.m}x{args.k}x{args.n}_{dt.name}",
                   jnp.matmul, (a, b), flops=2 * args.m * args.k * args.n,
                   iters=args.iters, json_mode=args.json)
    else:
        raise SystemExit(f"unknown op {args.op!r} (use: matmul | suite)")


if __name__ == "__main__":
    main()
