"""A/B the device-resident hot embedding tier (HeterEmbedding) against
the host pure_callback-per-lookup PS path (DistributedEmbedding) on the
Wide&Deep CTR workload (BASELINE configs[4]).

Run: python tools/bench_heter_embedding.py   (SMOKE=1 for a tiny CPU
config). Prints samples/sec for both paths + the hot-tier hit rate.
Target (round-3 verdict item 2): device path >= 10x the host path on
chip. The host fetch of the loss ends each timed region — see bench.py
`_timed_steps`. Runs on whatever backend jax picks; ``JAX_PLATFORMS=cpu``
holds it to the CPU.
"""
import os
import sys
import time

import numpy as np

# runnable from anywhere: repo root (paddle_tpu's parent) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from tools._mesh_setup import use_compile_cache

    use_compile_cache()
    import paddle_tpu as paddle
    from paddle_tpu.distributed.engine import ParallelTrainer
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.rec import WideDeep
    import jax.numpy as jnp

    smoke = os.environ.get("SMOKE") == "1"
    if smoke:
        fields, batch, steps, warmup = [1000] * 8, 256, 4, 2
        hidden, cap = (64, 32), 4096
    else:
        fields, batch, steps, warmup = [100_000] * 26, 4096, 20, 8
        hidden, cap = (400, 400, 400), 1_000_000

    rng = np.random.RandomState(0)
    # zipf-ish skew: real CTR traffic is head-heavy, which is what a
    # cache tier exploits
    def draw_ids():
        u = rng.zipf(1.3, size=(batch, len(fields)))
        return (u % np.asarray(fields)[None, :]).astype("int64")

    batches = [(draw_ids(), rng.randn(batch, 13).astype("float32"),
                rng.randint(0, 2, batch).astype("float32"))
               for _ in range(steps + warmup)]

    def bce(logit, y):
        return jnp.mean(jnp.maximum(logit, 0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    results = {}
    for mode, overlap in (("heter", False), ("heter", True), (True, False)):
        paddle.seed(0)
        build_mesh({"data": 1})
        model = WideDeep(fields, dense_dim=13, embedding_dim=16,
                         hidden_sizes=hidden, sparse=mode,
                         heter_capacity=cap)
        opt = paddle.optimizer.Adagrad(0.05, epsilon=1e-8,
                                       parameters=model.parameters())
        tr = ParallelTrainer(model, opt, bce)

        def run(run_batches):
            if mode == "heter" and overlap:
                # double-buffered: prepare(k+1) runs on the tier's
                # worker thread while the device executes step k — the
                # reference's heter client/server overlap
                # (heter_client.cc), TPU-shaped
                # ORDER MATTERS: submit prepare(k+1) only after
                # train_step(k) has DISPATCHED (it returns while the
                # device still computes) — the step donates the old
                # state buffers, so a prepare submitted before dispatch
                # can read donated arrays; after dispatch it reads the
                # step's (async) output arrays, overlapping cleanly
                fut = model.prepare_batch_async(run_batches[0][0])
                loss = None
                for i, (ids, dense, y) in enumerate(run_batches):
                    slots = fut.result()
                    loss = tr.train_step((slots, dense), y)
                    if i + 1 < len(run_batches):
                        fut = model.prepare_batch_async(
                            run_batches[i + 1][0])
                return loss
            for ids, dense, y in run_batches:
                if mode == "heter":
                    ids = model.prepare_batch(ids)
                loss = tr.train_step((ids, dense), y)
            return loss

        float(run(batches[:warmup]))
        if mode == "heter":
            model.ctr_table.stats["prepare_s"] = 0.0
            model.ctr_table.stats["tier_exchange_s"] = 0.0
        t0 = time.perf_counter()
        float(run(batches[warmup:]))
        dt = time.perf_counter() - t0
        name = ("host_ps_tier" if mode is True else
                "heter_overlapped" if overlap else "heter_device_tier")
        results[name] = batch * steps / dt
        line = f"{name:18s}: {results[name]:12,.1f} samples/sec"
        if mode == "heter":
            prep = model.ctr_table.stats["prepare_s"]
            tx = model.ctr_table.stats["tier_exchange_s"]
            line += (f"  (hot hit rate {model.ctr_table.hit_rate:.3f}, "
                     f"evicts {model.ctr_table.stats['evicts']}, "
                     f"prepare {prep:.3f}s [{tx:.3f}s tier-exchange] = "
                     f"{prep / dt:.0%} of wall"
                     f"{' — overlapped' if overlap else ''})")
        print(line)
    print(f"device/host speedup: "
          f"{results['heter_device_tier'] / results['host_ps_tier']:.1f}x"
          f" (overlapped: "
          f"{results['heter_overlapped'] / results['host_ps_tier']:.1f}x)")


if __name__ == "__main__":
    main()
