"""A/B the chunked LM-head CE (ops/chunked_ce.py) against the dense
logits path at the bench GPT config, on a real chip.

Run: python tools/bench_fused_ce.py [chunk ...]
Prints tok/s for the dense path and each chunk size; if a chunk wins,
switch bench_gpt's loss to GPTForPretraining.fused_head_loss.
Set SMOKE=1 for a tiny CPU-sized config (plumbing check only).
(The host fetch of the loss ends each timed region — see bench.py
`_timed_steps`.)
"""
import os
import sys
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.jit.functionalization import functional_call, state_of
    from paddle_tpu.text.models import GPTForPretraining

    smoke = os.environ.get("SMOKE") == "1"
    if smoke:
        cfg = dict(vocab_size=512, hidden_size=64, num_layers=2,
                   num_heads=4, max_position_embeddings=64)
        batch, seq = 2, 32
        chunks = [int(a) for a in sys.argv[1:]] or [128]
        iters, warmup = 3, 2
    else:
        cfg = dict(vocab_size=50304, hidden_size=768, num_layers=12,
                   num_heads=12, max_position_embeddings=1024)
        batch, seq = 8, 1024
        chunks = [int(a) for a in sys.argv[1:]] or [4192, 8384, 16768]
        iters, warmup = 12, 8

    paddle.seed(0)
    build_mesh({"data": 1})
    model = GPTForPretraining(tensor_parallel=False, attn_dropout=0.0,
                              hidden_dropout=0.0, **cfg)
    if not smoke:
        model.bfloat16()
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg["vocab_size"], (batch, seq)),
                      jnp.int32)
    lbl = jnp.asarray(rng.randint(0, cfg["vocab_size"], (batch, seq)),
                      jnp.int32)

    class FusedLoss(nn.Layer):
        def __init__(self, model, chunk):
            super().__init__()
            self.model = model
            self._chunk = chunk

        def forward(self, ids, lbl):
            return self.model.fused_head_loss(ids, lbl, chunk=self._chunk)

    def timed(step, params):
        p = params
        for _ in range(warmup):
            l, p = step(p)
        float(l)
        t0 = time.perf_counter()
        for _ in range(iters):
            l, p = step(p)
        float(l)
        return batch * seq * iters / (time.perf_counter() - t0)

    params, buffers = state_of(model)

    @jax.jit
    def dense_step(p):
        def lf(p):
            out, _ = functional_call(model, p, buffers, ids)
            return nn.functional.cross_entropy(out, lbl)
        l, g = jax.value_and_grad(lf)(p)
        return l, jax.tree.map(lambda a, b: a - 1e-4 * b, p, g)

    print(f"dense logits path : {timed(dense_step, params):,.0f} tok/s")

    for chunk in chunks:
        wrapper = FusedLoss(model, chunk)
        wp, wb = state_of(wrapper)

        @jax.jit
        def fused_step(p, wb=wb):
            def lf(p):
                out, _ = functional_call(wrapper, p, wb, ids, lbl)
                return out
            l, g = jax.value_and_grad(lf)(p)
            return l, jax.tree.map(lambda a, b: a - 1e-4 * b, p, g)

        print(f"chunked CE {chunk:6d}: {timed(fused_step, wp):,.0f} tok/s")


if __name__ == "__main__":
    main()
