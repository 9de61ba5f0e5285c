"""Plain GPT-2: forward, loss and gradients in float32 jax.numpy.

Written from the GPT-2 description (Radford et al. 2019, and the layer
order of the released model): token + learned position embeddings; per
block ``x += attn(ln_1(x)); x += mlp(ln_2(x))`` (pre-norm); causal
softmax attention scaled by 1/sqrt(head width); a 4x MLP with the tanh
approximation of GELU ("gelu_new"); a final LayerNorm; logits against
the transposed token embedding (tied head); mean next-token cross
entropy. No kernels, no cache, no batching tricks, no dropout, and every
matrix product at "highest" precision (on a TPU a float32 product is
otherwise rounded through bf16).

Nothing here imports the program under test. Departures from the
description, none of which changes a value:

- the parameters arrive as a plain dict of this module's own names (see
  ``PARAM_NAMES``); the family file maps the program's leaves onto them
  and puts the fused q/k/v projection into GPT-2's ``[q | k | v]``
  column order;
- labels are given (the caller shifts the tokens), so one row yields
  ``seq`` predictions, not ``seq - 1``;
- ``remat=True`` wraps each block in ``jax.checkpoint`` so that the
  gradients of a 24-layer model fit beside the trainer's state. It
  recomputes, it does not approximate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# per block; the model adds wte, wpe, lnf_g, lnf_b
BLOCK_PARAM_NAMES = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                     "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")
PARAM_NAMES = ("wte", "wpe", "blocks", "lnf_g", "lnf_b")


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(x, p, n_head):
    b, s, h = x.shape
    d = h // n_head
    qkv = x @ p["qkv_w"] + p["qkv_b"]                       # (b, s, 3h)
    q, k, v = (jnp.transpose(jnp.reshape(t, (b, s, n_head, d)), (0, 2, 1, 3))
               for t in jnp.split(qkv, 3, axis=-1))        # (b, n, s, d)
    scores = q @ jnp.swapaxes(k, -1, -2) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.reshape(jnp.transpose(probs @ v, (0, 2, 1, 3)), (b, s, h))
    return out @ p["proj_w"] + p["proj_b"]


def block(x, p, n_head, eps):
    x = x + attention(layer_norm(x, p["ln1_g"], p["ln1_b"], eps), p, n_head)
    m = gelu_new(layer_norm(x, p["ln2_g"], p["ln2_b"], eps) @ p["fc_w"]
                 + p["fc_b"])
    return x + m @ p["out_w"] + p["out_b"]


def loss(params, ids, labels, *, n_head, eps=1e-5, remat=False):
    """Mean next-token cross entropy of ``ids`` (rows, seq) against
    ``labels`` (rows, seq); ``params`` holds float32 leaves."""
    with jax.default_matmul_precision("highest"):
        s = ids.shape[1]
        x = params["wte"][ids] + params["wpe"][jnp.arange(s)][None]
        blk = jax.checkpoint(block, static_argnums=(2, 3)) if remat else block
        for p in params["blocks"]:
            x = blk(x, p, n_head, eps)
        x = layer_norm(x, params["lnf_g"], params["lnf_b"], eps)
        logits = x @ params["wte"].T                        # (rows, s, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked)


def loss_and_grads(params, ids, labels, *, n_head, eps=1e-5, remat=False):
    """``(loss, grads)`` with grads shaped like ``params``, all float32."""
    return jax.value_and_grad(loss)(params, ids, labels, n_head=n_head,
                                    eps=eps, remat=remat)
