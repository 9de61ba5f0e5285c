"""Block-diffusion training (BD3-LM, SDAR): the noise and the loss.

A clean row ``x0`` of ``L`` tokens lies in blocks of ``B``. Training draws
for every (row, block) a level ``t ~ U[t_min, 1]`` and turns each token of
the block into ``[MASK]`` independently with probability ``t`` (the linear
schedule of masked diffusion: MDLM, LLaDA). The model reads ``[xt ; x0]``,
``2L`` positions of which both copies of token ``i`` sit at position ``i``,
under the mask ``nn.functional.block_diffusion_mask``: a noised block sees
itself and the clean blocks before it. Its logits at the noised half
predict ``x0`` at the same position (no shift), and the loss is the
masked tokens' cross entropy weighted by ``1 / t``, over all ``rows * L``
tokens: an unbiased estimate of the diffusion bound a block.

These are the functions a training loop calls; ``text.models.
MixedDecoderForBlockDiffusion`` calls them around a mixed decoder.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn import functional as F


def noise(tokens, key, block_length: int, mask_token_id: int,
          t_min: float = 1e-3):
    """``(noised tokens, masked bool, t float32)``, each ``(rows, L)``: a
    pure function of ``(tokens, key)``. ``t`` is the level of a token's
    block. Staged under the scope ``block_diffusion_noise``."""
    rows, length = tokens.shape
    if length % block_length:
        raise ValueError(f"{length} tokens are not a whole number of blocks "
                         f"of {block_length}")
    with jax.named_scope("block_diffusion_noise"):
        level_key, mask_key = jax.random.split(key)
        t = t_min + (1.0 - t_min) * jax.random.uniform(
            level_key, (rows, length // block_length), jnp.float32)
        t = jnp.repeat(t, block_length, axis=1)
        masked = jax.random.uniform(mask_key, (rows, length),
                                    jnp.float32) < t
        return jnp.where(masked, mask_token_id, tokens), masked, t


def model_inputs(noised, tokens):
    """``(ids (rows, 2L), positions (2L,))``: the noised row, then the clean
    one; token ``i`` at position ``i`` in both."""
    length = tokens.shape[1]
    index = jnp.arange(length, dtype=jnp.int32)
    return (jnp.concatenate([noised, tokens], axis=1),
            jnp.concatenate([index, index]))


def loss(logits, tokens, masked, t):
    """``sum over masked i of CE(logits_i, tokens_i) / t_i``, over ``rows *
    L``: float32. ``logits`` ``(rows, L, vocab)`` are the noised half's."""
    ce = F.cross_entropy(logits, tokens, reduction="none")
    return jnp.sum(jnp.where(masked, ce / t, 0.0)) / masked.size
