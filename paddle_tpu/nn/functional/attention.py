"""Attention functional.

Replaces the reference's fused attention CUDA kernels
(operators/fused/multihead_matmul_op.cu, math/bert_encoder_functor.cu) which
materialize the O(S²) score matrix. Default path here is the Pallas flash
attention kernel (paddle_tpu/ops/pallas/flash_attention.py) — blockwise,
O(S) memory; falls back to a pure-XLA implementation off-TPU or for tiny
shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def block_diffusion_mask(seq: int, block_length: int):
    """The block-diffusion training mask (BD3-LM) as a boolean ``(seq,
    seq)`` matrix, True where query ``i`` sees key ``j``. A row holds a
    sequence twice, ``[noised ; clean]``, ``L = seq // 2`` positions each in
    blocks of ``block_length``: a noised query sees the noised keys of its
    own block and the clean keys of the blocks before it; a clean query the
    clean keys of its own block and of those before it; nobody sees a
    noised key of another block."""
    from ...ops.pallas.flash_attention import block_diffusion_visible
    index = jnp.arange(seq)
    return block_diffusion_visible(index[:, None], index[None, :], seq // 2,
                                   lambda p: p // block_length)


def _xla_attention(q, k, v, mask=None, scale=None, causal=False, dropout_p=0.0,
                   training=True, window=None, block_diffusion=None):
    # q: (B, S, H, D); k, v: (B, T, H_kv, D) with H % H_kv == 0
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    grouped = k.shape[2] != q.shape[2]
    if grouped:
        # query head i reads KV head i // group: no repeated K or V
        b, s, h, _ = q.shape
        q = jnp.reshape(q, (b, s, k.shape[2], h // k.shape[2], d))
        logits = jnp.reshape(jnp.einsum("bskgd,btkd->bkgst", q, k),
                             (b, h, s, k.shape[1])) * scale
    else:
        logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((s, t), dtype=bool))
        if window is not None:
            # key j is visible to query i iff 0 <= i - j < window
            cm = cm & ~jnp.tril(jnp.ones((s, t), dtype=bool), -int(window))
        logits = jnp.where(cm, logits, jnp.finfo(logits.dtype).min)
    if block_diffusion is not None:
        logits = jnp.where(
            block_diffusion_mask(logits.shape[-1], block_diffusion), logits,
            jnp.finfo(logits.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        from .common import dropout as _dropout
        probs = _dropout(probs, p=dropout_p, training=True)
    if grouped:
        probs = jnp.reshape(probs, (b, k.shape[2], h // k.shape[2], s,
                                    k.shape[1]))
        return jnp.reshape(jnp.einsum("bkgst,btkd->bskgd", probs, v),
                           (b, s, h, d))
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 kv_lens=None, name=None, window=None,
                                 block_diffusion=None):
    """query: (batch, seq, num_heads, head_dim); key/value: (batch, seq,
    kv_heads, head_dim), where ``kv_heads`` divides ``num_heads`` and query
    head ``i`` reads KV head ``i // (num_heads // kv_heads)``.

    window: optional causal window (needs ``is_causal``): query ``i`` sees
    key ``j`` iff ``0 <= i - j < window``.

    block_diffusion: optional block length ``B``: the block-diffusion
    training mask over rows ``[noised ; clean]`` of ``2L`` positions
    (``block_diffusion_mask``), on the flash kernels and on the XLA path
    alike. It is a mask of its own: with ``is_causal``, ``window``,
    ``kv_lens``, ``attn_mask`` or dropout, with keys that are not the
    queries' own positions, an odd ``seq`` or ``L % B != 0`` it raises.

    kv_lens: optional (batch,) valid key/value counts — the O(B) form of a
    trailing-padding key mask; keeps padded batches on the flash kernel
    (a dense (B,1,1,T) ``attn_mask`` falls back to XLA, since streaming an
    O(S²) mask forfeits flash's memory advantage anyway).
    """
    # Trace scopes: "sdpa" holds everything attention stages (the kernels
    # or the O(S^2) path, and the layout changes around them), "flash" or
    # "xla" inside it says which path ran. Read by the benchmark's
    # attn_path_ms_per_step through the device trace's tf_op.
    if window is not None and not is_causal:
        raise ValueError("window is a causal window: it needs is_causal")
    if block_diffusion is not None:
        _check_block_diffusion(query, key, block_diffusion, attn_mask,
                               dropout_p if training else 0.0, is_causal,
                               kv_lens, window)
    with jax.named_scope("sdpa"):
        return _sdpa(query, key, value, attn_mask, dropout_p, is_causal,
                     training, kv_lens, window, block_diffusion)


def _check_block_diffusion(query, key, block_length, attn_mask, dropout_p,
                           is_causal, kv_lens, window):
    """What the block-diffusion mask does not define is refused, not
    computed as something else."""
    seq = query.shape[1]
    given = [name for name, on in (
        ("is_causal", is_causal), ("window", window is not None),
        ("kv_lens", kv_lens is not None), ("attn_mask", attn_mask is not None),
        ("dropout", dropout_p > 0.0)) if on]
    if given:
        raise ValueError(f"block_diffusion is a mask of its own: it cannot "
                         f"be combined with {', '.join(given)}")
    if key.shape[1] != seq or seq % 2:
        raise ValueError(
            f"block_diffusion masks self-attention over [noised ; clean] "
            f"rows, two equal halves: got {seq} queries and {key.shape[1]} "
            f"keys")
    if int(block_length) < 1 or (seq // 2) % int(block_length):
        raise ValueError(f"a half of {seq // 2} positions is not a whole "
                         f"number of blocks of {block_length}")


def _sdpa(query, key, value, attn_mask, dropout_p, is_causal, training,
          kv_lens, window, block_diffusion=None):
    from ...ops.pallas.flash_attention import flash_attention, flash_supported
    # The gate at 512 positions has no run at 512 on record. What the
    # benchmark's cells measured (PERF.md sections 5 and 6, PRs 26, 28): at
    # 1,024 positions the kernels and the layout copies around them take
    # 39.1 ms of gpt2-small's 126.1 ms step, at 256 positions this XLA
    # path takes 42.0 ms of a 209.1 ms step for the same tokens. Re-judging
    # the gate is queued in PERF.md section 7. Dropout and kv_lens padding
    # masks run inside the kernel; only dense attn_mask tensors force the
    # XLA path.
    if attn_mask is None and flash_supported(query, key, min_seq=512):
        # no except around the kernel: one the gate selected must raise
        # when it breaks, not leave the model training on the O(S²) path
        rate, seed = 0.0, None
        if dropout_p > 0.0 and training:
            from ...framework.random import get_rng_key
            rate = float(dropout_p)
            seed = jax.random.randint(get_rng_key(), (), 0,
                                      jnp.iinfo(jnp.int32).max,
                                      dtype=jnp.int32)
        with jax.named_scope("flash"):
            return flash_attention(query, key, value, causal=is_causal,
                                   kv_lens=kv_lens, dropout_rate=rate,
                                   dropout_seed=seed, window=window,
                                   block_diffusion=block_diffusion)
    from ...ops.pallas.tuner import record_fallback
    record_fallback("flash_attention")
    if kv_lens is not None:
        t = key.shape[1]
        lens_mask = (jnp.arange(t)[None, None, None, :] <
                     jnp.asarray(kv_lens).reshape(-1, 1, 1, 1))
        if attn_mask is None:
            attn_mask = lens_mask
        elif attn_mask.dtype == jnp.bool_:
            attn_mask = attn_mask & lens_mask
        else:  # additive bias: padding keys get -inf-like logits
            attn_mask = attn_mask + jnp.where(
                lens_mask, 0.0, jnp.finfo(jnp.float32).min)
    with jax.named_scope("xla"):
        return _xla_attention(query, key, value, mask=attn_mask,
                              causal=is_causal, dropout_p=dropout_p,
                              training=training, window=window,
                              block_diffusion=block_diffusion)
