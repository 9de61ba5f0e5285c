"""Plain SDAR-MoE under the block-diffusion training objective: forward,
loss and gradients in float32 jax.numpy.

Written from the published ``config.json`` (JetLM/SDAR-30B-A3B-Chat, the
catalog row: a Qwen3-MoE block) and the equations of ISSUE 31. With ``D``
the hidden width, ``d`` the head width, ``L`` the clean row's length, ``B``
the block length, ``b(i) = i // B``, no biases anywhere, an untied head:

1. Noise: per (row, block) ``t ~ U[t_min, 1]``; each token of the block
   becomes ``[MASK]`` independently with probability ``t``: ``xt``, and the
   indicator ``m``.
2. Input ``[xt ; x0]``, ``2L`` positions, rotated by ``[0..L-1 ; 0..L-1]``.
3. Block (x layers): ``h = x + Attn(RMSNorm(x))``, ``y = h +
   MoE(RMSNorm(h))``.
   - ``Attn(u)``: ``q = u W_q`` as ``[2L, H, d]``, ``k = u W_k``, ``v = u
     W_v`` as ``[2L, KV, d]``; ``q <- RMSNorm_d(q)``, ``k <- RMSNorm_d(k)``
     per head with a learned ``d``-vector each; RoPE (rotate-half, theta,
     all ``d`` lanes) at the positions of 2; scores ``q k^T / sqrt(d)``;
     query head ``i`` reads KV head ``i // (H / KV)``; softmax over the
     visible keys; ``W_o``.
   - Visible, for query index ``i`` and key index ``j`` in ``[0, 2L)``,
     ``n(.)`` true in the noised half, ``p(.)`` the position: a noised query
     sees a noised key iff ``b(p_j) == b(p_i)`` and a clean key iff
     ``b(p_j) < b(p_i)``; a clean query sees a clean key iff ``b(p_j) <=
     b(p_i)`` and no noised key. An explicit ``[2L, 2L]`` boolean matrix.
   - ``MoE(u)``: logits ``u W_r`` over all experts; softmax over all of
     them; the ``top_k`` largest, divided by their sum; ``sum over e chosen
     and held here of w_e expert_e(u)``, ``expert(u) = (silu(u W_gate) * (u
     W_up)) W_down``, as a dense loop (``lax.scan``) over the held experts:
     each runs on every token, weight 0 where it was not chosen. No shared
     expert.
4. Final RMSNorm and the head over the noised half only. No shift: position
   ``i`` predicts ``x0[i]``. ``loss = (1 / (rows * L)) * sum over masked i
   of CE(logits_i, x0[i]) / t_b(i)``.

ASSUMED (the config row names neither; the configuration file lists them
under ``assumed``): ``B``, the schedule and ``t_min`` of 1, the weight and
the normalisation of 4, no shift, the QK norm of 3, the ``[MASK]`` id.

Nothing here imports the program under test; ``jax.random`` is shared, the
statement of the noise is this file's own. Other departures, none of which
changes a value:

- the parameters arrive as a plain dict of this module's own names
  (``BLOCK_PARAM_NAMES``); the family file maps the program's leaves onto it;
- the layer holds ``held = (first, count)`` of the experts, as the program's
  layer does: ``experts_*`` carry ``count`` stacked experts, the router its
  full width, and what the absent experts would add is left out;
- ``labels`` are taken and not read (the objective has no shift);
- attention runs query head by query head (``lax.map``) and, with
  ``remat=True``, each block and each head is under ``jax.checkpoint``, so
  that ``2L x 2L`` float32 scores exist for one head at a time (268 MB at
  ``2L`` = 8,192). It recomputes, it does not approximate.
- ``compare.py`` hands the architecture over under the keyword ``n_head``
  (its name for what describes the heads): here the dict ``arch`` documented
  at ``loss``, which also carries the noise: ``block_length``, ``t_min``,
  ``mask_token_id`` and ``noise_key``, the key data of the draw compared.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# per block; the model adds embed, lm_head, norm_g
BLOCK_PARAM_NAMES = ("norm1_g", "q_w", "k_w", "v_w", "q_norm_g", "k_norm_g",
                     "o_w", "norm2_g", "router_w", "experts_gate_w",
                     "experts_up_w", "experts_down_w")
PARAM_NAMES = ("embed", "blocks", "norm_g", "lm_head")


def _mm(a, b):
    """Every product against a parameter goes through here."""
    return a @ b


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gated_ffn(u, gate_w, up_w, down_w):
    return _mm(silu(_mm(u, gate_w)) * _mm(u, up_w), down_w)


def noise(x0, arch):
    """``(xt, m, t)`` of equation 1, each ``[rows, L]``."""
    rows, length = x0.shape
    block = arch["block_length"]
    key = jax.random.wrap_key_data(jnp.asarray(arch["noise_key"],
                                               jnp.uint32))
    level_key, mask_key = jax.random.split(key)
    u = jax.random.uniform(level_key, (rows, length // block), jnp.float32)
    t = jnp.repeat(arch["t_min"] + (1.0 - arch["t_min"]) * u, block, axis=1)
    m = jax.random.uniform(mask_key, (rows, length), jnp.float32) < t
    return jnp.where(m, arch["mask_token_id"], x0), m, t


def visible(length, block):
    """The ``[2L, 2L]`` boolean matrix of equation 3, from its four
    rules."""
    index = jnp.arange(2 * length)
    noised = index < length                       # n(.)
    b = (index % length) // block                 # b(p(.))
    qn, kn, qb, kb = noised[:, None], noised[None, :], b[:, None], b[None, :]
    return jnp.where(
        qn, jnp.where(kn, kb == qb, kb < qb),     # a noised query
        jnp.where(kn, False, kb <= qb))           # a clean query


def apply_rope(x, positions, theta):
    """x ``[b, s, heads, d]``; rotate-half over all ``d`` lanes."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [s, d]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos[None, :, None, :] + half * sin[None, :, None, :]


def attention(u, p, positions, mask, arch, eps, remat):
    b, s, _ = u.shape
    d, kv, heads = arch["head_dim"], arch["kv_heads"], arch["heads"]
    group = heads // kv
    q = jnp.reshape(_mm(u, p["q_w"]), (b, s, heads, d))
    k = jnp.reshape(_mm(u, p["k_w"]), (b, s, kv, d))
    v = jnp.reshape(_mm(u, p["v_w"]), (b, s, kv, d))
    q, k = rms_norm(q, p["q_norm_g"], eps), rms_norm(k, p["k_norm_g"], eps)
    theta = float(arch["rope_theta"])
    q, k = apply_rope(q, positions, theta), apply_rope(k, positions, theta)

    def one_head(args):
        i, qh = args                          # the head's index, [b, s, d]
        kh, vh = k[:, :, i // group], v[:, :, i // group]
        scores = jnp.einsum("bsd,btd->bst", qh, kh) / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bst,btd->bsd", jax.nn.softmax(scores, axis=-1),
                          vh)

    if remat:
        one_head = jax.checkpoint(one_head)
    o = jax.lax.map(one_head, (jnp.arange(heads), jnp.moveaxis(q, 2, 0)))
    return _mm(jnp.reshape(jnp.moveaxis(o, 0, 2), (b, s, heads * d)),
               p["o_w"])


def route(u, router_w, arch):
    """``(ids [.., k], weights [.., k])``: softmax over all experts, the
    ``top_k`` largest divided by their sum."""
    scores = jax.nn.softmax(_mm(u, router_w), axis=-1)
    top, ids = jax.lax.top_k(scores, arch["top_k"])
    return ids, top / jnp.sum(top, axis=-1, keepdims=True)


def moe(u, p, arch):
    ids, weights = route(u, p["router_w"], arch)
    first, count = arch["held"]

    def add_expert(out, expert):
        e, gate_w, up_w, down_w = expert
        # the weight this expert has for each token, 0 where not chosen
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return out + w[..., None] * gated_ffn(u, gate_w, up_w, down_w), None

    # every held expert on every token: a loop, compiled once
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        jnp.arange(count), p["experts_gate_w"], p["experts_up_w"],
        p["experts_down_w"]))
    return out, ids


def block(x, p, positions, mask, arch, eps, remat):
    """``(y, experts chosen)``."""
    x = x + attention(rms_norm(x, p["norm1_g"], eps), p, positions, mask,
                      arch, eps, remat)
    out, chosen = moe(rms_norm(x, p["norm2_g"], eps), p, arch)
    return x + out, chosen


def hidden_states(params, x0, arch, eps, remat=False):
    """The final norm's output over the noised half ``[rows, L, D]``, the
    noise ``(m, t)`` and, per layer, the experts chosen ``[rows, 2L, k]``."""
    length = x0.shape[1]
    xt, m, t = noise(x0, arch)
    ids = jnp.concatenate([xt, x0], axis=1)                    # equation 2
    positions = jnp.concatenate([jnp.arange(length)] * 2)
    mask = visible(length, arch["block_length"])
    x = params["embed"][ids]
    chosen = []
    for p in params["blocks"]:
        def blk(x_, p_):
            return block(x_, p_, positions, mask, arch, eps, remat)
        x, ids_l = (jax.checkpoint(blk) if remat else blk)(x, p)
        chosen.append(ids_l)
    return rms_norm(x[:, :length], params["norm_g"], eps), (m, t), chosen


def loss(params, ids, labels, *, n_head, eps=1e-6, remat=False):
    """The block-diffusion loss of the clean rows ``ids`` (rows, L);
    ``labels`` is not read; ``params`` holds float32 leaves.

    ``n_head`` is the architecture (``arch``): ``heads``, ``kv_heads``,
    ``head_dim``, ``rope_theta``, ``top_k``, ``held`` (first, count),
    ``block_length``, ``t_min``, ``mask_token_id`` and ``noise_key`` (the
    uint32 key data of the noise's draw)."""
    arch = n_head
    with jax.default_matmul_precision("highest"):
        x, (m, t), _ = hidden_states(params, ids, arch, eps, remat)
        logits = _mm(x, params["lm_head"])                  # (rows, L, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(m, ce / t, 0.0)) / m.size


def chosen_experts(params, ids, *, n_head, eps=1e-6):
    """The expert ids ``[rows, 2L, k]`` each layer's router chose."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, ids, n_head, eps)[2]
