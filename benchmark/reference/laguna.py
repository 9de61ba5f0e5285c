"""Plain Laguna-XS.2: forward, loss and gradients in float32 jax.numpy.

Written from the published ``config.json`` (poolside/Laguna-XS.2, the
catalog row) and the equations of ISSUE 27. With ``D`` the hidden width,
``d`` the head width, ``eps`` the RMSNorm epsilon, no biases anywhere, an
untied head and token embeddings only:

- block ``l``: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``;
  after the last block ``RMSNorm``, then ``lm_head``; mean next-token cross
  entropy.
- ``Attn_l(u)``: ``q = u W_q`` as ``[S, H_l, d]``, ``k = u W_k`` and ``v = u
  W_v`` as ``[S, KV, d]``; query head ``i`` reads KV head ``i // (H_l / KV)``.
  RoPE on q and k in the rotate-half convention: sliding layers plain (theta
  and all lanes as the config says), full layers YaRN on the first
  ``partial_rotary_factor * d`` lanes, the others pass through, cos and sin
  times ``attention_factor``. Scores ``q k^T / sqrt(d)`` under an explicit
  ``[S, S]`` mask: causal, and in sliding layers key ``j`` visible to query
  ``i`` iff ``0 <= i - j < window``. ``o = softmax(scores) v``.
- ``FFN`` dense: ``(silu(u W_gate) * (u W_up)) W_down``. Sparse: ``shared(u)
  + sum over e in top_k(s), e held here, of w_e expert_e(u)``, every expert
  the same gated SiLU FFN, as a dense loop (``lax.scan``) over the held
  experts: each runs on every token, weight 0 where it was not chosen.

ASSUMED (the config row has no equation for these; the configuration file
lists them under ``assumed``):

1. ``"gating": true`` is a per-head sigmoid gate on the attention output:
   ``g = sigmoid(u W_g)``, ``W_g`` ``[D, H_l]``, ``o_i <- g_i o_i``.
2. The router scores with a sigmoid, in float32: ``s = sigmoid(u W_r)``.
3. The chosen weights are normalised: ``w = factor * s_top / sum(s_top)``
   (``moe_routed_scaling_factor`` 2.5 is published), on the experts' output.
4. ``hidden_act`` is SiLU. No QK norm, no auxiliary loss, no bias correction.

Nothing here imports the program under test. Other departures, none of
which changes a value:

- the parameters arrive as a plain dict of this module's own names
  (``BLOCK_PARAM_NAMES``); the family file maps the program's leaves onto it;
- the layer holds ``held = (first, count)`` of the experts, as the program's
  layer does: ``experts_*`` carry ``count`` stacked experts, the router its
  full width, and what the absent experts would add is left out;
- labels are given (the caller shifts the tokens);
- attention runs KV head by KV head (``lax.map``) and, with ``remat=True``,
  each block and each KV head is under ``jax.checkpoint``, so that 4,096 x
  4,096 float32 scores for 64 heads never exist at once. It recomputes, it
  does not approximate.
- ``compare.py`` hands the architecture over under the keyword ``n_head``
  (its name for what describes the heads): here the dict ``arch`` documented
  at ``loss``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# per block; the model adds embed, lm_head, norm_g
BLOCK_PARAM_NAMES = ("norm1_g", "q_w", "k_w", "v_w", "g_w", "o_w", "norm2_g")
DENSE_PARAM_NAMES = ("gate_w", "up_w", "down_w")
SPARSE_PARAM_NAMES = ("router_w", "shared_gate_w", "shared_up_w",
                      "shared_down_w", "experts_gate_w", "experts_up_w",
                      "experts_down_w")
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


def rope_tables(seq, d, rope):
    """``(cos, sin)`` float32 ``[seq, r]`` for one kind of layer, ``r`` the
    rotated lanes; ``rope`` is the config's group for that kind."""
    r = int(round(d * rope.get("partial_rotary_factor", 1)))
    theta = float(rope["rope_theta"])
    i = jnp.arange(0, r, 2, dtype=jnp.float32)
    inv_freq = theta ** (-i / r)
    scale = 1.0
    if rope["rope_type"] == "yarn":
        factor = float(rope["factor"])
        original = rope["original_max_position_embeddings"]

        def correction(rotations):
            return r * math.log(original / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(correction(rope["beta_fast"])), 0)
        high = min(math.ceil(correction(rope["beta_slow"])), r - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        # pairs below `low` keep their frequency, above `high` it is
        # divided by `factor`
        inv_freq = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
        scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)        # [seq, r]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rope(x, cos, sin):
    """x ``[b, s, heads, d]``; rotate-half over the first ``r`` lanes."""
    r = cos.shape[-1]
    rot, rest = x[..., :r], x[..., r:]
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], axis=-1)
    rot = rot * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([rot, rest], axis=-1)


def attention(u, p, layer, arch, remat):
    b, s, _ = u.shape
    d, kv, heads = arch["head_dim"], arch["kv_heads"], layer["heads"]
    group = heads // kv
    q = jnp.reshape(_mm(u, p["q_w"]), (b, s, heads, d))
    k = jnp.reshape(_mm(u, p["k_w"]), (b, s, kv, d))
    v = jnp.reshape(_mm(u, p["v_w"]), (b, s, kv, d))
    cos, sin = rope_tables(s, d, arch["rope"][layer["attention"]])
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = i >= j                                              # [S, S]
    if layer["attention"] == "sliding_attention":
        mask = mask & (i - j < arch["sliding_window"])

    def one_kv_head(qkv):
        qh, kh, vh = qkv            # [b, s, group, d], [b, s, d], [b, s, d]
        scores = jnp.einsum("bsgd,btd->bgst", qh, kh) / jnp.sqrt(
            jnp.float32(d))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bgst,btd->bsgd", jax.nn.softmax(scores, axis=-1),
                          vh)

    if remat:
        one_kv_head = jax.checkpoint(one_kv_head)
    q = jnp.moveaxis(jnp.reshape(q, (b, s, kv, group, d)), 2, 0)
    o = jax.lax.map(one_kv_head, (q, jnp.moveaxis(k, 2, 0),
                                  jnp.moveaxis(v, 2, 0)))   # [kv,b,s,group,d]
    o = jnp.reshape(jnp.moveaxis(o, 0, 2), (b, s, heads, d))
    if arch["gated_attention"]:                                 # ASSUMED (1)
        o = o * jax.nn.sigmoid(_mm(u, p["g_w"]))[..., None]
    return _mm(jnp.reshape(o, (b, s, heads * d)), p["o_w"])


def route(u, router_w, arch):
    """``(ids [.., k], weights [.., k])``: ASSUMED (2) and (3)."""
    scores = jax.nn.sigmoid(_mm(u, router_w))
    top, ids = jax.lax.top_k(scores, arch["top_k"])
    return ids, arch["routed_scaling_factor"] * top / jnp.sum(
        top, axis=-1, keepdims=True)


def sparse_ffn(u, p, arch):
    ids, weights = route(u, p["router_w"], arch)
    out = gated_ffn(u, p["shared_gate_w"], p["shared_up_w"],
                    p["shared_down_w"])
    first, count = arch["held"]

    def add_expert(out, expert):
        e, gate_w, up_w, down_w = expert
        # the weight this expert has for each token, 0 where not chosen
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return out + w[..., None] * gated_ffn(u, gate_w, up_w, down_w), None

    # every held expert on every token: a loop, compiled once
    out, _ = jax.lax.scan(add_expert, out, (
        jnp.arange(count), p["experts_gate_w"], p["experts_up_w"],
        p["experts_down_w"]))
    return out, ids


def block(x, p, layer, arch, eps, remat):
    """``(y, experts chosen)``; the second is None in a dense layer."""
    x = x + attention(rms_norm(x, p["norm1_g"], eps), p, layer, arch, remat)
    u = rms_norm(x, p["norm2_g"], eps)
    if layer["ffn"] == "sparse":
        out, chosen = sparse_ffn(u, p, arch)
        return x + out, chosen
    return x + gated_ffn(u, p["gate_w"], p["up_w"], p["down_w"]), None


def hidden_states(params, ids, arch, eps, remat=False):
    """The final norm's output and, per sparse layer, the experts chosen."""
    x = params["embed"][ids]
    chosen = []
    for p, layer in zip(params["blocks"], arch["layers"]):
        def blk(x_, p_, layer=layer):
            return block(x_, p_, layer, arch, eps, remat)
        x, ids_l = (jax.checkpoint(blk) if remat else blk)(x, p)
        if ids_l is not None:
            chosen.append(ids_l)
    return rms_norm(x, params["norm_g"], eps), chosen


def loss(params, ids, labels, *, n_head, eps=1e-6, remat=False):
    """Mean next-token cross entropy of ``ids`` (rows, seq) against
    ``labels`` (rows, seq); ``params`` holds float32 leaves.

    ``n_head`` is the architecture (``arch``): ``layers`` (a list of
    ``{"attention", "heads", "ffn"}``), ``head_dim``, ``kv_heads``,
    ``sliding_window``, ``rope`` (the config's group per kind of attention),
    ``gated_attention``, ``top_k``, ``routed_scaling_factor`` and ``held``
    (first, count)."""
    arch = n_head
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(params, ids, arch, eps, remat)
        logits = _mm(x, params["lm_head"])                  # (rows, s, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked)


def chosen_experts(params, ids, *, n_head, eps=1e-6):
    """The expert ids ``[rows, seq, k]`` each sparse layer's router chose."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, ids, n_head, eps)[1]
