"""Plain Qwen3-Next: forward, loss and gradients in float32 jax.numpy.

Written from the published ``config.json`` (Qwen/Qwen3-Next-80B-A3B-Instruct,
the catalog row) and the equations of ISSUE 33. With ``D`` the hidden width,
no biases anywhere, an untied head and token embeddings only:

- ``norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred: ``w``
  starts at 0), for the input norm, the post-mixer norm, the final norm and
  the q and k norms.
- block ``l``: ``h = x + mixer_l(norm(x))``, ``y = h + moe(norm(h))``; layers
  with ``(l + 1) % full_attention_interval == 0`` are gated full attention,
  the others Gated DeltaNet. After the last block ``norm``, then ``lm_head``;
  mean next-token cross entropy.
- **Gated DeltaNet** (``H_k`` key heads, ``H_v`` value heads, ``r = H_v /
  H_k``, widths ``d_k``, ``d_v``):
  1. ``u W_qkvz`` as ``[S, H_k, 2 d_k + 2 r d_v]``: per key head its q, its
     k, its ``r`` value heads' v and their z (the published checkpoint's
     grouping); ``u W_ba`` as ``[S, H_k, 2 r]``: per key head ``b`` and ``a``
     of its value heads. Value head ``j`` reads key head ``j // r``.
  2. ``[q | k | v] <- silu(conv([q | k | v]))`` over the flattened channels
     (all q, all k, all v): depthwise, causal, ``kernel`` taps as shifted
     products, zeros to the left, no bias.
  3. ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``.
  4. ``q <- q / sqrt(sum q^2 + 1e-6) / sqrt(d_k)``, ``k <- k / sqrt(sum k^2 +
     1e-6)`` over a head.
  5. per value head, ``S = 0`` (``d_k x d_v``); for ``t = 1..L``: ``S <-
     exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``;
     ``o_t = S^T q_t``. A ``lax.scan`` over the positions, never chunked:
     the program's chunked form is held to the definition.
  6. ``y = (o * rsqrt(mean(o^2) + eps) * w_n) * silu(z)`` per head (``w_n``
     plain, starting at 1), then ``W_o``.
- **Gated full attention**: ``u W_q`` as ``[S, H, 2 d]``, a head's query and
  then its gate; ``k, v`` as ``[S, KV, d]``; ``q <- norm(q)``, ``k <-
  norm(k)`` over ``d``; RoPE (rotate-half) on the first
  ``partial_rotary_factor * d`` lanes at ``rope_theta``; scores ``q k^T /
  sqrt(d)`` under an explicit causal ``[S, S]`` mask; query head ``i`` reads
  KV head ``i // (H / KV)``; ``o <- o * sigmoid(gate)`` lane by lane; ``W_o``.
- **Expert block**: ``softmax(u W_r)`` over all experts, the ``top_k`` largest
  divided by their sum; ``sum over e chosen and held here of w_e
  expert_e(u)``, ``expert(u) = (silu(u W_gate) * (u W_up)) W_down``, as a
  dense loop (``lax.scan``) over the held experts; ``+ sigmoid(u w_s) *
  shared(u)``, ``shared`` the same form.

ASSUMED (the config row has no key for these; they follow the published
implementation, and the configuration file lists them under ``assumed``):
the zero-centred norms, the gate inside ``q_proj``, step 4 and its ``1 /
sqrt(d_k)``, ``A_log`` and ``dt_bias``, no convolution bias, no auxiliary
loss, no multi-token-prediction module.

Nothing here imports the program under test. Other departures, none of
which changes a value:

- the parameters arrive as a plain dict of this module's own names; the
  family file maps the program's leaves onto it (and its ``W_qkvz`` and
  ``W_ba`` columns, which the program keeps as ``[q | k | v | z]`` and ``[b
  | a]``, onto the grouping of step 1);
- the layer holds ``held = (first, count)`` of the experts, as the program's
  layer does; what the absent experts would add is left out;
- labels are given (the caller shifts the tokens);
- with ``remat=True`` each block is under ``jax.checkpoint``, attention runs
  a query head at a time (``lax.map``, each head checkpointed: 8,192 x
  8,192 float32 scores are 268 MB), each held expert's part of the sum is
  checkpointed (32 experts' outputs over 8,192 tokens are 2.1 GB
  otherwise), and the scan of step 5 is cut into
  segments of 64 positions, each checkpointed, so that its backward pass
  holds a state a segment and the 64 of the segment at hand, not 8,192. It
  recomputes, it does not approximate: the steps are the same steps.
- ``compare.py`` hands the architecture over under the keyword ``n_head``:
  here the dict ``arch`` documented at ``loss``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PARAM_NAMES = ("embed", "blocks", "norm_g", "lm_head")
BLOCK_PARAM_NAMES = ("norm1_g", "norm2_g", "router_w", "shared_gate_w",
                     "shared_up_w", "shared_down_w", "shared_expert_gate_w",
                     "experts_gate_w", "experts_up_w", "experts_down_w")
LINEAR_PARAM_NAMES = ("qkvz_w", "ba_w", "conv_w", "a_log", "dt_bias",
                      "gdn_norm_g", "out_w")
FULL_PARAM_NAMES = ("q_w", "k_w", "v_w", "q_norm_g", "k_norm_g", "o_w")
SEGMENT = 64          # positions a checkpointed segment of the scan


def _mm(a, b):
    """Every product against a parameter goes through here."""
    return a @ b


def _state(s):
    """Every state of the recurrence goes through here, once a position."""
    return s


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gated_ffn(u, gate_w, up_w, down_w):
    return _mm(silu(_mm(u, gate_w)) * _mm(u, up_w), down_w)


# -- Gated DeltaNet ----------------------------------------------------------

def causal_conv(x, w):
    """x ``[b, s, c]``, w ``[c, kernel]``: ``out[t] = sum_j w[:, j] x[t -
    (kernel - 1) + j]``, zeros before position 0."""
    kernel, seq = w.shape[-1], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(kernel):
        shift = kernel - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :shift]), x[:, :seq - shift]], axis=1)
        out = out + shifted * w[:, j]
    return out


def delta_rule(q, k, v, g, beta, remat):
    """Step 5. q, k ``[b, s, H_v, d_k]``, v ``[b, s, H_v, d_v]``, g, beta
    ``[b, s, H_v]``; returns o ``[b, s, H_v, d_v]``."""
    b, seq, h, dk = q.shape

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = _state(s + k_t[..., :, None] * u[..., None, :])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    if not remat:
        return jnp.moveaxis(jax.lax.scan(step, s0, xs)[1], 0, 1)
    size = math.gcd(seq, SEGMENT)

    @jax.checkpoint
    def segment(s, xs_):
        return jax.lax.scan(step, s, xs_)

    xs = tuple(jnp.reshape(x, (seq // size, size) + x.shape[1:]) for x in xs)
    o = jax.lax.scan(segment, s0, xs)[1]
    return jnp.moveaxis(jnp.reshape(o, (seq,) + o.shape[2:]), 0, 1)


def gated_delta_net(u, p, arch, eps, remat):
    b, s, _ = u.shape
    lin = arch["linear"]
    hk, hv, dk, dv = (lin["key_heads"], lin["value_heads"], lin["d_k"],
                      lin["d_v"])
    r = hv // hk
    qkvz = jnp.reshape(_mm(u, p["qkvz_w"]), (b, s, hk, 2 * dk + 2 * r * dv))
    ba = jnp.reshape(_mm(u, p["ba_w"]), (b, s, hk, 2 * r))
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = jnp.reshape(qkvz[..., 2 * dk + r * dv:], (b, s, hv, dv))
    mixed = jnp.concatenate([jnp.reshape(x, (b, s, -1)) for x in (q, k, v)],
                            axis=-1)
    mixed = silu(causal_conv(mixed, p["conv_w"]))
    q = jnp.reshape(mixed[..., :hk * dk], (b, s, hk, dk))
    k = jnp.reshape(mixed[..., hk * dk:2 * hk * dk], (b, s, hk, dk))
    v = jnp.reshape(mixed[..., 2 * hk * dk:], (b, s, hv, dv))
    beta = jax.nn.sigmoid(jnp.reshape(ba[..., :r], (b, s, hv)))
    a = jnp.reshape(ba[..., r:], (b, s, hv))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    o = delta_rule(q, k, v, g, beta, remat)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * p["gdn_norm_g"] * silu(z)
    return _mm(jnp.reshape(y, (b, s, hv * dv)), p["out_w"])


# -- gated full attention ----------------------------------------------------

def apply_rope(x, theta, r):
    """x ``[b, s, heads, d]``; rotate-half over the first ``r`` lanes."""
    s = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [s, r]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    rot, rest = x[..., :r], x[..., r:]
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], axis=-1)
    rot = rot * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([rot, rest], axis=-1)


def attention(u, p, arch, eps, remat):
    b, s, _ = u.shape
    d, kv, heads = arch["head_dim"], arch["kv_heads"], arch["heads"]
    group = heads // kv
    qg = jnp.reshape(_mm(u, p["q_w"]), (b, s, heads, 2 * d))
    q, gate = qg[..., :d], qg[..., d:]
    k = jnp.reshape(_mm(u, p["k_w"]), (b, s, kv, d))
    v = jnp.reshape(_mm(u, p["v_w"]), (b, s, kv, d))
    q, k = norm(q, p["q_norm_g"], eps), norm(k, p["k_norm_g"], eps)
    theta, r = float(arch["rope_theta"]), arch["rotary_dim"]
    q, k = apply_rope(q, theta, r), apply_rope(k, theta, r)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

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
    o = jnp.moveaxis(o, 0, 2) * jax.nn.sigmoid(gate)
    return _mm(jnp.reshape(o, (b, s, heads * d)), p["o_w"])


# -- the expert block ----------------------------------------------------------

def route(u, router_w, arch):
    """``(ids [.., k], weights [.., k])``: softmax over all experts, the
    ``top_k`` largest divided by their sum."""
    scores = jax.nn.softmax(_mm(u, router_w), axis=-1)
    top, ids = jax.lax.top_k(scores, arch["top_k"])
    return ids, top / jnp.sum(top, axis=-1, keepdims=True)


def moe(u, p, arch, remat=False):
    ids, weights = route(u, p["router_w"], arch)
    first, count = arch["held"]
    out = jax.nn.sigmoid(_mm(u, p["shared_expert_gate_w"])) * gated_ffn(
        u, p["shared_gate_w"], p["shared_up_w"], p["shared_down_w"])

    def part(expert):
        e, gate_w, up_w, down_w = expert
        # the weight this expert has for each token, 0 where not chosen
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return w[..., None] * gated_ffn(u, gate_w, up_w, down_w)

    if remat:
        part = jax.checkpoint(part)

    def add_expert(out, expert):
        return out + part(expert), None

    # every held expert on every token: a loop, compiled once
    out, _ = jax.lax.scan(add_expert, out, (
        jnp.arange(count), p["experts_gate_w"], p["experts_up_w"],
        p["experts_down_w"]))
    return out, ids


def block(x, p, kind, arch, eps, remat):
    """``(y, experts chosen)``."""
    u = norm(x, p["norm1_g"], eps)
    if kind == "linear_attention":
        x = x + gated_delta_net(u, p, arch, eps, remat)
    else:
        x = x + attention(u, p, arch, eps, remat)
    out, chosen = moe(norm(x, p["norm2_g"], eps), p, arch, remat)
    return x + out, chosen


def hidden_states(params, ids, arch, eps, remat=False):
    """The final norm's output and, per layer, the experts chosen."""
    x = params["embed"][ids]
    chosen = []
    for p, kind in zip(params["blocks"], arch["layers"]):
        def blk(x_, p_, kind=kind):
            return block(x_, p_, kind, arch, eps, remat)
        x, ids_l = (jax.checkpoint(blk) if remat else blk)(x, p)
        chosen.append(ids_l)
    return norm(x, params["norm_g"], eps), chosen


def loss(params, ids, labels, *, n_head, eps=1e-6, remat=False):
    """Mean next-token cross entropy of ``ids`` (rows, seq) against
    ``labels`` (rows, seq); ``params`` holds float32 leaves.

    ``n_head`` is the architecture (``arch``): ``layers`` (a list of
    ``"linear_attention"`` / ``"full_attention"``), ``heads``, ``kv_heads``,
    ``head_dim``, ``rope_theta``, ``rotary_dim``, ``linear`` (``key_heads``,
    ``value_heads``, ``d_k``, ``d_v``, ``conv_kernel``), ``top_k`` and
    ``held`` (first, count)."""
    arch = n_head
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(params, ids, arch, eps, remat)
        logits = _mm(x, params["lm_head"])                  # (rows, s, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked)


def chosen_experts(params, ids, *, n_head, eps=1e-6):
    """The expert ids ``[rows, seq, k]`` each layer's router chose."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, ids, n_head, eps)[1]
