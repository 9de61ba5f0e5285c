"""Plain Olmo-Hybrid: forward, loss and gradients in float32 jax.numpy.

Written from the published ``config.json`` (allenai/Olmo-Hybrid-7B, the
catalog row) and the layer equations of its family (Olmo 2 / Olmo 3 blocks,
FLA's ``GatedDeltaNet``). With ``D`` the hidden width, no biases anywhere,
an untied head and token embeddings only:

- ``norm(x) = x * rsqrt(mean(x^2) + eps) * w`` (``w`` starts at 1), over the
  last axis, for the block norms, the final norm and the QK norm.
- block ``l``: ``h = x + norm(mixer_l(x)); y = h + norm(ffn(h))``, the norms
  on the sublayers' outputs and none on their inputs (Olmo 2's "reordered
  norms"); ``layer_types[l]`` says which mixer. After the last block
  ``norm``, then ``lm_head``; mean next-token cross entropy.
- **Gated DeltaNet** (``H`` key heads and ``H`` value heads, widths ``d_k``,
  ``d_v``):
  1. ``q = u W_q``, ``k = u W_k`` ``[S, H, d_k]``; ``v = u W_v`` and the
     output gate ``z = u W_z`` ``[S, H, d_v]``; ``a = u W_a``, ``b = u W_b``
     ``[S, H]``.
  2. ``q, k, v <- silu(conv(q)), silu(conv(k)), silu(conv(v))``: each its
     own depthwise causal convolution of ``kernel`` taps over the flattened
     channels, shifted products, zeros to the left, no bias.
  3. ``beta = 2 sigmoid(b)`` (``linear_allow_neg_eigval``: a step's
     transition ``I - beta k k^T`` has the eigenvalue ``1 - beta`` in
     ``(-1, 1)``; ``sigmoid(b)`` without it), ``g = -exp(A_log) *
     softplus(a + dt_bias)``.
  4. ``q <- q / sqrt(sum q^2 + 1e-6) / sqrt(d_k)``, ``k <- k / sqrt(sum k^2 +
     1e-6)`` over a head.
  5. per head, ``S = 0`` (``d_k x d_v``); for ``t = 1..L``: ``S <- exp(g_t)
     S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``; ``o_t = S^T
     q_t``. A ``lax.scan`` over the positions, never chunked: the program's
     chunked form is held to the definition.
  6. ``y = (o * rsqrt(mean(o^2) + eps) * w_o) * silu(z)`` per head (one
     ``w_o`` of ``d_v`` for all heads), then ``W_out``.
- **Full attention**: ``q = norm(u W_q)``, ``k = norm(u W_k)``, each one norm
  over all of its lanes (``H x d``: Olmo 2's QK norm, not one a head),
  ``v = u W_v``; heads ``[S, H, d]``, no rotation (the row's ``rope_theta``
  is null); scores ``q k^T / sqrt(d)`` under an explicit causal ``[S, S]``
  mask; ``W_o``.
- **Feed-forward**: ``(silu(u W_gate) * (u W_up)) W_down``.

The chip's share (``arch``): ``H`` heads of every mixer and ``F`` columns of
every feed-forward block, of a layer that ``chips_sharing_a_layer`` chips
share in the deployment (Megatron's split: q, k, v, z, a, b, gate and up by
columns, out, o and down by rows). The deployment all-reduces two things a
layer across those chips: the row-parallel products' partial sums and the
QK norm's sum of squares. Both are left out, here as in the program: the
share's partial sums enter the post-sublayer norms as they are, and the QK
norm's mean runs over the share's own ``H x d`` lanes. The weights have the
share's shapes, so nothing here names the share.

ASSUMED (the config row has no key for these; they follow the family's
published implementations, and the configuration file lists them under
``assumed``): the reordered norms, the whole-width QK norm, no rotation from
``rope_theta: null``, FLA's gated output norm and its ``1 / sqrt(d_k)``, the
separate convolutions of q, k and v without bias, ``A_log`` and
``dt_bias``.

Nothing here imports the program under test. Other departures, none of
which changes a value:

- the parameters arrive as a plain dict of this module's own names; the
  family file maps the program's leaves onto it (the program fuses ``[W_q |
  W_k | W_v | W_z]`` into one projection, ``[W_b | W_a]`` into another and
  the three convolutions into one over the concatenated channels);
- labels are given (the caller shifts the tokens);
- with ``remat=True`` each block is under ``jax.checkpoint``, attention runs
  a head at a time (``lax.map``, each head checkpointed: 8,192 x 8,192
  float32 scores are 268 MB), and the scan of step 5 is cut into segments of
  ``SEGMENT`` positions, each checkpointed, so that its backward pass holds
  a state a segment and the positions of the segment at hand. It recomputes,
  it does not approximate: the steps are the same steps.
- ``compare.py`` hands the architecture over under the keyword ``n_head``:
  here the dict ``arch`` documented at ``loss``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PARAM_NAMES = ("embed", "blocks", "norm_g", "lm_head")
BLOCK_PARAM_NAMES = ("post_mixer_norm_g", "post_ffn_norm_g", "gate_w",
                     "up_w", "down_w")
LINEAR_PARAM_NAMES = ("q_w", "k_w", "v_w", "z_w", "b_w", "a_w", "conv_q_w",
                      "conv_k_w", "conv_v_w", "a_log", "dt_bias",
                      "out_norm_g", "out_w")
FULL_PARAM_NAMES = ("q_w", "k_w", "v_w", "q_norm_g", "k_norm_g", "o_w")
SEGMENT = 64          # positions a checkpointed segment of the scan


def _mm(a, b):
    """Every product against a parameter goes through here."""
    return a @ b


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def step_size(b, arch):
    """Step 3's ``beta``."""
    beta = jax.nn.sigmoid(b)
    return 2.0 * beta if arch["allow_neg_eigval"] else beta


def ffn(u, p):
    return _mm(silu(_mm(u, p["gate_w"])) * _mm(u, p["up_w"]), p["down_w"])


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
    """Step 5. q, k ``[b, s, H, d_k]``, v ``[b, s, H, d_v]``, g, beta ``[b,
    s, H]``; returns o ``[b, s, H, d_v]``."""
    b, seq, h, dk = q.shape

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
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
    h, dk, dv = lin["heads"], lin["d_k"], lin["d_v"]
    q = silu(causal_conv(_mm(u, p["q_w"]), p["conv_q_w"]))
    k = silu(causal_conv(_mm(u, p["k_w"]), p["conv_k_w"]))
    v = silu(causal_conv(_mm(u, p["v_w"]), p["conv_v_w"]))
    q, k = (jnp.reshape(x, (b, s, h, dk)) for x in (q, k))
    v = jnp.reshape(v, (b, s, h, dv))
    z = jnp.reshape(_mm(u, p["z_w"]), (b, s, h, dv))
    beta = step_size(_mm(u, p["b_w"]), arch)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(_mm(u, p["a_w"])
                                               + p["dt_bias"])
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    o = delta_rule(q, k, v, g, beta, remat)
    y = norm(o, p["out_norm_g"], eps) * silu(z)
    return _mm(jnp.reshape(y, (b, s, h * dv)), p["out_w"])


# -- full attention ----------------------------------------------------------

def attention(u, p, arch, eps, remat):
    b, s, _ = u.shape
    d, kv, heads = arch["head_dim"], arch["kv_heads"], arch["heads"]
    group = heads // kv
    q = jnp.reshape(norm(_mm(u, p["q_w"]), p["q_norm_g"], eps),
                    (b, s, heads, d))
    k = jnp.reshape(norm(_mm(u, p["k_w"]), p["k_norm_g"], eps), (b, s, kv, d))
    v = jnp.reshape(_mm(u, p["v_w"]), (b, s, kv, d))
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
    return _mm(jnp.reshape(jnp.moveaxis(o, 0, 2), (b, s, heads * d)),
               p["o_w"])


# -- the model ---------------------------------------------------------------

def block(x, p, kind, arch, eps, remat):
    mixer = gated_delta_net if kind == "linear_attention" else attention
    h = x + norm(mixer(x, p, arch, eps, remat), p["post_mixer_norm_g"], eps)
    return h + norm(ffn(h, p), p["post_ffn_norm_g"], eps)


def hidden_states(params, ids, arch, eps, remat=False):
    """The final norm's output."""
    x = params["embed"][ids]
    for p, kind in zip(params["blocks"], arch["layers"]):
        def blk(x_, p_, kind=kind):
            return block(x_, p_, kind, arch, eps, remat)
        x = (jax.checkpoint(blk) if remat else blk)(x, p)
    return norm(x, params["norm_g"], eps)


def loss(params, ids, labels, *, n_head, eps=1e-6, remat=False):
    """Mean next-token cross entropy of ``ids`` (rows, seq) against
    ``labels`` (rows, seq); ``params`` holds float32 leaves.

    ``n_head`` is the architecture (``arch``): ``layers`` (a list of
    ``"linear_attention"`` / ``"full_attention"``), ``heads``, ``kv_heads``,
    ``head_dim``, ``linear`` (``heads``, ``d_k``, ``d_v``) and
    ``allow_neg_eigval``."""
    arch = n_head
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, ids, arch, eps, remat)
        logits = _mm(x, params["lm_head"])                  # (rows, s, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked)
