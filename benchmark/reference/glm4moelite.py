"""Plain GLM-4.7-Flash (``glm4_moe_lite``): forward, both loss terms and
gradients in float32 jax.numpy.

Written from the published ``config.json`` (zai-org/GLM-4.7-Flash, the
catalog row) and the equations of ISSUE 37, which are DeepSeek-V2's
multi-head latent attention, DeepSeek-V3's bias-corrected router and
DeepSeek-V3's multi-token prediction. With ``D`` the hidden width, ``H``
heads, no biases anywhere, plain RMSNorm weights (``x * rsqrt(mean(x^2) +
eps) * g``), an untied head and token embeddings only:

- block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; after the
  last block ``N = RMSNorm(y)``, then ``lm_head``.
- ``Attn(u)``: ``c_q = RMSNorm(u W_dq)`` (``r_q`` wide); ``c_q W_uq`` as ``[S,
  H, d_nope + d_rope]``, a head's columns ``[q_nope | q_rope]`` (the
  published order); ``u W_dkv`` as ``[c_kv (r_kv) | k_r (d_rope)]``; ``c_kv
  <- RMSNorm(c_kv)``; ``c_kv W_ukv`` as ``[S, H, d_nope + d_v]``, a head's
  columns ``[k_nope | v]``. RoPE in the rotate-half convention over all
  ``d_rope`` lanes of ``q_rope`` (every head) and of ``k_r`` (ONE head,
  rotated once and read by all ``H`` key heads), theta as published, no
  scaling. ``q_h = [q_nope_h | q_rope_h]``, ``k_h = [k_nope_h | k_r]``;
  scores ``q_h k_h^T / sqrt(d_nope + d_rope)`` under an explicit causal
  ``[S, S]`` mask; ``o_h = softmax(scores) v_h``; ``concat_h(o_h) W_o``.
- ``FFN`` dense (the leading layer): ``(silu(u W_gate) * (u W_up)) W_down``.
  Sparse: ``shared(u) + sum over e chosen and held here of w_e expert_e(u)``,
  every expert the same gated SiLU FFN, as a dense loop (``lax.scan``) over
  the held experts. ``s = sigmoid(u W_r)`` over all experts; ``chosen =
  top_k(s + b)``, ``b`` the layer's ``e_score_correction_bias`` (no
  gradient: a constant here, handed over in ``arch["selection_bias"]``);
  ``w = s[chosen] / sum(s[chosen]) * routed_scaling_factor``.
- multi-token prediction, one module: ``z_i = [RMSNorm_e(embed[t_{i+1}]) |
  RMSNorm_h(N_i)] W_eh``; one more sparse block over ``z`` (causal over
  ``i``); ``logits2_i = RMSNorm_f2(block(z))_i lm_head``. ``embed`` and
  ``lm_head`` are the trunk's.
- ``loss = mean_i CE(N_i lm_head, t_{i+1}) + lambda * mean_{i <= L-2}
  CE(logits2_i, t_{i+2})``.

ASSUMED (the config row has ranks and widths, no equations; the
configuration file lists these under ``assumed``): all of the above where
the row is silent, in particular rotate-half pairing (``rope_interleave``
is not in the row; against interleaved pairs it is a fixed permutation of
``W_uq``'s and ``W_dkv``'s rotary columns), the module reading the trunk's
output AFTER the final norm in the order ``[embedding | hidden]``, lambda,
the bias at zero, no ``1e-20`` in the normalising sum, no group limit
(``n_group = topk_group = 1``).

Nothing here imports the program under test. Other departures, none of
which changes a value:

- the parameters arrive as a plain dict of this module's own names; the
  multi-token-prediction module is the block after the last (as the
  published checkpoint has it, layer ``num_hidden_layers``), with
  ``enorm_g``, ``hnorm_g``, ``eh_w`` and ``mtp_norm_g`` beside its block's
  tensors. The family file maps the program's leaves onto it (and permutes
  ``q_b_w``'s columns, which the program keeps ``[rope | nope]`` a head);
- the layer holds ``held = (first, count)`` of the experts, as the program's
  layer does; what the absent experts would add is left out;
- labels are given (``labels[i] = t_{i+1}``; the caller shifts the tokens);
- with ``remat=True`` each block is under ``jax.checkpoint``, attention runs
  a head at a time (``lax.map``, each head checkpointed: 4,096 x 4,096
  float32 scores are 67 MB) and each held expert's part of the sum is
  checkpointed. It recomputes, it does not approximate;
- ``compare.py`` hands the architecture over under the keyword ``n_head``:
  here the dict ``arch`` documented at ``loss``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PARAM_NAMES = ("embed", "blocks", "norm_g", "lm_head")
ATTENTION_PARAM_NAMES = ("q_a_w", "q_a_norm_g", "q_b_w", "kv_a_w",
                         "kv_a_norm_g", "kv_b_w", "o_w")
BLOCK_PARAM_NAMES = ("norm1_g", "norm2_g") + ATTENTION_PARAM_NAMES
DENSE_PARAM_NAMES = ("gate_w", "up_w", "down_w")
SPARSE_PARAM_NAMES = ("router_w", "shared_gate_w", "shared_up_w",
                      "shared_down_w", "experts_gate_w", "experts_up_w",
                      "experts_down_w")
MTP_PARAM_NAMES = ("enorm_g", "hnorm_g", "eh_w", "mtp_norm_g")


def _mm(a, b):
    """Every product against a parameter goes through here."""
    return a @ b


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gated_ffn(u, gate_w, up_w, down_w):
    return _mm(silu(_mm(u, gate_w)) * _mm(u, up_w), down_w)


def apply_rope(x, theta):
    """x ``[b, s, heads, r]``; rotate-half over all ``r`` lanes."""
    s, r = x.shape[1], x.shape[-1]
    inv_freq = float(theta) ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [s, r]
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    half = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], axis=-1)
    return x * cos + half * sin


def latent_norm(c, g, eps):
    """The RMSNorm on a latent (``c_q``, ``c_kv``), between two products."""
    return rms_norm(c, g, eps)


def attention(u, p, arch, eps, remat):
    b, s, _ = u.shape
    h, dn, dr, dv = arch["heads"], arch["d_nope"], arch["d_rope"], arch["d_v"]
    c_q = latent_norm(_mm(u, p["q_a_w"]), p["q_a_norm_g"], eps)
    q = jnp.reshape(_mm(c_q, p["q_b_w"]), (b, s, h, dn + dr))
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], arch["rope_theta"])], axis=-1)
    kv_a = _mm(u, p["kv_a_w"])
    c_kv = latent_norm(kv_a[..., :arch["kv_lora_rank"]], p["kv_a_norm_g"],
                       eps)
    # one rotary key head, rotated once, read by every head below
    k_r = apply_rope(kv_a[..., None, arch["kv_lora_rank"]:],
                     arch["rope_theta"])[:, :, 0]               # [b, s, dr]
    kv = jnp.reshape(_mm(c_kv, p["kv_b_w"]), (b, s, h, dn + dv))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(args):
        qh, kvh = args                        # [b, s, dn + dr], [.., dn + dv]
        kh = jnp.concatenate([kvh[..., :dn], k_r], axis=-1)
        scores = jnp.einsum("bsd,btd->bst", qh, kh) / jnp.sqrt(
            jnp.float32(dn + dr))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bst,btd->bsd", jax.nn.softmax(scores, axis=-1),
                          kvh[..., dn:])

    if remat:
        one_head = jax.checkpoint(one_head)
    o = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(kv, 2, 0)))
    return _mm(jnp.reshape(jnp.moveaxis(o, 0, 2), (b, s, h * dv)), p["o_w"])


def route(u, router_w, bias, arch):
    """``(ids [.., k], weights [.., k])``: the bias chooses, the scores
    weigh."""
    scores = jax.nn.sigmoid(_mm(u, router_w))
    _, ids = jax.lax.top_k(scores + bias, arch["top_k"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, arch["routed_scaling_factor"] * top / jnp.sum(
        top, axis=-1, keepdims=True)


def moe(u, p, bias, arch, remat=False):
    ids, weights = route(u, p["router_w"], bias, arch)
    first, count = arch["held"]
    out = gated_ffn(u, p["shared_gate_w"], p["shared_up_w"],
                    p["shared_down_w"])

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


def block(x, p, bias, arch, eps, remat):
    """``(y, experts chosen)``; sparse where the block has a router, and
    the second is None in a dense layer."""
    x = x + attention(rms_norm(x, p["norm1_g"], eps), p, arch, eps, remat)
    u = rms_norm(x, p["norm2_g"], eps)
    if "router_w" in p:
        out, chosen = moe(u, p, bias, arch, remat)
        return x + out, chosen
    return x + gated_ffn(u, p["gate_w"], p["up_w"], p["down_w"]), None


def _biases(params, arch):
    """A bias a block, zeros where none was handed over."""
    given = iter(arch.get("selection_bias") or ())
    width = arch["router_width"]
    return [jnp.asarray(next(given, jnp.zeros(width)), jnp.float32)
            if "router_w" in p else None for p in params["blocks"]]


def _run(blk, x, p, bias, arch, eps, remat):
    def run(x_, p_):
        return blk(x_, p_, bias, arch, eps, remat)
    return (jax.checkpoint(run) if remat else run)(x, p)


def hidden_states(params, ids, labels, arch, eps, remat=False):
    """``(N, z, chosen)``: the trunk's normed output, the multi-token-
    prediction module's normed output (None without a module) and, per
    sparse block (the module's last), the experts chosen."""
    n_mtp = arch.get("mtp_layers", 0)
    blocks = params["blocks"]
    trunk = blocks[:len(blocks) - n_mtp]
    biases = _biases(params, arch)
    x = params["embed"][ids]
    chosen = []
    for p, bias in zip(trunk, biases):
        x, ids_l = _run(block, x, p, bias, arch, eps, remat)
        if ids_l is not None:
            chosen.append(ids_l)
    hidden = rms_norm(x, params["norm_g"], eps)
    if not n_mtp:
        return hidden, None, chosen
    p = blocks[-1]
    z = _mm(jnp.concatenate(
        [rms_norm(params["embed"][labels], p["enorm_g"], eps),
         rms_norm(hidden, p["hnorm_g"], eps)], axis=-1), p["eh_w"])
    z, ids_l = _run(block, z, p, biases[-1], arch, eps, remat)
    chosen.append(ids_l)
    return hidden, rms_norm(z, p["mtp_norm_g"], eps), chosen


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss_terms(params, ids, labels, *, n_head, eps=1e-5, remat=False):
    """``(main, ahead)``: the next-token term over all ``L`` positions and
    the token-after-next term over the first ``L - 1`` (None without a
    module), both unweighted."""
    arch = n_head
    with jax.default_matmul_precision("highest"):
        hidden, z, _ = hidden_states(params, ids, labels, arch, eps, remat)
        main = jnp.mean(_cross_entropy(_mm(hidden, params["lm_head"]),
                                       labels))
        if z is None:
            return main, None
        ahead = jnp.mean(_cross_entropy(
            _mm(z[:, :-1], params["lm_head"]), labels[:, 1:]))
        return main, ahead


def loss(params, ids, labels, *, n_head, eps=1e-5, remat=False):
    """``main + mtp_loss_weight * ahead`` of ``ids`` (rows, seq) against
    ``labels`` (rows, seq); ``params`` holds float32 leaves.

    ``n_head`` is the architecture (``arch``): ``heads``, ``q_lora_rank``,
    ``kv_lora_rank``, ``d_nope``, ``d_rope``, ``d_v``, ``rope_theta``,
    ``top_k``, ``router_width``, ``routed_scaling_factor``, ``held`` (first,
    count), ``mtp_layers`` (0 or 1: the last block of ``params["blocks"]``
    is then the module's), ``mtp_loss_weight`` and ``selection_bias`` (a
    list, one ``(router_width,)`` array a sparse block in order, or None
    for zeros). Whether a block is dense or sparse is read from its
    tensors."""
    main, ahead = loss_terms(params, ids, labels, n_head=n_head, eps=eps,
                             remat=remat)
    if ahead is None:
        return main
    return main + n_head["mtp_loss_weight"] * ahead


def chosen_experts(params, ids, labels, *, n_head, eps=1e-5):
    """The expert ids ``[rows, seq, k]`` each sparse block's router chose,
    the module's block last."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, ids, labels, n_head, eps)[2]
