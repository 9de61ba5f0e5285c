"""Plain Nemotron-H: forward, loss and gradients in float32 jax.numpy.

Written from the published ``config.json`` (nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16, the catalog row, ``model_type: nemotron_h``) and the equations
of ISSUE 39. With ``D`` the hidden width, no biases but the convolution's,
an untied head and token embeddings only:

- ``norm(x) = x * rsqrt(mean(x^2) + eps) * w`` (plain weights starting at
  1), eps ``layer_norm_epsilon``.
- layer ``l`` is ONE residual sublayer, ``y = x + sublayer_l(norm(x))``,
  its kind the ``l``-th character of ``hybrid_override_pattern``: ``M`` a
  Mamba-2 mixer, ``E`` an expert layer, ``*`` attention. After the last
  layer ``norm``, then ``lm_head``; mean next-token cross entropy.
- **Mamba-2** (``H`` heads of width ``P``, ``G`` groups of ``B`` and ``C``
  of width ``N``, ``I = H P``):
  1. ``u W_in`` as ``[z (I) | x (I) | B (G N) | C (G N) | dt (H)]``;
  2. ``[x | B | C] <- silu(conv([x | B | C]) + b_conv)``: depthwise,
     causal, ``kernel`` taps as shifted products, zeros to the left;
  3. ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  4. per head ``h`` (group ``g = h // (H / G)``), ``S = 0`` (``P x N``);
     for ``t = 1..L``: ``S <- exp(dt_t A_h) S + dt_t x_t B_t^T``; ``y_t =
     S C_t + D_h x_t``. A ``lax.scan`` over the positions, in segments of
     ``SEGMENT``: the program's chunked form is held to the definition;
  5. ``y <- y * silu(z)``, then the RMSNorm over ``G`` groups of ``I / G``
     lanes, times ``w_n``; then ``W_out``.
- **Attention**: ``u W_q`` as ``[S, H_q, d]``, ``k, v`` as ``[S, KV, d]``;
  NO rotation and no positional encoding; scores ``q k^T / sqrt(d)`` under
  an explicit causal ``[S, S]`` mask; query head ``i`` reads KV head ``i //
  (H_q / KV)``; ``W_o``.
- **Expert layer**: ``s = sigmoid(u W_r)`` over all experts in float32; the
  ``top_k`` experts of ``s + bias`` are chosen (``e_score_correction_bias``,
  handed in, zeros unless the caller says otherwise) and weighted by their
  ``s`` over the chosen ``s``'s sum, times ``routed_scaling_factor``;
  ``sum over e chosen and held here of w_e expert_e(u)`` with ``expert(u) =
  relu(u W_up)^2 W_down``, as a dense loop (``lax.scan``) over the held
  experts; ``+ shared(u)``, the same form.

ASSUMED (the config row has no key for these; they follow the published
implementation, and the configuration file lists them under ``assumed``):
no rotation in attention (``rope_theta`` is not read), the gate before the
grouped norm, the convolution's bias (``use_conv_bias``), no auxiliary loss.

Nothing here imports the program under test. Other departures, none of
which changes a value:

- the parameters arrive as a plain dict of this module's own names; the
  family file maps the program's leaves onto it (both keep ``in_proj``'s
  columns in the published order);
- the layer holds ``held = (first, count)`` of the experts, as the
  program's layer does; what the absent experts would add is left out;
- labels are given (the caller shifts the tokens);
- the scan of step 4 is cut into segments of 128 positions (the published
  ``chunk_size``), each a ``lax.scan`` of a step a position; with
  ``remat=True`` each segment, each block, each query head of attention
  (``lax.map``) and each held expert's part of the sum is under
  ``jax.checkpoint``, so that the backward pass holds a state a segment and
  the 128 of the segment at hand, not 8,192. It recomputes, it does not
  approximate: the steps are the same steps. ``_entering`` is what a
  segment's first step starts from: the state the last one left;
- ``compare.py`` hands the architecture over under the keyword ``n_head``:
  here the dict ``arch`` documented at ``loss``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SEGMENT = 128         # positions a segment of the scan


def _mm(a, b):
    """Every product against a parameter goes through here."""
    return a @ b


def _entering(s):
    """The state a segment of the scan starts from, given the one the last
    segment left: that state itself."""
    return s


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# -- Mamba-2 --------------------------------------------------------------------

def causal_conv(x, w, b):
    """x ``[b, s, c]``, w ``[c, kernel]``, b ``[c]``: ``out[t] = b + sum_j
    w[:, j] x[t - (kernel - 1) + j]``, zeros before position 0."""
    kernel, seq = w.shape[-1], x.shape[1]
    out = jnp.zeros_like(x) + b
    for j in range(kernel):
        shift = kernel - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :shift]), x[:, :seq - shift]], axis=1)
        out = out + shifted * w[:, j]
    return out


def scan(x, dt, a, b, c, remat):
    """Step 4 without ``D``. x ``[b, s, H, P]``, dt and a (``= A``) ``[b,
    s, H]`` and ``[H]``, b, c ``[b, s, H, N]`` (a head's group's); returns
    y ``[b, s, H, P]``."""
    batch, seq, h, p = x.shape

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    def segment(s, xs_):
        return jax.lax.scan(step, _entering(s), xs_)

    if remat:
        segment = jax.checkpoint(segment)
    size = min(seq, SEGMENT)
    pad = -seq % size
    xs = tuple(jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
               for v in (x, dt, b, c))
    xs = tuple(jnp.reshape(jnp.moveaxis(v, 1, 0),
                           (-1, size) + (v.shape[0],) + v.shape[2:])
               for v in xs)
    s0 = jnp.zeros((batch, h, p, b.shape[-1]), jnp.float32)
    y = jax.lax.scan(segment, s0, xs)[1]
    return jnp.moveaxis(jnp.reshape(y, (-1,) + y.shape[2:]), 0, 1)[:, :seq]


def mamba(u, p, arch, eps, remat):
    bsz, s, _ = u.shape
    m = arch["mamba"]
    h, d, g, n = m["heads"], m["head_dim"], m["groups"], m["state"]
    inner = h * d
    proj = _mm(u, p["in_w"])
    z, xbc, dt = proj[..., :inner], proj[..., inner:-h], proj[..., -h:]
    xbc = silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = jnp.reshape(xbc[..., :inner], (bsz, s, h, d))
    b = jnp.reshape(xbc[..., inner:inner + g * n], (bsz, s, g, n))
    c = jnp.reshape(xbc[..., inner + g * n:], (bsz, s, g, n))
    b, c = jnp.repeat(b, h // g, axis=2), jnp.repeat(c, h // g, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = scan(x, dt, -jnp.exp(p["a_log"]), b, c, remat) \
        + p["d"][:, None] * x
    y = gated_norm(jnp.reshape(y, (bsz, s, inner)), z, p["mamba_norm_g"], g,
                   eps)
    return _mm(y, p["out_w"])


def gated_norm(y, z, w, groups, eps):
    """Step 5's norm: ``y * silu(z)``, then the RMSNorm over ``groups``
    groups of lanes, times ``w``."""
    y = y * silu(z)
    grouped = jnp.reshape(y, y.shape[:-1] + (groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return jnp.reshape(grouped, y.shape) * w


# -- attention ----------------------------------------------------------------

def attention(u, p, arch, remat):
    b, s, _ = u.shape
    d, kv, heads = arch["head_dim"], arch["kv_heads"], arch["heads"]
    group = heads // kv
    q = jnp.reshape(_mm(u, p["q_w"]), (b, s, heads, d))
    k = jnp.reshape(_mm(u, p["k_w"]), (b, s, kv, d))
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


# -- the expert layer ---------------------------------------------------------

def route(u, router_w, arch, bias):
    """``(ids [.., k], weights [.., k])``: sigmoid scores, the ``top_k`` of
    ``scores + bias`` chosen, their scores over their sum times the
    scaling factor."""
    scores = jax.nn.sigmoid(_mm(u, router_w))
    _, ids = jax.lax.top_k(scores + bias, arch["top_k"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, top / jnp.sum(top, axis=-1, keepdims=True) \
        * arch["routed_scaling_factor"]


def moe(u, p, arch, bias, remat=False):
    ids, weights = route(u, p["router_w"], arch, bias)
    first, count = arch["held"]
    out = _mm(relu2(_mm(u, p["shared_up_w"])), p["shared_down_w"])

    def part(expert):
        e, up_w, down_w = expert
        # the weight this expert has for each token, 0 where not chosen
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return w[..., None] * _mm(relu2(_mm(u, up_w)), down_w)

    if remat:
        part = jax.checkpoint(part)

    def add_expert(out, expert):
        return out + part(expert), None

    # every held expert on every token: a loop, compiled once
    out, _ = jax.lax.scan(add_expert, out, (
        jnp.arange(count), p["experts_up_w"], p["experts_down_w"]))
    return out, ids


def block(x, p, kind, arch, eps, remat, bias):
    """``(y, experts chosen or None)``."""
    u = norm(x, p["norm_g"], eps)
    if kind == "mamba":
        return x + mamba(u, p, arch, eps, remat), None
    if kind == "attention":
        return x + attention(u, p, arch, remat), None
    out, chosen = moe(u, p, arch, bias, remat)
    return x + out, chosen


def hidden_states(params, ids, arch, eps, remat=False):
    """The final norm's output and, per expert layer, the experts chosen."""
    x = params["embed"][ids]
    biases = iter(arch["selection_bias"]
                  or [jnp.zeros((arch["router_width"],), jnp.float32)]
                  * arch["layers"].count("moe"))
    chosen = []
    for p, kind in zip(params["blocks"], arch["layers"]):
        bias = next(biases) if kind == "moe" else None

        def blk(x_, p_, bias_, kind=kind):
            return block(x_, p_, kind, arch, eps, remat, bias_)
        x, ids_l = (jax.checkpoint(blk) if remat else blk)(x, p, bias)
        if ids_l is not None:
            chosen.append(ids_l)
    return norm(x, params["norm_g"], eps), chosen


def loss(params, ids, labels, *, n_head, eps=1e-5, remat=False):
    """Mean next-token cross entropy of ``ids`` (rows, seq) against
    ``labels`` (rows, seq); ``params`` holds float32 leaves.

    ``n_head`` is the architecture (``arch``): ``layers`` (a list of
    ``"mamba"`` / ``"moe"`` / ``"attention"``), ``mamba`` (``heads``,
    ``head_dim``, ``groups``, ``state``, ``conv_kernel``), ``heads``,
    ``kv_heads``, ``head_dim``, ``top_k``, ``router_width``,
    ``routed_scaling_factor``, ``held`` (first, count) and
    ``selection_bias`` (a list of ``[router_width]`` float32 arrays, one an
    expert layer, or None for zeros)."""
    arch = n_head
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(params, ids, arch, eps, remat)
        logits = _mm(x, params["lm_head"])                  # (rows, s, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked)


def chosen_experts(params, ids, *, n_head, eps=1e-5):
    """The expert ids ``[rows, seq, k]`` each expert layer's router
    chose."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, ids, n_head, eps)[1]
