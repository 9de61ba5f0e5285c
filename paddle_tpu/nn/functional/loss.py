"""Loss functionals (reference: python/paddle/nn/functional/loss.py;
operators/softmax_with_cross_entropy_op.*, bce_loss_op.*, …)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, name=None):
    """Reference: softmax_with_cross_entropy_op.cc — numerically-stable
    log-softmax + NLL in one fused XLA graph."""
    if use_softmax:
        logp = None if not soft_label else jax.nn.log_softmax(input, axis=axis)
    else:
        logp = jnp.log(jnp.clip(input, 1e-30, None))
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis)
    else:
        label = label.astype(jnp.int32)
        lbl = jnp.squeeze(label, axis=axis) if label.ndim == input.ndim else label
        valid = (lbl != ignore_index)
        safe = jnp.where(valid, lbl, 0)
        idx = safe[..., None] if axis in (-1, input.ndim - 1) \
            else jnp.expand_dims(safe, axis)
        if logp is None:
            # Hard-label fast path: loss = lse(logits) - logit[label]. Avoids
            # materializing the full log-prob tensor — for an LM head this is
            # (batch, seq, vocab) of HBM traffic saved. lse accumulates in
            # fp32 for bf16 stability.
            lse = jax.nn.logsumexp(input.astype(jnp.float32), axis=axis)
            picked = jnp.take_along_axis(input, idx, axis=axis) \
                .astype(jnp.float32)
            loss = lse - jnp.squeeze(picked, axis=axis)
        else:
            picked = jnp.take_along_axis(logp, idx, axis=axis)
            loss = -jnp.squeeze(picked, axis=axis)
        if weight is not None:
            w = jnp.take(weight, safe)
            loss = loss * w
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            denom = jnp.sum(jnp.where(valid, jnp.take(weight, safe) if weight is not None
                                      else jnp.ones_like(loss), 0.0))
            return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
    return _reduce(loss, reduction)


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    loss = jnp.expand_dims(loss, axis)
    if return_softmax:
        return loss, jax.nn.softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    lbl = label.astype(jnp.int32)
    valid = lbl != ignore_index
    safe = jnp.where(valid, lbl, 0)
    picked = -jnp.take_along_axis(input, safe[..., None], axis=-1)[..., 0] \
        if input.ndim == 2 else -jnp.take_along_axis(input, safe[:, None], axis=1)[:, 0]
    if weight is not None:
        picked = picked * jnp.take(weight, safe)
    picked = jnp.where(valid, picked, 0.0)
    if reduction == "mean":
        denom = jnp.sum(jnp.where(valid, jnp.take(weight, safe) if weight is not None
                                  else jnp.ones_like(picked), 0.0))
        return jnp.sum(picked) / jnp.maximum(denom, 1e-12)
    return _reduce(picked, reduction)


def mse_loss(input, label, reduction="mean", name=None):
    return _reduce(jnp.square(input - label), reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return _reduce(jnp.abs(input - label), reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta, diff - 0.5 * delta)
    # paddle multiplies by delta
    return _reduce(loss * delta, reduction)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.clip(input, eps, None)) +
             (1.0 - label) * jnp.log(jnp.clip(1.0 - input, eps, None)))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_w = (pos_weight - 1.0) * label + 1.0
        loss = (1.0 - label) * logit + log_w * (
            jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val)
    else:
        loss = (1.0 - label) * logit + jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def kl_div(input, label, reduction="mean", name=None):
    loss = label * (jnp.log(jnp.clip(label, 1e-12, None)) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce(loss, reduction)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    loss = jnp.clip(-label * (input - other) + margin, 0.0, None)
    return _reduce(loss, reduction)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    loss = jnp.where(label == 1.0, input, jnp.clip(margin - input, 0.0, None))
    return _reduce(loss, reduction)


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    from .common import cosine_similarity
    cos = cosine_similarity(input1, input2, axis=1)
    loss = jnp.where(label == 1, 1.0 - cos, jnp.clip(cos - margin, 0.0, None))
    return _reduce(loss, reduction)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0, epsilon=1e-6,
                        swap=False, reduction="mean", name=None):
    def dist(a, b):
        return jnp.sum(jnp.abs(a - b + epsilon) ** p, axis=-1) ** (1.0 / p)
    dp = dist(input, positive)
    dn = dist(input, negative)
    if swap:
        dn = jnp.minimum(dn, dist(positive, negative))
    return _reduce(jnp.clip(dp - dn + margin, 0.0, None), reduction)


def log_loss(input, label, epsilon=1e-4, name=None):
    return -(label * jnp.log(input + epsilon) +
             (1.0 - label) * jnp.log(1.0 - input + epsilon))


def square_error_cost(input, label):
    return jnp.square(input - label)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    p = jax.nn.sigmoid(logit)
    ce = binary_cross_entropy_with_logits(logit, label, reduction="none")
    p_t = p * label + (1 - p) * (1 - label)
    a_t = alpha * label + (1 - alpha) * (1 - label)
    loss = a_t * ((1 - p_t) ** gamma) * ce
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via the standard forward algorithm in log space, scan over time.

    Reference: operators/warpctc_op.* (wraps warp-ctc). Implemented natively
    with lax.scan — static shapes, TPU-friendly.
    log_probs: (T, B, C) log-softmax outputs. labels: (B, L) padded.
    """
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    NEG = -1e30
    blanks = jnp.full((B, L + 1), blank, dtype=labels.dtype)
    ext = jnp.zeros((B, S), dtype=labels.dtype)
    ext = ext.at[:, 1::2].set(labels)
    ext = ext.at[:, 0::2].set(blanks)
    # allow skip when ext[s] != ext[s-2] and ext[s] != blank
    can_skip = jnp.concatenate(
        [jnp.zeros((B, 2), bool),
         (ext[:, 2:] != ext[:, :-2]) & (ext[:, 2:] != blank)], axis=1)

    alpha0 = jnp.full((B, S), NEG)
    alpha0 = alpha0.at[:, 0].set(log_probs[0, jnp.arange(B), ext[:, 0]])
    alpha0 = alpha0.at[:, 1].set(jnp.where(L > 0, log_probs[0, jnp.arange(B), ext[:, 1]], NEG))

    def step(alpha, logp_t):
        emit = jnp.take_along_axis(logp_t, ext, axis=1)  # (B, S)
        prev1 = jnp.concatenate([jnp.full((B, 1), NEG), alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate([jnp.full((B, 2), NEG), alpha[:, :-2]], axis=1)
        prev2 = jnp.where(can_skip, prev2, NEG)
        new = jnp.logaddexp(jnp.logaddexp(alpha, prev1), prev2) + emit
        return new, new

    _, alphas = jax.lax.scan(step, alpha0, log_probs[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T, B, S)
    t_idx = jnp.clip(input_lengths - 1, 0, T - 1)
    last = alphas[t_idx, jnp.arange(B)]  # (B, S)
    s_last = 2 * label_lengths  # blank after last label
    s_prev = jnp.clip(2 * label_lengths - 1, 0, S - 1)
    ll = jnp.logaddexp(
        jnp.take_along_axis(last, s_last[:, None], axis=1)[:, 0],
        jnp.take_along_axis(last, s_prev[:, None], axis=1)[:, 0])
    loss = -ll
    if norm_by_times:
        loss = loss / jnp.maximum(input_lengths.astype(loss.dtype), 1.0)
    if reduction == "mean":
        return jnp.mean(loss / jnp.maximum(label_lengths.astype(loss.dtype), 1.0))
    return _reduce(loss, reduction)


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(anchor), axis=1)) +
                    jnp.mean(jnp.sum(jnp.square(positive), axis=1))) * 0.25
    sim = anchor @ positive.T
    labels = jnp.reshape(labels, (-1,))
    same = (labels[:, None] == labels[None, :]).astype(sim.dtype)
    tgt = same / jnp.sum(same, axis=1, keepdims=True)
    xe = -jnp.sum(tgt * jax.nn.log_softmax(sim, axis=1), axis=1)
    return jnp.mean(xe) + reg


def mae_loss(input, label, reduction="mean"):
    return l1_loss(input, label, reduction)


def dice_loss(input, label, epsilon=1e-5, name=None):
    """Dice loss for segmentation (reference nn/functional/loss.py dice_loss):
    input (N, ..., C) class probabilities, label (N, ..., 1) int labels."""
    label = jnp.squeeze(label, axis=-1)
    onehot = jax.nn.one_hot(label, input.shape[-1], dtype=input.dtype)
    reduce_dims = tuple(range(1, input.ndim))
    intersect = jnp.sum(input * onehot, axis=reduce_dims)
    denom = jnp.sum(input, axis=reduce_dims) + jnp.sum(onehot, axis=reduce_dims)
    dice = (2 * intersect + epsilon) / (denom + epsilon)
    return jnp.mean(1 - dice)


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False, name=None):
    """Hierarchical sigmoid loss (reference nn/functional/loss.py
    hsigmoid_loss; operators/hierarchical_sigmoid_op.cc). Default tree =
    heap-numbered complete binary tree over ``num_classes`` leaves: internal
    nodes 1..num_classes-1 map to rows of ``weight`` (num_classes-1, D);
    custom trees come in via ``path_table``/``path_code``.
    On TPU the per-sample variable-length path is padded to max depth and
    masked (static shapes for XLA).
    """
    import numpy as _np
    x = jnp.asarray(input)
    weight = jnp.asarray(weight)
    if bias is not None:
        bias = jnp.asarray(bias)
    label = jnp.asarray(label).reshape(-1)
    if path_table is not None:
        table = jnp.asarray(path_table)
        code = jnp.asarray(path_code)
        node_ids = jnp.take(table, label, axis=0)        # (N, depth)
        codes = jnp.take(code, label, axis=0).astype(x.dtype)
        mask = (node_ids >= 0).astype(x.dtype)
        rows = jnp.maximum(node_ids, 0)
    else:
        depth = max(1, int(_np.ceil(_np.log2(max(2, num_classes)))))
        leaf = label + num_classes                        # heap leaf id
        nodes, codes_l = [], []
        node = leaf
        for _ in range(depth):
            codes_l.append((node % 2).astype(x.dtype))
            node = node // 2
            nodes.append(node)
        node_ids = jnp.stack(nodes, axis=1)               # ancestors, (N, depth)
        codes = jnp.stack(codes_l, axis=1)
        mask = (node_ids >= 1).astype(x.dtype)
        rows = jnp.maximum(node_ids - 1, 0)               # weight row index
    w = jnp.take(weight, rows, axis=0)                    # (N, depth, D)
    logits = jnp.einsum("nd,nkd->nk", x, w)
    if bias is not None:
        logits = logits + jnp.take(jnp.asarray(bias).reshape(-1), rows, axis=0)
    # reference clips pre_out to [-40, 40] (hierarchical_sigmoid_op.h:107)
    logits = jnp.clip(logits, -40.0, 40.0)
    # reference loss_j = softplus(z) - bit*z = -log sigmoid((2*bit-1) * z):
    # bit==1 is trained toward +inf (matrix_bit_code.h calc_bit +
    # hierarchical_sigmoid_op.h:112-115 Sum(scale=-1) + softplus row-sum)
    sign = 2.0 * codes - 1.0
    loss = -jax.nn.log_sigmoid(sign * logits) * mask
    return jnp.sum(loss, axis=1, keepdims=True)


def fused_linear_cross_entropy(hidden, weight, labels, ignore_index=-100,
                               chunk=8192, kernel="auto", interpret=None,
                               name=None):
    """LM-head projection + softmax cross entropy WITHOUT materializing
    the (tokens, vocab) logits tensor — mean fp32 loss over labels !=
    ``ignore_index``, gradients to hidden and weight.

    hidden: (..., H); weight: (H, V); labels: (...,) int targets.

    kernel selects the implementation:
      - ``"pallas"``: the fused Mosaic kernel
        (``ops/pallas/fused_ce.py``; interpret mode auto-selected
        off-TPU unless ``interpret`` says otherwise),
      - ``"chunked"``: the jnp online-logsumexp scan
        (``ops/chunked_ce.py``, ``chunk`` classes per step),
      - ``"auto"``: pallas on TPU, chunked elsewhere — the chunked
        route is counted as ``pallas_config_resolved_total{
        kernel="fused_ce", source="fallback"}``.
    """
    from ...ops.chunked_ce import chunked_lm_ce
    from ...ops.pallas.fused_ce import fused_ce_supported, fused_lm_ce
    if kernel == "auto":
        if fused_ce_supported():
            kernel = "pallas"
        else:
            from ...ops.pallas.tuner import record_fallback
            record_fallback("fused_ce")
            kernel = "chunked"
    if kernel == "pallas":
        return fused_lm_ce(hidden, weight, labels,
                           ignore_index=ignore_index, interpret=interpret)
    if kernel == "chunked":
        return chunked_lm_ce(hidden, weight, labels, chunk=chunk,
                             ignore_index=ignore_index)
    raise ValueError(
        f"kernel must be 'auto', 'pallas' or 'chunked', got {kernel!r}")
