"""On-chip numerics smoke: validate the hand-written kernels' arithmetic
on the LIVE backend against dense jnp references.

Every Pallas kernel is trajectory-tested on the CPU interpreter, but TPU
hardware rounds differently (bf16 MXU accumulation, revectorized
reductions) and the interpreter never meets the TPU lowering. This
script runs the hot kernels — flash attention fwd/bwd (causal,
kv-masked, and the geometries whose tiles are computed by sub-blocks), QK
norm with RoPE fwd/bwd, the fused LM-head CE fwd/bwd, paged decode/verify attention,
chunked LM cross-entropy fwd/bwd, bf16 matmul — on whatever backend is
live and checks errors against references with bf16-appropriate
tolerances.

Usage: ``python tools/numerics_smoke.py`` — prints one JSON line per
check plus a final summary line ``{"numerics_ok": bool, ...}``; exit 0
iff every check passed. On CPU the Pallas kernels run under
``interpret=True`` at small shapes (the script is backend-agnostic so
the suite smokes it without a chip). ``chip_smoke.py`` calls the
``check_*`` functions itself with ``interpret=False`` at the bench
shapes: there the kernel is compiled or the check raises.

Reference intent anchor: the reference validates fused CUDA kernels
against unfused graphs the same way
(fluid/operators/fused/multihead_matmul_op.cu + its unittest).
"""
from __future__ import annotations

import json
import os
import sys

# runnable from anywhere: the repo root (paddle_tpu's parent) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ref_attention(q, k, v, causal, kv_lens, sm_scale):
    import jax.numpy as jnp

    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sm_scale
    b, _, sq, sk = logits.shape
    mask = jnp.ones((b, 1, sq, sk), bool)
    if causal:
        mask &= jnp.tril(jnp.ones((sq, sk), bool))[None, None]
    if kv_lens is not None:
        mask &= (jnp.arange(sk)[None, :] < kv_lens[:, None])[:, None, None]
    logits = jnp.where(mask, logits, -1e30)
    p = jnp.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


def check_flash_attention(interpret):
    import math

    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rs = np.random.RandomState(0)
    b, s, h, d = 2, 256, 4, 64
    q, k, v = (jnp.asarray(rs.randn(b, s, h, d), jnp.bfloat16)
               for _ in range(3))
    sm_scale = 1.0 / math.sqrt(d)
    results = []
    for name, kw in (("plain", {}), ("causal", dict(causal=True)),
                     ("kv_mask", dict(kv_lens=jnp.asarray([s, s // 2],
                                                          jnp.int32)))):
        out = flash_attention(q, k, v, interpret=interpret, **kw)
        ref = _ref_attention(q, k, v, kw.get("causal", False),
                             kw.get("kv_lens"), sm_scale)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
        # bf16 mantissa is 8 bits: |v|~O(1) rows give abs err ~1e-2
        results.append({"check": f"flash_fwd_{name}", "max_abs_err": err,
                        "tol": 5e-2, "ok": err < 5e-2})

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=interpret)
                       .astype(jnp.float32) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, True, None, sm_scale) ** 2)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b_.astype(jnp.float32))))
               for a, b_ in zip(g, gr))
    # backward accumulates over seq: looser than fwd
    results.append({"check": "flash_bwd_causal", "max_abs_err": gerr,
                    "tol": 0.5, "ok": gerr < 0.5})
    return results


def check_flash_tile_kinds(interpret):
    """The geometries whose tiles the kernels tell apart, as the cells run
    them (fewer rows and heads): one causal tile at head width 64 with two
    heads a lane block (blocks from the tuning DB), tiles of 1,024 rows at
    width 128 with grouped heads (two on the diagonal, a dense one before
    them), and a window of one 512-row block (both triangles, none
    between). Output and the three gradients against float32 XLA attention
    of the same inputs, each within 1% of the reference tensor's largest
    magnitude (the kernels' own roundings to bf16;
    tests/test_flash_attention_extras.py derives the figure)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    results = []
    for name, (s, h, h_kv, d, kw) in {
            "causal_tile_d64": (1024, 4, 4, 64, {}),
            "tiles_of_1024_d128_6_over_1": (
                2048, 6, 1, 128, dict(block_q=1024, block_k=1024)),
            "window_512_d128_8_over_1": (
                2048, 8, 1, 128, dict(window=512, block_q=512, block_k=512)),
    }.items():
        ks = jax.random.split(jax.random.key(len(name)), 3)
        q = jax.random.normal(ks[0], (2, s, h, d), jnp.bfloat16)
        k, v = (jax.random.normal(key, (2, s, h_kv, d), jnp.bfloat16)
                for key in ks[1:])
        window = kw.get("window")

        def flash(q, k, v):
            out = flash_attention(q, k, v, causal=True, interpret=interpret,
                                  **kw)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        def ref(q, k, v):
            out = _xla_attention(q, k, v, causal=True, window=window)
            return jnp.sum(out ** 2), out

        got, out = jax.grad(flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        want, out_ref = jax.grad(ref, argnums=(0, 1, 2), has_aux=True)(
            *(x.astype(jnp.float32) for x in (q, k, v)))
        rel = max(_max_err(a, w)[1]
                  for a, w in zip((out,) + got, (out_ref,) + want))
        results.append({"check": f"flash_{name}", "max_rel_err": rel,
                        "tol": 1e-2, "ok": rel < 1e-2})
    return results


def check_rope(interpret):
    """QK norm and RoPE as the two mixed-decoder cells stage them, q's
    shape a layer: all 128 lanes rotated, 64 of 128 with a YaRN factor,
    and with a norm weight at explicit positions that hold a row twice.
    Values, ``dx`` and the weight's gradient of the Pallas pass against
    ``F.rms_norm`` and the XLA formula over the same inputs in float32,
    each within 1% of the reference's largest magnitude (one rounding to
    bf16 is 0.4% of a value). On the chip through ``F.rotary_embedding``,
    which takes the kernels there; rehearsed on the CPU, whose entry point
    takes the formula, straight through the interpreted kernels at a
    sixteenth of the positions."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn import functional as F
    from paddle_tpu.nn.functional.rotary import _rotate_xla, rope_tables
    from paddle_tpu.ops.pallas import rotary as kernel

    yarn = {"factor": 64.0, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1}
    results = []
    for name, (shape, theta, rotated, scaling, norm) in {
            "laguna_sliding": ((4, 4096, 64, 128), 1e4, 128, None, False),
            "laguna_full_yarn": ((4, 4096, 48, 128), 5e5, 64, yarn, False),
            "sdar_norm_positions": ((2, 8192, 32, 128), 1e6, 128, None, True),
    }.items():
        b, seq, heads, d = shape
        if interpret:
            b, seq, heads = 1, seq // 16, 2
        inv_freq, scale = F.rope_frequencies(theta, rotated, scaling)
        positions = jnp.concatenate([jnp.arange(seq // 2)] * 2) \
            if norm else None
        ks = jax.random.split(jax.random.key(len(name)), 3)
        x = jax.random.normal(ks[0], (b, seq, heads, d), jnp.bfloat16)
        ct = jax.random.normal(ks[1], x.shape, jnp.float32)
        w = (1.0 + 0.1 * jax.random.normal(ks[2], (d,))).astype(
            jnp.bfloat16) if norm else None
        cos, sin = rope_tables(inv_freq, scale, positions, seq, d)

        def mine(x, w):
            if interpret:
                out = kernel.rotary(x, cos, sin, len(inv_freq), w, 1e-6,
                                    interpret=True)
            else:
                out = F.rotary_embedding(x, inv_freq, scale, positions, w,
                                         1e-6)
            return jnp.sum(out.astype(jnp.float32) * ct), out

        def ref(x, w):
            if w is not None:
                x = F.rms_norm(x, w, 1e-6)
            out = _rotate_xla(x, cos, sin, len(inv_freq))
            return jnp.sum(out * ct), out

        args = (0, 1) if norm else (0,)
        got, out = jax.grad(mine, argnums=args, has_aux=True)(x, w)
        want, out_ref = jax.grad(ref, argnums=args, has_aux=True)(
            x.astype(jnp.float32), None if w is None
            else w.astype(jnp.float32))
        rel = max(_max_err(a, r)[1]
                  for a, r in zip((out,) + got, (out_ref,) + want))
        results.append({"check": f"rope_{name}", "max_rel_err": rel,
                        "tol": 1e-2, "ok": rel < 1e-2})
    return results


def check_linear_attention(interpret):
    """The gated delta rule at the Qwen3-Next cell's head widths (4 value
    heads of d_k = d_v = 128, bf16, 1,024 positions; rehearsed on the CPU
    at 2 heads of 16 and 128 positions): the chunked path a layer takes
    (on the chip the Pallas kernels, rehearsed XLA's batched products)
    against the recurrence over positions, given the same bf16 inputs in
    float32; output and the five gradients within 2% of the reference's
    largest magnitude (the result's and the cotangents' rounding to bf16).
    Then a two-layer decoder of that configuration's kinds (a Gated
    DeltaNet block; gated full attention at head width 256 with the
    zero-centred QK norm and 64 rotated lanes; a dropless expert layer with
    the gated shared expert in both) forward and backward through
    ``MixedDecoderModel``: every gradient finite and none all zero, the
    expert layers' counts published. What path the rotation and the rule
    took is for the caller to read from the counters."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.moe import DroplessMoELayer
    from paddle_tpu.jit.functionalization import functional_call, state_of
    from paddle_tpu.nn import functional as F
    from paddle_tpu.text.models.mixed_decoder import MixedDecoderModel

    seq, h, d = (128, 2, 16) if interpret else (1024, 4, 128)
    ks = jax.random.split(jax.random.key(33), 6)
    q, k = (jax.random.normal(key, (2, seq, h, d)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / d ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, seq, h, d), jnp.bfloat16)
    g = -jax.random.uniform(ks[3], (2, seq, h)) * jnp.arange(1, h + 1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, seq, h)))
    ct = jax.random.normal(ks[5], v.shape, jnp.float32)

    def rule(path):
        def f(*args):
            out = F.gated_delta_rule(*args, path=path)
            return jnp.sum(out.astype(jnp.float32) * ct), out
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True))

    got, out = rule("chunked")(q, k, v, g, beta)
    want, out_ref = rule("recurrent")(*(
        x.astype(jnp.float32) for x in (q, k, v, g, beta)))
    rel = max(_max_err(a, w)[1]
              for a, w in zip((out,) + got, (out_ref,) + want))
    results = [{"check": "gated_delta_rule_chunked_vs_recurrent",
                "max_rel_err": rel, "tol": 2e-2, "ok": rel < 2e-2}]

    hidden, heads, kv, head_dim, experts = (64, 4, 2, 32, 8) if interpret \
        else (1024, 4, 2, 256, 16)
    model = MixedDecoderModel(
        vocab_size=512, hidden_size=hidden,
        layer_types=["linear_attention", "full_attention"],
        heads_per_layer=[heads] * 2, mlp_layer_types=["sparse"] * 2,
        kv_heads=kv, head_dim=head_dim,
        rope={"full_attention": {"theta": 1e7, "rotary_dim": head_dim // 4}},
        sliding_window=None, intermediate_size=2 * hidden,
        num_experts=experts, experts_per_token=2, expert_size=hidden // 2,
        shared_expert_size=hidden // 2, shared_expert_gate=True,
        held_experts=(0, experts // 2), router_scoring="softmax",
        qk_norm=True, attention_gate="elementwise", norm_offset=1.0,
        checkpoint_blocks=True,
        linear_attention=dict(key_heads=h // 2, value_heads=h, d_k=d, d_v=d,
                              conv_kernel=4))
    model.astype("bfloat16")
    params = dict(state_of(model)[0])
    ids = jax.random.randint(ks[0], (2, seq), 0, 512)

    def loss(p):
        # buffers None: the layers' own go in, all of them come out
        out, buffers = functional_call(model, p, None, ids, rng=ks[1])
        return jnp.mean(jnp.square(out.astype(jnp.float32))), buffers

    (value, buffers), grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(params)
    for name, layer in model.named_sublayers():
        if isinstance(layer, DroplessMoELayer):
            layer.publish_routing(buffers, name + ".", layer=name)
    finite = bool(jnp.isfinite(value)) and all(
        bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
        for x in grads.values())
    zero = sorted(n for n, x in grads.items()
                  if not float(jnp.max(jnp.abs(x.astype(jnp.float32)))))
    results.append({"check": "mixed_decoder_linear_and_gated_full_block",
                    "loss": float(value), "leaves": len(grads),
                    "zero_gradient_leaves": zero,
                    "ok": finite and not zero})
    return results


# ``check_gated_delta_precision``'s limits, as shares of each reference
# tensor's norm: between what the kernels read at three bf16 passes a float32
# product and what one pass reads (PERF.md section 6, PR 34, my chip runs:
# 1.3e-4 to 1.4e-4 against 3.7e-3 for o, dq, dk and dv, 3.7e-6 against 2.4e-3
# for dg and dbeta, which no bf16 rounds)
GATED_DELTA_PRECISION_TOL = {"o": 7e-4, "dq": 7e-4, "dk": 7e-4, "dv": 7e-4,
                             "dg": 1e-4, "dbeta": 1e-4}


def check_gated_delta_precision(interpret):
    """The guard the benchmark's comparison lacks (it does not tell a
    bf16-rounded state from a sound one): the kernels' ``o`` and five
    gradients on one layer-shaped input of the Qwen3-Next cell (a row of
    8,192 positions, 32 value heads of ``d_k = d_v = 128``, bf16; rehearsed
    on the CPU at 256 positions and 2 heads) against XLA's chunked path at
    ``Precision.HIGHEST`` over the same numbers in float32, each as the
    norm of the difference over the reference's norm. A product that fell
    to one bf16 pass reads an order of magnitude over these limits."""
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import linear_attention as la
    from paddle_tpu.ops.pallas import gated_delta

    seq, h, d = (256, 2, 128) if interpret else (8192, 32, 128)
    ks = jax.random.split(jax.random.key(34), 7)
    q, k = (jax.random.normal(key, (1, seq, h, d)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / d ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, seq, h, d), jnp.bfloat16)
    # the layer's decay, -A softplus(a + dt_bias): rates from e^-6 to e
    g = -jnp.exp(jax.random.uniform(ks[3], (1, seq, h), minval=-6.0,
                                    maxval=1.0)) \
        * jax.random.uniform(ks[6], (1, seq, h))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, h)))
    # a cotangent that bf16 holds: the kernels' o is bf16, so is its do
    ct = jax.random.normal(ks[5], v.shape, jnp.bfloat16).astype(jnp.float32)

    def rule(fn):
        def f(*args):
            out = fn(*args)
            return jnp.sum(out.astype(jnp.float32) * ct), out
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True))

    got, out = rule(lambda *a: gated_delta.gated_delta(
        *a, interpret=interpret))(q, k, v, g, beta)
    # ``kk`` and ``qk`` carry no precision of their own (their operands are
    # bf16 numbers), so their transposes, which multiply a float32 cotangent,
    # take the default: one pass on the chip unless it is raised here
    with mock.patch.object(la, "PRECISION", jax.lax.Precision.HIGHEST), \
            jax.default_matmul_precision("highest"):
        want, out_ref = rule(lambda *a: la._chunked(
            *a, gated_delta.CHUNK, la.GROUP_HEADS))(*(
                x.astype(jnp.float32) for x in (q, k, v, g, beta)))
    rel = {}
    for name, a, w in zip(GATED_DELTA_PRECISION_TOL, (out,) + got,
                          (out_ref,) + want):
        # the reference rounded as the kernels round what they write
        a, w = a.astype(jnp.float32), w.astype(a.dtype).astype(jnp.float32)
        rel[name] = float(jnp.linalg.norm(a - w) / jnp.linalg.norm(w))
    return [{"check": "gated_delta_kernels_vs_xla_highest", "rel_l2": rel,
             "tol": GATED_DELTA_PRECISION_TOL,
             "ok": all(rel[n] < t
                       for n, t in GATED_DELTA_PRECISION_TOL.items())}]


# ``check_latent_attention``'s limits: the loss as a relative difference,
# the gradients as |g - g_ref| / |g_ref| in the 2-norm, worst leaf and
# median leaf, the program in bf16 on the chip's kernels against the same
# model in float32 on XLA's paths at ``Precision.HIGHEST``. The chip read
# 2.0e-5, 0.184 (the module's router) and 0.0215 (my chip run, PR 37); the
# cell's comparison, which has a float8 control, keeps 1.2e-4, 0.5, 0.04.
LATENT_TOL = {"loss": 2e-4, "worst_leaf": 0.5, "median_leaf": 0.04}


def check_latent_attention(interpret):
    """One latent-attention block with its multi-token-prediction module at
    the GLM-4.7-Flash cell's widths (hidden 2,048, 20 heads of 192 + 64
    lanes from latents of 768 and 512, a sigmoid router of 64 choosing 4 by
    its bias with 8 held and a shared expert, experts 1,536 wide; two rows
    of 1,024 positions, bf16, a vocabulary of 4,096; rehearsed on the CPU
    at toy widths in float32): the model's two-term loss and every gradient
    leaf through the path a step takes (on the chip the flash kernels and
    the rotary kernel for q) against the same weights in float32 on XLA's
    attention and rotation at ``Precision.HIGHEST``. The two loss terms are
    published as ``publish_losses`` does; what path the rotation and the
    attention took is for the caller to read from the counters."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.jit.functionalization import functional_call, state_of
    from paddle_tpu.text.models import MixedDecoderForPretraining

    if interpret:
        sizes = dict(hidden=64, heads=4, inter=128, expert=32, seq=128,
                     vocab=512, experts=(16, 4), dtype="float32",
                     latent=dict(q_lora_rank=24, kv_lora_rank=16,
                                 qk_nope_head_dim=12, qk_rope_head_dim=4,
                                 v_head_dim=16))
    else:
        sizes = dict(hidden=2048, heads=20, inter=10240, expert=1536,
                     seq=1024, vocab=4096, experts=(64, 8), dtype="bfloat16",
                     latent=dict(q_lora_rank=768, kv_lora_rank=512,
                                 qk_nope_head_dim=192, qk_rope_head_dim=64,
                                 v_head_dim=256))
    paddle.seed(37)
    model = MixedDecoderForPretraining(
        mtp_layers=1, mtp_loss_weight=0.3, vocab_size=sizes["vocab"],
        hidden_size=sizes["hidden"], layer_types=["latent_attention"],
        heads_per_layer=[sizes["heads"]], mlp_layer_types=["sparse"],
        kv_heads=None, head_dim=None,
        rope={"latent_attention": {"theta": 1e6}}, sliding_window=None,
        intermediate_size=sizes["inter"], num_experts=sizes["experts"][0],
        experts_per_token=4, expert_size=sizes["expert"],
        shared_expert_size=sizes["expert"],
        held_experts=(0, sizes["experts"][1]), routed_scaling_factor=1.8,
        router_selection_bias=True, epsilon=1e-5, checkpoint_blocks=True,
        latent_attention=sizes["latent"],
        embedding_attr=paddle.nn.initializer.Normal(0.0, 1.0))
    model.astype(sizes["dtype"])
    params = dict(state_of(model)[0])
    tokens = jax.random.randint(jax.random.key(38), (2, sizes["seq"] + 1), 0,
                                sizes["vocab"])
    batch = (tokens[:, :-1], tokens[:, 1:])

    def loss(p):
        # buffers None: the layers' own go in, all of them come out
        return functional_call(model, p, None, batch, rng=jax.random.key(0))

    step = jax.value_and_grad(loss, has_aux=True)
    (value, buffers), grads = jax.jit(step)(params)
    model.publish_losses(buffers)
    # the same weights in float32 where the CPU's gates leave attention and
    # the rotation: XLA's, every product at the highest precision
    with mock.patch.object(jax, "default_backend", lambda: "cpu"), \
            jax.default_matmul_precision("highest"):
        (want, _), ref = jax.jit(step)(jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params))
    rel = {k: float(jnp.linalg.norm((grads[k].astype(jnp.float32) - ref[k])
                                    .ravel())
                    / jnp.linalg.norm(ref[k].ravel())) for k in ref}
    worst = max(rel, key=rel.get)
    got = {"loss": abs(float(value) - float(want)) / abs(float(want)),
           "worst_leaf": rel[worst],
           "median_leaf": float(np.median(list(rel.values())))}
    return [{"check": "latent_attention_block_and_mtp_vs_xla_highest",
             "loss": float(value), "loss_reference": float(want),
             "mtp_main_loss": float(buffers["mtp_main_loss"]),
             "mtp_next_loss": float(buffers["mtp_next_loss"]),
             "leaves": len(rel), "worst_leaf_name": worst, "rel": got,
             "tol": LATENT_TOL,
             "ok": all(np.isfinite(v) and v < LATENT_TOL[k]
                       for k, v in got.items())}]


def _max_err(got, ref):
    """(max abs error, the same over max |ref|) in fp32."""
    import jax.numpy as jnp

    g, r = (x.astype(jnp.float32) for x in (got, ref))
    err = float(jnp.max(jnp.abs(g - r)))
    return err, err / max(float(jnp.max(jnp.abs(r))), 1e-30)


def check_fused_ce(interpret, tokens=512, hidden=128, vocab=1024,
                   dtype="bfloat16"):
    """Pallas fused LM-head CE (block config from the tuning DB) against
    the chunked jnp scan it replaces: loss, d_hidden, d_weight."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.chunked_ce import chunked_lm_ce
    from paddle_tpu.ops.pallas.fused_ce import fused_lm_ce

    rs = np.random.RandomState(3)
    hid = jnp.asarray(rs.randn(tokens, hidden) * 0.1, dtype)
    w = jnp.asarray(rs.randn(hidden, vocab) * 0.1, dtype)
    lbl = jnp.asarray(rs.randint(0, vocab, tokens), jnp.int32)
    lbl = lbl.at[::7].set(-100)  # exercise ignore_index

    fused = jax.jit(jax.value_and_grad(
        lambda a, b: fused_lm_ce(a, b, lbl, interpret=interpret),
        argnums=(0, 1)))
    ref = jax.jit(jax.value_and_grad(
        lambda a, b: chunked_lm_ce(a, b, lbl, chunk=min(vocab, 8192)),
        argnums=(0, 1)))
    (lf, gf), (lr, gr) = fused(hid, w), ref(hid, w)
    lerr = abs(float(lf) - float(lr))
    out = [{"check": "fused_ce_fwd", "max_abs_err": lerr, "tol": 2e-2,
            "ok": lerr < 2e-2}]
    # grads are O(1/tokens): the absolute bar chunked CE is held to says
    # little here, so also bound the error against the largest entry
    for name, a, b in (("dh", gf[0], gr[0]), ("dw", gf[1], gr[1])):
        err, rel = _max_err(a, b)
        out.append({"check": f"fused_ce_bwd_{name}", "max_abs_err": err,
                    "tol": 2e-2, "rel_to_max": rel, "rel_tol": 5e-2,
                    "ok": err < 2e-2 and rel < 5e-2})
    return out


def check_paged_attention(interpret, heads=2, head_dim=32, page_size=8,
                          dtype="float32"):
    """Pallas paged attention — a decode step and a Tq=5 speculative-
    verify chunk — against the XLA gather path over the same pool."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    from paddle_tpu.ops.pallas.tuner import _paged_case_arrays

    tol = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 2e-3
    out = []
    for name, tq in (("decode", 1), ("verify_tq5", 5)):
        q, kp, vp, tables, lens, kn, vn = _paged_case_arrays(
            4, heads, head_dim, page_size, 8, jnp.dtype(dtype), tq=tq)
        got, ref = (paged_decode_attention(
            q, kp, vp, tables, lens, k_new=kn, v_new=vn, kernel=kernel,
            interpret=interpret) for kernel in ("pallas", "xla"))
        err, rel = _max_err(got, ref)
        out.append({"check": f"paged_{name}", "max_abs_err": err,
                    "rel_to_max": rel, "tol": tol, "ok": rel < tol})
    return out


def check_chunked_ce():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.chunked_ce import chunked_lm_ce

    rs = np.random.RandomState(1)
    n, h, vocab, chunk = 512, 128, 1024, 256
    hidden = jnp.asarray(rs.randn(n, h) * 0.1, jnp.bfloat16)
    weight = jnp.asarray(rs.randn(h, vocab) * 0.1, jnp.bfloat16)
    labels = jnp.asarray(rs.randint(0, vocab, n), jnp.int32)
    labels = labels.at[::7].set(-100)  # exercise ignore_index

    def dense(hid, w):
        logits = (hid.astype(jnp.float32) @ w.astype(jnp.float32))
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gather = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[:, None], axis=1)[:, 0]
        valid = labels >= 0
        per = jnp.where(valid, lse - gather, 0.0)
        return per.sum() / jnp.maximum(valid.sum(), 1)

    loss_c = chunked_lm_ce(hidden, weight, labels, chunk=chunk)
    loss_d = dense(hidden, weight)
    lerr = abs(float(loss_c) - float(loss_d))
    out = [{"check": "chunked_ce_fwd", "max_abs_err": lerr, "tol": 2e-2,
            "ok": lerr < 2e-2}]
    gc = jax.grad(lambda a, b: chunked_lm_ce(a, b, labels, chunk=chunk),
                  argnums=(0, 1))(hidden, weight)
    gd = jax.grad(dense, argnums=(0, 1))(hidden, weight)
    gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(gc, gd))
    out.append({"check": "chunked_ce_bwd", "max_abs_err": gerr,
                "tol": 2e-2, "ok": gerr < 2e-2})
    return out


def check_bf16_matmul():
    import numpy as np
    import jax.numpy as jnp

    rs = np.random.RandomState(2)
    a32 = rs.randn(512, 512).astype(np.float32)
    b32 = rs.randn(512, 512).astype(np.float32)
    prod = jnp.asarray(a32, jnp.bfloat16) @ jnp.asarray(b32, jnp.bfloat16)
    ref = np.asarray(a32 @ b32)
    # MXU accumulates in fp32: error comes from input rounding only —
    # relative to the row norms (~sqrt(512)*sigma), not the entries
    rel = float(np.max(np.abs(np.asarray(prod, np.float32) - ref))
                / np.abs(ref).max())
    return [{"check": "bf16_matmul", "max_rel_err": rel, "tol": 2e-2,
             "ok": rel < 2e-2}]


def main():
    import jax

    from tools._mesh_setup import use_compile_cache

    use_compile_cache()
    backend = jax.default_backend()
    interpret = backend != "tpu"
    checks = []
    for fn in (check_flash_attention, check_flash_tile_kinds, check_rope,
               check_linear_attention, check_gated_delta_precision,
               check_fused_ce, check_paged_attention):
        checks.extend(fn(interpret))
    for fn in (check_chunked_ce, check_bf16_matmul):
        checks.extend(fn())
    for c in checks:
        print(json.dumps(c))
    ok = all(c.get("ok") for c in checks)
    print(json.dumps({"numerics_ok": ok, "backend": backend,
                      "interpret": interpret, "n_checks": len(checks)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
