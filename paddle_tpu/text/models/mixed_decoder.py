"""A decoder whose layers differ: per layer, the kind of attention (full
causal or a causal sliding window), the number of query heads, and a dense
or a sparse (routed experts plus a shared expert) feed-forward block.

What every layer shares: pre-norm residual blocks with RMSNorm, grouped KV
heads (``kv_heads`` of width ``head_dim`` serve every layer's query heads),
rotary embeddings whose parameters go by the kind of attention (so a model
can rotate all lanes at one theta in its window layers and part of them,
YaRN-scaled, in its full layers), an optional per-head sigmoid gate on the
attention output, gated SiLU FFNs, token embeddings only, a final RMSNorm
and an untied head. Sparse layers are ``incubate.moe.DroplessMoELayer``:
the router's width and ``top_k`` are the model's, ``held_experts`` says
which experts this copy holds (expert parallelism's share; the whole set by
default). ``qk_norm`` adds an RMSNorm over the head width on q and k before
the rotation. A call may give the positions explicitly and ask for the
block-diffusion mask in place of the causal one;
``MixedDecoderForBlockDiffusion`` trains the trunk that way
(``text/block_diffusion.py``).

Names are what the benchmark's scope metrics read: root
``mixeddecoderforpretraining`` or ``mixeddecoderforblockdiffusion``, trunk
``decoder``, blocks ``h.N``, in a block ``attn`` and ``mlp`` or ``moe``, then
``lm_head``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import nn
from ...framework.random import get_rng_key
from ...incubate.moe import DroplessMoELayer
from ...nn import functional as F
from ...nn.layer import Layer
from .. import block_diffusion as bd

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


class GroupedQueryAttention(Layer):
    """``num_heads`` query heads over ``kv_heads`` key/value heads, rotary
    embeddings on q and k, causal with an optional window, and (``gated``)
    a per-head ``sigmoid(x W_g)`` on the heads' outputs before ``o_proj``.
    ``qk_norm_epsilon`` (off by default) puts an RMSNorm over the head
    width, one learned vector for q and one for k (``q_norm.weight``,
    ``k_norm.weight``), before the rotation. ``F.rotary_embedding`` takes
    the weight and computes both, on a TPU as one pass over the tensor, so
    the scope ``qk_norm`` holds the whole per-head prologue of q and k,
    norm and rotation (``.../qk_norm/rope/...``); without a norm the
    rotation is under ``rope`` alone."""

    def __init__(self, hidden_size, num_heads, kv_heads, head_dim, rope,
                 window=None, gated=False, qk_norm_epsilon=None):
        super().__init__()
        if num_heads % kv_heads:
            raise ValueError(f"{num_heads} query heads over {kv_heads} KV "
                             f"heads")
        self.num_heads, self.kv_heads = num_heads, kv_heads
        self.head_dim, self.window = head_dim, window
        # rope: {"theta", "rotary_dim", "yarn" or None}
        self.inv_freq, self.rope_scale = F.rope_frequencies(
            rope["theta"], rope["rotary_dim"], rope.get("yarn"))
        self.q_proj = nn.Linear(hidden_size, num_heads * head_dim,
                                bias_attr=False)
        self.k_proj = nn.Linear(hidden_size, kv_heads * head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(hidden_size, kv_heads * head_dim,
                                bias_attr=False)
        self.g_proj = (nn.Linear(hidden_size, num_heads, bias_attr=False)
                       if gated else None)
        self.o_proj = nn.Linear(num_heads * head_dim, hidden_size,
                                bias_attr=False)
        self.q_norm = self.k_norm = None
        if qk_norm_epsilon is not None:
            self.q_norm = nn.RMSNorm(head_dim, qk_norm_epsilon)
            self.k_norm = nn.RMSNorm(head_dim, qk_norm_epsilon)

    def _rope(self, x, positions, norm=None):
        if norm is None:
            return F.rotary_embedding(x, self.inv_freq, self.rope_scale,
                                      positions)
        return F.rotary_embedding(x, self.inv_freq, self.rope_scale,
                                  positions, norm.weight, norm.epsilon)

    def forward(self, x, positions=None, block_diffusion=None):
        """``positions`` ``(seq,)``: what the rotation turns by, ``arange``
        by default. ``block_diffusion``: the block length of the
        block-diffusion mask over ``[noised ; clean]`` rows, in place of
        the causal mask."""
        b, s, _ = x.shape
        d = self.head_dim
        q = jnp.reshape(self.q_proj(x), (b, s, self.num_heads, d))
        k = jnp.reshape(self.k_proj(x), (b, s, self.kv_heads, d))
        v = jnp.reshape(self.v_proj(x), (b, s, self.kv_heads, d))
        if self.q_norm is None:
            q, k = self._rope(q, positions), self._rope(k, positions)
        else:
            with jax.named_scope("qk_norm"):
                q = self._rope(q, positions, self.q_norm)
                k = self._rope(k, positions, self.k_norm)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=block_diffusion is None, window=self.window,
            training=self.training, block_diffusion=block_diffusion)
        if self.g_proj is not None:
            gate = jax.nn.sigmoid(self.g_proj(x).astype(jnp.float32))
            out = out * gate.astype(out.dtype)[..., None]
        return self.o_proj(jnp.reshape(out, (b, s, self.num_heads * d)))


class MixedDecoderBlock(Layer):
    """``h = x + attn(norm(x)); y = h + ffn(norm(h))`` with the sublayer
    ``mlp`` (dense) or ``moe`` (sparse)."""

    def __init__(self, attn: Layer, ffn: Layer, sparse: bool, hidden_size,
                 epsilon):
        super().__init__()
        self.input_norm = nn.RMSNorm(hidden_size, epsilon)
        self.attn = attn
        self.post_attn_norm = nn.RMSNorm(hidden_size, epsilon)
        if sparse:
            self.moe = ffn
        else:
            self.mlp = ffn
        self._ffn_name = "moe" if sparse else "mlp"

    def forward(self, x, positions=None, block_diffusion=None):
        x = x + self.attn(self.input_norm(x), positions, block_diffusion)
        return x + getattr(self, self._ffn_name)(self.post_attn_norm(x))


def _call_checkpointed(block: Layer, x, *args):
    """``block(x, *args)`` under ``jax.checkpoint``: its activations are
    recomputed in the backward pass. What the block writes to its buffers (an expert
    layer's counts) leaves the checkpointed function as values and is put
    back, so no tracer of the inner trace stays in a buffer."""
    owners = [(layer, name)
              for _, layer in block.named_sublayers(include_self=True)
              for name, value in layer._buffers.items() if value is not None]

    def run(x_):
        y = block(x_, *args)
        return y, [layer._buffers[name] for layer, name in owners]

    y, values = jax.checkpoint(run)(x)
    for (layer, name), value in zip(owners, values):
        layer._buffers[name] = value
    return y


class MixedDecoderModel(Layer):
    """Embedding, the blocks, the final norm.

    ``layer_types[i]`` is ``"full_attention"`` or ``"sliding_attention"``,
    ``heads_per_layer[i]`` the layer's query heads, ``mlp_layer_types[i]``
    ``"dense"`` or ``"sparse"`` (``router_scoring`` ``"sigmoid"`` or
    ``"softmax"``; ``shared_expert_size`` 0 for no shared expert;
    ``router_attr`` the routers' ``ParamAttr``).
    ``qk_norm``: an RMSNorm over the head width on q and k, the model's
    ``epsilon``. ``rope[kind]`` holds ``theta``,
    ``rotary_dim`` and optionally ``yarn`` for each kind of attention.
    ``checkpoint_blocks`` recomputes each block in the backward pass (the
    trainer's ``remat`` checkpoints the whole model at once).
    ``embedding_attr`` is ``nn.Embedding``'s ``weight_attr``. Its default,
    Xavier over vocab x hidden, is about 0.01 under blocks that write to the
    residual stream at unit scale: every token of a row then reaches a
    fresh router with nearly the same hidden state (the attention's running
    mean) and goes to the same few experts."""

    def __init__(self, vocab_size, hidden_size, layer_types, heads_per_layer,
                 mlp_layer_types, kv_heads, head_dim, rope, sliding_window,
                 intermediate_size, num_experts=0, experts_per_token=0,
                 expert_size=0, shared_expert_size=0, held_experts=None,
                 routed_scaling_factor=1.0, gated_attention=False,
                 epsilon=1e-6, checkpoint_blocks=False, embedding_attr=None,
                 qk_norm=False, router_scoring="sigmoid", router_attr=None):
        super().__init__()
        if not (len(layer_types) == len(heads_per_layer)
                == len(mlp_layer_types)):
            raise ValueError("the three per-layer lists differ in length")
        self.hidden_size = hidden_size
        self.checkpoint_blocks = checkpoint_blocks
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=embedding_attr)
        blocks = []
        for kind, heads, ffn_kind in zip(layer_types, heads_per_layer,
                                         mlp_layer_types):
            if kind not in (FULL, SLIDING) or ffn_kind not in (DENSE, SPARSE):
                raise ValueError(f"unknown layer kinds {kind!r}, {ffn_kind!r}")
            attn = GroupedQueryAttention(
                hidden_size, heads, kv_heads, head_dim, rope[kind],
                window=sliding_window if kind == SLIDING else None,
                gated=gated_attention,
                qk_norm_epsilon=epsilon if qk_norm else None)
            if ffn_kind == SPARSE:
                ffn = DroplessMoELayer(
                    hidden_size, expert_size, num_experts, experts_per_token,
                    held=held_experts,
                    routed_scaling_factor=routed_scaling_factor,
                    scoring=router_scoring,
                    d_shared=shared_expert_size or None,
                    router_attr=router_attr)
            else:
                ffn = nn.GatedSiluFFN(hidden_size, intermediate_size)
            blocks.append(MixedDecoderBlock(attn, ffn, ffn_kind == SPARSE,
                                            hidden_size, epsilon))
        self.h = nn.LayerList(blocks)
        self.norm = nn.RMSNorm(hidden_size, epsilon)

    def blocks(self, input_ids, positions=None, block_diffusion=None):
        """The last block's output, before the final norm."""
        x = self.embed_tokens(input_ids)
        for block in self.h:
            x = (_call_checkpointed(block, x, positions, block_diffusion)
                 if self.checkpoint_blocks
                 else block(x, positions, block_diffusion))
        return x

    def forward(self, input_ids, positions=None, block_diffusion=None):
        return self.norm(self.blocks(input_ids, positions, block_diffusion))


class MixedDecoderForPretraining(Layer):
    """Trunk and an untied head: ``forward`` gives the logits."""

    def __init__(self, decoder: MixedDecoderModel = None, **kwargs):
        super().__init__()
        self.decoder = decoder or MixedDecoderModel(**kwargs)
        vocab = self.decoder.embed_tokens.num_embeddings
        self.lm_head = nn.Linear(self.decoder.hidden_size, vocab,
                                 bias_attr=False)

    def forward(self, input_ids):
        return self.lm_head(self.decoder(input_ids))


class MixedDecoderForBlockDiffusion(Layer):
    """Trunk, an untied head and the block-diffusion objective: ``forward``
    takes clean rows ``(rows, L)`` and gives the loss.

    It draws the noise from the step's key (``get_rng_key()``, what dropout
    draws from: the trainer hands every step another), runs the trunk on
    ``[noised ; clean]``, ``2L`` positions under the block-diffusion mask
    with token ``i`` at position ``i`` in both halves, and the final norm,
    the head and the loss on the noised half only: the clean half's outputs
    enter no loss and their logits are never formed. ``mask_token_id`` is
    an id the data never holds (the vocabulary's last by default). The
    masked share of the last call's tokens leaves a jitted
    ``functional_call`` in the buffer ``masked_share``; ``publish_noise``
    writes it to the gauge ``block_diffusion_masked_share``."""

    def __init__(self, decoder: MixedDecoderModel = None, block_length=4,
                 mask_token_id=None, t_min=1e-3, **kwargs):
        super().__init__()
        self.decoder = decoder or MixedDecoderModel(**kwargs)
        vocab = self.decoder.embed_tokens.num_embeddings
        self.block_length, self.t_min = block_length, t_min
        self.mask_token_id = vocab - 1 if mask_token_id is None \
            else mask_token_id
        self.lm_head = nn.Linear(self.decoder.hidden_size, vocab,
                                 bias_attr=False)
        self.register_buffer("masked_share", jnp.zeros((), jnp.float32),
                             persistable=False)

    def forward(self, tokens):
        noised, masked, t = bd.noise(tokens, get_rng_key(), self.block_length,
                                     self.mask_token_id, self.t_min)
        self.masked_share = jnp.mean(masked.astype(jnp.float32))
        ids, positions = bd.model_inputs(noised, tokens)
        x = self.decoder.blocks(ids, positions, self.block_length)
        logits = self.lm_head(self.decoder.norm(x[:, :tokens.shape[1]]))
        with jax.named_scope("loss"):
            return bd.loss(logits, tokens, masked, t)

    def publish_noise(self, buffers=None, prefix="", **labels):
        """The last call's masked share into the telemetry registry
        (``buffers`` as ``DroplessMoELayer.publish_routing`` takes them)."""
        from ... import telemetry
        src = buffers if buffers is not None else dict(self.named_buffers())
        telemetry.gauge(
            "block_diffusion_masked_share",
            "share of a step's clean tokens the block-diffusion noise "
            "masked, last call").set(
                float(src[prefix + "masked_share"]), **labels)
