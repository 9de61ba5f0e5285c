"""A decoder whose layers differ: per layer, the sequence mixer (full
causal attention, a causal sliding window, or a Gated DeltaNet linear
recurrence), the number of query heads, and a dense or a sparse (routed
experts plus a shared expert) feed-forward block.

What every layer shares: pre-norm residual blocks with RMSNorm
(``norm_offset=1`` for zero-centred weights, scale ``1 + w``), gated SiLU
FFNs, token embeddings only, a final RMSNorm and an untied head. Attention
layers have grouped KV heads (``kv_heads`` of width ``head_dim`` serve
every layer's query heads), rotary embeddings whose parameters go by the
kind of attention (so a model can rotate all lanes at one theta in its
window layers and part of them, YaRN-scaled, in its full layers), and an
optional sigmoid gate on the attention output: one a head from a
projection of its own (``"head"``) or one a lane from a doubled ``q_proj``
(``"elementwise"``). ``qk_norm`` adds an RMSNorm over the head width on q
and k before the rotation. ``"linear_attention"`` layers are
``nn.GatedDeltaNet`` (``linear_attention`` holds its sizes): a state of
fixed size a head carried along the sequence, no positions and no mask.
Sparse layers are ``incubate.moe.DroplessMoELayer``: the router's width and
``top_k`` are the model's, ``held_experts`` says which experts this copy
holds (expert parallelism's share; the whole set by default),
``shared_expert_gate`` weighs the shared expert's output a token. A call
may give the positions explicitly and ask for the block-diffusion mask in
place of the causal one (a model with linear layers refuses both: its
recurrence defines neither); ``MixedDecoderForBlockDiffusion`` trains the
trunk that way (``text/block_diffusion.py``).

Names are what the benchmark's scope metrics read: root
``mixeddecoderforpretraining`` or ``mixeddecoderforblockdiffusion``, trunk
``decoder``, blocks ``h.N``, in a block ``attn`` or ``linear_attn`` and
``mlp`` or ``moe``, then ``lm_head``.
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
LINEAR = "linear_attention"
DENSE, SPARSE = "dense", "sparse"


class GroupedQueryAttention(Layer):
    """``num_heads`` query heads over ``kv_heads`` key/value heads, rotary
    embeddings on q and k, causal with an optional window, and a sigmoid
    gate on the heads' outputs before ``o_proj``: ``gate="head"``, one
    ``sigmoid(x W_g)`` a head from ``g_proj``; ``gate="elementwise"``, one
    a lane, from a ``q_proj`` twice as wide (a head's columns are its
    query's ``head_dim`` and then its gate's). ``qk_norm_epsilon`` (off by
    default) puts an RMSNorm over the head width, one learned vector for q
    and one for k (``q_norm.weight``, ``k_norm.weight``; zero-centred with
    ``norm_offset=1``), before the rotation. ``F.rotary_embedding`` takes
    the scale and computes both, on a TPU as one pass over the tensor, so
    the scope ``qk_norm`` holds the whole per-head prologue of q and k,
    norm and rotation (``.../qk_norm/rope/...``); without a norm the
    rotation is under ``rope`` alone."""

    def __init__(self, hidden_size, num_heads, kv_heads, head_dim, rope,
                 window=None, gate=None, qk_norm_epsilon=None,
                 norm_offset=0.0):
        super().__init__()
        if num_heads % kv_heads:
            raise ValueError(f"{num_heads} query heads over {kv_heads} KV "
                             f"heads")
        if gate not in (None, "head", "elementwise"):
            raise ValueError(f"unknown attention gate {gate!r}")
        self.gate = gate
        self.num_heads, self.kv_heads = num_heads, kv_heads
        self.head_dim, self.window = head_dim, window
        # rope: {"theta", "rotary_dim", "yarn" or None}
        self.inv_freq, self.rope_scale = F.rope_frequencies(
            rope["theta"], rope["rotary_dim"], rope.get("yarn"))
        self.q_proj = nn.Linear(
            hidden_size,
            num_heads * head_dim * (2 if gate == "elementwise" else 1),
            bias_attr=False)
        self.k_proj = nn.Linear(hidden_size, kv_heads * head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(hidden_size, kv_heads * head_dim,
                                bias_attr=False)
        self.g_proj = (nn.Linear(hidden_size, num_heads, bias_attr=False)
                       if gate == "head" else None)
        self.o_proj = nn.Linear(num_heads * head_dim, hidden_size,
                                bias_attr=False)
        self.q_norm = self.k_norm = None
        if qk_norm_epsilon is not None:
            self.q_norm = nn.RMSNorm(head_dim, qk_norm_epsilon,
                                     offset=norm_offset)
            self.k_norm = nn.RMSNorm(head_dim, qk_norm_epsilon,
                                     offset=norm_offset)

    def _rope(self, x, positions, norm=None):
        if norm is None:
            return F.rotary_embedding(x, self.inv_freq, self.rope_scale,
                                      positions)
        return F.rotary_embedding(x, self.inv_freq, self.rope_scale,
                                  positions, norm.scale(), norm.epsilon)

    def forward(self, x, positions=None, block_diffusion=None):
        """``positions`` ``(seq,)``: what the rotation turns by, ``arange``
        by default. ``block_diffusion``: the block length of the
        block-diffusion mask over ``[noised ; clean]`` rows, in place of
        the causal mask."""
        b, s, _ = x.shape
        d = self.head_dim
        q = jnp.reshape(self.q_proj(x), (b, s, self.num_heads, -1))
        if self.gate == "elementwise":
            q, gate = q[..., :d], q[..., d:]
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
        if self.gate == "head":
            gate = self.g_proj(x)[..., None]
        if self.gate is not None:
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                out.dtype)
        return self.o_proj(jnp.reshape(out, (b, s, self.num_heads * d)))


class MixedDecoderBlock(Layer):
    """``h = x + mixer(norm(x)); y = h + ffn(norm(h))`` with the sublayers
    ``attn`` (softmax attention) or ``linear_attn`` (``linear``: a mixer
    that takes the hidden states alone) and ``mlp`` (dense) or ``moe``
    (sparse)."""

    def __init__(self, mixer: Layer, ffn: Layer, sparse: bool, hidden_size,
                 epsilon, linear=False, norm_offset=0.0):
        super().__init__()
        self.input_norm = nn.RMSNorm(hidden_size, epsilon,
                                     offset=norm_offset)
        if linear:
            self.linear_attn = mixer
        else:
            self.attn = mixer
        self.post_attn_norm = nn.RMSNorm(hidden_size, epsilon,
                                         offset=norm_offset)
        if sparse:
            self.moe = ffn
        else:
            self.mlp = ffn
        self._linear = linear
        self._ffn_name = "moe" if sparse else "mlp"

    def mixer_half(self, x, positions=None, block_diffusion=None):
        if self._linear:
            return x + self.linear_attn(self.input_norm(x))
        return x + self.attn(self.input_norm(x), positions, block_diffusion)

    def ffn_half(self, x):
        return x + getattr(self, self._ffn_name)(self.post_attn_norm(x))

    def forward(self, x, positions=None, block_diffusion=None):
        return self.ffn_half(self.mixer_half(x, positions, block_diffusion))


def _call_checkpointed(block: Layer, fn, x, *args):
    """``fn(x, *args)`` (a method of ``block``) under
    ``jax.checkpoint``: its activations are recomputed in the backward
    pass. What it writes to the block's buffers (an expert layer's counts)
    leaves the checkpointed function as values and is put back, so no
    tracer of the inner trace stays in a buffer."""
    owners = [(layer, name)
              for _, layer in block.named_sublayers(include_self=True)
              for name, value in layer._buffers.items() if value is not None]

    def run(x_):
        y = fn(x_, *args)
        return y, [layer._buffers[name] for layer, name in owners]

    y, values = jax.checkpoint(run)(x)
    for (layer, name), value in zip(owners, values):
        layer._buffers[name] = value
    return y


class MixedDecoderModel(Layer):
    """Embedding, the blocks, the final norm.

    ``layer_types[i]`` is ``"full_attention"``, ``"sliding_attention"`` or
    ``"linear_attention"`` (then ``linear_attention`` holds
    ``nn.GatedDeltaNet``'s ``key_heads``, ``value_heads``, ``d_k``,
    ``d_v`` and ``conv_kernel``, and the layer's
    entry in ``heads_per_layer`` is not read), ``heads_per_layer[i]`` the
    layer's query heads, ``mlp_layer_types[i]`` ``"dense"`` or ``"sparse"``
    (``router_scoring`` ``"sigmoid"`` or ``"softmax"``;
    ``shared_expert_size`` 0 for no shared expert, ``shared_expert_gate``
    for a sigmoid weight a token on it; ``router_attr`` the routers'
    ``ParamAttr``). ``attention_gate`` is ``"head"`` (``gated_attention=
    True`` says the same), ``"elementwise"`` or None. ``qk_norm``: an
    RMSNorm over the head width on q and k, the model's ``epsilon``.
    ``norm_offset=1`` makes every RMSNorm weight zero-centred (scale ``1 +
    w``). ``rope[kind]`` holds ``theta``, ``rotary_dim`` and optionally
    ``yarn`` for each kind of attention.
    ``checkpoint_blocks`` recomputes each block in the backward pass (the
    trainer's ``remat`` checkpoints the whole model at once), its mixer
    half and its feed-forward half apart: one more hidden state is kept a
    block than under one checkpoint around it, the same work is done, and
    the two halves' intermediates never exist together (0.2 to 0.4 GB less
    and 1.7 to 2.5% more tokens a second in the two cells that ran under
    one checkpoint a block; PERF.md section 6, PR 33).
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
                 qk_norm=False, router_scoring="sigmoid", router_attr=None,
                 attention_gate=None, shared_expert_gate=False,
                 linear_attention=None, norm_offset=0.0):
        super().__init__()
        if not (len(layer_types) == len(heads_per_layer)
                == len(mlp_layer_types)):
            raise ValueError("the three per-layer lists differ in length")
        if gated_attention:
            attention_gate = attention_gate or "head"
        self.has_linear_layers = LINEAR in layer_types
        self.hidden_size = hidden_size
        self.checkpoint_blocks = checkpoint_blocks
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=embedding_attr)
        blocks = []
        for kind, heads, ffn_kind in zip(layer_types, heads_per_layer,
                                         mlp_layer_types):
            if kind not in (FULL, SLIDING, LINEAR) \
                    or ffn_kind not in (DENSE, SPARSE):
                raise ValueError(f"unknown layer kinds {kind!r}, {ffn_kind!r}")
            if kind == LINEAR:
                mixer = nn.GatedDeltaNet(hidden_size, epsilon=epsilon,
                                         **linear_attention)
            else:
                mixer = GroupedQueryAttention(
                    hidden_size, heads, kv_heads, head_dim, rope[kind],
                    window=sliding_window if kind == SLIDING else None,
                    gate=attention_gate,
                    qk_norm_epsilon=epsilon if qk_norm else None,
                    norm_offset=norm_offset)
            if ffn_kind == SPARSE:
                ffn = DroplessMoELayer(
                    hidden_size, expert_size, num_experts, experts_per_token,
                    held=held_experts,
                    routed_scaling_factor=routed_scaling_factor,
                    scoring=router_scoring,
                    d_shared=shared_expert_size or None,
                    router_attr=router_attr,
                    shared_expert_gate=shared_expert_gate)
            else:
                ffn = nn.GatedSiluFFN(hidden_size, intermediate_size)
            blocks.append(MixedDecoderBlock(
                mixer, ffn, ffn_kind == SPARSE, hidden_size, epsilon,
                linear=kind == LINEAR, norm_offset=norm_offset))
        self.h = nn.LayerList(blocks)
        self.norm = nn.RMSNorm(hidden_size, epsilon, offset=norm_offset)

    def blocks(self, input_ids, positions=None, block_diffusion=None):
        """The last block's output, before the final norm."""
        if self.has_linear_layers and not (positions is None
                                           and block_diffusion is None):
            raise ValueError(
                "a model with linear_attention layers runs a recurrence "
                "along the row: it takes no explicit positions and no "
                "block-diffusion mask")
        x = self.embed_tokens(input_ids)
        for block in self.h:
            if self.checkpoint_blocks:
                # under the scope block(...) would open
                with jax.named_scope(block._scope_name):
                    x = _call_checkpointed(block, block.mixer_half, x,
                                           positions, block_diffusion)
                    x = _call_checkpointed(block, block.ffn_half, x)
            else:
                x = block(x, positions, block_diffusion)
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
