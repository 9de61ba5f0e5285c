"""A decoder whose layers differ: per layer, the sequence mixer (full
causal attention, a causal sliding window, multi-head latent attention, a
Gated DeltaNet linear recurrence, a Mamba-2 state-space scan or none), the
number of query heads, and a dense, a sparse (routed experts plus a shared
expert) or no feed-forward block. A layer with both is two residual
sublayers, ``h = x + mixer(norm(x)); y = h + ffn(norm(h))``; a layer with
one of them is one, ``y = x + sublayer(norm(x))`` (Nemotron-H's pattern of
Mamba, expert and attention layers). ``norm_placement="post"`` moves every
block's norms onto its sublayers' outputs, ``h = x + norm(mixer(x)); y = h +
norm(ffn(h))`` (Olmo 2's reordered norms).

What every layer shares: residual blocks with RMSNorm
(``norm_offset=1`` for zero-centred weights, scale ``1 + w``), token
embeddings only, a final RMSNorm and an untied head. Feed-forward blocks
are gated SiLU FFNs, or squared-ReLU experts (``expert_activation=
"relu2"``). Attention
layers have grouped KV heads (``kv_heads`` of width ``head_dim`` serve
every layer's query heads), rotary embeddings whose parameters go by the
kind of attention (so a model can rotate all lanes at one theta in its
window layers and part of them, YaRN-scaled, in its full layers; or none
at all, ``rope[kind]`` None), and an
optional sigmoid gate on the attention output: one a head from a
projection of its own (``"head"``) or one a lane from a doubled ``q_proj``
(``"elementwise"``). ``qk_norm`` adds an RMSNorm over the head width on q
and k before the rotation, or with ``qk_norm="whole"`` one over all of q's
and all of k's lanes (Olmo 2's). ``"linear_attention"`` layers are
``nn.GatedDeltaNet`` (``linear_attention`` holds its sizes): a state of
fixed size a head carried along the sequence, no positions and no mask.
``"latent_attention"`` layers are ``MultiHeadLatentAttention``
(``latent_attention`` holds its ranks and widths): q and k, v come from
low-rank latents with an RMSNorm on each, and the rotary part of the key is
one head that all key heads share. ``"mamba"`` layers are
``nn.Mamba2Mixer`` (``mamba`` holds its sizes): a ``P x N`` state a head
carried along the sequence by ``F.ssd_scan``, no positions and no mask.
Sparse layers are ``incubate.moe.DroplessMoELayer``: the router's width and
``top_k`` are the model's, ``held_experts`` says which experts this copy
holds (expert parallelism's share; the whole set by default),
``shared_expert_gate`` weighs the shared expert's output a token,
``router_selection_bias`` gives each router the buffer
``e_score_correction_bias`` that chooses and does not weigh. A call
may give the positions explicitly and ask for the block-diffusion mask in
place of the causal one (a model with linear layers refuses both: its
recurrence defines neither; so does one with mamba layers);
``MixedDecoderForBlockDiffusion`` trains the
trunk that way (``text/block_diffusion.py``).
``MixedDecoderForPretraining(mtp_layers=1)`` adds a multi-token-prediction
module (``MultiTokenPrediction``) that predicts the token after the next
through the trunk's own embedding and head.

Names are what the benchmark's scope metrics read: root
``mixeddecoderforpretraining`` or ``mixeddecoderforblockdiffusion``, trunk
``decoder``, blocks ``h.N``, in a block ``attn``, ``linear_attn`` or
``mamba`` and ``mlp`` or ``moe`` (one of the two in a one-sublayer block),
under ``norm_placement="post"`` their norms ``post_mixer_norm`` and
``post_ffn_norm``, then ``lm_head``; the multi-token-prediction module is
``mtp`` with its block ``mtp.block``; in a latent-attention layer the scopes
``latent_q`` and ``latent_kv`` hold what stands where grouped-query
attention has its three projections.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import nn
from ...framework.random import get_rng_key
from ...incubate.moe import DroplessMoELayer
from ...nn import functional as F
from ...nn.functional.rotary import rotary_path
from ...nn.layer import Layer
from .. import block_diffusion as bd

FULL, SLIDING = "full_attention", "sliding_attention"
LINEAR, LATENT, MAMBA = "linear_attention", "latent_attention", "mamba"
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
    rotation is under ``rope`` alone. ``rope=None`` rotates nothing: the
    layer has no positional encoding and sees the order through the causal
    mask alone (a QK norm is then applied by itself).

    ``qk_norm_over="whole"`` puts the norm over all ``num_heads x head_dim``
    lanes of q and all ``kv_heads x head_dim`` of k instead, one weight of
    that width each, before the split into heads (Olmo 2's QK norm), under
    the scope ``qk_norm``; a rotation follows it. ``axis_name``: the layer
    is one share of a tensor-parallel layer whose heads are split over that
    named axis, and the whole-width norm's mean of squares runs over every
    share's lanes; None (the default) takes this layer's own lanes, as one
    chip that holds its share alone does."""

    def __init__(self, hidden_size, num_heads, kv_heads, head_dim, rope,
                 window=None, gate=None, qk_norm_epsilon=None,
                 norm_offset=0.0, qk_norm_over="head", axis_name=None):
        super().__init__()
        if num_heads % kv_heads:
            raise ValueError(f"{num_heads} query heads over {kv_heads} KV "
                             f"heads")
        if gate not in (None, "head", "elementwise"):
            raise ValueError(f"unknown attention gate {gate!r}")
        if qk_norm_over not in ("head", "whole"):
            raise ValueError(f"unknown QK norm width {qk_norm_over!r}")
        if qk_norm_over == "whole" and gate == "elementwise":
            raise ValueError("an elementwise gate beside a whole-width QK "
                             "norm is not built")
        self.gate, self.qk_norm_over = gate, qk_norm_over
        self.num_heads, self.kv_heads = num_heads, kv_heads
        self.head_dim, self.window = head_dim, window
        # rope: {"theta", "rotary_dim", "yarn" or None}, or None for no
        # rotation
        self.inv_freq = self.rope_scale = None
        if rope is not None:
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
        if qk_norm_epsilon is not None and qk_norm_over == "whole":
            self.q_norm = nn.RMSNorm(num_heads * head_dim, qk_norm_epsilon,
                                     offset=norm_offset, axis_name=axis_name)
            self.k_norm = nn.RMSNorm(kv_heads * head_dim, qk_norm_epsilon,
                                     offset=norm_offset, axis_name=axis_name)
        elif qk_norm_epsilon is not None:
            self.q_norm = nn.RMSNorm(head_dim, qk_norm_epsilon,
                                     offset=norm_offset)
            self.k_norm = nn.RMSNorm(head_dim, qk_norm_epsilon,
                                     offset=norm_offset)

    def _rope(self, x, positions, norm=None):
        if self.inv_freq is None:
            return x if norm is None else norm(x)
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
        if self.q_norm is not None and self.qk_norm_over == "whole":
            q, k, v, gate = self._whole_normed(x, positions)
        else:
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

    def _whole_normed(self, x, positions):
        """``q, k, v`` as heads (and no elementwise gate), q and k normed
        over all their lanes before the split into heads and then
        rotated."""
        b, s, _ = x.shape
        d = self.head_dim
        q, k = self.q_proj(x), self.k_proj(x)
        with jax.named_scope("qk_norm"):
            q, k = self.q_norm(q), self.k_norm(k)
        q = self._rope(jnp.reshape(q, (b, s, self.num_heads, d)), positions)
        k = self._rope(jnp.reshape(k, (b, s, self.kv_heads, d)), positions)
        v = jnp.reshape(self.v_proj(x), (b, s, self.kv_heads, d))
        return q, k, v, None


def _count_latent_call(rope_path: str):
    """``latent_attn_calls_staged_total{rope}``: one call of
    ``MultiHeadLatentAttention`` being staged, by the path its queries'
    rotation takes."""
    from ... import telemetry
    if telemetry.enabled():
        telemetry.counter(
            "latent_attn_calls_staged_total",
            "Staged calls of multi-head latent attention, by the path the "
            "rotation of its queries took").inc(1, rope=rope_path)


class MultiHeadLatentAttention(Layer):
    """Multi-head latent attention (DeepSeek-V2): queries and keys, values
    come from low-rank latents, each under an RMSNorm of its own, and the
    rotary part of a key is one head shared by all ``num_heads`` key heads.

    ::

        c_q  = q_a_norm(x W_dq)                    (s, q_lora_rank)
        q    = c_q W_uq      -> (s, heads, d_rope + d_nope)
        [c_kv | k_r] = x W_dkv  -> (s, kv_lora_rank), (s, d_rope)
        [k_nope | v] = kv_a_norm(c_kv) W_ukv -> (s, heads, d_nope + d_v)
        k_h  = [RoPE(k_r) | k_nope_h],  q_h = [RoPE(q_rope_h) | q_nope_h]
        o_h  = softmax(q_h k_h^T / sqrt(d_nope + d_rope) + causal) v_h
        y    = concat_h(o_h) W_o

    A head keeps its rotated lanes FIRST, ``[rope | nope]``, where the
    published checkpoints keep ``[nope | rope]``: the first lanes are the
    ones ``F.rotary_embedding`` turns, so a head of 256 lanes with 64
    rotated goes through the rotary kernel as it stands
    (``ops/pallas/rotary.py``), and a score is a sum over lanes, so the
    order changes no value as long as q and k share it. A published
    ``q_b_proj`` is loaded with each head's columns permuted
    (``benchmark/families/glm4moelite.py`` ``published_columns``); the
    other six tensors keep the published order (``kv_a_proj`` ``[c_kv |
    k_r]``, ``kv_b_proj`` ``[k_nope | v]`` a head). ``k_r`` is rotated
    once, as a head of its own, and then broadcast over the heads.

    Scopes: ``latent_q`` holds everything from ``x`` to the rotated ``q``,
    ``latent_kv`` everything from ``x`` to the assembled ``k`` and ``v``;
    ``sdpa`` and ``o_proj`` are their own. The flash kernels take one head
    width, so ``v_head_dim`` must equal ``qk_nope_head_dim +
    qk_rope_head_dim``; a window and the block-diffusion mask are not built
    for this layer. All three are refused with a ``ValueError``."""

    def __init__(self, hidden_size, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim, rope,
                 epsilon=1e-6, window=None):
        super().__init__()
        if window is not None:
            raise ValueError(
                "latent attention with a sliding window is not built: the "
                "layer is causal over the whole row")
        if v_head_dim != qk_nope_head_dim + qk_rope_head_dim:
            raise ValueError(
                f"v_head_dim {v_head_dim} != qk_nope_head_dim + "
                f"qk_rope_head_dim = {qk_nope_head_dim + qk_rope_head_dim}: "
                f"unequal key and value widths are not built (the flash "
                f"kernels take one head width)")
        self.num_heads, self.kv_lora_rank = num_heads, kv_lora_rank
        self.d_nope, self.d_rope, self.d_v = (
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
        # rope: {"theta", "yarn" or None}; all d_rope lanes turn
        self.inv_freq, self.rope_scale = F.rope_frequencies(
            rope["theta"], qk_rope_head_dim, rope.get("yarn"))
        self.q_a_proj = nn.Linear(hidden_size, q_lora_rank, bias_attr=False)
        self.q_a_norm = nn.RMSNorm(q_lora_rank, epsilon)
        self.q_b_proj = nn.Linear(
            q_lora_rank, num_heads * (qk_rope_head_dim + qk_nope_head_dim),
            bias_attr=False)
        self.kv_a_proj = nn.Linear(hidden_size,
                                   kv_lora_rank + qk_rope_head_dim,
                                   bias_attr=False)
        self.kv_a_norm = nn.RMSNorm(kv_lora_rank, epsilon)
        self.kv_b_proj = nn.Linear(
            kv_lora_rank, num_heads * (qk_nope_head_dim + v_head_dim),
            bias_attr=False)
        self.o_proj = nn.Linear(num_heads * v_head_dim, hidden_size,
                                bias_attr=False)

    def _rope(self, x, positions):
        return F.rotary_embedding(x, self.inv_freq, self.rope_scale,
                                  positions)

    def forward(self, x, positions=None, block_diffusion=None):
        if block_diffusion is not None:
            raise ValueError("latent attention under the block-diffusion "
                             "mask is not built")
        b, s, _ = x.shape
        h, dn, dr, dv = self.num_heads, self.d_nope, self.d_rope, self.d_v
        with jax.named_scope("latent_q"):
            q = self.q_b_proj(self.q_a_norm(self.q_a_proj(x)))
            q = jnp.reshape(q, (b, s, h, dr + dn))          # [rope | nope]
            _count_latent_call(rotary_path(q, self.inv_freq))
            q = self._rope(q, positions)
        with jax.named_scope("latent_kv"):
            kv_a = self.kv_a_proj(x)                        # [c_kv | k_r]
            c_kv = self.kv_a_norm(kv_a[..., :self.kv_lora_rank])
            k_r = self._rope(kv_a[..., None, self.kv_lora_rank:], positions)
            kv = jnp.reshape(self.kv_b_proj(c_kv), (b, s, h, dn + dv))
            k = jnp.concatenate(
                [jnp.broadcast_to(k_r, (b, s, h, dr)), kv[..., :dn]],
                axis=-1)
            v = kv[..., dn:]
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        return self.o_proj(jnp.reshape(out, (b, s, h * dv)))


class MixedDecoderBlock(Layer):
    """``h = x + mixer(norm(x)); y = h + ffn(norm(h))`` with the sublayers
    ``attn`` (softmax attention), ``linear_attn`` or ``mamba`` (the
    ``mixer_name``; these two take the hidden states alone) and ``mlp``
    (dense) or ``moe`` (sparse). A block of one sublayer (``mixer`` or
    ``ffn`` None) is ``y = x + sublayer(norm(x))`` under ``input_norm`` and
    has no ``post_attn_norm``. ``norm_placement="post"`` norms each
    sublayer's output instead, ``h = x + post_mixer_norm(mixer(x)); y = h +
    post_ffn_norm(ffn(h))``, and the block has neither of the other two."""

    def __init__(self, mixer: Layer, ffn: Layer, sparse: bool, hidden_size,
                 epsilon, norm_offset=0.0, mixer_name="attn",
                 norm_placement="pre"):
        super().__init__()
        if mixer is None and ffn is None:
            raise ValueError("a block needs a mixer or a feed-forward block")
        if norm_placement not in ("pre", "post"):
            raise ValueError(f"unknown norm placement {norm_placement!r}")
        self.post_norms = norm_placement == "post"

        def norm():
            return nn.RMSNorm(hidden_size, epsilon, offset=norm_offset)

        if not self.post_norms:
            self.input_norm = norm()
        self._mixer_name = mixer_name
        if mixer is not None:
            setattr(self, self._mixer_name, mixer)
            if self.post_norms:
                self.post_mixer_norm = norm()
            elif ffn is not None:
                self.post_attn_norm = norm()
        self._ffn_name = "moe" if sparse else "mlp"
        if ffn is not None:
            setattr(self, self._ffn_name, ffn)
            if self.post_norms:
                self.post_ffn_norm = norm()
        self.has_mixer, self.has_ffn = mixer is not None, ffn is not None

    def _mix(self, x, positions, block_diffusion):
        mixer = getattr(self, self._mixer_name)
        if self._mixer_name != "attn":
            return mixer(x)
        return mixer(x, positions, block_diffusion)

    def mixer_half(self, x, positions=None, block_diffusion=None):
        if self.post_norms:
            return x + self.post_mixer_norm(
                self._mix(x, positions, block_diffusion))
        return x + self._mix(self.input_norm(x), positions, block_diffusion)

    def ffn_half(self, x):
        ffn = getattr(self, self._ffn_name)
        if self.post_norms:
            return x + self.post_ffn_norm(ffn(x))
        norm = self.post_attn_norm if self.has_mixer else self.input_norm
        return x + ffn(norm(x))

    def forward(self, x, positions=None, block_diffusion=None):
        if self.has_mixer:
            x = self.mixer_half(x, positions, block_diffusion)
        return self.ffn_half(x) if self.has_ffn else x


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

    ``layer_types[i]`` is ``"full_attention"``, ``"sliding_attention"``,
    ``"linear_attention"`` (then ``linear_attention`` holds
    ``nn.GatedDeltaNet``'s ``key_heads``, ``value_heads``, ``d_k``,
    ``d_v`` and ``conv_kernel``, and the layer's
    entry in ``heads_per_layer`` is not read), ``"latent_attention"``
    (then ``latent_attention`` holds ``MultiHeadLatentAttention``'s
    ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim`` and ``v_head_dim``, ``rope["latent_attention"]``
    its ``theta`` and optionally ``yarn``, and ``kv_heads`` and
    ``head_dim`` are not read), ``"mamba"`` (then ``mamba`` holds
    ``nn.Mamba2Mixer``'s keyword arguments but ``hidden_size`` and
    ``epsilon``, and ``heads_per_layer[i]`` is not read) or None (the layer
    has no mixer), ``heads_per_layer[i]`` the
    layer's query heads, ``mlp_layer_types[i]`` ``"dense"``, ``"sparse"``
    or None (no feed-forward block; a layer has a mixer or a feed-forward
    block or both) (``router_scoring`` ``"sigmoid"`` or ``"softmax"``;
    ``expert_activation`` the experts' form, ``"silu"`` or ``"relu2"``;
    ``shared_expert_size`` 0 for no shared expert, ``shared_expert_gate``
    for a sigmoid weight a token on it; ``router_attr`` the routers'
    ``ParamAttr``; ``router_selection_bias`` for the buffer
    ``e_score_correction_bias`` a router, added to the scores that choose
    the experts and left out of their weights).
    ``make_block(mixer_kind, ffn_kind, heads)`` builds one more block of the
    model's sizes (the multi-token-prediction module's). ``attention_gate`` is ``"head"`` (``gated_attention=
    True`` says the same), ``"elementwise"`` or None. ``qk_norm``: an
    RMSNorm over the head width on q and k, the model's ``epsilon``;
    ``"whole"``: one over all of q's lanes and one over all of k's.
    ``norm_offset=1`` makes every RMSNorm weight zero-centred (scale ``1 +
    w``). ``norm_placement``: ``"pre"`` norms a sublayer's input, ``"post"``
    its output (``MixedDecoderBlock``); the final norm is there in both.
    ``rope[kind]`` holds ``theta``, ``rotary_dim`` and optionally ``yarn``
    for each kind of attention, or None for no rotation.
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
                 linear_attention=None, norm_offset=0.0,
                 latent_attention=None, router_selection_bias=False,
                 mamba=None, expert_activation="silu",
                 norm_placement="pre"):
        super().__init__()
        if not (len(layer_types) == len(heads_per_layer)
                == len(mlp_layer_types)):
            raise ValueError("the three per-layer lists differ in length")
        if gated_attention:
            attention_gate = attention_gate or "head"
        self.has_recurrent_layers = bool({LINEAR, MAMBA} & set(layer_types))
        self.hidden_size, self.epsilon = hidden_size, epsilon
        self.checkpoint_blocks = checkpoint_blocks
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=embedding_attr)

        def block(kind, ffn_kind, heads):
            if kind not in (FULL, SLIDING, LINEAR, LATENT, MAMBA, None) \
                    or ffn_kind not in (DENSE, SPARSE, None):
                raise ValueError(f"unknown layer kinds {kind!r}, {ffn_kind!r}")
            if kind is None:
                mixer = None
            elif kind == MAMBA:
                mixer = nn.Mamba2Mixer(hidden_size, epsilon=epsilon, **mamba)
            elif kind == LINEAR:
                mixer = nn.GatedDeltaNet(hidden_size, epsilon=epsilon,
                                         **linear_attention)
            elif kind == LATENT:
                mixer = MultiHeadLatentAttention(
                    hidden_size, heads, rope=rope[kind], epsilon=epsilon,
                    **latent_attention)
            else:
                mixer = GroupedQueryAttention(
                    hidden_size, heads, kv_heads, head_dim, rope[kind],
                    window=sliding_window if kind == SLIDING else None,
                    gate=attention_gate,
                    qk_norm_epsilon=epsilon if qk_norm else None,
                    norm_offset=norm_offset,
                    qk_norm_over="whole" if qk_norm == "whole" else "head")
            if ffn_kind == SPARSE:
                ffn = DroplessMoELayer(
                    hidden_size, expert_size, num_experts, experts_per_token,
                    held=held_experts,
                    routed_scaling_factor=routed_scaling_factor,
                    scoring=router_scoring,
                    d_shared=shared_expert_size or None,
                    router_attr=router_attr,
                    shared_expert_gate=shared_expert_gate,
                    selection_bias=router_selection_bias,
                    activation=expert_activation)
            elif ffn_kind == DENSE:
                ffn = nn.GatedSiluFFN(hidden_size, intermediate_size)
            else:
                ffn = None
            return MixedDecoderBlock(
                mixer, ffn, ffn_kind == SPARSE, hidden_size, epsilon,
                norm_offset=norm_offset, mixer_name={
                    LINEAR: "linear_attn", MAMBA: "mamba"}.get(kind, "attn"),
                norm_placement=norm_placement)

        self.make_block = block
        self.h = nn.LayerList([
            block(kind, ffn_kind, heads) for kind, heads, ffn_kind in zip(
                layer_types, heads_per_layer, mlp_layer_types)])
        self.last_layer = (layer_types[-1], mlp_layer_types[-1],
                           heads_per_layer[-1])
        self.norm = nn.RMSNorm(hidden_size, epsilon, offset=norm_offset)
        self._norm_offset = norm_offset

    def blocks(self, input_ids, positions=None, block_diffusion=None):
        """The last block's output, before the final norm."""
        if self.has_recurrent_layers and not (positions is None
                                           and block_diffusion is None):
            raise ValueError(
                "a model with linear_attention or mamba layers runs a "
                "recurrence along the row: it takes no explicit positions "
                "and no block-diffusion mask")
        x = self.embed_tokens(input_ids)
        for block in self.h:
            x = self.run_block(block, x, positions, block_diffusion)
        return x

    def run_block(self, block, x, positions=None, block_diffusion=None):
        """``block(x, ...)``, its two halves recomputed apart in the
        backward pass under ``checkpoint_blocks`` (a block of one sublayer
        under one checkpoint)."""
        if not self.checkpoint_blocks:
            return block(x, positions, block_diffusion)
        # under the scope block(...) would open
        with jax.named_scope(block._scope_name):
            if block.has_mixer:
                x = _call_checkpointed(block, block.mixer_half, x, positions,
                                       block_diffusion)
            if block.has_ffn:
                x = _call_checkpointed(block, block.ffn_half, x)
            return x

    def forward(self, input_ids, positions=None, block_diffusion=None):
        return self.norm(self.blocks(input_ids, positions, block_diffusion))


class MultiTokenPrediction(Layer):
    """One multi-token-prediction module (DeepSeek-V3, depth 1): from the
    trunk's normed output ``hidden`` at position ``i`` and the embedding of
    token ``i + 1``, the state the head turns into a prediction of token
    ``i + 2``::

        z = [enorm(embedded) | hnorm(hidden)] W_eh ;  norm(block(z))

    ``block`` is one more block of the decoder's last kind
    (``decoder.make_block``), causal over the row and checkpointed in
    halves like the trunk's under ``checkpoint_blocks``. The embedding and
    the head are the trunk's own tensors and are not sublayers here: the
    caller embeds and applies the head."""

    def __init__(self, decoder: "MixedDecoderModel"):
        super().__init__()
        hidden, eps = decoder.hidden_size, decoder.epsilon
        offset = decoder._norm_offset
        self.enorm = nn.RMSNorm(hidden, eps, offset=offset)
        self.hnorm = nn.RMSNorm(hidden, eps, offset=offset)
        self.eh_proj = nn.Linear(2 * hidden, hidden, bias_attr=False)
        self.block = decoder.make_block(*decoder.last_layer)
        self.norm = nn.RMSNorm(hidden, eps, offset=offset)
        self._run_block = decoder.run_block

    def forward(self, embedded, hidden):
        z = self.eh_proj(jnp.concatenate(
            [self.enorm(embedded), self.hnorm(hidden)], axis=-1))
        return self.norm(self._run_block(self.block, z))


class MixedDecoderForPretraining(Layer):
    """Trunk and an untied head: ``forward(input_ids)`` gives the logits.

    With ``mtp_layers=1`` the model also holds a multi-token-prediction
    module (sublayer ``mtp``) and computes its own loss: ``forward`` takes
    the pair ``(input_ids, labels)`` (``labels[i]`` the token after
    ``input_ids[i]``: the trainer's ``inputs`` is this pair and its
    ``loss_fn`` the identity) and returns ``CE(head(N_i), labels_i)``
    averaged over all ``L`` positions ``+ mtp_loss_weight x
    CE(head(mtp(Emb(labels_i), N_i)), labels_{i+1})`` averaged over the
    ``L - 1`` positions that have a token after the next, ``N`` the
    trunk's normed output. The module runs over all ``L`` positions (the
    kernels keep their shapes) and the last is left out of the mean.
    Embedding and head are the trunk's, used twice a step; both heads are
    staged under ``lm_head`` and both cross-entropies under ``loss``. The
    two terms leave a jitted ``functional_call`` in the buffers
    ``mtp_main_loss`` and ``mtp_next_loss``; ``publish_losses`` writes them
    to gauges of those names. ``mtp_layers=0`` (the default) builds and
    stages what the class did before it had the option."""

    def __init__(self, decoder: MixedDecoderModel = None, mtp_layers=0,
                 mtp_loss_weight=0.3, **kwargs):
        super().__init__()
        if mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers={mtp_layers}: a chain of "
                             f"prediction modules is not built, one is")
        self.decoder = decoder or MixedDecoderModel(**kwargs)
        vocab = self.decoder.embed_tokens.num_embeddings
        self.lm_head = nn.Linear(self.decoder.hidden_size, vocab,
                                 bias_attr=False)
        self.mtp, self.mtp_loss_weight = None, mtp_loss_weight
        if mtp_layers:
            self.mtp = MultiTokenPrediction(self.decoder)
            for name in ("mtp_main_loss", "mtp_next_loss"):
                self.register_buffer(name, jnp.zeros((), jnp.float32),
                                     persistable=False)

    def forward(self, input_ids):
        if self.mtp is None:
            return self.lm_head(self.decoder(input_ids))
        input_ids, labels = input_ids
        hidden = self.decoder(input_ids)
        logits = self.lm_head(hidden)
        with jax.named_scope("loss"):
            main = F.cross_entropy(logits, labels)
        z = self.mtp(self.decoder.embed_tokens(labels), hidden)
        logits = self.lm_head(z)
        with jax.named_scope("loss"):
            # position i predicts labels[i + 1]; the last has none
            after = jnp.concatenate(
                [labels[:, 1:], jnp.full_like(labels[:, :1], -100)], axis=1)
            ahead = F.cross_entropy(logits, after, ignore_index=-100)
            self.mtp_main_loss, self.mtp_next_loss = main, ahead
            return main + self.mtp_loss_weight * ahead

    def publish_losses(self, buffers=None, prefix="", **labels):
        """The last call's two loss terms into the telemetry registry
        (``buffers`` as ``DroplessMoELayer.publish_routing`` takes them)."""
        from ... import telemetry
        src = buffers if buffers is not None else dict(self.named_buffers())
        telemetry.gauge(
            "mtp_main_loss",
            "next-token cross entropy of the last call, unweighted").set(
                float(src[prefix + "mtp_main_loss"]), **labels)
        telemetry.gauge(
            "mtp_next_loss",
            "token-after-next cross entropy (the multi-token-prediction "
            "module's) of the last call, unweighted").set(
                float(src[prefix + "mtp_next_loss"]), **labels)


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
