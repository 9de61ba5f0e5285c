"""The ``olmohybrid`` family: Olmo-Hybrid decoders (Gated DeltaNet layers
three to one with full attention, dense feed-forward blocks, Olmo 2's
reordered norms and whole-width QK norm) through the program's
``text/models/mixed_decoder.py`` (``norm_placement="post"``,
``qk_norm="whole"``, ``layer_types`` with ``"linear_attention"``),
``nn.GatedDeltaNet(allow_neg_eigval=True)`` and ``ParallelTrainer``.

The configuration file carries the published ``config.json`` keys. Some of
them are the chip's share of a deployment and not the published values
(``reduced``): ``num_hidden_layers`` (the first ``num_hidden_layers``
entries of the published ``layer_types`` are built), the four head counts
(the heads HELD here: a layer is shared by ``deployment.
chips_sharing_a_layer`` chips, tensor-parallel) and ``vocab_size`` (the
slice of the embedding and the head held here). ``intermediate_size`` keeps
the published width; the feed-forward columns held here are
``deployment.ffn_columns_held``, that width over the chips sharing a layer.

What the harness asks of a family is what ``families/gpt.py`` gives; the
trainer-side half of ``Built`` is that file's, the column mapping of the
fused projections ``families/qwen3next.py``'s idea, and the mapping onto the
reference and the counts are this one's.
"""
from __future__ import annotations

import json

import numpy as np

from benchmark.families import gpt

REFERENCE = "olmohybrid"
LINEAR, FULL = "linear_attention", "full_attention"
HEAD_COUNTS = ("num_attention_heads", "num_key_value_heads",
               "linear_num_key_heads", "linear_num_value_heads")


# -- the configuration, read one way -----------------------------------------

def arch(config) -> dict:
    """What the reference is given (under ``n_head``) and the counts below
    are made from."""
    n, every = config["num_hidden_layers"], config["full_attention_interval"]
    layers = config["layer_types"][:n]
    if layers != [FULL if (i + 1) % every == 0 else LINEAR
                  for i in range(n)]:
        raise ValueError("layer_types is not full_attention_interval's "
                         "pattern")
    chips = config["deployment"]["chips_sharing_a_layer"]
    published = config["published"]
    for key in HEAD_COUNTS:
        if config[key] * chips != published[key]:
            raise ValueError(f"{key} must be the heads held here, "
                             f"{published[key]} over {chips} chips")
    if config["deployment"]["ffn_columns_held"] * chips \
            != config["intermediate_size"]:
        raise ValueError("ffn_columns_held must be intermediate_size over "
                         "the chips sharing a layer")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"] \
            or config["num_attention_heads"] != config[
                "num_key_value_heads"]:
        raise ValueError("this family wires as many key as value heads")
    if config["rope_parameters"].get("rope_theta") is not None:
        raise ValueError("this family wires attention without rotation")
    return {
        "layers": layers,
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        # the config has no head_dim: hidden over the published heads
        "head_dim": config["hidden_size"]
        // published["num_attention_heads"],
        "linear": {"heads": config["linear_num_value_heads"],
                   "d_k": config["linear_key_head_dim"],
                   "d_v": config["linear_value_head_dim"],
                   "conv_kernel": config["linear_conv_kernel_dim"]},
        "allow_neg_eigval": bool(config["linear_allow_neg_eigval"]),
        "ffn_columns": config["deployment"]["ffn_columns_held"]}


# -- counted from the configuration's shapes --------------------------------

def layer_params(config, kind) -> dict:
    """Parameters of one layer of ``kind``, by part; ``PRODUCTS`` lists the
    parts a token meets in a matrix product."""
    h, a = config["hidden_size"], arch(config)
    if kind == LINEAR:
        lin = a["linear"]
        key, value = lin["heads"] * lin["d_k"], lin["heads"] * lin["d_v"]
        mixer = {"in_proj_qkvz": h * (2 * key + 2 * value),
                 "in_proj_ba": h * 2 * lin["heads"],
                 "conv": (2 * key + value) * lin["conv_kernel"],
                 "decay": 2 * lin["heads"],             # A_log, dt_bias
                 "mixer_norms": lin["d_v"],
                 "out_proj": value * h}
    else:
        d = a["head_dim"]
        q, kv = a["heads"] * d, a["kv_heads"] * d
        mixer = {"q_proj": h * q, "kv_proj": 2 * h * kv,
                 "mixer_norms": q + kv,                 # the QK norm
                 "o_proj": q * h}
    return {**mixer, "norms": 2 * h, "mlp": 3 * h * a["ffn_columns"]}


PRODUCTS = ("in_proj_qkvz", "in_proj_ba", "out_proj", "q_proj", "kv_proj",
            "o_proj", "mlp")


def param_count(config) -> int:
    """All parameters held here: the built layers at the chip's share, the
    embedding's and the head's slice, the final norm."""
    h = config["hidden_size"]
    return (sum(sum(layer_params(config, kind).values())
                for kind in arch(config)["layers"])
            + 2 * config["vocab_size"] * h + h)


def model_flops_per_token(config, seq: int) -> dict:
    """Forward + backward operations one token needs here.

    ``six_n``: 6 x the parameters a token meets in a matrix product: every
    layer's mixer projections and feed-forward block at the chip's share,
    and the head's slice (the embedding is a lookup, the convolution four
    multiplications a channel). ``attention``: scores and values of the
    full layers at the causal half, ``12 x heads x head width x (seq + 1) /
    2``. ``recurrence``: the rule as its definition states it, three ``d_k
    x d_v`` products a head a position (``6 d_k d_v`` operations forward,
    three times that with the backward pass), in the linear layers. Not the
    chunked algorithm's further products and no recomputation."""
    a = arch(config)
    met = config["vocab_size"] * config["hidden_size"]
    attention = recurrence = 0.0
    lin = a["linear"]
    for kind in a["layers"]:
        parts = layer_params(config, kind)
        met += sum(parts.get(k, 0) for k in PRODUCTS)
        if kind == FULL:
            attention += 12 * a["heads"] * a["head_dim"] * (seq + 1) / 2
        else:
            recurrence += 3 * lin["heads"] * 6 * lin["d_k"] * lin["d_v"]
    six_n = 6 * met
    return {"total": six_n + attention + recurrence, "six_n": six_n,
            "attention": attention, "recurrence": recurrence}


def toy(config) -> dict:
    """The same code at a size the CPU walks in seconds (rehearsal and unit
    tests only; never a cell): one period (three linear layers and the full
    one), 2 of 4 heads a mixer held, head widths 12 / 24 (key / value) and 16
    (attention)."""
    out = json.loads(json.dumps(config))
    out.update(num_hidden_layers=4, hidden_size=64, num_attention_heads=2,
               num_key_value_heads=2, linear_num_key_heads=2,
               linear_num_value_heads=2, linear_key_head_dim=12,
               linear_value_head_dim=24, intermediate_size=96,
               vocab_size=512, vocab_used=512, eos_token_id=2)
    # float32, and a learning rate at which the rehearsal's six steps
    # (warm-up and two sync groups of two) move the toy's loss past the
    # rehearsal's margin: at 1e-5 ten toy steps move no loss, at the GPT
    # cells' 3e-4 six move it 0.08 nats
    out["run"] = dict(out["run"], param_dtype="float32", optimizer=dict(
        out["run"]["optimizer"], learning_rate=1e-3))
    out["published"] = dict(out["published"], **{k: 4 for k in HEAD_COUNTS})
    out["deployment"] = dict(out["deployment"], chips_sharing_a_layer=2,
                             ffn_columns_held=48)
    return out


# -- the program's model and trainer -----------------------------------------

TOP = {"decoder.embed_tokens.weight": "embed", "decoder.norm.weight":
       "norm_g", "lm_head.weight": "lm_head"}
IN_BLOCK = {"post_mixer_norm.weight": "post_mixer_norm_g",
            "post_ffn_norm.weight": "post_ffn_norm_g",
            "linear_attn.A_log": "a_log",
            "linear_attn.dt_bias": "dt_bias",
            "linear_attn.norm.weight": "out_norm_g",
            "linear_attn.out_proj.weight": "out_w",
            "attn.q_proj.weight": "q_w", "attn.k_proj.weight": "k_w",
            "attn.v_proj.weight": "v_w", "attn.q_norm.weight": "q_norm_g",
            "attn.k_norm.weight": "k_norm_g", "attn.o_proj.weight": "o_w",
            "mlp.gate_proj.weight": "gate_w", "mlp.up_proj.weight": "up_w",
            "mlp.down_proj.weight": "down_w"}
# the program's fused leaves of a linear layer and the published tensors
# they hold side by side, along the last axis (the projections' columns) or
# the first (the convolution's channels)
FUSED = {"linear_attn.in_proj_qkvz.weight": ("q_w", "k_w", "v_w", "z_w"),
         "linear_attn.in_proj_ba.weight": ("b_w", "a_w"),
         "linear_attn.conv_weight": ("conv_q_w", "conv_k_w", "conv_v_w")}


class Built(gpt.Built):
    """``families/gpt.py``'s ``Built`` over the mixed decoder: the step's
    arguments, the loss path, its gradients and the leaf selection (``"all"``
    here) are shared; the mapping onto ``reference/olmohybrid.py`` is this
    model's."""

    def __init__(self, config, *rest):
        # compare.py reads the reference's two keywords from here
        super().__init__(dict(config, n_head=arch(config),
                              layer_norm_epsilon=config["rms_norm_eps"]),
                         *rest)

    def to_reference(self, leaves) -> dict:
        """Program leaves (parameters or their gradients, any subset of
        whole blocks) in the reference's structure, dtype unchanged. Both
        sides keep a product's weight as (in, out); a linear layer's fused
        leaves are cut into the published tensors (``FUSED``: ``[q | k | v
        | z]`` and ``[b | a]`` by columns, each head's lanes together as the
        published projections keep them, the convolution's ``[q | k | v]``
        channels by rows)."""
        lin = self.config["n_head"]["linear"]
        key, value = lin["heads"] * lin["d_k"], lin["heads"] * lin["d_v"]
        widths = {"q_w": key, "k_w": key, "v_w": value, "z_w": value,
                  "b_w": lin["heads"], "a_w": lin["heads"],
                  "conv_q_w": key, "conv_k_w": key, "conv_v_w": value}
        out, blocks = {}, {}
        for name, v in leaves.items():
            if name in TOP:
                out[TOP[name]] = v
                continue
            _, _, idx, rest = name.split(".", 3)        # decoder.h.<i>.<rest>
            block = blocks.setdefault(int(idx), {})
            if rest not in FUSED:
                block[IN_BLOCK[rest]] = v
                continue
            start = 0
            for part in FUSED[rest]:
                cut = slice(start, start + widths[part])
                block[part] = v[cut] if rest.endswith("conv_weight") \
                    else v[..., cut]
                start += widths[part]
        out["blocks"] = blocks
        return out


def build(config, recipe, seed: int, mesh) -> Built:
    """Model, optimizer and ``ParallelTrainer`` as a user builds them; the
    constructors run inside one jitted call under ``rng_guard`` (see
    ``families/gpt.py``), so the weights are made on the device from
    ``seed`` in the dtype they train in."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.engine import ParallelTrainer
    from paddle_tpu.framework.random import rng_guard
    from paddle_tpu.jit.functionalization import state_of
    from paddle_tpu.text.models import MixedDecoderForPretraining

    a = arch(config)
    n = len(a["layers"])
    lin = a["linear"]
    holder = {}

    def construct(key):
        with rng_guard(key):
            model = MixedDecoderForPretraining(
                vocab_size=config["vocab_size"],
                hidden_size=config["hidden_size"],
                layer_types=a["layers"],
                heads_per_layer=[a["heads"]] * n,
                mlp_layer_types=["dense"] * n,
                kv_heads=a["kv_heads"], head_dim=a["head_dim"],
                rope={FULL: None}, sliding_window=None,
                intermediate_size=a["ffn_columns"],
                qk_norm="whole", norm_placement="post",
                linear_attention={
                    "key_heads": lin["heads"], "value_heads": lin["heads"],
                    "d_k": lin["d_k"], "d_v": lin["d_v"],
                    "conv_kernel": lin["conv_kernel"],
                    "allow_neg_eigval": a["allow_neg_eigval"]},
                epsilon=config["rms_norm_eps"],
                checkpoint_blocks=recipe["checkpoint_blocks"],
                embedding_attr=nn.initializer.Normal(
                    0.0, recipe["embedding_std"]))
            model.astype(recipe["param_dtype"])
        holder["model"] = model
        return dict(state_of(model)[0])

    init_fn = jax.jit(construct)
    values = init_fn(jax.random.key(seed))
    model = holder["model"]
    for name, box in model.named_parameters():
        box.value = values[name]
    built = sum(int(np.prod(v.shape)) for v in values.values())
    if built != param_count(config):
        raise ValueError(f"the program built {built} parameters, the "
                         f"configuration's shapes give {param_count(config)}")

    o = recipe["optimizer"]
    if o["name"] != "AdamW" or recipe["loss_path"] != "dense":
        raise ValueError("this family wires AdamW and the dense loss path")
    opt = paddle.optimizer.AdamW(o["learning_rate"],
                                 parameters=model.parameters(),
                                 slot_dtype=o.get("slot_dtype"))

    def loss_fn(logits, labels):
        return nn.functional.cross_entropy(logits, labels)

    trainer = ParallelTrainer(model, opt, loss_fn, mesh=mesh,
                              remat=recipe["remat"])
    return Built(config, recipe, trainer, model, model, loss_fn, init_fn)
