"""The ``glm4moelite`` family: GLM-4.7-Flash style decoders (multi-head
latent attention in every layer, a dense first layer and sparse layers whose
sigmoid router chooses by a bias it does not weigh by, a shared expert, and a
multi-token-prediction module that shares embedding and head) through the
program's ``text/models/mixed_decoder.py`` (``"latent_attention"`` layers,
``router_selection_bias``, ``mtp_layers``) and ``ParallelTrainer``.

The configuration file carries the published ``config.json`` keys. Three of
them are the chip's share of a deployment and not the published values
(``reduced``): ``num_hidden_layers`` (the first ``first_k_dense_replace``
are dense, the others sparse; the multi-token-prediction module, published
as the layer after the last, is built besides), ``n_routed_experts`` (the
experts HELD here, ``deployment.held_experts``; the router keeps the
published width, ``published.n_routed_experts``) and ``vocab_size`` (the
slice of the embedding and the head held here).

What the harness asks of a family is what ``families/gpt.py`` gives; the
trainer-side half of ``Built`` is that file's, the reading of the routers
``families/laguna.py``'s, and the mapping onto the reference, the step's
arguments (the model takes the labels and returns its loss), the routing
report and the counts are this one's.
"""
from __future__ import annotations

import json
import sys

import numpy as np

from benchmark.families import gpt, laguna

REFERENCE = "glm4moelite"
LATENT = "latent_attention"


# -- the configuration, read one way -----------------------------------------

def arch(config) -> dict:
    """What the reference is given (under ``n_head``) and the counts below
    are made from. ``ffn`` lists the trunk's layers; the module's block is
    sparse."""
    first, count = config["deployment"]["held_experts"]
    if count != config["n_routed_experts"]:
        raise ValueError("n_routed_experts must be the number of experts "
                         "held")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("a group-limited router is not built")
    if config["topk_method"] != "noaux_tc" or not config["norm_topk_prob"]:
        raise ValueError("this family wires the bias-corrected router with "
                         "normalised weights")
    if config["rope_scaling"] is not None \
            or config["partial_rotary_factor"] != 1:
        raise ValueError("this family rotates all qk_rope_head_dim lanes, "
                         "unscaled")
    n = config["num_hidden_layers"]
    return {
        "ffn": ["dense" if i < config["first_k_dense_replace"] else "sparse"
                for i in range(n)],
        "heads": config["num_attention_heads"],
        "q_lora_rank": config["q_lora_rank"],
        "kv_lora_rank": config["kv_lora_rank"],
        "d_nope": config["qk_nope_head_dim"],
        "d_rope": config["qk_rope_head_dim"],
        "d_v": config["v_head_dim"],
        "rope_theta": config["rope_theta"],
        "top_k": config["num_experts_per_tok"],
        "router_width": config["published"]["n_routed_experts"],
        "routed_scaling_factor": config["routed_scaling_factor"],
        "held": (first, count),
        "mtp_layers": config["num_nextn_predict_layers"],
        "mtp_loss_weight": config["mtp_loss_weight"],
        "selection_bias": None}


# -- counted from the configuration's shapes --------------------------------

def layer_params(config, ffn: str) -> dict:
    """Parameters of one layer whose feed-forward block is ``ffn``, by
    part."""
    h, a = config["hidden_size"], arch(config)
    heads, dn, dr, dv = a["heads"], a["d_nope"], a["d_rope"], a["d_v"]
    rq, rkv = a["q_lora_rank"], a["kv_lora_rank"]
    out = {"q_a_proj": h * rq, "q_b_proj": rq * heads * (dn + dr),
           "kv_a_proj": h * (rkv + dr), "kv_b_proj": rkv * heads * (dn + dv),
           "o_proj": heads * dv * h, "latent_norms": rq + rkv,
           "norms": 2 * h}
    if ffn == "sparse":
        out.update(
            router=h * a["router_width"],
            shared=3 * h * config["moe_intermediate_size"]
            * config["n_shared_experts"],
            experts=config["n_routed_experts"] * 3 * h
            * config["moe_intermediate_size"])
    else:
        out["mlp"] = 3 * h * config["intermediate_size"]
    return out


PRODUCTS = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj",
            "router", "shared", "mlp")


def mtp_params(config) -> dict:
    """Parameters of the multi-token-prediction module, by part: its sparse
    block, ``eh_proj`` and its three norms (embedding and head are the
    trunk's)."""
    h = config["hidden_size"]
    if not config["num_nextn_predict_layers"]:
        return {}
    return {"block": sum(layer_params(config, "sparse").values()),
            "eh_proj": 2 * h * h, "mtp_norms": 3 * h}


def param_count(config) -> int:
    """All parameters held here: the built layers with the held experts,
    the module, the embedding's and the head's slice, the final norm. The
    routers' biases are buffers and not counted."""
    h = config["hidden_size"]
    return (sum(sum(layer_params(config, ffn).values())
                for ffn in arch(config)["ffn"])
            + sum(mtp_params(config).values())
            + 2 * config["vocab_size"] * h + h)


def model_flops_per_token(config, seq: int) -> dict:
    """Forward + backward operations one token needs here.

    ``six_n``: 6 x the parameters a token meets in a matrix product: every
    layer's five attention projections, the dense MLP, routers, shared
    experts, the held experts at their expectation (``experts_per_token x
    held / router width`` experts a token a sparse layer: 0.5 here), the
    module's block and ``eh_proj``, and the head's slice once a loss term
    (the embedding is a lookup). ``attention``: scores and values at the
    causal half, ``12 x heads x head width x (seq + 1) / 2`` a layer, the
    module's among them. Recomputation is not counted."""
    a = arch(config)
    expected = a["top_k"] * a["held"][1] / a["router_width"]
    expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    layers = a["ffn"] + ["sparse"] * a["mtp_layers"]
    met = (1 + a["mtp_layers"]) * config["vocab_size"] \
        * config["hidden_size"] + mtp_params(config).get("eh_proj", 0)
    for ffn in layers:
        parts = layer_params(config, ffn)
        met += sum(parts.get(k, 0) for k in PRODUCTS)
        if ffn == "sparse":
            met += expected * expert
    attention = len(layers) * 12 * a["heads"] * (a["d_nope"] + a["d_rope"]) \
        * (seq + 1) / 2
    six_n = 6 * met
    return {"total": six_n + attention, "six_n": six_n,
            "attention": attention}


def toy(config) -> dict:
    """The same code at a size the CPU walks in seconds (rehearsal and unit
    tests only; never a cell): a dense and two sparse layers and the
    module, 4 of 16 experts held, 2 a token."""
    out = json.loads(json.dumps(config))
    out.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
               qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
               intermediate_size=128, moe_intermediate_size=32,
               n_routed_experts=4, num_experts_per_tok=2, vocab_size=512,
               vocab_used=512, eos_token_id=2)
    # float32 and the GPT cells' learning rate, for families/laguna.py's
    # reasons: bf16 turns a routing choice in a hundred, a large part of a
    # toy expert's load, and ten toy steps at 1e-5 move no loss
    out["run"] = dict(out["run"], param_dtype="float32", optimizer=dict(
        out["run"]["optimizer"], learning_rate=3e-4))
    out["published"] = dict(out["published"], n_routed_experts=16)
    out["deployment"] = dict(out["deployment"], chips_sharing_a_layer=4,
                             held_experts=[4, 4])
    return out


# -- the program's model and trainer -----------------------------------------

TOP = {"decoder.embed_tokens.weight": "embed", "decoder.norm.weight":
       "norm_g", "lm_head.weight": "lm_head"}
IN_BLOCK = {"input_norm.weight": "norm1_g",
            "post_attn_norm.weight": "norm2_g",
            "attn.q_a_proj.weight": "q_a_w",
            "attn.q_a_norm.weight": "q_a_norm_g",
            "attn.q_b_proj.weight": "q_b_w",
            "attn.kv_a_proj.weight": "kv_a_w",
            "attn.kv_a_norm.weight": "kv_a_norm_g",
            "attn.kv_b_proj.weight": "kv_b_w",
            "attn.o_proj.weight": "o_w",
            "mlp.gate_proj.weight": "gate_w", "mlp.up_proj.weight": "up_w",
            "mlp.down_proj.weight": "down_w",
            "moe.router.weight": "router_w",
            "moe.shared_expert.gate_proj.weight": "shared_gate_w",
            "moe.shared_expert.up_proj.weight": "shared_up_w",
            "moe.shared_expert.down_proj.weight": "shared_down_w",
            "moe.experts.gate_proj": "experts_gate_w",
            "moe.experts.up_proj": "experts_up_w",
            "moe.experts.down_proj": "experts_down_w"}
IN_MTP = {"enorm.weight": "enorm_g", "hnorm.weight": "hnorm_g",
          "eh_proj.weight": "eh_w", "norm.weight": "mtp_norm_g"}


def published_columns(heads: int, d_nope: int, d_rope: int) -> np.ndarray:
    """For ``q_b_proj``, the program's column that stands at each of the
    published checkpoint's (and the reference's): the program keeps a
    head's lanes ``[rope | nope]`` (the rotated lanes first, what the
    rotary kernel turns), the publication ``[nope | rope]``."""
    d = d_nope + d_rope
    one = np.concatenate([np.arange(d_nope) + d_rope, np.arange(d_rope)])
    return (np.arange(heads)[:, None] * d + one[None]).reshape(-1)


class Built(laguna.Built):
    """``families/laguna.py``'s ``Built`` (the loss path and its gradients,
    the leaf selection, how the routers are read as the layers run) with
    this model's step arguments (the model takes ``(ids, labels)`` and
    returns its loss), its mapping onto ``reference/glm4moelite.py``, the
    routers' biases handed to the reference, and its routing report."""

    def __init__(self, config, *rest):
        # compare.py reads the reference's two keywords from here
        gpt.Built.__init__(
            self, dict(config, n_head=arch(config),
                       layer_norm_epsilon=config["rms_norm_eps"]), *rest)

    def step_args(self, ids, labels):
        """The model takes the labels beside the ids (its second term
        embeds them) and returns its loss."""
        return (ids, labels), 0.0

    def to_reference(self, leaves) -> dict:
        """Program leaves (parameters or their gradients, any subset of
        whole blocks) in the reference's structure, dtype unchanged. Both
        sides keep a product's weight as (in, out) and the experts stacked;
        the names differ, the module is the block after the last, and
        ``q_b_proj``'s columns are put in the published order."""
        a = self.config["n_head"]
        columns = published_columns(a["heads"], a["d_nope"], a["d_rope"])
        module = len(a["ffn"])
        out, blocks = {}, {}
        for name, v in leaves.items():
            if name in TOP:
                out[TOP[name]] = v
                continue
            if name.startswith("mtp."):
                idx, rest = module, name[len("mtp."):]
                if rest in IN_MTP:
                    blocks.setdefault(idx, {})[IN_MTP[rest]] = v
                    continue
                rest = rest[len("block."):]
            else:
                _, _, idx, rest = name.split(".", 3)    # decoder.h.<i>.<rest>
            ref = IN_BLOCK[rest]
            if ref == "q_b_w":
                v = v[..., columns]
            blocks.setdefault(int(idx), {})[ref] = v
        out["blocks"] = blocks
        return out

    def selection_biases(self):
        """The routers' ``e_score_correction_bias``, a sparse block in
        order, as the model holds them (what ``_loss`` runs with)."""
        return [np.asarray(m.e_score_correction_bias, np.float32)
                for _, m in self.sparse_layers()]

    def loss_and_grads(self, params, names, ids, labels):
        # the reference reads the biases the program's layers read
        self.config["n_head"]["selection_bias"] = self.selection_biases()
        self.report_routing(params, ids, labels)
        return gpt.Built.loss_and_grads(self, params, names, ids, labels)

    def report_routing(self, params, ids, labels):
        """One line on stderr, as ``families/laguna.py`` prints it: per
        sparse block (the module's last) the share of (token, slot)
        assignments on which the program (its own precision) and the
        float32 reference chose another expert, and the load the program's
        layer had on ``ids``; and the two loss terms. The layers' counters
        and the loss gauges are published from the same buffers."""
        import jax
        import jax.numpy as jnp

        from benchmark import manifest

        reference = manifest.plugin("reference", REFERENCE)
        ours, buffers = self.chosen_experts(params, (ids, labels))
        ref_params = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32), self.to_reference(params))
        ref_params["blocks"] = [ref_params["blocks"][i]
                                for i in sorted(ref_params["blocks"])]
        theirs = jax.jit(lambda p, x, y: reference.chosen_experts(
            p, x, y, n_head=self.config["n_head"],
            eps=self.config["layer_norm_epsilon"]))(ref_params, ids, labels)
        width = self.config["n_head"]["router_width"]
        shares = []
        for a, b in zip(ours, theirs):
            a = jax.nn.one_hot(jnp.reshape(a, (-1, a.shape[-1])), width).sum(1)
            b = jax.nn.one_hot(jnp.reshape(b, (-1, b.shape[-1])), width).sum(1)
            shares.append(float(jnp.sum(jnp.abs(a - b)) / 2 / jnp.sum(b)))
        tokens = int(np.prod(ids.shape))
        held, fullest, second_part = [], [], []
        for name, m in self.sparse_layers():
            m.publish_routing(buffers, name + ".", layer=name)
            n = int(buffers[name + ".held_assignments"])
            held.append(n * m.num_experts / (tokens * m.top_k * m.count))
            fullest.append(float(buffers[name + ".max_load_over_mean"]))
            second_part.append(n > m.chunk_rows(tokens))
        self.model.publish_losses(buffers)
        print(json.dumps({"event": "routing_agreement",
                          "assignments_chosen_differently_by_layer": shares,
                          "held_assignments_over_expected_by_layer": held,
                          "max_load_over_mean_by_layer": fullest,
                          "second_part_ran_by_layer": second_part,
                          "mtp_main_loss": float(buffers["mtp_main_loss"]),
                          "mtp_next_loss": float(buffers["mtp_next_loss"]),
                          "tokens": tokens}),
              file=sys.stderr, flush=True)


def build(config, recipe, seed: int, mesh) -> Built:
    """Model, optimizer and ``ParallelTrainer`` as a user builds them; the
    constructors run inside one jitted call under ``rng_guard`` (see
    ``families/gpt.py``), so the weights are made on the device from
    ``seed`` in the dtype they train in."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.engine import ParallelTrainer
    from paddle_tpu.framework.random import rng_guard
    from paddle_tpu.jit.functionalization import state_of
    from paddle_tpu.text.models import MixedDecoderForPretraining

    a = arch(config)
    n = len(a["ffn"])
    holder = {}

    def construct(key):
        with rng_guard(key):
            model = MixedDecoderForPretraining(
                mtp_layers=a["mtp_layers"],
                mtp_loss_weight=a["mtp_loss_weight"],
                vocab_size=config["vocab_size"],
                hidden_size=config["hidden_size"],
                layer_types=[LATENT] * n,
                heads_per_layer=[a["heads"]] * n,
                mlp_layer_types=a["ffn"],
                kv_heads=None, head_dim=None,
                rope={LATENT: {"theta": a["rope_theta"]}},
                sliding_window=None,
                latent_attention={
                    "q_lora_rank": a["q_lora_rank"],
                    "kv_lora_rank": a["kv_lora_rank"],
                    "qk_nope_head_dim": a["d_nope"],
                    "qk_rope_head_dim": a["d_rope"],
                    "v_head_dim": a["d_v"]},
                intermediate_size=config["intermediate_size"],
                num_experts=a["router_width"],
                experts_per_token=a["top_k"],
                expert_size=config["moe_intermediate_size"],
                shared_expert_size=config["moe_intermediate_size"]
                * config["n_shared_experts"],
                held_experts=a["held"],
                routed_scaling_factor=a["routed_scaling_factor"],
                router_scoring="sigmoid", router_selection_bias=True,
                router_attr=nn.ParamAttr(
                    learning_rate=recipe["router_lr_scale"]),
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
    # the constructors ran under jit: what they registered as buffers (the
    # expert layers' counts, the loss terms, the routers' biases) are that
    # trace's values, made again here; the biases stay float32 whatever
    # dtype the parameters train in
    for layer in model.sublayers(include_self=True):
        for name, b in layer._buffers.items():
            if b is not None:
                dtype = jnp.float32 if name == "e_score_correction_bias" \
                    else b.dtype
                layer._buffers[name] = jnp.zeros(b.shape, dtype)
    built = sum(int(np.prod(v.shape)) for v in values.values())
    if built != param_count(config):
        raise ValueError(f"the program built {built} parameters, the "
                         f"configuration's shapes give {param_count(config)}")

    o = recipe["optimizer"]
    if o["name"] != "AdamW" or recipe["loss_path"] != "model":
        raise ValueError("this family wires AdamW and the model's own "
                         "two-term loss")
    opt = paddle.optimizer.AdamW(o["learning_rate"],
                                 parameters=model.parameters(),
                                 slot_dtype=o.get("slot_dtype"))

    def loss_fn(out, _labels):
        return out

    trainer = ParallelTrainer(model, opt, loss_fn, mesh=mesh,
                              remat=recipe["remat"])
    return Built(config, recipe, trainer, model, model, loss_fn, init_fn)
