"""The ``qwen3next`` family: Qwen3-Next decoders (Gated DeltaNet layers three
to one with gated full attention, softmax-routed experts and a gated shared
expert in every layer) through the program's
``text/models/mixed_decoder.py`` (``layer_types`` with
``"linear_attention"``), ``nn.GatedDeltaNet`` and ``ParallelTrainer``.

The configuration file carries the published ``config.json`` keys. Three of
them are the chip's share of a deployment and not the published values
(``reduced``): ``num_hidden_layers`` (layer ``i`` is full attention where
``(i + 1) % full_attention_interval == 0``; the first ``num_hidden_layers``
are built), ``num_experts`` (the experts HELD here,
``deployment.held_experts``; the router keeps the published width,
``published.num_experts``) and ``vocab_size`` (the slice of the embedding
and the head held here).

What the harness asks of a family is what ``families/gpt.py`` gives; the
trainer-side half of ``Built`` is that file's, the reading of the routers
``families/laguna.py``'s, and the mapping onto the reference, the routing
report and the counts are this one's.
"""
from __future__ import annotations

import json
import sys

import numpy as np

from benchmark.families import gpt, laguna

REFERENCE = "qwen3next"
LINEAR, FULL = "linear_attention", "full_attention"


# -- the configuration, read one way -----------------------------------------

def arch(config) -> dict:
    """What the reference is given (under ``n_head``) and the counts below
    are made from."""
    first, count = config["deployment"]["held_experts"]
    if count != config["num_experts"]:
        raise ValueError("num_experts must be the number of experts held")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("every layer of this family is sparse")
    every = config["full_attention_interval"]
    return {
        "layers": [FULL if (i + 1) % every == 0 else LINEAR
                   for i in range(config["num_hidden_layers"])],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_theta": config["rope_theta"],
        "rotary_dim": int(round(config["head_dim"]
                                * config["partial_rotary_factor"])),
        "linear": {"key_heads": config["linear_num_key_heads"],
                   "value_heads": config["linear_num_value_heads"],
                   "d_k": config["linear_key_head_dim"],
                   "d_v": config["linear_value_head_dim"],
                   "conv_kernel": config["linear_conv_kernel_dim"]},
        "top_k": config["num_experts_per_tok"],
        "router_width": config["published"]["num_experts"],
        "held": (first, count)}


# -- counted from the configuration's shapes --------------------------------

def layer_params(config, kind) -> dict:
    """Parameters of one layer of ``kind``, by part; ``products`` lists the
    parts a token meets in a matrix product."""
    h, a = config["hidden_size"], arch(config)
    if kind == LINEAR:
        lin = a["linear"]
        key = lin["key_heads"] * lin["d_k"]
        value = lin["value_heads"] * lin["d_v"]
        mixer = {"in_proj_qkvz": h * (2 * key + 2 * value),
                 "in_proj_ba": h * 2 * lin["value_heads"],
                 "conv": (2 * key + value) * lin["conv_kernel"],
                 "decay": 2 * lin["value_heads"],       # A_log, dt_bias
                 "mixer_norms": lin["d_v"],
                 "out_proj": value * h}
    else:
        d, heads, kv = a["head_dim"], a["heads"], a["kv_heads"]
        mixer = {"q_proj": h * heads * 2 * d,           # query and gate
                 "kv_proj": 2 * h * kv * d,
                 "mixer_norms": 2 * d,                  # q_norm, k_norm
                 "o_proj": heads * d * h}
    return {**mixer, "norms": 2 * h,
            "router": h * a["router_width"],
            "shared": 3 * h * config["shared_expert_intermediate_size"],
            "shared_gate": h,
            "experts": config["num_experts"] * 3 * h
            * config["moe_intermediate_size"]}


PRODUCTS = ("in_proj_qkvz", "in_proj_ba", "out_proj", "q_proj", "kv_proj",
            "o_proj", "router", "shared", "shared_gate")


def param_count(config) -> int:
    """All parameters held here: the built layers with the held experts,
    the embedding's and the head's slice, the final norm."""
    h = config["hidden_size"]
    return (sum(sum(layer_params(config, kind).values())
                for kind in arch(config)["layers"])
            + 2 * config["vocab_size"] * h + h)


def model_flops_per_token(config, seq: int) -> dict:
    """Forward + backward operations one token needs here.

    ``six_n``: 6 x the parameters a token meets in a matrix product: every
    layer's mixer projections, router, shared expert and its gate, the held
    experts at their expectation (``experts_per_token x held / router
    width`` experts a token a layer: 0.625 here), and the head's slice (the
    embedding is a lookup, the convolution four multiplications a channel).
    ``attention``: scores and values of the full layers at the causal half,
    ``12 x heads x head width x (seq + 1) / 2``. ``recurrence``: the rule as
    its definition states it, three ``d_k x d_v`` products a value head a
    position (``S^T k``, ``k u^T``, ``S^T q``: ``6 d_k d_v`` operations
    forward, three times that with the backward pass), in the linear
    layers. Not the chunked algorithm's further products and no
    recomputation: a larger chunk must not raise the utilization."""
    a = arch(config)
    expected = a["top_k"] * a["held"][1] / a["router_width"]
    expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    met = config["vocab_size"] * config["hidden_size"]
    attention = recurrence = 0.0
    lin = a["linear"]
    for kind in a["layers"]:
        parts = layer_params(config, kind)
        met += sum(parts.get(k, 0) for k in PRODUCTS) + expected * expert
        if kind == FULL:
            attention += 12 * a["heads"] * a["head_dim"] * (seq + 1) / 2
        else:
            recurrence += 3 * lin["value_heads"] * 6 * lin["d_k"] * lin["d_v"]
    six_n = 6 * met
    return {"total": six_n + attention + recurrence, "six_n": six_n,
            "attention": attention, "recurrence": recurrence}


def toy(config) -> dict:
    """The same code at a size the CPU walks in seconds (rehearsal and unit
    tests only; never a cell): one period (three linear layers and the full
    one), 8 of 16 experts held, 2 a token."""
    out = json.loads(json.dumps(config))
    out.update(num_hidden_layers=4, hidden_size=64, head_dim=32,
               num_attention_heads=4, num_key_value_heads=2,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16,
               intermediate_size=128, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, vocab_size=512, vocab_used=512,
               eos_token_id=2)
    # float32 and the GPT cells' learning rate, for families/laguna.py's
    # reasons: bf16 turns a routing choice in a hundred, a large part of a
    # toy expert's load, and ten toy steps at 1e-5 move no loss
    out["run"] = dict(out["run"], param_dtype="float32", optimizer=dict(
        out["run"]["optimizer"], learning_rate=3e-4))
    out["published"] = dict(out["published"], num_experts=16)
    out["deployment"] = dict(out["deployment"], chips_sharing_a_layer=2,
                             held_experts=[8, 8])
    return out


# -- the program's model and trainer -----------------------------------------

TOP = {"decoder.embed_tokens.weight": "embed", "decoder.norm.weight":
       "norm_g", "lm_head.weight": "lm_head"}
IN_BLOCK = {"input_norm.weight": "norm1_g",
            "post_attn_norm.weight": "norm2_g",
            "linear_attn.in_proj_qkvz.weight": "qkvz_w",
            "linear_attn.in_proj_ba.weight": "ba_w",
            "linear_attn.conv_weight": "conv_w",
            "linear_attn.A_log": "a_log",
            "linear_attn.dt_bias": "dt_bias",
            "linear_attn.norm.weight": "gdn_norm_g",
            "linear_attn.out_proj.weight": "out_w",
            "attn.q_proj.weight": "q_w", "attn.k_proj.weight": "k_w",
            "attn.v_proj.weight": "v_w", "attn.q_norm.weight": "q_norm_g",
            "attn.k_norm.weight": "k_norm_g", "attn.o_proj.weight": "o_w",
            "moe.router.weight": "router_w",
            "moe.shared_expert.gate_proj.weight": "shared_gate_w",
            "moe.shared_expert.up_proj.weight": "shared_up_w",
            "moe.shared_expert.down_proj.weight": "shared_down_w",
            "moe.shared_expert_gate.weight": "shared_expert_gate_w",
            "moe.experts.gate_proj": "experts_gate_w",
            "moe.experts.up_proj": "experts_up_w",
            "moe.experts.down_proj": "experts_down_w"}


def grouped_columns(lin) -> dict:
    """For the two input projections of a Gated DeltaNet layer, the
    program's column that stands at each of the reference's: the program
    keeps ``[q | k | v | z]`` and ``[b | a]`` (every head's lanes together,
    so the convolution reads one slice), the published checkpoint and the
    reference group by key head: its q, its k, its value heads' v, their z;
    its value heads' b, their a."""
    hk, hv, dk, dv = (lin["key_heads"], lin["value_heads"], lin["d_k"],
                      lin["d_v"])
    r = hv // hk
    key, value = hk * dk, hv * dv
    qkvz, ba = [], []
    for head in range(hk):
        qkvz += [np.arange(dk) + head * dk,
                 np.arange(dk) + key + head * dk,
                 np.arange(r * dv) + 2 * key + head * r * dv,
                 np.arange(r * dv) + 2 * key + value + head * r * dv]
        ba += [np.arange(r) + head * r, np.arange(r) + hv + head * r]
    return {"qkvz_w": np.concatenate(qkvz), "ba_w": np.concatenate(ba)}


class Built(laguna.Built):
    """``families/laguna.py``'s ``Built`` (the step's arguments, the loss
    path and its gradients, the leaf selection, how the routers are read as
    the layers run) with this model's mapping onto
    ``reference/qwen3next.py`` and its routing report."""

    def __init__(self, config, *rest):
        # compare.py reads the reference's two keywords from here
        gpt.Built.__init__(
            self, dict(config, n_head=arch(config),
                       layer_norm_epsilon=config["rms_norm_eps"]), *rest)

    def to_reference(self, leaves) -> dict:
        """Program leaves (parameters or their gradients, any subset of
        whole blocks) in the reference's structure, dtype unchanged. Both
        sides keep a product's weight as (in, out) and the experts stacked;
        the names differ, and the columns of a linear layer's two input
        projections are put in the reference's order."""
        columns = grouped_columns(self.config["n_head"]["linear"])
        out, blocks = {}, {}
        for name, v in leaves.items():
            if name in TOP:
                out[TOP[name]] = v
                continue
            _, _, idx, rest = name.split(".", 3)        # decoder.h.<i>.<rest>
            ref = IN_BLOCK[rest]
            if ref in columns:
                v = v[..., columns[ref]]
            blocks.setdefault(int(idx), {})[ref] = v
        out["blocks"] = blocks
        return out

    def report_routing(self, params, ids):
        """One line on stderr, as ``families/laguna.py`` prints it: per
        layer the share of (token, slot) assignments on which the program
        (its own precision) and the float32 reference chose another expert,
        and the load the program's layer had on ``ids``. The layers'
        counters are published from the same buffers."""
        import jax
        import jax.numpy as jnp

        from benchmark import manifest

        reference = manifest.plugin("reference", REFERENCE)
        ours, buffers = self.chosen_experts(params, ids)
        ref_params = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32), self.to_reference(params))
        ref_params["blocks"] = [ref_params["blocks"][i]
                                for i in sorted(ref_params["blocks"])]
        theirs = jax.jit(lambda p, x: reference.chosen_experts(
            p, x, n_head=self.config["n_head"],
            eps=self.config["layer_norm_epsilon"]))(ref_params, ids)
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
        print(json.dumps({"event": "routing_agreement",
                          "assignments_chosen_differently_by_layer": shares,
                          "held_assignments_over_expected_by_layer": held,
                          "max_load_over_mean_by_layer": fullest,
                          "second_part_ran_by_layer": second_part,
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
    n = len(a["layers"])
    holder = {}

    def construct(key):
        with rng_guard(key):
            model = MixedDecoderForPretraining(
                vocab_size=config["vocab_size"],
                hidden_size=config["hidden_size"],
                layer_types=a["layers"],
                heads_per_layer=[a["heads"]] * n,
                mlp_layer_types=["sparse"] * n,
                kv_heads=a["kv_heads"], head_dim=a["head_dim"],
                rope={FULL: {"theta": a["rope_theta"],
                             "rotary_dim": a["rotary_dim"]}},
                sliding_window=None,
                intermediate_size=config["intermediate_size"],
                num_experts=a["router_width"],
                experts_per_token=a["top_k"],
                expert_size=config["moe_intermediate_size"],
                shared_expert_size=config["shared_expert_intermediate_size"],
                shared_expert_gate=True, held_experts=a["held"],
                router_scoring="softmax", qk_norm=True,
                attention_gate="elementwise", norm_offset=1.0,
                linear_attention=a["linear"],
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
    # expert layers' counts) are that trace's values, made again here
    for layer in model.sublayers(include_self=True):
        for name, b in layer._buffers.items():
            if b is not None:
                layer._buffers[name] = jnp.zeros(b.shape, b.dtype)
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
