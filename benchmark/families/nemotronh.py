"""The ``nemotronh`` family: Nemotron-H decoders (layers of one sublayer each,
a Mamba-2 mixer, an expert layer or grouped attention without rotation, in
the order ``hybrid_override_pattern`` gives; squared-ReLU experts and a
shared expert under a sigmoid router that chooses by a bias) through the
program's ``text/models/mixed_decoder.py`` (``layer_types`` with
``"mamba"`` and None, ``mlp_layer_types`` with None), ``nn.Mamba2Mixer`` and
``ParallelTrainer``.

The configuration file carries the published ``config.json`` keys. Three of
them are the chip's share of a deployment and not the published values
(``reduced``): ``num_hidden_layers`` (the first ``num_hidden_layers``
characters of the published ``hybrid_override_pattern`` are built),
``n_routed_experts`` (the experts HELD here, ``deployment.held_experts``;
the router keeps the published width, ``published.n_routed_experts``) and
``vocab_size`` (the slice of the embedding and the head held here).

What the harness asks of a family is what ``families/gpt.py`` gives; the
trainer-side half of ``Built`` is that file's, the reading of the routers
``families/laguna.py``'s, the routers' biases handed to the reference
``families/glm4moelite.py``'s, and the mapping onto the reference, the
routing report and the counts are this one's.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmark import manifest, traffic_gen
from benchmark.families import gpt, laguna

REFERENCE = "nemotronh"
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


# -- the configuration, read one way -----------------------------------------

def arch(config) -> dict:
    """What the reference is given (under ``n_head``) and the counts below
    are made from."""
    first, count = config["deployment"]["held_experts"]
    if count != config["n_routed_experts"]:
        raise ValueError("n_routed_experts must be the number of experts "
                         "held")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("a group-limited router is not built")
    if not config["norm_topk_prob"] or config["mlp_hidden_act"] != "relu2" \
            or config["mamba_hidden_act"] != "silu":
        raise ValueError("this family wires normalised weights, squared-"
                         "ReLU experts and SiLU in the mixer")
    if config["use_bias"] or config["mamba_proj_bias"] \
            or config["attention_bias"] or config["mlp_bias"] \
            or not config["use_conv_bias"]:
        raise ValueError("this family wires the convolution's bias and no "
                         "other")
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    return {
        "layers": [KINDS[c] for c in pattern],
        "mamba": {"heads": config["mamba_num_heads"],
                  "head_dim": config["mamba_head_dim"],
                  "groups": config["n_groups"],
                  "state": config["ssm_state_size"],
                  "conv_kernel": config["conv_kernel"]},
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "top_k": config["num_experts_per_tok"],
        "router_width": config["published"]["n_routed_experts"],
        "routed_scaling_factor": config["routed_scaling_factor"],
        "held": (first, count),
        "selection_bias": None}


# -- counted from the configuration's shapes --------------------------------

def layer_params(config, kind) -> dict:
    """Parameters of one layer of ``kind``, by part (its norm among
    them)."""
    h, a = config["hidden_size"], arch(config)
    if kind == "mamba":
        m = a["mamba"]
        inner = m["heads"] * m["head_dim"]
        conv = inner + 2 * m["groups"] * m["state"]
        return {"in_proj": h * (inner + conv + m["heads"]),
                "conv": conv * (m["conv_kernel"] + 1),   # taps and bias
                "heads": 3 * m["heads"],                 # A_log, D, dt_bias
                "mixer_norm": inner,
                "out_proj": inner * h, "norm": h}
    if kind == "attention":
        d, heads, kv = a["head_dim"], a["heads"], a["kv_heads"]
        return {"q_proj": h * heads * d, "kv_proj": 2 * h * kv * d,
                "o_proj": heads * d * h, "norm": h}
    return {"router": h * a["router_width"],
            "shared": 2 * h * config["moe_shared_expert_intermediate_size"],
            "experts": config["n_routed_experts"] * 2 * h
            * config["moe_intermediate_size"], "norm": h}


PRODUCTS = ("in_proj", "out_proj", "q_proj", "kv_proj", "o_proj", "router",
            "shared")


def param_count(config) -> int:
    """All parameters held here: the built layers with the held experts,
    the embedding's and the head's slice, the final norm. The routers'
    biases are buffers and not counted."""
    h = config["hidden_size"]
    return (sum(sum(layer_params(config, kind).values())
                for kind in arch(config)["layers"])
            + 2 * config["vocab_size"] * h + h)


def model_flops_per_token(config, seq: int) -> dict:
    """Forward + backward operations one token needs here.

    ``six_n``: 6 x the parameters a token meets in a matrix product: every
    Mamba layer's ``in_proj`` and ``out_proj``, the attention projections,
    routers, shared experts, the held experts at their expectation
    (``experts_per_token x held / router width`` experts a token an expert
    layer: 0.375 here) and the head's slice (the embedding is a lookup, the
    convolution four multiplications a channel). ``attention``: scores and
    values at the causal half, ``12 x heads x head width x (seq + 1) / 2``
    an attention layer. ``scan``: the state-space scan as its definition
    states it, two ``P x N`` products a head a position (``dt x B^T`` and
    ``S C``: ``4 P N`` operations forward, three times that with the
    backward pass) in the Mamba layers: not the chunked algorithm's further
    products (the masked intra-chunk scores, the chunk states), so a larger
    chunk does not raise the utilization. Recomputation is not counted."""
    a = arch(config)
    expected = a["top_k"] * a["held"][1] / a["router_width"]
    expert = 2 * config["hidden_size"] * config["moe_intermediate_size"]
    met = config["vocab_size"] * config["hidden_size"]
    attention = scan = 0.0
    m = a["mamba"]
    for kind in a["layers"]:
        parts = layer_params(config, kind)
        met += sum(parts.get(k, 0) for k in PRODUCTS)
        if kind == "moe":
            met += expected * expert
        elif kind == "attention":
            attention += 12 * a["heads"] * a["head_dim"] * (seq + 1) / 2
        else:
            scan += m["heads"] * 12 * m["head_dim"] * m["state"]
    six_n = 6 * met
    return {"total": six_n + attention + scan, "six_n": six_n,
            "attention": attention, "scan": scan}


def toy(config) -> dict:
    """The same code at a size the CPU walks in seconds (rehearsal and unit
    tests only; never a cell): the cut's nine layers, 4 of 16 experts held,
    2 a token."""
    out = json.loads(json.dumps(config))
    out.update(hidden_size=64, mamba_num_heads=4, mamba_head_dim=16,
               n_groups=2, ssm_state_size=16, chunk_size=16,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=48, n_routed_experts=4,
               num_experts_per_tok=2, vocab_size=512, vocab_used=512,
               eos_token_id=2)
    # float32 and the GPT cells' learning rate, for families/laguna.py's
    # reasons: bf16 turns a routing choice in a hundred, a large part of a
    # toy expert's load, and ten toy steps at 1e-5 move no loss
    out["run"] = dict(out["run"], param_dtype="float32", optimizer=dict(
        out["run"]["optimizer"], learning_rate=3e-4))
    if out["run"]["router_bias_balanced_on"]:
        out["run"]["router_bias_balanced_on"] = {"traffic": "seq1024",
                                                 "rows": 2}
    out["published"] = dict(out["published"], n_routed_experts=16)
    out["deployment"] = dict(out["deployment"], chips_sharing_a_layer=4,
                             held_experts=[4, 4])
    return out


# -- the program's model and trainer -----------------------------------------

TOP = {"decoder.embed_tokens.weight": "embed", "decoder.norm.weight":
       "norm_g", "lm_head.weight": "lm_head"}
IN_BLOCK = {"input_norm.weight": "norm_g",
            "mamba.in_proj.weight": "in_w",
            "mamba.conv_weight": "conv_w", "mamba.conv_bias": "conv_b",
            "mamba.A_log": "a_log", "mamba.D": "d",
            "mamba.dt_bias": "dt_bias", "mamba.norm.weight": "mamba_norm_g",
            "mamba.out_proj.weight": "out_w",
            "attn.q_proj.weight": "q_w", "attn.k_proj.weight": "k_w",
            "attn.v_proj.weight": "v_w", "attn.o_proj.weight": "o_w",
            "moe.router.weight": "router_w",
            "moe.shared_expert.up_proj.weight": "shared_up_w",
            "moe.shared_expert.down_proj.weight": "shared_down_w",
            "moe.experts.up_proj": "experts_up_w",
            "moe.experts.down_proj": "experts_down_w"}


class Built(laguna.Built):
    """``families/laguna.py``'s ``Built`` (the step's arguments, the loss
    path and its gradients, the leaf selection, how the routers are read as
    the layers run) with this model's mapping onto
    ``reference/nemotronh.py`` and its routing report, which hands the
    routers' biases to the reference."""

    def __init__(self, config, *rest):
        # compare.py reads the reference's two keywords from here
        gpt.Built.__init__(
            self, dict(config, n_head=arch(config),
                       layer_norm_epsilon=config["layer_norm_epsilon"]),
            *rest)

    def to_reference(self, leaves) -> dict:
        """Program leaves (parameters or their gradients, any subset of
        whole blocks) in the reference's structure, dtype unchanged. Both
        sides keep a product's weight as (in, out), ``in_proj``'s columns in
        the published order and the experts stacked: only the names
        differ."""
        out, blocks = {}, {}
        for name, v in leaves.items():
            if name in TOP:
                out[TOP[name]] = v
                continue
            _, _, idx, rest = name.split(".", 3)        # decoder.h.<i>.<rest>
            blocks.setdefault(int(idx), {})[IN_BLOCK[rest]] = v
        out["blocks"] = blocks
        return out

    def selection_biases(self):
        """The routers' ``e_score_correction_bias``, an expert layer in
        order, as the model holds them (what ``_loss`` runs with)."""
        return [np.asarray(m.e_score_correction_bias, np.float32)
                for _, m in self.sparse_layers()]

    def report_routing(self, params, ids):
        """One line on stderr, as ``families/laguna.py`` prints it: per
        expert layer the share of (token, slot) assignments on which the
        program (its own precision) and the float32 reference chose another
        expert, and the load the program's layer had on ``ids``. The
        layers' counters are published from the same buffers."""
        import jax
        import jax.numpy as jnp

        # the reference reads the biases the program's layers read, here and
        # in the comparison that follows (laguna.Built.loss_and_grads)
        self.config["n_head"]["selection_bias"] = self.selection_biases()
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


def balanced_biases(built, ids, iterations: int = 1000):
    """The routers' ``e_score_correction_bias``, an expert layer at a time
    in order, moved until every expert takes the same share of ``ids``'
    assignments, and written to the layers and to the trainer's state.

    What the published recipe's bias is for (auxiliary-loss-free balancing:
    the bias chooses and does not weigh, and a step pushes it against an
    expert's excess load), done once before the first step on rows of the
    run's own traffic: a layer's router inputs come from a forward pass
    under the biases already set (so a later layer sees the earlier ones'
    choices), and its bias takes ``iterations`` signed steps from 1e-2 down
    to 1e-4 against each expert's load over its share, ``top_k / experts``
    of the assignments. ``build`` hands it rows of the run's traffic made
    from the run's seed: the same ranking of token ids, so the same common
    tokens, as every batch the run cycles. Fresh routers with a zero bias gave this chip's
    eight held experts 0.6 to 1.4 of their expected load a layer by seed,
    and the step follows the load at about 17 ms a layer a unit (PERF.md
    section 6, PR 39): six seeds then spread by 1.6% in tokens/s."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.jit.functionalization import functional_call

    model, trainer = built.model, built.trainer
    layers = built.sparse_layers()
    params = dict(trainer.state["params"])

    @jax.jit
    def router_inputs(params, buffers, x):
        seen = []

        def read(layer, args, _out):
            seen.append(jnp.reshape(args[0], (-1, args[0].shape[-1])))

        hooks = [m.register_forward_post_hook(read) for _, m in layers]
        # a hook's value cannot leave a checkpointed block
        was, model.decoder.checkpoint_blocks = \
            model.decoder.checkpoint_blocks, False
        try:
            functional_call(model, params, buffers, x, rng=jax.random.key(0))
        finally:
            model.decoder.checkpoint_blocks = was
            for hook in hooks:
                hook.remove()
        return seen

    @jax.jit
    def balance(tokens, router_w):
        logits = lax.dot_general(
            tokens, router_w.astype(tokens.dtype), (((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        scores = jax.nn.sigmoid(logits)
        experts, k = scores.shape[1], layers[0][1].top_k
        share = scores.shape[0] * k / experts

        def step(i, bias):
            _, chosen = lax.top_k(scores + bias, k)
            load = jnp.zeros(experts).at[jnp.reshape(chosen, (-1,))].add(1.0)
            size = 1e-2 * 1e-2 ** (i / iterations)
            return bias - size * jnp.sign(load - share)

        return lax.fori_loop(0, iterations, step,
                             jnp.zeros(experts, jnp.float32))

    buffers = dict(trainer.state["buffers"])
    for i, (name, m) in enumerate(layers):
        tokens = router_inputs(params, buffers, ids)[i]
        key = name + ".e_score_correction_bias"
        bias = balance(tokens, params[name + ".router.weight"])
        buffers[key] = jax.device_put(bias, buffers[key].sharding)
        m.e_score_correction_bias = bias
    trainer.state["buffers"] = buffers


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
    kinds = a["layers"]
    m = a["mamba"]
    holder = {}

    def construct(key):
        with rng_guard(key):
            model = MixedDecoderForPretraining(
                vocab_size=config["vocab_size"],
                hidden_size=config["hidden_size"],
                layer_types=[{"mamba": "mamba", "attention": "full_attention"}
                             .get(k) for k in kinds],
                heads_per_layer=[a["heads"]] * len(kinds),
                mlp_layer_types=["sparse" if k == "moe" else None
                                 for k in kinds],
                kv_heads=a["kv_heads"], head_dim=a["head_dim"],
                rope={"full_attention": None}, sliding_window=None,
                intermediate_size=config["intermediate_size"],
                num_experts=a["router_width"],
                experts_per_token=a["top_k"],
                expert_size=config["moe_intermediate_size"],
                shared_expert_size=config[
                    "moe_shared_expert_intermediate_size"]
                * config["n_shared_experts"],
                held_experts=a["held"],
                routed_scaling_factor=a["routed_scaling_factor"],
                router_scoring="sigmoid", router_selection_bias=True,
                expert_activation="relu2",
                mamba={"num_heads": m["heads"], "head_dim": m["head_dim"],
                       "n_groups": m["groups"], "state_size": m["state"],
                       "conv_kernel": m["conv_kernel"],
                       "chunk": config["chunk_size"],
                       "time_step_min": config["time_step_min"],
                       "time_step_max": config["time_step_max"],
                       "time_step_floor": config["time_step_floor"]},
                router_attr=nn.ParamAttr(
                    learning_rate=recipe["router_lr_scale"]),
                epsilon=config["layer_norm_epsilon"],
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
    # expert layers' counts, the routers' biases) are that trace's values,
    # made again here; the biases stay float32 whatever dtype the
    # parameters train in
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
    if o["name"] != "AdamW" or recipe["loss_path"] != "dense":
        raise ValueError("this family wires AdamW and the dense loss path")
    opt = paddle.optimizer.AdamW(o["learning_rate"],
                                 parameters=model.parameters(),
                                 slot_dtype=o.get("slot_dtype"))

    def loss_fn(logits, labels):
        return nn.functional.cross_entropy(logits, labels)

    trainer = ParallelTrainer(model, opt, loss_fn, mesh=mesh,
                              remat=recipe["remat"])
    built = Built(config, recipe, trainer, model, model, loss_fn, init_fn)
    balance = recipe["router_bias_balanced_on"]
    if balance:
        mix = json.load(open(os.path.join(manifest.HERE, "traffic",
                                          balance["traffic"] + ".json")))
        ids, _ = traffic_gen.make_pool(
            dict(mix, pool_batches=1), config["vocab_used"],
            config["eos_token_id"], balance["rows"], seed)
        balanced_biases(built, ids[0])
    return built
