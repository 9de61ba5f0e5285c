"""The ``laguna`` family: Laguna-XS.2 style decoders (window and full
attention over grouped KV heads, two RoPEs, a dense first layer and sparse
layers with a shared expert) through the program's
``text/models/mixed_decoder.py`` and ``ParallelTrainer``.

The configuration file carries the published ``config.json`` keys. Three of
them are the chip's share of a deployment and not the published values
(``reduced``): ``num_hidden_layers`` (the per-layer lists keep their
published length; the first ``num_hidden_layers`` entries are built),
``num_experts`` (the experts HELD here, ``deployment.held_experts``; the
router keeps the published width, ``published.num_experts``) and
``vocab_size`` (the slice of the embedding and the head held here).

What the harness asks of a family is what ``families/gpt.py`` gives; the
trainer-side half of ``Built`` is that file's and only the mapping onto the
reference, the routing report and the counts are this one's.
"""
from __future__ import annotations

import json
import sys

import numpy as np

from benchmark.families import gpt
from benchmark.kernel_costs_mixed import visible_keys

REFERENCE = "laguna"


# -- the configuration, read one way -----------------------------------------

def arch(config) -> dict:
    """What the reference is given (under ``n_head``) and the counts below
    are made from: the built layers and the sizes they share."""
    n = config["num_hidden_layers"]
    first, count = config["deployment"]["held_experts"]
    if count != config["num_experts"]:
        raise ValueError("num_experts must be the number of experts held")
    return {
        "layers": [{"attention": a, "heads": h, "ffn": f} for a, h, f in zip(
            config["layer_types"][:n],
            config["num_attention_heads_per_layer"][:n],
            config["mlp_layer_types"][:n])],
        "head_dim": config["head_dim"],
        "kv_heads": config["num_key_value_heads"],
        "sliding_window": config["sliding_window"],
        "rope": {k: v for k, v in config["rope_parameters"].items()
                 if isinstance(v, dict)},
        "gated_attention": bool(config["gating"]),
        "top_k": config["num_experts_per_tok"],
        "router_width": config["published"]["num_experts"],
        "routed_scaling_factor": config["moe_routed_scaling_factor"],
        "held": (first, count)}


# -- counted from the configuration's shapes --------------------------------

def layer_params(config, layer) -> dict:
    """Parameters of one built layer, by part."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    ffn = 3 * h * config["moe_intermediate_size"]
    out = {"attention": h * layer["heads"] * d * 2 + 2 * h * kv * d
           + (h * layer["heads"] if config["gating"] else 0),
           "norms": 2 * h}
    if layer["ffn"] == "sparse":
        out.update(router=h * config["published"]["num_experts"],
                   shared=3 * h * config["shared_expert_intermediate_size"],
                   experts=config["num_experts"] * ffn)
    else:
        out["mlp"] = 3 * h * config["intermediate_size"]
    return out


def param_count(config) -> int:
    """All parameters held here: the built layers with the held experts,
    the embedding's and the head's slice, the final norm."""
    h = config["hidden_size"]
    return (sum(sum(layer_params(config, layer).values())
                for layer in arch(config)["layers"])
            + 2 * config["vocab_size"] * h + h)


def model_flops_per_token(config, seq: int) -> dict:
    """Forward + backward operations one token needs here.

    ``six_n``: 6 x the parameters a token meets in a matrix product: every
    layer's attention projections, the dense MLP, shared experts and
    routers, the head (the embedding is a lookup), and the held experts at
    their expectation, ``experts_per_token x held / router width`` experts a
    token a layer (1 here: 8 x 32 / 256). ``attention``: scores and values
    at the keys really visible, 12 x heads x head width x visible keys a
    layer (the causal half in full layers, at most the window in sliding
    ones). Recomputation is not counted."""
    a = arch(config)
    expected = a["top_k"] * a["held"][1] / a["router_width"]
    expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    met, attention = config["vocab_size"] * config["hidden_size"], 0.0
    for layer in a["layers"]:
        parts = layer_params(config, layer)
        met += parts["attention"] + parts.get("mlp", 0) \
            + parts.get("router", 0) + parts.get("shared", 0)
        if layer["ffn"] == "sparse":
            met += expected * expert
        window = a["sliding_window"] \
            if layer["attention"] == "sliding_attention" else None
        attention += 12 * layer["heads"] * a["head_dim"] \
            * visible_keys(seq, window)
    six_n = 6 * met
    return {"total": six_n + attention, "six_n": six_n,
            "attention": attention}


def toy(config) -> dict:
    """The same code at a size the CPU walks in seconds (rehearsal and unit
    tests only; never a cell): three layers (full dense, two sliding sparse),
    4 of 16 experts held, 2 a token."""
    out = json.loads(json.dumps(config))
    heads = list(out["num_attention_heads_per_layer"])
    heads[:3] = [6, 8, 8]
    out.update(num_hidden_layers=3, hidden_size=64, head_dim=16,
               num_key_value_heads=2, num_attention_heads=6,
               num_attention_heads_per_layer=heads, intermediate_size=128,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_experts=4, num_experts_per_tok=2, sliding_window=32,
               vocab_size=512, vocab_used=512, eos_token_id=511)
    # float32: at these sizes bf16 turns one routing choice in a hundred,
    # and one token is a large part of a toy expert's 30: the comparison
    # would measure the toy, not the code it walks
    # and the GPT cells' learning rate, at which ten toy steps move the loss
    out["run"] = dict(out["run"], param_dtype="float32", optimizer=dict(
        out["run"]["optimizer"], learning_rate=3e-4))
    out["published"] = dict(out["published"], num_experts=16)
    out["deployment"] = dict(out["deployment"], held_experts=[4, 4])
    return out


# -- the program's model and trainer -----------------------------------------

TOP = {"decoder.embed_tokens.weight": "embed", "decoder.norm.weight":
       "norm_g", "lm_head.weight": "lm_head"}
IN_BLOCK = {"input_norm.weight": "norm1_g", "attn.q_proj.weight": "q_w",
            "attn.k_proj.weight": "k_w", "attn.v_proj.weight": "v_w",
            "attn.g_proj.weight": "g_w", "attn.o_proj.weight": "o_w",
            "post_attn_norm.weight": "norm2_g",
            "mlp.gate_proj.weight": "gate_w", "mlp.up_proj.weight": "up_w",
            "mlp.down_proj.weight": "down_w",
            "moe.router.weight": "router_w",
            "moe.shared_expert.gate_proj.weight": "shared_gate_w",
            "moe.shared_expert.up_proj.weight": "shared_up_w",
            "moe.shared_expert.down_proj.weight": "shared_down_w",
            "moe.experts.gate_proj": "experts_gate_w",
            "moe.experts.up_proj": "experts_up_w",
            "moe.experts.down_proj": "experts_down_w"}


class Built(gpt.Built):
    """``families/gpt.py``'s ``Built`` over the mixed decoder: the step's
    arguments, the loss path, its gradients and the leaf selection (``"all"``
    here) are shared; the mapping onto ``reference/laguna.py`` and the
    routing report are this model's."""

    def __init__(self, config, *rest):
        # compare.py reads the reference's two keywords from here
        super().__init__(dict(config, n_head=arch(config),
                              layer_norm_epsilon=config["rms_norm_eps"]),
                         *rest)

    def to_reference(self, leaves) -> dict:
        """Program leaves (parameters or their gradients, any subset of
        whole blocks) in the reference's structure, dtype unchanged. Both
        sides keep a product's weight as (in, out) and the experts stacked:
        only the names differ."""
        out, blocks = {}, {}
        for name, v in leaves.items():
            if name in TOP:
                out[TOP[name]] = v
                continue
            _, _, idx, rest = name.split(".", 3)        # decoder.h.<i>.<rest>
            blocks.setdefault(int(idx), {})[IN_BLOCK[rest]] = v
        out["blocks"] = blocks
        return out

    def loss_and_grads(self, params, names, ids, labels):
        self.report_routing(params, ids)
        return super().loss_and_grads(params, names, ids, labels)

    # -- how often program and reference route a token differently ---------
    def sparse_layers(self):
        """``[(name, expert layer)]``, names as in the model's state."""
        from paddle_tpu.incubate.moe import DroplessMoELayer
        return [(name, m) for name, m in self.model.named_sublayers()
                if isinstance(m, DroplessMoELayer)]

    def chosen_experts(self, params, ids):
        """The experts each sparse layer of the program chose for ``ids``,
        from its own activations (the routers are read as the layers run),
        and the buffers the call left: the expert layers' counts."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.jit.functionalization import functional_call

        decoder = self.model.decoder
        sparse = [m for _, m in self.sparse_layers()]

        def forward(p, x):
            seen = []

            def read(layer, args, _out):
                tokens = jnp.reshape(args[0], (-1, args[0].shape[-1]))
                seen.append(layer.route(tokens)[0])

            hooks = [m.register_forward_post_hook(read) for m in sparse]
            # a hook's value cannot leave a checkpointed block
            was, decoder.checkpoint_blocks = decoder.checkpoint_blocks, False
            try:
                # buffers None: the layers' own go in, all of them come out
                _, buffers = functional_call(self.model, p, None, x,
                                             rng=jax.random.key(0))
            finally:
                decoder.checkpoint_blocks = was
                for hook in hooks:
                    hook.remove()
            return seen, buffers

        return jax.jit(forward)(params, ids)

    def report_routing(self, params, ids):
        """One line on stderr: per sparse layer, the share of (token, slot)
        assignments on which the program (its own precision) and the float32
        reference chose another expert, and the load the program's layer
        had on ``ids``: the assignments on its held experts over their
        expectation, the fullest held expert over the mean, and whether the
        part of the buffer past the expected load ran. The layers' counters
        are published from the same buffers (``publish_routing``)."""
        import jax
        import jax.numpy as jnp

        from benchmark import manifest

        reference = manifest.plugin("reference", REFERENCE)
        ours, buffers = self.chosen_experts(params, ids)
        ref_params = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32),
            self.to_reference(params))
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
    d = config["head_dim"]
    rope = {}
    for kind, group in a["rope"].items():
        yarn = None
        if group["rope_type"] == "yarn":
            yarn = {k: group[k] for k in (
                "factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor")}
        rope[kind] = {"theta": group["rope_theta"], "yarn": yarn,
                      "rotary_dim": int(round(
                          d * group.get("partial_rotary_factor", 1)))}
    holder = {}

    def construct(key):
        with rng_guard(key):
            model = MixedDecoderForPretraining(
                vocab_size=config["vocab_size"],
                hidden_size=config["hidden_size"],
                layer_types=[x["attention"] for x in a["layers"]],
                heads_per_layer=[x["heads"] for x in a["layers"]],
                mlp_layer_types=[x["ffn"] for x in a["layers"]],
                kv_heads=a["kv_heads"], head_dim=d, rope=rope,
                sliding_window=a["sliding_window"],
                intermediate_size=config["intermediate_size"],
                num_experts=a["router_width"],
                experts_per_token=a["top_k"],
                expert_size=config["moe_intermediate_size"],
                shared_expert_size=config["shared_expert_intermediate_size"],
                held_experts=a["held"],
                routed_scaling_factor=a["routed_scaling_factor"],
                gated_attention=a["gated_attention"],
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
    n = sum(int(np.prod(v.shape)) for v in values.values())
    if n != param_count(config):
        raise ValueError(f"the program built {n} parameters, the "
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
