"""The ``sdar`` family: SDAR-MoE decoders (a Qwen3-MoE block: grouped KV
heads, QK norm, RoPE, softmax-routed experts and no shared one) trained
under the block-diffusion objective, through the program's
``text/models/mixed_decoder.py`` (``MixedDecoderForBlockDiffusion``),
``text/block_diffusion.py`` and ``ParallelTrainer``.

The configuration file carries the published ``config.json`` keys. Three of
them are the chip's share of a deployment and not the published values
(``reduced``): ``num_hidden_layers`` (every layer is alike),
``num_experts`` (the experts HELD here, ``deployment.held_experts``; the
router keeps the published width, ``published.num_experts``) and
``vocab_size`` (the slice of the embedding and the head held here, whose
last row is ``[MASK]``). ``block_diffusion`` holds the objective's sizes,
which the published config does not name (``assumed``).

What the harness asks of a family is what ``families/gpt.py`` gives; the
trainer-side half of ``Built`` is that file's. A step takes clean rows: the
model draws the noise itself from the step's key, so the comparison's key
is pinned here and handed to the reference inside the architecture.
"""
from __future__ import annotations

import json
import sys

import numpy as np

from benchmark.families import gpt
from benchmark.kernel_costs_block_diffusion import visible_keys

REFERENCE = "sdar"


# -- the configuration, read one way -----------------------------------------

def arch(config) -> dict:
    """What the reference is given (under ``n_head``, with the noise's key
    that ``Built`` adds) and the counts below are made from."""
    first, count = config["deployment"]["held_experts"]
    if count != config["num_experts"]:
        raise ValueError("num_experts must be the number of experts held")
    objective = config["block_diffusion"]
    return {
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_theta": config["rope_theta"],
        "top_k": config["num_experts_per_tok"],
        "router_width": config["published"]["num_experts"],
        "held": (first, count),
        "block_length": objective["block_length"],
        "t_min": objective["t_min"],
        "mask_token_id": objective["mask_token_id"]}


# -- counted from the configuration's shapes --------------------------------

def layer_params(config) -> dict:
    """Parameters of one layer, by part."""
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return {"attention": 2 * h * heads * d + 2 * h * kv * d,
            "norms": 2 * h + 2 * d,
            "router": h * config["published"]["num_experts"],
            "experts": config["num_experts"] * 3 * h
            * config["moe_intermediate_size"]}


def param_count(config) -> int:
    """All parameters held here: the layers with the held experts, the
    embedding's and the head's slice, the final norm."""
    h = config["hidden_size"]
    return (config["num_hidden_layers"] * sum(layer_params(config).values())
            + 2 * config["vocab_size"] * h + h)


def model_flops_per_token(config, seq: int) -> dict:
    """Forward + backward operations one CLEAN token needs here (a step's
    tokens are ``rows x seq`` clean ones; each passes the blocks twice, as
    its noised and as its clean copy).

    ``six_n``: 6 x the parameters met in a matrix product: at both
    positions every layer's attention projections and router and the held
    experts at their expectation (``experts_per_token x held / router
    width`` a position a layer: 1 here), and once the head (the noised half
    alone reaches it; the embedding is a lookup). ``attention``: scores and
    values at the keys the mask leaves, ``12 x heads x head width x visible
    keys`` a position a layer, ``(seq + block) / 2`` keys at either
    position. Recomputation is not counted."""
    a = arch(config)
    parts = layer_params(config)
    expected = a["top_k"] * a["held"][1] / a["router_width"]
    expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    in_a_layer = parts["attention"] + parts["router"] + expected * expert
    six_n = 6 * (2 * a["layers"] * in_a_layer
                 + config["vocab_size"] * config["hidden_size"])
    attention = 12 * a["heads"] * a["head_dim"] * a["layers"] \
        * 2 * visible_keys(seq, a["block_length"])
    return {"total": six_n + attention, "six_n": six_n,
            "attention": attention}


def toy(config) -> dict:
    """The same code at a size the CPU walks in seconds (rehearsal and unit
    tests only; never a cell): two layers, 8 of 16 experts held, 2 a
    token."""
    out = json.loads(json.dumps(config))
    out.update(num_hidden_layers=2, hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, moe_intermediate_size=32,
               num_experts=8, num_experts_per_tok=2,
               vocab_size=512, vocab_used=511, eos_token_id=2)
    # float32 (see families/laguna.py) at learning rate 3e-3, and the
    # noise level from 0.5 up: a toy step has 128 blocks for the cell's
    # 2,048, and 1 / t weights of up to 1 / t_min on so few would bury the
    # ten steps' fall in the estimate's own spread (0.45 to 0.6 nats on
    # five seeds as it is, for the rehearsal's margin of 0.1)
    out["run"] = dict(out["run"], param_dtype="float32", optimizer=dict(
        out["run"]["optimizer"], learning_rate=3e-3))
    out["block_diffusion"] = dict(out["block_diffusion"], mask_token_id=511,
                                  t_min=0.5)
    # two chips share a toy layer, as many as experts a token (the
    # routers' initialiser ties on that): the second chip's eight of 16
    out["published"] = dict(out["published"], num_experts=16)
    out["deployment"] = dict(out["deployment"], chips_sharing_a_layer=2,
                             held_experts=[8, 8])
    return out


# -- the program's model and trainer -----------------------------------------

CHECK_KEY_SEED = 0        # gpt.Built._loss runs the model under key(0)
TOP = {"decoder.embed_tokens.weight": "embed", "decoder.norm.weight":
       "norm_g", "lm_head.weight": "lm_head"}
IN_BLOCK = {"input_norm.weight": "norm1_g", "attn.q_proj.weight": "q_w",
            "attn.k_proj.weight": "k_w", "attn.v_proj.weight": "v_w",
            "attn.q_norm.weight": "q_norm_g",
            "attn.k_norm.weight": "k_norm_g", "attn.o_proj.weight": "o_w",
            "post_attn_norm.weight": "norm2_g",
            "moe.router.weight": "router_w",
            "moe.experts.gate_proj": "experts_gate_w",
            "moe.experts.up_proj": "experts_up_w",
            "moe.experts.down_proj": "experts_down_w"}


def check_noise_key():
    """The key data of the noise the comparison's forward draws: the first
    key a model asks for under ``gpt.Built._loss``'s ``key(0)``."""
    import jax

    from paddle_tpu.framework.random import get_rng_key, rng_guard

    with rng_guard(jax.random.key(CHECK_KEY_SEED)):
        return tuple(int(x) for x in jax.random.key_data(get_rng_key()))


class Built(gpt.Built):
    """``families/gpt.py``'s ``Built`` over the block-diffusion model: the
    loss path, its gradients and the leaf selection (``"all"`` here) are
    shared; a step's arguments are the clean rows alone, and the mapping
    onto ``reference/sdar.py``, the pinned noise and the routing report are
    this model's."""

    def __init__(self, config, *rest):
        # compare.py reads the reference's two keywords from here
        super().__init__(dict(
            config, n_head=dict(arch(config), noise_key=check_noise_key()),
            layer_norm_epsilon=config["rms_norm_eps"]), *rest)

    def step_args(self, ids, labels):
        """The model takes clean rows and returns its loss; the next-token
        labels of the traffic are not read (no shift)."""
        return ids, 0.0

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

    # -- what the noise masked, how the routers chose ----------------------
    def sparse_layers(self):
        """``[(name, expert layer)]``, names as in the model's state."""
        from paddle_tpu.incubate.moe import DroplessMoELayer
        return [(name, m) for name, m in self.model.named_sublayers()
                if isinstance(m, DroplessMoELayer)]

    def chosen_experts(self, params, ids):
        """The experts each layer of the program chose for the ``2L``
        positions of ``ids`` under the comparison's noise, from its own
        activations, and the buffers the call left (the expert layers'
        counts, the masked share)."""
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
                _, buffers = functional_call(
                    self.model, p, None, x,
                    rng=jax.random.key(CHECK_KEY_SEED))
            finally:
                decoder.checkpoint_blocks = was
                for hook in hooks:
                    hook.remove()
            return seen, buffers

        return jax.jit(forward)(params, ids)

    def report_routing(self, params, ids):
        """One line on stderr, as ``families/laguna.py`` prints it: per
        layer the share of (position, slot) assignments on which the program
        (its own precision) and the float32 reference chose another expert,
        and the load the program's layer had; and the share of the clean
        tokens the noise masked. The layers' counters and the gauge are
        published from the same buffers."""
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
        positions = 2 * int(np.prod(ids.shape))
        held, fullest, second_part = [], [], []
        for name, m in self.sparse_layers():
            m.publish_routing(buffers, name + ".", layer=name)
            n = int(buffers[name + ".held_assignments"])
            held.append(n * m.num_experts / (positions * m.top_k * m.count))
            fullest.append(float(buffers[name + ".max_load_over_mean"]))
            second_part.append(n > m.chunk_rows(positions))
        self.model.publish_noise(buffers)
        print(json.dumps({"event": "routing_agreement",
                          "assignments_chosen_differently_by_layer": shares,
                          "held_assignments_over_expected_by_layer": held,
                          "max_load_over_mean_by_layer": fullest,
                          "second_part_ran_by_layer": second_part,
                          "masked_share": float(buffers["masked_share"]),
                          "positions": positions}),
              file=sys.stderr, flush=True)


def tied_across_chips(chips: int):
    """The routers' initialiser of this family's recipe: Xavier columns for
    the experts one chip holds (its ``slots``), the same on every chip of
    the group, expert ``c * slots + s`` starting as slot ``s``. With as many
    experts a token as chips, a token's top scores are then the copies of
    its best slot, one on every chip, whatever the token is: every chip
    receives exactly its expected load, ``tokens x experts a token / chips``
    assignments a layer, on every seed and at every step, where Xavier
    columns drawn apart leave a chip's load to which few experts the
    commonest tokens and the ``[MASK]`` positions (a quarter of a row, all
    alike to a fresh router) happen to pick: 0.1 to 2.7 of the expectation a
    layer by seed, and 13 ms of step time a unit (PERF.md section 6, PR 31).
    The router's equation is untouched; this is where it starts."""
    from paddle_tpu import nn

    class TiedAcrossChips(nn.initializer.Initializer):
        def __call__(self, shape, dtype):
            import jax.numpy as jnp
            d_model, experts = shape
            if experts % chips:
                raise ValueError(f"{experts} experts over {chips} chips")
            own = nn.initializer.XavierUniform(fan_in=d_model,
                                               fan_out=experts)(
                (d_model, experts // chips), dtype)
            return jnp.tile(own, (1, chips))

    return TiedAcrossChips()


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
    from paddle_tpu.text.models import MixedDecoderForBlockDiffusion

    a = arch(config)
    n = a["layers"]
    holder = {}

    def construct(key):
        with rng_guard(key):
            model = MixedDecoderForBlockDiffusion(
                block_length=a["block_length"], t_min=a["t_min"],
                mask_token_id=a["mask_token_id"],
                vocab_size=config["vocab_size"],
                hidden_size=config["hidden_size"],
                layer_types=["full_attention"] * n,
                heads_per_layer=[a["heads"]] * n,
                mlp_layer_types=["sparse"] * n,
                kv_heads=a["kv_heads"], head_dim=a["head_dim"],
                rope={"full_attention": {"theta": a["rope_theta"],
                                         "rotary_dim": a["head_dim"]}},
                sliding_window=None,
                intermediate_size=config["intermediate_size"],
                num_experts=a["router_width"],
                experts_per_token=a["top_k"],
                expert_size=config["moe_intermediate_size"],
                shared_expert_size=0, held_experts=a["held"],
                router_scoring="softmax", qk_norm=True,
                router_attr=nn.ParamAttr(
                    initializer=tied_across_chips(
                        config["deployment"]["chips_sharing_a_layer"]),
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
    # expert layers' counts, the masked share) are that trace's values,
    # made again here
    for layer in model.sublayers(include_self=True):
        for name, b in layer._buffers.items():
            if b is not None:
                layer._buffers[name] = jnp.zeros(b.shape, b.dtype)
    built = sum(int(np.prod(v.shape)) for v in values.values())
    if built != param_count(config):
        raise ValueError(f"the program built {built} parameters, the "
                         f"configuration's shapes give {param_count(config)}")

    o = recipe["optimizer"]
    if o["name"] != "AdamW" or recipe["loss_path"] != "block_diffusion":
        raise ValueError("this family wires AdamW and the model's own "
                         "block-diffusion loss")
    opt = paddle.optimizer.AdamW(o["learning_rate"],
                                 parameters=model.parameters(),
                                 slot_dtype=o.get("slot_dtype"))

    def loss_fn(out, _labels):
        return out

    trainer = ParallelTrainer(model, opt, loss_fn, mesh=mesh,
                              remat=recipe["remat"])
    return Built(config, recipe, trainer, model, model, loss_fn, init_fn)
