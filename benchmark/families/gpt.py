"""The ``gpt`` family: GPT-2 / GPT-3 style decoders through the program's
``text/models/gpt.py`` and ``ParallelTrainer``.

A family file is what a configuration's ``"family"`` key names. It
builds the program's model and trainer for a configuration, says which
plain reference it is compared with (``REFERENCE``), maps the program's
parameters onto that reference, counts parameters and model FLOPs from
the configuration's shapes, and knows how to shrink the configuration
for the CPU rehearsal. Adding a model of another family adds a file
like this one and its reference, not a branch here.
"""
from __future__ import annotations

import numpy as np

REFERENCE = "gpt"


# -- counted from the configuration's shapes --------------------------------

def param_count(config) -> int:
    """All parameters, the tied LM head counted once."""
    h, layers = config["n_embd"], config["n_layer"]
    inner = config.get("n_inner") or 4 * h
    block = (2 * h) + (h * 3 * h + 3 * h) + (h * h + h) + (2 * h) \
        + (h * inner + inner) + (inner * h + h)
    return (config["vocab_size"] * h + config["n_positions"] * h
            + layers * block + 2 * h)


def model_flops_per_token(config, seq: int) -> dict:
    """Forward + backward operations one token needs: ``6*N`` for the
    matrix products against parameters (2 forward, 4 backward) and
    ``6*L*seq*hidden`` for causal attention (scores and values, 2*seq*h
    each forward once the causal half is dropped, times three for the
    backward pass). Recomputation is not counted."""
    six_n = 6 * param_count(config)
    attention = 6 * config["n_layer"] * seq * config["n_embd"]
    return {"total": six_n + attention, "six_n": six_n,
            "attention": attention}


def toy(config) -> dict:
    """The same code at a size the CPU walks in seconds (rehearsal and
    unit tests only; never a cell)."""
    out = dict(config)
    out.update(n_layer=2, n_embd=64, n_head=4, n_positions=64, n_ctx=64,
               vocab_size=512, vocab_used=500, eos_token_id=499)
    out.pop("n_inner", None)
    return out


# -- the program's model and trainer -----------------------------------------

class Built:
    """The trainer of one cell and the pure functions the correctness
    comparison needs over the same model and loss path."""

    def __init__(self, config, recipe, trainer, model, wrapped, loss_fn,
                 init_fn):
        self.config, self.recipe = config, recipe
        self.trainer, self.model = trainer, model
        self._wrapped, self._loss_fn, self._init_fn = wrapped, loss_fn, init_fn
        # a loss wrapper around the model prefixes every leaf's name;
        # parameters pass through this class under the trainer's names
        self._prefix = "" if wrapped is model else "inner."
        self._names = list(trainer.state["params"])
        self._forward = None

    # what train_step takes for a (rows, seq) batch
    def step_args(self, ids, labels):
        if self.recipe["loss_path"] == "dense":
            return ids, labels
        return (ids, labels), 0.0

    def initial_params(self, seed: int):
        """The parameters ``build`` started from, made again from the
        seed (one jitted call, on the default device)."""
        import jax
        return {self._prefix + k: v
                for k, v in self._init_fn(jax.random.key(seed)).items()}

    def _loss(self, params, ids, labels):
        """The cell's own loss path as a pure function of the parameters:
        ``functional_call`` over the model the trainer drives, under
        ``jax.checkpoint`` exactly where the engine puts it."""
        import jax

        from paddle_tpu.jit.functionalization import functional_call

        inputs, lbl = self.step_args(ids, labels)
        key = jax.random.key(0)  # dropout is 0: nothing draws from it

        def fwd(p, x):
            return functional_call(self._wrapped, p, {}, x, rng=key)[0]

        if self.recipe["remat"]:
            fwd = jax.checkpoint(fwd)
        return self._loss_fn(fwd(params, inputs), lbl)

    def forward_loss(self, params, ids, labels):
        """The loss alone; jitted once, it is called chunk by chunk."""
        import jax
        if self._forward is None:
            self._forward = jax.jit(self._loss)
        return self._forward(params, ids, labels)

    def loss_and_grads(self, params, names, ids, labels):
        """Loss and its gradients for the leaves in ``names``, through
        the framework's functional autograd under ``jax.jit``."""
        import jax

        def of_subset(sub, rest, ids_, labels_):
            return self._loss({**rest, **sub}, ids_, labels_)

        sub = {k: params[k] for k in names}
        rest = {k: v for k, v in params.items() if k not in sub}
        return jax.jit(jax.value_and_grad(of_subset))(sub, rest, ids, labels)

    # -- the mapping onto reference/gpt.py ---------------------------------
    def _qkv_perm(self):
        """Column order of the program's fused projection (per head
        ``[q_h | k_h | v_h]``, what ``GPTAttention`` reshapes and splits)
        as positions in GPT-2's ``[q | k | v]`` order."""
        h, n = self.config["n_embd"], self.config["n_head"]
        d = h // n
        part, head, j = np.meshgrid(np.arange(3), np.arange(n), np.arange(d),
                                    indexing="ij")
        return (head * 3 * d + part * d + j).reshape(-1)

    def to_reference(self, leaves) -> dict:
        """Program leaves (parameters or their gradients, any subset of
        whole blocks) in the reference's structure, dtype unchanged."""
        perm = self._qkv_perm()
        names = {"ln_1.weight": "ln1_g", "ln_1.bias": "ln1_b",
                 "attn.qkv_proj.weight": "qkv_w",
                 "attn.qkv_proj.bias": "qkv_b",
                 "attn.out_proj.weight": "proj_w",
                 "attn.out_proj.bias": "proj_b",
                 "ln_2.weight": "ln2_g", "ln_2.bias": "ln2_b",
                 "mlp.fc_in.weight": "fc_w", "mlp.fc_in.bias": "fc_b",
                 "mlp.fc_out.weight": "out_w", "mlp.fc_out.bias": "out_b"}
        top = {"gpt.embeddings.word_embeddings.weight": "wte",
               "gpt.embeddings.position_embeddings.weight": "wpe",
               "gpt.ln_f.weight": "lnf_g", "gpt.ln_f.bias": "lnf_b"}
        out, blocks = {}, {}
        for name, v in leaves.items():
            name = name[len(self._prefix):]
            if name in top:
                out[top[name]] = v
                continue
            _, _, idx, rest = name.split(".", 3)            # gpt.h.<i>.<rest>
            ref = names[rest]
            if ref in ("qkv_w", "qkv_b"):
                v = v[..., perm]
            blocks.setdefault(int(idx), {})[ref] = v
        out["blocks"] = blocks
        return out

    def leaf_names(self, which: str):
        """``"all"``, or ``"ends"``: the embeddings, the final norm and
        the first and last block."""
        names = self._names
        if which == "all":
            return names
        last = self.config["n_layer"] - 1
        keep = tuple(self._prefix + k for k in (
            "gpt.h.0.", f"gpt.h.{last}.", "gpt.embeddings.", "gpt.ln_f."))
        return [n for n in names if n.startswith(keep)]


def build(config, recipe, seed: int, mesh) -> Built:
    """Model, optimizer and ``ParallelTrainer`` as a user builds them,
    with one difference that changes no value: the constructors run
    inside one jitted call under ``rng_guard``, so the weights are made
    on the device from ``seed`` in the dtype they train in, by one
    cached program and not one small program per leaf."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.engine import ParallelTrainer
    from paddle_tpu.framework.random import rng_guard
    from paddle_tpu.jit.functionalization import state_of
    from paddle_tpu.text.models import GPTForPretraining

    holder = {}

    def construct(key):
        with rng_guard(key):
            model = GPTForPretraining(
                tensor_parallel=False, vocab_size=config["vocab_size"],
                hidden_size=config["n_embd"], num_layers=config["n_layer"],
                num_heads=config["n_head"],
                intermediate_size=config.get("n_inner"),
                max_position_embeddings=config["n_positions"],
                attn_dropout=config["attn_pdrop"],
                hidden_dropout=config["resid_pdrop"],
                layer_norm_epsilon=config["layer_norm_epsilon"])
            model.astype(recipe["param_dtype"])
        holder["model"] = model
        return dict(state_of(model)[0])

    init_fn = jax.jit(construct)
    values = init_fn(jax.random.key(seed))
    model = holder["model"]
    for name, box in model.named_parameters():
        box.value = values[name]
    n = sum(int(np.prod(v.shape)) for v in values.values())
    if n != param_count(config):
        raise ValueError(f"the program built {n} parameters, the "
                         f"configuration's shapes give {param_count(config)}")

    o = recipe["optimizer"]
    if o["name"] != "AdamW":
        raise ValueError(f"optimizer {o['name']!r} is not wired in this family")
    opt = paddle.optimizer.AdamW(o["learning_rate"],
                                 parameters=model.parameters(),
                                 slot_dtype=o.get("slot_dtype"))

    if recipe["loss_path"] == "dense":
        wrapped = model

        def loss_fn(logits, labels):
            return nn.functional.cross_entropy(logits, labels)
    elif recipe["loss_path"] == "fused_chunked":
        chunk = recipe["loss_chunk"]

        class FusedLoss(nn.Layer):
            def __init__(self, inner):
                super().__init__()
                self.inner = inner

            def forward(self, batch):
                ids, labels = batch
                return self.inner.fused_head_loss(ids, labels, chunk=chunk,
                                                 ce_kernel="chunked")

        wrapped = FusedLoss(model)

        def loss_fn(out, _labels):
            return out
    else:
        raise ValueError(f"unknown loss_path {recipe['loss_path']!r}")

    trainer = ParallelTrainer(wrapped, opt, loss_fn, mesh=mesh,
                              remat=recipe["remat"])
    return Built(config, recipe, trainer, model, wrapped, loss_fn, init_fn)
