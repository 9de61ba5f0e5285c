"""Operations and bytes of the gated delta rule, from the cell's shapes,
under ``kernel_costs.py``'s contract: what the rule must do as its
definition states it (``nn/functional/linear_attention.py``, the recurrence:
every operand read once, every result written once, every product it must
form), for one training step on one chip, whatever implements it. Not the
chunk size, not the triangular systems a chunked form adds, not
recomputation under a checkpoint: a larger chunk or a kernel that keeps the
state on the chip changes the measured time and not this count.

The configuration is read as ``families/qwen3next.py`` reads it: layer ``i``
is full attention where ``(i + 1) % full_attention_interval == 0``, the
others are linear.
"""


def linear_layers(config) -> int:
    every = config["full_attention_interval"]
    return sum((i + 1) % every != 0
               for i in range(config["num_hidden_layers"]))


def gated_delta_rule(config, rows_per_chip: int, seq: int) -> dict:
    """A value head a position a layer: forward the three ``d_k x d_v``
    products of the recurrence (``S^T k``, ``k u^T``, ``S^T q``: ``2 d_k
    d_v`` operations each) and backward the two of each, nine in all.
    Bytes: ``q``, ``k`` (``d_k``) and ``v``, ``o`` (``d_v``) once in bf16
    and their four gradients once, ``g``, ``beta`` and their gradients once
    in float32. No state: ``d_k x d_v`` float32 a head fits on the chip
    beside the kernel, and nothing in the definition asks for it in HBM."""
    heads = config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    calls = rows_per_chip * seq * heads * linear_layers(config)
    return {"flops": calls * 9 * 2 * dk * dv,
            "bytes": calls * (2 * (2 * dk + 2 * dv) * 2 + 4 * 4)}
