"""Operations and bytes of the kernels a mixed decoder adds (window and
full attention over grouped KV heads, the held experts' grouped products),
from the cell's shapes, under ``kernel_costs.py``'s contract: what the
kernel must do (every operand read once, every result written once, every
product it must form), for one training step on one chip. Recomputation
under a checkpoint is not required work and is not counted.

The configuration is read as ``families/laguna.py`` reads it: the first
``num_hidden_layers`` entries of the per-layer lists.
"""


def _layers(config):
    n = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:n],
                    config["num_attention_heads_per_layer"][:n],
                    config["mlp_layer_types"][:n]))


def visible_keys(seq: int, window=None) -> float:
    """Mean keys a query sees over ``seq`` causal positions, ``min(i + 1,
    window)`` at position ``i``."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def flash_window(config, rows_per_chip: int, seq: int) -> dict:
    """The three flash kernels over every layer, full and sliding.

    Per (row, query head) one ``seq x keys x d`` product is ``2 * seq *
    visible_keys * d`` operations, the keys being those the causal mask and
    the layer's window leave; nine such products as in
    ``kernel_costs.flash_attention`` (forward 2, dq 3, dk/dv 4). Bytes: q,
    o, do and dq once per query head, k, v, dk and dv once per KV head
    (grouped heads share them), the float32 statistics in 8 lanes per
    query head."""
    d, kv = config["head_dim"], config["num_key_value_heads"]
    tensor = seq * d * 2                    # one (seq, d) bf16 operand
    stat = seq * 8 * 4
    flops = nbytes = 0
    for kind, heads, _ in _layers(config):
        window = config["sliding_window"] \
            if kind == "sliding_attention" else None
        unit = 2 * seq * visible_keys(seq, window) * d
        flops += rows_per_chip * heads * 9 * unit
        fwd = heads * (2 * tensor + stat) + kv * 2 * tensor
        dq = heads * (3 * tensor + 2 * stat) + kv * 2 * tensor
        dkv = heads * (2 * tensor + 2 * stat) + kv * 4 * tensor
        nbytes += rows_per_chip * (fwd + dq + dkv)
    return {"flops": flops, "bytes": nbytes}


def moe_experts(config, rows_per_chip: int, seq: int) -> dict:
    """The held experts' nine grouped products of every sparse layer: three
    forward (gate, up, down), and for each its two backward products (the
    rows' gradient and the weights'), at the expected number of assignments
    ``tokens * experts_per_token * held / router width``. Bytes: each
    product's two operands and its result once, bf16: the stacked weights
    of all held experts whatever the load, the rows at the expected
    assignments."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["num_experts"]
    rows = rows_per_chip * seq * config["num_experts_per_tok"] * held \
        / config["published"]["num_experts"]
    x, mid, w = rows * h * 2, rows * f * 2, held * h * f * 2
    # (operands..., result) of the forward products and of their two
    # backward products
    up_like = [(x, w, mid), (mid, w, x), (x, mid, w)]      # gate, and up
    down = [(mid, w, x), (x, w, mid), (mid, x, w)]
    sparse = sum(ffn == "sparse" for _, _, ffn in _layers(config))
    return {"flops": sparse * 9 * 2 * rows * h * f,
            "bytes": sparse * sum(sum(p) for p in 2 * up_like + down)}
