"""Operations and bytes of the flash kernels under the block-diffusion
mask, from the cell's shapes, under ``kernel_costs.py``'s contract: what the
kernels must do (every operand read once, every result written once, every
product they must form at the pairs the mask leaves visible), for one
training step on one chip, whatever implements it: one call over ``2L`` or
two, whichever tiles a kernel happens to run. Recomputation under a
checkpoint is not required work and is not counted.
"""


def visible_keys(seq: int, block: int) -> float:
    """Mean keys a query of a ``[noised ; clean]`` row of ``2 x seq``
    positions sees, noised or clean alike: a noised query in block ``b`` its
    own ``block`` noised keys and the ``b * block`` clean ones before it, a
    clean one ``(b + 1) * block``; over ``seq / block`` blocks both average
    ``(seq + block) / 2``."""
    return (seq + block) / 2


def flash_block_diffusion(config, rows_per_chip: int, seq: int) -> dict:
    """The three flash kernels over every layer, ``seq`` clean tokens a row:
    ``2 * seq`` positions.

    Per (row, query head) one product over the visible pairs is ``2 * (2 *
    seq) * visible_keys * d`` operations; nine such products as in
    ``kernel_costs.flash_attention`` (forward 2, dq 3, dk/dv 4). Bytes: q,
    o, do and dq once per query head, k, v, dk and dv once per KV head
    (grouped heads share them), the float32 statistics in 8 lanes per query
    head, all over ``2 * seq`` positions."""
    d, kv = config["head_dim"], config["num_key_value_heads"]
    heads, layers = config["num_attention_heads"], config["num_hidden_layers"]
    positions = 2 * seq
    block = config["block_diffusion"]["block_length"]
    unit = 2 * positions * visible_keys(seq, block) * d
    tensor = positions * d * 2              # one (positions, d) bf16 operand
    stat = positions * 8 * 4
    fwd = heads * (2 * tensor + stat) + kv * 2 * tensor
    dq = heads * (3 * tensor + 2 * stat) + kv * 2 * tensor
    dkv = heads * (2 * tensor + 2 * stat) + kv * 4 * tensor
    return {"flops": layers * rows_per_chip * heads * 9 * unit,
            "bytes": layers * rows_per_chip * (fwd + dq + dkv)}
