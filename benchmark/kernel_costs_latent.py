"""Operations and bytes of the kernels a latent-attention decoder runs, from
the cell's shapes, under ``kernel_costs.py``'s contract: what the kernel
must do (every operand read once, every result written once, every product
it must form), for one training step on one chip. Recomputation under a
checkpoint is not required work and is not counted.

The configuration is read as ``families/glm4moelite.py`` reads it.
"""
from benchmark.kernel_costs_mixed import visible_keys


def latent_layers(config) -> int:
    """Latent-attention layers a step runs: the built trunk and the
    multi-token-prediction module's block."""
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def flash_latent(config, rows_per_chip: int, seq: int) -> dict:
    """The three flash kernels over every latent-attention layer.

    Multi-head latent attention hands the kernels ``num_attention_heads``
    full heads of q, k and v (no grouping: every key and value head is
    the up-projection's own), all ``qk_nope_head_dim + qk_rope_head_dim =
    v_head_dim`` wide. Per (row, head) one ``seq x keys x d`` product over
    the causal half is ``2 * seq * (seq + 1) / 2 * d`` operations; nine
    such products as in ``kernel_costs.flash_attention`` (forward 2, dq 3,
    dk/dv 4). Bytes: q, k, v, o and their gradients once a head in bf16
    (forward q k v -> o; dq: q k v do -> dq; dk/dv: q k v do -> dk dv), the
    float32 statistics in 8 lanes."""
    d = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    if config["v_head_dim"] != d:
        raise ValueError("the flash kernels take one head width")
    calls = rows_per_chip * config["num_attention_heads"] \
        * latent_layers(config)
    unit = 2 * seq * visible_keys(seq) * d
    tensor = seq * d * 2                    # one (seq, d) bf16 operand
    stat = seq * 8 * 4
    fwd = 4 * tensor + stat
    dq = 5 * tensor + 2 * stat
    dkv = 6 * tensor + 2 * stat
    return {"flops": calls * 9 * unit, "bytes": calls * (fwd + dq + dkv)}
