"""Operations and bytes of Mamba-2's state-space scan, from the cell's
shapes, under ``kernel_costs.py``'s contract: what the scan must do as its
definition states it (``nn/functional/state_space.py``, the recurrence:
every operand read once, every result written once, every product it must
form), for one training step on one chip, whatever implements it. Not the
chunk size, not the masked scores and chunk states a chunked form adds, not
recomputation under a checkpoint: a larger chunk or a kernel that keeps the
state on the chip changes the measured time and not this count.

The configuration is read as ``families/nemotronh.py`` reads it: the first
``num_hidden_layers`` characters of ``hybrid_override_pattern``, ``M`` a
Mamba layer.
"""


def mamba_layers(config) -> int:
    pattern = config["hybrid_override_pattern"]
    return pattern[:config["num_hidden_layers"]].count("M")


def ssd_scan(config, rows_per_chip: int, seq: int) -> dict:
    """A head a position a layer: forward the two ``P x N`` products of the
    recurrence (``dt x B^T``, ``S C``: ``2 P N`` operations each) and
    backward the two of each, six in all. Bytes: ``x`` and ``y`` (``P``)
    once in bf16 and their two gradients once, ``dt`` and its gradient once
    in float32, a head a position; ``B`` and ``C`` (``N``) and their
    gradients once in bf16, a group a position (the group's heads share
    them). No state: ``P x N`` float32 a head fits on the chip beside a
    kernel, and nothing in the definition asks for it in HBM."""
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    positions = rows_per_chip * seq * mamba_layers(config)
    return {"flops": positions * heads * 6 * 2 * p * n,
            "bytes": positions * (heads * (4 * p * 2 + 2 * 4)
                                  + groups * 4 * n * 2)}
