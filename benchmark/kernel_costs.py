"""Operations and bytes a kernel's calls need, from the cell's shapes.

Each function returns ``{"flops", "bytes"}`` for one training step on
one chip: what the kernel's own contract requires (every operand read
once, every result written once, every matrix product it must form),
not what an implementation happens to do. The roofline reader divides
these by the published peaks.
"""


def flash_attention(config, rows_per_chip: int, seq: int) -> dict:
    """The three causal flash-attention kernels of one layer (forward;
    backward for dq; backward for dk and dv), times the layers.

    Per (row, head), with ``unit = 2 * seq * seq * d / 2`` (one
    seq x seq x d product, causal half): the forward forms scores and
    values (2 units); the dq kernel re-forms the scores, forms dP and dQ
    (3); the dkv kernel re-forms the scores, forms dV, dP and dK (4).
    Nine units, against seven for a fused backward that forms the
    scores and dP once: the split is the kernels' design and is counted
    as required work. Operands are bf16, the per-row statistics (lse,
    delta) float32 in 8 lanes."""
    d = config["n_embd"] // config["n_head"]
    calls = rows_per_chip * config["n_head"] * config["n_layer"]
    unit = seq * seq * d                    # 2*s*s*d halved by causality
    tensor = seq * d * 2                    # one (seq, d) bf16 operand
    stat = seq * 8 * 4
    fwd = 4 * tensor + stat                 # q k v -> o, lse
    dq = 5 * tensor + 2 * stat              # q k v do, lse delta -> dq
    dkv = 6 * tensor + 2 * stat             # q k v do, lse delta -> dk dv
    return {"flops": calls * 9 * unit, "bytes": calls * (fwd + dq + dkv)}
