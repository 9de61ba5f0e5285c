"""The three Pallas kernels must COMPILE for a TPU v5e at the shapes
``chip_smoke.py`` runs — checked here without a chip.

Interpret mode (every other kernel test) never meets the TPU lowering's
block-shape rule or Mosaic's scoped-VMEM limit; both broke kernels that
were green on CPU. ``jax.experimental.topologies`` builds a v5e device
description from the installed libtpu, and jit's AOT path compiles
against it: nothing executes, so this says "lowers and fits", never
"is right" — numerics on the chip are ``chip_smoke.py``'s ``kernels``
phase.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot build a v5e topology: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    # conftest pins "highest" matmul precision for CPU numerics; the chip
    # runs the default, and Mosaic's multi-pass fp32 matmul needs VMEM the
    # block bounds are not sized for
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


def _tile_kinds(seq, block, window):
    """(dense, triangular, masked) tiles a lane block's grid walks in each
    kernel of a causal call, as ``flash_tiles_staged_total`` counts them."""
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    n = seq // block
    band = fa._Band(window, block, block, n, n)
    return {kernel: tuple(fa._Tiles(kernel, False, block, block, n, n, band,
                                    True).counts[kind]
                          for kind in fa.TILE_KINDS)
            for kernel in fa.KERNEL_NAMES}


@pytest.mark.parametrize("kernel, transform", [
    ("flash_fwd", "jvp("), ("flash_bwd_dq", "transpose(jvp("),
    ("flash_bwd_dkv", "transpose(jvp(")])
def test_flash_kernels_keep_name_and_scope_on_the_tpu(v5e, kernel, transform):
    """What the benchmark's trace readers find a kernel by: the compiled
    program calls it ``%<name>.N`` and its ``op_name`` (the trace's
    ``tf_op``) holds the scope it was staged under and the kernel's own."""
    import re

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def f(q, k, v):
        def loss(q, k, v):
            # straight to the kernel: scaled_dot_product_attention's gate
            # asks the running backend, which is the CPU here
            with jax.named_scope("attn"):
                return jnp.sum(flash_attention(
                    q, k, v, causal=True).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    qkv = ((4, 1024, 12, 64), jnp.bfloat16)
    text = _compile(f, v5e, qkv, qkv, qkv)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and f"%{kernel}" in line]
    assert calls, kernel
    op_name = re.search(r'op_name="([^"]+)"', calls[0]).group(1)
    # the layers of a model share one staged forward and one staged
    # backward (an inner jit each); the call site's scopes stay in front
    shared = "jit(_fwd)" if kernel == "flash_fwd" else "jit(_bwd_calls)"
    assert op_name == f"jit(f)/{transform}attn{')' * transform.count('(')}" \
                      f"/{shared}/{kernel}/pallas_call"


@pytest.mark.parametrize("shape", [
    (8, 1024, 12, 64),     # chip_smoke.py's GPT-base
    (16, 1024, 12, 64),    # gpt2-small.seq1024 and .dp4, rows a chip
    (8, 1024, 16, 64),     # gpt2-medium.seq1024
    (4, 1024, 16, 128),    # head width 128: gpt3-1p3b, the next one
], ids=lambda s: "x".join(map(str, s)))
def test_flash_attention_fwd_bwd_gpt_base(v5e, shape):
    """Lowers and fits on a v5e at every shape the cells run, in the blocks
    the tuning DB resolves for it ((1024, 1024) at head width 64), with the
    bf16 products (transposed-left ones in ``flash_bwd_dkv`` among them)
    that interpret mode never shows Mosaic."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    qkv = (shape, jnp.bfloat16)
    _compile(f, v5e, qkv, qkv, qkv)
    # what lowered: the one causal tile of a lane block cut into sub-blocks
    # in the two backward kernels, whole under its mask in the forward
    assert _tile_kinds(1024, 1024, None) == {
        "flash_fwd": (0, 0, 1), "flash_bwd_dq": (0, 1, 0),
        "flash_bwd_dkv": (0, 1, 0)}


@pytest.mark.parametrize("heads, window, blocks", [
    (48, None, (1024, 1024)), (64, 512, (512, 512))],
    ids=["full_48_over_8", "window_512_64_over_8"])
def test_flash_grouped_heads_and_window_at_the_mixed_decoder_shapes(
        v5e, heads, window, blocks):
    """laguna-xs2.seq4096's two kinds of layer, rows of 4,096 positions at
    head width 128 over 8 KV heads: the kernels lower and fit in the blocks
    of the tuning DB's row (cut to the window where there is one: the grid
    says which ran), and the dk/dv kernel's grid runs over the KV heads."""
    import re

    from paddle_tpu.analysis.walker import walk
    from paddle_tpu.ops.pallas import tuner
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    cfg, source = tuner.resolve(
        "flash_attention", jnp.bfloat16, tuner.flash_dims(128, 4096, 4096), {})
    assert source == "db" and (cfg["block_q"], cfg["block_k"]) == (1024, 1024)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    kv = ((2, 4096, 8, 128), jnp.bfloat16)
    # (rows x heads, query blocks, key blocks walked): the whole row of
    # 1,024-key blocks without a window, the band's two of 512 with it
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window))(
            *(jax.ShapeDtypeStruct(*a) for a in (
                ((2, 4096, heads, 128), jnp.bfloat16), kv, kv)))
    grids = [site.eqn.params["grid_mapping"].grid for site in walk(jaxpr)
             if site.eqn.primitive.name == "pallas_call"]
    assert grids == [(2 * heads, 4096 // blocks[0],
                      2 if window else 4096 // blocks[1])]
    text = _compile(f, v5e, ((2, 4096, heads, 128), jnp.bfloat16), kv, kv)
    dkv = [line for line in text.splitlines()
           if "tpu_custom_call" in line and "flash_bwd_dkv" in line]
    # dk and dv come out per KV head (2 rows x 8), q goes in per query head
    assert dkv and re.search(
        r"= \(bf16\[16,4096,128\]\S*, bf16\[16,4096,128\]", dkv[0])
    assert f"bf16[{2 * heads},4096,128]" in dkv[0]
    # what lowered. Full layers: 4 tiles on the diagonal, 6 dense before
    # them. Window layers: 8 on the diagonal and the 7 band edges, cut in
    # the forward and the dq kernel (sub-blocks of 128 rows); the dk/dv
    # kernel cuts from 256 rows up
    cut, whole = ((0, 15, 0), (0, 0, 15)) if window else ((6, 4, 0), (6, 0, 4))
    assert _tile_kinds(4096, blocks[0], window) == {
        "flash_fwd": cut if window else whole, "flash_bwd_dq": cut,
        "flash_bwd_dkv": whole if window else cut}


def test_flash_at_head_width_256_in_the_tuning_dbs_blocks(v5e):
    """qwen3-next-80b-a3b-instruct.seq8192's one full layer: rows of 8,192
    positions, 16 query heads over 2 KV heads at head width 256 (one head a
    256-lane block). The tuning DB's row says (512, 1024): 5.09 ms a call
    forward and backward against 4.66 in (1024, 1024), 5.32 at 512 and 8.49
    at 256 (my chip run, PR 33); (1024, 1024) is not taken because the
    dk/dv kernel then leaves the compiler too little VMEM beside it in a
    whole training step of one row (refused here: "ran out of memory in
    memory space vmem"), alone it fits. The three kernels lower and fit."""
    from paddle_tpu.ops.pallas import tuner
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    cfg, source = tuner.resolve(
        "flash_attention", jnp.bfloat16, tuner.flash_dims(256, 8192, 8192),
        {})
    assert source == "db" and (cfg["block_q"], cfg["block_k"]) == (512, 1024)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    kv = ((2, 8192, 2, 256), jnp.bfloat16)
    text = _compile(f, v5e, ((2, 8192, 16, 256), jnp.bfloat16), kv, kv)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any("tpu_custom_call" in line and f"%{kernel}" in line
                   for line in text.splitlines()), kernel
    # the operands the benchmark's flash_attn_ms_per_step finds them by
    assert "s32[32]" in text and "bf16[32,8192,256]" in text


@pytest.mark.parametrize("rows", [4, 1], ids=["timed_4_rows",
                                              "compared_1_row"])
def test_flash_at_20_ungrouped_heads_of_width_256(v5e, rows):
    """glm-4.7-flash.seq4096's six latent-attention layers: rows of 4,096
    positions, 20 query heads over 20 key/value heads (no grouping: each is
    the up-projection's own) at head width 256, the step's four rows and
    the comparison's one, in the blocks the lookup resolves for that shape,
    (512, 1024): 8 x 4 steps a lane block, 12 of them over the diagonal,
    where the index maps hold their block (a ``min``, a ``max`` and a
    division of scalars in the maps and in the kernels' ``run``). The three
    kernels lower and fit."""
    from paddle_tpu.ops.pallas import tuner
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    cfg, source = tuner.resolve(
        "flash_attention", jnp.bfloat16, tuner.flash_dims(256, 4096, 4096),
        {})
    assert source == "db" and (cfg["block_q"], cfg["block_k"]) == (512, 1024)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    qkv = ((rows, 4096, 20, 256), jnp.bfloat16)
    text = _compile(f, v5e, qkv, qkv, qkv)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any("tpu_custom_call" in line and f"%{kernel}" in line
                   for line in text.splitlines()), kernel
    # the operands the benchmark's flash_attn_ms_per_step finds them by
    assert f"s32[{20 * rows}]" in text
    assert f"bf16[{20 * rows},4096,256]" in text


@pytest.mark.parametrize("block, non_power", [(512, False), (1024, False),
                                              (384, True)],
                         ids=["512", "1024", "384_block_length_12"])
def test_flash_block_diffusion_at_the_sdar_cells_shape(v5e, block, non_power):
    """sdar-30b-a3b-chat.seq4096's attention, [noised ; clean] rows of 8,192
    positions at head width 128 with 8 query heads a KV head under the
    block-diffusion mask: the three kernels lower and fit, their grids walk
    the tables' 80 (24 in 1,024-blocks) tiles and nothing else, and their
    first two operands are still ``lens`` and ``seed``, by which the
    benchmark's ``flash_attn_ms_per_step`` finds them. A block length that
    is no power of two divides by ``lax.div`` in the mask."""
    import re

    from paddle_tpu.analysis.walker import walk
    from paddle_tpu.ops.pallas.flash_attention import (KERNEL_NAMES,
                                                        flash_attention)

    length = 12 if non_power else 4

    def attend(q, k, v):
        return flash_attention(q, k, v, block_diffusion=length,
                               block_q=block, block_k=block)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    seq = 6144 if non_power else 8192
    q, kv = ((1, seq, 8, 128), jnp.bfloat16), ((1, seq, 1, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(f)(*(jax.ShapeDtypeStruct(*a) for a in (q, kv, kv)))
    grids = {site.eqn.params["name"]: site.eqn.params["grid_mapping"].grid
             for site in walk(jaxpr)
             if site.eqn.primitive.name == "pallas_call"}
    n = seq // 2 // block
    tiles = n + n * (n + 1)
    assert (seq, tiles) in ((8192, 80), (8192, 24), (6144, 80))
    assert grids == {"flash_fwd": (8, 1, tiles), "flash_bwd_dq": (8, 1, tiles),
                     "flash_bwd_dkv": (1, 1, 8 * tiles)}
    text = _compile(f, v5e, q, kv, kv)
    pattern = re.compile(
        r"operand_layout_constraints=\{s32\[8\]\{0\}, s32\[1\]\{0\}, "
        r"s32\[(\d+)\]\{0\}")
    for kernel in KERNEL_NAMES:
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and f"%{kernel}" in line]
        assert calls, kernel
        steps = int(pattern.search(calls[0]).group(1))
        assert steps == grids[kernel][2]


@pytest.mark.parametrize("heads, kv_heads, kw", [
    (8, 2, {}), (3, 1, {"dropout_rate": 0.1, "dropout_seed": 5})],
    ids=["8_over_2", "3_over_1_dropout"])
def test_flash_grouped_heads_at_width_64(v5e, heads, kv_heads, kw):
    """Grouped heads at head width 64: a query head's KV head lies in
    another slot of its lane block, found by a traced index (a 32-bit
    lane rotation and a select in ``_Pack.place``), and with a KV head
    padded on the dropout hash's head id is a scalar division: neither
    runs in any cell, so Mosaic sees them here."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, **kw).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    kv = ((2, 1024, kv_heads, 64), jnp.bfloat16)
    _compile(f, v5e, ((2, 1024, heads, 64), jnp.bfloat16), kv, kv)


@pytest.mark.parametrize("shape, kv_heads, rotated, norm", [
    ((4, 4096, 64, 128), 8, 128, False),   # laguna-xs2, a sliding layer
    ((4, 4096, 48, 128), 8, 64, False),    # a full one: 64 lanes, two rolls
    ((2, 8192, 32, 128), 4, 128, True),    # sdar-30b-a3b-chat: with QK norm
    ((2, 8192, 16, 256), 2, 64, True),     # qwen3-next: two lane blocks a
                                           # head, a quarter of them rotated
    ((4, 4096, 20, 256), 20, 64, False),   # glm-4.7-flash: the queries of a
                                           # latent layer, [rope | nope]
], ids=["sliding_64_and_8", "full_48_and_8_half_rotated",
        "sdar_32_and_4_norm", "qwen3next_16_and_2_norm_d256",
        "glm47flash_20_latent_d256"])
def test_rope_kernels_at_the_mixed_decoder_shapes(v5e, shape, kv_heads,
                                                  rotated, norm):
    """QK norm and RoPE of q and k as the two mixed-decoder cells stage
    them: ``rope_fwd`` and ``rope_bwd`` lower and fit on a v5e in the row
    tiles the VMEM budget gives, under the scopes the benchmark's
    ``rope_ms_per_step`` and ``qk_norm_ms_per_step`` read, q and k of two
    layers through one staged forward and one staged backward, and the
    result leaves head-major (no ``transpose`` follows the kernel)."""
    import re

    from paddle_tpu.nn.functional.rotary import rope_tables
    from paddle_tpu.ops.pallas import rotary

    b, seq, heads, d = shape
    inv_freq = np.ones((rotated // 2,), np.float32)

    def f(q, k, wq, wk, positions):
        def loss(q, k, wq, wk):
            cos, sin = rope_tables(inv_freq, 1.0, positions, seq, d)
            total = 0.0
            for _ in range(2):                     # two layers
                with jax.named_scope("rope"):
                    q, k = (rotary.rotary(x, cos, sin, rotated // 2,
                                          w if norm else None)
                            for x, w in ((q, wq), (k, wk)))
                total += sum(jnp.sum(x.astype(jnp.float32) ** 2)
                             for x in (q, k))
            return total
        return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, wq, wk)

    assert rotary.supported(shape, jnp.bfloat16, rotated // 2)
    weight = ((d,), jnp.bfloat16)
    text = _compile(f, v5e, (shape, jnp.bfloat16),
                    ((b, seq, kv_heads, d), jnp.bfloat16), weight, weight,
                    ((seq,), jnp.int32))
    for kernel, shared, transform in (
            ("rope_fwd", "jit(_fwd)", "jvp("),
            ("rope_bwd", "jit(_bwd_call)", "transpose(jvp(")):
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and f"%{kernel}" in line]
        assert len(calls) == 4, (kernel, len(calls))
        op_name = re.search(r'op_name="([^"]+)"', calls[0]).group(1)
        assert op_name == f"jit(f)/{transform}rope{')' * transform.count('(')}" \
                          f"/{shared}/{kernel}/pallas_call"
    forward = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "%rope_fwd" in line]
    assert any(f"bf16[{b},{heads},{seq},{d}]" in line for line in forward)


@pytest.mark.parametrize("rows", [2, 1])
def test_gated_delta_kernels_at_the_qwen3_next_cells_shape(v5e, rows):
    """The gated delta rule of ``qwen3-next-80b-a3b-instruct.seq8192``'s
    three linear layers: 32 value heads of ``d_k = d_v = 128`` over 8,192
    positions in bf16, two timed rows and the comparison's one.
    ``gated_delta_fwd`` and ``gated_delta_bwd`` lower and fit Mosaic's
    scoped VMEM in the tile the module gives, in one program with the flash
    kernels of the cell's full layer ((512, 1024) blocks at head width
    256); two layers share one staged forward and one staged backward, and
    the compiled program calls each kernel by its name with the scope
    ``gated_delta_rule`` (which ``F.gated_delta_rule`` opens: its gate asks
    the running backend, the CPU here) in front, forward and transposed:
    what ``gated_delta_rule_ms_per_step`` finds them by."""
    import re

    from paddle_tpu.ops.pallas import gated_delta
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    shape = (rows, 8192, 32, 128)
    assert gated_delta.supported(shape, shape, jnp.bfloat16, 64)

    def f(q, k, v, g, beta, fq, fk, fv):
        def loss(q, k, v, g, beta, fq, fk, fv):
            for _ in range(2):                     # two layers
                with jax.named_scope("gated_delta_rule"):
                    v = gated_delta.gated_delta(q, k, v, g, beta)
            with jax.named_scope("attn"):
                o = flash_attention(fq, fk, fv, causal=True)
            return jnp.sum(v.astype(jnp.float32) ** 2) \
                + jnp.sum(o.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=tuple(range(8)))(
            q, k, v, g, beta, fq, fk, fv)

    x, vec = (shape, jnp.bfloat16), (shape[:3], jnp.float32)
    kv = ((rows, 8192, 2, 256), jnp.bfloat16)
    text = _compile(f, v5e, x, x, x, vec, vec,
                    ((rows, 8192, 16, 256), jnp.bfloat16), kv, kv)
    for kernel, shared, transform, calls in (
            ("gated_delta_fwd", "jit(_fwd)", "jvp(", 2),
            ("gated_delta_bwd", "jit(_bwd_call)", "transpose(jvp(", 2)):
        lines = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and f"%{kernel}" in line]
        assert len(lines) == calls, (kernel, len(lines))
        op_name = re.search(r'op_name="([^"]+)"', lines[0]).group(1)
        assert op_name == f"jit(f)/{transform}gated_delta_rule" \
                          f"{')' * transform.count('(')}/{shared}/{kernel}" \
                          f"/pallas_call"
    assert any("tpu_custom_call" in line and "%flash_bwd_dkv" in line
               for line in text.splitlines())
    # the residual: the state entering each chunk, float32
    assert f"f32[{rows},32,128,128,128]" in text


@pytest.mark.parametrize("rows", [2, 1])
def test_ssd_scan_kernels_at_the_nemotron_cells_shape(v5e, rows):
    """The state-space scan of ``nemotron-3-nano-30b-a3b.seq8192``'s Mamba
    layers: 64 heads of 64 over 8 groups of a 128-wide state, 8,192
    positions in bf16, two timed rows and the comparison's one. ``ssd_fwd``
    and ``ssd_bwd`` lower and fit Mosaic's scoped VMEM in the tile the
    module gives; two layers share one staged forward and one staged
    backward, and the compiled program calls each kernel by its name with
    the scope ``ssd_scan`` (which ``F.ssd_scan`` opens: its gate asks the
    running backend, the CPU here) in front, forward and transposed: what
    ``ssd_scan_ms_per_step`` and ``mamba_ms_per_step`` find them by."""
    import re

    from paddle_tpu.ops.pallas import ssd_scan

    x_shape, bc_shape = (rows, 8192, 64, 64), (rows, 8192, 8, 128)
    assert ssd_scan.supported(x_shape, bc_shape, jnp.bfloat16, 128)

    def f(x, dt, A, B, C, D):
        def loss(x, dt, A, B, C, D):
            for _ in range(2):                     # two layers
                with jax.named_scope("ssd_scan"):
                    x = ssd_scan.ssd_scan(x, dt, A, B, C, D)
            return jnp.sum(x.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=tuple(range(6)))(x, dt, A, B, C, D)

    bc = (bc_shape, jnp.bfloat16)
    head = ((64,), jnp.float32)
    text = _compile(f, v5e, (x_shape, jnp.bfloat16),
                    (x_shape[:3], jnp.float32), head, bc, bc, head)
    for kernel, shared, transform in (
            ("ssd_fwd", "jit(_fwd)", "jvp("),
            ("ssd_bwd", "jit(_bwd_call)", "transpose(jvp(")):
        lines = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and f"%{kernel}" in line]
        assert len(lines) == 2, (kernel, len(lines))
        op_name = re.search(r'op_name="([^"]+)"', lines[0]).group(1)
        assert op_name == f"jit(f)/{transform}ssd_scan" \
                          f"{')' * transform.count('(')}/{shared}/{kernel}" \
                          f"/pallas_call"
    # the residual: the state entering each chunk, float32, a group's heads
    # stacked along the rows
    assert f"f32[{rows},8,64,512,128]" in text


@pytest.mark.parametrize("h,dtype", [(768, jnp.bfloat16),
                                     (768, jnp.float32),
                                     (2048, jnp.bfloat16)])
def test_fused_ce_fwd_bwd_fits_vmem(v5e, h, dtype):
    """h768 bf16 is the bench shape (its DB row asked for 18 MB of the
    16 MB scoped VMEM before the bound); fp32 and h2048 take the module
    defaults, which did not fit either."""
    from paddle_tpu.ops.pallas.fused_ce import fused_lm_ce

    def f(hid, w, lbl):
        return jax.value_and_grad(
            lambda a, b: fused_lm_ce(a, b, lbl, interpret=False),
            argnums=(0, 1))(hid, w)

    _compile(f, v5e, ((8192, h), dtype), ((h, 50304), dtype),
             ((8192,), jnp.int32))


@pytest.mark.parametrize("tq", [1, 5])
def test_paged_attention_decode_and_verify(v5e, tq):
    from paddle_tpu.ops.pallas import paged_attention as pa

    b, h, d, ps, pages, width = 4, 12, 64, 16, 64, 8
    impl = pa._pallas_paged_decode if tq == 1 else pa._pallas_paged_verify

    def f(q, kp, vp, tables, lens, kn, vn):
        # the wrapper, not the dispatcher: paged_decode_supported gates
        # on the RUNNING backend, which is the CPU here
        return impl(q, kp, vp, tables, lens, kn, vn, d ** -0.5,
                    max(16, pa.verify_rows(tq)), False)

    tok = ((b, tq, h, d), jnp.bfloat16)
    pool = ((pages, h, ps, d), jnp.bfloat16)
    _compile(f, v5e, tok, pool, pool, ((b, width), jnp.int32),
             ((b,), jnp.int32), tok, tok)
