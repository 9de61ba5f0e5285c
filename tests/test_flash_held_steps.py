"""A causal call's hidden grid steps fetch nothing (ISSUE 38): the index
maps of the three flash kernels name a skipped step's nearest computed
block, which is in VMEM already, and the kernels skip exactly those steps.

The maps are read where the kernels are staged: each ``pallas_call``'s
``grid_mapping`` holds an index-map jaxpr an operand, evaluated here at
every grid step and held to a geometry worked out from the mask alone.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _staged
from paddle_tpu.analysis.walker import walk
from paddle_tpu.ops.pallas.flash_attention import (BWD_DKV, BWD_DQ, FWD,
                                                    KERNEL_NAMES,
                                                    flash_attention)

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _staged_maps(sq, sk, heads, kv_heads, blocks, **kw):
    """``{kernel: (grid, [index map of each tensor operand and result])}``
    of a staged forward and backward, each map a function of the grid's
    three indices as numpy arrays."""
    q = jax.ShapeDtypeStruct((1, sq, heads, 128), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, sk, kv_heads, 128), jnp.float32)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, block_q=blocks[0], block_k=blocks[1], interpret=True,
            **kw)), argnums=(0, 1, 2))(q, k, v)

    def evaluate(closed):
        def one(*idx):
            return [jnp.asarray(x, jnp.int32) for x in jax.core.eval_jaxpr(
                closed.jaxpr, closed.consts, *idx)]
        return lambda *idx: tuple(
            np.asarray(x) for x in jax.vmap(one)(
                *(jnp.asarray(i, jnp.int32) for i in idx)))

    out = {}
    for site in walk(jax.make_jaxpr(f)(q, kv, kv)):
        if site.eqn.primitive.name == "pallas_call":
            mapping = site.eqn.params["grid_mapping"]
            # after lens and seed, which live in SMEM whole
            out[site.eqn.params["name"]] = (mapping.grid, [
                evaluate(m.index_map_jaxpr)
                for m in mapping.block_mappings[2:]])
    assert sorted(out) == sorted(KERNEL_NAMES)
    return out


def _grid_steps(grid):
    """Every ``(b, outer, step)`` of a grid, a row each."""
    return np.stack(np.meshgrid(*(np.arange(n) for n in grid),
                                indexing="ij"), -1).reshape(-1, 3)


def _seen(sq, sk, bq, bk, window):
    """``seen[iq][ik]``: does any query of q block ``iq`` see any key of key
    block ``ik`` under the causal mask (and the window), from the positions
    alone."""
    rows, cols = np.arange(sq)[:, None], np.arange(sk)[None, :]
    mask = rows >= cols
    if window is not None:
        mask &= rows - cols < window
    nq, nk = -(-sq // bq), -(-sk // bk)
    return [[bool(mask[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk].any())
             for ik in range(nk)] for iq in range(nq)]


def _nearest(visible, x):
    return min(visible, key=lambda y: abs(y - x))


# name: (sq, sk, query heads, KV heads, (block_q, block_k), window)
_GEOMETRIES = {
    "square_4x4": (512, 512, 1, 1, (128, 128), None),
    "square_4x4_group_6": (512, 512, 6, 1, (128, 128), None),
    "glm_8x4_ungrouped": (1024, 1024, 2, 2, (128, 256), None),
    "qwen3next_8x4_group_8": (1024, 1024, 8, 1, (128, 256), None),
    "tall_blocks_2x4_group_6": (512, 512, 6, 1, (256, 128), None),
    "more_keys_than_queries": (512, 1024, 2, 1, (128, 256), None),
    "more_queries_than_keys_group_8": (1024, 512, 8, 1, (256, 128), None),
    "window_of_two_blocks_group_8": (1024, 1024, 8, 1, (128, 128), 256),
    "window_inside_a_block": (512, 512, 2, 1, (128, 256), 100),
}


@pytest.mark.parametrize("name", list(_GEOMETRIES))
def test_every_grid_step_names_its_tile_or_the_nearest_computed(name):
    """For every step of the three kernels' grids: where the step's tile
    shows a pair the kernel computes it (``_Tiles.kind`` and the predicates
    of ``walk_keys`` / ``walk_queries``) and every map names the tile's own
    blocks; where the mask hides it the kernel skips it and the maps name
    the block of the nearest tile it computes, the one before a hidden
    step of a key walk, the one after a hidden step of an unwindowed query
    walk. The maps of what a step owns (q of the forward and dq kernels,
    K/V of the dk/dv kernel, the results) never move."""
    sq, sk, heads, kv_heads, (bq, bk), window = _GEOMETRIES[name]
    group = heads // kv_heads
    seen = _seen(sq, sk, bq, bk, window)
    nq, nk = len(seen), len(seen[0])
    maps = _staged_maps(sq, sk, heads, kv_heads, (bq, bk), causal=True,
                        window=window)
    band = fa._Band(window, bq, bk, nq, nk)
    held = {}
    for kernel in KERNEL_NAMES:
        tiles = fa._Tiles(kernel, False, bq, bk, nq, nk, band, sq == sk)
        for iq in range(nq):
            for ik in range(nk):
                assert (tiles.kind(iq, ik) is not None) == seen[iq][ik]
        grid, operands = maps[kernel]
        held[kernel] = 0
        steps = _grid_steps(grid)
        got = [m(*steps.T) for m in operands]
        for n, (b, outer, step) in enumerate(steps.tolist()):
            if kernel == BWD_DKV:
                # (KV lane block, key block, head of the group x q step)
                per_head = grid[2] // group
                head, jq = step // per_head, step % per_head
                visible = [i for i in range(nq) if seen[i][outer]]
                iq = jq + (visible[0] if window is not None else 0)
                run = iq < nq and seen[iq][outer]
                walked = tiles.walk_queries(b, outer, step, per_head, group,
                                            ())
                moving = (b * group + head,
                          iq if run else _nearest(visible, iq)
                          if visible else None, 0)
                own = (b, outer, 0)
                # q, k, v, do, lse, delta | dk, dv
                which = [moving, own, own, moving, moving, moving, own, own]
                tile = (iq, outer)
            else:
                # (query lane block, q block, key step)
                visible = [j for j in range(nk) if seen[outer][j]]
                ik = step + (visible[0] if window is not None else 0)
                run = ik < nk and seen[outer][ik]
                walked = tiles.walk_keys(b, outer, step, grid[2], ())
                moving = (b // group, ik if run else _nearest(visible, ik), 0)
                own = (b, outer, 0)
                # q, k, v | o, lse  or  q, k, v, do, lse, delta | dq
                which = [own, moving, moving] + [own] * (len(operands) - 3)
                tile = (outer, ik)
            assert walked[1:3] == tile
            assert any(bool(when) for when, _ in walked[5]) == run, \
                (kernel, b, outer, step)
            held[kernel] += not run
            for operand, want in enumerate(which):
                block = tuple(int(x[n]) for x in got[operand])
                if want[1] is None:     # keys that no query sees: any block
                    assert 0 <= block[1] < nq and block[::2] == want[::2]
                else:
                    assert block == want, (kernel, operand, b, outer, step)
        lane_blocks = grid[0]
        assert held[kernel] == lane_blocks * tiles.held * (
            group if kernel == BWD_DKV else 1)
    if window is None and sq == sk:
        assert held[FWD] == held[BWD_DQ] > 0


def test_without_causal_every_map_is_the_plain_one():
    """No mask, no band: every step runs and names its own blocks."""
    maps = _staged_maps(512, 512, 2, 1, (128, 256))
    for kernel, (grid, operands) in maps.items():
        steps = _grid_steps(grid)
        b, outer, step = steps.T
        zero = np.zeros_like(b)
        if kernel == BWD_DKV:
            moving = (b * 2 + step // 4, step % 4, zero)
            own = (b, outer, zero)
            which = [moving, own, own, moving, moving, moving, own, own]
        else:
            own, moving = (b, outer, zero), (b // 2, step, zero)
            which = [own, moving, moving] + [own] * (len(operands) - 3)
        for m, want in zip(operands, which):
            np.testing.assert_array_equal(np.stack(m(*steps.T)),
                                          np.stack(want))


def _parents_index_maps(group, band, tiles):
    """``_index_maps`` as it stood before the index was held: the plain
    lambdas of an unwindowed call."""
    assert band is None or band.window is None
    return (lambda b, i, j: (b, i, 0),
            lambda b, i, j: (b if group == 1 else b // group, j, 0))


def _parents_dkv_index_maps(group, band, q_steps, tiles):
    assert band is None or band.window is None

    def q_map(b, j, t):
        jq = t if group == 1 else t % q_steps
        return (b if group == 1 else b * group + t // q_steps, jq, 0)
    return q_map, lambda b, j, t: (b, j, 0)


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["plain", "dropout"])
@pytest.mark.parametrize("tiles, blocks, heads, kv_heads, d", [
    ("4x4", (128, 128), 2, 1, 128),
    ("8x4", (128, 256), 4, 4, 64),
], ids=["4x4_group_2", "8x4_two_heads_a_block"])
def test_bit_equal_to_the_plain_maps(tiles, blocks, heads, kv_heads, d,
                                     dropout, monkeypatch):
    """``o``, ``dq``, ``dk`` and ``dv`` with the index held are, bit for
    bit, what the parent's plain maps give: no tile's arithmetic changes,
    nor the order of the tiles an accumulator sees, nor a dropout bit (the
    hash reads the grid's ids)."""
    seq = blocks[0] * int(tiles.split("x")[0])
    ks = jax.random.split(jax.random.key(38), 4)
    q, ct = (jax.random.normal(key, (2, seq, heads, d), jnp.float32)
             for key in ks[:2])
    k, v = (jax.random.normal(key, (2, seq, kv_heads, d), jnp.float32)
            for key in ks[2:])
    kw = dict(dropout_rate=dropout, dropout_seed=7) if dropout else {}

    def run():
        fa._fwd.clear_cache()
        fa._bwd_calls.clear_cache()

        out, grads = _staged.out_and_grads(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
                interpret=True, **kw), q, k, v, ct=ct)
        return (out,) + grads

    held = run()
    monkeypatch.setattr(fa, "_index_maps", _parents_index_maps)
    monkeypatch.setattr(fa, "_dkv_index_maps", _parents_dkv_index_maps)
    plain = run()
    fa._fwd.clear_cache()
    fa._bwd_calls.clear_cache()
    for name, a, b in zip(("o", "dq", "dk", "dv"), held, plain):
        assert float(jnp.max(jnp.abs(b))) > 0.0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _held(flash_staged):
    """The steps held in each kernel, as ``KERNEL_NAMES`` orders them."""
    held = flash_staged("flash_steps_held_total")
    return tuple(int(held.value(kernel=kernel)) for kernel in KERNEL_NAMES)


# name: (q shape, KV heads, kw of the call, steps held a lane block in
#        flash_fwd, flash_bwd_dq, flash_bwd_dkv); blocks from the tuning DB
_CELL_GEOMETRIES = {
    # six latent layers, (512, 1024) blocks: 8 x 4 steps, 20 computed
    "glm-4.7-flash.seq4096": ((4, 4096, 20, 256), 20, {}, (12, 12, 12)),
    # two full layers, (1024, 1024): 4 x 4 steps, 10 computed, 6 heads a
    # KV head
    "laguna-xs2.seq4096_full": ((4, 4096, 48, 128), 8, {}, (6, 6, 36)),
    # one full layer, (512, 1024): 16 x 8 steps, 72 computed, 8 heads
    "qwen3-next-80b-a3b-instruct.seq8192": (
        (2, 8192, 16, 256), 2, {}, (56, 56, 448)),
    # one tile a lane block, two heads in it
    "gpt2-small.seq1024": ((16, 1024, 12, 64), 12, {}, (0, 0, 0)),
    "gpt2-medium.seq1024": ((8, 1024, 16, 64), 16, {}, (0, 0, 0)),
    # the sliding layers' band of two 512-blocks held before this PR what it
    # holds now: the second step of the first q block and of the last key
    # block (8 heads)
    "laguna-xs2.seq4096_window": (
        (4, 4096, 64, 128), 8, dict(window=512), (1, 1, 8)),
    # a band that is one block wide has no short row
    "window_of_one_position": (
        (1, 1024, 2, 128), 2, dict(window=1, block_q=256, block_k=256),
        (0, 0, 0)),
    "not_causal": ((1, 2048, 2, 128), 2, dict(causal=False), (0, 0, 0)),
}


@pytest.mark.parametrize("cell", list(_CELL_GEOMETRIES))
def test_steps_held_at_the_cells_geometries(cell, flash_staged):
    """``flash_steps_held_total{kernel}`` of one staged layer at the shapes
    the cells run, in the blocks the tuning DB resolves for them: the steps
    of a lane block's grid over the diagonal (the dk/dv kernel's for each
    query head of its group), none where a lane block is one tile."""
    shape, kv_heads, kw, want = _CELL_GEOMETRIES[cell]
    kw = dict({"causal": True}, **kw)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(shape[:2] + (kv_heads, shape[3]), jnp.bfloat16)
    jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, interpret=True, **kw).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, kv, kv)
    assert _held(flash_staged) == want


def test_a_tile_table_holds_nothing(flash_staged):
    """Block diffusion walks a list of the visible tiles: every step
    computes."""
    q = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.float32)
    jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_diffusion=4, block_q=256, block_k=256,
        interpret=True)), argnums=(0, 1, 2)))(q, q, q)
    assert _held(flash_staged) == (0, 0, 0)
