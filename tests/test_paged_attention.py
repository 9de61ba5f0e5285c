"""Paged decode/prefill attention numerics
(paddle_tpu/ops/pallas/paged_attention.py): both the XLA gather baseline
and the Pallas kernel (interpret mode on CPU) must reproduce a dense
contiguous-KV reference to dtype tolerance — the ISSUE's acceptance
gate — including shuffled block tables, ragged lengths, empty rows, and
both q_pad tile choices.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_decode_supported, paged_dims,
    paged_prefill_attention)


def case(b=4, h=2, d=32, ps=8, pool_pages=12, width=6, seed=0,
         dtype=np.float32, with_new=True, lens=None):
    """Random pool + per-row shuffled tables; returns arrays + a dense
    per-row (K, V) reconstruction for the reference."""
    rs = np.random.RandomState(seed)
    q = rs.randn(b, 1, h, d).astype(dtype)
    kp = rs.randn(pool_pages, ps, h, d).astype(dtype)
    vp = rs.randn(pool_pages, ps, h, d).astype(dtype)
    tables = np.stack([rs.permutation(pool_pages)[:width]
                       for _ in range(b)]).astype(np.int32)
    if lens is None:
        lens = rs.randint(0, width * ps + 1, (b,)).astype(np.int32)
    else:
        lens = np.asarray(lens, np.int32)
    kn = rs.randn(b, 1, h, d).astype(dtype) if with_new else None
    vn = rs.randn(b, 1, h, d).astype(dtype) if with_new else None
    return q, kp, vp, tables, lens, kn, vn


def head_major(x):
    """The token-major (P, ps, H, D) pool the float64 references read,
    presented in the API's head-major (P, H, ps, D) layout."""
    return jnp.asarray(np.swapaxes(np.asarray(x), 1, 2))


def dense_decode_ref(q, kp, vp, tables, lens, kn, vn):
    """float64 contiguous-KV attention: gather each row's pages into a
    dense sequence, append the new token, plain softmax."""
    b, _, h, d = q.shape
    ps = kp.shape[1]
    scale = 1.0 / math.sqrt(d)
    out = np.zeros((b, 1, h, d))
    for i in range(b):
        n = int(lens[i])
        kd = kp[tables[i]].reshape(-1, h, d)[:n].astype(np.float64)
        vd = vp[tables[i]].reshape(-1, h, d)[:n].astype(np.float64)
        if kn is not None:
            kd = np.concatenate([kd, kn[i].astype(np.float64)])
            vd = np.concatenate([vd, vn[i].astype(np.float64)])
        if kd.shape[0] == 0:
            continue
        s = np.einsum("hd,uhd->hu", q[i, 0].astype(np.float64) * scale, kd)
        p = np.exp(s - s.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        out[i, 0] = np.einsum("hu,uhd->hd", p, vd)
    return out


TOL = {np.float32: 2e-5, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_decode_matches_dense_reference(kernel, dtype):
    q, kp, vp, tables, lens, kn, vn = case(dtype=np.float32)
    ref = dense_decode_ref(q, kp, vp, tables, lens, kn, vn)
    cast = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    got = paged_decode_attention(
        cast(q), cast(head_major(kp)), cast(head_major(vp)),
        jnp.asarray(tables), jnp.asarray(lens), k_new=cast(kn), v_new=cast(vn),
        kernel=kernel, interpret=True)
    assert got.shape == q.shape and got.dtype == jnp.dtype(dtype)
    err = np.max(np.abs(np.asarray(got, np.float64) - ref))
    assert err < TOL[dtype], f"{kernel}/{jnp.dtype(dtype)}: err={err}"


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("q_pad", [8, 16])
def test_decode_edge_lens_and_qpad(kernel, q_pad):
    # row 0 empty (pure new-token), row 1 exactly one page, row 2 a
    # partial page, row 3 the full table capacity
    q, kp, vp, tables, lens, kn, vn = case(lens=[0, 8, 3, 48])
    ref = dense_decode_ref(q, kp, vp, tables, lens, kn, vn)
    got = paged_decode_attention(
        jnp.asarray(q), head_major(kp), head_major(vp),
        jnp.asarray(tables), jnp.asarray(lens), k_new=jnp.asarray(kn),
        v_new=jnp.asarray(vn), kernel=kernel, q_pad=q_pad,
        interpret=True)
    err = np.max(np.abs(np.asarray(got, np.float64) - ref))
    assert err < TOL[np.float32]
    # the empty row attends only to its own token -> exactly v_new
    np.testing.assert_allclose(np.asarray(got)[0, 0], vn[0, 0],
                               rtol=1e-5, atol=1e-6)


def test_decode_without_new_token_xla():
    q, kp, vp, tables, lens, _, _ = case(with_new=False,
                                         lens=[5, 0, 16, 30])
    ref = dense_decode_ref(q, kp, vp, tables, lens, None, None)
    got = paged_decode_attention(
        jnp.asarray(q), head_major(kp), head_major(vp),
        jnp.asarray(tables), jnp.asarray(lens), kernel="xla")
    err = np.max(np.abs(np.asarray(got, np.float64) - ref))
    assert err < TOL[np.float32]
    # a fully-masked row (no context, no new token) yields zeros, not NaN
    assert np.all(np.asarray(got)[1] == 0.0)


def test_pallas_gate_and_dispatch():
    q, kp, vp, tables, lens, kn, vn = case(d=32)
    assert paged_decode_supported(jnp.asarray(q), head_major(kp),
                                  interpret=True)
    # unsupported head_dim: explicit pallas raises, auto falls back
    qb, kpb, vpb, tb, lb, knb, vnb = case(d=48, seed=1)
    assert not paged_decode_supported(jnp.asarray(qb), head_major(kpb),
                                      interpret=True)
    with pytest.raises(ValueError):
        paged_decode_attention(
            jnp.asarray(qb), head_major(kpb), head_major(vpb),
            jnp.asarray(tb), jnp.asarray(lb), k_new=jnp.asarray(knb),
            v_new=jnp.asarray(vnb), kernel="pallas", interpret=True)
    got = paged_decode_attention(
        jnp.asarray(qb), head_major(kpb), head_major(vpb),
        jnp.asarray(tb), jnp.asarray(lb), k_new=jnp.asarray(knb),
        v_new=jnp.asarray(vnb), kernel="auto", interpret=True)
    ref = dense_decode_ref(qb, kpb, vpb, tb, lb, knb, vnb)
    assert np.max(np.abs(np.asarray(got, np.float64) - ref)) \
        < TOL[np.float32]


def test_paged_dims_buckets_capacity():
    assert paged_dims(32, 16, 16) == {"d": 32, "ps": 16, "sk": 256}
    assert paged_dims(32, 16, 8) == {"d": 32, "ps": 16, "sk": 128}
    assert paged_dims(64, 8, 100) == {"d": 64, "ps": 8, "sk": 1024}


# -- ragged prefill -----------------------------------------------------------

def dense_prefill_ref(q, k, v, row_id, positions, valid, kp, vp, tables,
                      ctx_lens):
    """float64 reference over the flattened varlen layout: each token
    attends to its row's cached context plus the chunk tokens of the
    same row at <= its position."""
    t, h, d = q.shape
    ps = kp.shape[1]
    scale = 1.0 / math.sqrt(d)
    out = np.zeros((t, h, d))
    for i in range(t):
        if not valid[i]:
            continue
        r = int(row_id[i])
        n = int(ctx_lens[r])
        kd = kp[tables[r]].reshape(-1, h, d)[:n].astype(np.float64)
        vd = vp[tables[r]].reshape(-1, h, d)[:n].astype(np.float64)
        sel = [u for u in range(t)
               if valid[u] and row_id[u] == r
               and positions[u] <= positions[i]]
        kd = np.concatenate([kd, k[sel].astype(np.float64)])
        vd = np.concatenate([vd, v[sel].astype(np.float64)])
        s = np.einsum("hd,uhd->hu", q[i].astype(np.float64) * scale, kd)
        p = np.exp(s - s.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        out[i] = np.einsum("hu,uhd->hd", p, vd)
    return out


def test_prefill_matches_dense_reference():
    rs = np.random.RandomState(2)
    h, d, ps, pool = 2, 32, 8, 10
    # two rows: row 0 has cached context (10 tokens) + 5 chunk tokens,
    # row 1 is cold with 3 chunk tokens; 4 padding slots
    t = 12
    chunks = [(0, 10, 5), (1, 0, 3)]
    row_id = np.zeros(t, np.int32)
    positions = np.zeros(t, np.int32)
    valid = np.zeros(t, np.int32)
    off = 0
    tables = np.zeros((2, 4), np.int32)
    ctx_lens = np.zeros(2, np.int32)
    for r, ctx, n in chunks:
        row_id[off:off + n] = r
        positions[off:off + n] = np.arange(ctx, ctx + n)
        valid[off:off + n] = 1
        tables[r] = rs.permutation(pool)[:4]
        ctx_lens[r] = ctx
        off += n
    q = rs.randn(t, h, d).astype(np.float32)
    k = rs.randn(t, h, d).astype(np.float32)
    v = rs.randn(t, h, d).astype(np.float32)
    kp = rs.randn(pool, ps, h, d).astype(np.float32)
    vp = rs.randn(pool, ps, h, d).astype(np.float32)
    ref = dense_prefill_ref(q, k, v, row_id, positions, valid, kp, vp,
                            tables, ctx_lens)
    got = paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(row_id), jnp.asarray(positions), jnp.asarray(valid),
        head_major(kp), head_major(vp), jnp.asarray(tables),
        jnp.asarray(ctx_lens))
    got = np.asarray(got, np.float64)
    err = np.max(np.abs(got[valid.astype(bool)]
                        - ref[valid.astype(bool)]))
    assert err < TOL[np.float32]


# -- multi-query speculative verify -------------------------------------------

def dense_verify_ref(q, kp, vp, tables, lens, kn, vn):
    """float64 reference for the Tq>1 verify form: chunk slot p of row i
    attends to the row's cached context plus new tokens 0..p (causal
    within the chunk)."""
    b, tq, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    out = np.zeros((b, tq, h, d))
    for i in range(b):
        n = int(lens[i])
        ctx_k = kp[tables[i]].reshape(-1, h, d)[:n].astype(np.float64)
        ctx_v = vp[tables[i]].reshape(-1, h, d)[:n].astype(np.float64)
        for p in range(tq):
            kd = np.concatenate([ctx_k, kn[i, :p + 1].astype(np.float64)])
            vd = np.concatenate([ctx_v, vn[i, :p + 1].astype(np.float64)])
            s = np.einsum("hd,uhd->hu",
                          q[i, p].astype(np.float64) * scale, kd)
            pr = np.exp(s - s.max(axis=1, keepdims=True))
            pr /= pr.sum(axis=1, keepdims=True)
            out[i, p] = np.einsum("hu,uhd->hd", pr, vd)
    return out


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("tq", [2, 5])
def test_verify_multi_query_matches_dense_reference(kernel, tq):
    # rows at the edges: cold (lens=0 — pure causal chunk attention),
    # one full page, partial page, full table capacity
    b, h, d, ps, pool, width = 4, 2, 32, 8, 12, 6
    rs = np.random.RandomState(3)
    q = rs.randn(b, tq, h, d).astype(np.float32)
    kp = rs.randn(pool, ps, h, d).astype(np.float32)
    vp = rs.randn(pool, ps, h, d).astype(np.float32)
    tables = np.stack([rs.permutation(pool)[:width]
                       for _ in range(b)]).astype(np.int32)
    lens = np.asarray([0, 8, 3, 48], np.int32)
    kn = rs.randn(b, tq, h, d).astype(np.float32)
    vn = rs.randn(b, tq, h, d).astype(np.float32)
    ref = dense_verify_ref(q, kp, vp, tables, lens, kn, vn)
    got = paged_decode_attention(
        jnp.asarray(q), head_major(kp), head_major(vp),
        jnp.asarray(tables), jnp.asarray(lens), k_new=jnp.asarray(kn),
        v_new=jnp.asarray(vn), kernel=kernel, interpret=True)
    assert got.shape == q.shape
    err = np.max(np.abs(np.asarray(got, np.float64) - ref))
    assert err < TOL[np.float32], f"{kernel}/tq={tq}: err={err}"
    # the cold row's first slot attends only to its own token -> v_new
    np.testing.assert_allclose(np.asarray(got)[0, 0], vn[0, 0],
                               rtol=1e-5, atol=1e-6)
