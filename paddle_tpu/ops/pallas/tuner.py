"""Search-based Pallas kernel autotuner with a persistent tuning DB.

The flash-attention kernel shipped with hand-swept block constants
(``DEFAULT_BLOCK_Q/K``) frozen for one chip and one shape; CUDA-L2
(arXiv:2512.02551) shows grid search over the tile/config space beats
hand-tuned constants per (shape, dtype, arch).  This module is that
search for the repo's Pallas tier:

- **Keys** — ``kernel|device_kind|dtype|dim=..,dim=..`` with sequence /
  token dims bucketed to the next power of two (the step-cache idea:
  one entry serves every shape that lands in the bucket, so a DB tuned
  at s=1024 also covers s=900 after the wrapper pads).
- **DB** — a JSON file shipped in-repo
  (``paddle_tpu/ops/pallas/tuning_db.json``, interpret-validated seeds)
  plus a user-writable overlay (``PADDLE_TPU_TUNING_DB`` or
  ``~/.cache/paddle_tpu/tuning_db.json``).  Overlay entries win per key;
  a corrupt file is treated as empty (warn once), never a crash.
- **Resolution** — kernels call :func:`resolve` at trace time: DB hit →
  tuned blocks, miss → the kernel's compiled-in defaults, unsupported
  shape → the caller's XLA fallback (counted via
  :func:`record_fallback`).  Every outcome increments
  ``pallas_config_resolved_total{kernel, source=db|default|fallback}``.
- **Tuning** — :func:`tune` grid-searches candidate configs per (kernel,
  shape bucket, dtype, device kind).  Each candidate is validated
  against the XLA reference for numerics BEFORE it may be timed; on CPU
  (no TPU) the sweep runs the kernels in interpret mode and is
  correctness-only — winners are the validated defaults with a null
  timing, so the first real-TPU run only has to refresh timings, not
  re-establish correctness.

CLI (writes the overlay by default)::

    python -m paddle_tpu.ops.pallas.tuner --suite quick        # CPU ok
    python -m paddle_tpu.ops.pallas.tuner --suite bench --db ops/pallas/tuning_db.json --generic
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "TuningDB", "default_db_path", "overlay_db_path", "get_db",
    "clear_cache", "shape_bucket", "device_kind", "make_key", "resolve",
    "record_fallback", "entry_origin", "tune", "flash_candidates",
    "ce_candidates",
    "paged_candidates", "entry_for_traced_call", "GENERIC_DEVICE",
]

GENERIC_DEVICE = "any"  # device-agnostic seed entries (interpret-validated)

_VERSION = 1


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def shape_bucket(n: int, floor: int = 128) -> int:
    """Next power of two >= n (min ``floor``): the shape-bucket axis of
    the DB key. Wrappers pad ragged shapes anyway, so one tuned entry
    serves the whole bucket."""
    n = max(int(n), 1)
    b = floor
    while b < n:
        b <<= 1
    return b


def device_kind() -> str:
    """Normalized accelerator name for DB keys ("cpu", "tpu-v5e", ...)."""
    try:
        import jax
        kind = jax.devices()[0].device_kind
    except Exception:
        return "cpu"
    return str(kind).strip().lower().replace(" ", "-")


def _dtype_name(dtype) -> str:
    try:
        import jax.numpy as jnp
        return jnp.dtype(dtype).name
    except Exception:
        return str(dtype)


def make_key(kernel: str, device: str, dtype, dims: Dict[str, int]) -> str:
    dt = _dtype_name(dtype)
    dim_s = ",".join(f"{k}{int(v)}" for k, v in sorted(dims.items()))
    return f"{kernel}|{device}|{dt}|{dim_s}"


def flash_dims(d: int, sq: int, sk: int) -> Dict[str, int]:
    """Bucketed dims for a flash-attention call: head_dim exact (it is a
    hardware tile), sequence lengths bucketed. ``sq`` buckets with
    floor=1 so DECODE-shaped calls (sq = 1..8) keep exact small keys
    instead of collapsing into — and colliding with — the 128 prefill
    bucket; keys for sq >= 128 are unchanged."""
    return {"d": int(d), "sq": shape_bucket(sq, floor=1),
            "sk": shape_bucket(sk)}


def ce_dims(h: int, v: int, tokens: int) -> Dict[str, int]:
    """Bucketed dims for a fused-CE call: hidden and vocab exact (vocab
    is a model constant, not a batch axis), token count bucketed."""
    return {"h": int(h), "v": int(v), "t": shape_bucket(tokens)}


# ---------------------------------------------------------------------------
# DB
# ---------------------------------------------------------------------------

def default_db_path() -> str:
    """The in-repo seed DB shipped next to the kernels."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tuning_db.json")


def overlay_db_path() -> str:
    """User-writable overlay: ``PADDLE_TPU_TUNING_DB`` or a cache-dir
    default. Tuner runs write here so the shipped seed stays pristine."""
    env = os.environ.get("PADDLE_TPU_TUNING_DB")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                        "tuning_db.json")


class TuningDB:
    """A {key: entry} map with JSON round-trip. An entry is::

        {"config": {"block_q": 256, "block_k": 512},
         "kernel": "flash_attention", "device": "tpu-v5e",
         "dtype": "bfloat16", "dims": {"d": 64, "sq": 1024, "sk": 1024},
         "mean_us": 123.4 | None,        # None = correctness-only sweep
         "validated": "interpret" | "device" | "seed",
         "swept": 6}                     # candidates that passed numerics

    ``validated: "seed"`` marks a shipped config-only entry for a shape
    too large to interpret-validate on CPU (the TPU bench buckets): the
    blocks are legal for the shape but unmeasured — a device tuner run
    (``--suite bench``) refreshes them in place. ``"device"`` with a null
    ``mean_us`` is a config compared with its reference on the chip but
    never timed (``chip_smoke.py``'s ``kernels`` phase).
    """

    def __init__(self, entries: Optional[Dict[str, dict]] = None,
                 path: Optional[str] = None):
        self.entries: Dict[str, dict] = dict(entries or {})
        self.path = path

    # -- io -----------------------------------------------------------------
    @classmethod
    def load(cls, path: str) -> "TuningDB":
        """Load a DB file; missing or corrupt files yield an EMPTY db
        (warn once on corruption) — a broken overlay must never take
        down trace time."""
        if not os.path.exists(path):
            return cls(path=path)
        try:
            with open(path) as f:
                raw = json.load(f)
            if not isinstance(raw, dict) or \
                    not isinstance(raw.get("entries", {}), dict):
                raise ValueError("not a tuning DB object")
            return cls(raw.get("entries", {}), path=path)
        except (OSError, ValueError) as e:
            warnings.warn(f"tuning DB {path!r} unreadable ({e}); "
                          "treating as empty", stacklevel=2)
            return cls(path=path)

    def save(self, path: Optional[str] = None):
        path = path or self.path
        if not path:
            raise ValueError("TuningDB.save: no path")
        os.makedirs(os.path.dirname(os.path.abspath(path)) or ".",
                    exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": _VERSION, "entries": self.entries}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, path)

    # -- access -------------------------------------------------------------
    def lookup(self, key: str) -> Optional[dict]:
        return self.entries.get(key)

    def put(self, key: str, entry: dict):
        self.entries[key] = entry

    def merged_over(self, base: "TuningDB") -> "TuningDB":
        """self (overlay) wins per key over ``base``."""
        merged = dict(base.entries)
        merged.update(self.entries)
        return TuningDB(merged)

    def __len__(self):
        return len(self.entries)


_db_cache: Dict[str, Any] = {}


def get_db(refresh: bool = False) -> TuningDB:
    """The merged (seed + overlay) DB, cached per (seed, overlay) paths."""
    key = (default_db_path(), overlay_db_path())
    if refresh or _db_cache.get("key") != key:
        base = TuningDB.load(key[0])
        overlay = TuningDB.load(key[1])
        _db_cache["key"] = key
        _db_cache["db"] = overlay.merged_over(base)
    return _db_cache["db"]


def clear_cache():
    """Drop the cached merged DB (tests / after a tuner run)."""
    _db_cache.clear()


# ---------------------------------------------------------------------------
# trace-time resolution
# ---------------------------------------------------------------------------

def _count(kernel: str, source: str):
    from ... import telemetry
    if telemetry.enabled():
        telemetry.counter(
            "pallas_config_resolved_total",
            "Pallas kernel config resolutions, by source"
        ).inc(kernel=kernel, source=source)


def resolve(kernel: str, dtype, dims: Dict[str, int],
            defaults: Dict[str, int]) -> Tuple[Dict[str, int], str]:
    """Trace-time config lookup: (config, source).

    Tries the exact device kind, then the :data:`GENERIC_DEVICE` seed
    entries. Hit → the tuned config (source "db"); miss → ``defaults``
    (source "default"). Either way the outcome is counted in
    ``pallas_config_resolved_total{kernel, source}``.
    """
    _, entry = _find(kernel, dtype, dims)
    if entry is not None:
        _count(kernel, "db")
        cfg = dict(defaults)
        cfg.update({k: int(v) for k, v in entry["config"].items()})
        return cfg, "db"
    _count(kernel, "default")
    return dict(defaults), "default"


def _find(kernel: str, dtype, dims: Dict[str, int]):
    """(key, entry) of the merged-DB row serving this call — the exact
    device kind first, then :data:`GENERIC_DEVICE` — or (None, None)."""
    db = get_db()
    for dev in (device_kind(), GENERIC_DEVICE):
        key = make_key(kernel, dev, dtype, dims)
        entry = db.lookup(key)
        if entry and isinstance(entry.get("config"), dict):
            return key, entry
    return None, None


def entry_origin(kernel: str, dtype, dims: Dict[str, int]) -> \
        Tuple[Optional[str], Optional[str]]:
    """(key, DB file) of the entry :func:`resolve` picks for this call,
    (None, None) when it takes the kernel's compiled-in defaults. Lets a
    run say whether a config came from the checkout's seed DB or from an
    overlay outside it."""
    key, _ = _find(kernel, dtype, dims)
    if key is None:
        return None, None
    # overlay entries win per key, so the overlay holding the key owns it
    overlay = overlay_db_path()
    return key, (overlay if TuningDB.load(overlay).lookup(key)
                 else default_db_path())


def record_fallback(kernel: str):
    """Count an XLA-fallback resolution (unsupported shape / backend):
    the third ``source`` label of ``pallas_config_resolved_total``."""
    _count(kernel, "fallback")


def entry_for_traced_call(kernel_name: str, eqn) -> \
        Tuple[Optional[str], Optional[dict]]:
    """Map a traced ``pallas_call`` equation back to its DB entry — the
    analysis rule's hook (``pallas-config-untuned``).

    ``kernel_name`` is the pallas_call's ``name=`` (the flash kernels)
    or its kernel function's name; ``eqn`` the equation.  Returns
    ``(key, entry_or_None)``; ``(None, None)`` when the kernel is not
    one the tuner knows.  For fused CE the vocab seen in the jaxpr is
    the block-padded one, so the match accepts any DB entry whose true
    vocab pads to the traced width.
    """
    from .flash_attention import KERNEL_NAMES as flash_kernels
    from .flash_attention import dims_of_call
    db = get_db()
    avals = [getattr(v, "aval", None) for v in eqn.invars]
    if kernel_name in flash_kernels:
        dims = flash_dims(*dims_of_call(eqn))
        # q: the first operand after lens, seed and any table of tiles
        dtype = next(a.dtype for a in avals if len(a.shape) == 3)
        for dev in (device_kind(), GENERIC_DEVICE):
            key = make_key("flash_attention", dev, dtype, dims)
            entry = db.lookup(key)
            if entry:
                return key, entry
        return make_key("flash_attention", device_kind(), dtype, dims), None
    if kernel_name in ("_ce_fwd_kernel", "_ce_bwd_dh_kernel",
                       "_ce_bwd_dw_kernel"):
        # fused CE: hid (N, H) and w (H, Vpad) are the two matrix invars,
        # identified by the shape relation hid.shape[1] == w.shape[0]
        mats = [a for a in avals if len(getattr(a, "shape", ())) == 2]
        hid = w = None
        for a in mats:
            for b in mats:
                if a is not b and a.shape[1] == b.shape[0]:
                    hid, w = a, b
                    break
            if hid is not None:
                break
        if hid is None:
            return None, None
        n, h = hid.shape
        vpad = w.shape[1]
        tb = shape_bucket(n)
        for dev in (device_kind(), GENERIC_DEVICE):
            prefix = f"fused_ce|{dev}|{_dtype_name(hid.dtype)}|"
            for key, entry in db.entries.items():
                if not key.startswith(prefix):
                    continue
                d = entry.get("dims", {})
                bv = entry.get("config", {}).get("block_vocab", 0)
                if d.get("h") == h and d.get("t") == tb and bv and \
                        d.get("v", 0) <= vpad and \
                        -(-d.get("v", 1) // bv) * bv == vpad:
                    return key, entry
        return make_key(
            "fused_ce", device_kind(), hid.dtype,
            {"h": int(h), "v": int(vpad), "t": tb}), None
    if kernel_name in ("_paged_decode_kernel", "_paged_verify_kernel"):
        # paged decode/verify attention: invars (tables, lens, q, k_pool,
        # v_pool) with q (B, H, rows, D) and k_pool (P, H, page_size, D).
        # For the verify kernel the traced row count IS the bucketed
        # speculative chunk width, so it keys the ``sq`` dim directly.
        if len(avals) < 4:
            return None, None
        tables, q, kpool = avals[0], avals[2], avals[3]
        from .paged_attention import paged_dims
        tq = q.shape[2] if kernel_name == "_paged_verify_kernel" else 1
        dims = paged_dims(q.shape[-1], kpool.shape[2], tables.shape[1],
                          tq=tq)
        for dev in (device_kind(), GENERIC_DEVICE):
            key = make_key("paged_attention", dev, q.dtype, dims)
            entry = db.lookup(key)
            if entry:
                return key, entry
        return make_key("paged_attention", device_kind(), q.dtype,
                        dims), None
    return None, None


# ---------------------------------------------------------------------------
# candidate grids
# ---------------------------------------------------------------------------

def flash_candidates(sq: int, sk: int) -> List[Dict[str, int]]:
    """(block_q, block_k) grid: lane-aligned powers of two that divide
    the bucketed sequence lengths (the wrapper's clamp would mangle
    anything else)."""
    out = []
    for bq in (128, 256, 512, 1024):
        if bq > sq or sq % bq:
            continue
        for bk in (128, 256, 512, 1024):
            if bk > sk or sk % bk:
                continue
            out.append({"block_q": bq, "block_k": bk})
    return out or [{"block_q": min(sq, 128), "block_k": min(sk, 128)}]


def ce_candidates(tokens: int, vocab: int, hidden: int,
                  dtype) -> List[Dict[str, int]]:
    """(block_tokens, block_vocab) grid for the fused CE kernel, less
    the blocks the kernel's VMEM bound would shrink (a candidate must
    run as the config it is recorded under)."""
    from .fused_ce import VMEM_BUDGET, vmem_bytes
    out = []
    for bt in (128, 256, 512):
        if bt > tokens or tokens % bt:
            continue
        for bv in (512, 1024, 2048, 4096):
            if bv > max(vocab, 512) or \
                    vmem_bytes(hidden, bt, bv, dtype) > VMEM_BUDGET:
                continue
            out.append({"block_tokens": bt, "block_vocab": bv})
    return out or [{"block_tokens": min(tokens, 128),
                    "block_vocab": min(shape_bucket(vocab), 512)}]


def paged_candidates(sq: Optional[int] = None) -> List[Dict[str, int]]:
    """q_pad grid for the paged decode kernel: the sublane rows the
    single query is broadcast to — 8 matches the f32 tile, 16 the bf16
    tile shape. For the speculative-verify path (``sq`` set) the rows
    ARE the bucketed chunk width, so there is exactly one candidate."""
    if sq is not None:
        return [{"q_pad": int(sq)}]
    return [{"q_pad": 8}, {"q_pad": 16}]


# ---------------------------------------------------------------------------
# validation + timing
# ---------------------------------------------------------------------------

def _time_op(fn, args, iters: int = 20, warmup: int = 3) -> float:
    """Mean seconds a call of the jitted ``fn`` in steady state; the
    host fetch of the first output leaf ends each region."""
    import jax
    import numpy as np
    jfn = jax.jit(fn)
    out = None
    for _ in range(warmup):
        out = jfn(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfn(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    return (time.perf_counter() - t0) / iters


def _validate_flash(cfg, b, h, d, sq, sk, dtype, interpret,
                    loss_tol=1e-3, grad_tol=2e-2) -> bool:
    """Candidate gate: fwd output AND input grads must match the XLA
    attention reference before the candidate may be timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ...nn.functional.attention import _xla_attention
    from .flash_attention import flash_attention

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, sq, h, d), dtype)
    k = jnp.asarray(rs.randn(b, sk, h, d), dtype)
    v = jnp.asarray(rs.randn(b, sk, h, d), dtype)

    def f_fl(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=cfg["block_q"],
            block_k=cfg["block_k"], interpret=interpret) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    try:
        lf, gf = jax.value_and_grad(f_fl, argnums=(0, 1, 2))(q, k, v)
        lr, gr = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    except Exception:
        return False
    tol = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else loss_tol
    if not np.allclose(float(lf), float(lr),
                       rtol=tol, atol=tol * max(1.0, abs(float(lr)))):
        return False
    gtol = 1e-1 if jnp.dtype(dtype) == jnp.bfloat16 else grad_tol
    for a, b_ in zip(gf, gr):
        err = np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b_, np.float32)))
        scale = max(1.0, float(np.max(np.abs(np.asarray(b_, np.float32)))))
        if err / scale > gtol:
            return False
    return True


def _validate_ce(cfg, tokens, h, v, dtype, interpret,
                 loss_tol=1e-3) -> bool:
    """Candidate gate: loss AND grads vs the chunked_lm_ce oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..chunked_ce import chunked_lm_ce
    from .fused_ce import fused_lm_ce

    rs = np.random.RandomState(0)
    hid = jnp.asarray(rs.randn(tokens, h) * 0.1, dtype)
    w = jnp.asarray(rs.randn(h, v) * 0.1, dtype)
    lbl = jnp.asarray(rs.randint(0, v, (tokens,)), jnp.int32)

    def f_fu(hid, w):
        return fused_lm_ce(hid, w, lbl,
                           block_tokens=cfg["block_tokens"],
                           block_vocab=cfg["block_vocab"],
                           interpret=interpret)

    def f_ref(hid, w):
        return chunked_lm_ce(hid, w, lbl, min(4096, shape_bucket(v)))

    try:
        lf, gf = jax.value_and_grad(f_fu, argnums=(0, 1))(hid, w)
        lr, gr = jax.value_and_grad(f_ref, argnums=(0, 1))(hid, w)
    except Exception:
        return False
    tol = 1e-2 if jnp.dtype(dtype) == jnp.bfloat16 else loss_tol
    if abs(float(lf) - float(lr)) > tol * max(1.0, abs(float(lr))):
        return False
    gtol = 5e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-2
    for a, b_ in zip(gf, gr):
        err = np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b_, np.float32)))
        scale = max(1.0, float(np.max(np.abs(np.asarray(b_, np.float32)))))
        if err / scale > gtol:
            return False
    return True


def _time_flash(cfg, b, h, d, sq, sk, dtype, interpret, iters) -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .flash_attention import flash_attention

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, sq, h, d), dtype)
    k = jnp.asarray(rs.randn(b, sk, h, d), dtype)
    v = jnp.asarray(rs.randn(b, sk, h, d), dtype)

    def step(q, k, v):
        def f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=cfg["block_q"],
                block_k=cfg["block_k"], interpret=interpret) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    return _time_op(step, (q, k, v), iters=iters)


def _time_ce(cfg, tokens, h, v, dtype, interpret, iters) -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .fused_ce import fused_lm_ce

    rs = np.random.RandomState(0)
    hid = jnp.asarray(rs.randn(tokens, h) * 0.1, dtype)
    w = jnp.asarray(rs.randn(h, v) * 0.1, dtype)
    lbl = jnp.asarray(rs.randint(0, v, (tokens,)), jnp.int32)

    def step(hid, w):
        return jax.grad(
            lambda hid, w: fused_lm_ce(
                hid, w, lbl, block_tokens=cfg["block_tokens"],
                block_vocab=cfg["block_vocab"], interpret=interpret),
            argnums=(0, 1))(hid, w)

    return _time_op(step, (hid, w), iters=iters)


def _paged_case_arrays(b, h, d, ps, pages, dtype, tq=1):
    import jax.numpy as jnp
    import numpy as np

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, tq, h, d), dtype)
    kp = jnp.asarray(rs.randn(pages, h, ps, d), dtype)
    vp = jnp.asarray(rs.randn(pages, h, ps, d), dtype)
    # shuffled tables + ragged lens exercise the gather and masking
    tables = jnp.asarray(
        np.stack([rs.permutation(pages) for _ in range(b)]), jnp.int32)
    lens = jnp.asarray(rs.randint(0, ps * pages + 1, (b,)), jnp.int32)
    kn = jnp.asarray(rs.randn(b, tq, h, d), dtype)
    vn = jnp.asarray(rs.randn(b, tq, h, d), dtype)
    return q, kp, vp, tables, lens, kn, vn


def _validate_paged(cfg, b, h, d, ps, pages, dtype, interpret,
                    tol=2e-3, tq=1) -> bool:
    """Candidate gate: the Pallas paged decode/verify output must match
    the XLA gather baseline (the mandatory reference path) for the same
    pool."""
    import jax.numpy as jnp
    import numpy as np

    from .paged_attention import paged_decode_attention

    q, kp, vp, tables, lens, kn, vn = _paged_case_arrays(
        b, h, d, ps, pages, dtype, tq=tq)
    try:
        got = paged_decode_attention(q, kp, vp, tables, lens, k_new=kn,
                                     v_new=vn, kernel="pallas",
                                     q_pad=cfg["q_pad"],
                                     interpret=interpret)
        ref = paged_decode_attention(q, kp, vp, tables, lens, k_new=kn,
                                     v_new=vn, kernel="xla")
    except Exception:
        return False
    t = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else tol
    err = np.max(np.abs(np.asarray(got, np.float32)
                        - np.asarray(ref, np.float32)))
    return err / max(1.0, float(np.max(np.abs(np.asarray(
        ref, np.float32))))) <= t


def _time_paged(cfg, b, h, d, ps, pages, dtype, interpret, iters,
                tq=1) -> float:
    from .paged_attention import paged_decode_attention

    q, kp, vp, tables, lens, kn, vn = _paged_case_arrays(
        b, h, d, ps, pages, dtype, tq=tq)

    def step(q, kp, vp):
        return paged_decode_attention(q, kp, vp, tables, lens, k_new=kn,
                                      v_new=vn, kernel="pallas",
                                      q_pad=cfg["q_pad"],
                                      interpret=interpret)

    return _time_op(step, (q, kp, vp), iters=iters)


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def _flash_defaults():
    from .flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q
    return {"block_q": DEFAULT_BLOCK_Q, "block_k": DEFAULT_BLOCK_K}


def _ce_defaults():
    from .fused_ce import DEFAULT_BLOCK_TOKENS, DEFAULT_BLOCK_VOCAB
    return {"block_tokens": DEFAULT_BLOCK_TOKENS,
            "block_vocab": DEFAULT_BLOCK_VOCAB}


def tune_case(kernel: str, case: Dict[str, int], dtype,
              iters: int = 10, device: Optional[str] = None,
              log: Callable[[str], None] = lambda s: None) -> \
        Tuple[str, Optional[dict]]:
    """Sweep ONE (kernel, shape case, dtype): validate every candidate,
    time the survivors when a real accelerator is present, and return
    (key, winning entry | None when nothing validates)."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    dev = device or device_kind()
    if kernel == "flash_attention":
        b, h = case.get("b", 1), case.get("h", 2)
        d, sq, sk = case["d"], shape_bucket(case["sq"], floor=1), \
            shape_bucket(case["sk"])
        dims = flash_dims(d, sq, sk)
        cands = flash_candidates(sq, sk)
        validate = lambda c: _validate_flash(c, b, h, d, sq, sk, dtype,  # noqa: E731
                                             interpret)
        timeit = lambda c: _time_flash(c, b, h, d, sq, sk, dtype,  # noqa: E731
                                       interpret, iters)
        defaults = _flash_defaults()
    elif kernel == "fused_ce":
        hdim, v = case["h"], case["v"]
        tokens = shape_bucket(case["t"], floor=128)
        dims = ce_dims(hdim, v, tokens)
        cands = ce_candidates(tokens, v, hdim, dtype)
        validate = lambda c: _validate_ce(c, tokens, hdim, v, dtype,  # noqa: E731
                                          interpret)
        timeit = lambda c: _time_ce(c, tokens, hdim, v, dtype,  # noqa: E731
                                    interpret, iters)
        defaults = _ce_defaults()
    elif kernel == "paged_attention":
        from .paged_attention import (DEFAULT_Q_PAD, paged_dims,
                                      verify_rows)
        b, h = case.get("b", 4), case.get("h", 2)
        d, ps, pages = case["d"], case["ps"], case["pages"]
        tq = case.get("tq", 1)          # 1 + K for speculative verify
        dims = paged_dims(d, ps, pages, tq=tq)
        cands = paged_candidates(verify_rows(tq) if tq > 1 else None)
        validate = lambda c: _validate_paged(c, b, h, d, ps, pages,  # noqa: E731
                                             dtype, interpret, tq=tq)
        timeit = lambda c: _time_paged(c, b, h, d, ps, pages, dtype,  # noqa: E731
                                       interpret, iters, tq=tq)
        defaults = ({"q_pad": DEFAULT_Q_PAD} if tq == 1
                    else {"q_pad": verify_rows(tq)})
    else:
        raise ValueError(f"unknown kernel {kernel!r}")

    key = make_key(kernel, dev, dtype, dims)
    valid: List[Dict[str, int]] = []
    for cfg in cands:
        ok = validate(cfg)
        log(f"  {kernel} {dims} {cfg}: "
            f"{'ok' if ok else 'FAILED numerics'}")
        if ok:
            valid.append(cfg)
    if not valid:
        return key, None

    if on_tpu:
        timed = [(timeit(cfg), cfg) for cfg in valid]
        timed.sort(key=lambda t: t[0])
        best_us, best = timed[0][0] * 1e6, timed[0][1]
        validated = "device"
    else:
        # correctness-only (interpret): keep the compiled-in default when
        # it validated, else the first survivor; timing stays null so a
        # real-TPU refresh knows it still owes a measurement
        best = next((c for c in valid
                     if all(c.get(k) == v for k, v in defaults.items())),
                    valid[0])
        best_us, validated = None, "interpret"
    return key, {
        "config": best, "kernel": kernel, "device": dev,
        "dtype": _dtype_name(dtype), "dims": dims,
        "mean_us": round(best_us, 2) if best_us is not None else None,
        "validated": validated, "swept": len(valid),
    }


def tune(cases: List[Tuple[str, Dict[str, int], Any]],
         db_path: Optional[str] = None, iters: int = 10,
         device: Optional[str] = None, verbose: bool = False) -> TuningDB:
    """Run the sweep over ``cases`` (list of (kernel, case, dtype)) and
    persist winners into ``db_path`` (default: the user overlay),
    merging with whatever that file already holds."""
    path = db_path or overlay_db_path()
    db = TuningDB.load(path)
    log = (lambda s: print(s, flush=True)) if verbose else (lambda s: None)
    for kernel, case, dtype in cases:
        t0 = time.perf_counter()
        key, entry = tune_case(kernel, case, dtype, iters=iters,
                               device=device, log=log)
        if entry is None:
            log(f"{key}: no candidate passed numerics — not recorded")
            continue
        if entry["mean_us"] is not None:
            # predicted-vs-measured (telemetry.calibration): the DB's
            # prior timing for this key is the "prediction" a fresh
            # device sweep just re-measured — drift here means the
            # stored entry went stale (driver bump, thermals, new part)
            prior = get_db().lookup(key)
            prior_us = prior.get("mean_us") if prior else None
            if prior_us:
                from paddle_tpu.telemetry import calibration
                calibration.record(f"tuner:{kernel}", prior_us * 1e-6,
                                   entry["mean_us"] * 1e-6)
        db.put(key, entry)
        log(f"{key} -> {entry['config']} "
            f"({entry['mean_us']} us, {entry['swept']} valid, "
            f"{time.perf_counter() - t0:.1f}s)")
    db.save(path)
    clear_cache()
    return db


# ---------------------------------------------------------------------------
# suites + CLI
# ---------------------------------------------------------------------------

def _suite(name: str) -> List[Tuple[str, Dict[str, int], Any]]:
    import jax.numpy as jnp
    f32, bf16 = jnp.float32, jnp.bfloat16
    if name == "smoke":       # seconds on CPU — CI plumbing check
        return [
            ("flash_attention", {"b": 1, "h": 1, "d": 64, "sq": 128,
                                 "sk": 128}, f32),
            ("fused_ce", {"h": 64, "v": 512, "t": 128}, f32),
        ]
    if name == "quick":       # the CPU-bench GPT shapes
        return [
            ("flash_attention", {"b": 1, "h": 2, "d": 64, "sq": 256,
                                 "sk": 256}, f32),
            ("flash_attention", {"b": 1, "h": 2, "d": 64, "sq": 512,
                                 "sk": 512}, f32),
            ("fused_ce", {"h": 128, "v": 1024, "t": 512}, f32),
            ("fused_ce", {"h": 64, "v": 512, "t": 128}, f32),
        ]
    if name == "decode":      # bench_serving decode-shape buckets
        return [
            ("paged_attention", {"b": 4, "h": 2, "d": 32, "ps": 16,
                                 "pages": 16}, f32),
            ("paged_attention", {"b": 4, "h": 2, "d": 32, "ps": 16,
                                 "pages": 8}, f32),
            ("paged_attention", {"b": 4, "h": 2, "d": 64, "ps": 16,
                                 "pages": 16}, bf16),
            # speculative-verify chunks (tq = 1 + K for K in {4, 8})
            ("paged_attention", {"b": 4, "h": 2, "d": 32, "ps": 16,
                                 "pages": 16, "tq": 5}, f32),
            ("paged_attention", {"b": 4, "h": 2, "d": 32, "ps": 16,
                                 "pages": 8, "tq": 5}, f32),
            ("paged_attention", {"b": 4, "h": 2, "d": 32, "ps": 16,
                                 "pages": 16, "tq": 9}, f32),
            ("paged_attention", {"b": 4, "h": 2, "d": 32, "ps": 16,
                                 "pages": 8, "tq": 9}, f32),
            ("paged_attention", {"b": 4, "h": 2, "d": 64, "ps": 16,
                                 "pages": 16, "tq": 5}, bf16),
            ("paged_attention", {"b": 4, "h": 2, "d": 64, "ps": 16,
                                 "pages": 16, "tq": 9}, bf16),
        ]
    if name == "bench":       # the TPU bench GPT-base shapes
        return [
            ("flash_attention", {"b": 2, "h": 4, "d": 64, "sq": 1024,
                                 "sk": 1024}, bf16),
            ("flash_attention", {"b": 1, "h": 2, "d": 64, "sq": 2048,
                                 "sk": 2048}, bf16),
            ("fused_ce", {"h": 768, "v": 50304, "t": 8192}, bf16),
        ]
    raise SystemExit(f"unknown suite {name!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--suite", default="quick",
                    choices=("smoke", "quick", "decode", "bench"),
                    help="shape-case set to sweep")
    ap.add_argument("--db", default=None,
                    help="DB file to update (default: the user overlay "
                         "path)")
    ap.add_argument("--iters", type=int, default=10,
                    help="timing iterations per candidate (TPU only)")
    ap.add_argument("--generic", action="store_true",
                    help=f"record under device {GENERIC_DEVICE!r} — used "
                         "to build the shipped interpret-validated seed "
                         "DB")
    args = ap.parse_args(argv)
    db = tune(_suite(args.suite), db_path=args.db, iters=args.iters,
              device=GENERIC_DEVICE if args.generic else None,
              verbose=True)
    print(json.dumps({"tuning_db": db.path, "entries": len(db)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
