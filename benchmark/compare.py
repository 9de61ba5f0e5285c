"""The comparisons that decide ``correct``.

Each returns a dict with ``ok`` and the numbers it compared, so that a
failure says by how much. Tolerances come from the cell's workload file,
where each is written with its reason.
"""
from __future__ import annotations

import math

import numpy as np


def _merge_reference(sub: dict, rest: dict) -> dict:
    """Two partial parameter trees in the reference's structure (as
    ``family.to_reference`` returns them, blocks keyed by index) as the
    one tree ``reference.loss`` takes, blocks as a list."""
    blocks = {}
    for part in (rest, sub):
        for i, b in part.get("blocks", {}).items():
            blocks.setdefault(i, {}).update(b)
    out = {k: v for part in (rest, sub) for k, v in part.items()
           if k != "blocks"}
    out["blocks"] = [blocks[i] for i in sorted(blocks)]
    return out


def _flatten(tree: dict) -> dict:
    out = {k: v for k, v in tree.items() if k != "blocks"}
    for i, b in tree.get("blocks", {}).items():
        out.update({f"blocks.{i}.{k}": v for k, v in b.items()})
    return out


def against_reference(built, reference, params, ids, labels, spec) -> dict:
    """The program's loss and gradients (its own loss path, the
    framework's functional autograd under ``jax.jit``) against the plain
    float32 reference given the same weights, on the same rows.

    Gradients are compared leaf by leaf as ``|g - g_ref| / |g_ref|`` in
    the 2-norm: one number per leaf that a wrong term, a dropped term or
    a lower precision moves, and that does not drown a small leaf in a
    large one. ``spec["grad_leaves"]`` says which leaves: ``"all"``, or
    ``"ends"`` where float32 gradients of everything do not fit."""
    import jax
    import jax.numpy as jnp

    cfg = built.config
    names = built.leaf_names(spec["grad_leaves"])
    loss_p, grads_p = built.loss_and_grads(params, names, ids, labels)

    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda v: v.astype(jnp.float32), t)
    sub = f32(built.to_reference({k: params[k] for k in names}))
    rest = built.to_reference({k: v for k, v in params.items()
                               if k not in set(names)})

    def ref_loss(sub_, rest_, ids_, labels_):
        return reference.loss(
            _merge_reference(sub_, f32(rest_)), ids_, labels_,
            n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"],
            remat=bool(spec.get("reference_remat")))

    loss_r, grads_r = jax.jit(jax.value_and_grad(ref_loss))(
        sub, rest, ids, labels)

    got = _flatten(f32(built.to_reference(grads_p)))
    want = _flatten(grads_r)

    @jax.jit
    def errors(a, b):
        return {k: (jnp.linalg.norm((a[k] - b[k]).ravel()),
                    jnp.linalg.norm(b[k].ravel())) for k in b}

    errs = {k: (float(d), float(n)) for k, (d, n) in
            errors(got, want).items()}
    rel = {k: d / n for k, (d, n) in errs.items() if n > 0}
    worst = max(rel, key=rel.get)
    median = float(np.median(list(rel.values())))
    loss_p, loss_r = float(loss_p), float(loss_r)
    loss_rel = abs(loss_p - loss_r) / abs(loss_r)
    ok = (math.isfinite(loss_p) and loss_rel <= spec["loss_rtol"]
          and all(math.isfinite(v) for v in rel.values())
          and rel[worst] <= spec["grad_rel_l2"]
          and median <= spec["grad_median_rel_l2"]
          and len(rel) == len(want))
    return {"ok": ok, "loss_program": loss_p, "loss_reference": loss_r,
            "loss_rel_diff": loss_rel, "loss_rtol": spec["loss_rtol"],
            "grad_leaves": len(rel), "grad_worst_leaf": worst,
            "grad_worst_rel_l2": rel[worst],
            "grad_median_rel_l2": median,
            "grad_rel_l2_limits": [spec["grad_rel_l2"],
                                   spec["grad_median_rel_l2"]]}


def losses_fall(losses, group: int, margin: float) -> dict:
    """Every loss finite, and the mean of the last ``group`` losses below
    the mean of the first ``group`` by ``margin``."""
    finite = all(math.isfinite(x) for x in losses)
    first = float(np.mean(losses[:group]))
    last = float(np.mean(losses[-group:]))
    return {"ok": finite and len(losses) >= 2 * group
            and last < first - margin, "finite": finite,
            "first_group_mean": first, "last_group_mean": last,
            "margin": margin, "steps": len(losses)}


def one_chip_forward(built, seed, ids, labels, rows_per_chunk, first_loss,
                     rtol) -> dict:
    """The first global loss of a run across chips against the program's
    forward on one chip over the same rows, in chunks, from the same
    initial parameters (made again from the seed)."""
    params = built.initial_params(seed)
    chunks = [float(built.forward_loss(params, ids[i:i + rows_per_chunk],
                                       labels[i:i + rows_per_chunk]))
              for i in range(0, ids.shape[0], rows_per_chunk)]
    want = float(np.mean(chunks))
    diff = abs(first_loss - want) / abs(want)
    return {"ok": diff <= rtol, "first_global_loss": first_loss,
            "one_chip_loss": want, "rel_diff": diff, "rtol": rtol,
            "chunks": len(chunks)}


def replicas_equal(array, n_devices: int) -> dict:
    """A parameter leaf that the layout replicates holds the same bits on
    every device: one checksum per device, all equal."""
    sums = {}
    for shard in array.addressable_shards:
        raw = np.asarray(shard.data)
        bits = raw.view(np.uint16 if raw.dtype.itemsize == 2 else np.uint32)
        sums[shard.device.id] = int(bits.astype(np.uint64).sum())
    return {"ok": len(sums) == n_devices and len(set(sums.values())) == 1,
            "devices": len(sums), "checksums": sorted(set(sums.values()))}
