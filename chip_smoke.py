"""Chip smoke: the main path, once, on a TPU — the quickest proof that
the system still starts on the chip.

    python chip_smoke.py                       # on a TPU v5e: exit 0 or not
    python chip_smoke.py --phases multichip    # on the four-chip host
    python chip_smoke.py --rehearse-cpu        # tiny sizes, never a pass

One process, no children: a chip belongs to one process at a time. It
fails at once when jax's first device is not a TPU (with JAX_PLATFORMS
unset jax drops to the CPU without a word when libtpu finds no chip).
Every line of output is one JSON object naming the device it ran on;
the last line is ``{"ok": true, "device": {...}}`` and only a run on a
TPU in which every requested phase passed prints it and exits 0.

Phases (each through the entry points a user calls, weights from a seed):

- ``train``  — GPT-base at full width (vocab 50,304, seq 1,024, 12 x 768
  x 12 heads, bf16, batch 8): ``GPTForPretraining`` + ``AdamW`` +
  ``ParallelTrainer.train_step`` on one fixed batch. Loss starts near
  ln(vocab), stays finite and falls; nothing compiles after step 2; the
  staged step holds the flash-attention Pallas calls, none fell back, and
  the backward kernels cut their one causal tile into sub-blocks
  (``flash_tiles_staged_total``).
- ``kernels`` — each Pallas kernel compiled (``interpret=False``) against
  its reference: flash fwd/bwd (the geometries whose tiles are computed by
  sub-blocks among them, with the counter's kinds and the steps the grids
  skip, ``flash_steps_held_total``), QK norm with RoPE
  fwd/bwd at the two mixed-decoder cells' shapes against ``F.rms_norm``
  and the XLA formula (``rope_calls_staged_total`` says the entry point
  took the kernels), the chunked gated delta rule against the recurrence
  and a Gated DeltaNet block beside a gated full-attention block at head
  width 256 (``linear_attn_calls_staged_total`` says the rule took the
  kernels, ``gated_delta_chunks_total``, the expert layers' counters), the
  rule's kernels on a layer-shaped row of 8,192 positions against XLA's
  chunked path at ``Precision.HIGHEST`` (the precision guard: a float32
  product that fell to one bf16 pass fails here and nowhere in the
  benchmark), fused LM-head CE fwd/bwd at the bench
  shape against the chunked scan, paged decode and Tq=5 verify at
  h12/d64/page 16 bf16 against the XLA gather.
- ``serve``  — ``DecodeServer`` over ``PagedKVCache`` with
  ``kernel="auto"``: a warm-up and shared-prefix generations equal
  ``dense_generate`` token for token, through the Pallas route.
- ``multichip`` — needs four chips (else "not run"): GPT-base on
  ``{"data": 4}`` and on ``{"data": 2, "model": 2}`` with tensor
  parallelism; shards on four devices with the bytes the pspecs imply,
  and losses equal to a one-chip run at the same global batch.

``--rehearse-cpu`` walks the same code at toy sizes with the kernels
interpreted, to debug the script without spending chip time. Its lines
say ``cpu`` and carry no time, its last line says ``"ok": false`` and
it never exits 0. A chip run prints step and phase times so that a
builder sees the chip ran; no line holds a rate or a utilization:
``benchmark/run.py`` is the one place that states a speed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

T0 = time.perf_counter()
PHASES = ("train", "kernels", "serve", "multichip")

# GPT-2 small at full width, batch 8; the rehearsal keeps the code path
# and drops the size
FULL = dict(vocab=50304, seq=1024, layers=12, hidden=768, heads=12,
            batch=8)
TINY = dict(vocab=512, seq=64, layers=2, hidden=64, heads=4, batch=4)
TRAIN_STEPS = 10
MULTICHIP_STEPS = 3
# bf16 training and a reduction order that differs between layouts; the
# first run on four v5e chips differed from one chip by at most 8e-5
MULTICHIP_LOSS_RTOL = 2e-3


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


class Run:
    """What every phase needs: the device as jax reports it, the sizes,
    and the one way a line reaches stdout."""

    def __init__(self, jax, rehearsal: bool):
        dev = jax.devices()[0]
        self.jax = jax
        self.rehearsal = rehearsal
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.cfg = TINY if rehearsal else FULL
        # compiled kernels on the chip; the interpreter only to rehearse
        self.interpret = rehearsal

    def say(self, phase: str, times=None, **fields):
        """``times`` holds the fields that are a time: a CPU run states
        counts and leaves them out."""
        line = {"phase": phase, "device": self.device, **fields}
        if self.rehearsal:
            line["rehearsal"] = True
        elif times:
            line.update(times)
        print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def _gpt_trainer(cfg, mesh, tensor_parallel=False, **trainer_kw):
    """The recipe of the benchmark's gpt2-small cells: bf16 parameters,
    AdamW at library defaults, dense logits into
    nn.functional.cross_entropy."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.engine import ParallelTrainer
    from paddle_tpu.text.models import GPTForPretraining

    paddle.seed(0)
    model = GPTForPretraining(
        tensor_parallel=tensor_parallel, vocab_size=cfg["vocab"],
        hidden_size=cfg["hidden"], num_layers=cfg["layers"],
        num_heads=cfg["heads"], max_position_embeddings=cfg["seq"],
        attn_dropout=0.0, hidden_dropout=0.0)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters())
    if tensor_parallel:
        # vocab-sharded logits: the model's ParallelCrossEntropy loss
        loss_fn = model.loss
    else:
        loss_fn = lambda logits, lbl: nn.functional.cross_entropy(  # noqa: E731
            logits, lbl)
    return ParallelTrainer(model, opt, loss_fn, mesh=mesh, **trainer_kw)


def _batch(cfg, batch):
    import numpy as np

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg["vocab"], (batch, cfg["seq"])).astype("int32")
    labels = rng.randint(0, cfg["vocab"],
                         (batch, cfg["seq"])).astype("int32")
    return ids, labels


def _timed_steps(jax, trainer, ids, labels, steps):
    """[(loss, seconds)] with the clock stopped after block_until_ready."""
    out = []
    for _ in range(steps):
        t = time.perf_counter()
        loss = trainer.train_step(ids, labels)
        jax.block_until_ready(loss)
        out.append((float(loss), time.perf_counter() - t))
    return out


def _compiled_since(moment):
    """{function: programs} the backend compiled (or loaded) since
    ``moment`` (``time.time()``), from the program's own staging record:
    a compile anywhere in the process, not only a re-staged train step."""
    from collections import Counter

    from paddle_tpu.telemetry import staging

    return dict(Counter(e["fun"] for e in staging.entries()
                        if e["phase"] == "compile" and e["start"] >= moment))


def _pallas_kernels(closed_jaxpr):
    """{kernel name: count} over every pallas_call of a jaxpr: the call's
    ``name=`` where it has one, else its kernel function's name."""
    from collections import Counter

    from paddle_tpu.analysis import walker

    return dict(Counter(
        walker.pallas_kernel_name(site.eqn)
        for site in walker.walk(closed_jaxpr)
        if site.primitive == "pallas_call"))


def _check_flash_calls(kernels, layers):
    """Every layer's attention staged the three flash kernels."""
    from paddle_tpu.ops.pallas.flash_attention import KERNEL_NAMES

    n_flash = sum(kernels.get(k, 0) for k in KERNEL_NAMES)
    check(n_flash == len(KERNEL_NAMES) * layers,
          f"staged step holds {kernels}, expected "
          f"{len(KERNEL_NAMES) * layers} flash-attention Pallas calls")


def _flash_tiles(registry):
    """``flash_tiles_staged_total`` as ``{kernel: {kind: tiles}}``: how the
    staged flash kernels compute the tiles a lane block's grid walks."""
    from paddle_tpu.ops.pallas.flash_attention import KERNEL_NAMES, TILE_KINDS

    staged = registry.get("flash_tiles_staged_total")
    return {kernel: {kind: int(staged.value(kernel=kernel, kind=kind))
                     for kind in TILE_KINDS}
            for kernel in KERNEL_NAMES} if staged else {}


def _flash_held(registry):
    """``flash_steps_held_total`` as ``{kernel: steps}``: the grid steps of
    a lane block that the staged flash kernels skip, their block index
    held."""
    from paddle_tpu.ops.pallas.flash_attention import KERNEL_NAMES

    held = registry.get("flash_steps_held_total")
    return {kernel: int(held.value(kernel=kernel))
            for kernel in KERNEL_NAMES} if held else {}


def _rope_calls(registry):
    """``rope_calls_staged_total`` as ``{path: {norm: calls}}``: which path
    the staged calls of ``F.rotary_embedding`` took."""
    staged = registry.get("rope_calls_staged_total")
    return {path: {f"norm={norm}": int(staged.value(path=path, norm=norm))
                   for norm in (0, 1)}
            for path in ("pallas", "xla")} if staged else {}


def _linear_attn_calls(registry):
    """``linear_attn_calls_staged_total`` by path, the chunk states those
    calls walk (``gated_delta_chunks_total``) and the expert layers' three
    (``publish_routing``'s), as the registry holds them."""
    out = {}
    staged = registry.get("linear_attn_calls_staged_total")
    if staged:
        out["calls"] = {path: int(staged.value(path=path))
                        for path in ("pallas", "chunked", "recurrent")}
    chunks = registry.get("gated_delta_chunks_total")
    if chunks:
        out["chunks"] = int(chunks.value())
    flat = registry.to_dict()
    for name in ("moe_tokens_routed_total", "moe_held_assignments_total",
                 "moe_max_load_over_mean"):
        if name in flat:
            out[name] = flat[name]["series"]
    return out


def _ssd_scan_calls(registry):
    """``ssd_scan_calls_staged_total`` by path, as the registry holds
    it."""
    staged = registry.get("ssd_scan_calls_staged_total")
    return {path: int(staged.value(path=path))
            for path in ("pallas", "chunked", "recurrent")} if staged else {}


def _latent_attn_calls(registry):
    """``latent_attn_calls_staged_total`` by the queries' rotation, the
    rotations and flash tiles those calls staged and the two loss gauges
    (``publish_losses``'s), as the registry holds them."""
    out = {"rope_calls_staged": _rope_calls(registry),
           "flash_tiles_staged": _flash_tiles(registry)}
    staged = registry.get("latent_attn_calls_staged_total")
    if staged:
        out["calls"] = {path: int(staged.value(rope=path))
                        for path in ("pallas", "xla")}
    flat = registry.to_dict()
    for name in ("mtp_main_loss", "mtp_next_loss"):
        if name in flat:
            out[name] = flat[name]["series"]
    return out


def _config_origins(run, entries):
    """Where each kernel config used here resolves from. A tuning-DB
    file outside the checkout would make the run depend on what an
    earlier one left in a home directory: that fails."""
    from paddle_tpu.ops.pallas import tuner
    from tools._mesh_setup import repo_root

    root = os.path.realpath(repo_root()) + os.sep
    out = {}
    for label, (kernel, dtype, dims) in entries.items():
        key, path = tuner.entry_origin(kernel, dtype, dims)
        out[label] = {"key": key, "db": path or "compiled-in defaults",
                      "config": tuner.get_db().lookup(key)["config"]
                      if key else None}
        check(path is None or os.path.realpath(path).startswith(root),
              f"{label}: config {key!r} comes from {path}, outside the "
              f"checkout {root}")
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train(run: Run):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import telemetry
    from paddle_tpu.ops.pallas import tuner
    from paddle_tpu.telemetry import staging
    from tools._mesh_setup import data_mesh

    jax, cfg = run.jax, run.cfg
    ids, labels = _batch(cfg, cfg["batch"])
    with telemetry.scope(profile=False) as tel:
        step_programs_before = staging.programs("train_step")
        trainer = _gpt_trainer(cfg, data_mesh(1))
        steps = _timed_steps(jax, trainer, ids, labels, 2)
        warm = time.time()
        # where the seconds to here went: the step's programs and the
        # call each was staged in, their trace, lower and compile, the
        # trainer's construction
        staged = trainer.staging_summary()
        run.say("train", event="first_step", times={
            "first_step_s": round(steps[0][1], 2),
            "since_start_s": round(time.perf_counter() - T0, 2),
            "staging": staged},
            step_programs=staged["train_step"]["programs"],
            step_programs_staged_in_steps=staged["train_step"][
                "staged_in_steps"])
        steps += _timed_steps(jax, trainer, ids, labels, TRAIN_STEPS - 2)
        compiled_late = _compiled_since(warm)
        kernels = _pallas_kernels(trainer.staged_jaxpr(ids, labels))
        resolved = tel.registry.get("pallas_config_resolved_total")
        flash_fallbacks = resolved.value(
            kernel="flash_attention", source="fallback") if resolved else 0
        recompiles = int(tel.registry.get("recompiles_total").value())
        flash_tiles = _flash_tiles(tel.registry)

    losses = [l for l, _ in steps]
    steady = sorted(t for _, t in steps[2:])
    step_s = steady[len(steady) // 2]
    n_params = sum(int(np.prod(p.shape))
                   for p in trainer.model.parameters())
    stats = jax.devices()[0].memory_stats() or {}
    out = {
        "losses": [round(l, 4) for l in losses],
        "ln_vocab": round(math.log(cfg["vocab"]), 4),
        "params": n_params,
        "compiles_after_step_2": compiled_late,
        "recompiles_total": recompiles,
        "pallas_calls": kernels,
        "flash_fallbacks": int(flash_fallbacks),
        "flash_tiles_staged": flash_tiles,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "config_origins": _config_origins(run, {
            "flash_attention": (
                "flash_attention", jnp.bfloat16,
                tuner.flash_dims(cfg["hidden"] // cfg["heads"],
                                 cfg["seq"], cfg["seq"]))}),
    }
    # the median step time tells a builder the chip ran; rates and
    # utilization are the benchmark's to state (benchmark/run.py)
    times = {"steps_3_to_10_median_ms": round(step_s * 1e3, 2)}
    run.say("train", event="result", times=times, **out)

    check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    check(abs(losses[0] - math.log(cfg["vocab"])) < 0.5,
          f"first loss {losses[0]} is not near ln(vocab)")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(not compiled_late,
          f"compilations after step 2, by function: {compiled_late}")
    # one batch shape, one program: the state goes into the first call as
    # the pytree the step hands back (ParallelTrainer._init_state)
    mine = staged["train_step"]["programs"] - step_programs_before
    calls = staged["train_step"]["staged_in_steps"][-mine:] if mine else []
    check(calls == [1],
          f"the step was staged in train_step calls {calls}, not in the "
          f"first alone")
    if not run.rehearsal:
        # off the TPU the gate routes attention to XLA by design
        _check_flash_calls(kernels, cfg["layers"])
        check(flash_fallbacks == 0,
              f"flash attention fell back {flash_fallbacks} times")
        # one causal (1024, 1024) tile a lane block: the backward kernels
        # cut it into sub-blocks over the keys that can be seen, the
        # forward computes it whole under its mask (ops/pallas/
        # flash_attention.py, _SUB_ROWS)
        want = {"flash_fwd": {"dense": 0, "triangular": 0, "masked": 1}}
        for kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
            want[kernel] = {"dense": 0, "triangular": 1, "masked": 0}
        check(flash_tiles == want,
              f"flash tiles staged as {flash_tiles}, expected {want}")
        check(out["peak_bytes_in_use"], "backend reports no memory stats")


def phase_kernels(run: Run):
    import jax.numpy as jnp

    from paddle_tpu import telemetry
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas import tuner
    from tools import numerics_smoke as ns

    if run.rehearsal:
        ce = dict(tokens=512, hidden=128, vocab=1024)
        paged = dict(heads=2, head_dim=32, page_size=8, dtype="float32")
    else:
        ce = dict(tokens=FULL["batch"] * FULL["seq"],
                  hidden=FULL["hidden"], vocab=FULL["vocab"])
        paged = dict(heads=12, head_dim=64, page_size=16, dtype="bfloat16")
    with telemetry.scope(profile=False) as tel:
        checks = ns.check_flash_tile_kinds(run.interpret)
        flash_tiles = _flash_tiles(tel.registry)
        flash_held = _flash_held(tel.registry)
        checks += ns.check_rope(run.interpret)
        rope_calls = _rope_calls(tel.registry)
    with telemetry.scope(profile=False) as tel:
        checks += ns.check_linear_attention(run.interpret)
        linear_attn = _linear_attn_calls(tel.registry)
        linear_rope_calls = _rope_calls(tel.registry)
    checks += ns.check_gated_delta_precision(run.interpret)
    with telemetry.scope(profile=False) as tel:
        checks += ns.check_ssd_scan(run.interpret)
        ssd = _ssd_scan_calls(tel.registry)
    with telemetry.scope(profile=False) as tel:
        checks += ns.check_latent_attention(run.interpret)
        latent = _latent_attn_calls(tel.registry)
    checks += (ns.check_flash_attention(run.interpret)
               + ns.check_fused_ce(run.interpret, **ce)
               + ns.check_paged_attention(run.interpret, **paged))
    for c in checks:
        run.say("kernels", **c)
    entries = {"fused_ce": ("fused_ce", jnp.bfloat16, tuner.ce_dims(
        ce["hidden"], ce["vocab"], ce["tokens"]))}
    for name, tq in (("paged_decode", 1), ("paged_verify", 5)):
        # check_paged_attention's pool: 8 pages per row
        entries[name] = ("paged_attention", jnp.dtype(paged["dtype"]),
                         pa.paged_dims(paged["head_dim"],
                                       paged["page_size"], 8, tq=tq))
    origins = _config_origins(run, entries)
    run.say("kernels", event="result", n_checks=len(checks),
            interpret=run.interpret, config_origins=origins,
            flash_tiles_staged=flash_tiles, flash_steps_held=flash_held,
            rope_calls_staged=rope_calls, linear_attn_staged=linear_attn,
            linear_model_rope_calls_staged=linear_rope_calls,
            ssd_scan_staged=ssd, latent_attn_staged=latent)
    bad = [c["check"] for c in checks if not c["ok"]]
    check(not bad, f"kernel checks out of tolerance: {bad}")
    # on the chip check_rope goes through F.rotary_embedding: two cases
    # without a norm weight and one with, each staged once, all by the
    # kernels (rehearsed, it calls the interpreted kernels itself)
    want = {} if run.rehearsal else {"pallas": {"norm=0": 2, "norm=1": 1},
                                     "xla": {"norm=0": 0, "norm=1": 0}}
    check(rope_calls == want, f"rotary_embedding staged as {rope_calls}, "
                              f"expected {want}")
    # check_linear_attention: the rule staged twice chunked (alone, and in
    # the decoder's one linear block), on the chip by the kernels (heads of
    # 128 lanes), rehearsed at toy heads by XLA's batched products, and once
    # as the recurrence; the decoder's full layer normalises and rotates q
    # and k in one call each, on the chip by the kernels (head width 256,
    # 64 lanes rotated)
    path = "xla" if run.rehearsal else "pallas"
    want = {"pallas": 0, "chunked": 0, "recurrent": 1}
    want["chunked" if run.rehearsal else "pallas"] = 2
    check(linear_attn.get("calls") == want,
          f"gated_delta_rule staged as {linear_attn}, expected {want}")
    check(linear_rope_calls.get(path, {}).get("norm=1") == 2
          and sum(n for calls in linear_rope_calls.values()
                  for n in calls.values()) == 2,
          f"the gated full layer's rotary_embedding staged as "
          f"{linear_rope_calls}, expected 2 calls with norm=1 on {path}")
    # check_ssd_scan: on the chip the scan staged once through F.ssd_scan,
    # by the kernels (heads of 64, a state of 128); rehearsed, it calls the
    # interpreted kernels itself and stages nothing
    want = {} if run.rehearsal else {"pallas": 1, "chunked": 0,
                                     "recurrent": 0}
    check(ssd == want, f"ssd_scan staged as {ssd}, expected {want}")
    # check_latent_attention: the trunk's block and the module's, each
    # staged once on the kernels' path (q through the rotary kernel, the one
    # rotary key head through XLA) and once more for the float32 reference,
    # whose rotations are all XLA's
    path = "xla" if run.rehearsal else "pallas"
    want = {"pallas": 0, "xla": 2}
    want[path] += 2
    check(latent.get("calls") == want,
          f"latent attention staged as {latent.get('calls')}, expected "
          f"{want}")
    rope = latent["rope_calls_staged"]
    check(rope.get("pallas", {}).get("norm=0") == (0 if run.rehearsal else 2)
          and sum(n for calls in rope.values() for n in calls.values()) == 8,
          f"the latent layers' rotary_embedding staged as {rope}: expected "
          f"8 calls, 2 of them (q on the chip's path) by the kernels")
    check(run.rehearsal or all(
        sum(kinds.values()) > 0
        for kinds in latent["flash_tiles_staged"].values()),
        f"the latent layers staged no flash tiles: "
        f"{latent['flash_tiles_staged']}")
    check(set(latent) >= {"mtp_main_loss", "mtp_next_loss"},
          f"the loss gauges were not published: {sorted(latent)}")
    # the three geometries of check_flash_tile_kinds, (dense, triangular,
    # masked): one 1024-row tile; two of them on the diagonal and one dense;
    # 4 tiles of 512 rows on the diagonal and 3 band edges. A triangle is
    # cut where a sub-block has 128 rows (forward), 128 or more (dq), 256
    # or more (dk/dv); else it is whole under its mask
    want = {"flash_fwd": (1, 7, 3), "flash_bwd_dq": (1, 10, 0),
            "flash_bwd_dkv": (1, 3, 7)}
    got = {kernel: tuple(kinds.values())
           for kernel, kinds in flash_tiles.items()}
    check(got == want, f"flash tiles staged as {flash_tiles}, expected "
                       f"(dense, triangular, masked) {want}")
    # the other steps of those grids, skipped with the block index held: none
    # of one tile; the tile over the diagonal of the 2 x 2 (for each of the 6
    # query heads of the dk/dv kernel's group); the second step of the
    # window's first q block and of its last key block (8 heads)
    want = {"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 6 + 8}
    check(flash_held == want,
          f"flash steps held as {flash_held}, expected {want}")


def phase_serve(run: Run):
    """Verify flow 6 (.claude/skills/verify/SKILL.md) on the live
    backend. The toy LM is fp32: at the TPU's default matmul precision
    the Pallas kernel and the dense oracle round differently and a near
    tie in the logits may flip an argmax, so the phase runs both at
    "highest" — what token-for-token equality is a statement about."""
    import numpy as np

    from paddle_tpu import telemetry
    from paddle_tpu.inference import serving
    from paddle_tpu.inference.decode_model import (dense_generate,
                                                   init_decode_model,
                                                   make_step_fn)
    from paddle_tpu.inference.kv_cache import PagedKVCache

    jax = run.jax
    page, new_tokens, followers = 8, 5, 4
    params = init_decode_model(vocab=128, num_heads=2, head_dim=32, seed=7)
    system = [int(t) for t in
              np.random.RandomState(11).randint(0, 128, 2 * page)]

    def prompt(i):
        rs = np.random.RandomState(100 + i)
        return system + [int(t) for t in rs.randint(0, 128, 4)]

    def tokens(request):
        return [int(x) for x in request.result(timeout=600)[0]]

    # the config, not the context manager: that one is thread-local, and
    # the server's replicas trace the step in threads of their own
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        with telemetry.scope(profile=False) as tel:
            cache = PagedKVCache(64, page, 2, 32)
            step = make_step_fn(params, cache, kernel="auto",
                                interpret=run.interpret)
            # the first call of each bucket compiles: leave it the time
            cfg = serving.ServingConfig(max_batch=32, call_timeout_s=300.0)
            server = serving.DecodeServer(
                step, cache, replicas=2, config=cfg, prefill_chunk=8,
                max_pages_per_seq=8, max_batch_rows=4)
            with server:
                t = time.perf_counter()
                got = [tokens(server.submit_generate(prompt(0),
                                                     new_tokens))]
                warm_s = time.perf_counter() - t
                hits0 = cache.prefix_hit_tokens
                got += [tokens(r) for r in [
                    server.submit_generate(prompt(i), new_tokens)
                    for i in range(1, followers + 1)]]
                accounted = server.accounted()
                stats = server.stats()
            want = [dense_generate(params, prompt(i), new_tokens)
                    for i in range(followers + 1)]
            resolved = tel.registry.get("pallas_config_resolved_total")
            routes = {source: int(resolved.value(kernel="paged_attention",
                                                 source=source))
                      for source in ("db", "default", "fallback")} \
                if resolved else {}
    finally:
        jax.config.update("jax_default_matmul_precision", precision)

    hits = cache.prefix_hit_tokens - hits0
    run.say("serve", event="result", generations=len(got),
            tokens_equal_dense=got == want, prefix_hit_tokens=hits,
            accounted=accounted, completed=stats["completed"],
            recompiles=stats["recompiles"], paged_attention_routes=routes,
            times={"warmup_generation_s": round(warm_s, 2)})
    check(got == want, f"served tokens {got} != dense_generate {want}")
    check(hits == followers * 2 * page,
          f"prefix hits {hits}, expected {followers * 2 * page}")
    check(accounted, "server.accounted() is false")
    check(routes.get("db", 0) + routes.get("default", 0) > 0
          and routes.get("fallback", 0) == 0,
          f"decode did not take the Pallas route: {routes}")


def _shard_report(trainer):
    """Per-device parameter bytes as placed, against what the pspecs
    imply; and the devices each parameter's shards sit on."""
    import numpy as np

    mesh = trainer.mesh
    placed, implied, spread = {}, 0, set()
    for name, arr in trainer.state["params"].items():
        factor = 1
        for ax in trainer.param_specs[name]:
            for a in ((ax,) if isinstance(ax, str) else (ax or ())):
                factor *= mesh.shape[a]
        want = arr.nbytes // factor
        implied += want
        spread.add(len({s.device for s in arr.addressable_shards}))
        for s in arr.addressable_shards:
            check(s.data.nbytes == want,
                  f"{name}: shard of {s.data.nbytes} B on {s.device}, "
                  f"pspec {trainer.param_specs[name]} implies {want} B")
            placed[s.device.id] = placed.get(s.device.id, 0) + s.data.nbytes
    n_dev = int(np.prod(list(mesh.shape.values())))
    check(spread == {n_dev},
          f"parameters sit on {spread} devices each, mesh has {n_dev}")
    check(set(placed.values()) == {implied},
          f"per-device parameter bytes {placed}, pspecs imply {implied}")
    return {"param_bytes_per_device": implied, "devices": sorted(placed)}


def phase_multichip(run: Run):
    from paddle_tpu.distributed.mesh import build_mesh

    jax, cfg = run.jax, run.cfg
    n = run.device["count"]
    if n != 4:
        run.say("multichip", event="not run",
                reason=f"not run: {n} device" + ("s" if n != 1 else ""))
        return "not run"
    global_batch = 4 * cfg["batch"]
    ids, labels = _batch(cfg, global_batch)

    def steps_of(trainer):
        steps = _timed_steps(jax, trainer, ids, labels, MULTICHIP_STEPS)
        return [l for l, _ in steps], round(steps[-1][1] * 1e3, 2)

    # the reference: one chip, the same global batch in four accumulated
    # chunks, so that it never holds more than the train phase's batch
    ref_trainer = _gpt_trainer(
        cfg, build_mesh({"data": 1}, devices=jax.devices()[:1]),
        accumulate_steps=4)
    ref, ref_ms = steps_of(ref_trainer)
    run.say("multichip", event="reference", devices=1,
            global_batch=global_batch, losses=[round(l, 4) for l in ref],
            times={"last_step_ms": ref_ms})
    del ref_trainer
    gc.collect()

    failures = []
    for name, degrees, tp in (("data4", {"data": 4}, False),
                              ("data2_model2", {"data": 2, "model": 2},
                               True)):
        trainer = _gpt_trainer(cfg, build_mesh(degrees), tensor_parallel=tp)
        got, last_ms = steps_of(trainer)
        shards = _shard_report(trainer)
        in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()}
        worst = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
        run.say("multichip", event="result", layout=name, degrees=degrees,
                tensor_parallel=tp, global_batch=global_batch,
                losses=[round(l, 4) for l in got],
                times={"last_step_ms": last_ms},
                max_rel_diff_vs_one_chip=round(worst, 5),
                rtol=MULTICHIP_LOSS_RTOL, bytes_in_use=in_use, **shards)
        if worst > MULTICHIP_LOSS_RTOL:
            failures.append(f"{name}: losses {got} vs one chip {ref}")
        if not run.rehearsal:
            idle = [i for i, b in in_use.items()
                    if i and (b or 0) < shards["param_bytes_per_device"]]
            if idle:
                failures.append(f"{name}: devices {idle} hold less than "
                                f"the parameters: {in_use}")
        del trainer
        gc.collect()
    check(not failures, "; ".join(failures))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of " + ",".join(PHASES))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU with interpreted kernels; "
                         "output says cpu, exit code is never 0")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}")

    if args.rehearse_cpu:
        # before jax initializes: the CPU, as four devices for multichip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    import jax

    from tools._mesh_setup import use_compile_cache

    run = Run(jax, rehearsal=args.rehearse_cpu)
    if not args.rehearse_cpu and run.device["platform"] != "tpu":
        print(f"chip_smoke: no TPU: jax's first device is "
              f"{run.device} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}). Nothing was run; "
              f"--rehearse-cpu walks the script at toy sizes.",
              file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    run.say("start", phases=phases, compile_cache=cache_dir,
            cache_from_env="JAX_COMPILATION_CACHE_DIR" in os.environ,
            cache_entries=cache_entries())

    fns = {"train": phase_train, "kernels": phase_kernels,
           "serve": phase_serve, "multichip": phase_multichip}
    verdicts = {}
    for name in phases:
        t = time.perf_counter()
        try:
            verdicts[name] = fns[name](run) or "ok"
        except Exception as e:  # one phase's failure must not hide the next
            traceback.print_exc()
            verdicts[name] = f"FAILED: {type(e).__name__}: {e}"[:500]
        run.say(name, event="verdict", verdict=verdicts[name],
                times={"phase_s": round(time.perf_counter() - t, 2)})
        gc.collect()

    passed = all(v in ("ok", "not run") for v in verdicts.values())
    run.say("summary", verdicts=verdicts, passed=passed,
            cache_entries=cache_entries(),
            times={"total_s": round(time.perf_counter() - T0, 2)})
    if args.rehearse_cpu:
        # a rehearsal is never a pass: 2 = walked through, 1 = broke
        print(json.dumps({"ok": False, "rehearsal_passed": passed,
                          "device": run.device}))
        return 2 if passed else 1
    if not passed:
        return 1
    print(json.dumps({"ok": True, "device": run.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
