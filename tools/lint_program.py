"""Lint the shipped bench models' train steps with the jaxpr analyzer.

Stages the bench GPT / BERT configurations (CPU shapes), traces the
EXACT jitted step each ParallelTrainer would run (donation mask,
comm_err / compressed grad-sync plumbing included) and runs every rule
in paddle_tpu.analysis over it, plus the cost model's top-k
most-expensive-equations table. The serving path is linted too: the
DecodeServer executor programs (``decode-mixed`` ragged prefill,
``decode-decode`` paged decode, ``decode-verify`` the rectangular
speculative-verify repack) are traced from ShapeDtypeStructs at the
bench shapes.

Exit status is the CI contract: 0 when no error-severity finding on any
model, 1 otherwise — warnings and infos print but do not fail unless
``--strict`` (then any warning fails too; infos never gate).

Usage:
    python tools/lint_program.py                  # all programs, text report
    python tools/lint_program.py --model gpt --json  # machine-readable
    python tools/lint_program.py --smoke --strict # tiny configs, tier-1 CI
    python tools/lint_program.py --model gpt --dump-sharding
                                  # per-equation sharding/conflict table
"""
from __future__ import annotations

import argparse
import json
import os
import sys

try:
    from _mesh_setup import (data_mesh, ensure_repo_on_path,
                             force_host_devices)
except ImportError:  # imported as tools.lint_program (tests)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _mesh_setup import (data_mesh, ensure_repo_on_path,
                             force_host_devices)


def _build_gpt(smoke: bool):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.engine import ParallelTrainer
    from paddle_tpu.text.models import GPTForPretraining

    if smoke:
        vocab, h, layers, heads, seq, batch = 256, 64, 1, 2, 32, 4
    else:  # the toy GPT shape bench_plan and bench_collectives share
        vocab, h, layers, heads, seq, batch = 1024, 128, 2, 4, 128, 4
    paddle.seed(0)
    model = GPTForPretraining(
        tensor_parallel=False, vocab_size=vocab, hidden_size=h,
        num_layers=layers, num_heads=heads, max_position_embeddings=seq,
        attn_dropout=0.0, hidden_dropout=0.0)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters())
    trainer = ParallelTrainer(
        model, opt,
        lambda logits, lbl: nn.functional.cross_entropy(logits, lbl))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq)).astype("int32")
    labels = rng.randint(0, vocab, (batch, seq)).astype("int32")
    return trainer, ids, labels


def _build_gpt_planner(smoke: bool):
    """The auto-parallel planner's chosen config at the lint device
    count: ``plan_search`` over the bench GPT spec, winner realized via
    ``ParallelTrainer.from_plan`` (tools/bench_plan.py's builder). The
    shipped planner path must stage and lint as clean as the
    hand-written configs."""
    import jax

    from bench_plan import _gpt_spec, make_gpt_builder, search

    spec = _gpt_spec(smoke)
    n = len(jax.devices())
    builder = make_gpt_builder(spec, spec["batch_per_device"] * n)
    ranked, _baselines, _n_params = search(spec, n)
    return builder(ranked[0])


def _build_bert(smoke: bool):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.engine import ParallelTrainer
    from paddle_tpu.text.models import BertForPretraining

    if smoke:
        cfg = dict(vocab_size=256, hidden_size=64, num_layers=1,
                   num_heads=2, max_position_embeddings=32)
        batch, seq = 4, 32
    else:  # a two-layer toy BERT
        cfg = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                   num_heads=4, max_position_embeddings=128)
        batch, seq = 4, 64
    paddle.seed(0)
    model = BertForPretraining(tensor_parallel=False, attn_dropout=0.0,
                               hidden_dropout=0.0, **cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(outputs, labels):
        mlm_logits, nsp_logits = outputs
        mlm_labels, nsp_labels = labels
        return model.loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels)

    trainer = ParallelTrainer(model, opt, loss_fn)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg["vocab_size"], (batch, seq)).astype("int32")
    mlm = np.full((batch, seq), -100, dtype="int32")
    mlm[:, ::8] = rng.randint(0, cfg["vocab_size"], (batch, seq // 8))
    nsp = rng.randint(0, 2, (batch,)).astype("int32")
    return trainer, ids, (mlm, nsp)


def _decode_jaxpr(which: str, smoke: bool):
    """Trace one DecodeServer executor fn (PR 11 serving contract) at
    the bench shapes from ShapeDtypeStructs — nothing materialized."""
    import jax
    import numpy as np

    from paddle_tpu.inference.decode_model import (init_decode_model,
                                                   make_step_fn)
    from paddle_tpu.inference.kv_cache import PagedKVCache

    if smoke:
        vocab, heads, hd, t, r, w, pages, page = 128, 2, 16, 16, 4, 4, 16, 8
    else:  # tools/bench_serving.py default shapes
        vocab, heads, hd, t, r, w, pages, page = 256, 4, 32, 64, 8, 8, 64, 16
    params = init_decode_model(vocab, heads, hd, max_len=1024)
    cache = PagedKVCache(pages, page, heads, hd, num_layers=1)
    step = make_step_fn(params, cache)
    mixed, decode, verify = step.jit_fns
    kp, vp = cache.pools(0)
    s = jax.ShapeDtypeStruct
    if which == "verify":
        # speculative-verify chunks: (R, S) rectangular repack, S = the
        # bucketed 1 + K chunk width (K = 4 at the bench spec shapes)
        sv = 8
        args = (s(kp.shape, kp.dtype), s(vp.shape, vp.dtype),
                s((r, sv), np.int32), s((r,), np.int32),
                s((r, w), np.int32), s((r,), np.int32))
        return jax.make_jaxpr(lambda *a: verify(*a))(*args)
    args = (s(kp.shape, kp.dtype), s(vp.shape, vp.dtype),
            s((t,), np.int32), s((t,), np.int32), s((t,), np.int32),
            s((t,), np.bool_), s((r, w), np.int32), s((r,), np.int32),
            s((r,), np.int32))
    fn = mixed if which == "mixed" else decode
    return jax.make_jaxpr(lambda *a: fn(*a))(*args)


# ParallelTrainer programs: staged via trainer.compile(analyze=True).
BUILDERS = {"gpt": _build_gpt, "gpt-planner": _build_gpt_planner,
            "bert": _build_bert}
# Inference executor programs: plain ClosedJaxprs, no trainer.
PROGRAMS = {"decode-mixed": lambda smoke: _decode_jaxpr("mixed", smoke),
            "decode-decode": lambda smoke: _decode_jaxpr("decode", smoke),
            "decode-verify": lambda smoke: _decode_jaxpr("verify", smoke)}
ALL_MODELS = tuple(BUILDERS) + tuple(PROGRAMS)


# ---------------------------------------------------------------------------
# ProgramFamily registration: every shipped multi-program dispatch site
# (trainer integrity pair, LocalSGD sync/no-sync, decode executor router)
# declared so the schedule verifier can prove its member schedules are
# picked by a rank-invariant host predicate.
# ---------------------------------------------------------------------------

def _trainer_family(smoke: bool):
    """The bench GPT trainer's step / step-with-integrity-check pair."""
    trainer, ids, labels = _build_gpt(smoke)
    return trainer.program_family(ids, labels)


def _localsgd_family(smoke: bool):
    """A small LocalSGD trainer's sync / no-sync pair (the shapes don't
    change the schedule contract, only the payload buckets)."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.meta_parallel.localsgd import \
        LocalSGDTrainer

    paddle.seed(0)
    mesh = data_mesh(1)
    model = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(0.05, parameters=model.parameters())
    # compressed param sync: the averaging collectives are explicit
    # primitives, so the verified sync schedule is non-trivial
    tr = LocalSGDTrainer(model, opt,
                         lambda out, y: jnp.mean((out - y) ** 2),
                         mesh=mesh, k_steps=4, param_sync="int8")
    x = np.zeros((8, 8), np.float32)
    y = np.zeros((8, 4), np.float32)
    return tr.program_family(x, y)


def _decode_family(smoke: bool):
    """The DecodeServer mixed/decode/verify executor router as a
    declared family (same shapes as :func:`_decode_jaxpr`)."""
    import jax
    import numpy as np

    from paddle_tpu.inference.decode_model import (executor_family,
                                                   init_decode_model,
                                                   make_step_fn)
    from paddle_tpu.inference.kv_cache import PagedKVCache

    if smoke:
        vocab, heads, hd, t, r, w, pages, page = 128, 2, 16, 16, 4, 4, 16, 8
    else:
        vocab, heads, hd, t, r, w, pages, page = 256, 4, 32, 64, 8, 8, 64, 16
    params = init_decode_model(vocab, heads, hd, max_len=1024)
    cache = PagedKVCache(pages, page, heads, hd, num_layers=1)
    step = make_step_fn(params, cache)
    kp, vp = cache.pools(0)
    s = jax.ShapeDtypeStruct
    sv = 8
    step_args = (s(kp.shape, kp.dtype), s(vp.shape, vp.dtype),
                 s((t,), np.int32), s((t,), np.int32), s((t,), np.int32),
                 s((t,), np.bool_), s((r, w), np.int32), s((r,), np.int32),
                 s((r,), np.int32))
    verify_args = (s(kp.shape, kp.dtype), s(vp.shape, vp.dtype),
                   s((r, sv), np.int32), s((r,), np.int32),
                   s((r, w), np.int32), s((r,), np.int32))
    return executor_family(step, {"mixed": step_args, "decode": step_args,
                                  "verify": verify_args})


FAMILY_BUILDERS = {"trainer-step": _trainer_family,
                   "localsgd-step": _localsgd_family,
                   "decode-executor": _decode_family}


def verify_families(smoke: bool, top: int = 10):
    """Register + schedule-verify every shipped ProgramFamily. Returns
    the per-family verdict dicts keyed by family name."""
    from paddle_tpu.analysis import AnalysisConfig
    from paddle_tpu.analysis import schedule as sched

    cfg = AnalysisConfig(top_k=top)
    out = {}
    for name, build in FAMILY_BUILDERS.items():
        fam = build(smoke)
        sched.register_family(fam, replace=True)
        out[name] = sched.verify_family(fam, config=cfg)
    return out


def lint_model(name: str, smoke: bool, top: int,
               dump_schedule: bool = False, dump_sharding: bool = False):
    from paddle_tpu import analysis
    from paddle_tpu.analysis import AnalysisConfig
    from paddle_tpu.analysis import schedule as sched

    mesh = data_mesh(1)
    cfg = AnalysisConfig(top_k=top)
    schedule = sharding = None
    if name in BUILDERS:
        trainer, inputs, labels = BUILDERS[name](smoke)
        _, report = trainer.compile(inputs, labels, analyze=True,
                                    config=cfg)
        closed = trainer.staged_jaxpr(inputs, labels)
        prog_mesh = trainer.mesh
        if dump_schedule:
            from paddle_tpu.analysis import cost
            schedule = cost.overlap_summary(closed, trainer.mesh,
                                            include_timeline=True)
        if dump_sharding:
            from paddle_tpu.analysis.sharding import propagate
            info = propagate(closed, trainer.mesh,
                             trainer.staged_in_specs(inputs, labels),
                             collect_table=True)
            sharding = info.to_dict()
    else:
        closed = PROGRAMS[name](smoke)
        prog_mesh = mesh
        report = analysis.analyze_jaxpr(closed, mesh=mesh, config=cfg)
        if dump_schedule:
            from paddle_tpu.analysis import cost
            schedule = cost.overlap_summary(closed, mesh,
                                            include_timeline=True)
        if dump_sharding:
            from paddle_tpu.analysis.sharding import propagate
            n = len(closed.jaxpr.invars)
            info = propagate(closed, mesh, [None] * n, collect_table=True)
            sharding = info.to_dict()
    sites = sched.extract_schedule(closed, mesh=prog_mesh)
    collectives = {"fingerprint": sched.fingerprint(sites),
                   "num_collectives": len(sites),
                   "rows": sched.schedule_rows(sites),
                   "text": sched.format_schedule(sites)}
    return report, schedule, sharding, collectives


def _schedule_text(name: str, sched: dict) -> str:
    """Render the overlap timeline as a fixed-width per-equation table."""
    lines = [f"-- {name} schedule: "
             f"makespan {sched['makespan'] * 1e6:.4g}us, "
             f"compute {sched['compute_time'] * 1e6:.4g}us, "
             f"collective {sched['collective_time'] * 1e6:.4g}us, "
             f"stalled {sched['stalled_time'] * 1e6:.4g}us, "
             "overlap_efficiency "
             + (f"{sched['overlap_efficiency']:.3f}"
                if sched["overlap_efficiency"] is not None else "n/a"),
             f"{'start_us':>10} {'end_us':>10} {'kind':<10} "
             f"{'primitive':<22} {'cost':>12}  path"]
    for e in sched.get("timeline", ()):
        cost = (f"{e['bytes']:.0f}B/{e['link']}"
                if e["kind"] in ("collective", "reshard")
                else f"{e['flops']:.0f}F")
        stall = (f" (+{e['stall'] * 1e6:.3g}us stall)"
                 if e.get("stall") else "")
        lines.append(f"{e['start'] * 1e6:>10.3f} {e['end'] * 1e6:>10.3f} "
                     f"{e['kind']:<10} {e['primitive']:<22} {cost:>12}  "
                     f"{e['path']}{stall}")
    return "\n".join(lines)


def _sharding_text(name: str, info: dict) -> str:
    """Render the sharding-propagation pass's per-equation table plus
    the predicted implicit-collective sites."""
    lines = [f"-- {name} sharding: {info['n_sites']} predicted implicit "
             f"collectives, {info['total_time_s'] * 1e6:.4g}us modeled, "
             f"{info['total_wire_bytes']:.0f} wire bytes",
             f"{'#':>5} {'primitive':<22} {'out spec':<28} {'conf':>4}  "
             "path"]
    for row in info.get("table", ()):
        out = ", ".join(row["out"])
        lines.append(f"{row['eqn_index']:>5} {row['primitive']:<22} "
                     f"{out:<28} {row['conflicts'] or '':>4}  "
                     f"{row['path']}")
    for s in info.get("sites", ()):
        lines.append(f"  site: {s['kind']} over {s['axes']} "
                     f"{s['bytes']:.0f}B on {s['link']} at "
                     f"{s['path']}#{s['eqn_index']} ({s['primitive']}): "
                     f"{s['detail']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", choices=ALL_MODELS + ("decode", "all"),
                    default="all")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON report object keyed by model")
    ap.add_argument("--top", type=int, default=10,
                    help="cost-table length (default 10)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny 1-layer configs; the tier-1 CI wrapper")
    ap.add_argument("--strict", action="store_true",
                    help="warnings also exit 1 (CI mode); infos never "
                         "gate")
    ap.add_argument("--devices", type=int, default=1,
                    help="forced host device count when no accelerator")
    ap.add_argument("--dump-schedule", action="store_true",
                    help="print the overlap model's per-equation "
                         "compute/collective timeline (with --json: a "
                         "'schedule' object per model)")
    ap.add_argument("--dump-sharding", action="store_true",
                    help="print the sharding-propagation pass's "
                         "per-equation spec/conflict table and predicted "
                         "implicit collectives (with --json: a "
                         "'sharding' object per model)")
    ap.add_argument("--dump-collectives", action="store_true",
                    help="print the canonical ordered collective "
                         "schedule per program (kind/axes/dtype/bucket/"
                         "link/context + fingerprint; with --json: a "
                         "'collectives' row list per model)")
    args = ap.parse_args(argv)

    force_host_devices(args.devices)
    ensure_repo_on_path()

    if args.model == "all":
        models = ALL_MODELS
    elif args.model == "decode":
        models = tuple(PROGRAMS)
    else:
        models = (args.model,)
    reports, schedules, shardings, collectives = {}, {}, {}, {}
    for name in models:
        (reports[name], schedules[name], shardings[name],
         collectives[name]) = lint_model(
            name, args.smoke, args.top, dump_schedule=args.dump_schedule,
            dump_sharding=args.dump_sharding)
    # every shipped program family is registered and schedule-verified
    # whenever the full suite runs — tier-1 (--smoke --strict) fails on
    # any new deadlock hazard or undeclared family drift
    families = verify_families(args.smoke, args.top) \
        if args.model == "all" else {}

    if args.json:
        out = {n: r.to_dict() for n, r in reports.items()}
        for n in out:
            out[n]["schedule_fingerprint"] = collectives[n]["fingerprint"]
            out[n]["num_collectives"] = collectives[n]["num_collectives"]
        if args.dump_schedule:
            for n in out:
                out[n]["schedule"] = schedules[n]
        if args.dump_sharding:
            for n in out:
                out[n]["sharding"] = shardings[n]
        if args.dump_collectives:
            for n in out:
                out[n]["collectives"] = collectives[n]["rows"]
        if families:
            out["__families__"] = families
        print(json.dumps(out))
    else:
        for name, rep in reports.items():
            print(f"== {name} ==")
            print(rep.to_text())
            if args.dump_schedule and schedules[name] is not None:
                print(_schedule_text(name, schedules[name]))
            if args.dump_sharding and shardings[name] is not None:
                print(_sharding_text(name, shardings[name]))
            if args.dump_collectives:
                c = collectives[name]
                print(f"-- {name} collective schedule: "
                      f"{c['num_collectives']} collective(s), "
                      f"fingerprint {c['fingerprint'][:16]}")
                print(c["text"])
        for fname, res in families.items():
            status = "ok" if res["ok"] else "FAIL"
            fps = {m: v["fingerprint"][:12]
                   for m, v in res["members"].items()}
            print(f"== family {fname} == {status} "
                  f"(selector: {res['selector']}) {fps}")
    ok = all(r.ok for r in reports.values())
    families_ok = all(res["ok"] for res in families.values())
    if ok and families_ok and args.strict:
        n_warn = sum(1 for r in reports.values() for f in r.findings
                     if f.severity == "warning")
        if n_warn:
            print(f"lint_program: --strict and {n_warn} warning(s) "
                  "present", file=sys.stderr)
            return 1
    if not ok:
        print("lint_program: error-severity findings present",
              file=sys.stderr)
    if not families_ok:
        bad = [n for n, res in families.items() if not res["ok"]]
        print(f"lint_program: program-family schedule verification "
              f"failed: {bad}", file=sys.stderr)
    return 0 if ok and families_ok else 1


if __name__ == "__main__":
    sys.exit(main())
