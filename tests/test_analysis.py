"""Tests for paddle_tpu.analysis: the canonical jaxpr walker, the cost
model, the rule registry, and the three integration layers
(static.Program, ParallelTrainer.compile, tools/lint_program.py).

The rule tests are seeded-violation fixtures: each constructs the
smallest program that contains EXACTLY ONE instance of its violation and
asserts the rule fires exactly once (and that a clean variant stays
silent), so a rule that over- or under-matches fails loudly here before
it pollutes CI lint reports.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import analysis, nn
from paddle_tpu.analysis import AnalysisConfig, analyze, analyze_jaxpr
from paddle_tpu.analysis import cost as acost
from paddle_tpu.analysis import walker
from paddle_tpu.distributed.mesh import build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rule_hits(report, rule):
    return [f for f in report.findings if f.rule == rule]


# ---------------------------------------------------------------------------
# walker
# ---------------------------------------------------------------------------

class TestWalker:
    def test_count_eqns_recursive(self):
        def f(x):
            def body(c, _):
                return jnp.sin(c) + 1.0, None
            out, _ = lax.scan(body, x, None, length=3)
            return jax.jit(jnp.tanh)(out)

        cj = jax.make_jaxpr(f)(jnp.zeros(4))
        top = len(cj.jaxpr.eqns)
        total = walker.count_eqns(cj)
        assert total > top  # scan body + jitted tanh counted through

    def test_walk_scan_trips_and_loop_flag(self):
        def f(x):
            def body(c, _):
                return jnp.dot(c, c), None
            out, _ = lax.scan(body, x, None, length=5)
            return out

        cj = jax.make_jaxpr(f)(jnp.zeros((4, 4)))
        dots = [s for s in walker.walk(cj) if s.primitive == "dot_general"]
        assert len(dots) == 1
        assert dots[0].trips == 5.0
        assert dots[0].in_loop

    def test_walk_bound_axes_through_shard_map(self):
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        f = jax.shard_map(lambda v: lax.psum(v, "data"), mesh=mesh,
                          in_specs=P("data"), out_specs=P(),
                          check_vma=False)
        cj = jax.make_jaxpr(f)(jnp.zeros(4))
        psums = [s for s in walker.walk(cj) if s.primitive == "psum"]
        assert len(psums) == 1
        assert "data" in psums[0].bound_axes

    def test_inline_target_knows_remat2(self):
        """jax.checkpoint traces to the 'remat2' primitive on this jax;
        the walker must classify it as transparently inlineable (the old
        hand-rolled ONNX dispatch only knew 'remat'/'checkpoint')."""
        cj = jax.make_jaxpr(jax.checkpoint(jnp.sin))(jnp.zeros(3))
        (eqn,) = cj.jaxpr.eqns
        assert eqn.primitive.name == "remat2"
        assert walker.inline_target(eqn) is not None
        assert walker.has_inner(eqn)

    def test_iter_jaxprs_yields_every_scope(self):
        def f(x):
            return lax.cond(x.sum() > 0, jnp.sin, jnp.cos, x)

        cj = jax.make_jaxpr(f)(jnp.zeros(3))
        paths = [p for p, _ in walker.iter_jaxprs(cj)]
        assert () in paths
        assert sum(1 for p in paths if p and p[-1].startswith("cond[")) >= 2


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

class TestCost:
    def test_dot_flops_exact(self):
        cj = jax.make_jaxpr(jnp.dot)(jnp.zeros((8, 16)), jnp.zeros((16, 4)))
        assert acost.matmul_flops(cj) == 2.0 * 8 * 16 * 4

    def test_scan_multiplies_by_length(self):
        def f(x):
            def body(c, _):
                return jnp.dot(c, c), None
            out, _ = lax.scan(body, x, None, length=7)
            return out

        cj = jax.make_jaxpr(f)(jnp.zeros((4, 4)))
        assert acost.matmul_flops(cj) == 7 * 2.0 * 4 * 4 * 4

    def test_cond_bills_max_branch(self):
        def f(x):
            return lax.cond(x[0, 0] > 0,
                            lambda v: jnp.dot(v, v) + jnp.dot(v, v),
                            lambda v: jnp.dot(v, v), x)

        cj = jax.make_jaxpr(f)(jnp.zeros((4, 4)))
        one_dot = 2.0 * 4 * 4 * 4
        assert acost.matmul_flops(cj) == 2 * one_dot  # max, not sum

    def test_peak_live_bytes_bounds(self):
        def f(x):
            y = x + 1.0
            return (y * 2.0).sum()

        cj = jax.make_jaxpr(f)(jnp.zeros((256,), jnp.float32))
        peak = acost.peak_live_bytes(cj)
        assert peak >= 1024.0       # the input alone
        assert peak <= 4 * 1024.0   # never more than a few temporaries

    def test_top_equations_sorted_and_bounded(self):
        def f(x, w):
            return jnp.dot(jnp.dot(x, w), w)

        cj = jax.make_jaxpr(f)(jnp.zeros((8, 8)), jnp.zeros((8, 8)))
        top = acost.top_equations(cj, k=1)
        assert len(top) == 1
        assert top[0].primitive == "dot_general"

    def test_summarize_report_renders(self):
        rep = analyze(lambda x: jnp.dot(x, x), jnp.zeros((4, 4)))
        text = rep.to_text()
        assert "dot_general" in text
        parsed = json.loads(rep.to_json())
        assert parsed["cost"]["matmul_flops"] == 2.0 * 4 * 4 * 4
        assert parsed["ok"] is True


# ---------------------------------------------------------------------------
# seeded-violation fixtures: each rule fires exactly once
# ---------------------------------------------------------------------------

class TestRules:
    def test_fp64_leak_fires_once(self):
        with jax.enable_x64():
            cj = jax.make_jaxpr(
                lambda x: x.astype(jnp.float64))(jnp.zeros(4, jnp.float32))
        rep = analyze_jaxpr(cj)
        assert len(rule_hits(rep, "fp64-leak")) == 1
        assert not rep.ok

    def test_fp64_silent_on_fp32(self):
        rep = analyze(lambda x: x * 2.0, jnp.zeros(4, jnp.float32))
        assert not rule_hits(rep, "fp64-leak")

    def test_unbound_axis_psum_fires_once(self):
        """psum over an axis no shard_map binds — the vmap-without-
        axis_name / stale-axis-env shape of collective misuse."""
        cj = jax.make_jaxpr(lambda x: lax.psum(x, "rogue"),
                            axis_env=[("rogue", 4)])(jnp.zeros(4))
        rep = analyze_jaxpr(cj)
        hits = rule_hits(rep, "collective-unbound-axis")
        assert len(hits) == 1
        assert "rogue" in hits[0].message
        assert not rep.ok

    def test_bound_axis_psum_clean(self):
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        f = jax.shard_map(lambda v: lax.psum(v, "data"), mesh=mesh,
                          in_specs=P("data"), out_specs=P(),
                          check_vma=False)
        rep = analyze_jaxpr(jax.make_jaxpr(f)(jnp.zeros(4)), mesh=mesh)
        assert not rule_hits(rep, "collective-unbound-axis")
        assert not rule_hits(rep, "collective-axis-not-in-mesh")

    def test_axis_not_in_active_mesh_fires_once(self):
        rogue = Mesh(np.array(jax.devices()[:2]), ("rogue",))
        f = jax.shard_map(lambda v: lax.psum(v, "rogue"), mesh=rogue,
                          in_specs=P("rogue"), out_specs=P(),
                          check_vma=False)
        cj = jax.make_jaxpr(f)(jnp.zeros(4))
        active = build_mesh({"data": 2})
        rep = analyze_jaxpr(cj, mesh=active)
        assert len(rule_hits(rep, "collective-axis-not-in-mesh")) == 1

    def test_ppermute_non_permutation_fires_once(self):
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        f = jax.shard_map(
            lambda v: lax.ppermute(v, "data", perm=[(0, 1), (0, 0)]),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False)
        rep = analyze_jaxpr(jax.make_jaxpr(f)(jnp.zeros(4)), mesh=mesh)
        assert len(rule_hits(rep, "ppermute-non-permutation")) == 1

    def test_ppermute_rotation_clean(self):
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        f = jax.shard_map(
            lambda v: lax.ppermute(v, "data", perm=[(0, 1), (1, 0)]),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False)
        rep = analyze_jaxpr(jax.make_jaxpr(f)(jnp.zeros(4)), mesh=mesh)
        assert not rule_hits(rep, "ppermute-non-permutation")

    def test_host_callback_in_step_fires_once(self):
        def step(x):
            jax.debug.callback(lambda v: None, x)
            return x * 2.0

        rep = analyze(step, jnp.zeros(4))
        hits = rule_hits(rep, "host-callback")
        assert len(hits) == 1
        assert "host round-trip" in hits[0].message

    def test_non_donated_large_arg_fires_once(self):
        big = jax.ShapeDtypeStruct((512, 1024), jnp.float32)  # 2 MiB
        cj = jax.make_jaxpr(jax.jit(lambda x: x + 1.0))(big)
        rep = analyze_jaxpr(cj)
        assert len(rule_hits(rep, "non-donated-large-arg")) == 1

    def test_donated_arg_clean(self):
        big = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
        cj = jax.make_jaxpr(
            jax.jit(lambda x: x + 1.0, donate_argnums=0))(big)
        rep = analyze_jaxpr(cj)
        assert not rule_hits(rep, "non-donated-large-arg")

    def test_explicit_donation_info_overrides(self):
        """When the caller supplies the donation mask (the trainer path),
        it is authoritative — no pjit-param double counting."""
        big = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
        cj = jax.make_jaxpr(jax.jit(lambda x: x + 1.0))(big)
        rep = analyze_jaxpr(cj, donated={0})
        assert not rule_hits(rep, "non-donated-large-arg")

    def test_recompile_scalar_const_fires_once(self):
        c = jnp.asarray(2.5)  # 0-d closed-over const -> retrace hazard
        rep = analyze(lambda x: x * c, jnp.zeros(4))
        assert len(rule_hits(rep, "recompile-scalar-const")) == 1

    def test_dead_equation_fires_once(self):
        def f(x):
            _unused = jnp.sin(x)
            return x * 2.0

        rep = analyze(f, jnp.zeros(4))
        hits = rule_hits(rep, "dead-equation")
        assert len(hits) == 1
        assert hits[0].primitive == "sin"

    def test_oversized_allgather_fires_once(self):
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        f = jax.shard_map(lambda v: lax.all_gather(v, "data"), mesh=mesh,
                          in_specs=P("data"), out_specs=P(),
                          check_vma=False)
        cj = jax.make_jaxpr(f)(jnp.zeros((8, 4)))
        cfg = AnalysisConfig(allgather_warn_bytes=64.0)
        rep = analyze_jaxpr(cj, mesh=mesh, config=cfg)
        assert len(rule_hits(rep, "oversized-allgather")) == 1
        # default 64 MiB threshold: same program is clean
        assert not rule_hits(analyze_jaxpr(cj, mesh=mesh),
                             "oversized-allgather")

    def test_amp_fp32_leak_fires_once(self):
        def f(x, w):
            return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))

        rep = analyze(f, jnp.zeros((4, 8), jnp.bfloat16),
                      jnp.zeros((8, 4), jnp.bfloat16))
        assert len(rule_hits(rep, "amp-fp32-leak")) == 1
        # a matmul kept in bf16 is what AMP wants: silent
        clean = analyze(lambda x, w: jnp.dot(x, w),
                        jnp.zeros((4, 8), jnp.bfloat16),
                        jnp.zeros((8, 4), jnp.bfloat16))
        assert not rule_hits(clean, "amp-fp32-leak")

    def test_int4_overflow_fires_on_narrow_accumulator(self):
        """An int16 sum over 8192 int4-range values can reach 8192*7 >
        int16 max — the hand-rolled-exchange overflow shape."""
        x = jnp.zeros((8192, 4), jnp.int16)
        cj = jax.make_jaxpr(
            lambda v: jnp.sum(v, axis=0, dtype=jnp.int16))(x)
        rep = analyze_jaxpr(cj)
        assert len(rule_hits(rep, "int4-grad-sync-overflow")) == 1
        assert not rep.ok   # severity "error" gates CI

    def test_int4_overflow_silent_when_safe_or_widened(self):
        # 64 * 7 fits int16: silent
        small = jax.make_jaxpr(
            lambda v: jnp.sum(v, axis=0, dtype=jnp.int16))(
                jnp.zeros((64, 4), jnp.int16))
        assert not rule_hits(analyze_jaxpr(small),
                             "int4-grad-sync-overflow")
        # the int4_accum_dtype fix — widen to int32 — is also silent
        wide = jax.make_jaxpr(
            lambda v: jnp.sum(v, axis=0, dtype=jnp.int32))(
                jnp.zeros((8192, 4), jnp.int16))
        assert not rule_hits(analyze_jaxpr(wide),
                             "int4-grad-sync-overflow")

    def _linked_mesh(self, links):
        from paddle_tpu.distributed import mesh as mesh_mod
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))
        mesh_mod.set_axis_links(links, mesh=mesh)
        return mesh, mesh_mod

    def test_link_mismatch_fires_on_int8_over_ici_only(self):
        mesh, mesh_mod = self._linked_mesh({"data": "dcn",
                                            "model": "ici"})
        try:
            f = jax.shard_map(
                lambda v: lax.all_to_all(v.astype(jnp.int8), "model",
                                         split_axis=0, concat_axis=0,
                                         tiled=False),
                mesh=mesh, in_specs=P(("data", "model"), None),
                out_specs=P(("data", "model"), None), check_vma=False)
            cj = jax.make_jaxpr(f)(jnp.zeros((8, 8), jnp.float32))
            rep = analyze_jaxpr(cj, mesh=mesh)
            hits = rule_hits(rep, "compressed-collective-link-mismatch")
            assert len(hits) == 1
            assert "ICI-only" in hits[0].message
        finally:
            mesh_mod._state.links.clear()

    def test_link_mismatch_fires_on_large_fp32_over_dcn(self):
        mesh, mesh_mod = self._linked_mesh({"data": "dcn"})
        try:
            f = jax.shard_map(
                lambda v: lax.psum(v, "data"), mesh=mesh,
                in_specs=P(("data", "model"), None),
                out_specs=P(("data", "model"), None), check_vma=False)
            cj = jax.make_jaxpr(f)(jnp.zeros((4, 64), jnp.float32))
            cfg = AnalysisConfig(dcn_uncompressed_min_bytes=64.0)
            rep = analyze_jaxpr(cj, mesh=mesh, config=cfg)
            hits = rule_hits(rep, "compressed-collective-link-mismatch")
            assert len(hits) == 1
            assert "DCN" in hits[0].message
            # default 1 MiB threshold: this tiny psum is clean
            assert not rule_hits(analyze_jaxpr(cj, mesh=mesh),
                                 "compressed-collective-link-mismatch")
        finally:
            mesh_mod._state.links.clear()

    def test_link_mismatch_silent_without_links(self):
        """Single-slice CPU meshes (no explicit map, inference says all
        ICI) must not spam: the gating question does not arise."""
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        f = jax.shard_map(
            lambda v: lax.psum(v.astype(jnp.int8), "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P(), check_vma=False)
        cj = jax.make_jaxpr(f)(jnp.zeros(256, jnp.float32))
        rep = analyze_jaxpr(cj, mesh=mesh)
        assert not rule_hits(rep, "compressed-collective-link-mismatch")

    def test_link_mismatch_silent_on_int8_over_dcn(self):
        """Compressed traffic on the DCN axis is the WANTED deployment —
        must stay silent."""
        mesh, mesh_mod = self._linked_mesh({"data": "dcn"})
        try:
            f = jax.shard_map(
                lambda v: lax.psum(v.astype(jnp.int8)
                                   .astype(jnp.int8), "data"),
                mesh=mesh, in_specs=P(("data", "model"), None),
                out_specs=P(None, None), check_vma=False)
            cj = jax.make_jaxpr(f)(jnp.zeros((4, 8), jnp.float32))
            rep = analyze_jaxpr(cj, mesh=mesh)
            assert not rule_hits(rep,
                                 "compressed-collective-link-mismatch")
        finally:
            mesh_mod._state.links.clear()

    def test_register_rule_plugs_in_and_rejects_dupes(self):
        from paddle_tpu.analysis import rules as arules
        rid = "test-always-fires"

        @arules.register_rule(rid, "info")
        def _always(ctx):
            yield ctx.finding(None, "hello from plugin")

        try:
            rep = analyze(lambda x: x + 1.0, jnp.zeros(2))
            assert len(rule_hits(rep, rid)) == 1
            with pytest.raises(ValueError):
                arules.register_rule(rid, "info")(lambda ctx: iter(()))
            with pytest.raises(ValueError):
                arules.register_rule("x", "fatal")(lambda ctx: iter(()))
        finally:
            del arules.RULES[rid]


# ---------------------------------------------------------------------------
# integration: Program / ParallelTrainer / lint CLI
# ---------------------------------------------------------------------------

class TestProgramIntegration:
    def _program(self):
        from paddle_tpu import static

        def net(x):
            def body(c, _):
                return jnp.tanh(c), None
            out, _ = lax.scan(body, x, None, length=4)
            return jax.jit(lambda v: v * 2.0)(out)

        return static.Program.trace(
            net, static.data("x", [None, 8], "float32"), static_batch=2)

    def test_num_ops_counts_recursively(self):
        prog = self._program()
        assert prog.num_ops() > len(prog._jaxpr.jaxpr.eqns)

    def test_program_analyze_and_repr(self):
        prog = self._program()
        rep = prog.analyze()
        assert rep.num_eqns == prog.num_ops()
        r = repr(prog)
        assert "ops" in r and "errors" in r

    def test_empty_program(self):
        from paddle_tpu import static
        prog = static.Program()
        assert prog.num_ops() == 0
        assert prog.analyze().num_eqns == 0
        assert repr(prog) == "<Program: empty>"


class TestTrainerCompile:
    def _trainer(self):
        paddle.seed(0)
        build_mesh({"data": 1})
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                            nn.Linear(32, 4))
        opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
        from paddle_tpu.distributed.engine import ParallelTrainer
        return ParallelTrainer(
            net, opt, lambda out, y: jnp.mean((out - y) ** 2))

    def test_compile_returns_step_without_running(self):
        tr = self._trainer()
        x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
        y = jax.ShapeDtypeStruct((8, 4), jnp.float32)
        step = tr.compile(x, y)
        assert callable(step)
        assert len(tr._step_cache) == 1

    def test_compile_analyze_reports_clean_step(self):
        tr = self._trainer()
        x = np.zeros((8, 16), np.float32)
        y = np.zeros((8, 4), np.float32)
        step, rep = tr.compile(x, y, analyze=True)
        assert callable(step)
        assert rep.ok, rep.to_text()      # shipped step: zero errors
        assert rep.cost.matmul_flops > 0  # fwd+bwd matmuls priced
        # params/opt donated by the step's donate_argnums: no warning
        assert not rule_hits(rep, "non-donated-large-arg")

    def test_compile_shares_step_cache_with_train(self):
        tr = self._trainer()
        x = np.zeros((8, 16), np.float32)
        y = np.zeros((8, 4), np.float32)
        step = tr.compile(x, y)
        tr.train_step(x, y)
        assert len(tr._step_cache) == 1  # train reused the staged step
        assert tr._step_cache[next(iter(tr._step_cache))] is step


@pytest.mark.parametrize("model", ["gpt"])
def test_lint_program_smoke(model):
    """The tier-1 CI wrapper: lint_program --smoke on the bench model
    family must exit 0 (no error findings) and emit a JSON report with
    a populated cost table."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_program.py"),
         "--smoke", "--json", "--model", model],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout.strip().splitlines()[-1])[model]
    assert rep["ok"] is True
    assert rep["counts"]["error"] == 0
    assert 1 <= len(rep["cost"]["top"]) <= 10
    assert rep["cost"]["matmul_flops"] > 0
    assert rep["num_eqns"] > 100  # recursed through the jitted step
