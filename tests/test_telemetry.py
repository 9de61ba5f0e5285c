"""Telemetry (ISSUE 3): registry, exporters, scope, and the built-in
instrumentation — including the acceptance e2e: ``telemetry.scope()``
around a 3-step CPU-mesh GPT loop producing JSONL + Prometheus text +
a chrome trace whose counter track aligns with the profiler's host
``train_step`` ranges."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, telemetry
from paddle_tpu.distributed.engine import ParallelTrainer
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.telemetry.export import JsonlSink, prometheus_text
from paddle_tpu.telemetry.metrics import Registry


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_labels_and_total(self):
        reg = Registry()
        c = reg.counter("reqs_total", "requests")
        c.inc(policy="int8")
        c.inc(2, policy="fp32")
        assert c.value(policy="int8") == 1.0
        assert c.value(policy="fp32") == 2.0
        assert c.value() == 3.0                 # no labels -> family sum
        assert reg.counter("reqs_total") is c   # get-or-create

    def test_gauge_set_inc_dec(self):
        reg = Registry()
        g = reg.gauge("depth")
        g.set(5.0)
        g.inc(2)
        g.dec()
        assert g.value() == 6.0

    def test_histogram_buckets_and_mean(self):
        reg = Registry()
        h = reg.histogram("lat", "latency", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v, op="save")
        assert h.count(op="save") == 4
        assert h.count() == 4
        assert h.value() == pytest.approx(55.55 / 4)

    def test_kind_mismatch_raises(self):
        reg = Registry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")
        with pytest.raises(TypeError):
            reg.histogram("x")

    def test_marks_record_only_when_enabled(self):
        reg = Registry()
        c = reg.counter("n")
        c.inc()
        assert reg.marks() == []
        reg.marks_enabled = True
        c.inc()
        (t, name, key, value), = reg.marks()
        assert name == "n" and key == () and value == 2.0 and t > 0

    def test_reset_drops_everything(self):
        reg = Registry()
        reg.marks_enabled = True
        reg.counter("n").inc()
        reg.reset()
        assert reg.get("n") is None and reg.marks() == []


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

class TestPrometheusText:
    def test_counter_and_gauge_lines(self):
        reg = Registry()
        reg.counter("reqs_total", "req count").inc(3, policy="int8")
        reg.gauge("mfu").set(0.5)
        text = prometheus_text(reg)
        assert "# HELP reqs_total req count" in text
        assert "# TYPE reqs_total counter" in text
        assert 'reqs_total{policy="int8"} 3' in text
        assert "# TYPE mfu gauge" in text
        assert "mfu 0.5" in text

    def test_histogram_cumulative_buckets(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v, op="save")
        text = prometheus_text(reg)
        assert 'lat_bucket{op="save",le="0.1"} 1' in text
        assert 'lat_bucket{op="save",le="1"} 2' in text
        assert 'lat_bucket{op="save",le="10"} 3' in text
        assert 'lat_bucket{op="save",le="+Inf"} 4' in text
        assert 'lat_count{op="save"} 4' in text

    def test_label_escaping(self):
        reg = Registry()
        reg.counter("c").inc(path='a"b\\c')
        text = prometheus_text(reg)
        assert 'path="a\\"b\\\\c"' in text

    def test_label_newline_escaping(self):
        reg = Registry()
        reg.counter("c").inc(path="a\nb")
        text = prometheus_text(reg)
        assert 'path="a\\nb"' in text
        # the exposition stays line-oriented: no raw newline inside a
        # label value
        assert all(line.count('"') % 2 == 0
                   for line in text.splitlines())

    def test_empty_histogram_scrapes_consistently(self):
        # a declared-but-unobserved histogram must still expose the
        # +Inf bucket, _sum and _count (at 0) — scrapers reject a TYPE
        # histogram with no samples
        reg = Registry()
        reg.histogram("lat", "latency", buckets=(0.1, 1.0))
        text = prometheus_text(reg)
        assert 'lat_bucket{le="+Inf"} 0' in text
        assert "lat_sum 0" in text
        assert "lat_count 0" in text

    def test_help_keeps_double_quotes_escapes_newline(self):
        # HELP text escapes ONLY backslash and newline; a double quote
        # is legal and escaping it corrupts the exposition
        reg = Registry()
        reg.counter("c", 'fraction of "bad" rows\nsecond line')
        text = prometheus_text(reg)
        assert '# HELP c fraction of "bad" rows\\nsecond line' in text

    def test_nonfinite_histogram_bound_not_duplicated(self):
        import math as _math
        reg = Registry()
        h = reg.histogram("lat", buckets=(0.1, _math.inf))
        h.observe(0.05)
        text = prometheus_text(reg)
        # the user-supplied inf bound must not render as le="inf"
        # alongside the synthesized +Inf line
        assert text.count('le="+Inf"') == 1
        assert 'le="inf"' not in text


def test_jsonl_sink_append_and_close(tmp_path):
    p = tmp_path / "events.jsonl"
    sink = JsonlSink(str(p))
    sink.emit({"event": "a", "n": 1})
    sink.emit({"event": "b"})
    sink.close()
    sink.emit({"event": "dropped"})  # after close: silently ignored
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert [e["event"] for e in lines] == ["a", "b"]


# ---------------------------------------------------------------------------
# scope: registry swap + artifacts + restoration
# ---------------------------------------------------------------------------

class TestScope:
    def test_swaps_and_restores_globals(self):
        prev_reg = telemetry.get_registry()
        assert not telemetry.enabled()
        with telemetry.scope(profile=False) as tel:
            assert telemetry.enabled()
            assert telemetry.get_registry() is tel.registry
            assert tel.registry is not prev_reg
            telemetry.counter("inside").inc()
        assert not telemetry.enabled()
        assert telemetry.get_registry() is prev_reg
        assert prev_reg.get("inside") is None

    def test_run_dir_artifacts(self, tmp_path):
        run = tmp_path / "run"
        with telemetry.scope(str(run), profile=False) as tel:
            telemetry.counter("n_total", "things").inc(2)
            telemetry.emit("custom", foo=1)
        assert "n_total 2" in (run / "metrics.prom").read_text()
        events = [json.loads(l)
                  for l in (run / "events.jsonl").read_text().splitlines()]
        assert events[0]["event"] == "scope_start"
        assert any(e["event"] == "custom" and e["foo"] == 1 for e in events)
        summary = events[-1]
        assert summary["event"] == "summary"
        assert summary["metrics"]["n_total"]["series"][""] == 2.0
        trace = json.loads((run / "trace.json").read_text())
        counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
        assert counters and all(e["ts"] >= 0 for e in counters)
        assert tel.registry.get("n_total").value() == 2.0


# ---------------------------------------------------------------------------
# instrumentation sites
# ---------------------------------------------------------------------------

def _mlp_trainer(grad_sync="fp32", ndata=2):
    paddle.seed(7)
    mesh = build_mesh({"data": ndata})

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(16, 32)
            self.l2 = nn.Linear(32, 4)

        def forward(self, x):
            return self.l2(nn.functional.relu(self.l1(x)))

    model = MLP()
    opt = paddle.optimizer.Momentum(0.05, momentum=0.9,
                                    parameters=model.parameters())
    return ParallelTrainer(model, opt,
                           lambda out, y: jnp.mean((out - y) ** 2),
                           mesh=mesh, grad_sync=grad_sync,
                           grad_sync_block=64)


def _xy(batch):
    rng = np.random.RandomState(3)
    return (rng.randn(batch, 16).astype(np.float32),
            rng.randn(batch, 4).astype(np.float32))


def test_disabled_trainer_records_nothing():
    assert not telemetry.enabled()
    prev = telemetry.get_registry()
    reg = Registry()
    telemetry._set_registry(reg)
    try:
        tr = _mlp_trainer()
        tr.train_step(*_xy(8))
    finally:
        telemetry._set_registry(prev)
    assert reg.get("step_time_seconds") is None
    assert reg.get("recompiles_total") is None
    assert reg.get("grad_sync_bytes_total") is None


def test_recompile_counter_stage_and_shape_miss():
    with telemetry.scope(profile=False) as tel:
        tr = _mlp_trainer()
        for _ in range(3):
            tr.train_step(*_xy(8))
        c = tel.registry.get("recompiles_total")
        n0 = c.value()
        assert n0 == 1                       # the initial staging, alone
        # new batch shape: same staged structure, but jit compiles a new
        # executable — caught by the cache-size probe, counted as recompile
        tr.train_step(*_xy(4))
        assert c.value() > n0
        assert tel.registry.get("step_time_seconds").count() == 4
        assert tel.registry.get("stage_time_seconds").count() >= 1


def test_grad_sync_wire_metrics_int8():
    with telemetry.scope(profile=False) as tel:
        tr = _mlp_trainer(grad_sync="int8")
        for _ in range(2):
            tr.train_step(*_xy(8))
        reg = tel.registry
        wire = reg.get("grad_sync_bytes_total")
        assert wire is not None and \
            wire.value(policy="int8", link="ici", bucket="0") > 0
        # int8 wire bytes are a fraction of fp32's
        assert reg.get("grad_sync_compression_x").value() > 1.0
        # error-feedback residual exists and was normed
        assert reg.get("grad_sync_residual_norm").value() > 0


def test_compile_records_histogram():
    with telemetry.scope(profile=False) as tel:
        tr = _mlp_trainer()
        X, Y = _xy(8)
        tr.compile(X, Y)
        assert tel.registry.get("compile_time_seconds").count() == 1


def test_dataloader_fetch_histogram():
    from paddle_tpu.io import DataLoader, Dataset

    class DS(Dataset):
        def __getitem__(self, i):
            return np.full(4, i, np.float32), np.int64(i % 2)

        def __len__(self):
            return 8

    with telemetry.scope(profile=False) as tel:
        batches = list(DataLoader(DS(), batch_size=2))
    assert len(batches) == 4
    assert tel.registry.get("dataloader_fetch_seconds").count() == 4
    assert tel.registry.get("dataloader_batches_total").value() == 4
    # disabled -> the plain iterator, nothing recorded
    prev = telemetry.get_registry()
    reg = Registry()
    telemetry._set_registry(reg)
    try:
        list(DataLoader(DS(), batch_size=2))
    finally:
        telemetry._set_registry(prev)
    assert reg.get("dataloader_fetch_seconds") is None


def test_checkpoint_metrics(tmp_path):
    from paddle_tpu.distributed.checkpoint import (load_checkpoint,
                                                   save_checkpoint)
    state = {"w": np.arange(16, dtype=np.float32)}
    with telemetry.scope(profile=False) as tel:
        save_checkpoint(str(tmp_path / "ck"), state)
        out = load_checkpoint(str(tmp_path / "ck"))
    reg = tel.registry
    assert reg.get("checkpoint_save_seconds").count() == 1
    assert reg.get("checkpoint_restore_seconds").count() == 1
    assert reg.get("checkpoint_bytes_total").value(op="save") == 64.0
    assert reg.get("checkpoint_bytes_total").value(op="restore") == 64.0
    np.testing.assert_array_equal(np.asarray(out["w"]), state["w"])


def test_monitor_bridges_onto_registry():
    with telemetry.scope(profile=False) as tel:
        g = paddle.monitor.stat("STAT_tel_bridge")
        g.reset()
        g.increase(5)
        assert g.get() == 5
        assert tel.registry.get("STAT_tel_bridge").value() == 5.0
        assert "STAT_tel_bridge 5" in telemetry.prometheus_text(tel.registry)
    # outside the scope the same StatValue writes to the restored registry
    g.increase(2)
    assert telemetry.get_registry().get("STAT_tel_bridge").value() == 2.0


def test_hapi_telemetry_callback_folds_logs():
    from paddle_tpu.hapi.callbacks import Callback, TelemetryCallback
    from paddle_tpu.io import Dataset

    class DS(Dataset):
        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            return (rng.randn(4).astype(np.float32),
                    np.asarray(i % 2, dtype=np.int64))

        def __len__(self):
            return 8

    seen = []

    class Probe(Callback):
        def on_train_batch_end(self, step, logs=None):
            seen.append(dict(logs or {}))

    net = nn.Linear(4, 2)
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.SGD(0.1, parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    with telemetry.scope(profile=False) as tel:
        model.fit(DS(), epochs=1, batch_size=4, verbose=0,
                  callbacks=[TelemetryCallback(), Probe()])
    assert seen and all("step_time" in logs and logs["step_time"] > 0
                        for logs in seen)
    assert tel.registry.get("step_time_seconds").count() == len(seen)


# ---------------------------------------------------------------------------
# acceptance e2e (ISSUE 3): scope around a short GPT train loop
# ---------------------------------------------------------------------------

def test_scope_e2e_gpt_cpu_mesh(tmp_path):
    from paddle_tpu.text.models import GPTForPretraining
    paddle.seed(0)
    mesh = build_mesh({"data": 2})
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (4, 16)).astype("int32")
    labels = rng.randint(0, 128, (4, 16)).astype("int32")
    run = tmp_path / "run"

    with telemetry.scope(str(run)) as tel:
        model = GPTForPretraining(
            tensor_parallel=False, vocab_size=128, hidden_size=32,
            num_layers=1, num_heads=2, max_position_embeddings=16,
            attn_dropout=0.0, hidden_dropout=0.0)
        opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
        tr = ParallelTrainer(
            model, opt,
            lambda logits, lbl: nn.functional.cross_entropy(logits, lbl),
            mesh=mesh, grad_sync="int8", grad_sync_block=64)
        for _ in range(3):
            loss = tr.train_step(ids, labels)
        assert np.isfinite(float(loss))
        reg = tel.registry

    # -- registry values ----------------------------------------------------
    assert reg.get("step_time_seconds").count() == 3
    assert reg.get("recompiles_total").value() >= 1
    assert reg.get("mfu").value() > 0
    assert reg.get("tokens_per_sec").value() > 0
    assert reg.get("grad_sync_bytes_total").value(policy="int8",
                                                  link="ici",
                                                  bucket="0") > 0
    assert reg.get("peak_live_bytes").value() > 0

    # -- prometheus text ----------------------------------------------------
    prom = (run / "metrics.prom").read_text()
    for name in ("step_time_seconds", "recompiles_total", "mfu",
                 "grad_sync_bytes_total"):
        assert name in prom, f"{name} missing from metrics.prom"
    assert "step_time_seconds_count 3" in prom

    # -- JSONL event log ----------------------------------------------------
    events = [json.loads(l)
              for l in (run / "events.jsonl").read_text().splitlines()]
    steps = [e for e in events if e["event"] == "step"]
    assert len(steps) == 3
    assert all(e["step_time"] > 0 for e in steps)
    assert any("mfu" in e for e in steps)
    assert events[0]["event"] == "scope_start"
    assert events[-1]["event"] == "summary"

    # -- chrome trace: counter track aligns with host train_step ranges ----
    trace = json.loads((run / "trace.json").read_text())
    evs = trace["traceEvents"]
    assert all(e["ts"] >= 0 for e in evs), "negative chrome-trace ts"
    xs = [e for e in evs if e["ph"] == "X" and e["name"] == "train_step"]
    cs = [e for e in evs if e["ph"] == "C"
          and e["name"] == "step_time_seconds"]
    assert len(xs) == 3 and len(cs) == 3
    lo = min(e["ts"] for e in xs)
    hi = max(e["ts"] + e["dur"] for e in xs)
    for c in cs:  # each mark lands just after its step's host range (µs)
        assert lo <= c["ts"] <= hi + 1e6, (c["ts"], lo, hi)
