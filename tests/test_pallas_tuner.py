"""Search-based Pallas autotuner (ISSUE 6): tuning-DB round-trip, shape
bucketing, overlay precedence, corrupt-DB resilience, trace-time config
resolution (+ telemetry labels) and the ``pallas-config-untuned``
analysis rule."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import telemetry
from paddle_tpu.analysis import analyze
from paddle_tpu.ops.pallas import tuner
from paddle_tpu.telemetry.metrics import Registry

# a key the shipped seed DB is known to hold (interpret-validated)
SEED_FLASH_DIMS = {"d": 64, "sq": 512, "sk": 512}
SEED_CE_DIMS = {"h": 64, "v": 512, "t": 128}


@pytest.fixture(autouse=True)
def _fresh_db_cache(tmp_path, monkeypatch):
    # point the overlay at an (absent) per-test file so a developer's
    # real ~/.cache overlay can't leak into assertions
    monkeypatch.setenv("PADDLE_TPU_TUNING_DB",
                       str(tmp_path / "overlay.json"))
    tuner.clear_cache()
    yield
    tuner.clear_cache()


class TestBucketing:
    def test_shape_bucket_next_pow2_with_floor(self):
        assert tuner.shape_bucket(1) == 128
        assert tuner.shape_bucket(128) == 128
        assert tuner.shape_bucket(129) == 256
        assert tuner.shape_bucket(512) == 512
        assert tuner.shape_bucket(513) == 1024

    def test_flash_dims_bucket_seq_not_head(self):
        assert tuner.flash_dims(64, 300, 511) == \
            {"d": 64, "sq": 512, "sk": 512}

    def test_flash_dims_small_sq_stays_exact(self):
        # decode-shaped calls (sq = 1..8) must NOT collapse into the 128
        # prefill bucket — their tuned configs resolve independently
        assert tuner.flash_dims(64, 1, 256) == \
            {"d": 64, "sq": 1, "sk": 256}
        assert tuner.flash_dims(64, 8, 256)["sq"] == 8
        assert tuner.flash_dims(64, 128, 256)["sq"] == 128  # unchanged
        assert tuner.flash_dims(64, 130, 256)["sq"] == 256

    def test_paged_dims_page_exact_capacity_bucketed(self):
        from paddle_tpu.ops.pallas.paged_attention import paged_dims
        assert paged_dims(32, 16, 16) == {"d": 32, "ps": 16, "sk": 256}
        assert paged_dims(32, 16, 8) == {"d": 32, "ps": 16, "sk": 128}

    def test_ce_dims_bucket_tokens_not_vocab(self):
        assert tuner.ce_dims(64, 500, 200) == {"h": 64, "v": 500, "t": 256}

    def test_make_key_sorts_dims(self):
        k = tuner.make_key("flash_attention", "any", jnp.float32,
                           {"sq": 512, "d": 64, "sk": 512})
        assert k == "flash_attention|any|float32|d64,sk512,sq512"


class TestTuningDB:
    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "db.json")
        db = tuner.TuningDB(path=p)
        entry = {"config": {"block_q": 128, "block_k": 128},
                 "kernel": "flash_attention", "device": "any",
                 "dtype": "float32", "dims": {"d": 64, "sq": 128,
                                              "sk": 128},
                 "mean_us": None, "validated": "interpret", "swept": 1}
        db.put("k1", entry)
        db.save()
        back = tuner.TuningDB.load(p)
        assert len(back) == 1
        assert back.lookup("k1") == entry
        with open(p) as f:
            raw = json.load(f)
        assert raw["version"] == 1

    def test_missing_file_is_empty(self, tmp_path):
        db = tuner.TuningDB.load(str(tmp_path / "nope.json"))
        assert len(db) == 0

    def test_corrupt_file_warns_and_is_empty(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        with pytest.warns(UserWarning, match="unreadable"):
            db = tuner.TuningDB.load(str(p))
        assert len(db) == 0

    def test_wrong_schema_is_empty(self, tmp_path):
        p = tmp_path / "list.json"
        p.write_text("[1, 2, 3]")
        with pytest.warns(UserWarning):
            assert len(tuner.TuningDB.load(str(p))) == 0

    def test_overlay_wins_per_key(self, tmp_path, monkeypatch):
        seed_key = tuner.make_key("flash_attention", tuner.GENERIC_DEVICE,
                                  jnp.float32, SEED_FLASH_DIMS)
        assert tuner.get_db().lookup(seed_key) is not None  # shipped seed
        over = tuner.TuningDB()
        over.put(seed_key, {"config": {"block_q": 128, "block_k": 128}})
        over.save(os.environ["PADDLE_TPU_TUNING_DB"])
        tuner.clear_cache()
        merged = tuner.get_db()
        assert merged.lookup(seed_key)["config"]["block_q"] == 128
        # other seed entries survive the merge
        ce_key = tuner.make_key("fused_ce", tuner.GENERIC_DEVICE,
                                jnp.float32, SEED_CE_DIMS)
        assert merged.lookup(ce_key) is not None


class TestResolve:
    def _registry(self):
        prev = telemetry.get_registry()
        reg = Registry()
        telemetry._set_registry(reg)
        telemetry.enable()
        return prev, reg

    def _restore(self, prev):
        telemetry.disable()
        telemetry._set_registry(prev)

    def test_seed_hit_miss_and_fallback_counted(self):
        prev, reg = self._registry()
        try:
            cfg, src = tuner.resolve(
                "flash_attention", jnp.float32, SEED_FLASH_DIMS,
                {"block_q": 256, "block_k": 512})
            assert src == "db" and set(cfg) == {"block_q", "block_k"}
            # bf16 has no seed entry -> defaults
            cfg2, src2 = tuner.resolve(
                "flash_attention", jnp.bfloat16, SEED_FLASH_DIMS,
                {"block_q": 256, "block_k": 512})
            assert src2 == "default"
            assert cfg2 == {"block_q": 256, "block_k": 512}
            tuner.record_fallback("flash_attention")
            c = reg.get("pallas_config_resolved_total")
            for source in ("db", "default", "fallback"):
                assert c.value(kernel="flash_attention", source=source) == 1
        finally:
            self._restore(prev)

    def test_exact_device_beats_generic(self, monkeypatch):
        over = tuner.TuningDB()
        key = tuner.make_key("flash_attention", tuner.device_kind(),
                             jnp.float32, SEED_FLASH_DIMS)
        over.put(key, {"config": {"block_q": 128, "block_k": 128}})
        over.save(os.environ["PADDLE_TPU_TUNING_DB"])
        tuner.clear_cache()
        cfg, src = tuner.resolve("flash_attention", jnp.float32,
                                 SEED_FLASH_DIMS, {"block_q": 256,
                                                   "block_k": 512})
        assert (src, cfg["block_q"]) == ("db", 128)

    def test_resolution_happens_off_telemetry_too(self):
        assert not telemetry.enabled()
        cfg, src = tuner.resolve("fused_ce", jnp.float32, SEED_CE_DIMS,
                                 {"block_tokens": 256, "block_vocab": 1024})
        assert src == "db"


class TestTuneSweep:
    def test_smoke_sweep_persists_db(self, tmp_path):
        """The acceptance path: a CPU tuner run validates candidates in
        interpret mode and persists a DB with null timings."""
        p = str(tmp_path / "tuned.json")
        db = tuner.tune(tuner._suite("smoke"), db_path=p, iters=1,
                        device=tuner.GENERIC_DEVICE)
        assert os.path.exists(p) and len(db) == 2
        for entry in db.entries.values():
            assert entry["device"] == tuner.GENERIC_DEVICE
            assert entry["validated"] == "interpret"
            assert entry["mean_us"] is None
            assert entry["swept"] >= 1

    @pytest.mark.parametrize("kernel", ["flash_attention", "fused_ce"])
    def test_timing_loop_runs_a_smoke_case(self, kernel):
        """On the CPU ``tune`` validates and does not time, so the
        timing closures a TPU sweep runs are walked here: the smoke
        suite's case of each kernel, interpreted, one timed call."""
        (dims, dtype), = [(d, t) for k, d, t in tuner._suite("smoke")
                          if k == kernel]
        if kernel == "flash_attention":
            dt = tuner._time_flash(
                tuner.flash_candidates(dims["sq"], dims["sk"])[0],
                dims["b"], dims["h"], dims["d"], dims["sq"], dims["sk"],
                dtype, True, 1)
        else:
            dt = tuner._time_ce(
                tuner.ce_candidates(dims["t"], dims["v"], dims["h"],
                                    dtype)[0],
                dims["t"], dims["h"], dims["v"], dtype, True, 1)
        assert dt > 0

    def test_tune_merges_into_existing_db(self, tmp_path):
        p = str(tmp_path / "tuned.json")
        pre = tuner.TuningDB(path=p)
        pre.put("keep|me", {"config": {"x": 1}})
        pre.save()
        db = tuner.tune([("fused_ce", {"h": 64, "v": 512, "t": 128},
                          jnp.float32)], db_path=p, iters=1)
        assert db.lookup("keep|me") is not None
        assert len(db) == 2


class TestAnalysisRule:
    def _flash(self, d=64, s=256):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, s, 1, d), jnp.float32)
        return jax.make_jaxpr(
            lambda a: flash_attention(a, a, a, interpret=True))(q)

    def _findings(self, closed):
        rep = analyze(closed, rule_ids=["pallas-config-untuned"])
        return [f for f in rep.findings if f.rule == "pallas-config-untuned"]

    def test_silent_when_db_has_entry(self):
        assert self._findings(self._flash(d=64, s=256)) == []

    def test_fires_on_untuned_shape(self):
        fs = self._findings(self._flash(d=128, s=256))
        assert len(fs) == 1
        assert fs[0].severity == "warning"
        assert "flash_attention" in fs[0].message
        assert "d128" in fs[0].message

    @pytest.mark.parametrize("h, h_kv, d, seq, row", [
        (12, 12, 64, 1024, "d64,sk1024,sq1024"),    # two heads a lane block
        (25, 25, 64, 1024, "d64,sk1024,sq1024"),    # a zero head padded on
        (6, 1, 128, 4096, "d128,sk4096,sq4096"),    # grouped, one a block
    ])
    def test_traced_kernels_map_to_their_row(self, h, h_kv, d, seq, row):
        """All three kernels' traced calls, operands (batch x lane blocks,
        seq, lanes) with a row a head in the statistics, give the bucket
        the call resolved (``flash_attention.dims_of_call``): the two rows
        the benchmark's cells take."""
        from paddle_tpu.analysis.walker import walk
        from paddle_tpu.ops.pallas.flash_attention import (KERNEL_NAMES,
                                                           flash_attention)
        q = jax.ShapeDtypeStruct((2, seq, h, d), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((2, seq, h_kv, d), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, kv, kv)
        seen = {}
        for site in walk(jaxpr):
            if site.eqn.primitive.name != "pallas_call":
                continue
            key, entry = tuner.entry_for_traced_call(
                site.eqn.params["name"], site.eqn)
            seen[site.eqn.params["name"]] = key
            assert entry["config"] == {"block_q": 1024, "block_k": 1024}
        assert sorted(seen) == sorted(KERNEL_NAMES)
        assert all(key.endswith("|bfloat16|" + row) for key in seen.values())

    def test_fused_ce_untuned_vocab_fires(self):
        from paddle_tpu.ops.pallas.fused_ce import fused_lm_ce
        rs = np.random.RandomState(1)
        hid = jnp.asarray(rs.randn(128, 32), jnp.float32)
        w = jnp.asarray(rs.randn(32, 300) * 0.05, jnp.float32)
        y = jnp.asarray(rs.randint(0, 300, 128).astype("i4"))
        closed = jax.make_jaxpr(
            lambda a, b: fused_lm_ce(a, b, y, interpret=True))(hid, w)
        fs = self._findings(closed)
        assert len(fs) == 1 and "fused_ce" in fs[0].message

    def test_fused_ce_tuned_is_silent(self):
        from paddle_tpu.ops.pallas.fused_ce import fused_lm_ce
        rs = np.random.RandomState(2)
        hid = jnp.asarray(rs.randn(128, 64), jnp.float32)
        w = jnp.asarray(rs.randn(64, 512) * 0.05, jnp.float32)
        y = jnp.asarray(rs.randint(0, 512, 128).astype("i4"))
        closed = jax.make_jaxpr(
            lambda a, b: fused_lm_ce(a, b, y, interpret=True))(hid, w)
        assert self._findings(closed) == []

    def _paged(self, d=32, ps=16, pages=16, pool=64):
        from paddle_tpu.ops.pallas.paged_attention import \
            paged_decode_attention
        rs = np.random.RandomState(3)
        q = jnp.asarray(rs.randn(2, 1, 2, d), jnp.float32)
        kp = jnp.asarray(rs.randn(pool, 2, ps, d), jnp.float32)
        tb = jnp.zeros((2, pages), jnp.int32)
        ln = jnp.asarray([ps, 2 * ps], jnp.int32)
        return jax.make_jaxpr(
            lambda q, kp, vp: paged_decode_attention(
                q, kp, vp, tb, ln, kernel="pallas",
                interpret=True))(q, kp, kp)

    def test_paged_decode_tuned_is_silent(self):
        # the shipped seed DB carries the bench_serving decode buckets
        assert self._findings(self._paged(d=32, ps=16, pages=16)) == []
        assert self._findings(self._paged(d=32, ps=16, pages=8)) == []

    def test_paged_decode_untuned_shape_fires(self):
        fs = self._findings(self._paged(d=128, ps=16, pages=16))
        assert len(fs) == 1
        assert "paged_attention" in fs[0].message
        assert "d128" in fs[0].message


class TestPagedTuneCase:
    def test_decode_sweep_validates_and_records(self, tmp_path):
        """Interpret-mode sweep of one decode case: both q_pad
        candidates validate against the XLA gather baseline, the entry
        lands with mean_us null (no TPU to time on)."""
        key, entry = tuner.tune_case(
            "paged_attention",
            {"b": 2, "h": 2, "d": 32, "ps": 8, "pages": 4}, jnp.float32)
        assert key.startswith("paged_attention|")
        assert entry is not None and entry["swept"] == 2
        assert entry["validated"] == "interpret"
        assert entry["mean_us"] is None
        assert entry["config"]["q_pad"] in (8, 16)
        assert entry["dims"] == {"d": 32, "ps": 8, "sk": 128}
