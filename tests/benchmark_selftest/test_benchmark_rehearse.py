"""The harness end to end without a chip: every cell's own code walks
through at toy size on the CPU (the four-chip cell on four of the
suite's host devices), a rehearsal never looks like a result, a run
without a TPU prints no metric, and the traffic is a function of the
seed."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import manifest, rehearse, traffic_gen
from paddle_tpu.distributed import mesh as mesh_mod

MAN = manifest.Manifest()


@pytest.fixture
def keep_mesh():
    before = mesh_mod.get_mesh()
    yield
    mesh_mod.set_mesh(before)


@pytest.mark.parametrize("cell", sorted(MAN.workloads))
def test_cell_walks_through_at_toy_size(cell, keep_mesh):
    line = rehearse.walk(cell, seed=1)
    checks = {d["check"]: d for d in line["details"]
              if d.get("event") == "check"}
    assert line["walked_through"], checks
    assert line["rehearsal"] is True and line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] == line["counts"]["steps"]
    assert {"losses_fall", "compiles_in_window", "reference"} <= set(checks)
    if MAN.workloads[cell]["chips"] > 1:
        assert {"replicas_equal", "one_chip_forward"} <= set(checks)
        assert checks["replicas_equal"]["devices"] == 4
    # counts and comparisons only: nothing a clock measured, no metric
    text = json.dumps(line)
    assert "metrics" not in line
    assert not any(k in text for k in ('"t":', "window_s", "tokens_per_s\":",
                                       "setup_s\":"))


def test_without_a_tpu_the_command_fails_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = MAN.doc["command"] + ["--workload", "gpt2-small.seq1024", "--seed",
                                "0", "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    out = subprocess.run(cmd, cwd=manifest.ROOT, env=env, text=True,
                         capture_output=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU chip" in out.stderr


def test_unknown_workload_is_named_in_the_error():
    with pytest.raises(manifest.ManifestError, match="no workload"):
        MAN.cell("gpt9.seq1")


MIX = MAN.cell("gpt2-small.seq1024")["traffic"]


def small_pool(seed, **over):
    return traffic_gen.make_pool(dict(MIX, seq=64, pool_batches=8, **over),
                                 500, 499, rows=16, seed=seed)


def test_traffic_is_a_function_of_the_seed():
    a, b, c = small_pool(7), small_pool(7), small_pool(8)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    ids, labels = a
    assert ids.shape == labels.shape == (8, 16, 64) and ids.dtype == np.int32
    flat_ids, flat_labels = ids.reshape(-1), labels.reshape(-1)
    assert np.array_equal(flat_ids[1:], flat_labels[:-1])  # the next token
    assert 0 <= ids.min() and ids.max() <= 499


def test_traffic_has_the_structure_its_file_states():
    ids, _ = small_pool(3, doc_length_median=20, doc_length_min=2)
    stream = ids.reshape(-1)
    # documents: about one EOS per mean document length
    per_eos = len(stream) / (stream == 499).sum()
    assert 20 < per_eos < 20 * np.exp(0.5) * 1.6
    # the first-order rule: the commonest follower of a token follows it
    # about half the time (0.5 + the unigram's own share)
    top = np.bincount(stream).argmax()
    after = stream[1:][stream[:-1] == top]
    assert 0.4 < np.bincount(after).max() / len(after) < 0.75
    # Zipf: the commonest token far above the uniform share of 1/500
    assert np.bincount(stream).max() / len(stream) > 0.03


def test_traffic_file_must_be_complete():
    with pytest.raises(KeyError, match="zipf_exponent"):
        traffic_gen.make_pool({"seq": 8, "pool_batches": 1}, 50, 49, 1, 0)
