"""BENCHMARK.json meets the benchmark's contract as far as a file can
show, and every name in it leads to its files."""
import copy
import json
import os
import re

import pytest

from benchmark import manifest

MAN = manifest.Manifest()
DOC = MAN.doc
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")


def test_every_name_resolves_to_its_files():
    assert MAN.problems() == []


def test_keys_and_limits_of_the_contract():
    assert sorted(DOC) == sorted(["command", "paths", "run_seconds",
                                  "configs", "workloads", "end_to_end",
                                  "per_layer"])
    size = os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024
    assert 1 <= len(DOC["paths"]) <= 16 and len(DOC["command"]) <= 32
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    assert 1 <= len(DOC["configs"]) <= 24 and 2 <= len(DOC["workloads"]) <= 24
    assert 1 <= len(DOC["end_to_end"]) <= 16
    assert 1 <= len(DOC["per_layer"]) <= 128
    # the command names no file outside paths
    files = [a for a in DOC["command"] if "/" in a]
    assert files and all(
        any(f.startswith(p + "/") for p in DOC["paths"]) for f in files)
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in DOC["workloads"])
    assert len(four) <= max(1, len(DOC["workloads"]) // 4)
    for group in ("configs", "workloads"):
        assert all(len(x["why"]) <= 200 for x in DOC[group])
    for c in DOC["configs"]:
        assert any(c["file"].startswith(p + "/") for p in DOC["paths"])
        assert c["source"].startswith("https://")


def test_metrics_of_the_contract():
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert manifest.UNIT.match(m["unit"]), m
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in DOC["per_layer"]:
        assert "bound" not in m and m["layer"] and m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in DOC["workloads"]:
        cell = MAN.cell(w["name"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


@pytest.mark.parametrize("name", sorted(
    x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer")
    for x in DOC[g]))
def test_names_are_plain(name):
    assert NAME.match(name)


def test_files_under_paths_have_plain_names():
    plain = re.compile(r"^[A-Za-z0-9_./-]+$")
    for path in DOC["paths"]:
        for base, dirs, files in os.walk(os.path.join(manifest.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), manifest.ROOT)
                assert plain.match(rel), rel


def test_configuration_files_state_what_reduced_says():
    for c in DOC["configs"]:
        cfg = MAN.config(c["name"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(c["reduced"]) <= set(cfg), "a reduced key the file lacks"
        # no width is ever reduced
        assert not any(k.endswith(("_dim", "_rank")) or k in (
            "n_embd", "n_inner", "n_head") for k in c["reduced"])


def problems_of(doc, tmp_path):
    """The manifest check over a doctored BENCHMARK.json beside the real
    files."""
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    man = manifest.Manifest(root=str(tmp_path))
    man.root = manifest.ROOT        # files are looked up in the checkout
    return man.problems()


def test_the_check_is_not_vacuous(tmp_path):
    doc = copy.deepcopy(DOC)
    doc["per_layer"][0]["moves"] = "no_such_metric"
    doc["per_layer"].append(dict(doc["per_layer"][1], name="no_reader_file"))
    doc["workloads"].append(dict(doc["workloads"][0], name="no.such.cell",
                                 traffic="other"))
    doc["workloads"][1]["chips"] = 4
    doc["end_to_end"][1]["unit"] = "share of peak"
    found = "\n".join(problems_of(doc, tmp_path))
    assert "no_such_metric" in found
    assert "layer_metrics/no_reader_file.json" in found
    assert "workloads/no.such.cell.json" in found
    assert "BENCHMARK.json says 4" in found
    assert "unit 'share of peak'" in found


def test_unknown_device_kind_is_an_error_not_a_default():
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(manifest.ManifestError):
        manifest.peaks("TPU v9")
