"""``BENCHMARK.json`` and the files its names stand for.

The harness is driven by data: a cell, a configuration, a traffic mix,
a per-layer metric is an entry in ``BENCHMARK.json`` plus files of its
own, found by name. ``Manifest`` loads them and ``problems()`` says
what does not resolve, for the harness (which refuses to start) and
for the self-test.

    workloads[].name   -> benchmark/workloads/<name>.json
    workloads[].config -> configs[].file (benchmark/configs/<config>.json)
                          its "family" -> benchmark/families/<family>.py
                                          benchmark/reference/<REFERENCE>.py
    workloads[].traffic-> benchmark/traffic/<traffic>.json
    workload "kind"    -> benchmark/kinds/<kind>.py
    per_layer[].name   -> benchmark/layer_metrics/<name>.json
                          its "reducer" -> benchmark/reducers/<reducer>.py
    device kind        -> benchmark/peaks.json
"""
from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    """A name in BENCHMARK.json that leads to no file, or a file that
    lacks what the harness reads from it."""


def _read(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file: {os.path.relpath(path, ROOT)}") \
            from None
    except ValueError as e:
        raise ManifestError(
            f"{os.path.relpath(path, ROOT)} is not JSON: {e}") from None


def plugin(group: str, name: str):
    """The module ``benchmark/<group>/<name>.py``."""
    if not NAME.match(name) or "." in name:
        raise ManifestError(f"{group} name {name!r} is not a module name")
    path = os.path.join(HERE, group, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(
            f"missing file: {os.path.relpath(path, ROOT)}")
    return importlib.import_module(f"benchmark.{group}.{name}")


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _read(os.path.join(root, "BENCHMARK.json"))
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    # -- one cell's files ----------------------------------------------------
    def cell(self, name: str) -> dict:
        """Everything one run of the cell reads: its entry, its workload
        file, its configuration, its traffic mix, the metrics it reports."""
        if name not in self.workloads:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json; there are "
                f"{sorted(self.workloads)}")
        entry = self.workloads[name]
        workload = _read(os.path.join(HERE, "workloads", name + ".json"))
        config = self.config(entry["config"])
        traffic = _read(os.path.join(HERE, "traffic",
                                     entry["traffic"] + ".json"))
        for key, want in (("config", entry["config"]),
                          ("traffic", entry["traffic"]),
                          ("chips", entry["chips"])):
            if workload.get(key) != want:
                raise ManifestError(
                    f"workloads/{name}.json says {key}="
                    f"{workload.get(key)!r}, BENCHMARK.json says {want!r}")
        return {"name": name, "entry": entry, "workload": workload,
                "config": config, "traffic": traffic,
                "end_to_end": self._metrics_of(self.end_to_end, name),
                "per_layer": self._metrics_of(self.per_layer, name)}

    def config(self, name: str) -> dict:
        if name not in self.configs:
            raise ManifestError(f"no configuration {name!r} in "
                                f"BENCHMARK.json")
        return _read(os.path.join(self.root, self.configs[name]["file"]))

    @staticmethod
    def _metrics_of(table: dict, cell: str) -> list:
        return [m for m in table.values()
                if "workloads" not in m or cell in m["workloads"]]

    def layer_metric(self, name: str) -> dict:
        return _read(os.path.join(HERE, "layer_metrics", name + ".json"))

    # -- the check -----------------------------------------------------------
    def problems(self) -> list:
        """Every name that does not resolve, as sentences; empty when
        the manifest and its files agree."""
        out = []

        def attempt(what, fn):
            try:
                return fn()
            except (ManifestError, KeyError, ImportError) as e:
                out.append(f"{what}: {type(e).__name__}: {e}")

        names = [self.doc["configs"], self.doc["workloads"],
                 self.doc["end_to_end"], self.doc["per_layer"]]
        flat = [x["name"] for group in names for x in group]
        out += [f"name {n!r} is not of the form {NAME.pattern}"
                for n in flat if not NAME.match(n)]
        out += [f"name {n!r} is used more than once"
                for n in sorted(set(flat)) if flat.count(n) > 1]

        out += [f"metric {m['name']}: unit {m.get('unit')!r} is not of "
                f"the form {UNIT.pattern}"
                for m in self.doc["end_to_end"] + self.doc["per_layer"]
                if not UNIT.match(str(m.get("unit", "")))]

        for path in self.doc["paths"]:
            if not os.path.isdir(os.path.join(self.root, path)):
                out.append(f"path {path!r} is not a directory")
        used = {w["config"] for w in self.doc["workloads"]}
        out += [f"configuration {c!r} is used by no workload"
                for c in self.configs if c not in used]

        for name, c in self.configs.items():
            cfg = attempt(f"configuration {name}", lambda: self.config(name))
            if not cfg:
                continue
            if sorted(cfg.get("reduced", [])) != sorted(c["reduced"]):
                out.append(f"configuration {name}: 'reduced' differs "
                           f"between BENCHMARK.json and {c['file']}")
            fam = attempt(f"configuration {name}",
                          lambda: plugin("families", cfg["family"]))
            if fam:
                attempt(f"family {cfg['family']}",
                        lambda: plugin("reference", fam.REFERENCE))

        for name in self.workloads:
            cell = attempt(f"workload {name}", lambda: self.cell(name))
            if not cell:
                continue
            attempt(f"workload {name}",
                    lambda: plugin("kinds", cell["workload"]["kind"]))
            e2e = [m["name"] for m in cell["end_to_end"]]
            if "setup_s" not in e2e or len(e2e) < 2:
                out.append(f"workload {name} reports {e2e}: setup_s and "
                           f"one more end-to-end metric are required")
            if not cell["per_layer"]:
                out.append(f"workload {name} reports no per-layer metric")

        for name, m in self.end_to_end.items():
            if m["source"] not in ("host_clock", "device_trace"):
                out.append(f"end-to-end metric {name}: source "
                           f"{m['source']!r} is not the benchmark's own")
        for name, m in self.per_layer.items():
            if m["source"] not in SOURCES:
                out.append(f"per-layer metric {name}: unknown source "
                           f"{m['source']!r}")
            if not m.get("layer"):
                out.append(f"per-layer metric {name} names no layer")
            if m.get("moves") not in self.end_to_end:
                out.append(f"per-layer metric {name} moves "
                           f"{m.get('moves')!r}, not an end-to-end metric")
            for cell in m.get("workloads", []):
                if cell not in self.workloads:
                    out.append(f"per-layer metric {name} lists unknown "
                               f"workload {cell!r}")
            spec = attempt(f"per-layer metric {name}",
                           lambda: self.layer_metric(name))
            if not spec:
                continue
            for key in ("layer", "moves"):
                if spec.get(key) != m.get(key):
                    out.append(f"layer_metrics/{name}.json says {key}="
                               f"{spec.get(key)!r}, BENCHMARK.json says "
                               f"{m.get(key)!r}")
            attempt(f"per-layer metric {name}",
                    lambda: plugin("reducers", spec["reducer"]))
        attempt("peaks", lambda: _read(os.path.join(HERE, "peaks.json")))
        return out


def peaks(device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an
    error, never a default."""
    table = _read(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise ManifestError(
            f"no published peak for device kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]
