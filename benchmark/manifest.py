"""BENCHMARK.json and the per-name data files the harness finds from it.

Whatever belongs to one configuration, one cell or one per-layer metric is a
file of its own, found by the name in BENCHMARK.json:

    configs/<config>.json        the sizes as run, source, reduced, assumed
    workloads/<cell>.json        the cell: its traffic, recipe, expectations
    layer_metrics/<metric>.json  reader kind + name patterns of one metric
    readers/<kind>.py            a reader kind (``read(ctx, spec)``)
    references/<name>.py         a plain reference (``forward(...)``)
    counts/<name>.py             a configuration's own count of operations and
                                 bytes (its file's ``"flops": "<name>"``);
                                 a configuration that names none is counted
                                 by flops.py, the dense GPT block

A later PR adds a cell, a configuration or a metric by adding files and
entries; nothing here names one of them. No JAX import in this module.
"""

import importlib
import json
import math
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _load_json(path):
    with open(path) as f:
        return json.load(f)


class Manifest:
    """BENCHMARK.json of `root` plus lookups of the files it names."""

    def __init__(self, root=ROOT):
        self.root = root
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        (self.dir,) = [p for p in self.doc["paths"] if os.path.isfile(os.path.join(root, p, "manifest.py"))]
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    def path(self, *parts):
        return os.path.join(self.root, self.dir, *parts)

    def cell(self, name):
        """The cell's BENCHMARK.json entry merged over its own file."""
        if name not in self.cells:
            raise ManifestError(f"no workload {name!r} in BENCHMARK.json (have {sorted(self.cells)})")
        entry = self.cells[name]
        spec = _load_json(self.path("workloads", f"{name}.json"))
        for key in ("name", "config", "traffic", "chips"):
            if spec.get(key) != entry[key]:
                raise ManifestError(f"workloads/{name}.json: {key}={spec.get(key)!r} != BENCHMARK.json {entry[key]!r}")
        mesh = spec.get("mesh")
        if mesh is not None and not (
                isinstance(mesh, list) and len(mesh) == 4 and all(isinstance(n, int) and n > 0 for n in mesh)
                and math.prod(mesh) == entry["chips"]):
            raise ManifestError(f"workloads/{name}.json: mesh {mesh!r} is not four sizes (dp, fsdp, tp, sp) "
                                f"whose product is the cell's {entry['chips']} chip(s)")
        if spec.get("traced_cycle", "whole") not in ("whole", "train_steps"):
            raise ManifestError(f"workloads/{name}.json: traced_cycle {spec['traced_cycle']!r} is neither "
                                "'whole' (reward call to reward call) nor 'train_steps'")
        return spec

    def config(self, name):
        entry = self.configs[name]
        spec = _load_json(os.path.join(self.root, entry["file"]))
        if spec.get("source") != entry["source"]:
            raise ManifestError(f"{entry['file']}: source differs from BENCHMARK.json")
        missing = [k for k in entry["reduced"] if k not in spec.get("reduced", {})]
        if missing:
            raise ManifestError(f"{entry['file']}: reduced keys {missing} not explained in the file")
        return spec

    def metrics_for(self, cell_name, table):
        """Metrics of `table` ('end_to_end' | 'per_layer') this cell reports."""
        return [m for m in self.doc[table] if cell_name in m.get("workloads", [cell_name])]

    def layer_metric(self, name):
        spec = _load_json(self.path("layer_metrics", f"{name}.json"))
        entry = self.per_layer[name]
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            if spec.get(key) != entry[key]:
                raise ManifestError(f"layer_metrics/{name}.json: {key}={spec.get(key)!r} != BENCHMARK.json {entry[key]!r}")
        return spec

    def reader(self, kind):
        return self._module("readers", kind).read

    def reference(self, name):
        return self._module("references", name)

    def counts(self, name=None):
        """The module the readers take operations and bytes from: a
        configuration's own (`"flops": "<name>"` in its file, counts/<name>.py)
        or, where it names none, flops.py. Either exposes what the readers
        call: `ppo_train_step_flops`, `ilql_train_step_flops`,
        `layer_windows`, `flash_call`, `logprob_head_call`, `least_seconds`."""
        if name is None:
            return importlib.import_module(f"{os.path.basename(self.dir)}.flops")
        return self._module("counts", name)

    def _module(self, package, name):
        if not NAME_RE.match(name) or not os.path.isfile(self.path(package, f"{name}.py")):
            raise ManifestError(f"no {package}/{name}.py")
        return importlib.import_module(f"{os.path.basename(self.dir)}.{package}.{name}")

    def peaks(self, device_kind):
        """Published peaks of one chip; a kind not in the table is an error."""
        table = _load_json(self.path("peaks.json"))
        if device_kind not in table:
            raise ManifestError(f"device_kind {device_kind!r} is not in peaks.json ({sorted(k for k in table if k != 'source')})")
        return table[device_kind]

    def validate(self):
        """The contract's static rules this file can check; raises on the first breach."""
        d = self.doc
        if set(d) != {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}:
            raise ManifestError(f"BENCHMARK.json keys: {sorted(d)}")
        seen = set()
        for table in ("end_to_end", "per_layer"):
            for m in d[table]:
                if m["name"] in seen:
                    raise ManifestError(f"metric name twice: {m['name']}")
                seen.add(m["name"])
                if not NAME_RE.match(m["name"]) or not UNIT_RE.match(m["unit"]):
                    raise ManifestError(f"bad name or unit: {m}")
                if m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
                    raise ManifestError(f"bad better/source: {m}")
                for w in m.get("workloads", []):
                    if w not in self.cells:
                        raise ManifestError(f"{m['name']}: unknown workload {w}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace") or not 0 < m["bound"] <= 0.1:
                raise ManifestError(f"end-to-end metric {m}")
        if "setup_s" not in self.end_to_end:
            raise ManifestError("setup_s missing")
        for m in d["per_layer"]:
            if m["moves"] not in self.end_to_end:
                raise ManifestError(f"{m['name']} moves unknown metric {m['moves']}")
            self.layer_metric(m["name"])
        pairs = set()
        for w in d["workloads"]:
            for key in ("name", "config", "traffic"):
                if not NAME_RE.match(w[key]):
                    raise ManifestError(f"bad {key}: {w[key]!r}")
            if w["config"] not in self.configs or w["chips"] not in (1, 4) or len(w["why"]) > 200:
                raise ManifestError(f"workload {w}")
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"pair twice: {w}")
            pairs.add((w["config"], w["traffic"]))
            self.cell(w["name"])
            for table in ("end_to_end", "per_layer"):
                if not self.metrics_for(w["name"], table):
                    raise ManifestError(f"{w['name']} reports no {table} metric")
        for c in d["configs"]:
            if not NAME_RE.match(c["name"]) or not any(w["config"] == c["name"] for w in d["workloads"]):
                raise ManifestError(f"config {c['name']} unnamed or unused")
            self.config(c["name"])
        return self
