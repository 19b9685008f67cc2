"""Every name in BENCHMARK.json resolves to its files; and a cell, a
configuration and a per-layer metric can each be added as new files plus
entries, editing no file that is there."""

import json
import os
import shutil

import pytest

from benchmark.manifest import NAME_RE, ROOT, UNIT_RE, Manifest, ManifestError


def test_benchmark_json_meets_the_static_contract():
    m = Manifest(ROOT).validate()
    doc = m.doc
    assert doc["command"] == ["python3", "benchmark/run.py"] and doc["paths"] == ["benchmark"]
    assert 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # the whole check has to fit 43,200 s with the full 24 cells
    assert (2 + 14 * 24) * (doc["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    for entry in doc["end_to_end"] + doc["per_layer"]:
        assert NAME_RE.match(entry["name"]) and UNIT_RE.match(entry["unit"]), entry
    for c in doc["configs"]:
        assert c["file"].startswith("benchmark/") and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16 and all(NAME_RE.match(k) for k in c["reduced"])
        banned = ("_dim", "_rank", "hidden", "intermediate", "d_model", "n_embd", "d_ff", "head")
        assert not any(b in k for k in c["reduced"] for b in banned), "a width may never be reduced"
    kernels = [p["name"] for p in doc["per_layer"] if "roofline" in p["name"]]
    assert kernels and all(n.endswith("_roofline") for n in kernels)
    for p in doc["per_layer"]:
        if p["name"].endswith("_roofline"):
            assert p["unit"] == "%"


def test_every_name_resolves_to_its_files():
    m = Manifest(ROOT)
    for name, entry in m.cells.items():
        cell = m.cell(name)
        config = m.config(entry["config"])
        # a cell on a mesh expects no kernel: every model-layer gate is closed there (ROADMAP A6)
        assert cell["method"] in ("ppo", "ilql") and cell["tolerances"]
        assert isinstance(cell["expect_kernels"], list) and (cell["expect_kernels"] or "mesh" in cell)
        assert config["model_arch"]["d_model"] and hasattr(m.reference(config["reference"]), "forward")
        assert "setup_s" in [x["name"] for x in m.metrics_for(name, "end_to_end")]
        assert len(m.metrics_for(name, "end_to_end")) >= 2 and m.metrics_for(name, "per_layer")
    for name in m.per_layer:
        spec = m.layer_metric(name)
        assert callable(m.reader(spec["reader"]))
    assert m.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ManifestError):
        m.peaks("TPU v9")
    # every file under configs/, workloads/ and layer_metrics/ is named in BENCHMARK.json
    for folder, names in (("workloads", m.cells), ("layer_metrics", m.per_layer)):
        assert sorted(f[:-5] for f in os.listdir(m.path(folder))) == sorted(names)
    assert sorted(os.listdir(m.path("configs"))) == sorted(os.path.basename(c["file"]) for c in m.doc["configs"])


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    root = str(tmp_path)
    ignore = shutil.ignore_patterns("__pycache__", "tests")
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"), ignore=ignore)
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()

    def write(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    config = json.load(open(os.path.join(root, "benchmark/configs/gptneo-1.3b.json")))
    config.update(name="gptneo-2.7b", source="https://huggingface.co/EleutherAI/gpt-neo-2.7B/blob/main/config.json")
    write("configs/gptneo-2.7b.json", config)
    cell = json.load(open(os.path.join(root, "benchmark/workloads/gptneo1.3b.ilql-256.json")))
    cell.update(name="gptneo2.7b.ilql-128", config="gptneo-2.7b", traffic="ilql-128")
    write("workloads/gptneo2.7b.ilql-128.json", cell)
    metric = json.load(open(os.path.join(root, "benchmark/layer_metrics/train_step_device_ms.json")))
    metric.update(name="polyak_sync_device_ms", programs="^jit__polyak_sync$", workloads=["gptneo2.7b.ilql-128"])
    write("layer_metrics/polyak_sync_device_ms.json", metric)

    doc["configs"].append({"name": "gptneo-2.7b", "source": config["source"], "file": "benchmark/configs/gptneo-2.7b.json",
                           "reduced": ["num_layers_unfrozen"], "why": "a test's configuration"})
    doc["workloads"].append({"name": "gptneo2.7b.ilql-128", "config": "gptneo-2.7b", "traffic": "ilql-128", "chips": 1,
                             "why": "a test's cell"})
    doc["per_layer"].append({k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves", "workloads")})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    m = Manifest(root).validate()
    assert m.cell("gptneo2.7b.ilql-128")["config"] == "gptneo-2.7b"
    names = [x["name"] for x in m.metrics_for("gptneo2.7b.ilql-128", "per_layer")]
    assert "polyak_sync_device_ms" in names and "rollout_s_per_iter" not in names
    assert "polyak_sync_device_ms" not in [x["name"] for x in m.metrics_for("gptneo1.3b.ilql-256", "per_layer")]
    assert callable(m.reader(m.layer_metric("polyak_sync_device_ms")["reader"]))
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


def test_a_broken_entry_is_refused(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    doc["per_layer"][0]["unit"] = "tokens per second"
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    with pytest.raises(ManifestError):
        Manifest(root).validate()
