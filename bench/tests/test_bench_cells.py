"""Every cell of BENCHMARK.json resolves to its files, and a new
configuration, mix or metric is found by its name alone."""

import json
import os
import shutil

import pytest

from benchlib import cells, correct

ROOT = os.path.dirname(cells.BENCH_DIR)
SPEC = cells.load_benchmark(ROOT)


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(workload):
    cell = cells.resolve(workload, ROOT)
    assert cell.chips in (1, 4)
    assert list(cell.mix["mesh"]) == [str(cell.chips)]
    assert set(correct.NUMBERS) <= set(cell.limits)
    for m in cell.per_layer:
        assert callable(cells.metric_reader(cell, m["name"]).read)
    assert cell.module(cell.family).init_weights
    assert cell.mix["step"]["grad_path"] in ("auto", "mlfabric")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_each_pair_of_config_and_traffic_once():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_keeps_its_source(conf):
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == conf["source"]
    assert cfg["reduced"] == conf["reduced"]
    for key in conf["reduced"]:
        assert key in cfg.get("published", {}), key


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(cells.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads(json.dumps(SPEC))
    base = spec["workloads"][0]
    # a configuration: its file of sizes
    cfg = json.loads((bench / "configs" / "qwen2-0.5b.json").read_text())
    cfg["num_hidden_layers"] = 2
    (bench / "configs" / "qwen2-new.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "qwen2-new", "source": cfg["source"],
                            "file": "bench/configs/qwen2-new.json",
                            "reduced": ["num_hidden_layers"], "why": "test"})
    # a traffic mix: a data file
    mix = json.loads((bench / "mixes" / "dp-auto.json").read_text())
    mix["seq_len"] = 4096
    (bench / "mixes" / "long.json").write_text(json.dumps(mix))
    # a per-layer metric: a reader of its own
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec["per_layer"].append({"name": "new_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "tokens_per_s",
                              "workloads": ["qwen2-new.long.1chip"]})
    spec["workloads"].append({"name": "qwen2-new.long.1chip",
                              "config": "qwen2-new", "traffic": "long",
                              "chips": 1, "why": "test"})
    (bench / "limits" / "qwen2-new.long.1chip.json").write_text(
        (bench / "limits" / (base["name"] + ".json")).read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cells.resolve("qwen2-new.long.1chip", str(tmp_path))
    assert cell.config["num_hidden_layers"] == 2
    assert cell.mix["seq_len"] == 4096
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert cells.metric_reader(cell, "new_metric").read(None) == 42.0
    with pytest.raises(KeyError):
        cells.resolve("no.such.cell", str(tmp_path))
