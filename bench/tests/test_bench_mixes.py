"""Each traffic mix serves the cells of one chip count, and each per-layer
metric that lists its cells names cells of the benchmark."""

import json
import os

from benchlib import cells

ROOT = os.path.dirname(cells.BENCH_DIR)
SPEC = cells.load_benchmark(ROOT)


def test_each_mix_serves_one_chip_count():
    mixes = sorted(f[:-len(".json")] for f in
                   os.listdir(os.path.join(cells.BENCH_DIR, "mixes")))
    chips = {}
    for w in SPEC["workloads"]:
        chips.setdefault(w["traffic"], set()).add(w["chips"])
    assert sorted(chips) == mixes
    for traffic, counts in chips.items():
        with open(os.path.join(cells.BENCH_DIR, "mixes",
                               traffic + ".json")) as f:
            mesh = json.load(f)["mesh"]
        assert [int(c) for c in mesh] == sorted(counts), traffic


def test_per_layer_metrics_name_reported_cells():
    names = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert set(m.get("workloads", names)) <= names, m["name"]
