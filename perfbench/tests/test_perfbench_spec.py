"""Cells, configurations, traffic mixes and metric readers are found by
name: a new one is new files plus new entries, with no edit to a file
that is there."""
import json
import os
import shutil

from perfbench import spec
from perfbench.measures import Run

ROOT = os.path.dirname(spec.HERE)


def load():
    return spec.Benchmark.load(ROOT)


def test_every_cell_finds_its_files_and_readers():
    bench = load()
    names = [c["name"] for c in bench.data["configs"]]
    for cell in bench.cells.values():
        assert cell.config in names
        cfg = bench.config(cell)
        assert cfg["name"] == cell.config
        assert spec.reference(cfg).logit_gaps
        mix = bench.traffic(cell)
        assert mix["arrival"]["process"] in ("poisson", "backlog")
        e2e = bench.end_to_end_for(cell)
        assert "setup_s" in [m.name for m in e2e] and len(e2e) >= 2
        per = bench.per_layer_for(cell)
        assert per
        for m in e2e + per:
            assert callable(bench.reader(m.name))
        for m in per:
            assert m.moves in [x.name for x in e2e]


def test_an_added_config_traffic_and_metric_are_discovered(tmp_path):
    base = tmp_path / "perfbench"
    shutil.copytree(spec.HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    data = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    cfg = json.loads((base / "configs" / "smollm-360m.json").read_text())
    cfg["name"] = "smollm-360m-extra"
    (base / "configs" / "smollm-360m-extra.json").write_text(json.dumps(cfg))
    (base / "traffic" / "burst.json").write_text(json.dumps({
        "arrival": {"process": "backlog", "count": 3},
        "classes": [{"name": "q", "share": 1, "prompt": {"source": "corpus"},
                     "output": {"source": "corpus"}}]}))
    (base / "metrics" / "extra_count.py").write_text(
        "def read(run):\n    return float(len(run.records))\n")
    data["configs"].append({"name": "smollm-360m-extra", "source": "x",
                            "file": "perfbench/configs/smollm-360m-extra.json",
                            "reduced": [], "why": "test"})
    data["workloads"].append({"name": "extra-cell",
                              "config": "smollm-360m-extra",
                              "traffic": "burst", "chips": 1, "why": "test"})
    data["end_to_end"].append({"name": "extra_count", "unit": "1",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["extra-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    bench = spec.Benchmark.load(str(tmp_path), base=str(base))
    cell = bench.cell("extra-cell")
    assert bench.config(cell)["name"] == "smollm-360m-extra"
    assert bench.traffic(cell)["arrival"]["count"] == 3
    e2e = [m.name for m in bench.end_to_end_for(cell)]
    assert sorted(e2e) == ["extra_count", "setup_s"]
    run = Run(cfg={}, records=[1, 2], t0=0, window_s=1, end_t=1,
              open_loop=False, setup_s=1)
    assert bench.reader("extra_count")(run) == 2.0
    # a split name with no file of its own takes its stem's reader
    (base / "metrics" / "extra_count.train.py").write_text(
        "def read(run):\n    return -1.0\n")
    assert bench.reader("extra_count.serve")(run) == 2.0
    assert bench.reader("extra_count.train")(run) == -1.0
    # the cells already there keep their metrics
    assert [m.name for m in bench.end_to_end_for(
        bench.cell("glm4-9b-decode"))] == ["tok_s", "setup_s"]
