"""Cells, configurations, traffic kinds and per-layer metrics are found
by name: adding one takes new files and entries, no edit."""

import json
import shutil
from pathlib import Path

import pytest

from bench import harness

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def copy(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def _add(root: Path, section: str, entry: dict) -> None:
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench[section].append(entry)
    path.write_text(json.dumps(bench))


def test_every_cell_resolves():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.driver().Driver
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m["name"]).read)


def test_added_files_make_a_new_cell(copy):
    cfg = json.loads((copy / "bench/configs/paper-n256.json").read_text())
    cfg["workers"] = 64
    (copy / "bench/configs/paper-n64.json").write_text(json.dumps(cfg))
    _add(copy, "configs", {"name": "paper-n64", "source": "x",
                           "file": "bench/configs/paper-n64.json",
                           "reduced": ["workers"], "why": "x"})
    traffic = {"driver": "probe", "limits": {}}
    (copy / "bench/workloads/tiny-probe.json").write_text(
        json.dumps(traffic))
    (copy / "bench/drivers/probe.py").write_text(
        "class Driver:\n    def __init__(self, ctx):\n        self.n = 1\n")
    _add(copy, "end_to_end", {"name": "probes_per_s", "unit": "1/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["probe-n64"]})
    (copy / "bench/metrics/probe_share.py").write_text(
        "def read(ctx):\n    return 0.5\n")
    _add(copy, "per_layer", {"name": "probe_share", "unit": "%",
                             "better": "higher", "source": "device_trace",
                             "layer": "device", "moves": "probes_per_s"})
    _add(copy, "workloads", {"name": "probe-n64", "config": "paper-n64",
                             "traffic": "tiny-probe", "chips": 1,
                             "why": "x"})
    cell = harness.resolve("probe-n64", root=copy)
    assert cell.config["workers"] == 64
    assert cell.driver().Driver(None).n == 1
    assert sorted(m["name"] for m in cell.end_to_end) == ["probes_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["probe_share"]
    assert cell.metric_reader("probe_share").read(None) == 0.5
    # the cells already there are untouched by the new entries
    old = harness.resolve("sweep-table1", root=copy)
    assert "probe_share" not in {m["name"] for m in old.per_layer}


def test_a_split_metric_falls_back_to_its_quantity(copy):
    (copy / "bench/metrics/probe_share.py").write_text(
        "def read(ctx):\n    return 0.25\n")
    cell = harness.resolve("train-gc", root=copy)
    assert cell.metric_reader("probe_share.serve").read(None) == 0.25
    (copy / "bench/metrics/probe_share.serve.py").write_text(
        "def read(ctx):\n    return 0.75\n")
    assert cell.metric_reader("probe_share.serve").read(None) == 0.75


def test_unknown_device_kind_has_no_peaks():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
