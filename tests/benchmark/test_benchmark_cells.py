"""Each runner end to end at a toy size on the CPU, the result line's
keys, and a cell added as new files and entries only."""
import io
import json
import os
import shutil

import pytest

from benchmarks import harness
from _bench_common import ROOT, SCALE

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device",
             "setup_phases", "observed", "compared"]


@pytest.mark.parametrize("name", sorted(SCALE))
def test_cell_runs_and_is_correct(name):
    cell = harness.Cell(name)
    line = harness.run_cell(cell, 2 ** 31 + 17, 2.0, 0, require_chip=False,
                            scale=SCALE[name])
    assert list(line) == LINE_KEYS            # `compared` comes last
    phases = list(line["setup_phases"].values())
    assert phases == sorted(phases) and len(phases) >= 3
    assert phases[-1] == round(line["metrics"]["setup_s"]["value"], 3)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    out, err = io.StringIO(), io.StringIO()
    harness.print_result(line, out, err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == line
    last = err.getvalue().strip().splitlines()[-len(line["compared"]):]
    assert all(l.startswith("compared ") and "limit" in l for l in last)


def test_no_chip_is_an_error():
    with pytest.raises(harness.BenchmarkError, match="no accelerator"):
        harness.find_devices(1, require_chip=True)
    with pytest.raises(harness.BenchmarkError, match="not in"):
        harness.load_peaks("cpu")
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_large_seed_gives_a_key():
    import jax
    a = harness.seed_key(2 ** 31 + 5)
    b = harness.seed_key(5)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()


def test_a_cell_is_added_as_files_and_entries_only(tmp_path):
    """A later PR's configuration, traffic mix, runner and per-layer
    metric: new files under a directory of its own and new entries in
    BENCHMARK.json; no file that is there is edited."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    new = root / "benchmarks_pr99"
    for sub in ("configs", "traffic", "runners", "metrics"):
        (new / sub).mkdir(parents=True)
    (new / "configs" / "toy.json").write_text(json.dumps(
        {"hidden_size": 8, "reduced": []}))
    (new / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"runner": "toy", "work": 3}))
    (new / "runners" / "toy.py").write_text(
        "class Runner:\n"
        "    def __init__(self, cell, seed, seconds, devices, probe, scale):\n"
        "        self.cell, self.probe = cell, probe\n"
        "    def run(self):\n"
        "        self.probe.window_open(); self.probe.window_close()\n"
        "    def results(self):\n"
        "        return {'end_to_end': {'toy_rate': 7.0}, 'attempted': 3,\n"
        "                'failed': 0, 'facts': {'n': self.cell.traffic['work']}}\n"
        "    def release(self): pass\n"
        "    def check(self):\n"
        "        return [{'name': 'exact', 'value': 0.0, 'limit': 0.0}]\n")
    (new / "metrics" / "toy.count.py").write_text(
        "def read(ctx):\n    return ctx['facts']['n']\n")
    spec["paths"].append("benchmarks_pr99")
    spec["configs"].append({"name": "toy", "source": "none", "reduced": [],
                            "file": "benchmarks_pr99/configs/toy.json",
                            "why": "test"})
    spec["workloads"].append({"name": "toy.cell", "config": "toy",
                              "traffic": "toy-mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "toy_rate", "unit": "x/s",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["toy.cell"]})
    spec["per_layer"].append({"name": "toy.count", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "toy", "moves": "toy_rate",
                              "workloads": ["toy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.Cell("toy.cell", root=str(root))
    assert cell.traffic["work"] == 3 and cell.config["hidden_size"] == 8
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s",
                                                          "toy_rate"]
    assert [m["name"] for m in cell.per_layer] == ["toy.count"]
    line = harness.run_cell(cell, 1, 0.1, 0, require_chip=False)
    assert line["correct"] and line["metrics"]["toy_rate"]["value"] == 7.0
    assert cell.reader("toy.count").read({"facts": {"n": 3}}) == 3
    # the cells that were there still load from the copy
    assert harness.Cell("olmo1b-l8-train", root=str(root)).chips == 1
