"""BENCHMARK.json against the contract's schema and character rules."""
import json
import os
import re

import pytest

from _bench_common import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51 and isinstance(
        spec["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_command_and_paths(spec):
    assert 1 <= len(spec["paths"]) <= 16 and len(spec["command"]) <= 32
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in spec["command"]:
        assert line_ok(word) and not word.startswith("/") and ".." not in word
    files = [w for w in spec["command"] if "/" in w]
    assert all(any(w.startswith(p + "/") for p in spec["paths"])
               for w in files)


def test_configs(spec):
    assert 1 <= len(spec["configs"]) <= 24
    used = {w["config"] for w in spec["workloads"]}
    files = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        for key in c["reduced"]:
            assert NAME.match(key) and key in held
            # never a width
            assert not re.search(
                r"(_dim$|_rank$|_size$|head_|expansion|per_tok)", key)
        assert held["reduced"] == c["reduced"]
    assert len({c["name"] for c in spec["configs"]}) == len(spec["configs"])


def test_workloads(spec):
    cells = spec["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    cfgs = {c["name"] for c in spec["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert line_ok(w["why"])
        assert any(os.path.exists(os.path.join(
            ROOT, p, "traffic", w["traffic"] + ".json"))
            for p in spec["paths"])
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e, per = spec["end_to_end"], spec["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    by_name = {m["name"]: m for m in e2e}
    assert by_name["setup_s"]["bound"] <= 0.1
    assert "workloads" not in by_name["setup_s"]
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in per:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and line_ok(m["layer"])
        assert m["moves"] in by_name
        moved = by_name[m["moves"]].get("workloads", sorted(cells))
        assert set(m.get("workloads", moved)) <= set(moved)
        assert any(os.path.exists(os.path.join(
            ROOT, p, "metrics", m["name"] + ".py")) for p in spec["paths"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in e2e + per:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    for c in cells:
        assert any(c in m.get("workloads", [c]) for m in e2e
                   if m["name"] != "setup_s")
        assert any(c in m.get("workloads", [c]) for m in per)
    # a kernel's roofline stands beside the whole step's mfu, same metric
    for m in per:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in per)
