"""BENCHMARK.json: names, units and text within the contract's charset and
lengths, every entry's keys, and every file each entry is found by."""
import json
import os
import re

import pytest

from chipbench_testlib import CHIP, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(B["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p and not p.startswith("/")
               for p in B["paths"])
    assert len(B["command"]) <= 32 and all(line(w) for w in B["command"])
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    # 2 + 14 runs per cell at run_seconds + 60, 180 s more per cell to
    # compile and 1200 s spare fit 43200 s with 24 cells
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_configs():
    assert 1 <= len(B["configs"]) <= 24
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(B["paths"][0] + "/")
        assert len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]
        assert os.path.exists(os.path.join(CHIP, conf["reference"]))
    assert len({c["file"] for c in B["configs"]}) == len(B["configs"])


def test_workloads():
    names = {c["name"] for c in B["configs"]}
    assert 1 <= len(B["workloads"]) <= 24
    pairs = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4) and line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(CHIP, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(CHIP, "loadgen",
                                           mix["kind"] + ".py"))
        with open(os.path.join(CHIP, "limits", w["name"] + ".json")) as f:
            assert set(json.load(f)) >= {"max_logit_gap", "requests_failed"}
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(
        1, len(B["workloads"]) // 2)
    assert {w["config"] for w in B["workloads"]} == names


def _metrics():
    return B["end_to_end"] + B["per_layer"]


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"] for m in B["end_to_end"]}
    assert len({m["name"] for m in _metrics()}) == len(_metrics())
    assert "setup_s" in e2e and 1 <= len(B["end_to_end"]) <= 16
    for m in _metrics():
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m["workloads"]) <= cells if "workloads" in m else True
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line(m["layer"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(CHIP, "metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            kernel = m["name"][:-len("_roofline")]
            assert os.path.exists(os.path.join(CHIP, "kernels",
                                               kernel + ".py"))


@pytest.mark.parametrize("cell", sorted(w["name"] for w in B["workloads"]))
def test_every_cell_reports_what_it_must(cell):
    def has(m):
        return cell in m.get("workloads", [cell])
    e2e = [m["name"] for m in B["end_to_end"] if has(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    per = [m for m in B["per_layer"] if has(m)]
    assert per and all(m["moves"] in e2e for m in per)
