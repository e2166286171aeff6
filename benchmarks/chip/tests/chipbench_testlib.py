"""Loads the benchmark's harness for its tests, and cuts a cell to a size
a CPU test run holds."""
from __future__ import annotations

import importlib.util
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))


def harness():
    """``run.py`` as a module (it registers the ``chipbench`` package)."""
    if "chipbench_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "chipbench_run", os.path.join(CHIP, "run.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chipbench_run"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["chipbench_run"]


def tiny_cell(name: str):
    """The cell's BENCHMARK.json entries and files, with every width and
    length cut to a CPU test's size."""
    run = harness()
    bench = run.read_json("..", "..", "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == name)
    conf = run.read_json("configs", cell["config"] + ".json")
    m = conf["model"]
    m.update(n_layers=2 * len(m["layer_pattern"]), d_model=64,
             vocab_size=500)
    m["ssm"].update(d_state=16, headdim=16, chunk=32)
    mix = run.read_json("traffic", cell["traffic"] + ".json")
    mix["engine"]["max_seq"] = 1024
    mix["check"]["ref_block"] = 256
    if mix["kind"] == "closed":
        mix["prompt"].update(lo=100, hi=600)
        mix["output"] = {"fixed": 20}
    else:
        mix["rate_per_s"] = 4.0
        mix["prompt"]["hi"] = 500
        mix["output"]["hi"] = 200
    return bench, cell, conf, mix
