"""BENCHMARK.json keeps to its format, and every name in it finds its
files: configuration, job, limits and one reader per per-layer metric."""
import importlib
import json
import os
import re

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def _bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_format():
    b = _bench()
    assert set(b) == TOP
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        names.add(c["name"])
    used = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert w["config"] in names
        used.add(w["config"])
    assert used == names
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert _line(m["layer"]) and NAME.match(m["name"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_name_finds_its_files():
    b = _bench()
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"])
        # a cell on several chips also holds its replicas equal
        assert set(cell.limits["limits"]) == {"loss_gap", "grad_gap",
                                              "delta_gap"} | (
            {"replica_gap"} if cell.chips > 1 else set())
        assert cell.per_layer and cell.end_to_end
        assert importlib.import_module(f"reference.{cell.family}")
    for m in b["per_layer"]:
        assert callable(importlib.import_module(f"metrics.{m['name']}").read)
