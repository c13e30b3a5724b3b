"""``BENCHMARK.json`` against the rules it is checked by before any run,
and against the files the harness finds by name."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(bench, group, cell):
    return {m["name"] for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]}


def test_keys_and_limits(bench):
    assert sorted(bench) == ["command", "configs", "end_to_end", "paths",
                             "per_layer", "run_seconds", "workloads"]
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, cells // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_names_and_units(bench):
    names = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for w in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key]), w[key]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        names.append(w["name"])
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        names.append(c["name"])
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_metrics(bench):
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    assert set(by_name) == {"train_images_per_s", "decode_tokens_per_s",
                            "ttft_p95_ms", "itl_p95_ms", "setup_s"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert "workloads" not in by_name["setup_s"]


def test_every_cell_reports_enough_and_every_arrow_lands(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cell in cells:
        assert len(_reports(bench, "end_to_end", cell) - {"setup_s"}) >= 1
        assert _reports(bench, "per_layer", cell)
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert m["moves"] in _reports(bench, "end_to_end", cell), \
                (m["name"], cell)
    shares = [m for m in bench["per_layer"]
              if m["name"].endswith("_roofline") or "mfu" in m["name"]]
    assert shares and all(m["unit"] == "%" for m in shares)


def test_files_are_found_by_name(bench):
    paths = bench["paths"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    files = set()
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"] and held["reduced"] == c["reduced"]
        files.add(c["file"])
        for kind in ("configs", "reference"):
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", kind, c["name"] + ".py"))
    assert len(files) == len(bench["configs"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "drivers",
                                           kind + ".py"))
        with open(os.path.join(ROOT, "benchmark", "correct",
                               w["name"] + ".json")) as f:
            limits = json.load(f)["limits"]
        assert limits and all(v >= 0 for v in limits.values())
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in paths:
        for base, _dirs, names in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in base:
                continue
            for n in names:
                assert ok.match(os.path.relpath(os.path.join(base, n), ROOT))
