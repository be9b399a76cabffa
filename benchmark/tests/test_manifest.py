"""Lint of BENCHMARK.json against the parts of the contract a file can show."""
import os
import re

import pytest

from benchmark.lib import manifest as M

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return M.manifest()


def test_keys_and_names(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in man[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert any(m["name"] == "setup_s" for m in man["end_to_end"])


def test_files_exist_and_are_found_by_name(man):
    files = set()
    for c in man["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in man["paths"]))
        assert os.path.exists(os.path.join(M.ROOT, c["file"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    used = set()
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = M.Cell(w["name"], man)
        assert cell.traffic["driver"]
        assert os.path.exists(os.path.join(
            M.BENCH, "drivers", cell.traffic["driver"] + ".py"))
        used.add(w["config"])
    assert used == {c["name"] for c in man["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 4)


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for m in man["per_layer"]:
        read, _ = M.metric_reader(m["name"])
        assert callable(read)
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        cells = m.get("workloads") or [
            w["name"] for w in man["workloads"]
            if "workloads" not in moved or w["name"] in moved["workloads"]]
        for c in cells:
            assert "workloads" not in moved or c in moved["workloads"], \
                (m["name"], c)
    for w in man["workloads"]:
        cell = M.Cell(w["name"], man)
        reported = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer()
    layers = {}
    for m in man["per_layer"]:
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] \
                or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], []).append(m["name"])
    perf = open(os.path.join(M.ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's layers lack {layer!r}"
