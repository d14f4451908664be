"""BENCHMARK.json against the benchmark's contract, and every name in it
against the file that the harness finds by that name."""
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
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(m):
    return m["end_to_end"] + m["per_layer"]


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    for p in manifest["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    for w in manifest["command"]:
        assert not w.startswith("/") and ".." not in w


def test_names_and_units(manifest):
    names = [c["name"] for c in manifest["configs"]]
    names += [w["name"] for w in manifest["workloads"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in _metrics(manifest):
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for kind in ("configs", "workloads"):
        assert len({x["name"] for x in manifest[kind]}) == len(manifest[kind])
        for x in manifest[kind]:
            assert re.fullmatch(r"[^\n\t]{1,200}", x["why"])
    for c in manifest["configs"]:
        assert re.fullmatch(r"[^\n\t]{1,200}", c["source"])
    assert len({m["name"] for m in _metrics(manifest)}) == len(
        _metrics(manifest))


def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        c = configs[w["config"]]
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", f"{w['traffic']}.json"))
        assert w["chips"] in (1, 4)
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs)


def test_every_metric_has_a_reader(manifest):
    from bench import harness
    for m in _metrics(manifest):
        assert callable(harness.reader(m["name"]))


def test_metrics_name_cells_that_report_what_they_move(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)
    for w in cells:
        reported = [n for n, m in e2e.items() if w in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(w in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_layers_are_named_alike(manifest):
    by_layer = {}
    for m in manifest["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_at_most_half_the_cells_on_four_chips(manifest):
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)
