"""`BENCHMARK.json` against the form the driver refuses anything outside
of, and against the files it names."""

import importlib.util
import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|expan"
                   r"|_dim$|_rank$|experts_per_tok)")


def load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def line(s, n=200):
    return 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_form():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    assert all(line(w) for w in b["command"]) and len(b["command"]) <= 32
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if WIDTH.search(k)]
        names.add(c["name"])
    cells = {}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert line(w["why"])
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(
            BENCH, "limits", w["name"] + ".json"))
        cells[w["name"]] = w
    assert {w["config"] for w in b["workloads"]} == names
    e2e = {}
    for mt in b["end_to_end"]:
        assert set(mt) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
        assert NAME.match(mt["name"]) and UNIT.match(mt["unit"])
        assert mt["better"] in ("lower", "higher")
        assert mt["source"] in ("host_clock", "device_trace")
        assert 0.01 <= mt["bound"] <= 0.1
        assert set(mt.get("workloads", cells)) <= set(cells)
        e2e[mt["name"]] = mt
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    seen = set(e2e)
    for mt in b["per_layer"]:
        assert set(mt) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
        assert NAME.match(mt["name"]) and UNIT.match(mt["unit"])
        assert mt["name"] not in seen
        seen.add(mt["name"])
        assert mt["source"] in SOURCES and line(mt["layer"])
        assert mt["moves"] in e2e
        reporters = set(e2e[mt["moves"]].get("workloads", cells))
        assert set(mt.get("workloads", cells)) <= reporters
        # every per-layer metric has a reader, found by its name
        import run as harness
        assert callable(harness.reader_of(mt["name"]).read)
    for name in cells:
        mine = [m for m in b["end_to_end"]
                if name in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert [m for m in b["per_layer"]
                if name in m.get("workloads", cells)]


def test_configuration_files_state_their_source_and_cuts():
    b = load()
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["assumed"] and cfg["deployment"]
        assert cfg["precision"] and cfg["control"]
