"""BENCHMARK.json against the benchmark's contract, and every part it
names present under bench/ by name."""
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "bench/run.py"]
    assert M["paths"] == ["bench"]
    assert 1 <= M["run_seconds"] <= 51
    # a full check of 24 cells at this length fits its time
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("bench/")
        data = json.loads((ROOT / c["file"]).read_text())
        for k in c["reduced"]:
            assert NAME.match(k) and k in data
        assert data["reduced"] == c["reduced"]
        assert 1 <= len(c["why"]) <= 200


def test_cells_find_their_files():
    pairs = set()
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert traffic["kind"] in ("route", "serve")
        limits = json.loads((ROOT / "bench" / "workloads"
                             / f"{w['name']}.json").read_text())["limits"]
        assert limits and all(v >= 0 for v in limits.values())
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(M["workloads"]) // 2)


def test_sharded_cell():
    """The 4-chip cell: its configuration splits capacity evenly over
    its shards, one shard a chip, and at most half the cells (or one)
    take 4 chips."""
    cells = {w["name"]: w for w in M["workloads"]}
    configs = {c["name"]: c for c in M["configs"]}
    for w in M["workloads"]:
        conf = configs[w["config"]]["file"]
        db = json.loads((ROOT / conf).read_text())["db"]
        assert db["capacity"] % db["shards"] == 0
        assert db["shards"] in (1, w["chips"])
        assert db["capacity"] - db["headroom"] >= db["fit_prompts"]
    assert cells["route.paper4m.4chip"]["chips"] == 4
    assert cells["route.paper4m.4chip"]["config"] == "eagle-paper-4m-sharded"
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


def test_metric_cells_exist():
    cells = {w["name"] for w in M["workloads"]}
    for e in M["end_to_end"] + M["per_layer"]:
        assert set(e.get("workloads", cells)) <= cells, e["name"]
    layers = {}
    for p in M["per_layer"]:
        assert 1 <= len(p["layer"]) <= 200
        layers.setdefault(p["layer"].lower(), set()).add(p["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def _reports(entry, cell):
    return "workloads" not in entry or cell in entry["workloads"]


def test_metrics():
    e2e = {e["name"]: e for e in M["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(e2e) + [p["name"] for p in M["per_layer"]]
    assert len(names) == len(set(names))
    for e in M["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    for p in M["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"])
        assert p["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert p["moves"] in e2e and p["moves"] != "setup_s"
        reader = ROOT / "bench" / "metrics" / f"{p['name']}.py"
        assert "def read(ctx)" in reader.read_text()
        for cell in p["workloads"]:
            assert _reports(e2e[p["moves"]], cell), (p["name"], cell)
    for w in M["workloads"]:
        cell = w["name"]
        assert sum(_reports(e, cell) for e in M["end_to_end"]
                   if e["name"] != "setup_s") >= 1
        assert any(cell in p["workloads"] for p in M["per_layer"])
