"""The registry finds every part by name; new parts are new files."""

from __future__ import annotations

import hashlib
import json
import re

from _tiny import CHIP, REPO, make_copy

from registry import Registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_every_part_is_found_by_name():
    reg = Registry()
    for c in reg.spec["configs"]:
        cfg = reg.config(c["name"])
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    for w in reg.spec["workloads"]:
        reg.config(w["config"])
        assert "bad_answers" in reg.cell(w["name"])["limits"]
        kind = reg.traffic(w["traffic"])["kind"]
        assert hasattr(reg.module("traffic", kind), "Driver")
        e2e = {m["name"] for m in reg.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert reg.per_layer(w["name"]), w["name"]
    for m in reg.spec["per_layer"]:
        assert callable(reg.module("metrics", m["name"]).read)
        for cell in m.get("workloads", []):
            assert m["moves"] in {e["name"] for e in reg.end_to_end(cell)}
    for name in ("tree_sep_update", "lsh_bucket_accept"):
        mod = reg.module("kernels", name)
        assert callable(mod.cost) and mod.TRACE_NAME


def test_names_and_units_use_allowed_characters():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(spec) == TOP_KEYS
    assert spec["command"][1].startswith("benchmarks/chip/")
    assert all(PATH.match(p) and (REPO / p).is_dir() for p in spec["paths"])
    names = []
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names += [c["name"], *c["reduced"]]
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    bad = [n for n in names if not NAME.match(n)]
    assert not bad, bad
    for path in CHIP.rglob("*"):
        if "__pycache__" not in path.parts:
            assert PATH.match(str(path.relative_to(REPO))), path


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_added_config_cell_and_metric_are_picked_up(tmp_path):
    make_copy(tmp_path)
    root = tmp_path / "benchmarks" / "chip"
    before = _digests(CHIP)
    (root / "metrics" / "tiny_answers.reseed.py").write_text(
        "def read(run):\n    return float(len(run.window['answers']))\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "tiny_answers.reseed", "unit": "answers", "better": "higher",
        "source": "program_counter", "layer": "solve program",
        "moves": "cost_ratio", "workloads": ["tiny.reseed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    reg = Registry(tmp_path)
    assert reg.config("tiny")["n"] == 2048
    assert reg.traffic(reg.workload("tiny.reseed")["traffic"])
    assert [m["name"] for m in reg.per_layer("tiny.reseed")] == [
        "tiny_answers.reseed"]
    run = type("Run", (), {"window": {"answers": [1, 2, 3]}})()
    assert reg.module("metrics", "tiny_answers.reseed").read(run) == 3.0
    # Every file the copy had before the additions is unchanged.
    after = _digests(root)
    assert {p: after[p] for p in before} == before
