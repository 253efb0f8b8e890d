"""BENCHMARK.json against the contract's character and size rules, and
every cell's files found by name."""

import os
import re

import pytest

from benchmark.lib import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json("BENCHMARK.json")


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32 and all(one_line(w) for w in manifest["command"])
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p for p in manifest["paths"])
    # the limit that fits the full 24 cells into a check
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entries(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert PATH.match(c["file"]) and c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4) and one_line(w["why"])
    pairs = [(w["config"], w["traffic"], w["chips"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(len(manifest["workloads"]) // 2, 1)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert one_line(m["layer"]) and m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
    assert {c["name"] for c in manifest["configs"]} == {w["config"] for w in manifest["workloads"]}


def test_every_cell_resolves_to_files(manifest):
    """Configuration, traffic mix, generators, references and per-layer
    readers are all found by the names the data files give."""
    for w in manifest["workloads"]:
        _, cell, config, mix = harness.resolve_cell(w["name"])
        assert config["name"] == cell["config"] and mix["name"] == cell["traffic"]
        entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
        assert sorted(config["reduced"]) == sorted(entry["reduced"]) and config["source"] == entry["source"]
        for t in config["tables"]:
            module, _, func = t["generator"].rpartition(".")
            assert callable(getattr(harness.load_by_name("generators", module), func))
        for tpl in mix["templates"].values():
            ref = harness.load_by_name("references", tpl["reference"])
            assert callable(ref.reference) and callable(ref.compare)
    for m in manifest["per_layer"]:
        assert callable(harness.load_by_name("layer_metrics", m["name"]).read)
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)


def test_perf_md_states_the_bounds_of_the_manifest(manifest):
    """PERF.md section 2 is where a bound is argued; what it argues for
    has to be what the driver holds later PRs to."""
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        text = f.read()
    section_2 = text[text.index("\n## 2."):text.index("\n## 3.")]
    stated = dict(re.findall(r"^\| `(\w+)` \|[^|]*\| ([0-9.]+) \|", section_2, re.M))
    assert stated == {m["name"]: str(m["bound"]) for m in manifest["end_to_end"]}
    assert f"`run_seconds` {manifest['run_seconds']})" in text
    with open(os.path.join(harness.ROOT, "CHANGES.md")) as f:  # the newest benchmark PR's line
        line = [ln for ln in f if re.match(r"- PR \d+ \[benchmark\]", ln)][-1]
    for m in manifest["end_to_end"]:
        assert f"`{m['name']}` bound {m['bound']}" in line


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.resolve_cell("no_such_cell")
    with pytest.raises(FileNotFoundError):
        harness.load_by_name("layer_metrics", "no_such_metric")
