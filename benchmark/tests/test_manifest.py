"""``BENCHMARK.json`` against the contract's limits that can be checked here,
and against the files it names."""

import dataclasses
import json
import os
import re

import pytest

import manifest
import readers
import traffic
from fuzzyheavyhitters_tpu.utils.config import Config

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def bench():
    return manifest.benchmark()


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert len(bench["command"]) <= 32 and all(_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_just_the_contracts_keys(bench, section):
    names = [e["name"] for e in bench[section]]
    assert len(names) == len(set(names))
    for e in bench[section]:
        assert set(e) - {"workloads"} == KEYS[section], e["name"]
        assert NAME.match(e["name"])
        for key in ("why", "layer", "source"):
            assert key not in e or _line(e[key]), (e["name"], key)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        # every cell that lists the metric reports the end-to-end metric it moves
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells)), m["name"]


def test_every_cell_finds_its_files_by_name(bench):
    conf_files = [c["file"] for c in bench["configs"]]
    assert len(conf_files) == len(set(conf_files))
    used = set()
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        cell = manifest.cell(w["name"])
        used.add(w["config"])
        # the configuration as it is run: every Config field, and no other
        assert set(cell.config["config"]) == {f.name for f in dataclasses.fields(Config)}
        Config(**cell.config["config"])
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert PATH.match(conf["file"]) and conf["file"].startswith("benchmark/")
        assert cell.config["source"] == conf["source"]
        assert cell.config["reduced"] == conf["reduced"] and len(conf["reduced"]) <= 16
        assert all(NAME.match(k) and k in cell.config["published"] for k in conf["reduced"])
        # what is never cut
        for key in ("data_len", "n_dims", "ball_size", "num_sites", "zipf_exponent", "distribution"):
            assert cell.config["config"][key] == cell.config["published"][key], key
        assert cell.config["guarantees"] and cell.config["lane_evidence"]
        traffic.plan(cell.mix)
        assert cell.config["config"]["distribution"] in traffic.DISTRIBUTIONS
        assert hasattr(manifest.reference(cell.config), "frontiers")
        # at least setup_s, one more end-to-end metric and one per-layer metric
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert used == {c["name"] for c in bench["configs"]}


def test_metric_files_agree_with_the_manifest(bench):
    on_disk = {f[:-len(".json")] for f in os.listdir(os.path.join(manifest.ROOT, "benchmark", "metrics"))}
    names = {m["name"] for m in bench["per_layer"]}
    assert on_disk <= names
    # ``<metric>.<tag>`` with no file of its own is read as ``<metric>``'s file says
    assert {n.rsplit(".", 1)[0] for n in names - on_disk} <= on_disk
    for m in bench["per_layer"]:
        spec = manifest.metric_file(m)
        assert {k: spec.get(k) for k in m} == m
        assert spec["reader"] in readers.READERS and spec["what"]


def test_a_missing_file_or_name_is_refused(bench, tmp_path, monkeypatch):
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.cell("no-such-cell")
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(manifest.ManifestError, match="cannot read"):
        manifest.cell(bench["workloads"][0]["name"])


def test_an_unknown_reader_is_refused():
    with pytest.raises(ValueError, match="no reader"):
        readers.read({"name": "x", "reader": "nope"}, None)


def _mixes():
    folder = os.path.join(manifest.ROOT, "benchmark", "traffic")
    return sorted(f[:-len(".json")] for f in os.listdir(folder) if f.endswith(".json"))


@pytest.mark.parametrize("mix", _mixes())
def test_every_committed_mix_is_one_the_generator_reads(mix):
    with open(os.path.join(manifest.ROOT, "benchmark", "traffic", f"{mix}.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert spec["name"] == mix and spec["why"]
    plan = traffic.plan(spec)
    # a mix that states no ``close_on`` closes at the level in flight
    assert plan.close_on == spec["window"].get("close_on", "level")


def test_an_unknown_close_on_is_refused_by_name():
    with open(os.path.join(manifest.ROOT, "benchmark", "traffic", "whole-crawls.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert traffic.plan(spec).close_on == "crawl"
    spec["window"]["close_on"] = "hitter-set"
    with pytest.raises(ValueError, match=r"close_on 'hitter-set'.*level.*crawl"):
        traffic.plan(spec)
