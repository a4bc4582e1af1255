"""The benchmark's data files held to each other (no chip, no run)."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def test_the_two_secure_configurations_differ_by_size_alone():
    """``zipf-flagship-secure-hbm`` is ``zipf-flagship-secure`` at the
    client count a chip holds: another name, ``clients``, and the prose
    that says so; the lane, the reference, the guarantees, the lane's
    evidence and the whole ``config`` group are the same."""
    small = _load("benchmark", "configs", "zipf-flagship-secure.json")
    big = _load("benchmark", "configs", "zipf-flagship-secure-hbm.json")
    assert small.keys() == big.keys()
    differ = {k for k in small if small[k] != big[k]}
    assert differ == {"name", "clients", "deployment", "source", "reduced_why"}
    assert (small["clients"], big["clients"]) == (16384, 131072)
    assert big["reduced"] == ["clients"] == list(big["reduced_why"])
    assert len(big["source"]) <= 200


@pytest.mark.parametrize("metric", [
    "secure_chunks_per_level", "b2a_ms_per_level",
    "wire_slab_new_bytes_per_level", "wire_queue_ms_per_level",
])
def test_new_metric_files_agree_with_their_entries(metric):
    """A per-layer entry of BENCHMARK.json and its file say the same of
    what both state, and the file reads through a reader the harness
    has, in the one cell that lists it."""
    bench = _load("BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    spec = _load("benchmark", "metrics", f"{metric}.json")
    for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == ["flagship-secure-hbm"]
    assert spec["reader"] in ("counter_per_level", "span_ms_per_level")
    assert spec["what"]


def test_the_hbm_cell_reports_what_the_secure_cell_reports():
    """Every per-layer metric that lists ``flagship-secure`` has a
    ``.hbm`` entry for ``flagship-secure-hbm`` (an entry, no file), and
    the cell joins ``crawl_clients_per_s``."""
    bench = _load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == "flagship-secure-hbm")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zipf-flagship-secure-hbm", "steady-levels", 1)
    rate = next(m for m in bench["end_to_end"] if m["name"] == "crawl_clients_per_s")
    assert rate["workloads"] == ["flagship-secure", "flagship-secure-hbm"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        if m.get("workloads") != ["flagship-secure"]:
            continue
        twin = by_name[m["name"] + ".hbm"]
        assert twin["workloads"] == ["flagship-secure-hbm"]
        assert twin["moves"] == "setup_s"
        assert {k: twin[k] for k in ("unit", "better", "source", "layer")} == {
            k: m[k] for k in ("unit", "better", "source", "layer")}
        assert not os.path.exists(
            os.path.join(ROOT, "benchmark", "metrics", twin["name"] + ".json"))
