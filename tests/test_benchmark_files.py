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
    assert rate["workloads"][:2] == ["flagship-secure", "flagship-secure-hbm"]
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


def test_the_2d_configuration_is_amazon_json_through_the_secure_lane():
    """``amazon-zipf-2d`` runs the reference's second shipped
    configuration (``configs/amazon.json``) as shipped: its ``config``
    group equals that file key for key (but for the servers' addresses,
    which the harness sets), and on every key that file lacks it equals
    the secure flagship's; nothing published is cut, and the client count
    the source does not give is listed as assumed."""
    conf = _load("benchmark", "configs", "amazon-zipf-2d.json")
    shipped = _load("configs", "amazon.json")
    hbm = _load("benchmark", "configs", "zipf-flagship-secure-hbm.json")
    assert conf.keys() == hbm.keys()
    assert conf["config"].keys() == hbm["config"].keys()
    for key, value in conf["config"].items():
        if key in ("server0", "server1"):
            assert value == ""
        elif key in shipped:
            assert value == shipped[key], key
        else:
            assert value == hbm["config"][key], key
    assert {k: conf["config"][k] for k in ("data_len", "n_dims", "ball_size", "threshold")} == {
        "data_len": 64, "n_dims": 2, "ball_size": 8, "threshold": 0.005}
    assert conf["config"]["secure_exchange"] is True and conf["config"]["ot_path"] == "auto"
    for key, value in shipped.items():
        if key not in ("server0", "server1"):
            assert conf["published"][key] == value, key
    assert conf["reduced"] == [] and conf["reduced_why"] == {}
    assert "clients" in conf["assumed"] and str(conf["clients"]) in conf["assumed"]["clients"]
    assert conf["clients"] in (131072, 65536)
    assert (conf["lane"], conf["reference"], conf["chips"]) == ("secure", "linf_ball_nd", 1)
    assert conf["guarantees"] == hbm["guarantees"]
    assert conf["lane_evidence"] == hbm["lane_evidence"]
    assert "EMPTY AT DEPTH 59" in conf["deployment"].upper()
    assert len(conf["source"]) <= 200


# The hbm cell's entries that ``amazon-2d-secure`` leaves out: each reads
# span names that a chunk opens once (some 17,600 spans of each a traced
# run) and that are SHORT, and ``benchmark/trace_reduce.reduce`` walks
# through every span shorter than a gap's owner for every idle gap of the
# capture.  With them that search alone was an hour of a traced run of
# this cell, without them a third of it (PERF.md section 7); they read
# 82, 46, 27 and 26 ms of a 3.5 s level.
_LEFT_OUT_2D = {"h2d_ms_per_level", "peer_wait_ms_per_level",
                "wire_queue_ms_per_level", "wire_codec_ms_per_level"}


def test_the_2d_cell_reports_what_the_hbm_cell_reports():
    """Every per-layer metric of ``flagship-secure-hbm`` but the four of
    ``_LEFT_OUT_2D`` has a ``.2d`` entry for ``amazon-2d-secure`` (an
    entry, no file: the original's reader and arguments), the cell joins
    ``crawl_clients_per_s``, and its own two metrics have files that
    agree with their entries."""
    bench = _load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == "amazon-2d-secure")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "amazon-zipf-2d", "steady-levels", 1)
    rate = next(m for m in bench["end_to_end"] if m["name"] == "crawl_clients_per_s")
    assert rate["workloads"] == [
        "flagship-secure", "flagship-secure-hbm", "amazon-2d-secure", "rides-geo-secure"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    originals = [m for m in bench["per_layer"] if m.get("workloads") == ["flagship-secure-hbm"]]
    assert len(originals) == 19
    for m in originals:
        base = m["name"][:-len(".hbm")] if m["name"].endswith(".hbm") else m["name"]
        if base in _LEFT_OUT_2D:
            assert base + ".2d" not in by_name
            continue
        twin = by_name[base + ".2d"]
        assert twin["workloads"] == ["amazon-2d-secure"]
        assert {k: twin[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: m[k] for k in ("unit", "better", "source", "layer", "moves")}
        assert twin["moves"] == "setup_s"
        assert not os.path.exists(
            os.path.join(ROOT, "benchmark", "metrics", twin["name"] + ".json"))
    own = [m for m in bench["per_layer"] if m.get("workloads") == ["amazon-2d-secure"]
           and not m["name"].endswith(".2d")]
    assert [m["name"] for m in own] == ["ot2s_table_ms_per_level", "equality_tests_per_level"]
    for entry in own:
        spec = _load("benchmark", "metrics", entry["name"] + ".json")
        for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
            assert spec[key] == entry[key], key
        assert spec["reader"] in ("counter_per_level", "span_ms_per_level") and spec["what"]
        assert entry["moves"] == "setup_s"  # so that the rehearsal's twins stand as they are


# The stage account's twelve metrics (PR 39): one file and ONE entry
# each, for the three secure cells together, no ``.hbm`` / ``.2d`` twins.
_STAGE_ACCOUNT = {
    **{f"{stage}_idle_ms_per_level":
       [f"stage_starved:{stage}", f"stage_blocked:{stage}"]
       for stage in ("build", "msg_fetch", "msg_send", "extend", "u_fetch",
                     "u_send", "open")},
    "program_dispatch_ms_per_level": ["program_dispatch"],
    "program_device_ms_per_level": ["program_device"],
    "loop_hop_ms_per_level": ["program_hop", "d2h_hop", "send_resume"],
    "d2h_ready_ms_per_level": ["d2h_ready"],
    "d2h_copy_ms_per_level": ["d2h_copy"],
}
_SECURE_CELLS = ["flagship-secure", "flagship-secure-hbm", "amazon-2d-secure"]


def _manifest():
    import sys

    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import manifest

    return manifest


@pytest.mark.parametrize("metric", list(_STAGE_ACCOUNT))
def test_stage_account_metrics_load_and_read_what_rpc_records(metric):
    """Each of the twelve loads through ``manifest.cell`` under its own
    name for the three secure cells and no other, agrees with its entry,
    reads through ``span_ms_per_level`` (the idle of a stage summed over the servers,
    one of which runs it in a level; the rest their mean), and every
    timer it names is one ``protocol/rpc.py`` records."""
    import re

    manifest = _manifest()
    bench = _load("BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    entry = by_name[metric]
    assert entry == {
        "name": metric, "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "2PC exchange", "moves": "setup_s", "workloads": _SECURE_CELLS}
    # ``rides-geo-secure`` reports it under a ``.geo`` twin (an entry, no
    # file), and no other entry has the stem
    assert by_name[metric + ".geo"] == dict(
        entry, name=metric + ".geo", workloads=["rides-geo-secure"])
    assert [m for m in by_name if m.split(".")[0] == metric] == [metric, metric + ".geo"]
    for w in bench["workloads"]:
        specs = [s for s in manifest.cell(w["name"]).per_layer if s["name"] == metric]
        assert len(specs) == (w["name"] in _SECURE_CELLS)
    (spec,) = specs = [s for s in manifest.cell("amazon-2d-secure").per_layer
                       if s["name"] == metric]
    assert {k: spec[k] for k in entry} == entry
    assert spec["reader"] == "span_ms_per_level"
    assert spec["args"] == {
        "spans": _STAGE_ACCOUNT[metric],
        "servers": "sum" if metric.endswith("_idle_ms_per_level") else "mean",
        "levels": "mean"}
    assert "gc_ot_ms_per_level" in spec["what"] and "K chunks" in spec["what"]
    with open(os.path.join(ROOT, "fuzzyheavyhitters_tpu", "protocol", "rpc.py"),
              encoding="utf-8") as f:
        source = f.read()
    from fuzzyheavyhitters_tpu.protocol import rpc

    for name in spec["args"]["spans"]:
        kind, _, stage = name.partition(":")
        if stage:
            # a stage's timers: ``_Stage`` makes the three names from its
            # stage, which a task (or ``_chunk_senders``' caller) gives
            assert kind in rpc.STAGE_TIMERS[:2]
            assert re.search(rf'_Stage\(cs\.obs, "{stage}", level\)', source) or re.search(
                rf'\("\w+", "{stage}"\)|\("{stage}", "\w+"\)', source), name
        else:
            assert re.search(rf'timer_add\(\s*"{name}"', source), name


# ``rides-geo`` / ``rides-geo-secure`` (PR 46): the reference's shipped
# ``src/bin/config.json`` through the secure lane, as whole crawls.
_GEO_CELL = "rides-geo-secure"


def test_the_geo_configuration_is_config_json_through_the_secure_lane():
    """``rides-geo`` runs the configuration the reference ships as its
    default (``configs/config.json``) as shipped: its ``config`` group equals
    that file key for key (but for the servers' addresses, which the harness
    sets) and ``amazon-zipf-2d``'s on every key that file lacks; nothing
    published is cut; the client count, the stand-in for the RideAustin file
    and the pinned allocator are listed as assumed; the allocator's group is
    the trusted cell's; the guarantees and the lane's evidence are the hbm
    cell's."""
    conf = _load("benchmark", "configs", "rides-geo.json")
    shipped = _load("configs", "config.json")
    amazon = _load("benchmark", "configs", "amazon-zipf-2d.json")
    hbm = _load("benchmark", "configs", "zipf-flagship-secure-hbm.json")
    trusted = _load("benchmark", "configs", "zipf-flagship-trusted.json")
    assert list(conf)[:2] == ["name", "process"]  # the group is stated first
    assert conf.keys() == hbm.keys() | {"process"} == trusted.keys()
    assert list(conf["config"]) == list(amazon["config"])
    for key, value in conf["config"].items():
        if key in ("server0", "server1"):
            assert value == ""
        elif key in shipped:
            assert value == shipped[key], key
        else:
            assert value == amazon["config"][key], key
    assert set(shipped) <= set(conf["config"])
    assert {k: conf["config"][k] for k in (
        "data_len", "n_dims", "ball_size", "threshold", "distribution")} == {
        "data_len": 16, "n_dims": 2, "ball_size": 1, "threshold": 0.075, "distribution": "rides"}
    assert {k: conf["config"][k] for k in (
        "secure_exchange", "malicious", "ot_path", "f_max", "crawl_radix_bits")} == {
        "secure_exchange": True, "malicious": False, "ot_path": "auto", "f_max": 256,
        "crawl_radix_bits": 1}
    for key, value in shipped.items():
        if key not in ("server0", "server1"):
            assert conf["published"][key] == value, key
    assert conf["reduced"] == [] and conf["reduced_why"] == {}
    assert conf["clients"] == 131072 and "131072" in conf["assumed"]["clients"]
    assert "RideAustin" in conf["assumed"]["stand_in"]
    assert "1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 4, 6, 8, 8, 10, 24, 54" in conf["assumed"]["stand_in"]
    assert conf["process"] == trusted["process"] and conf["assumed"]["process_malloc"]
    assert (conf["lane"], conf["reference"], conf["chips"], conf["link_delay_ms"]) == (
        "secure", "linf_ball_nd", 1, 0)
    assert conf["guarantees"] == hbm["guarantees"]
    assert conf["lane_evidence"] == hbm["lane_evidence"]
    entry = next(c for c in _load("BENCHMARK.json")["configs"] if c["name"] == "rides-geo")
    assert entry["file"] == "benchmark/configs/rides-geo.json" and entry["reduced"] == []
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert "src/bin/config.json" in conf["source"] and "src/collect.rs:419-501" in conf["source"]
    others = [c["source"] for c in _load("BENCHMARK.json")["configs"] if c["name"] != "rides-geo"]
    assert conf["source"] not in others


def test_the_geo_cell_is_whole_crawls_on_one_chip():
    """The cell names the mix ``whole-crawls`` as committed (a window that
    closes where a crawl ends, a warm-up of one whole crawl, no tail), takes
    one chip, joins ``crawl_clients_per_s`` under the benchmark's own bound
    and brings no end-to-end entry of its own."""
    bench = _load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == _GEO_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("rides-geo", "whole-crawls", 1)
    assert len(cell["why"]) <= 200 and "131072" in cell["why"]
    assert [w["name"] for w in bench["workloads"] if w["traffic"] == "whole-crawls"] == [_GEO_CELL]
    mix = _load("benchmark", "traffic", "whole-crawls.json")
    assert mix["window"]["close_on"] == "crawl" and "tail" not in mix
    assert mix["warmup"]["min_levels"] == 17  # one whole crawl of 16 levels ends on its own
    reports = [m["name"] for m in bench["end_to_end"] if _GEO_CELL in m.get("workloads", [_GEO_CELL])]
    assert reports == ["crawl_clients_per_s", "setup_s"]
    assert [m["bound"] for m in bench["end_to_end"]] == [0.12, 0.15, 0.25]
    assert bench["run_seconds"] == 51
    loaded = _manifest().cell(_GEO_CELL)
    assert loaded.config["name"] == "rides-geo" and loaded.mix["name"] == "whole-crawls"
    assert [m["name"] for m in loaded.end_to_end] == reports


def _geo_originals(bench):
    """What ``amazon-2d-secure`` reports per layer, by the name each
    ``.geo`` twin is made from: the hbm cell's entries but the four of
    ``_LEFT_OUT_2D``, the 2-D cell's own two, the stage account's twelve."""
    out = {}
    for m in bench["per_layer"]:
        if m.get("workloads") == ["flagship-secure-hbm"]:
            base = m["name"].removesuffix(".hbm")
            if base not in _LEFT_OUT_2D:
                out[base] = m
        elif m["name"] in ("ot2s_table_ms_per_level", "equality_tests_per_level") or (
                m["name"] in _STAGE_ACCOUNT):
            out[m["name"]] = m
    return out


def test_the_geo_cell_reports_what_the_2d_cell_reports():
    """Twenty-nine ``.geo`` twins, each an entry with no file that agrees
    with its original in unit, better, source, layer and ``moves``; with the
    leaf level's two metrics and ``compile_s`` they are all the cell
    reports, and every one loads through ``manifest.cell``."""
    bench = _load("BENCHMARK.json")
    originals = _geo_originals(bench)
    assert len(originals) == 15 + 2 + 12
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for base, m in originals.items():
        twin = by_name[base + ".geo"]
        assert twin == dict(m, name=base + ".geo", workloads=[_GEO_CELL]), base
        assert twin["moves"] == "setup_s"
        assert not os.path.exists(
            os.path.join(ROOT, "benchmark", "metrics", twin["name"] + ".json"))
    for base in _LEFT_OUT_2D:
        assert base + ".geo" not in by_name  # they come back for both 2-D cells in one PR
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [_GEO_CELL]]
    assert mine == [base + ".geo" for base in originals] + [
        "leaf_gc_ot_ms_per_level", "leaf_tests_per_level"]
    assert mine == [m["name"] for m in bench["per_layer"][-31:]]  # appended, nothing in between
    specs = {s["name"]: s for s in _manifest().cell(_GEO_CELL).per_layer}
    assert set(specs) == set(mine) | {"compile_s"}
    for base, m in originals.items():
        spec, orig = specs[base + ".geo"], _load("benchmark", "metrics", base + ".json")
        assert (spec["reader"], spec.get("args")) == (orig["reader"], orig.get("args")), base


@pytest.mark.parametrize("metric, reader, args, recorded", [
    ("leaf_gc_ot_ms_per_level", "span_ms_per_level",
     {"spans": ["leaf_gc_ot"], "servers": "mean", "levels": "mean"},
     r'cs\.obs\.span\("leaf_gc_ot", level=level\)'),
    ("leaf_tests_per_level", "counter_per_level",
     {"counters": ["leaf_tests"], "registries": ["server0"]},
     r'cs\.obs\.count\("leaf_tests", \w+, level=level\)'),
])
def test_the_leaf_metrics_read_what_rpc_records(metric, reader, args, recorded):
    """The leaf level's two metrics: file and entry say the same, the cell
    alone lists them, and the span and the counter they name are recorded
    by ``protocol/rpc.py``, once, where a crawl's last level is exchanged."""
    import re

    bench = _load("BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    spec = _load("benchmark", "metrics", f"{metric}.json")
    for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == [_GEO_CELL] and entry["layer"] == "2PC exchange"
    assert entry["moves"] == "setup_s"  # as ot2s_table_ms_per_level: the rehearsal's twins stand
    assert (spec["reader"], spec["args"]) == (reader, args) and spec["what"]
    with open(os.path.join(ROOT, "fuzzyheavyhitters_tpu", "protocol", "rpc.py"),
              encoding="utf-8") as f:
        source = f.read()
    assert len(re.findall(recorded, source)) == 1
    assert "self._leaf_exchange(cs, level, B) if last else _NO_CTX" in source
