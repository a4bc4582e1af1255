"""The reference's shipped geo deployment (``configs/config.json``:
``data_len`` 16, ``n_dims`` 2, ball 1, threshold 0.075, ``distribution:
rides``) through the secure lane on the normal path, at N = 256 on the CPU:
a whole 16-level crawl through two ``CollectorServer``s and an ``RpcLeader``
against the plain reference ``benchmark/references/linf_ball_nd.py``, the
leaf level's span and counter (``leaf_gc_ot`` / ``leaf_tests``), and the
benchmark's cell ``rides-geo-secure`` itself, cut to N = 256, through
``benchmark/run.py`` under the committed ``whole-crawls.json``."""

import asyncio
import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

from fuzzyheavyhitters_tpu import workloads
from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.utils.config import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, DATA_LEN = 256, 16


def _load(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, found as ``benchmark/run.py`` finds them."""
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    import lane
    import manifest
    import readers
    import run

    return types.SimpleNamespace(lane=lane, manifest=manifest, readers=readers, run=run)


@pytest.fixture(scope="module")
def one_crawl(bench):
    """One whole crawl of the deployment as committed but for N: the
    program's sampler and key generator, ``benchmark/lane.py``'s pair and
    tapped leader.  -> (points, the sixteen levels, the hitter set, the
    servers' registries' reports)."""
    conf = _load(BENCH, "configs", "rides-geo.json")
    cfg = Config(**conf["config"])
    rng = np.random.default_rng(46)
    points = workloads.sample_points(cfg, N, rng)
    k0, k1 = ibdcf.gen_l_inf_ball(points, cfg.ball_size, rng, engine=ibdcf.best_engine())

    async def go():
        async with bench.lane.pair(cfg, ("gc_ot", "leaf_gc_ot")) as (lead, s0, s1):
            await lead.upload_keys(k0, k1)
            lead.after_level = lambda rec: False
            res = await lead.run(N)
            return lead.records, res, [s.obs.report() for s in (s0, s1)]

    return (points, cfg, *asyncio.run(go()))


def test_a_whole_rides_crawl_agrees_with_the_plain_reference(bench, one_crawl):
    """Every level's frontier and counts, and the hitter set handed over,
    are ``linf_ball_nd``'s over the same points: the 2-D F255 leaf among
    them."""
    points, cfg, levels, res, _ = one_crawl
    ref = bench.manifest.reference({"reference": "linf_ball_nd"})
    thresh = max(1, int(cfg.threshold * N))
    want = ref.frontiers(points, cfg.ball_size, thresh, DATA_LEN)
    assert [lv.level for lv in levels] == list(range(DATA_LEN))
    assert all(lv.error is None for lv in levels)
    for lv in levels:
        assert ref.crawl_frontier(lv.paths, lv.counts) == want[lv.level + 1], lv.level
    assert want[DATA_LEN], "the crawl must end in hitters, not die out"
    assert res.paths.shape[1:] == (2, DATA_LEN)
    assert ref.crawl_frontier(res.paths, res.counts) == want[DATA_LEN]
    assert want[DATA_LEN] == ref.plain_count(points, cfg.ball_size, DATA_LEN, thresh)


def test_the_leaf_level_has_a_span_and_a_counter_of_its_own(one_crawl):
    """``leaf_gc_ot`` is entered once a crawl on each server, at the last
    level, inside that level's ``gc_ot``; ``leaf_tests`` is that level's
    ``B = F*C*N`` (its ``gc_tests``), and no inner level has either."""
    _, _, levels, _, reports = one_crawl
    last = str(DATA_LEN - 1)
    for rep in reports:
        leaf, gc = rep["phases"]["leaf_gc_ot"], rep["phases"]["gc_ot"]
        assert leaf["count"] == 1 and gc["count"] == DATA_LEN
        assert set(leaf["by_level"]) == {last}
        assert 0 < leaf["by_level"][last] <= gc["by_level"][last]
        tests = rep["counters"]["leaf_tests"]
        assert set(tests["by_level"]) == {last}
        assert tests["total"] == rep["counters"]["gc_tests"]["by_level"][last]
        # F nodes of the frontier the leaf level expands (the bucket the
        # level before it ended in), four child patterns a node, N clients
        assert tests["total"] == levels[-2].bucket * 4 * N
    for lv in levels[:-1]:
        assert all(reg["leaf_gc_ot"] == 0 for reg in lv.spans.values())
    assert all(reg["leaf_gc_ot"] > 0 for reg in levels[-1].spans.values())


@pytest.fixture
def tiny_cell(bench, tmp_path, monkeypatch):
    """A copy of the benchmark as ``benchmark/tests/conftest.py``'s
    ``tiny_root`` makes one (BENCHMARK.json and ``benchmark/`` under a
    directory of the test's own, ``manifest.ROOT`` pointed at it) in which
    ``rides-geo`` holds 256 clients and nothing else differs: the committed
    cell, entries, metric files and mix."""
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, "benchmark", "configs", "rides-geo.json")
    conf = _load(path)
    conf["clients"] = N
    with open(path, "w", encoding="utf-8") as f:
        json.dump(conf, f)
    monkeypatch.setattr(bench.manifest, "ROOT", root)
    monkeypatch.setattr(bench.run, "require_tpu",
                        lambda chips: {"platform": "cpu", "kind": "rehearsal", "count": 1})
    # the thresholds are the chip run's to set: this process is a test
    # worker's, and goes on to other files
    pinned = []
    monkeypatch.setattr(bench.run, "pin_allocator", lambda spec: pinned.append(spec) or {})
    return pinned


# nothing to read on the CPU for the device's two: no device plane, no
# memory stats
_DEVICE_ONLY = {"device_idle_share.geo", "hbm_peak_gb.geo"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_itself_at_256_clients(bench, tiny_cell, capsys, trace):
    rc = bench.run.main(["--workload", "rides-geo-secure", "--seed", str(2**31 + 46),
                         "--seconds", "1.5", "--trace", str(trace)])
    out = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.out.strip().splitlines() if ln]
    assert rc == 0
    res, by_phase = lines[-1], {ln.get("phase"): ln for ln in lines[:-1]}
    assert res["correct"] is True and res["failed"] == 0
    tail = out.err.strip().splitlines()[-4:]
    assert tail[0].startswith("compare levels_differing=0 limit=0")
    assert "0 of them after the window, the leaf level among them" in tail[0]
    assert tail[1].startswith("compare levels_raised=0 limit=0")
    assert tail[2].startswith("compare lane_evidence_mismatches=0 limit=0")
    # the configuration's own group reached the harness as it stands
    trusted = _load(BENCH, "configs", "zipf-flagship-trusted.json")
    assert tiny_cell == [trusted["process"]["malloc"]]
    window = by_phase["window"]
    assert window["closed_on"] == "crawl" and window["whole_crawls"] >= 2
    assert window["levels"] == DATA_LEN * window["whole_crawls"] == res["attempted"]
    assert window["compiles"] == 0 and window["tail_levels"] == 0
    assert by_phase["setup"]["warmup_levels"] == DATA_LEN
    cell = bench.manifest.cell("rides-geo-secure")
    if trace == 0:
        assert set(res["metrics"]) == {"crawl_clients_per_s", "setup_s"}
        assert res["metrics"]["crawl_clients_per_s"]["value"] == pytest.approx(
            N * window["whole_crawls"] / window["seconds"])
        return
    names = {spec["name"] for spec in cell.per_layer}
    assert len(names) == 32  # the 29 twins, the leaf's two, compile_s
    assert names - set(res["metrics"]) == _DEVICE_ONLY
    value = lambda name: res["metrics"][name]["value"]
    # the leaf level is one level in sixteen of whole crawls: its seconds
    # lie inside the exchange's, and its tests are the leaf's B a crawl
    assert 0 < value("leaf_gc_ot_ms_per_level") < value("gc_ot_ms_per_level.geo")
    leaf_bucket = window["bucket_by_level"][DATA_LEN - 2]
    assert value("leaf_tests_per_level") * DATA_LEN == leaf_bucket * 4 * N
    assert value("leaf_tests_per_level") < value("equality_tests_per_level.geo")
    assert value("compiles_in_window.geo") == 0


def test_the_parent_reads_nothing_for_the_leaf_metrics(bench):
    """On a program without the span and the counter (the parent commit, on
    which the driver tries the cell first) the two readers find nothing and
    do not raise: no span seconds, a counter that reads 0."""
    readers = bench.readers
    specs = {s["name"]: s for s in bench.manifest.cell("rides-geo-secure").per_layer}
    levels = [readers.Level(crawl=0, level=i, spans={
        "server0": {"gc_ot": 0.2, "leaf_gc_ot": 0.0}, "server1": {"gc_ot": 0.2, "leaf_gc_ot": 0.0}})
        for i in range(DATA_LEN)]
    run = readers.Run(levels=levels, counters={"server0": {"leaf_tests": 0}}, readings={})
    assert readers.read(specs["leaf_gc_ot_ms_per_level"], run) is None
    assert readers.read(specs["leaf_tests_per_level"], run) == 0
    assert readers.read(specs["gc_ot_ms_per_level.geo"], run) == pytest.approx(200.0)
