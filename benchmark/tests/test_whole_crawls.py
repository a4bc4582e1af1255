"""A window that ends where a crawl ends (``window.close_on: "crawl"``), on the
CPU through ``run.main``: a tiny ``rides`` secure cell ADDED to ``tiny_root``
as files (``test_rehearsal_geo.py``'s cell) under the committed
``traffic/whole-crawls.json``, its capture started at the first crawl's end
and nothing else changed; and a mix without the key, which runs as it did."""

import json
import os

import pytest

import manifest
import run

# the ``window`` line of a mix that states no ``close_on``, as the parent
# (4c32977) logs it
PARENT_WINDOW_KEYS = {
    "phase", "seconds", "levels", "crawls", "first_level", "last_level", "buckets",
    "level_ms_median", "level_ms_max", "level_samples", "compiles", "compile_s", "tail_levels",
    "tail_s", "tail_compiles", "tail_last_level", "host", "span_ms_median", "level_ms",
    "bucket_by_level"}
N, DATA_LEN = 256, 16


def _load(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture
def tiny_rides(tiny_root):
    """``tiny_root`` with the shipped rides deployment (``configs/config.json``'s
    shapes) through the secure lane at N = 256; returns a function that adds a
    cell of it under ``whole-crawls.json``."""
    root = manifest.ROOT
    bench = _load(root, "BENCHMARK.json")
    conf = _load(root, "benchmark", "configs", "amazon-zipf-2d.json")
    conf = dict(conf, name="tiny-rides", clients=N, config=dict(
        conf["config"], f_max=64, distribution="rides", data_len=DATA_LEN, n_dims=2,
        ball_size=1, threshold=0.075))
    with open(os.path.join(root, "benchmark", "configs", "tiny-rides.json"), "w",
              encoding="utf-8") as f:
        json.dump(conf, f)
    bench["configs"].append({"name": "tiny-rides", "source": "a test", "reduced": [],
                             "file": "benchmark/configs/tiny-rides.json", "why": "rehearsal size"})
    committed = _load(root, "benchmark", "traffic", "whole-crawls.json")

    def add_cell(tag: str) -> str:
        mix = dict(committed, name=f"whole-crawls-{tag}",
                   trace=dict(committed["trace"], start_after_s=0.0))
        with open(os.path.join(root, "benchmark", "traffic", f"{mix['name']}.json"), "w",
                  encoding="utf-8") as f:
            json.dump(mix, f)
        name = f"tiny-rides-{tag}"
        bench["workloads"].append({"name": name, "config": "tiny-rides", "traffic": mix["name"],
                                   "chips": 1, "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "tiny-secure" in m.get("workloads", []):
                m["workloads"].append(name)
        with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
            json.dump(bench, f)
        return name

    return add_cell


def _run(capsys, name, seconds, trace=0):
    rc = run.main(["--workload", name, "--seed", str(2**31 + 43), "--seconds", str(seconds),
                   "--trace", str(trace)])
    out = capsys.readouterr()
    lines = [ln for ln in out.out.strip().splitlines() if ln]
    assert rc == 0
    by_phase = {json.loads(ln).get("phase"): json.loads(ln) for ln in lines[:-1]}
    return json.loads(lines[-1]), by_phase, out.err.strip().splitlines()[-4:]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_window_closes_where_a_crawl_ends(tiny_rides, no_chip_check, capsys, trace):
    seconds = 1.5
    res, by_phase, tail = _run(capsys, tiny_rides("committed"), seconds, trace)
    assert res["correct"] is True and res["failed"] == 0
    assert tail[0].startswith("compare levels_differing=0 limit=0")
    assert "0 of them after the window, the leaf level among them" in tail[0]
    assert tail[1].startswith("compare levels_raised=0 limit=0")
    assert tail[2].startswith("compare lane_evidence_mismatches=0 limit=0")
    window = by_phase["window"]
    assert set(window) == PARENT_WINDOW_KEYS | {"closed_on", "whole_crawls", "overrun_s"}
    assert window["closed_on"] == "crawl" and window["whole_crawls"] >= 2
    assert window["levels"] == DATA_LEN * window["whole_crawls"] == res["attempted"]
    assert window["crawls"] == window["whole_crawls"]
    assert (window["first_level"], window["last_level"]) == (0, DATA_LEN - 1)
    assert (window["tail_levels"], window["tail_last_level"]) == (0, None)
    assert window["compiles"] == 0
    # the clock stopped past the deadline, at the end of the crawl in flight
    # there, and no crawl was started after it
    assert 0 <= window["overrun_s"] == pytest.approx(window["seconds"] - seconds)
    whole = sum(window["level_ms"][-DATA_LEN:]) / 1e3
    assert window["overrun_s"] < whole + 0.5
    # ``e2e_readings`` is the parent's: with whole crawls in the window it is
    # N x crawls over their own seconds
    rate = by_phase["readings"]["crawl_clients_per_s"]
    assert rate == pytest.approx(N * window["whole_crawls"] / window["seconds"])
    if trace == 0:
        assert res["metrics"]["crawl_clients_per_s.tiny"]["value"] == rate
    else:
        assert res["metrics"]["compiles_in_window.tiny"]["value"] == 0
        assert res["metrics"]["gc_ot_ms_per_level.tiny"]["value"] > 0


def test_a_traced_run_captures_one_whole_crawl(tiny_rides, no_chip_check, capsys, monkeypatch):
    """Under ``close_on: "crawl"`` the capture starts and stops where a crawl
    ends: the committed mix's ``capture_s`` 0 gives exactly one crawl, every
    level of it whole, whatever the cell."""
    seen = []
    reduce = run.trace_reduce.reduce
    monkeypatch.setattr(run.trace_reduce, "reduce",
                        lambda cap, spans: seen.append(cap["levels"]) or reduce(cap, spans))
    res, by_phase, _ = _run(capsys, tiny_rides("traced"), 1.5, trace=1)
    assert res["correct"] is True and by_phase["window"]["whole_crawls"] >= 2
    (levels,) = seen
    assert len(levels) == DATA_LEN


def test_a_crawl_that_raises_is_not_a_whole_crawl(tiny_rides, no_chip_check, capsys, monkeypatch):
    """The second crawl fails at its fourth level: the window line says so
    (``closed_on`` "level", one whole crawl) and the run is not correct."""
    import lane

    one_level = lane.RpcLeader._run_one_level
    second = []

    async def failing(self, level, nreqs, thresh):
        if self.crawl == 1:
            second.append(level)
            if len(second) == 4:
                raise RuntimeError("planted")
        return await one_level(self, level, nreqs, thresh)

    name = tiny_rides("raises")
    monkeypatch.setattr(lane.RpcLeader, "_run_one_level", failing)
    res, by_phase, _ = _run(capsys, name, 1.5)
    window = by_phase["window"]
    assert res["correct"] is False
    assert (window["closed_on"], window["whole_crawls"]) == ("level", 1)
    assert window["levels"] < 2 * DATA_LEN


def test_a_mix_without_close_on_logs_the_parents_window_line(tiny_root, no_chip_check, capsys):
    res, by_phase, _ = _run(capsys, "tiny-secure", 1.0)
    assert res["correct"] is True
    assert set(by_phase["window"]) == PARENT_WINDOW_KEYS
    # the crawl in flight at the deadline went on, untimed, to its leaf level
    assert by_phase["window"]["tail_last_level"] in (DATA_LEN - 1, None)
