"""``run.py`` end to end on a tiny TWO-dimensional secure cell, on the CPU: the
harness draws ``n_dims`` strings a site, loads the plain reference the
configuration names (``linf_ball_nd``) and compares every level with it,
through files ADDED to ``tiny_root``'s copy of the benchmark and no edit."""

import json
import os

import pytest

import control
import manifest
import run


@pytest.fixture
def tiny_2d(tiny_root):
    """``tiny_root`` with one more configuration and cell: the 2-D secure
    deployment at a size a test run holds."""
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(manifest.ROOT, "benchmark", "configs", "amazon-zipf-2d.json"),
              encoding="utf-8") as f:
        conf = json.load(f)
    name = "tiny-2d-secure"
    conf = dict(conf, name=name, clients=256,
                config=dict(conf["config"], data_len=16, num_sites=8, threshold=0.03, f_max=64))
    assert (conf["config"]["n_dims"], conf["reference"]) == (2, "linf_ball_nd")
    with open(os.path.join(manifest.ROOT, "benchmark", "configs", f"{name}.json"), "w",
              encoding="utf-8") as f:
        json.dump(conf, f)
    bench["configs"].append({"name": name, "source": "a test", "reduced": [],
                             "file": f"benchmark/configs/{name}.json", "why": "rehearsal size"})
    bench["workloads"].append({"name": name, "config": name, "traffic": "tiny-levels",
                               "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-secure" in m.get("workloads", []):
            m["workloads"].append(name)
    # the cell's own per-layer files, again under the tiny cell's name
    for m in list(bench["per_layer"]):
        if m.get("workloads") == ["amazon-2d-secure"] and not m["name"].endswith(".2d"):
            m["workloads"].append(name)
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return name


@pytest.mark.parametrize("trace", [0, 1])
def test_two_dimensional_secure_cell_at_tiny_size(tiny_2d, no_chip_check, capsys, trace):
    rc = run.main(["--workload", tiny_2d, "--seed", str(2**31 + 37), "--seconds", "1.5",
                   "--trace", str(trace)])
    out = capsys.readouterr()
    lines = [ln for ln in out.out.strip().splitlines() if ln]
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 8
    tail = out.err.strip().splitlines()[-4:]
    assert tail[0].startswith("compare levels_differing=0 limit=0")
    assert tail[2].startswith("compare lane_evidence_mismatches=0 limit=0")
    by_phase = {json.loads(ln).get("phase"): json.loads(ln) for ln in lines[:-1]}
    window = by_phase["window"]
    assert window["compiles"] == 0 and window["levels"] >= 8
    if trace:
        m = res["metrics"]
        # four child patterns a node: a level runs N x bucket x 4 tests, by
        # the bucket it was expanded at (1 at a crawl's level 0, else the
        # one the level before left)
        tests = round(m["equality_tests_per_level"]["value"] * window["levels"])
        assert tests % (256 * 4) == 0
        assert window["levels"] <= tests // (256 * 4) <= sum([1] + window["bucket_by_level"][:-1])
        assert m["ot2s_table_ms_per_level"]["value"] > 0


def test_both_controls_come_out_not_correct_in_two_dimensions(tiny_2d, no_chip_check, capsys):
    """``control.py`` on the 2-D cell: every second client's keys uploaded
    twice changes the counts of most levels (against ``linf_ball_nd``), and
    the cell served by the trusted swap is caught by the lane's counters
    with every count still exact."""
    rec = control.dup(manifest.cell(tiny_2d), seed=4, seconds=0.5)
    assert rec["correct"] is False and rec["failed"] >= rec["attempted"] // 2
    assert "compare lane_evidence_mismatches=0" in capsys.readouterr().err
    rec = control.lane(manifest.cell(tiny_2d), seed=4, seconds=0.5)
    assert rec["secure_exchange"] is False
    assert rec["failed"] == 0 and rec["correct"] is False
    assert "compare lane_evidence_mismatches=4 limit=0" in capsys.readouterr().err
