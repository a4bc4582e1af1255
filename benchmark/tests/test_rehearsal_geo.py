"""``run.py`` end to end on a tiny ``rides`` and a tiny ``covid`` secure cell,
on the CPU: the harness draws the geo points itself
(``traffic.DISTRIBUTIONS``), the configuration names ``linf_ball_nd`` and
every level is compared with it, through files ADDED to ``tiny_root``'s copy
of the benchmark and no edit: what the ``model_config`` PR that brings
``rides-geo`` may do."""

import json
import os

import pytest

import control
import manifest
import run

# the reference's shipped shapes (configs/config.json; amazon.json's ball
# with COVID's 64-bit coordinates); the thresholds are a test's, for N = 256
GEO = {
    "rides": dict(distribution="rides", data_len=16, n_dims=2, ball_size=1, threshold=0.075),
    "covid": dict(distribution="covid", data_len=64, n_dims=2, ball_size=8, threshold=0.05),
}


@pytest.fixture(params=sorted(GEO))
def tiny_geo(request, tiny_root):
    """``tiny_root`` with one more configuration and cell: the secure lane
    over geo points at a size a test run holds."""
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(manifest.ROOT, "benchmark", "configs", "amazon-zipf-2d.json"),
              encoding="utf-8") as f:
        conf = json.load(f)
    name = f"tiny-{request.param}-secure"
    conf = dict(conf, name=name, clients=256,
                config=dict(conf["config"], f_max=64, **GEO[request.param]))
    assert conf["reference"] == "linf_ball_nd" and conf["lane"] == "secure"
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
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return request.param, name


def test_geo_secure_cell_at_tiny_size(tiny_geo, no_chip_check, capsys):
    kind, name = tiny_geo
    rc = run.main(["--workload", name, "--seed", str(2**31 + 41), "--seconds", "1.5",
                   "--trace", "1"])
    out = capsys.readouterr()
    lines = [ln for ln in out.out.strip().splitlines() if ln]
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 8
    tail = out.err.strip().splitlines()[-4:]
    assert tail[0].startswith("compare levels_differing=0 limit=0")
    assert tail[1].startswith("compare levels_raised=0 limit=0")
    assert tail[2].startswith("compare lane_evidence_mismatches=0 limit=0")
    # rides: 16 levels down to the two-dimensional F255 leaf; covid: the
    # crawl dies out long before its 64th level and the next one starts
    assert ("the leaf level among them" in tail[0]) == (kind == "rides")
    by_phase = {json.loads(ln).get("phase"): json.loads(ln) for ln in lines[:-1]}
    window = by_phase["window"]
    assert window["compiles"] == 0 and window["crawls"] >= 2
    assert window["last_level"] < GEO[kind]["data_len"]
    assert res["metrics"]["gc_ot_ms_per_level.tiny"]["value"] > 0


def test_the_dup_control_comes_out_not_correct_on_geo_points(tiny_geo, no_chip_check, capsys):
    """Every second client's keys uploaded twice: the counts of most levels
    differ from ``linf_ball_nd``'s over the points the harness drew."""
    _, name = tiny_geo
    rec = control.dup(manifest.cell(name), seed=4, seconds=0.5)
    assert rec["correct"] is False and rec["failed"] >= 1
    assert "compare lane_evidence_mismatches=0" in capsys.readouterr().err
