"""``run.py`` end to end at a tiny size on the CPU, for both lanes and both
``--trace`` values, through cells that exist only as files ADDED to a copy of
the benchmark (``tiny_root``): the harness needs no edit for a new
configuration, mix, cell or span-read metric."""

import json

import pytest

import run

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(capsys, *argv):
    rc = run.main(list(argv))
    out = capsys.readouterr()
    lines = [ln for ln in out.out.strip().splitlines() if ln]
    return rc, lines, out.err


def test_an_unpatched_run_fails_without_a_tpu(capsys):
    rc, lines, err = _run(capsys, "--workload", "flagship-trusted", "--seed", "1",
                          "--seconds", "1", "--trace", "0")
    assert rc != 0 and "no TPU" in err
    assert not any('"correct"' in ln for ln in lines)


@pytest.mark.parametrize("lane", ["trusted", "secure"])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_at_tiny_size(tiny_root, no_chip_check, capsys, lane, trace):
    rc, lines, err = _run(capsys, "--workload", f"tiny-{lane}", "--seed", str(2**31 + 11),
                          "--seconds", "1.5", "--trace", str(trace))
    assert rc == 0
    res = json.loads(lines[-1])
    assert set(res) - {"breakdown"} == KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 16
    assert res["device"]["platform"] == "cpu"  # the patched stamp, never a device number
    # each number compared is printed beside its limit, last on stderr too
    tail = err.strip().splitlines()[-4:]
    assert tail[0].startswith("compare levels_differing=0 limit=0")
    assert "the leaf level among them" in tail[0]
    assert tail[-1].startswith("correct=True")
    by_phase = {json.loads(ln).get("phase"): json.loads(ln) for ln in lines[:-1]}
    window = by_phase["window"]
    assert window["compiles"] == 0
    assert window["crawls"] >= 2  # a crawl that ends is followed by another
    # the crawl in flight at the deadline went on, untimed, to its leaf level
    assert window["tail_last_level"] in (15, None)
    assert window["levels"] + window["tail_levels"] == res["attempted"]
    assert (window["last_level"] + 1 + window["tail_levels"]) % 16 == 0
    names = set(res["metrics"])
    if trace == 0:
        # the rate under the cells' own name and bound; not the flagship's
        assert names == {"crawl_clients_per_s.tiny", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
        assert res["metrics"]["crawl_clients_per_s.tiny"]["value"] == by_phase["readings"]["crawl_clients_per_s"]
    else:
        # per-layer metrics, the ADDED span-read one among them and those
        # that are there again under the cells' tag (an entry, no file);
        # nothing to read on the CPU for the device's (no device plane, no
        # memory stats)
        tagged = {"fss_ms_per_level.tiny", "gc_ot_ms_per_level.tiny", "field_ms_per_level.tiny",
                  "leader_wire_ms_per_level.tiny", "level_p95_ms.leader.tiny",
                  "wire_bytes_per_level.tiny", "compiles_in_window.tiny"}
        rate = {"crawl_clients_per_s.trusted.tiny"} if lane == "trusted" else set()
        assert names == {"verb_ms_per_level", "compile_s"} | tagged | rate
        assert all(res["metrics"][k]["value"] == by_phase["readings"]["crawl_clients_per_s"]
                   for k in rate)
        assert res["metrics"]["compiles_in_window.tiny"]["value"] == 0
        assert res["metrics"]["verb_ms_per_level"]["value"] > 0
