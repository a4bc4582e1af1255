"""The comparison that decides ``correct`` has to fail where it should: both
controls of ``control.py`` at a size a test run holds, and the rest of a run
driven with the timed path broken underneath (an answer altered where it is
produced, a level that raises)."""

import json

import numpy as np
import pytest

import control
import manifest
import run
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader


@pytest.mark.parametrize("lane", ["trusted", "secure"])
def test_every_second_client_uploaded_twice_is_not_correct(tiny_root, no_chip_check, capsys, lane):
    """The program itself, the same shapes and programs, half the clients
    counted twice and half never: most levels hold other counts."""
    rec = control.dup(manifest.cell(f"tiny-{lane}"), seed=4, seconds=0.5)
    assert rec["correct"] is False and rec["failed"] >= rec["attempted"] // 2
    err = capsys.readouterr().err
    assert "compare levels_differing=" in err and "compare lane_evidence_mismatches=0" in err


def test_the_other_lane_is_not_correct(tiny_root, no_chip_check, capsys):
    """The secure cell served by the trusted swap: every count is still
    exact, and the run fails on the lane's evidence counters."""
    rec = control.lane(manifest.cell("tiny-secure"), seed=4, seconds=0.5)
    assert rec["secure_exchange"] is False
    assert rec["failed"] == 0 and rec["correct"] is False
    assert "compare lane_evidence_mismatches=4 limit=0" in capsys.readouterr().err


def _result(capsys, cell="tiny-trusted"):
    rc = run.main(["--workload", cell, "--seed", "9", "--seconds", "0.5", "--trace", "0"])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


def test_one_altered_count_makes_the_run_not_correct(tiny_root, no_chip_check,
                                                     monkeypatch, capsys):
    real = RpcLeader._crawl_level

    async def one_more(self, level, last):
        s0, s1 = await real(self, level, last)
        if level == 5:  # every count of the level reads one more
            s0 = np.array(s0) + 1
        return s0, s1

    monkeypatch.setattr(RpcLeader, "_crawl_level", one_more)
    rc, res, err = _result(capsys)
    assert rc == 0 and res["correct"] is False
    assert 1 <= res["failed"] < res["attempted"]
    assert "first differing level: crawl 0 level 5" in err


def test_a_level_that_raises_makes_the_run_not_correct(tiny_root, no_chip_check,
                                                       monkeypatch, capsys):
    real = RpcLeader._crawl_level

    visits = []

    async def breaks(self, level, last):
        visits.append(level)
        if visits.count(7) == 2:  # the warm-up's passes, the window's does not
            raise RuntimeError("the exchange was left out")
        return await real(self, level, last)

    monkeypatch.setattr(RpcLeader, "_crawl_level", breaks)
    rc, res, err = _result(capsys)
    assert rc == 0 and res["correct"] is False and res["failed"] == 1
    assert res["attempted"] == 8
    assert "compare levels_raised=1 limit=0 RuntimeError" in err


def test_a_window_without_a_level_is_not_correct(tiny_root, no_chip_check,
                                                 monkeypatch, capsys):
    async def nothing(lead, n, after_level):
        return False

    import lane
    real = lane.crawl
    calls = []

    async def first_only(lead, n, after_level):  # the warm-up runs, the window does not
        calls.append(1)
        return await (real if len(calls) == 1 else nothing)(lead, n, after_level)

    monkeypatch.setattr(lane, "crawl", first_only)
    rc, res, _ = _result(capsys)
    assert rc == 0 and res["correct"] is False and res["attempted"] == 0
