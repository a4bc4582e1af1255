"""The reduction from a profiler capture to busy time, operations and idle
gaps, against a small recorded capture: three steady levels of
``flagship-trusted`` cut from a capture of this PR's chip run (TPU v5 lite;
device ``XLA Ops`` and ``XLA Modules`` lines and the harness's annotations,
HLO text after the operation's name dropped), with the program's own spans
of those levels beside it.  What the reduction gives is checked against a
count made another way (a grid of one sample a microsecond)."""

import json
import os

import numpy as np
import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "trusted_capture.textproto"), encoding="utf-8") as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("capture") / "cut.xplane.pb"
    path.write_bytes(blob)
    return tr.read_capture(str(path))


@pytest.fixture(scope="module")
def spans():
    with open(os.path.join(DATA, "trusted_capture.spans.json"), encoding="utf-8") as f:
        rec = json.load(f)
    lines = [json.dumps(e) for e in rec["events"]] + ['{"ph": "X", "name": "fss", "comp": "serv']  # torn tail
    return tr.program_spans(lines, rec["offset_ns"], {"fss", "gc_ot", "field"})


def test_what_the_capture_holds(capture, spans):
    assert list(capture["devices"]) == ["/device:TPU:0"]
    assert len(capture["devices"]["/device:TPU:0"]) > 1000
    assert len(capture["levels"]) == 3 and len(capture["sync"]) == 1
    assert len(spans) == 18 and {n for n, _, _ in spans} == {
        f"server{i}:{s}" for i in (0, 1) for s in ("fss", "gc_ot", "field")}
    assert tr.sync_offset_ns(capture, 1_000) == capture["sync"][0][0] - 1_000
    assert tr.sync_offset_ns(dict(capture, sync=[]), 1_000) is None


def test_busy_time_against_a_grid(capture, spans):
    out = tr.reduce(capture, spans)
    lo, hi = capture["levels"][0][0], capture["levels"][-1][1]
    grid = np.zeros(int((hi - lo) / 1e3) + 1, bool)  # one sample a microsecond
    for s, e, _ in capture["devices"]["/device:TPU:0"]:
        a, b = int((max(s, lo) - lo) / 1e3), int((min(e, hi) - lo) / 1e3)
        if b > a:
            grid[a:b] = True
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(grid.sum() / 1e6, rel=0.02)
    assert 0.15 < out["busy_s"] / out["window_s"] < 0.30  # a steady trusted level: ~21 of ~97 ms
    assert out["levels_in_capture"] == 3
    # the idle gaps are the rest of the window, and the swap owns most of them
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert out["idle_gaps"][0][0] == "server1:gc_ot"
    # self times: no operation is counted inside another
    assert out["device_ops"][0][0] == "constant_dynamic-slice_fusion"
    assert sum(s for _, s in out["device_ops"]) <= out["busy_s"] * 1.001
    assert all(" = " not in name and not name.startswith("%") for name, _ in out["device_ops"])


def test_gaps_without_program_spans_go_to_the_level(capture):
    out = tr.reduce(capture)
    assert [n for n, _ in out["idle_gaps"]][0] == "bench_level"


def test_no_device_plane_gives_nothing(capture):
    assert tr.reduce(dict(capture, devices={})) is None
    assert tr.reduce(dict(capture, devices={"/device:TPU:0": []})) is None


def test_pieces():
    assert tr._union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert tr._clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]
    # a while of 10 holding two children of 3 and 4, then a lone op of 2
    assert tr._self_times([(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (12, 14, "a")]) == {
        "while": 3, "a": 5, "b": 4}
    assert tr.op_name("%fusion.2 = u32[8]{0} fusion(u32[8]{0} %p)") == "fusion.2"
    assert tr.op_name("expand_packed.1") == "expand_packed.1"
