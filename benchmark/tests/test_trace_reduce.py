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


def _oracle_gaps(capture, host_spans, first_plane):
    """The search as it stood until PR 42, kept as the oracle: every gap of
    the first device plane walks the spans of the whole process, shortest
    first (a stable sort: of equal lengths the first in file order)."""
    lo, hi = capture["levels"][0][0], capture["levels"][-1][1]
    evs = [ev for ev in capture["devices"][first_plane] if ev[1] > lo and ev[0] < hi]
    busy = tr._clip(tr._union([[s, e] for s, e, _ in evs]), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    spans = sorted(host_spans, key=lambda sp: sp[2] - sp[1])
    by_name = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            mid = (s + e) / 2
            owner = next((n for n, a, b in spans if a <= mid <= b), None)
            if owner is None:
                owner = "bench_level" if any(a <= mid <= b for a, b in capture["levels"]) else "between levels"
            by_name[owner] = by_name.get(owner, 0.0) + (e - s)
    return [[k, v / 1e9] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:10]]


def test_the_sweep_names_the_owners_the_walk_named(capture, spans):
    """On the recorded capture, with the program's spans and with the same
    spans in another file order, the idle gaps are the oracle's to the digit."""
    for sp in (spans, spans[::-1], []):
        assert tr.reduce(capture, sp)["idle_gaps"] == _oracle_gaps(capture, sp, "/device:TPU:0")


def _chunked_level(seed):
    """A made-up secure level in chunks: two levels with a hole between them,
    device operations of 30 us every 100 us (so some 400 gaps), and host
    spans of a chunk's kind on both servers: many of EQUAL length that
    overlap their neighbours (a tie goes to the first in file order), a
    ``gc_ot`` around each level, spans that end before the capture and start
    after it, spans that only touch it, and a stretch inside the second
    level that no span covers."""
    rng = np.random.default_rng(seed)
    levels = [(1_000_000.0, 21_000_000.0), (22_000_000.0, 42_000_000.0)]
    ops = [(float(t), float(t + 30_000), f"op{i % 3}") for i, t in
           enumerate(range(1_000_000, 42_000_000, 100_000))]
    host = []
    for lo, hi in levels:
        host += [(f"server{i}:gc_ot", lo + 10_000 * i, hi - 2_000_000) for i in (0, 1)]
        for k, t in enumerate(np.arange(lo, hi - 6_000_000, 250_000.0)):
            # equal lengths, each over the next one's first half
            host.append((f"server{k % 2}:otext", float(t), float(t + 375_000)))
            host.append((f"server{(k + 1) % 2}:d2h", float(t + 50_000), float(t + 425_000)))
            host.append(("server0:h2d", float(t + 100_000), float(t + 100_000 + rng.integers(1, 9) * 10_000)))
    host += [("server0:otext", -5e6, 0.5e6), ("server1:b2a", 43e6, 50e6),          # outside
             ("server0:wire_read", 0.0, 1_000_000.0), ("server1:wire_read", 42e6, 44e6),  # touching
             ("server1:b2a", -1e6, 60e6)]                                          # around everything
    order = rng.permutation(len(host))
    return ({"devices": {"/device:TPU:0": ops, "/device:TPU:1": ops[::2]},
             "levels": levels, "sync": [], "planes": []}, [host[i] for i in order])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_sweep_on_a_made_up_chunked_level(seed):
    cap, host = _chunked_level(seed)
    out = tr.reduce(cap, host)
    assert out["idle_gaps"] == _oracle_gaps(cap, host, "/device:TPU:0")
    names = {n for n, _ in out["idle_gaps"]}
    assert {"server0:otext", "server1:otext", "server0:h2d", "server1:b2a"} <= names
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(out["window_s"] - 410 * 30e-6)
    assert out["gap_search_s"] < 1.0
    # without the span around everything the uncovered stretch and the hole
    # between the levels fall to the level and to nobody
    bare = [sp for sp in host if sp[2] - sp[1] < 50e6]
    out = tr.reduce(cap, bare)
    assert out["idle_gaps"] == _oracle_gaps(cap, bare, "/device:TPU:0")
    assert {"bench_level", "between levels"} <= {n for n, _ in out["idle_gaps"]}


def test_owners_at_the_edges_and_on_ties():
    spans = [("long", 0, 100), ("b", 10, 20), ("a", 10, 20), ("c", 20, 30), ("late", 90, 95)]
    assert tr._owners([5, 10, 15, 20, 25, 30, 31, 92, 100, 101], spans) == [
        "long", "b", "b", "b", "c", "c", "long", "late", "long", None]
    assert tr._owners([1, 2], []) == [None, None]
