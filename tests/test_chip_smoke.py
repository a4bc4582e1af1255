"""chip_smoke.py rehearsed at a tiny size on CPU.

The script itself refuses to run without a TPU; its phases are a function of
the sizes, so the whole control flow — two servers + leader over localhost
sockets per lane, the plain-count oracle, the tapped secure/gc comparison,
the zero-compile second crawl — runs here at N=256, data_len=16 on the CPU
engines.  The platform and engine assertions are patched HERE, never in the
script.
"""

import json

import numpy as np
import pytest

import chip_smoke

PORT = 29231
TINY = dict(num_sites=8, threshold=0.03, f_max=64)


def test_cli_is_seed_chips_and_dimensions_only(capsys):
    for argv in (["--n", "8"], ["--chips", "2"], ["--n-dims", "4"],
                 ["--n-dims", "2", "--chips", "4"]):
        with pytest.raises(SystemExit) as e:
            chip_smoke.main(argv)
        assert e.value.code == 2
        capsys.readouterr()


def test_unpatched_run_fails_without_a_tpu(capsys):
    """On this CPU sandbox the script must fail before it runs anything:
    non-zero exit (an uncaught SmokeFailure) and no ``ok`` line."""
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_plain_count_is_a_saturating_ball_count():
    # 3-bit domain, ball 1: points 0, 0, 7, 3 -> [0,1] x2, [6,7], [2,4]
    pts = np.array([[0, 0, 0], [0, 0, 0], [1, 1, 1], [0, 1, 1]], bool)[:, None, :]
    assert chip_smoke.plain_count(pts, 1, 3, 1) == {
        0: 2, 1: 2, 6: 1, 7: 1, 2: 1, 3: 1, 4: 1,
    }
    assert chip_smoke.plain_count(pts, 1, 3, 2) == {0: 2, 1: 2}
    # depth-1 prefixes: [0,1] x2 and [2,4] touch prefix 0; [2,4], [6,7] prefix 1
    assert chip_smoke.plain_count(pts, 1, 1, 1) == {0: 3, 1: 2}


def test_plain_count_in_two_dimensions_is_a_product_of_balls():
    # (0, 7) -> [0,1] x [6,7]; (3, 3) -> [2,4] x [2,4]
    pts = np.array([[[0, 0, 0], [1, 1, 1]], [[0, 1, 1], [0, 1, 1]]], bool)
    assert chip_smoke.plain_count(pts, 1, 3, 1) == {
        **{(a, b): 1 for a in (0, 1) for b in (6, 7)},
        **{(a, b): 1 for a in (2, 3, 4) for b in (2, 3, 4)},
    }
    assert chip_smoke.plain_count(pts, 1, 1, 1) == {
        (0, 1): 2, (0, 0): 1, (1, 0): 1, (1, 1): 1}


DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 1}


@pytest.fixture
def patched(monkeypatch):
    """The platform and engine assertions, patched in the TEST."""
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda chips: DEVICE)
    monkeypatch.setattr(chip_smoke, "check_engines", lambda data_len: {})


def test_phases_at_tiny_size(patched, capsys):
    assert chip_smoke.run_phases(256, 128, 16, 4, port=PORT, **TINY) == DEVICE
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    # one JSON line per phase, in order, after the start line
    by_phase = {rec["phase"]: rec for rec in lines}
    assert list(by_phase) == ["start", "keygen", "trusted", "secure", "secure_gc"]
    assert len(lines) == 5
    assert by_phase["keygen"]["n"] == 256
    trusted, secure, gc = (by_phase[k] for k in ("trusted", "secure", "secure_gc"))
    assert (trusted["n"], trusted["levels"]) == (256, 16)
    assert (secure["n"], secure["levels"]) == (128, 16)
    assert (gc["n"], gc["levels"]) == (128, 4)
    # each lane line says what ITS servers ran (CollectorServer.engine_tags)
    assert by_phase["keygen"]["engine"] == "np"
    assert "ot_path" not in trusted["engines"]
    assert secure["engines"]["ot_path"] == "ot2s"
    assert gc["engines"]["ot_path"] == "gc"
    # the oracle comparison ran against non-empty sets (run_phases raises
    # on any mismatch, so reaching here means every set and count agreed)
    assert trusted["hitters"] > 0 and secure["hitters"] > 0 and gc["frontier"] > 0
    assert trusted["second_crawl"]["fresh_compiles"] == 0
    for rec in (trusted, secure, gc):
        assert rec["seconds"] >= rec["compile_seconds"] >= 0
        assert rec["engines"]["platform"] == "cpu"
        assert rec["engines"]["expand"] == "xla"
        assert rec["engines"]["data_devices"] == 1
        assert rec["compile_cache_dir"]


def test_sharded_comparison_at_tiny_size(patched, capsys):
    """``--chips 4``'s path on four of the suite's virtual CPU devices:
    both lanes agree with the plain count, the sharded lane's servers say
    so (four data devices, XLA expand) and its key planes really spread;
    bytes in use are sampled right after ingest and again after the
    crawl, with the key planes resident."""
    assert chip_smoke.run_sharded(
        512, 16, data_devices=4, port=PORT + 400, num_sites=8,
        threshold=0.012, f_max=64,
    ) == DEVICE
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert [rec["phase"] for rec in lines] == ["start", "sharded", "one_device"]
    sharded, one = lines[1:]
    assert sharded["n"] == one["n"] == 512 and sharded["hitters"] > 0
    assert sharded["engines"]["data_devices"] == 4
    assert sharded["engines"]["expand"] == "xla"
    assert sharded["engines"]["kernel_shards_max"] == 4
    assert len(sharded["key_plane_devices"]) == 4
    assert one["engines"]["data_devices"] == 1 and one["key_plane_devices"] == [0]
    for rec in (sharded, one):
        assert len(rec["bytes_in_use_per_device_after_ingest"]) == 8
        assert len(rec["bytes_in_use_per_device_keys_resident"]) == 8


def test_two_dimensional_secure_lane_at_tiny_size(patched, capsys):
    """``--n-dims 2``'s path: the secure lane alone on two strings a
    client (S = 4, the 1-of-16 table, four patterns a node), compared
    with the plain count at the tapped depth and where the crawl ended."""
    assert chip_smoke.run_secure_nd(256, 16, 2, 1, 4, port=PORT + 200, **TINY) == DEVICE
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert [rec["phase"] for rec in lines] == ["start", "secure"]
    rec = lines[1]
    assert (rec["n"], rec["n_dims"], rec["ball"]) == (256, 2, 1)
    assert rec["engines"]["ot_path"] == "ot2s" and rec["frontier_at_tap"] > 0
    # to the leaf level with hitters, or died out on the way with none
    assert (rec["levels"] == 16) == (rec["hitters"] > 0)


def test_last_line_is_the_contract_and_nothing_more(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "run_phases", lambda *a, **kw: DEVICE)
    assert chip_smoke.main(["--seed", "3"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": True, "device": DEVICE}


def test_a_wrong_count_fails_the_run(patched, monkeypatch):
    """The oracle comparison is a hard failure: a reference that disagrees
    by one count stops the run at the trusted phase."""
    real = chip_smoke.plain_count

    def off_by_one(*a, **kw):
        want = real(*a, **kw)
        k = next(iter(want))
        return {**want, k: want[k] + 1}

    monkeypatch.setattr(chip_smoke, "plain_count", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match="disagree"):
        chip_smoke.run_phases(256, 128, 16, 4, port=PORT + 200, **TINY)
