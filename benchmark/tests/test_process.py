"""``run.pin_allocator``: a configuration's ``process.malloc`` group reaches
glibc's ``mallopt`` by name, prose beside the numbers is skipped, a threshold
that is refused fails the run, and a configuration without the group leaves
the allocator as it comes."""

import ctypes
import json
import os

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Libc:
    def __init__(self, answer):
        self.calls, self.answer = [], answer

    def mallopt(self, param, value):
        self.calls.append((param, value))
        return self.answer


def test_no_group_sets_nothing(monkeypatch):
    libc = _Libc(1)
    monkeypatch.setattr(ctypes, "CDLL", lambda name: libc)
    assert run.pin_allocator(None) == {} == run.pin_allocator({})
    assert libc.calls == []


def test_the_stated_thresholds_reach_mallopt_by_name(monkeypatch):
    libc = _Libc(1)
    monkeypatch.setattr(ctypes, "CDLL", lambda name: libc)
    with open(os.path.join(BENCH, "configs", "zipf-flagship-trusted.json"), encoding="utf-8") as f:
        spec = json.load(f)["process"]["malloc"]
    assert spec["what"]
    done = run.pin_allocator(spec)
    assert done == {"mmap_threshold": 32 << 20, "trim_threshold": 2**31 - 1, "top_pad": 256 << 20}
    # M_TRIM_THRESHOLD -1, M_TOP_PAD -2, M_MMAP_THRESHOLD -3 (malloc.h); an int each
    assert sorted(libc.calls) == [(-3, 32 << 20), (-2, 256 << 20), (-1, 2**31 - 1)]


def test_a_refused_threshold_fails_the_run(monkeypatch):
    monkeypatch.setattr(ctypes, "CDLL", lambda name: _Libc(0))
    with pytest.raises(RuntimeError, match="mmap_threshold"):
        run.pin_allocator({"mmap_threshold": 1})


def test_the_real_allocator_takes_them():
    assert run.pin_allocator({"what": "a test", "mmap_threshold": 32 << 20}) == {"mmap_threshold": 32 << 20}


def test_main_says_what_it_set(tiny_root, no_chip_check, capsys):
    """The tiny cells are copies of ``zipf-flagship-trusted``, group and
    all: the run's ``process`` line names the thresholds."""
    assert run.main(["--workload", "tiny-trusted", "--seed", "3", "--seconds", "1", "--trace", "0"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    proc = next(ln for ln in lines if ln.get("phase") == "process")
    assert proc["malloc"]["mmap_threshold"] == 32 << 20
    assert lines[-1]["correct"] is True
