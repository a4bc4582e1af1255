"""The wait account of the secure level's chunk pipeline (protocol/rpc.py
``_Stage``, ``_timed``; ``_program`` / ``_phase_sync`` / ``_fetch_behind``
/ ``_fetch`` / ``_dp_send``): a CPU pair over real sockets,
``secure.CHUNK_FRAME_BYTES`` patched small so that a level crosses in
K > 1 chunks, as tests/test_secure_chunks.py does.

What is held, for every level and server: no stage waited longer than
the level's ``gc_ot``; a stage that dispatches (``build``, ``extend``)
is its waits, its ``h2d`` and its dispatches and awaits nothing else; a
stage that sends or opens is its waits and its leaf spans; a fetch stage
does nothing between its waits but await the thread that waits for the
chunk's programs and copies, so its busy seconds lie inside the program
wait and ``d2h``; a span
that waits on a thread is the sum of its three parts; one chunk's
``otext``, ``b2a`` and ``d2h`` are disjoint and in order; the counters
say where each program was waited for, and the gauge that no more
waits for the device were parked at once than a server has threads for;
the account writes no span-log line; and an untraced level writes
nothing at all.
"""

import asyncio
import collections
import sys
import time

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.obs import report as obsreport
from fuzzyheavyhitters_tpu.obs import trace as tracemod
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.protocol import rpc, secure

from test_secure_chunks import BLOCK, L, WHOLE, _Pair, _points_2d, _root_counts

BASE_PORT = 32331  # a range of its own (.. 32602: above test_rpc's 32231-32302; tests/test_secure_chunks.py's second range begins at 32631), under the ephemeral ports

EV_STAGES = ("extend", "u_fetch", "u_send", "open")
GB_STAGES = ("build", "msg_fetch", "msg_send")
# what each stage does between its waits: the leaf spans and timers
# (``dispatch``: the stage's own share of ``program_dispatch``, which
# ``_dispatches`` reads; a fetch stage has no span of its own, below)
WORK = {
    "extend": ("dispatch",),
    "open": ("h2d", "eval", "b2a"),
    "build": ("h2d", "dispatch"),
    # a send stage keeps two frames with the writer thread: these, the
    # first frame's ``wire_queue`` and the last frame's ``send_resume``
    # are the most it can have been busy (``_check_sends``)
    "u_send": ("wire_pickle", "wire_write", "stream_gap"),
    "msg_send": ("wire_pickle", "wire_write", "stream_gap"),
}
FETCH_STAGES = ("u_fetch", "msg_fetch")
SEND_STAGES = ("u_send", "msg_send")


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    yield


# the interpreter's switch interval as it comes (5 ms)
_SWITCH = sys.getswitchinterval()


@pytest.fixture
def no_forced_switch():
    """The loop thread keeps the interpreter lock until it lets go of it
    (an ``await`` that reaches the selector, a call that releases it:
    every one inside a timer of the account).  As it comes, a thread that
    has waited ``_SWITCH`` for the lock is handed it at the loop thread's
    next bytecode, which may lie BETWEEN two clock reads of the account;
    on a loaded host that thread may then wait for a CPU with the lock
    in hand, and the account is short by as much (what failed this file
    in the driver's runs, a case or two in a dozen under eight spinning
    processes)."""
    sys.setswitchinterval(60.0)
    yield
    sys.setswitchinterval(_SWITCH)


def _frame_bytes(path, S, field, blocks=1):
    """A frame budget of ``blocks`` planar blocks a chunk."""
    from fuzzyheavyhitters_tpu.parallel.kernel_shard import n_msg_planes

    W = secure.payload_words(field)
    return blocks * BLOCK * max(16 * S, 4 * n_msg_planes(path, S, W))


def _field_fetches(monkeypatch):
    """{(registry, level): seconds of ``d2h`` inside the ``field`` phase}:
    the one fetch of a secure level that no chunk stage makes."""
    seen = {}
    real = rpc.CollectorServer._reduced_fetch

    async def spy(self, cs, level, fn, *args):
        before = cs.obs.timer_seconds("d2h", level)
        out = await real(self, cs, level, fn, *args)
        seen[cs.obs.name, level] = cs.obs.timer_seconds("d2h", level) - before
        return out

    monkeypatch.setattr(rpc.CollectorServer, "_reduced_fetch", spy)
    return seen


def _dispatches(monkeypatch):
    """{(registry, level, stage task): seconds the task spent handing
    work over}: inside its jitted calls (``program_dispatch`` by task)
    and inside ``_fetch_behind`` (the copy queued, the thread called)."""
    seen = collections.Counter()

    def timed(real, first):
        def spy(*args, **kw):
            cs, level = args[first:first + 2]
            t0 = time.perf_counter()
            try:
                return real(*args, **kw)
            finally:
                task = asyncio.current_task().get_coro().__name__
                stage = {"consume": "open"}.get(task, task)
                seen[cs.obs.name, level, stage] += time.perf_counter() - t0
        return spy

    cls = rpc.CollectorServer
    monkeypatch.setattr(cls, "_fetch_behind", timed(cls._fetch_behind, 1))
    monkeypatch.setattr(
        cls, "_dispatched", staticmethod(timed(cls._dispatched, 0)))
    return seen


def _resumes(monkeypatch):
    """{(registry, level, fetch stage): seconds the stage waited for its
    turn on the loop with its chunk's fetch already DONE}: of each
    ``_fetch_taken`` what lies after the loop recorded the finished
    fetch (``_fetched``, which ends ``d2h``), all of it where the chunk
    had run ahead and the fetch was done before the stage asked.  Even
    then ``asyncio.wait`` comes back only turns of the loop later,
    behind whatever else is ready (a sibling's dispatch, which on the
    CPU holds the loop for the program's whole run, a compile's where
    it is a case's first): no timer of the account holds that wait, so
    the test measures it."""
    seen, done_at = collections.Counter(), {}
    cls = rpc.CollectorServer
    real_fetched, real_taken = cls._fetched.__func__, cls._fetch_taken

    def fetched(klass, reg, level, marks, waits, held, fut):
        real_fetched(klass, reg, level, marks, waits, held, fut)
        done_at[id(held)] = time.perf_counter()

    async def taken(self, cs, level, stage, k, held):
        asked = time.perf_counter()
        try:
            return await real_taken(self, cs, level, stage, k, held)
        finally:
            since = max(asked, done_at.pop(id(held), asked))
            seen[cs.obs.name, level, stage] += time.perf_counter() - since

    monkeypatch.setattr(cls, "_fetched", classmethod(fetched))
    monkeypatch.setattr(cls, "_fetch_taken", taken)
    return seen


def _frames(monkeypatch):
    """{(registry, level): [(t_put, t_begin, t_end, resumed)]}: the
    frames a registry sent under a level, in the order they were waited
    for: the hand-over, the writer thread's two stamps, and the wall
    clock where ``_dp_send_finish`` had returned on the loop."""
    seen = collections.defaultdict(list)
    real = rpc.CollectorServer._dp_send_finish

    async def spy(self, out):
        t_begin, t_end = await real(self, out)
        seen[out.reg.name, out.level].append(
            (out.t_put, t_begin, t_end, time.time()))
        return t_begin, t_end

    monkeypatch.setattr(rpc.CollectorServer, "_dp_send_finish", spy)
    return seen


def _close(a, b):
    """Within 5% or 2 ms."""
    return abs(a - b) <= max(0.05 * max(a, b), 2e-3)


def _check_sends(reg, lv, st, frames, K):
    """(b'') a send stage, which keeps ``FRAMES_OUT`` frames with the
    writer thread: that thread's life with the stage's frames,
    ``wire_write`` + ``stream_gap``, lies inside the stage's; before it
    the stage waited for its first chunk, pickled it and handed it over
    (the first frame's ``wire_queue``), after it the last frame's
    ``send_resume`` and ``on_sent``.  So its busy seconds are at most
    the upper sum, and at least the thread's life less the waits that
    ran beside it (a stage that starves while a frame is written is not
    busy then: ``wire_write`` alone is NOT a lower bound)."""
    t = lambda name: reg.timer_seconds(name, level=lv)
    sent = frames[reg.name, lv]
    assert len(sent) == K == reg.counter_value("plane_stream_frames", level=lv)
    # frames left and ended in the order of their hand-over
    assert all(a[0] <= a[1] <= a[2] <= a[3] for a in sent), sent
    assert all(a[2] <= b[1] and a[0] <= b[0] for a, b in zip(sent, sent[1:]))
    # stream_gap is the thread's wait between this stage's frames, by
    # the thread's own stamps
    gaps = sum(b[1] - a[2] for a, b in zip(sent, sent[1:]))
    assert t("stream_gap") == pytest.approx(gaps, abs=1e-6)
    assert t("wire_write") == pytest.approx(
        sum(a[2] - a[1] for a in sent), abs=1e-6)
    starved, blocked, wall = (t(f"{k}:{st}") for k in rpc.STAGE_TIMERS)
    busy = wall - starved - blocked
    life = t("wire_write") + t("stream_gap")
    most = (sum(t(name) for name in WORK[st]) + (sent[0][1] - sent[0][0])
            + (sent[-1][3] - sent[-1][2]))
    slack = max(0.05 * wall, 2e-3)
    assert life - starved - blocked - slack <= busy <= most + slack, (
        st, busy, life, most, starved, blocked, wall)
    # frames handed over while the thread held another: none where the
    # level is one frame, never the first, never more than it had out
    over = reg.counter_value("plane_sends_overlapped", level=lv)
    assert 0 <= over <= K - 1
    high = reg.gauge_value("plane_send_queue_high", level=lv)
    assert high == (2 if over else 1), (over, high)
    if K == 1:
        # one frame: the whole send, one call after the other
        assert t("stream_gap") == 0.0
        assert _close(
            starved + blocked + t("wire_pickle") + t("wire_queue")
            + t("wire_write") + t("send_resume"), wall)


def _check_account(reg, lv, stages, field_d2h, dispatched, frames, resumed,
                   synced=True, K=None):
    """Identities (a), (b) and (c) on one server's registry at one level."""
    t = lambda name: reg.timer_seconds(name, level=lv)
    gc_ot = t("gc_ot")
    assert gc_ot > 0
    for st in stages:
        starved, blocked, wall = (t(f"{k}:{st}") for k in rpc.STAGE_TIMERS)
        # (a) no stage waited longer than the level took
        assert 0 <= starved + blocked <= gc_ot, (st, starved, blocked, gc_ot)
        assert 0 < wall <= gc_ot, (st, wall, gc_ot)
        busy = wall - starved - blocked
        if st in FETCH_STAGES:
            # (b') between its waits a fetch stage awaits the chunk's
            # thread: at most the program wait, the hand-over and d2h,
            # and some of them where no chunk ran ahead to hide it; and
            # then its own turn on the loop (``_resumes``)
            most = (t("program_device") + t("program_hop") + t("d2h")
                    - field_d2h[reg.name, lv])
            late = resumed[reg.name, lv, st]
            assert -1e-4 <= busy <= most + late + 2e-3, (st, busy, most, late)
            assert K != 1 or busy > 0, (st, busy)
            continue
        if st in SEND_STAGES:
            _check_sends(reg, lv, st, frames, K)
            continue
        # (b) its waits and its work fill its wall, inside gc_ot: a
        # stage that dispatches awaits nothing but input and room
        work = sum(
            dispatched[reg.name, lv, st] if name == "dispatch" else t(name)
            for name in WORK[st]
        )
        # (between the timed calls of a stage that dispatches, a thread
        # that wakes may be handed the interpreter lock: twice a level
        # at most is let pass)
        gap = wall - starved - blocked - work
        assert _close(starved + blocked + work, wall) or (
            "dispatch" in WORK[st] and 0 <= gap <= 2 * _SWITCH + 2e-3
        ), (st, starved, blocked, work, wall)
    for st in set(EV_STAGES + GB_STAGES) - set(stages):
        assert t(f"stage_wall:{st}") == 0.0  # the other server's
    # (c) a span that waits on a thread is its three parts
    parts = [t("program_dispatch"), t("program_device"), t("program_hop")]
    spans = [t("otext"), t("b2a"), t("eval"), t("garble")]
    assert _close(sum(parts), sum(spans)), (parts, spans)
    assert t("program_dispatch") > 0 and t("otext") > 0
    if synced:
        assert t("program_device") > 0
    else:
        assert t("program_device") == t("program_hop") == 0.0
    parts = [t("d2h_ready"), t("d2h_copy"), t("d2h_hop")]
    assert _close(sum(parts), t("d2h")), (parts, t("d2h"))
    assert t("d2h_ready") >= 0 and t("d2h_copy") > 0 and t("send_resume") >= 0


_CASES = {
    # name: (path, garbler, last, two dimensions, n, bucket, K, Config fields)
    "ot2s_garbler0": ("ot2s", 0, False, False, 4096, 4, 4, {}),
    "ot2s_garbler1": ("ot2s", 1, False, False, 4096, 4, 4, {}),
    "gc_garbler0": ("gc", 0, False, False, 4096, 4, 4, {}),
    "gc_garbler1": ("gc", 1, False, False, 4096, 4, 4, {}),
    "leaf_level": ("ot2s", 1, True, False, 3072, 4, 3, {}),
    "two_dimensions": ("auto", 0, False, True, 1024, 4, 2, {}),
    # one chunk: the same calls, one after the other, the same timers
    "whole_level": ("ot2s", 0, False, False, 1024, 4, 1, {}),
    "no_phase_sync": ("ot2s", 1, False, False, 4096, 4, 4,
                      {"secure_phase_sync": False}),
    "gc_no_phase_sync": ("gc", 0, False, False, 4096, 4, 4,
                         {"secure_phase_sync": False}),
    "gc_whole_level": ("gc", 1, False, False, 1024, 4, 1, {}),
    "eight_chunks": ("ot2s", 0, False, False, 4096, 8, 8, {}),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_every_stage_accounts_for_its_level(monkeypatch, no_forced_switch, case):
    """(a), (b), (c) of the stage account at a level of K chunks, on both
    equality paths, with either server garbling, at the leaf level, in
    two dimensions, at K = 1 and without the phase sync."""
    path, garbler, last, two_d, n, f, K, cfg = _CASES[case]
    S = 4 if two_d else 2
    real_path = secure.ot_path(S) if path == "auto" else path
    field = F255 if last else FE62
    lv = L - 1 if last else 0
    field_d2h = _field_fetches(monkeypatch)
    dispatched = _dispatches(monkeypatch)
    frames = _frames(monkeypatch)
    resumed = _resumes(monkeypatch)
    monkeypatch.setattr(
        secure, "CHUNK_FRAME_BYTES",
        WHOLE if K == 1 else _frame_bytes(real_path, S, field),
    )
    port = BASE_PORT + 20 * list(_CASES).index(case)

    async def run():
        pts = _points_2d(n) if two_d else None
        async with _Pair(port, n, pts=pts, **cfg) as pair:
            await pair.both("tree_init", {"root_bucket": f})
            shares = await pair.level(garbler, last=last, path=path)
            regs = [cs.obs for cs in pair.sessions]
            ks = [r.counter_value("secure_chunks", level=lv) for r in regs]
            rep = obsreport.run_report(regs)
            return shares, regs, ks, rep, pair.pts

    shares, regs, ks, rep, pts = asyncio.run(run())
    assert ks == [K, K]
    synced = cfg.get("secure_phase_sync", True)
    for sid, reg in enumerate(regs):
        _check_account(
            reg, lv, GB_STAGES if sid == garbler else EV_STAGES, field_d2h,
            dispatched, frames, resumed, synced=synced, K=K,
        )
    # (e) where each program was waited for: the garbling server's on
    # its fetch's thread (two a chunk, the circuit's a third), the other
    # server's extension there too and what it opens by its stage (the
    # circuit's evaluation and the field conversion are two); no waits
    # at all without the sync, and the programs counted as before
    gc = real_path == "gc"
    for name, want in (
        ("secure_chunk_programs", (2 * K, 2 * K)),
        ("secure_fetch_syncs", ((3 if gc else 2) * K * synced, K * synced)),
        ("secure_phase_waits", (0, (2 if gc else 1) * K * synced)),
    ):
        got = [r.counter_value(name, level=lv) for r in regs]
        assert (got[garbler], got[1 - garbler]) == want, (name, got)
    sk = rep["secure_kernels"]
    assert sk["chunk_programs_by_level"] == {str(lv): 2 * K}
    assert sk["fetch_syncs_by_level"] == (
        {str(lv): (3 if gc else 2) * K} if synced else {})
    assert sk["phase_waits_by_level"] == (
        {str(lv): (2 if gc else 1) * K} if synced else {})
    # (f) the waits for the device parked on the servers' own threads:
    # never more at once than a level can have in flight, which is the
    # threads a server has for them; and every fetch was recorded
    assert sk["device_wait_threads"] == rpc.CollectorServer.DEVICE_WAITS
    assert 1 <= sk["device_waits_high"] <= sk["device_wait_threads"]
    assert sk["account_errors"] == 0
    if not last and not two_d:
        got = np.asarray(FE62.canon(FE62.sub(shares[0], shares[1])))
        assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()
    # the operator's reader: the run report's ``stages`` block
    stages = rep["secure_kernels"]["stages"]
    assert set(stages) == {r.name for r in regs}
    for sid, reg in enumerate(regs):
        mine = stages[reg.name]
        want = GB_STAGES if sid == garbler else EV_STAGES
        assert set(mine["by_stage"]) == set(want)
        for st, row in mine["by_stage"].items():
            assert _close(
                row["busy_seconds"],
                row["wall_seconds"] - row["starved_seconds"]
                - row["blocked_seconds"],
            )
            assert 0 <= row["busy_share_of_gc_ot"] <= 1.0
            # a fetch stage only awaits its chunk's thread: the report
            # adds none of that thread's spans to it (its busy seconds
            # are its wall less its waits, never below zero)
            assert row["busy_seconds"] >= -1e-4, (st, row)
            # a send stage's row has the writer thread's account of its
            # frames beside it, and no other stage's has
            t = lambda name: reg.timer_seconds(name, level=lv)
            if st in SEND_STAGES:
                assert row["wire_write_seconds"] == round(t("wire_write"), 6)
                assert row["stream_gap_seconds"] == round(t("stream_gap"), 6)
            else:
                assert not {"wire_write_seconds", "stream_gap_seconds"} & set(row)
        assert mine["pace_setter"] in want
        assert rep["plane"][reg.name]["sends_overlapped"] == reg.counter_value(
            "plane_sends_overlapped")
        assert set(mine["split"]) == {
            "program_dispatch", "program_device", "program_hop",
            "d2h_ready", "d2h_copy", "d2h_hop", "send_resume",
        }


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """fhh-trace armed into a directory of the test's own."""
    d = tmp_path / "trace"
    monkeypatch.setenv(tracemod.ENV_DIR, str(d))
    tracemod._refresh()
    yield d
    monkeypatch.delenv(tracemod.ENV_DIR, raising=False)
    tracemod._refresh()


async def _one_level(port):
    """``tree_init`` and a K = 4 level garbled by server 0, under a
    trace root where tracing is on."""
    async with _Pair(port, 4096) as pair:
        with tracemod.root("crawl"):
            await pair.both("tree_init", {"root_bucket": 4})
            await pair.level(0, path="ot2s")
        return [cs.obs for cs in pair.sessions]


# the span-log lines of ``_one_level``, by name, counted on the parent of
# the stage account (commit c70dd32, both servers and the leader): four
# chunks' spans a stage, the level's three phases, the field's fetch,
# the sessions' handshake and both verbs' control-plane frames
_PARENT_LINES = {
    "b2a": 8, "call:tree_crawl": 2, "call:tree_init": 2, "d2h": 10,
    "device_turn": 2, "field": 2, "frontier_init": 2, "fss": 2, "gc_ot": 2,
    "h2d": 8, "key_place": 2, "ot2s": 8, "otext": 8, "peer_wait": 14,
    "plane_handshake": 2, "verb:tree_crawl": 2, "verb:tree_init": 2,
    "wire_pickle": 18, "wire_queue": 14, "wire_read": 14,
    "wire_unpickle": 14, "wire_wait": 14, "wire_write": 18,
}


def test_the_account_writes_no_span_log_line(trace_dir, monkeypatch):
    """A traced K = 4 level writes the lines the parent wrote, name by
    name: the account is timers alone, so ``benchmark/trace_reduce`` is
    handed not one span more."""
    monkeypatch.setattr(
        secure, "CHUNK_FRAME_BYTES", _frame_bytes("ot2s", 2, FE62))
    regs = asyncio.run(_one_level(BASE_PORT + 220))
    tracemod.flush()
    events = [e for e in tracemod.load_events(str(trace_dir)) if e["ph"] == "X"]
    lines = collections.Counter(e["name"] for e in events)
    assert dict(lines) == _PARENT_LINES
    # (d) one chunk's spans are stamped on its fetch's thread, disjoint
    # and in order: on the garbling server otext ends where b2a (which
    # is ot2s) begins and b2a where the message's d2h begins; on the
    # other otext ends where u's d2h begins (its b2a is the opening's)
    at = {
        (e["comp"], e["chunk"], e["name"]): (e["ts"], e["ts"] + e["dur"])
        for e in events if "chunk" in e
    }
    same = lambda a, b: abs(a - b) <= 3e-6  # the log rounds to 1 us
    for k in range(4):
        otext, b2a, ot2s, d2h = (
            at["server0", k, name] for name in ("otext", "b2a", "ot2s", "d2h"))
        assert otext[0] < otext[1] and same(otext[1], b2a[0])
        assert b2a[0] < b2a[1] and same(b2a[1], d2h[0]) and d2h[0] < d2h[1]
        assert same(ot2s[0], b2a[0]) and same(ot2s[1], b2a[1])
        otext, d2h = at["server1", k, "otext"], at["server1", k, "d2h"]
        assert otext[0] < otext[1] and same(otext[1], d2h[0]) and d2h[0] < d2h[1]
    # and the timers are there all the same: every name the benchmark's
    # twelve metric files of the account read, on one server or the other
    for reg in regs:
        assert reg.timer_seconds("program_dispatch", level=0) > 0
        assert reg.timer_seconds("d2h_copy", level=0) > 0
        assert reg.counter_value("secure_chunk_programs", level=0) == 8
    assert [reg.counter_value("secure_fetch_syncs", level=0) for reg in regs] == [8, 4]
    assert [reg.counter_value("secure_phase_waits", level=0) for reg in regs] == [0, 4]
    recorded = {name for reg in regs for name in reg.report()["phases"]}
    from test_benchmark_files import _STAGE_ACCOUNT, _load

    for metric in _STAGE_ACCOUNT:
        spec = _load("benchmark", "metrics", f"{metric}.json")
        assert set(spec["args"]["spans"]) <= recorded, metric


def test_an_untraced_level_writes_nothing(tmp_path, monkeypatch):
    """``FHH_TRACE_DIR`` unset: no writer, no file, and ``obs`` has not
    imported ``jax.profiler`` (it does at the first TRACED span)."""
    monkeypatch.delenv(tracemod.ENV_DIR, raising=False)
    monkeypatch.chdir(tmp_path)
    tracemod._refresh()
    monkeypatch.setattr(tracemod, "_ANNOTATION", None)
    monkeypatch.setattr(
        secure, "CHUNK_FRAME_BYTES", _frame_bytes("ot2s", 2, FE62))
    regs = asyncio.run(_one_level(BASE_PORT + 240))
    tracemod.flush()
    assert tracemod.enabled() is False
    assert tracemod._WRITER is None and tracemod._ANNOTATION is None
    assert not list(tmp_path.iterdir())
    assert all(r.timer_seconds("stage_wall:open") + r.timer_seconds(
        "stage_wall:build") > 0 for r in regs)


def test_the_span_log_is_whole_when_a_verb_has_answered(trace_dir):
    """The ring file is block-buffered and flushed where a server's verb
    has answered: a reader in the process (benchmark/run.py, which calls
    no flush) finds every span of the verb on disk."""

    async def run():
        async with _Pair(BASE_PORT + 260, 1024) as pair:
            with tracemod.root("crawl"):
                await pair.both("tree_init", {"root_bucket": 4})
                await pair.level(0, path="ot2s")
            # no tracemod.flush() here
            return collections.Counter(
                e["name"] for e in tracemod.load_events(str(trace_dir))
                if e["ph"] == "X" and e.get("level") == 0
            )

    lines = asyncio.run(run())
    assert tracemod._BUFFER_BYTES > 1 << 16
    # K = 1: one of each a server, and the field's fetch
    for name in ("gc_ot", "fss", "field", "otext", "b2a", "h2d",
                 "wire_queue", "wire_write", "peer_wait"):
        assert lines[name] == 2, name
    assert lines["d2h"] == 4
