"""The secure level as a stream of row chunks (protocol/rpc.py
``_ev_chunks`` / ``_gb_chunks``, ``secure.level_chunks``): a CPU pair
over real sockets, ``secure.CHUNK_FRAME_BYTES`` patched small so that a
level of a few planar blocks crosses in K > 1 chunks.

What is held: each server's shares and both OT cursors after a chunked
level are those of the level gone whole (K = 1), bit for bit, on both
equality paths and with either server garbling; the chunks' frames put
side by side are the whole level's two messages; ``secure_chunks``
counts K; a plane cut mid-level fails the verb, leaves no task behind
and the level run again gives the exact counts, and so does a chunk's
device program or fetch thread that fails; a stage runs ahead of the
device by its queues and no further; a peer that cut the level
differently gets ``ConnectionError`` and nobody hangs.
"""

import asyncio
import logging
import threading
import time

import jax
import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import gc_pallas, ibdcf
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.protocol import rpc, secure, wire
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 30731  # a range of its own (.. 31622: tests/test_resilience.py begins at 31631), under the ephemeral ports
WAITS_PORT = 32631  # a second one (.. 32702: above tests/test_stage_account.py's), for the tests of a chunk's waits
BLOCK = gc_pallas.R_BLK * gc_pallas.GROUP  # tests of one planar block
L = 3
WHOLE = 1 << 40  # a frame budget no level of these tests reaches


def _blocks(blocks, S=2, field=FE62, path="ot2s"):
    """The frame budget that cuts a level of string width ``S`` over
    ``field`` into chunks of ``blocks`` planar blocks: the larger
    frame's bytes a test, as ``secure.level_chunks`` counts them (an
    FE62 level at S = 2: 32 either way, the u rows' 16 * S and the
    table's 4 * 2^S * 2)."""
    from fuzzyheavyhitters_tpu.parallel.kernel_shard import n_msg_planes

    W = secure.payload_words(field)
    return blocks * BLOCK * max(16 * S, 4 * n_msg_planes(path, S, W))


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    yield


def _cfg(port, n_dims=1, **kw):
    return Config(
        data_len=L, n_dims=n_dims, ball_size=1, addkey_batch_size=1024,
        num_sites=4, threshold=0.2, zipf_exponent=1.03,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=64, secure_exchange=True, **kw,
    )


def _bits(pts):
    """int[n] (one dimension) or int[n, d] -> bool[n, d, L]."""
    return np.array([[bitutils.int_to_bits(L, int(v)) for v in np.atleast_1d(row)]
                     for row in pts])


def _keys(n, seed=7, pts=None):
    """``pts``: int[n] (one dimension, drawn from ``seed`` if not given)
    or int[n, d]."""
    rng = np.random.default_rng(seed)
    if pts is None:
        pts = rng.integers(0, 1 << L, size=n)
    return pts, ibdcf.gen_l_inf_ball(_bits(pts), 1, rng, engine="np")


def _root_counts(pts):
    """Clients whose ball [x-1, x+1] meets the left / the right half."""
    half = 1 << (L - 1)
    return np.array([np.sum(pts - 1 < half), np.sum(pts + 1 >= half)])


class _Pair:
    """Both servers, their clients and a leader in this process."""

    def __init__(self, port, n, pts=None, **cfg):
        self.port, self.n = port, n
        self.cfg = _cfg(port, n_dims=1 if pts is None else pts.shape[1], **cfg)
        self.pts, (self.k0, self.k1) = _keys(n, pts=pts)

    async def __aenter__(self):
        p = self.port
        self.s0 = rpc.CollectorServer(0, self.cfg)
        self.s1 = rpc.CollectorServer(1, self.cfg)
        t1 = asyncio.create_task(
            self.s1.start("127.0.0.1", p + 10, "127.0.0.1", p + 11)
        )
        await asyncio.sleep(0.05)
        t0 = asyncio.create_task(
            self.s0.start("127.0.0.1", p, "127.0.0.1", p + 11)
        )
        await asyncio.gather(t0, t1)
        self.c0 = await rpc.CollectorClient.connect("127.0.0.1", p)
        self.c1 = await rpc.CollectorClient.connect("127.0.0.1", p + 10)
        lead = RpcLeader(self.cfg, self.c0, self.c1)
        await lead._both("reset")
        await lead.upload_keys(self.k0, self.k1)
        return self

    async def __aexit__(self, *exc):
        for c in (self.c0, self.c1):
            await c.aclose()
        for s in (self.s0, self.s1):
            await s.aclose()

    @property
    def sessions(self):
        return self.s0._default(), self.s1._default()

    async def both(self, verb, req=None):
        return await asyncio.gather(
            self.c0.call(verb, req), self.c1.call(verb, req)
        )

    def ot_state(self):
        """Every cursor of both directions' OT sessions, and what seeds
        a level's randomness."""
        out = []
        for cs in self.sessions:
            for ep in (cs._ot_snd, cs._ot_rcv):
                out.append((ep.consumed, ep.stream_offset))
            out.append(cs._crawl_ctr)
        return out

    def set_ot_state(self, state):
        it = iter(state)
        for cs in self.sessions:
            for ep, ctr in ((cs._ot_snd, "_sent"), (cs._ot_rcv, "_recv")):
                sent, off = next(it)
                setattr(ep, ctr, sent)
                ep._off = off
            cs._crawl_ctr = next(it)

    async def level(self, garbler, last=False, path="auto"):
        verb = "tree_crawl_last" if last else "tree_crawl"
        req = {"level": L - 1 if last else 0, "garbler": garbler,
               "ot_path": path}
        return [np.asarray(v) for v in await self.both(verb, req)]


def _run(coro):
    return asyncio.run(coro)


def _plane_threads():
    """The live I/O threads of data planes (wire.PlaneStreams)."""
    return {t for t in threading.enumerate() if "-plane-" in t.name}


def _spy_frames(monkeypatch):
    """Every payload the servers put on the data plane, in order."""
    sent = []
    real = rpc._encode

    def spy(obj, reg=None, counter=None):
        if counter == "data_bytes_sent":
            sent.append(obj[1])
        return real(obj, reg, counter)

    monkeypatch.setattr(rpc, "_encode", spy)
    return sent


# B = F * 2 * N tests at the root level of a one-dimensional crawl
_SHAPES = {
    # one chunk exactly: the level goes whole
    "one_chunk": dict(f=4, n=1024, blocks=1, K=1),
    # one chunk and one planar block more
    "chunk_plus_block": dict(f=4, n=3072, blocks=2, K=2),
    # eight whole chunks
    "K8": dict(f=8, n=4096, blocks=1, K=8),
    # the last chunk ends inside a block, as the level does
    "ragged_tail": dict(f=4, n=2500, blocks=1, K=3),
}


@pytest.mark.parametrize("garbler", [0, 1])
@pytest.mark.parametrize("path", ["ot2s", "gc"])
@pytest.mark.parametrize("shape", list(_SHAPES))
def test_chunked_level_is_the_whole_level(monkeypatch, shape, path, garbler):
    """Shares, cursors and frames of a level in K chunks against the
    same level gone whole from the same OT state."""
    sh = _SHAPES[shape]
    port = BASE_PORT + 20 * (
        list(_SHAPES).index(shape) * 4 + (path == "gc") * 2 + garbler
    )
    sent = _spy_frames(monkeypatch)
    # a chunk of ``blocks`` planar blocks, by the larger frame's bytes
    S, W = 2, secure.payload_words(FE62)
    from fuzzyheavyhitters_tpu.parallel.kernel_shard import n_msg_planes

    small = _blocks(sh["blocks"], path=path)

    async def run():
        async with _Pair(port, sh["n"]) as pair:
            await pair.both("tree_init", {"root_bucket": sh["f"]})
            del sent[:]  # the session's handshake
            before = pair.ot_state()
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", WHOLE)
            whole = await pair.level(garbler, path=path)
            after_whole, frames_whole = pair.ot_state(), list(sent)
            chunks0 = [
                cs.obs.counter_value("secure_chunks", level=0)
                for cs in pair.sessions
            ]
            pair.set_ot_state(before)
            del sent[:]
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", small)
            cut = await pair.level(garbler, path=path)
            chunks1 = [
                cs.obs.counter_value("secure_chunks", level=0)
                for cs in pair.sessions
            ]
            return (whole, after_whole, frames_whole, cut,
                    pair.ot_state(), list(sent), chunks0, chunks1, pair.pts)

    (whole, after_whole, frames_whole, cut, after_cut, frames_cut,
     chunks0, chunks1, pts) = _run(run())
    K = sh["K"]
    # the same shares and the same cursors on each server
    for a, b in zip(whole, cut):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert after_cut == after_whole
    assert chunks0 == [1, 1] and chunks1 == [1 + K, 1 + K]
    # and they are shares of the exact counts
    got = np.asarray(FE62.canon(FE62.sub(cut[0], cut[1])))
    assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()
    # the frames: u then the table when whole, K of each in chunks
    assert len(frames_whole) == 2 and len(frames_cut) == 2 * K
    u_whole, msg_whole = sorted(frames_whole, key=lambda a: a.nbytes)
    if K == 1:
        u_cut, msg_cut = sorted(frames_cut, key=lambda a: a.nbytes)
        assert np.array_equal(u_cut, u_whole)
        assert np.array_equal(msg_cut, msg_whole)
        return
    assert all(isinstance(f, tuple) and f[1] == K for f in frames_cut)
    us = [f[2] for f in frames_cut if f[2].ndim == 2]
    msgs = [f[2] for f in frames_cut if f[2].ndim == 1]
    assert [f[0] for f in frames_cut if f[2].ndim == 2] == list(range(K))
    assert [f[0] for f in frames_cut if f[2].ndim == 1] == list(range(K))
    # u: the level's column words in order
    assert np.array_equal(np.concatenate(us, axis=1), u_whole)
    # the message: the level's planar blocks in order, plane by plane
    planes = n_msg_planes(path, S, W)
    assert np.array_equal(
        np.concatenate([m.reshape(planes, -1) for m in msgs], axis=1),
        msg_whole.reshape(planes, -1),
    )


def test_leaf_level_in_chunks(monkeypatch):
    """The leaf level (F255 shares, a table twice as wide a test) cut
    by the same byte budget: the whole level's shares."""
    port = BASE_PORT + 340
    small = BLOCK * 4 * (1 << 2) * secure.payload_words(F255)

    async def run():
        async with _Pair(port, 3072) as pair:
            await pair.both("tree_init", {"root_bucket": 4})
            before = pair.ot_state()
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", WHOLE)
            whole = await pair.level(1, last=True)
            pair.set_ot_state(before)
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", small)
            cut = await pair.level(1, last=True)
            ks = [
                cs.obs.counter_value("secure_chunks", level=L - 1)
                for cs in pair.sessions
            ]
            return whole, cut, ks

    whole, cut, ks = _run(run())
    assert ks == [1 + 3, 1 + 3]
    for a, b in zip(whole, cut):
        assert a.shape[-1] == 8 and np.array_equal(a, b)


@pytest.mark.parametrize("n_dims", [1, 2])
def test_a_levels_bytes_are_its_tests_at_the_width_of_its_field(n_dims):
    """What a level puts on the data plane: ``16 * S`` bytes of u rows a
    test one way and ``4 * 2^S * W`` of table the other, the table padded
    to whole planar blocks, plus framing; ``W`` is two words at an inner
    level (FE62) and eight at the leaf (F255), and the gauge
    ``secure_payload_words`` says which."""
    n, f = 1024, 4
    S, C = 2 * n_dims, 1 << n_dims
    B = f * C * n
    pts = None if n_dims == 1 else _points_2d(n)

    async def run():
        # (the range is full: these sit between the first pair's ports,
        # which are BASE_PORT, + 10 and + 11)
        async with _Pair(BASE_PORT + 2 + 2 * n_dims, n, pts=pts) as pair:
            await pair.both("tree_init", {"root_bucket": f})
            await pair.level(0)
            await pair.level(1, last=True)
            obs = [cs.obs for cs in pair.sessions]
            return [
                ([o.counter_value(f"data_bytes_{way}", level=lv) for o in obs
                  for way in ("sent", "recv")],
                 [o.gauge_value("secure_payload_words", level=lv) for o in obs],
                 [o.counter_value("secure_chunks", level=lv) for o in obs])
                for lv in (0, L - 1)
            ]

    inner, leaf = _run(run())
    for (nbytes, words, ks), W in ((inner, 2), (leaf, 8)):
        assert words == [W, W] and ks == [1, 1]
        assert W == secure.payload_words(FE62 if W == 2 else F255)
        u = B * 16 * S
        table = gc_pallas.padded_tests(B) * 4 * (1 << S) * W
        # each server sends one frame and receives the other: both
        # frames are counted at both ends, and a frame's header and
        # pickle are a few hundred bytes
        assert sum(nbytes) == 2 * sum(nbytes[0::2])
        over = sum(nbytes[0::2]) - (u + table)
        assert 0 < over < 2048, (nbytes, u, table)
    # the inner level's table is the u rows' size at S = 2 and twice it
    # at S = 4; the old four-word table was twice and four times
    assert gc_pallas.padded_tests(B) * 4 * (1 << S) * 2 == (S // 2) * B * 16 * S


@pytest.mark.parametrize("when", ["before_chunk_1", "two_frames_out"])
def test_plane_cut_mid_level_fails_the_verb_and_the_retry_is_exact(monkeypatch, when):
    """The plane closed under chunk 1's frame, before its hand-over or
    right after it, with two frames of the stage with the writer thread
    and neither waited for yet: both verbs fail, no chunk task and no
    I/O thread of that plane is left, and after a plane reset (four new
    threads) the same level gives the exact counts."""
    port = BASE_PORT + (360 if when == "before_chunk_1" else 320)
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1))
    real = rpc.CollectorServer._dp_send_begin
    cut = {"armed": False}
    # the writer threads held before a write, for the second case: so
    # that chunk 0's frame is still whole with its thread when chunk
    # 1's is handed over, whatever the host's speed
    gate, send_all = threading.Event(), wire._send_all
    gate.set()

    def gated(sock, pieces):  # on a writer thread
        gate.wait(30)
        send_all(sock, pieces)

    monkeypatch.setattr(wire, "_send_all", gated)

    async def cutting(self, cs, obj):
        # the evaluating server's u, the level's first frames
        k = obj[0] if (
            cut["armed"] and isinstance(obj, tuple) and self.server_id == 1
        ) else None
        if k == 1 and when == "before_chunk_1":
            cut["armed"] = False
            self._peer.close()
        if k == 0 and when == "two_frames_out":
            gate.clear()
        out = await real(self, cs, obj)
        if k == 1 and when == "two_frames_out":
            # chunk 0's frame and this one, neither written, neither
            # waited for
            assert out.held == self._peer.waiting == 2
            assert not out.done.done()
            cut["armed"] = False
            self._peer.close()
            gate.set()
        return out

    monkeypatch.setattr(rpc.CollectorServer, "_dp_send_begin", cutting)

    async def run():
        async with _Pair(port, 4096) as pair:
            await pair.both("tree_init", {"root_bucket": 4})
            cut["armed"] = True
            tasks0 = asyncio.all_tasks()
            threads0 = _plane_threads()
            res = await asyncio.wait_for(
                asyncio.gather(
                    pair.c0.call("tree_crawl", {"level": 0, "garbler": 0}),
                    pair.c1.call("tree_crawl", {"level": 0, "garbler": 0}),
                    return_exceptions=True,
                ),
                60,
            )
            await asyncio.sleep(0.05)
            # a new connection's pump and handler may come, a chunk task
            # may not stay
            left = [
                t for t in asyncio.all_tasks() - tasks0
                if "_chunks" in repr(t.get_coro())
            ]
            for t in threads0:
                await asyncio.to_thread(t.join, 10)
            threads = [len(threads0), len(_plane_threads())]
            await pair.both("plane_reset")
            again = await pair.level(0)
            threads.append(len(_plane_threads() - threads0))
            ks = [
                cs.obs.counter_value("secure_chunks", level=0)
                for cs in pair.sessions
            ]
            return res, left, again, ks, pair.pts, threads

    res, left, again, ks, pts, threads = _run(run())
    assert all(isinstance(r, Exception) for r in res), res
    assert not cut["armed"] and not left
    assert threads == [4, 0, 4]
    assert ks == [8, 8]  # four chunks each time
    got = np.asarray(FE62.canon(FE62.sub(again[0], again[1])))
    assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()


def _fail_second(real, armed, mine=lambda *a, **kw: True):
    """``real``, raising on the second of its calls that ``mine`` picks
    once ``armed["calls"]`` is a number: the level's chunk 1."""

    def failing(*args, **kw):
        if armed["calls"] is not None and mine(*args, **kw):
            armed["calls"] += 1
            if armed["calls"] == 2:
                armed["calls"] = None
                raise RuntimeError("injected into chunk 1")
        return real(*args, **kw)

    return failing


@pytest.mark.parametrize("fault", ["device_program", "fetch_thread"])
def test_chunk_fault_mid_level_fails_both_verbs_and_the_retry_is_exact(monkeypatch, fault):
    """Chunk 1 of 4 fails on the garbling server: the table's program
    where ``build`` hands it to the device, or the thread that waits for
    the chunk's programs and copies (nobody awaits either before the
    fetch stage does).  That server's verb fails with the injected
    error and every sibling task goes with it; its peer, which waits
    for a message that will not come, fails when the plane is broken
    (the leader's quiesce), nobody hangs, no chunk task is left, and
    after a plane reset the same level gives the exact counts."""
    port = BASE_PORT + 880
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1))
    armed = {"calls": None}
    if fault == "device_program":
        monkeypatch.setattr(
            secure, "gb_chunk_table", _fail_second(secure.gb_chunk_table, armed))
    else:
        # the garbling server's fetch: two programs to wait for
        monkeypatch.setattr(rpc, "_fetch_on_thread", _fail_second(
            rpc._fetch_on_thread, armed,
            lambda x, waits=(), note=None: len(waits) == 2))

    async def run():
        async with _Pair(port, 4096) as pair:
            await pair.both("tree_init", {"root_bucket": 4})
            armed["calls"] = 0
            tasks0 = asyncio.all_tasks()
            calls = [
                asyncio.ensure_future(
                    c.call("tree_crawl", {"level": 0, "garbler": 0}))
                for c in (pair.c0, pair.c1)
            ]
            done, _ = await asyncio.wait(
                calls, timeout=60, return_when=asyncio.FIRST_COMPLETED)
            first = [c in done for c in calls]
            await pair.both("plane_break")
            res = await asyncio.wait_for(
                asyncio.gather(*calls, return_exceptions=True), 60)
            await asyncio.sleep(0.05)
            left = [
                t for t in asyncio.all_tasks() - tasks0
                if "_chunks" in repr(t.get_coro())
            ]
            await pair.both("plane_reset")
            again = await pair.level(0)
            ks = [
                cs.obs.counter_value("secure_chunks", level=0)
                for cs in pair.sessions
            ]
            return first, res, left, again, ks, pair.pts

    first, res, left, again, ks, pts = _run(run())
    assert first == [True, False]  # the garbling server's verb, at once
    assert all(isinstance(r, Exception) for r in res), res
    assert "injected into chunk 1" in str(res[0]), res
    assert armed["calls"] is None and not left
    assert ks == [8, 8]  # four chunks each time
    got = np.asarray(FE62.canon(FE62.sub(again[0], again[1])))
    assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()


def test_a_stage_runs_ahead_of_the_device_by_its_queues_and_no_further(monkeypatch):
    """K = 32: ``build`` and ``extend`` await nothing between a chunk's
    dispatch and its ``put``, so they run ahead of the device, but only
    as far as the queues that were there let them: chunks dispatched
    less chunks handed to the data plane never pass the two queues of
    two (``built`` / ``made``, ``fetched``) and the one in each of the
    two stages' hands."""
    n, K = 4096, 32
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1))
    ahead = {"gb": [0, 0], "ev": [0, 0]}  # role: now, the high-water mark

    def counting(role, real):
        def spy(*args, **kw):
            ahead[role][0] += 1
            ahead[role][1] = max(ahead[role])
            return real(*args, **kw)
        return spy

    monkeypatch.setattr(
        secure, "gb_chunk_table", counting("gb", secure.gb_chunk_table))
    monkeypatch.setattr(
        secure, "ev_chunk_extend", counting("ev", secure.ev_chunk_extend))
    real_send = rpc.CollectorServer._dp_send_begin

    async def handed(self, cs, obj):
        if isinstance(obj, tuple) and obj[1] == K:
            ahead["gb" if obj[2].ndim == 1 else "ev"][0] -= 1
        return await real_send(self, cs, obj)

    monkeypatch.setattr(rpc.CollectorServer, "_dp_send_begin", handed)

    async def run():
        async with _Pair(WAITS_PORT + 60, n) as pair:
            await pair.both("tree_init", {"root_bucket": 32})
            shares = await pair.level(0, path="ot2s")
            ks = [cs.obs.counter_value("secure_chunks", level=0)
                  for cs in pair.sessions]
            return shares, ks, pair.pts

    shares, ks, pts = _run(run())
    assert ks == [K, K]
    for role in ("gb", "ev"):
        now, high = ahead[role]
        assert now == 0 and 1 <= high <= 2 + 2 + 2, (role, ahead)
    got = np.asarray(FE62.canon(FE62.sub(shares[0], shares[1])))
    assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()


def test_a_send_stage_keeps_two_frames_with_the_writer_and_no_third(monkeypatch):
    """K = 8 with the garbling server's reader thread held inside the
    first u (and the sockets of that direction too small for a frame):
    the evaluator's send stage has handed over exactly ``FRAMES_OUT``
    frames, the one on the socket and one behind it, and no third;
    ``fetched`` holds ``STAGE_QUEUE`` chunks and the fetch stage the
    next; and when the reader goes on, the level ends with the shares
    of the whole level, bit for bit."""
    import socket

    cls = rpc.CollectorServer
    assert cls.FRAMES_OUT == 2
    hold, real_buffer = threading.Event(), wire._recv_buffer
    hold.set()

    def held_buffer(size, reg=None):  # on a reader thread
        if threading.current_thread().name.startswith("server0-"):
            hold.wait(60)
        return real_buffer(size, reg)

    monkeypatch.setattr(wire, "_recv_buffer", held_buffer)
    seen = {"begun": 0, "fetched": 0}
    real_begin, real_taken = cls._dp_send_begin, cls._fetch_taken

    async def begin(self, cs, obj):
        out = await real_begin(self, cs, obj)
        seen["begun"] += self.server_id == 1 and isinstance(obj, tuple)
        return out

    async def taken(self, cs, level, stage, k, fut):
        arr = await real_taken(self, cs, level, stage, k, fut)
        seen["fetched"] += stage == "u_fetch"
        return arr

    monkeypatch.setattr(cls, "_dp_send_begin", begin)
    monkeypatch.setattr(cls, "_fetch_taken", taken)

    async def run():
        async with _Pair(WAITS_PORT + 80, 4096) as pair:
            await pair.both("tree_init", {"root_bucket": 8})
            before = pair.ot_state()
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", WHOLE)
            whole = await pair.level(0, path="ot2s")
            pair.set_ot_state(before)
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1))
            # u's direction: server 1 writes, server 0 reads
            for sock, opt in ((pair.s1._peer._socks[0], socket.SO_SNDBUF),
                              (pair.s0._peer._socks[1], socket.SO_RCVBUF)):
                sock.setsockopt(socket.SOL_SOCKET, opt, 1 << 14)
            seen.update(begun=0, fetched=0)  # the whole level's
            hold.clear()
            level = asyncio.ensure_future(pair.level(0, path="ot2s"))
            want = (cls.FRAMES_OUT, cls.FRAMES_OUT + cls.STAGE_QUEUE + 1)
            for _ in range(600):
                if (seen["begun"], seen["fetched"]) == want:
                    break
                await asyncio.sleep(0.05)
            await asyncio.sleep(0.5)  # and no third, however long it waits
            stalled = (seen["begun"], seen["fetched"], pair.s1._peer.waiting,
                       level.done())
            hold.set()
            cut = await asyncio.wait_for(level, 60)
            ev = pair.sessions[1].obs
            return (whole, cut, want, stalled, dict(seen), [
                ev.counter_value(n, level=0)
                for n in ("secure_chunks", "plane_stream_frames",
                          "plane_sends_overlapped")
            ], ev.gauge_value("plane_send_queue_high", level=0))

    whole, cut, want, stalled, seen, counts, high = _run(run())
    assert stalled == (*want, cls.FRAMES_OUT, False), stalled
    assert seen == {"begun": 8, "fetched": 8}
    # the whole level's one frame and the eight; of these every one but
    # the first was handed over behind another at the most
    assert counts[:2] == [1 + 8, 1 + 8] and 1 <= counts[2] <= 7, counts
    assert high == 2
    for a, b in zip(whole, cut):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_on_sent_follows_each_frame_in_chunk_order(monkeypatch):
    """K = 32: ``on_sent`` is awaited once a chunk, with that chunk's
    token, in chunk order, and never before the writer thread ended
    that chunk's frame, though the next frame is handed over before;
    the evaluator's unopened chunks (``secure_t_rows_held_bytes``) stay
    within the ``CHUNKS_AHEAD`` of its queue and the one that waits to
    get in."""
    cls = rpc.CollectorServer
    n, S, K = 4096, 2, 32
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1))
    rows, ends, calls = [], [], []
    real_extend, real_finish = secure.ev_chunk_extend, cls._dp_send_finish
    real_senders = cls._chunk_senders

    # the evaluator's writer thread begins a frame only when the next is
    # queued behind it, so every frame but the first is handed over
    # beside another whatever the host's speed
    behind, last = threading.Event(), threading.Event()
    last.set()  # (until the level: nothing comes behind a handshake's frame)
    real_begin, send_all = cls._dp_send_begin, wire._send_all

    def gated(sock, pieces):  # on a writer thread
        if threading.current_thread().name.startswith("server1-"):
            if not last.is_set():
                behind.wait(30)
                behind.clear()
        send_all(sock, pieces)

    async def begin(self, cs, obj):
        out = await real_begin(self, cs, obj)
        if self.server_id == 1 and isinstance(obj, tuple):
            if obj[0] == K - 1:
                last.set()
            if out.held == 2 or last.is_set():
                behind.set()
        return out

    def extend(*args, **kw):
        u, t_rows, y = real_extend(*args, **kw)
        rows.append(t_rows)
        return u, t_rows, y

    async def finish(self, out):
        t_begin, t_end = await real_finish(self, out)
        if self.server_id == 1:
            ends.append(t_end)
        return t_begin, t_end

    def senders(self, cs, level, K, made, stages, on_sent=None):
        async def spy(token):
            # the frames whose end the stage has seen, and the clock
            calls.append((token, len(ends), time.time()))
            await on_sent(token)

        return real_senders(
            self, cs, level, K, made, stages, on_sent and spy)

    monkeypatch.setattr(secure, "ev_chunk_extend", extend)
    monkeypatch.setattr(wire, "_send_all", gated)
    monkeypatch.setattr(cls, "_dp_send_begin", begin)
    monkeypatch.setattr(cls, "_dp_send_finish", finish)
    monkeypatch.setattr(cls, "_chunk_senders", senders)

    async def run():
        async with _Pair(WAITS_PORT + 100, n) as pair:
            await pair.both("tree_init", {"root_bucket": 32})
            del rows[:], ends[:], calls[:]
            last.clear()
            shares = await pair.level(0, path="ot2s")
            ev = pair.sessions[1].obs
            return shares, pair.pts, [
                ev.counter_value("plane_sends_overlapped", level=0),
                ev.gauge_value("secure_t_rows_held_bytes", level=0),
            ]

    shares, pts, (over, held) = _run(run())
    assert len(rows) == len(ends) == len(calls) == K
    for k, (token, seen, at) in enumerate(calls):
        # chunk k's token, after chunk k's frame and before the next's
        # end was seen, the writer thread done with it
        assert token[1] is rows[k] and seen == k + 1 and at >= ends[k], k
    assert ends == sorted(ends) and over == K - 1
    token = BLOCK * S * (1 + 16)
    assert token <= held <= (cls.CHUNKS_AHEAD + 1) * token
    got = np.asarray(FE62.canon(FE62.sub(shares[0], shares[1])))
    assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()


def test_a_fault_in_the_recording_of_a_fetch_resolves_it_and_the_level_is_exact(monkeypatch):
    """The callback that records a chunk's stamps raises (chunk 1 on
    either server): the fetch stage gets its array all the same, the
    level is exact, and the fault is counted (``secure_account_errors``;
    the run report's ``account_errors``) and logged."""
    from fuzzyheavyhitters_tpu.obs import report as obsreport

    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1))
    armed = {"calls": None}
    monkeypatch.setattr(
        rpc.CollectorServer, "_record_fetch", staticmethod(_fail_second(
            rpc.CollectorServer._record_fetch, armed,
            lambda reg, *a: reg.name == "server0")))

    async def run():
        async with _Pair(WAITS_PORT, 4096) as pair:
            await pair.both("tree_init", {"root_bucket": 4})
            armed["calls"] = 0
            shares = await asyncio.wait_for(pair.level(0), 120)
            regs = [cs.obs for cs in pair.sessions]
            return shares, regs, obsreport.run_report(regs), pair.pts

    shares, regs, rep, pts = _run(run())
    assert armed["calls"] is None
    assert [r.counter_value("secure_account_errors", level=0) for r in regs] == [1, 0]
    assert rep["secure_kernels"]["account_errors"] == 1
    # the chunk whose recording failed is not in the count of syncs
    assert regs[0].counter_value("secure_fetch_syncs", level=0) == 2 * 3
    got = np.asarray(FE62.canon(FE62.sub(shares[0], shares[1])))
    assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()


def test_a_fetch_thread_that_does_not_return_fails_both_verbs_within_the_bound(monkeypatch):
    """Chunk 1's fetch on the garbling server parks for ever (a thread
    call that never comes back from the device): ``msg_fetch`` gives up
    at the bound the plane gives a silent peer (shortened here), its
    verb fails with the stage and chunk it stood in, the peer's fails
    with the leader's quiesce, no chunk task is left, and after a plane
    reset the same level gives the exact counts."""
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1))
    release = threading.Event()
    armed = {"calls": None}
    real = rpc._fetch_on_thread

    def parked(x, waits=(), note=None):
        if armed["calls"] is not None and len(waits) == 2:
            armed["calls"] += 1
            if armed["calls"] == 2:
                armed["calls"] = None
                release.wait(120)
        return real(x, waits, note)

    monkeypatch.setattr(rpc, "_fetch_on_thread", parked)

    async def run():
        async with _Pair(WAITS_PORT + 20, 4096) as pair:
            await pair.both("tree_init", {"root_bucket": 4})
            before = pair.ot_state()
            await pair.level(0)  # every program compiled, under the real bound
            pair.set_ot_state(before)
            keepalive = rpc.CollectorServer.PLANE_KEEPALIVE
            monkeypatch.setattr(rpc.CollectorServer, "PLANE_KEEPALIVE", (1, 1, 2))
            bound = rpc.CollectorServer._plane_silence_s()
            armed["calls"] = 0
            tasks0 = asyncio.all_tasks()
            loop = asyncio.get_running_loop()
            t0 = loop.time()
            calls = [
                asyncio.ensure_future(
                    c.call("tree_crawl", {"level": 0, "garbler": 0}))
                for c in (pair.c0, pair.c1)
            ]
            done, _ = await asyncio.wait(
                calls, timeout=bound + 30, return_when=asyncio.FIRST_COMPLETED)
            took = loop.time() - t0
            first = [c in done for c in calls]
            await pair.both("plane_break")
            res = await asyncio.wait_for(
                asyncio.gather(*calls, return_exceptions=True), 60)
            await asyncio.sleep(0.05)
            left = [
                t for t in asyncio.all_tasks() - tasks0
                if "_chunks" in repr(t.get_coro())
            ]
            timeouts = pair.sessions[0].obs.counter_value(
                "device_wait_timeouts", level=0)
            release.set()  # the thread comes back to a level that is over
            monkeypatch.setattr(rpc.CollectorServer, "PLANE_KEEPALIVE", keepalive)
            await pair.both("plane_reset")
            again = await pair.level(0)
            return bound, took, first, res, left, timeouts, again, pair.pts

    try:
        bound, took, first, res, left, timeouts, again, pts = _run(run())
    finally:
        release.set()
    assert first == [True, False]  # the garbling server's verb, by itself
    assert bound == 3.0 and bound <= took < bound + 20, took
    assert all(isinstance(r, Exception) for r in res), res
    assert "msg_fetch waited 3 s for chunk 1" in str(res[0]), res
    assert timeouts == 1 and not left
    got = np.asarray(FE62.canon(FE62.sub(again[0], again[1])))
    assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()


def test_a_level_of_32_chunks_on_two_threads_each_is_the_whole_level(monkeypatch):
    """The small-host case: each server's device-wait threads AND the
    loop's default executor held to two workers.  A level of K = 32
    finishes, its shares and cursors are the whole level's bit for bit,
    and the waits that found no thread free show in
    ``device_waits_high``."""
    import concurrent.futures

    n, K, small = 4096, 32, _blocks(1)

    async def run():
        asyncio.get_running_loop().set_default_executor(
            concurrent.futures.ThreadPoolExecutor(2))
        async with _Pair(WAITS_PORT + 40, n) as pair:
            for s in (pair.s0, pair.s1):
                s._waits.close()
                s._waits = rpc._DeviceWaits(s.obs.name, 2)
            await pair.both("tree_init", {"root_bucket": 32})
            before = pair.ot_state()
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", WHOLE)
            whole = await pair.level(0, path="ot2s")
            after_whole = pair.ot_state()
            pair.set_ot_state(before)
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", small)
            cut = await asyncio.wait_for(pair.level(0, path="ot2s"), 300)
            ks = [cs.obs.counter_value("secure_chunks", level=0)
                  for cs in pair.sessions]
            highs = [cs.obs.gauge_value("device_waits_high", level=0)
                     for cs in pair.sessions]
            threads = [cs.obs.gauge_value("device_wait_threads", level=0)
                       for cs in pair.sessions]
            return whole, after_whole, cut, pair.ot_state(), ks, highs, threads, pair.pts

    whole, after_whole, cut, after_cut, ks, highs, threads, pts = _run(run())
    assert ks == [1 + K, 1 + K] and threads == [2, 2]
    # in flight at once: never more than a level can park
    # (``DEVICE_WAITS``), whatever the threads there are for them
    assert all(1 <= h <= rpc.CollectorServer.DEVICE_WAITS for h in highs), highs
    for a, b in zip(whole, cut):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert after_cut == after_whole
    got = np.asarray(FE62.canon(FE62.sub(cut[0], cut[1])))
    assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()


def test_peer_with_another_cut_gets_connection_error(monkeypatch):
    """One server cuts the level in four, its peer in two: the plane
    fails, both verbs return an error, and nothing waits for ever."""
    port = BASE_PORT + 380
    real = secure.level_chunks
    calls = []

    def uneven(B, S, W, path):
        calls.append(B)
        monkeypatch.setattr(
            secure, "CHUNK_FRAME_BYTES", _blocks(1 + len(calls) % 2)
        )
        return real(B, S, W, path)

    monkeypatch.setattr(secure, "level_chunks", uneven)

    async def run():
        async with _Pair(port, 4096) as pair:
            await pair.both("tree_init", {"root_bucket": 4})
            res = await asyncio.wait_for(
                asyncio.gather(
                    pair.c0.call("tree_crawl", {"level": 0, "garbler": 0}),
                    pair.c1.call("tree_crawl", {"level": 0, "garbler": 0}),
                    return_exceptions=True,
                ),
                60,
            )
            return res, [
                cs.obs.counter_value("secure_chunks", level=0)
                for cs in pair.sessions
            ]

    res, ks = _run(run())
    assert sorted(ks) == [2, 4]
    assert all(isinstance(r, Exception) for r in res), res
    assert any("ConnectionError" in str(r) and "chunk" in str(r) for r in res), res


@pytest.mark.parametrize(
    "B,S,W,path,want",
    [
        # the flagship's steady bucket: 32 nodes x 2 patterns x 16,384;
        # an FE62 payload is two words, so table and u rows weigh the
        # same 32 bytes a test and a frame holds 524,288 tests
        (32 * 2 * 16384, 2, 2, "ot2s", [524288] * 2),
        (16 * 2 * 16384, 2, 2, "ot2s", [524288]),
        (64 * 2 * 16384, 2, 2, "ot2s", [524288] * 4),
        # buckets 2-8 go whole
        (8 * 2 * 16384, 2, 2, "ot2s", [262144]),
        (2 * 2 * 16384, 2, 2, "ot2s", [65536]),
        # the same buckets at N = 131,072 (the hbm cell): K = 16 and 32
        (32 * 2 * 131072, 2, 2, "ot2s", [524288] * 16),
        (64 * 2 * 131072, 2, 2, "ot2s", [524288] * 32),
        # the leaf level's F255 table is four times as wide a test
        (32 * 2 * 16384, 2, 8, "ot2s", [131072] * 8),
        (32 * 2 * 131072, 2, 8, "ot2s", [131072] * 64),
        # two dimensions (S = 4, four patterns a node) at N = 131,072: a
        # 1-of-16 table of 128 bytes a test, 131,072 tests a frame, and
        # 512 bytes and 32,768 at the leaf level
        (32 * 4 * 131072, 4, 2, "ot2s", [131072] * 128),
        (64 * 4 * 131072, 4, 2, "ot2s", [131072] * 256),
        (32 * 4 * 131072, 4, 8, "ot2s", [32768] * 512),
        # the geo cell's one-node levels: 4 patterns x 131,072 clients
        (1 * 4 * 131072, 4, 2, "ot2s", [131072] * 4),
        # the garbled batch of S = 8: 93 words a test (105 at the leaf)
        (1 << 20, 8, 2, "gc", [40960] * 25 + [24576]),
        (1 << 20, 8, 8, "gc", [32768] * 32),
        # a tail that is no whole block
        (524288 + 5, 2, 2, "ot2s", [524288, 5]),
    ],
)
def test_level_chunks_from_the_level_dimensions(B, S, W, path, want):
    """K and the boundaries at the shipped 16 MiB: whole planar blocks,
    the larger frame at most the budget, every test once, and every cut
    a whole block of the b2a stream's draw (``field.SAMPLE_WORDS``: 4
    or 8 words a test, whatever the wire's ``W``)."""
    from fuzzyheavyhitters_tpu.parallel.kernel_shard import n_msg_planes

    assert secure.CHUNK_FRAME_BYTES == 16 << 20
    chunks = secure.level_chunks(B, S, W, path)
    assert [n for _, n in chunks] == want
    assert [t0 for t0, _ in chunks] == list(np.cumsum([0] + want[:-1]))
    assert all(t0 % BLOCK == 0 for t0, _ in chunks)
    assert all(t0 * S % 512 == 0 and t0 * 4 % 16 == 0 for t0, _ in chunks)
    per_test = max(16 * S, 4 * n_msg_planes(path, S, W))
    assert max(want) * per_test <= secure.CHUNK_FRAME_BYTES


# -- the 64-bit OT index, and levels of many chunks --------------------------

EDGE = 1 << 32  # where a 32-bit pad index would wrap


def _pin_sessions(pair, seed=11):
    """Both directions' OT sessions and both servers' level seeds made
    from ``seed`` instead of the system's randomness, so that a level's
    frames and shares are the same bytes run after run."""
    from fuzzyheavyhitters_tpu.ops import otext

    rng = np.random.default_rng(seed)
    cs = pair.sessions
    for g in (0, 1):
        s_bits = rng.integers(0, 2, 128).astype(bool)
        s_bits[0] = True
        seeds0, seeds1 = (
            rng.integers(0, 1 << 32, (128, 4), dtype=np.uint32) for _ in "01"
        )
        cs[g]._ot_snd = otext.OtExtSender(
            s_bits, np.where(s_bits[:, None], seeds1, seeds0)
        )
        cs[1 - g]._ot_rcv = otext.OtExtReceiver(seeds0, seeds1)
    for i, c in enumerate(cs):
        c._ot = (c._ot_snd, c._ot_rcv)
        c._sec_seed = np.arange(4, dtype=np.uint32) + np.uint32(100 * i + seed)
        c._crawl_ctr = 0


def _set_cursors(pair, consumed):
    """Every OT endpoint's pad index set to ``consumed``; the column
    streams stay where they are."""
    for cs in pair.sessions:
        cs._ot_snd._sent = cs._ot_rcv._recv = consumed


def _interpret_engines(monkeypatch):
    """The chip's engine for the 1-of-2^S table on the CPU: the Pallas
    kernels in interpret mode, through the dispatchers the servers
    call."""
    import functools

    from fuzzyheavyhitters_tpu.ops import otext_pallas

    monkeypatch.setattr(secure, "_ot2s_pallas_engine", lambda: True)
    for name in ("ot2s_encrypt", "ot2s_decrypt",
                 "ot2s_encrypt_planes", "ot2s_decrypt_planes"):
        monkeypatch.setattr(
            otext_pallas, name,
            functools.partial(getattr(otext_pallas, name), interpret=True),
        )


def _digest(frames, shares):
    import hashlib

    h = hashlib.sha256()
    for f in frames:
        h.update(np.ascontiguousarray(f[2] if isinstance(f, tuple) else f))
    for v in shares:
        h.update(np.ascontiguousarray(v))
    return h.hexdigest()


async def _three_levels(pair, monkeypatch, sent, start, frame_bytes):
    """Levels garbled by server 0, 1, 0 from pinned sessions whose pad
    indices stand at ``start``: (frames, shares) of each, then every
    cursor."""
    await pair.both("tree_init", {"root_bucket": 4})
    _pin_sessions(pair)
    _set_cursors(pair, start)
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", frame_bytes)
    out = []
    for g in (0, 1, 0):
        del sent[:]
        shares = await pair.level(g, path="ot2s")
        out.append((list(sent), shares))
    return out, pair.ot_state()


_INDEX_N = 3072              # B = 4 * 2 * 3072 tests = 3 planar blocks
_INDEX_ROWS = 4 * 2 * _INDEX_N * 2
_TWO_BLOCKS = _blocks(2)  # 32 bytes a test, u rows and table alike
# frames and shares of the three levels below, with the pad indices
# starting 4 levels under 2^32, so that none passes it.  Recorded on the
# FOUR-WORD code (commit 67a3f69, the parent of the payload at the width
# of its field), whose own frames and shares hashed to what the parent
# of the 64-bit index recorded (commit ff34183: the index a uint32), with
# every table frame cut to planes c*4 + {0, 1} of its 16 before it was
# hashed: what a two-word table must be if the kept words did not move
_RECORDED = {
    "whole": "d2910c5f7c5b1872a5e687758bbb8c0f9e57aacf4fa70398eab087196ba5d685",
    "K2": "5aa7e101ebd5b1d678847afa001871c74a58de7be707205cf00d485032337e1b",
}


@pytest.mark.parametrize("engine", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("flow", ["whole", "K2"])
def test_levels_under_2_32_are_the_recorded_bytes(monkeypatch, flow, engine):
    """An index under 2^32 hashes as it did when the index was 32 bits
    wide and the payload four words: shares byte for byte, u frames
    byte for byte, the table the kept planes of that table, on either
    engine."""
    if engine == "pallas_interpret":
        _interpret_engines(monkeypatch)
    sent = _spy_frames(monkeypatch)
    port = BASE_PORT + 400 + 20 * (2 * (flow == "K2") + (engine != "xla"))

    async def run():
        async with _Pair(port, _INDEX_N) as pair:
            return await _three_levels(
                pair, monkeypatch, sent, EDGE - 4 * _INDEX_ROWS,
                WHOLE if flow == "whole" else _TWO_BLOCKS,
            )

    levels, state = _run(run())
    assert all(c < EDGE for c, _ in state[0:2] + state[3:5])
    got = _digest([f for fr, _ in levels for f in fr],
                  [v for _, sh in levels for v in sh])
    assert got == _RECORDED[flow]


@pytest.mark.parametrize("engine", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("flow", ["whole", "K2"])
def test_levels_across_2_32_keep_exact_shares_and_lockstep(monkeypatch, flow, engine):
    """Pad indices that start half a level under 2^32: the first level
    of each direction crosses inside its first chunk (in the middle of a
    kernel's planar block) and every later base is itself past 2^32.
    Shares reconstruct to the exact counts, both endpoints of each
    session stay in lockstep and ``ot_index_high`` says 1 (that a
    crossed index is not the wrapped one: tests/test_otext.py)."""
    if engine == "pallas_interpret":
        _interpret_engines(monkeypatch)
    sent = _spy_frames(monkeypatch)
    port = BASE_PORT + 500 + 20 * (2 * (flow == "K2") + (engine != "xla"))
    start = EDGE - _INDEX_ROWS // 8 * 3  # 1.5 planar blocks of tests under

    async def run():
        async with _Pair(port, _INDEX_N) as pair:
            levels, state = await _three_levels(
                pair, monkeypatch, sent, start,
                WHOLE if flow == "whole" else _TWO_BLOCKS,
            )
            high = [
                (cs.obs.gauge_value("ot_index_high"),
                 cs.obs.gauge_max("ot_index_high"))
                for cs in pair.sessions
            ]
            return levels, state, high, pair.pts

    levels, state, high, pts = _run(run())
    for frames, shares in levels:
        got = np.asarray(FE62.canon(FE62.sub(shares[0], shares[1])))
        assert np.array_equal(got[0], _root_counts(pts)) and not got[1:].any()
        assert len(frames) == (2 if flow == "whole" else 4)
    # server 0's sender with server 1's receiver, and the reverse
    (s0_snd, s0_rcv, _, s1_snd, s1_rcv, _) = state
    assert s0_snd == s1_rcv and s1_snd == s0_rcv
    assert s0_snd[0] == start + 2 * _INDEX_ROWS > EDGE
    assert s1_snd[0] == start + _INDEX_ROWS > EDGE
    assert high == [(1, 1), (1, 1)]


def test_chunked_and_whole_agree_across_2_32_on_both_engines(monkeypatch):
    """One level across the boundary four ways (whole or in two chunks,
    XLA twin or the Pallas kernels in interpret mode) from the same
    pinned sessions: the same shares, and the chunks' frames side by
    side are the whole level's."""
    sent = _spy_frames(monkeypatch)
    start = EDGE - _INDEX_ROWS // 8 * 3

    async def run():
        out = {}
        async with _Pair(BASE_PORT + 600, _INDEX_N) as pair:
            for engine in ("xla", "pallas_interpret"):
                if engine == "pallas_interpret":
                    _interpret_engines(monkeypatch)
                for flow, budget in (("whole", WHOLE), ("K2", _TWO_BLOCKS)):
                    levels, _ = await _three_levels(
                        pair, monkeypatch, sent, start, budget
                    )
                    out[engine, flow] = levels[0]
        return out

    out = _run(run())
    want_frames, want_shares = out["xla", "whole"]
    u_whole, msg_whole = sorted(want_frames, key=lambda a: a.nbytes)
    for (engine, flow), (frames, shares) in out.items():
        for a, b in zip(want_shares, shares):
            assert np.array_equal(a, b), (engine, flow)
        if flow == "whole":
            u, msg = sorted(frames, key=lambda a: a.nbytes)
        else:
            u = np.concatenate([f[2] for f in frames if f[2].ndim == 2], axis=1)
            msg = np.concatenate(
                [f[2].reshape(8, -1) for f in frames if f[2].ndim == 1], axis=1
            ).reshape(-1)
        assert np.array_equal(u, u_whole), (engine, flow)
        assert np.array_equal(msg, msg_whole), (engine, flow)


@pytest.mark.parametrize("K,f", [(32, 32), (64, 64)])
def test_level_of_many_chunks_is_the_whole_level(monkeypatch, K, f):
    """The flagship's K at N = 131,072 (32 chunks at bucket 32, 64 at
    bucket 64), here chunks of one planar block: the whole level's two
    messages and shares bit for bit, and the evaluator never holds more
    T rows on the device than its queue of unopened chunks allows."""
    from fuzzyheavyhitters_tpu.parallel.kernel_shard import n_msg_planes

    sent = _spy_frames(monkeypatch)
    n, S, W = 4096, 2, secure.payload_words(FE62)

    async def run():
        async with _Pair(BASE_PORT + 620 + 20 * (K == 64), n) as pair:
            await pair.both("tree_init", {"root_bucket": f})
            del sent[:]
            before = pair.ot_state()
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", WHOLE)
            whole = await pair.level(0, path="ot2s")
            after_whole, frames_whole = pair.ot_state(), list(sent)
            pair.set_ot_state(before)
            del sent[:]
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1))
            cut = await pair.level(0, path="ot2s")
            held = [cs.obs.gauge_value("secure_t_rows_held_bytes", level=0)
                    for cs in pair.sessions]
            ks = [cs.obs.counter_value("secure_chunks", level=0)
                  for cs in pair.sessions]
            return (whole, after_whole, frames_whole, cut, pair.ot_state(),
                    list(sent), held, ks)

    (whole, after_whole, frames_whole, cut, after_cut, frames_cut, held,
     ks) = _run(run())
    assert f * 2 * n == K * BLOCK and ks == [1 + K, 1 + K]
    for a, b in zip(whole, cut):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert after_cut == after_whole
    u_whole, msg_whole = sorted(frames_whole, key=lambda a: a.nbytes)
    assert len(frames_cut) == 2 * K
    us = [fr for fr in frames_cut if fr[2].ndim == 2]
    msgs = [fr for fr in frames_cut if fr[2].ndim == 1]
    assert [fr[:2] for fr in us] == [fr[:2] for fr in msgs] == [
        (k, K) for k in range(K)
    ]
    assert np.array_equal(np.concatenate([fr[2] for fr in us], axis=1), u_whole)
    planes = n_msg_planes("ot2s", S, W)
    assert np.array_equal(
        np.concatenate([fr[2].reshape(planes, -1) for fr in msgs], axis=1),
        msg_whole.reshape(planes, -1),
    )
    # the evaluator (server 1) held some chunks' (strings, T rows), never
    # more than the CHUNKS_AHEAD its queue takes and the one that waits
    # to get in; the garbler holds none
    token = BLOCK * S * (1 + 16)
    assert held[0] is None
    assert token <= held[1] <= (rpc.CollectorServer.CHUNKS_AHEAD + 1) * token


# -- two dimensions: strings of S = 4 bits, four child patterns a node --------


def _plain_reference():
    """The benchmark's d-dimensional plain reference, from its file."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "references", "linf_ball_nd.py")
    spec = importlib.util.spec_from_file_location("linf_ball_nd", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _points_2d(n):
    """Most clients at (4, 4), whose ball [3, 5] lies across the middle
    of both dimensions (four nodes at depth 1 and at depth 2, nine
    leaves), the rest anywhere."""
    rng = np.random.default_rng(5)
    pts = rng.integers(0, 1 << L, size=(n, 2))
    pts[: n - n // 8] = 4
    return pts


@pytest.mark.parametrize("garbler", [0, 1])
@pytest.mark.parametrize("last", [False, True], ids=["FE62", "F255-leaf"])
def test_two_dimensional_level_in_chunks_is_the_whole_level(monkeypatch, garbler, last):
    """``n_dims`` = 2 (configs/amazon.json): S = 4, a 1-of-16 table of
    256 bytes a test (512 at the leaf level) and four patterns a node.
    A level of two planar blocks in K = 2 chunks, garbled by either
    server, in either field: the whole level's shares and cursors, the
    chunks' frames side by side its two messages, and in FE62 the exact
    counts of the plain reference."""
    from fuzzyheavyhitters_tpu.parallel.kernel_shard import n_msg_planes

    n, f, S, K = 1024, 4, 4, 2
    field = F255 if last else FE62
    W = secure.payload_words(field)
    planes = n_msg_planes("ot2s", S, W)
    assert secure.ot_path(S) == "ot2s" and planes == 16 * W
    assert f * 4 * n == K * BLOCK
    sent = _spy_frames(monkeypatch)
    pts = _points_2d(n)
    lv = L - 1 if last else 0

    async def run():
        async with _Pair(BASE_PORT + 700 + 20 * (2 * last + garbler), n, pts=pts) as pair:
            await pair.both("tree_init", {"root_bucket": f})
            del sent[:]
            before = pair.ot_state()
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", WHOLE)
            whole = await pair.level(garbler, last=last)
            after_whole, frames_whole = pair.ot_state(), list(sent)
            pair.set_ot_state(before)
            del sent[:]
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", BLOCK * 4 * planes)
            cut = await pair.level(garbler, last=last)
            obs = [cs.obs for cs in pair.sessions]
            return (whole, after_whole, frames_whole, cut, pair.ot_state(), list(sent),
                    [o.counter_value("secure_chunks", level=lv) for o in obs],
                    [o.counter_value("gc_tests", level=lv) for o in obs],
                    [(o.gauge_value("secure_string_bits", level=lv),
                      o.gauge_value("child_patterns", level=lv)) for o in obs])

    (whole, after_whole, frames_whole, cut, after_cut, frames_cut, ks, tests,
     shape) = _run(run())
    for a, b in zip(whole, cut):
        assert a.dtype == b.dtype and a.shape[:2] == (f, 4) and np.array_equal(a, b)
    assert after_cut == after_whole
    assert ks == [1 + K, 1 + K] and tests == [2 * f * 4 * n] * 2
    assert shape == [(S, 4), (S, 4)]
    u_whole, msg_whole = sorted(frames_whole, key=lambda a: a.nbytes)
    us = [fr for fr in frames_cut if fr[2].ndim == 2]
    msgs = [fr for fr in frames_cut if fr[2].ndim == 1]
    assert [fr[:2] for fr in us] == [fr[:2] for fr in msgs] == [(k, K) for k in range(K)]
    assert np.array_equal(np.concatenate([fr[2] for fr in us], axis=1), u_whole)
    assert np.array_equal(
        np.concatenate([fr[2].reshape(planes, -1) for fr in msgs], axis=1),
        msg_whole.reshape(planes, -1),
    )
    if not last:
        # the root's four children, pattern c taking bit (c >> j) & 1 in
        # dimension j; the bucket's other slots are dead
        got = np.asarray(FE62.canon(FE62.sub(cut[0], cut[1])))
        want = _plain_reference().plain_count(_bits(pts), 1, 1, 1)
        assert [int(v) for v in got[0]] == [want.get((c & 1, c >> 1), 0) for c in range(4)]
        assert sum(int(v) for v in got[0]) > n and not got[1:].any()


def test_two_dimensional_crawl_in_chunks_matches_the_driver_and_the_reference(monkeypatch):
    """A whole served crawl at ``n_dims`` = 2 with a frame budget of one
    planar block: its inner level of bucket 4 (FE62) and its leaf level
    (F255) cross in K = 2 chunks, garbled by different servers; the
    hitters and counts are the in-process ``driver.Leader``'s and the
    plain reference's."""
    from fuzzyheavyhitters_tpu.protocol import driver

    n, S = 1024, 4
    pts = _points_2d(n)
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", _blocks(1, S=4))

    async def run():
        async with _Pair(BASE_PORT + 780, n, pts=pts) as pair:
            lead = RpcLeader(pair.cfg, pair.c0, pair.c1)
            res = await lead.run(n)
            obs = [cs.obs for cs in pair.sessions]
            return (res, pair.k0, pair.k1, pair.cfg,
                    [[o.counter_value("secure_chunks", level=lv) for lv in range(L)]
                     for o in obs],
                    [[o.counter_value("gc_tests", level=lv) for lv in range(L)]
                     for o in obs],
                    [o.counter_value("ot_path_ot2s") for o in obs])

    res, k0, k1, cfg, ks, tests, ot2s = _run(run())
    assert ks == [[1, 2, 2]] * 2 and ot2s == [L, L]
    assert tests == [[1 * 4 * n, 4 * 4 * n, 4 * 4 * n]] * 2
    ref = _plain_reference()
    thresh = max(1, int(cfg.threshold * n))
    want = ref.frontiers(_bits(pts), cfg.ball_size, thresh, L)
    got = ref.crawl_frontier(res.paths, res.counts)
    assert got == want[L] and len(got) == 9
    assert got == ref.plain_count(_bits(pts), cfg.ball_size, L, thresh)
    s0, s1 = driver.make_servers(k0, k1)
    inproc = driver.Leader(s0, s1, n_dims=2, data_len=L, f_max=cfg.f_max).run(
        nreqs=n, threshold=cfg.threshold)
    assert ref.crawl_frontier(inproc.paths, inproc.counts) == got


# -- one device program a span ------------------------------------------------

# what the eager chunk dispatched beside its kernels until PR 38: each a
# program of its own, several of them a lane-padded copy through HBM
_EAGER_GLUE = {"reshape", "convert_element_type", "add", "sub", "sample",
               "to_blocks", "from_blocks"}
# ports of this file's range that no other case binds (a case's servers
# are closed before the next case's start)
_PROGRAM_PORTS = [480, 580, 660, 680, 800, 820, 840, 860]


def _compiled(caplog):
    """The names of the programs XLA has compiled since the last call
    (``jax.log_compiles`` records under ``caplog``)."""
    names = [
        r.getMessage().split()[1].removeprefix("jit(").removesuffix(")")
        for r in caplog.records if r.getMessage().startswith("Compiling ")
    ]
    caplog.clear()
    return names


@pytest.mark.parametrize("K", [2, 1])
@pytest.mark.parametrize("garbler", [0, 1])
@pytest.mark.parametrize("last", [False, True], ids=["FE62", "F255-leaf"])
@pytest.mark.parametrize("S", [2, 4])
def test_a_chunk_is_one_program_a_span(monkeypatch, caplog, S, last, garbler, K):
    """A level of two planar blocks on the table path, whole (K = 1) or
    in two chunks, after the same level cut the OTHER way has compiled
    everything a level needs but the chunk's own programs.  Then (c) the
    level's cut compiles four programs, once each though two servers and
    a second chunk (``t0 > 0``) run them too: the evaluator's slice + extension and
    its open + field, the garbler's extension and its pair + table; none
    is a piece of the eager glue, and with the level's other programs
    already there all of them lie inside ``gc_ot``; (b) the level run
    again compiles nothing; (a) ``secure_chunk_programs`` reads 2 x K
    on either server."""
    from fuzzyheavyhitters_tpu.ops import otext

    n, f = (2048, 4) if S == 2 else (1024, 4)
    C = 1 << (S // 2)
    assert f * C * n == 2 * BLOCK
    one_block = _blocks(1, S=S, field=F255 if last else FE62)
    first, second = (WHOLE, one_block) if K == 2 else (one_block, WHOLE)
    case = ((S == 4) * 2 + last) * 4 + garbler * 2 + (K == 1)
    port = BASE_PORT + _PROGRAM_PORTS[case % len(_PROGRAM_PORTS)]
    pts = None if S == 2 else _points_2d(n)
    lv = L - 1 if last else 0

    # both servers share this process's executables, and so do the cases:
    # each starts with none of the chunk's four
    for program in (secure._ev_extend, secure._ev_open, secure._gb_table,
                    otext._sender_extend):
        program.clear_cache()

    async def run():
        async with _Pair(port, n, pts=pts) as pair:
            await pair.both("tree_init", {"root_bucket": f})
            before = pair.ot_state()
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", first)
            await pair.level(garbler, last=last)
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", second)
            counted = []
            with jax.log_compiles(True), caplog.at_level(logging.WARNING, "jax"):
                caplog.clear()
                for _ in "ab":
                    pair.set_ot_state(before)
                    p0 = [cs.obs.counter_value("secure_chunk_programs", level=lv)
                          for cs in pair.sessions]
                    shares = await pair.level(garbler, last=last)
                    counted.append((
                        _compiled(caplog),
                        [cs.obs.counter_value("secure_chunk_programs", level=lv) - p
                         for cs, p in zip(pair.sessions, p0)],
                        [cs.obs.counter_value("secure_chunks", level=lv)
                         for cs in pair.sessions],
                        shares,
                    ))
            return counted

    (new, programs, chunks, shares), (again, programs2, _, shares2) = _run(run())
    assert chunks == [1 + 2, 1 + 2]  # the level whole and in two, either order
    # (c) one compile a program, whichever chunk met it first
    own = sorted(x for x in new if x != "concatenate")
    assert own == ["_ev_extend", "_ev_open", "_gb_table", "_sender_extend_core"]
    assert not _EAGER_GLUE & set(new)
    # the chunks' shares side by side, once a level of K > 1
    assert new.count("concatenate") <= (K == 2)
    # (b) the second level of the bucket
    assert again == []
    # (a)
    assert programs == programs2 == [2 * K, 2 * K]
    for a, b in zip(shares, shares2):
        assert np.array_equal(a, b)
