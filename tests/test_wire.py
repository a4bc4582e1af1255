"""The wire format (protocol/wire.py) over real loopback sockets: array
payloads cross as raw buffers, everything else as pickled metadata."""

import asyncio
import functools
import pickle
import socket
import struct
import threading

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.obs import metrics as obsmetrics
from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.protocol import rpc, sessions, wire
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.resilience.chaos import ChaosProxy, parse_faults
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 25431
OOB = wire.OOB_MIN


def _cfg(port=BASE_PORT + 90, **kw):
    base = dict(
        data_len=4, n_dims=1, ball_size=1, addkey_batch_size=1024,
        num_sites=4, threshold=0.2, zipf_exponent=1.03,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=32,
    )
    base.update(kw)
    return Config(**base)


def _big(n_bytes, dtype=np.uint32, seed=0):
    raw = np.random.default_rng(seed).bytes(n_bytes)
    return np.frombuffer(raw, dtype=dtype).copy()


def _readonly(a):
    a.setflags(write=False)
    return a


# name -> (object, bytes expected out of band)
_CASES = {
    "secure-chan-array": (lambda: ("default", _big(4 * OOB)), 4 * OOB),
    "secure-chan-array-hdr": (
        lambda: ("default", _big(4 * OOB), ("a1b2c3", "s9")), 4 * OOB),
    "several-large-arrays": (
        lambda: (_big(2 * OOB, np.uint64, 1), {"k": _big(3 * OOB, np.uint8, 2)},
                 [_big(OOB, np.int16, 3).reshape(-1, 64)]),
        6 * OOB),
    "fortran-order": (
        lambda: np.asfortranarray(_big(2 * OOB).reshape(128, -1)), 2 * OOB),
    "non-contiguous-slice": (lambda: ("c", _big(8 * OOB)[::2]), 0),
    "read-only-source": (lambda: ("c", _readonly(_big(2 * OOB))), 2 * OOB),
    "zero-size": (lambda: ("c", np.empty((0, 4), np.uint32)), 0),
    "zero-d": (lambda: ("c", np.array(7, dtype=np.int64)), 0),
    "just-under": (lambda: _big(OOB - 4), 0),
    "just-over": (lambda: _big(OOB + 4), OOB + 4),
    "exactly": (lambda: _big(OOB), OOB),
    "bytes": (lambda: b"\x00\x01" * (2 * OOB), 0),
    "dict": (lambda: {"verb": "status", "n": 3, "nested": {"x": [1, 2.5, None]}}, 0),
    "str": (lambda: "one", 0),
}


def _same(a, b):
    """Identical in value, dtype, shape and nesting."""
    assert type(a) is type(b), (type(a), type(b))
    if isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b)
        assert a.flags.writeable == b.flags.writeable
    elif isinstance(a, (tuple, list)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _same(x, y)
    elif isinstance(a, dict):
        assert a.keys() == b.keys()
        for k in a:
            _same(a[k], b[k])
    else:
        assert a == b


async def _pair(port, on_connect=None):
    """A loopback connection: (client reader, client writer, server
    reader, server writer, listener)."""
    accepted = asyncio.get_running_loop().create_future()

    async def on(r, w):
        accepted.set_result((r, w))

    srv = await wire.start_server(on_connect or on, "127.0.0.1", port)
    cr, cw = await wire.open_connection("127.0.0.1", port)
    if on_connect is not None:
        return cr, cw, None, None, srv
    sr, sw = await asyncio.wait_for(accepted, 5)
    return cr, cw, sr, sw, srv


def _stream_socks(port):
    """Two loopback TCP connections, one a direction of a data plane
    between ends a and b: ``(a_send, b_recv, a_recv, b_send)``."""
    lsock = socket.create_server(("127.0.0.1", port))
    socks = []
    for _ in range(2):
        dialed = socket.create_connection(("127.0.0.1", port))
        socks += [dialed, lsock.accept()[0]]
    lsock.close()
    return socks


async def _plane_pair(port, regs=(None, None)):
    """A data plane over loopback, as two servers hold it: two one-way
    TCP streams, and at each end a ``PlaneMux`` fed by a
    ``wire.PlaneStreams``.  ``[(mux, streams), (mux, streams)]``."""
    a_send, b_recv, a_recv, b_send = _stream_socks(port)
    ends = []
    for send, recv, reg in ((a_send, a_recv, regs[0]), (b_send, b_recv, regs[1])):
        mux = sessions.PlaneMux()
        epoch = mux.attach()
        ends.append((mux, wire.PlaneStreams(
            send, recv,
            on_frame=functools.partial(mux.route, epoch),
            on_lost=functools.partial(mux.lost, epoch), reg=reg,
        )))
    return ends


async def _plane_send(streams, obj):
    return await streams.send(wire.encode(obj)[0])


async def _plane_close(*ends):
    """Close the planes and wait for their threads to end."""
    for _, streams in ends:
        streams.close()
    for _, streams in ends:
        for t in streams.threads:
            await asyncio.to_thread(t.join, 5)
            assert not t.is_alive()


async def _close(srv, *writers):
    for w in writers:
        if w is not None:
            w.close()
    srv.close()
    await asyncio.wait_for(srv.wait_closed(), 5)


@pytest.mark.parametrize("case", sorted(_CASES))
def test_frame_round_trip(case):
    make, want_oob = _CASES[case]
    port = BASE_PORT + sorted(_CASES).index(case)

    async def run():
        cr, cw, sr, sw, srv = await _pair(port)
        tx, rx = obsmetrics.Registry("tx"), obsmetrics.Registry("rx")
        obj = make()
        await rpc._send(cw, obj, reg=tx, counter="data_bytes_sent")
        got = await asyncio.wait_for(
            rpc._recv(sr, reg=rx, counter="data_bytes_recv"), 10)
        _same(obj, got)
        # and back, through the other direction of the same connection
        await rpc._send(sw, got)
        _same(obj, await asyncio.wait_for(rpc._recv(cr), 10))
        await _close(srv, cw, sw)
        return tx.report()["counters"], rx.report()["counters"]

    sent, recv = asyncio.run(run())
    assert sent["data_bytes_sent"]["total"] == recv["data_bytes_recv"]["total"]
    assert sent.get("wire_oob_bytes", {"total": 0})["total"] == want_oob
    pieces, nbytes, oob = wire.encode(make())
    assert oob == want_oob and nbytes == sent["data_bytes_sent"]["total"]
    assert nbytes == sum(memoryview(p).nbytes for p in pieces)
    # the outer prefix covers the whole body: what chaos.py re-frames by
    assert wire.HDR.unpack(pieces[0][:8])[0] == nbytes - 8
    # and the frame is the bare pickle's size give or take a few dozen
    # bytes: wire_bytes_per_level keeps its meaning
    k = len(pieces) - 2
    bare = 8 + len(pickle.dumps(make(), protocol=5))
    assert abs(nbytes - bare) <= 4 + 8 * (k + 1) + 16 * k


def test_large_arrays_are_not_pickled():
    """The metadata of a frame of large arrays holds none of their
    bytes, and the pieces handed to the transport are the arrays' own
    memory."""
    a, b = _big(4 * OOB, seed=4), _big(2 * OOB, np.uint8, seed=5)
    pieces, nbytes, oob = wire.encode(("chan", (a, b)))
    assert oob == a.nbytes + b.nbytes and len(pieces) == 4
    assert len(pieces[0]) + len(pieces[1]) < 512
    assert nbytes == len(pieces[0]) + len(pieces[1]) + oob
    assert np.shares_memory(np.frombuffer(pieces[2], np.uint8), a)
    assert np.shares_memory(np.frombuffer(pieces[3], np.uint8), b)


def test_three_frames_back_to_back_arrive_in_order():
    async def run():
        cr, cw, sr, sw, srv = await _pair(BASE_PORT + 40)
        frames = [("c", _big(3 * OOB, seed=i), i) for i in range(3)]
        for f in frames[:-1]:
            await rpc._send(cw, f, flush=False)
        await rpc._send(cw, frames[-1])
        got = [await asyncio.wait_for(rpc._recv(sr), 10) for _ in frames]
        await _close(srv, cw, sw)
        return frames, got

    frames, got = asyncio.run(run())
    for f, g in zip(frames, got):
        _same(f, g)


def _corrupt(obj, grow=8):
    """``obj``'s frame with a prefix that its inner lengths do not sum
    to (the body is padded, so the bytes are all there)."""
    pieces, nbytes, _ = wire.encode(obj)
    body = b"".join(bytes(p) for p in pieces)[8:]
    return wire.HDR.pack(len(body) + grow) + body + b"\0" * grow


def test_inner_lengths_not_summing_fail_the_plane():
    """A corrupt data-plane frame fails the mux with ConnectionError
    and delivers nothing of itself; the frames before it are intact."""
    async def run():
        (_, tx), (mux, rx) = ends = await _plane_pair(BASE_PORT + 41)
        good = ("chan", _big(2 * OOB, seed=9))
        await _plane_send(tx, good)
        await tx.send([_corrupt(("chan", _big(2 * OOB, seed=10)))])
        first = await asyncio.wait_for(mux.recv("chan"), 10)
        errs = []
        for _ in range(2):  # the failure stays visible
            with pytest.raises(ConnectionError) as ei:
                await asyncio.wait_for(mux.recv("chan"), 10)
            errs.append(ei.value)
        depth = mux._queue("chan").qsize()
        assert rx.is_closing()  # a corrupt stream ends the plane
        await _plane_close(*ends)
        return good, first, errs, depth

    good, first, errs, depth = asyncio.run(run())
    _same(good[1], first)
    assert isinstance(errs[0].__cause__, wire.FrameError)
    assert depth == 1  # the failure marker alone: no half-read frame


@pytest.mark.parametrize("body", [
    b"\x80\x05K\x01.",                        # a bare pickle: the old format
    struct.pack("<IQQ", 1, 5, 70000) + b"x",  # lengths beyond the prefix
    b"\x01",                                  # too short for a count
])
def test_corrupt_control_frame_raises_frame_error(body):
    """What a control-plane reader sees: a ConnectionError (transient,
    so the client redials and the serve loop drops the connection)."""
    async def run():
        cr, cw, sr, sw, srv = await _pair(BASE_PORT + 42)
        cw.writelines([wire.HDR.pack(len(body)) + body])
        with pytest.raises(wire.FrameError):
            await asyncio.wait_for(rpc._recv(sr), 10)
        await _close(srv, cw, sw)

    asyncio.run(run())
    assert issubclass(wire.FrameError, ConnectionError)


def test_serve_loop_drops_a_connection_that_sends_a_corrupt_frame():
    async def run():
        s0 = rpc.CollectorServer(0, _cfg())
        s0._rpc_srv = await wire.start_server(
            s0._handle_leader, "127.0.0.1", BASE_PORT + 43)
        errors = []
        asyncio.get_running_loop().set_exception_handler(
            lambda loop, ctx: errors.append(ctx.get("exception")))
        r, w = await wire.open_connection("127.0.0.1", BASE_PORT + 43)
        w.writelines([_corrupt((1, "status", {}))])
        with pytest.raises((asyncio.IncompleteReadError, ConnectionError)):
            await asyncio.wait_for(rpc._recv(r), 10)  # EOF or reset: dropped
        w.close()
        await s0.aclose()
        return errors

    errors = asyncio.run(run())
    assert [type(e) for e in errors] == [wire.FrameError]


def test_frame_through_chaos_proxy_with_duplication_and_delay():
    async def run():
        got = asyncio.Queue()

        async def sink(r, w):
            try:
                while True:
                    got.put_nowait(await rpc._recv(r))
            except (asyncio.IncompleteReadError, ConnectionError):
                pass
            finally:
                w.close()

        port_s, port_p = BASE_PORT + 44, BASE_PORT + 45
        _, _, _, _, srv = await _pair(port_s, on_connect=sink)
        px = await ChaosProxy(
            "127.0.0.1", port_p, "127.0.0.1", port_s,
            parse_faults("x:flood@msg=1,count=2;x:delay@msg=2,ms=100"),
            link="x",
        ).start()
        r, w = await wire.open_connection("127.0.0.1", port_p)
        one = ("c", _big(3 * OOB, seed=1), _big(2 * OOB, np.uint8, seed=2))
        two = ("c", _big(5 * OOB, seed=3))
        await rpc._send(w, one)
        await rpc._send(w, two)
        out = [await asyncio.wait_for(got.get(), 10) for _ in range(4)]
        fired = list(px.fired)
        w.close()
        await px.stop()
        await _close(srv)
        return one, two, out, fired

    one, two, out, fired = asyncio.run(run())
    for g in out[:3]:  # the original and its two duplicates
        _same(one, g)
    _same(two, out[3])
    assert [f[0] for f in fired] == ["flood", "delay"]


@pytest.mark.parametrize("slabs", [False, True], ids=["new-memory", "slabs"])
def test_queued_frames_each_own_the_buffer_their_socket_read_filled(monkeypatch, slabs):
    """Two frames queued on one channel before either is consumed: each
    received array's memory IS a buffer the socket was read into, no
    buffer serves two frames, and neither frame changes when the other
    (or a third) arrives; with buffers of new memory and with slabs
    (what buffers of 1 MiB and more are)."""
    if slabs:
        monkeypatch.setattr(wire, "_SLAB_MIN", OOB)
    filled = []
    real = wire._recv_buffer

    def spy(size, reg=None):  # the buffers the reader thread reads into
        filled.append(real(size, reg))
        return filled[-1]

    monkeypatch.setattr(wire, "_recv_buffer", spy)

    async def run():
        (_, tx), (mux, _) = ends = await _plane_pair(BASE_PORT + 46 + 4 * slabs)
        sent = [_big(4 * OOB, seed=20 + i) for i in range(3)]
        await _plane_send(tx, ("chan", sent[0]))
        await _plane_send(tx, ("chan", sent[1]))
        while mux._queue("chan").qsize() < 2:  # both queued, none consumed
            await asyncio.sleep(0.01)
        first = await mux.recv("chan")
        keep = first.copy()
        await _plane_send(tx, ("chan", sent[2]))
        while mux._queue("chan").qsize() < 2:
            await asyncio.sleep(0.01)
        assert np.array_equal(first, keep)  # untouched by the third
        rest = [await mux.recv("chan"), await mux.recv("chan")]
        await _plane_close(*ends)
        return sent, [first, *rest]

    sent, got = asyncio.run(asyncio.wait_for(run(), 30))
    assert len(filled) == 3 and len({id(b) for b in filled}) == 3
    for s, g, buf in zip(sent, got, filled):
        assert np.array_equal(s, g)
        assert np.shares_memory(g, buf)
        assert g.ctypes.data == buf.ctypes.data
        assert slabs or buf.base is None
    for i in range(3):
        for j in range(i + 1, 3):
            assert not np.shares_memory(got[i], got[j])


@pytest.mark.skipif(not wire._LEASES, reason="slabs need PEP 688 (Python 3.12)")
def test_slab_returns_only_when_its_last_reader_is_gone(monkeypatch, cpu_default):
    """A slab (a receive buffer of 1 MiB or more; here the threshold
    is lowered) serves the next frame only once the array made on it,
    every view of it and a ``jax.device_put`` of it are gone; until
    then later frames of the same size get other memory and what the
    readers see does not change.  WHEN ``jax.device_put`` lets go is
    JAX's business (at once where it copied on the calling thread, with
    the device array where it aliased the buffer, in between where a
    thread of its own copies), so nothing here asks where the frames
    after it land among the slabs it may hold: only that no reader's
    bytes change."""
    import jax

    monkeypatch.setattr(wire, "_SLAB_MIN", OOB)
    wire._free_slabs.clear()
    size = 8 * OOB

    async def run():
        (_, tx), (mux, _) = ends = await _plane_pair(BASE_PORT + 51)
        sent = [_big(size, seed=40 + i) for i in range(5)]

        async def frame(i):
            await _plane_send(tx, ("chan", sent[i]))
            return await asyncio.wait_for(mux.recv("chan"), 10)

        first = await frame(0)
        addr = first.ctypes.data
        view = first[1000:2000]
        del first
        second = await frame(1)  # `view` holds frame 0's slab
        on_device = jax.device_put(second)
        addr2 = second.ctypes.data
        del second
        third = await frame(2)  # the device copy may hold frame 1's
        assert third.ctypes.data != addr
        assert np.array_equal(view, sent[0][1000:2000])
        del view
        fourth = await frame(3)  # a released slab: frame 0's, or frame 1's if JAX let go since
        assert fourth.ctypes.data in (addr, addr2) and fourth.ctypes.data != third.ctypes.data
        assert np.array_equal(fourth, sent[3])
        assert np.array_equal(third, sent[2])
        assert np.array_equal(np.asarray(on_device), sent[1])
        del third, fourth, on_device
        # (the list is the process's: another test's slab may land on it)
        assert 2 <= sum(s.nbytes == size for s in wire._free_slabs) <= 3
        monkeypatch.setattr(wire, "_SLAB_KEEP", size)  # room for one
        fifth = await frame(4)
        assert np.array_equal(fifth, sent[4])
        assert sum(s.nbytes for s in wire._free_slabs) <= size
        await _plane_close(*ends)

    asyncio.run(asyncio.wait_for(run(), 30))
    wire._free_slabs.clear()


@pytest.mark.skipif(not wire._LEASES, reason="slabs need PEP 688 (Python 3.12)")
@pytest.mark.parametrize("in_flight,keep", [(2, None), (8, 4)],
                         ids=["two-in-flight", "more-than-the-list-keeps"])
def test_slab_counters_say_how_many_receive_buffers_were_new(
        monkeypatch, in_flight, keep):
    """64 frames of one size through a mux whose consumer holds
    ``in_flight`` of them at a time: ``wire_slab_new_bytes`` +
    ``wire_slab_reused_bytes`` count every frame once; with two in flight
    three slabs at most are ever new, and with more in flight than the
    free list keeps (``_SLAB_KEEP`` lowered to ``keep`` slabs) every
    round past the first pays for the ones that were dropped."""
    monkeypatch.setattr(wire, "_SLAB_MIN", OOB)
    size, frames = 8 * OOB, 64
    if keep is not None:
        monkeypatch.setattr(wire, "_SLAB_KEEP", keep * size)
    wire._free_slabs.clear()
    reg = obsmetrics.Registry("rx")

    async def run():
        (_, tx), (mux, _) = ends = await _plane_pair(
            BASE_PORT + 53 + in_flight, regs=(None, reg))
        payload = _big(size, seed=60)
        held = []
        for i in range(frames):
            await _plane_send(tx, ("chan", payload))
            held.append(await asyncio.wait_for(mux.recv("chan"), 10))
            assert np.array_equal(held[-1], payload)
            if len(held) == in_flight:
                held.clear()  # the consumer is done with these
        await _plane_close(*ends)

    asyncio.run(asyncio.wait_for(run(), 60))
    wire._free_slabs.clear()
    new = reg.counter_value("wire_slab_new_bytes")
    reused = reg.counter_value("wire_slab_reused_bytes")
    assert new + reused == frames * size
    if keep is None:
        assert size <= new <= 3 * size
    else:
        # a round's first receive takes one of the 8 released and keeps
        # 4 more: the round pays for the 3 that were dropped
        rounds = frames // in_flight
        dropped = in_flight - keep - 1
        assert new >= (in_flight + (rounds - 1) * dropped) * size
        assert reused >= (rounds - 1) * keep * size


def test_read_cancelled_midway_aborts_the_connection():
    """A frame read cancelled after some of its bytes were taken cannot
    resume: the connection is aborted, not left out of step."""
    async def run():
        cr, cw, sr, sw, srv = await _pair(BASE_PORT + 47)
        pieces, _, _ = wire.encode(("c", _big(2 * OOB)))
        cw.writelines([bytes(pieces[0]), bytes(pieces[1]), bytes(pieces[2])[:100]])
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(rpc._recv(sr), 0.3)
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(rpc._recv(sr), 5)
        # a read cancelled while it waits for a frame's first byte is not
        cr2, cw2, sr2, sw2, srv2 = await _pair(BASE_PORT + 48)
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(rpc._recv(sr2), 0.1)
        await rpc._send(cw2, "still here")
        assert await asyncio.wait_for(rpc._recv(sr2), 5) == "still here"
        await _close(srv, cw, sw)
        await _close(srv2, cw2, sw2)

    asyncio.run(run())


def test_writer_backpressure_and_close():
    """``drain()`` waits while the peer does not read, returns once it
    does, and raises after the connection is lost."""
    async def run():
        cr, cw, sr, sw, srv = await _pair(BASE_PORT + 49)
        big = _big(64 << 20, np.uint8)  # more than the socket buffers hold
        send = asyncio.ensure_future(rpc._send(cw, big))
        await asyncio.sleep(0.3)
        assert not send.done()  # the receiver has not read: drain waits
        got = await asyncio.wait_for(rpc._recv(sr), 30)
        await asyncio.wait_for(send, 10)
        assert np.array_equal(got, big)
        # drained means the kernel has it all: no view of `big` is left
        assert cw.transport.get_write_buffer_size() == 0
        sw.close()
        await asyncio.wait_for(sw.wait_closed(), 5)
        with pytest.raises(asyncio.IncompleteReadError):
            await asyncio.wait_for(rpc._recv(cr), 5)
        cw.close()
        await asyncio.wait_for(cw.wait_closed(), 5)
        with pytest.raises((ConnectionError, RuntimeError)):
            await rpc._send(cw, big)
        await _close(srv)

    asyncio.run(run())


# ---------------------------------------------------------------------------
# the data plane's streams: a thread a direction (wire.PlaneStreams)
# ---------------------------------------------------------------------------


def _plane_threads():
    return [t for t in threading.enumerate() if "-plane-" in t.name]


@pytest.mark.parametrize("size", [1 << 10, 1 << 20, 40 << 20],
                         ids=["1KiB", "1MiB", "40MiB"])
def test_frames_both_ways_at_once_arrive_whole_and_in_order(size):
    """Both ends send frames of ``size`` bytes at the same moment, on two
    channels each: every frame arrives whole and each channel keeps its
    order, whatever the other direction and the other channel do; every
    frame went through the writer thread, one a time a sender."""
    frames = 3 if size >= 32 << 20 else 6
    before = set(_plane_threads())

    async def run():
        a, b = ends = await _plane_pair(BASE_PORT + 70 + size.bit_length())
        assert len(set(_plane_threads()) - before) == 4

        def payload(end, chan, i):
            return _big(size, np.uint8, seed=hash((end, chan, i)) % 1000)

        async def talk(me, mine, theirs, chan):
            mux, streams = me
            got, held = [], []
            for i in range(frames):
                held.append((await _plane_send(
                    streams, (chan, (i, payload(mine, chan, i)))))[3])
                got.append(await asyncio.wait_for(mux.recv(chan), 60))
            for i, (k, arr) in enumerate(got):
                assert k == i and np.array_equal(arr, payload(theirs, chan, i))
            return held

        held = await asyncio.wait_for(asyncio.gather(
            talk(a, 0, 1, "x"), talk(a, 0, 1, "y"),
            talk(b, 1, 0, "x"), talk(b, 1, 0, "y")), 120)
        # two senders an end: the writer held one frame or two, never more
        assert {h for hs in held for h in hs} <= {1, 2}
        assert a[1].waiting == b[1].waiting == 0
        await _plane_close(*ends)

    asyncio.run(run())
    assert set(_plane_threads()) <= before


def test_two_sessions_interleaved_on_one_plane_keep_their_own_fifo():
    """Two sessions' channels on one plane, one sending 40 small frames
    while the other sends 5 large ones, nobody receiving until all are
    sent: each channel yields its own frames in its own order."""
    async def run():
        (_, tx), (mux, _) = ends = await _plane_pair(BASE_PORT + 69)
        big = [_big(3 << 20, np.uint8, seed=i) for i in range(5)]

        async def send(chan, items):
            for it in items:
                await _plane_send(tx, (chan, it))

        await asyncio.wait_for(asyncio.gather(
            send("tenant-a", list(range(40))), send("tenant-b", big)), 60)
        assert [await mux.recv("tenant-a") for _ in range(40)] == list(range(40))
        for want in big:
            assert np.array_equal(await mux.recv("tenant-b"), want)
        await _plane_close(*ends)

    asyncio.run(run())


def test_plane_cut_under_a_blocked_send_and_a_blocked_receive():
    """A send blocked on a peer that does not read, three more queued
    behind it, and a receive blocked on a frame that never comes: the
    plane's close fails every one of them with ConnectionError, later
    sends fail at once, the mux hears of the loss, and no I/O thread is
    left."""
    before = set(_plane_threads())

    async def run():
        # the peer's ends: no thread on them
        send_sock, deaf, recv_sock, mute = _stream_socks(BASE_PORT + 68)
        mux = sessions.PlaneMux()
        epoch = mux.attach()
        streams = wire.PlaneStreams(
            send_sock, recv_sock,
            on_frame=functools.partial(mux.route, epoch),
            on_lost=functools.partial(mux.lost, epoch))
        big = _big(64 << 20, np.uint8)  # more than the socket buffers hold
        sends = [asyncio.ensure_future(_plane_send(streams, ("c", big)))
                 for _ in range(4)]
        recv = asyncio.ensure_future(mux.recv("c"))
        await asyncio.sleep(0.5)
        assert not any(f.done() for f in (*sends, recv))
        assert streams.waiting == 4
        streams.close()
        for f in (*sends, recv):
            with pytest.raises(ConnectionError):
                await asyncio.wait_for(f, 10)
        assert streams.is_closing() and streams.waiting == 0
        with pytest.raises(ConnectionError):
            await _plane_send(streams, ("c", 1))
        with pytest.raises(ConnectionError):
            await mux.recv("other")
        for t in streams.threads:
            await asyncio.to_thread(t.join, 5)
        for s in (deaf, mute):
            s.close()

    asyncio.run(asyncio.wait_for(run(), 60))
    assert set(_plane_threads()) <= before


def test_send_queue_is_bounded_and_a_lost_peer_fails_it():
    """More senders than ``SEND_DEPTH`` on a peer that does not read:
    the writer thread never holds more than its bound, the others wait
    for a slot, and when the peer's end dies every one of them fails."""
    async def run():
        send_sock, deaf, recv_sock, mute = _stream_socks(BASE_PORT + 67)
        lost = []
        streams = wire.PlaneStreams(
            send_sock, recv_sock, on_frame=lambda *a: None, on_lost=lost.append)
        big = _big(64 << 20, np.uint8)
        n = wire.PlaneStreams.SEND_DEPTH + 3
        sends = [asyncio.ensure_future(_plane_send(streams, ("c", big)))
                 for _ in range(n)]
        await asyncio.sleep(0.5)
        assert streams.waiting == wire.PlaneStreams.SEND_DEPTH
        for s in (deaf, mute):  # the peer dies: a reset, an EOF
            s.close()
        for f in sends:
            with pytest.raises(ConnectionError):
                await asyncio.wait_for(f, 10)
        assert len(lost) == 1 and streams.is_closing()
        for t in streams.threads:
            await asyncio.to_thread(t.join, 5)
            assert not t.is_alive()

    asyncio.run(asyncio.wait_for(run(), 60))


@pytest.mark.parametrize("how", ["two_handed_over", "send_alone"])
def test_a_second_frame_handed_over_waits_behind_the_first(how):
    """``hand_over`` is the first half of ``send``: two frames handed
    over back to back, neither waited for, are with the writer thread
    together (``held`` 1 then 2), leave in the order of their hand-over
    (the second begins where the first ended, no sooner) and arrive so;
    ``send`` alone, frame after frame, holds one at a time as before."""
    async def run():
        (_, tx), (mux, _) = ends = await _plane_pair(
            BASE_PORT + 61 + (how == "send_alone"))
        frames = [_big(24 << 20, np.uint8, seed=i) for i in range(2)]
        if how == "send_alone":
            stamps = [await _plane_send(tx, ("c", f)) for f in frames]
            assert [s[3] for s in stamps] == [1, 1]
        else:
            out = [await tx.hand_over(wire.encode(("c", f))[0]) for f in frames]
            assert [held for _, _, held in out] == [1, 2]
            assert tx.waiting == 2 and not out[0][0].done()
            # the younger frame first: it ends no sooner for that
            ends_ = [await asyncio.wait_for(done, 60) for done, _, _ in out[::-1]]
            # (and the loop heard of each after its end)
            assert all(e[1] <= e[2] for e in ends_)
            stamps = [(t_put, *ends_[1 - i][:2], held)
                      for i, (_, t_put, held) in enumerate(out)]
            # handed over before the first was written: queued behind it
            assert stamps[1][0] <= stamps[0][2] <= stamps[1][1]
        (p0, b0, e0, _), (p1, b1, e1, _) = stamps
        assert p0 <= b0 <= e0 <= b1 <= e1 and p0 <= p1
        assert tx.waiting == 0
        for want in frames:
            assert np.array_equal(await asyncio.wait_for(mux.recv("c"), 60), want)
        await _plane_close(*ends)

    asyncio.run(asyncio.wait_for(run(), 120))


def test_a_frame_given_up_goes_whole_and_gives_its_slot_back():
    """A sender that hands a frame over and cancels the future instead
    of waiting for it (a send stage that was cancelled): the frame
    arrives whole all the same, the slot comes back, and nothing is
    logged about a result nobody took."""
    async def run():
        (_, tx), (mux, _) = ends = await _plane_pair(BASE_PORT + 63)
        want = _big(8 << 20, np.uint8, seed=3)
        done, _, held = await tx.hand_over(wire.encode(("c", want))[0])
        done.cancel()
        assert held == 1
        assert np.array_equal(await asyncio.wait_for(mux.recv("c"), 60), want)
        for _ in range(100):
            if not tx.waiting:
                break
            await asyncio.sleep(0.01)
        assert tx.waiting == 0 and tx._slots._value == tx.SEND_DEPTH
        assert (await _plane_send(tx, ("c", 1)))[3] == 1
        await _plane_close(*ends)

    asyncio.run(asyncio.wait_for(run(), 60))


@pytest.mark.skipif(not wire._LEASES, reason="slabs need PEP 688 (Python 3.12)")
def test_two_reader_threads_take_from_one_slab_list(monkeypatch):
    """Two planes in one process (both servers of a pair), their reader
    threads taking receive buffers at the same time, 64 frames each with
    one in flight: a taker never finds the list emptied by the other's
    scan, so all but the first few buffers are reused."""
    monkeypatch.setattr(wire, "_SLAB_MIN", OOB)
    size, frames = 8 * OOB, 64
    wire._free_slabs.clear()
    regs = [obsmetrics.Registry("rx0"), obsmetrics.Registry("rx1")]

    async def run():
        planes = [await _plane_pair(BASE_PORT + 65 + i, regs=(None, regs[i]))
                  for i in range(2)]
        payload = _big(size, seed=61)

        async def stream(ends):
            (_, tx), (mux, _) = ends
            for _ in range(frames):
                await _plane_send(tx, ("chan", payload))
                got = await asyncio.wait_for(mux.recv("chan"), 10)
                assert np.array_equal(got, payload)
                del got

        await asyncio.gather(*(stream(p) for p in planes))
        for p in planes:
            await _plane_close(*p)

    asyncio.run(asyncio.wait_for(run(), 60))
    wire._free_slabs.clear()
    new = sum(r.counter_value("wire_slab_new_bytes") for r in regs)
    reused = sum(r.counter_value("wire_slab_reused_bytes") for r in regs)
    assert new + reused == 2 * frames * size
    assert size <= new <= 6 * size


# ---------------------------------------------------------------------------
# the mechanism engages in a crawl
# ---------------------------------------------------------------------------


def _keys(rng, L, n):
    pts = np.concatenate(
        [np.full(n - 4, 11), rng.integers(0, 1 << L, size=4)]
    )[:, None]
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    return ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")


def test_secure_crawl_sends_its_levels_out_of_band(rng, cpu_default):
    """A two-server secure crawl on the CPU, wide enough that a level's
    frames are arrays of 64 KiB and more: over the crawl's levels
    ``wire_oob_bytes`` is over 99% of ``data_bytes_sent`` on both
    servers, and the two ends of the stream still agree to the byte."""
    L, n, port = 4, 4096, BASE_PORT + 60
    k0, k1 = _keys(rng, L, n)
    cfg = _cfg(port, data_len=L, secure_exchange=True)

    async def run():
        s0, s1 = rpc.CollectorServer(0, cfg), rpc.CollectorServer(1, cfg)
        t1 = asyncio.create_task(
            s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11))
        await asyncio.sleep(0.05)
        await asyncio.gather(
            s0.start("127.0.0.1", port, "127.0.0.1", port + 11), t1)
        c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
        c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
        lead = RpcLeader(cfg, c0, c1)
        await lead._both("reset")
        res = await lead.run_supervised(n, k0, k1)
        reps = [s.obs.report()["counters"] for s in (s0, s1)]
        lead_rep = lead.obs.report()["counters"]
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()
        return res, reps, lead_rep

    res, (r0, r1), lead_rep = asyncio.run(asyncio.wait_for(run(), 600))
    assert len(res.counts) > 0
    for rep in (r0, r1):
        sent = rep["data_bytes_sent"]["by_level"]
        oob = rep["wire_oob_bytes"]["by_level"]
        assert set(sent) >= {str(lv) for lv in range(L)}
        for lv, nbytes in sent.items():
            assert oob.get(lv, 0) > 0.99 * nbytes, (lv, oob.get(lv), nbytes)
    assert r0["data_bytes_sent"]["total"] == r1["data_bytes_recv"]["total"]
    assert r1["data_bytes_sent"]["total"] == r0["data_bytes_recv"]["total"]
    # the upload's key chunks cross out of band too (contiguous slices)
    assert lead_rep["wire_oob_bytes"]["total"] > 0.5 * (
        lead_rep["control_bytes_sent"]["total"])
