"""Control-plane tests: both collector servers + leader in one asyncio loop
(the reference's in-process duplex-socket 2PC test pattern,
ref: equalitytest.rs:222-266) — full 8-verb protocol over real TCP on
localhost, counts reconstructed from field-element shares."""

import asyncio

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.protocol import driver, rpc
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 21131


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """CPU backend: the RPC layer under test is host-side glue; its device
    programs are the same crawl kernels test_protocol.py compiles (shapes
    harmonized)."""
    yield


def _cfg(**kw):
    defaults = dict(
        data_len=6,
        n_dims=1,
        ball_size=2,
        addkey_batch_size=8,
        num_sites=4,
        threshold=0.1,
        zipf_exponent=1.03,
        server0="127.0.0.1:21131",
        server1="127.0.0.1:21141",
        distribution="zipf",
        f_max=128,
    )
    defaults.update(kw)
    return Config(**defaults)


async def _run_protocol(cfg, keys0, keys1, nreqs, port0, port1):
    s0 = rpc.CollectorServer(0, cfg)
    s1 = rpc.CollectorServer(1, cfg)
    peer_port = port1 + 1
    # server1 first (it listens on the data plane), then server0 dials —
    # the reference's startup ordering constraint (server.rs:344-354)
    t1 = asyncio.create_task(s1.start("127.0.0.1", port1, "127.0.0.1", peer_port))
    await asyncio.sleep(0.05)
    t0 = asyncio.create_task(s0.start("127.0.0.1", port0, "127.0.0.1", peer_port))
    c0 = await rpc.CollectorClient.connect("127.0.0.1", port0)
    c1 = await rpc.CollectorClient.connect("127.0.0.1", port1)
    await asyncio.gather(t0, t1)

    lead = RpcLeader(cfg, c0, c1)
    await asyncio.gather(c0.call("reset"), c1.call("reset"))
    await lead.upload_keys(keys0, keys1)
    return await lead.run(nreqs)


def test_rpc_protocol_matches_colocated(rng):
    # (L, d, n, f_max) match test_protocol.py's d=1 scenarios so the crawl
    # kernels compile once for both files
    L, d, n = 6, 1, 40
    cfg = _cfg(data_len=L, n_dims=d)
    pts = np.concatenate([np.full(32, 20), rng.integers(0, 1 << L, size=8)])[:, None]
    pts_bits = np.array([[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts])
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, cfg.ball_size, rng)

    res = asyncio.run(_run_protocol(cfg, k0, k1, n, BASE_PORT, BASE_PORT + 10))
    got = {
        tuple(int(v) for v in r): int(c)
        for r, c in zip(res.decode_ints(), res.counts)
    }

    s0, s1 = driver.make_servers(k0, k1)
    lead = driver.Leader(s0, s1, n_dims=d, data_len=L, f_max=cfg.f_max)
    want_res = lead.run(nreqs=n, threshold=cfg.threshold)
    want = {
        tuple(int(v) for v in r): int(c)
        for r, c in zip(want_res.decode_ints(), want_res.counts)
    }
    assert got == want
    assert got  # the 16 stacked clients at 20 must clear the threshold


def test_share_masks_cancel():
    """Server0's and server1's mask streams are identical, so shares
    reconstruct exactly (the shared-seed trick, ref: server.rs:331-332)."""
    from fuzzyheavyhitters_tpu.ops.fields import F255, FE62

    r0 = rpc.mask_fe62(3, 10)
    r1 = rpc.mask_fe62(3, 10)
    np.testing.assert_array_equal(r0, r1)
    assert not np.array_equal(r0, rpc.mask_fe62(4, 10))  # level-keyed
    counts = np.arange(10).astype(np.uint64)
    rec = np.asarray(FE62.canon(FE62.sub(FE62.add(counts, r0), r1)))
    np.testing.assert_array_equal(rec, counts)

    m0 = rpc.mask_f255(2, 6)
    c = np.zeros((6, 8), np.uint32)
    c[:, 0] = np.arange(6)
    rec = np.asarray(F255.sub(F255.add(c, m0), rpc.mask_f255(2, 6)))
    np.testing.assert_array_equal(rec[:, 0], np.arange(6))
    assert not rec[:, 1:].any()


def test_reset_clears_state(rng):
    """reset → add_keys → tree_init works twice (ref: server.rs:64-69)."""

    async def flow():
        cfg = _cfg()
        s0 = rpc.CollectorServer(0, cfg)
        pts_bits = np.array([[bitutils.int_to_bits(6, 20)]])
        k0, _ = ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")
        for _ in range(2):
            await s0.reset({})
            await s0.add_keys({"keys": tuple(np.asarray(x) for x in k0)})
            await s0.tree_init({})
            assert s0.keys.cw_seed.shape[0] == 1
        return True

    assert asyncio.run(flow())


# ---------------------------------------------------------------------------
# failure paths the resilience layer builds on
# ---------------------------------------------------------------------------


def test_error_response_propagates_and_connection_survives(rng):
    """A verb that fails server-side comes back as an __error__ response
    raising RuntimeError at the caller — and the connection stays usable
    (the error is a RESPONSE, not a transport death)."""
    port = 21231

    async def flow():
        cfg = _cfg(
            server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}"
        )
        s0 = rpc.CollectorServer(0, cfg)
        s1 = rpc.CollectorServer(1, cfg)
        t1 = asyncio.create_task(
            s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11)
        )
        await asyncio.sleep(0.05)
        t0 = asyncio.create_task(
            s0.start("127.0.0.1", port, "127.0.0.1", port + 11)
        )
        await asyncio.gather(t0, t1)
        c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
        with pytest.raises(RuntimeError, match="tree_init before add_keys"):
            await c0.call("tree_init")
        # protocol errors are NOT retried (they would never succeed) and
        # the transport survives them
        assert c0.epoch == 1
        assert await c0.call("reset") is True
        await c0.aclose()
        await s0.aclose()
        await s1.aclose()

    asyncio.run(flow())


def test_read_loop_death_fails_inflight_futures():
    """Reader death must fail EVERY in-flight caller loudly (no future
    left dangling), and once redials exhaust, the call surfaces a
    ConnectionError — with the pending table empty (the send-failure /
    reader-death paths may not leak futures)."""
    port = 21251

    async def flow():
        conns = []

        async def half_server(reader, writer):
            # answer the hello, then die mid-protocol without responding
            req_id, verb, req = await rpc._recv(reader)
            assert verb == "__hello__"
            await rpc._send(writer, (req_id, {"boot_id": "fake"}))
            conns.append((reader, writer))
            await rpc._recv(reader)  # swallow one verb frame...
            writer.close()  # ...and hang up without answering

        srv = await asyncio.start_server(half_server, "127.0.0.1", port)
        from fuzzyheavyhitters_tpu.resilience import policy as respolicy

        c = await rpc.CollectorClient.connect(
            "127.0.0.1", port,
            dial_policy=respolicy.RetryPolicy(
                base_s=0.001, attempts=2, rand=lambda: 0.0
            ),
            budgets=respolicy.VerbBudgets(default_s=5.0, per_verb={}),
        )
        srv.close()  # no more accepts: redials must exhaust
        with pytest.raises(ConnectionError):
            await c.call("reset")
        assert c._pending == {}  # nothing leaked across the failed call
        await c.aclose()
        await srv.wait_closed()  # returns once half_server hung up

    asyncio.run(flow())


def test_send_failure_pops_pending():
    """The _send-raises-mid-write path: the pending future is dropped so
    _pending cannot grow across failed calls (it used to leak one entry
    per failure), and a non-transport bug propagates unretried."""
    port = 21261

    async def flow():
        async def hello_only(reader, writer):
            req_id, verb, _ = await rpc._recv(reader)
            await rpc._send(writer, (req_id, {"boot_id": "fake"}))
            await reader.read()  # until the client hangs up...
            writer.close()  # ...then close: srv.wait_closed() waits for it

        srv = await asyncio.start_server(hello_only, "127.0.0.1", port)
        c = await rpc.CollectorClient.connect("127.0.0.1", port)

        class Boom(Exception):
            pass

        real_send = rpc._send

        async def broken_send(writer, obj, **_kw):
            raise Boom("pickling exploded mid-write")

        rpc._send = broken_send
        try:
            with pytest.raises(Boom):
                await c.call("reset")
        finally:
            rpc._send = real_send
        assert c._pending == {}
        await c.aclose()
        srv.close()
        await srv.wait_closed()

    asyncio.run(flow())


def test_keepalive_sets_socket_options():
    """_keepalive arms SO_KEEPALIVE with the aggressive-ish probe timing
    on the data-plane socket (a silently-dead peer surfaces in ~2 min,
    not the kernel's ~2 h default)."""
    import socket

    port = 21271

    async def flow():
        async def server(reader, writer):
            await asyncio.sleep(0.2)
            writer.close()

        srv = await asyncio.start_server(server, "127.0.0.1", port)
        _, w = await asyncio.open_connection("127.0.0.1", port)
        sock = w.get_extra_info("socket")
        rpc.CollectorServer._keepalive(sock)
        assert sock.getsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE) == 1
        for opt, want in (
            ("TCP_KEEPIDLE", 60), ("TCP_KEEPINTVL", 20), ("TCP_KEEPCNT", 3)
        ):
            if hasattr(socket, opt):
                assert sock.getsockopt(
                    socket.IPPROTO_TCP, getattr(socket, opt)
                ) == want
        w.close()
        srv.close()
        await srv.wait_closed()

    asyncio.run(flow())


# ---------------------------------------------------------------------------
# the data plane between two started servers: two streams, a thread each
# ---------------------------------------------------------------------------


def _plane_threads(settle=()):
    """The live I/O threads of data planes (``wire.PlaneStreams``);
    those of ``settle`` (planes that were closed) are given a moment
    to end first."""
    import threading

    for t in settle:
        t.join(10)
    return {t for t in threading.enumerate() if "-plane-" in t.name}


async def _started_pair(cfg, port):
    s0, s1 = rpc.CollectorServer(0, cfg), rpc.CollectorServer(1, cfg)
    t1 = asyncio.create_task(s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11))
    await asyncio.sleep(0.05)
    await asyncio.gather(s0.start("127.0.0.1", port, "127.0.0.1", port + 11), t1)
    return s0, s1


def test_both_servers_swap_frames_larger_than_the_socket_buffers():
    """The duplex ``_swap``: both servers send 48 MiB at the same moment
    and both complete (each direction has a stream and a reader thread
    of its own; on one loop thread the two sends waited on each other
    for ever, which the old role order existed to avoid), three times
    over on two sessions' channels at once, each channel in its order.
    The plane came up by the dial: two connections, a hello on each."""
    port = 32231  # (these three: a range of their own, beside test_obs's)

    async def flow():
        s0, s1 = await _started_pair(_cfg(), port)
        assert s0._plane.epoch == s1._plane.epoch == 1
        assert len(_plane_threads()) == 4

        def payload(server, chan, i):
            return np.full(48 << 20, 16 * server + 4 * chan + i, np.uint8)

        async def talk(s, chan):
            cs = s._table.get(f"tenant{chan}")
            for i in range(3):
                got = await s._swap(cs, payload(s.server_id, chan, i))
                assert np.array_equal(got, payload(1 - s.server_id, chan, i))

        await asyncio.wait_for(asyncio.gather(
            *(talk(s, chan) for s in (s0, s1) for chan in (0, 1))), 120)
        out = []
        for s in (s0, s1):
            regs = [s._table.get(f"tenant{c}").obs for c in (0, 1)]
            out.append([
                sum(r.counter_value(n) for r in regs)
                for n in ("data_msgs_sent", "plane_stream_frames")
            ] + [max(r.gauge_value("plane_send_queue_high") for r in regs)])
            assert all(r.timer_seconds("wire_write") > 0 for r in regs)
            assert all(r.timer_seconds("wire_queue") > 0 for r in regs)
        mine = _plane_threads()
        for s in (s0, s1):
            await s.aclose()
        return out, mine

    before = _plane_threads()
    out, mine = asyncio.run(flow())
    # every frame went through its stream's thread; two sessions sending
    # at once: the writer held two frames at most
    assert all(o[:2] == [6, 6] and o[2] in (1, 2) for o in out), out
    assert _plane_threads(settle=mine) <= before


def test_plane_cut_under_blocked_verbs_then_reset_carries_a_level(rng, monkeypatch):
    """Server 0 blocked in a send (its peer's reader thread is held
    inside a frame, so the socket fills) and server 1 blocked in a
    receive: ``plane_break`` fails both with ConnectionError and the
    plane's four I/O threads end; ``plane_reset`` brings up a new plane
    (new threads, the next epoch) that carries a crawl level bit for
    bit."""
    import threading

    from fuzzyheavyhitters_tpu.protocol import wire

    port, L, n = 32261, 6, 40
    cfg = _cfg(data_len=L)
    pts = np.concatenate([np.full(32, 20), rng.integers(0, 1 << L, size=8)])[:, None]
    pts_bits = np.array([[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts])
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, cfg.ball_size, rng)
    hold, real = threading.Event(), wire._recv_buffer
    hold.set()

    def held_buffer(size, reg=None):  # on a reader thread
        hold.wait(30)
        return real(size, reg)

    monkeypatch.setattr(wire, "_recv_buffer", held_buffer)

    async def flow():
        s0, s1 = await _started_pair(cfg, port)
        c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
        c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
        lead = RpcLeader(cfg, c0, c1)
        await lead._both("reset")
        await lead.upload_keys(k0, k1)
        await lead._both("tree_init")
        first = await lead._both("tree_crawl", {"level": 0})
        old = _plane_threads()
        assert len(old) == 4

        hold.clear()
        cs0, cs1 = s0._default(), s1._default()
        send = asyncio.ensure_future(s0._dp_send(cs0, np.zeros(64 << 20, np.uint8)))
        queued = asyncio.ensure_future(s0._dp_send(cs0, b"behind it"))
        recv = asyncio.ensure_future(s0._dp_recv(cs0))
        await asyncio.sleep(0.5)
        assert not (send.done() or queued.done() or recv.done())
        await c0.call("plane_break")
        hold.set()
        for f in (send, queued, recv):
            with pytest.raises(ConnectionError):
                await asyncio.wait_for(f, 10)
        with pytest.raises(ConnectionError):  # server 1 saw the plane go too
            await asyncio.wait_for(s1._dp_recv(cs1), 10)
        for t in old:
            await asyncio.to_thread(t.join, 10)
        assert not _plane_threads() & old and not _plane_threads()

        await lead._both("plane_reset")
        again = await lead._both("tree_crawl", {"level": 0})
        new = _plane_threads()
        epochs = (s0._plane.epoch, s1._plane.epoch)
        sent = [s.obs.counter_value(n) for s in (s0, s1)
                for n in ("data_msgs_sent", "plane_stream_frames")]
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()
        return first, again, new, epochs, sent

    before = _plane_threads()
    first, again, new, epochs, sent = asyncio.run(asyncio.wait_for(flow(), 120))
    n_new = len(new)
    assert n_new == 4 and epochs == (2, 2)
    for a, b in zip(first, again):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # the frames that failed were counted as sent and not as through a
    # stream: the difference is what took any other way, here nowhere
    assert sent[0] - sent[1] == 2 and sent[2] == sent[3]
    assert _plane_threads(settle=new) <= before


def test_a_peer_that_opens_the_plane_the_old_way_is_refused():
    """One connection whose first frame is a data frame (the
    one-connection plane) is refused at the hello: logged, counted,
    closed; so is a hello of another generation; the plane the servers
    hold is untouched and a real dial still completes."""
    import socket

    from fuzzyheavyhitters_tpu.protocol import wire

    port = 32291

    async def flow():
        s0, s1 = await _started_pair(_cfg(), port)
        loop = asyncio.get_running_loop()
        old_way = b"".join(bytes(p) for p in wire.encode(("default", b"\x00" * 16))[0])
        for first in (old_way,
                      wire.hello_frame(b"fhh-plane/1 0to1 abcd"),
                      wire.hello_frame(rpc._PLANE_HELLO + b" sideways abcd"),
                      wire.HDR.pack(1 << 20) + b"a frame too long for a hello"):
            sock = socket.create_connection(("127.0.0.1", port + 11))
            sock.setblocking(False)
            await loop.sock_sendall(sock, first)
            try:  # closed on us, nothing said: an EOF, or a reset
                assert await asyncio.wait_for(loop.sock_recv(sock, 1), 10) == b""
            except ConnectionResetError:
                pass  # (what it had not read of ours was thrown away)
            sock.close()
        refused = s1.obs.counter_value("plane_hellos_refused")
        epoch = s1._plane.epoch
        theirs = await asyncio.wait_for(asyncio.gather(
            s0._swap(s0._default(), b"still"), s1._swap(s1._default(), b"there")), 10)
        await s0.plane_reset({})
        again = await asyncio.wait_for(asyncio.gather(
            s0._swap(s0._default(), b"and"), s1._swap(s1._default(), b"again")), 10)
        for s in (s0, s1):
            await s.aclose()
        return refused, epoch, theirs, again, s1._plane.epoch

    refused, epoch, theirs, again, epoch2 = asyncio.run(flow())
    assert refused == 4 and (epoch, epoch2) == (1, 2)
    assert theirs == [b"there", b"still"] and again == [b"again", b"and"]
