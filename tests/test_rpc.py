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
        rpc.CollectorServer._keepalive(w)
        sock = w.get_extra_info("socket")
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
