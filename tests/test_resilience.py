"""Fault-tolerance tests: retry/deadline policy, the chaos proxy, the
reconnecting client's idempotent replay, and end-to-end crawl recovery.

The e2e scenarios are the acceptance surface of the resilience layer: a
SECURE (GC+OT) crawl severed mid-flight on the leader↔server control
link AND a server killed+restarted at a checkpoint boundary completes
with heavy hitters bit-identical to a fault-free run, with no verb
double-applied (the dedup-cache hit counter proves replays were answered
from cache).  Shapes mirror tests/test_secure.py (L=5, d=1, n=12) so the
crawl kernels compile once across both files.
"""

import asyncio

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.protocol import driver, rpc
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.resilience import policy as respolicy
from fuzzyheavyhitters_tpu.resilience.chaos import (
    ChaosProxy,
    MeshChaos,
    MeshFaultError,
    MeshFaultSpec,
    parse_faults,
    parse_mesh_faults,
)
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 31631  # a range of its own: 21631 + offsets ran into test_obs and test_ops under xdist


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """CPU backend: the resilience layer under test is host-side glue;
    its device programs are the same crawl kernels test_secure.py
    compiles (shapes harmonized)."""
    yield


# ---------------------------------------------------------------------------
# policy: backoff, deadlines, classification
# ---------------------------------------------------------------------------


def test_retry_policy_full_jitter_envelope():
    pol = respolicy.RetryPolicy(
        base_s=0.1, cap_s=1.0, factor=2.0, attempts=6, rand=lambda: 1.0
    )
    # undithered envelope: base·2^k capped
    assert [pol.delay(k) for k in range(5)] == [0.1, 0.2, 0.4, 0.8, 1.0]
    half = respolicy.RetryPolicy(
        base_s=0.1, cap_s=1.0, factor=2.0, attempts=6, rand=lambda: 0.5
    )
    assert half.delay(3) == pytest.approx(0.4)  # jitter scales the envelope
    assert list(pol.delays()) and len(list(pol.delays())) == 5


def test_deadline_remaining_and_expiry():
    d = respolicy.Deadline(100.0)
    rem = d.remaining()
    assert 0 < rem <= 100.0 and not d.expired()
    assert respolicy.Deadline(None).remaining() is None
    assert not respolicy.Deadline(None).expired()
    z = respolicy.Deadline(0.0)
    assert z.expired() and z.remaining() == 0.0


def test_deadline_wait_for_times_out():
    async def run():
        d = respolicy.Deadline(0.05)
        with pytest.raises(asyncio.TimeoutError):
            await d.wait_for(asyncio.sleep(5))

    asyncio.run(run())


def test_is_transient_classification():
    assert respolicy.is_transient(ConnectionResetError())
    assert respolicy.is_transient(asyncio.IncompleteReadError(b"", 8))
    assert respolicy.is_transient(TimeoutError())
    assert respolicy.is_transient(OSError(111, "refused"))
    assert not respolicy.is_transient(ValueError("bug"))
    assert not respolicy.is_transient(RuntimeError("server error on x"))
    assert not respolicy.is_transient(asyncio.CancelledError())


def test_retry_async_retries_transient_then_succeeds():
    calls = []

    async def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionResetError("blip")
        return "ok"

    pol = respolicy.RetryPolicy(base_s=0.001, attempts=5, rand=lambda: 0.0)

    async def run():
        return await respolicy.retry_async(flaky, pol, what="t")

    assert asyncio.run(run()) == "ok"
    assert len(calls) == 3


def test_retry_async_fatal_and_exhaustion():
    async def fatal():
        raise ValueError("bug")

    async def always_down():
        raise ConnectionResetError("down")

    pol = respolicy.RetryPolicy(base_s=0.001, attempts=3, rand=lambda: 0.0)

    async def run_fatal():
        await respolicy.retry_async(fatal, pol)

    async def run_down():
        await respolicy.retry_async(always_down, pol)

    with pytest.raises(ValueError):
        asyncio.run(run_fatal())
    with pytest.raises(ConnectionResetError):
        asyncio.run(run_down())


def test_retry_async_respects_shared_deadline():
    calls = []

    async def always_down():
        calls.append(1)
        raise ConnectionResetError("down")

    pol = respolicy.RetryPolicy(base_s=0.05, attempts=100, rand=lambda: 1.0)

    async def run():
        await respolicy.retry_async(
            always_down, pol, deadline=respolicy.Deadline(0.12)
        )

    with pytest.raises(ConnectionResetError):
        asyncio.run(run())
    assert len(calls) < 10  # the wall clock, not attempts, stopped it


def test_verb_budgets_lookup():
    b = respolicy.VerbBudgets()
    assert b.budget("tree_crawl") == b.default_s
    assert b.budget("reset") == 300.0
    assert b.deadline("reset").budget_s == 300.0


# ---------------------------------------------------------------------------
# chaos: fault-spec grammar + proxy behavior
# ---------------------------------------------------------------------------


def test_parse_faults_grammar():
    faults = parse_faults(
        "ctl0:sever@msg=12;plane:delay@msg=3,ms=50;"
        "ctl1:blackhole@msg=2,count=4,dir=s2c"
    )
    assert [f.action for f in faults] == ["sever", "delay", "blackhole"]
    assert faults[0].link == "ctl0" and faults[0].at_msg == 12
    assert faults[1].ms == 50 and faults[1].direction == "c2s"
    assert faults[2].count == 4 and faults[2].direction == "s2c"
    assert parse_faults("") == [] and parse_faults(None) == []


@pytest.mark.parametrize(
    "bad",
    [
        "ctl0:sever",  # no trigger
        "ctl0:sever@ms=5",  # missing msg=
        "ctl0:explode@msg=1",  # unknown action
        "ctl0:sever@msg=0",  # 1-indexed
        "ctl0:sever@msg=1,dir=sideways",  # unknown direction
        "justgarbage",
    ],
)
def test_parse_faults_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_faults(bad)


def _echo_server_port(offset):
    return BASE_PORT + 80 + offset


def test_chaos_proxy_forwards_delays_blackholes_and_severs():
    """One framed echo server behind a proxy: clean forwarding first,
    then a blackholed frame (dropped, connection alive), then a sever —
    and the listener survives the sever so a redial works."""
    port_s, port_p = _echo_server_port(0), _echo_server_port(1)

    async def run():
        async def echo(reader, writer):
            try:
                while True:
                    obj = await rpc._recv(reader)
                    await rpc._send(writer, ("echo", obj))
            except (asyncio.IncompleteReadError, ConnectionResetError):
                writer.close()

        srv = await asyncio.start_server(echo, "127.0.0.1", port_s)
        faults = parse_faults("t:blackhole@msg=2;t:sever@msg=4")
        px = await ChaosProxy(
            "127.0.0.1", port_p, "127.0.0.1", port_s, faults, link="t"
        ).start()

        r, w = await asyncio.open_connection("127.0.0.1", port_p)
        await rpc._send(w, "one")  # frame 1: forwarded
        assert await rpc._recv(r) == ("echo", "one")
        await rpc._send(w, "two")  # frame 2: black-holed silently
        await rpc._send(w, "three")  # frame 3: forwarded (echo of three)
        assert await rpc._recv(r) == ("echo", "three")
        await rpc._send(w, "four")  # frame 4: sever
        with pytest.raises((asyncio.IncompleteReadError, ConnectionResetError)):
            await rpc._recv(r)
        # the listener survives: a fresh dial works end-to-end
        r2, w2 = await asyncio.open_connection("127.0.0.1", port_p)
        await rpc._send(w2, "again")
        assert await rpc._recv(r2) == ("echo", "again")
        assert ("blackhole", "c2s", 2) in px.fired
        assert ("sever", "c2s", 4) in px.fired
        w2.close()
        await px.stop()
        srv.close()
        await srv.wait_closed()

    asyncio.run(run())


def test_chaos_proxy_truncate_tears_the_frame():
    port_s, port_p = _echo_server_port(2), _echo_server_port(3)

    async def run():
        got = []

        async def sink(reader, writer):
            try:
                got.append(await rpc._recv(reader))
            except (asyncio.IncompleteReadError, ConnectionResetError) as e:
                got.append(("torn", type(e).__name__))
            finally:
                writer.close()  # srv.wait_closed() waits for this end

        srv = await asyncio.start_server(sink, "127.0.0.1", port_s)
        px = await ChaosProxy(
            "127.0.0.1", port_p, "127.0.0.1", port_s,
            parse_faults("t:truncate@msg=1"), link="t",
        ).start()
        r, w = await asyncio.open_connection("127.0.0.1", port_p)
        await rpc._send(w, {"payload": list(range(100))})
        await asyncio.sleep(0.2)
        assert got and got[0][0] == "torn"
        await px.stop()
        srv.close()
        await srv.wait_closed()

    asyncio.run(run())


def test_chaos_proxy_delay_defers_the_frame():
    port_s, port_p = _echo_server_port(4), _echo_server_port(5)

    async def run():
        async def echo(reader, writer):
            while True:
                await rpc._send(writer, await rpc._recv(reader))

        srv = await asyncio.start_server(echo, "127.0.0.1", port_s)
        px = await ChaosProxy(
            "127.0.0.1", port_p, "127.0.0.1", port_s,
            parse_faults("t:delay@msg=1,ms=150"), link="t",
        ).start()
        r, w = await asyncio.open_connection("127.0.0.1", port_p)
        loop = asyncio.get_event_loop()
        t0 = loop.time()
        await rpc._send(w, "slow")
        assert await rpc._recv(r) == "slow"
        assert loop.time() - t0 >= 0.14
        await px.stop()
        srv.close()
        await srv.wait_closed()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# the device-loss schedule (FHH_MESH_FAULTS): grammar and fire-once.  What
# a sharded server does with a fired clause is tests/test_multichip.py's.
# ---------------------------------------------------------------------------


def test_parse_mesh_faults_grammar():
    faults = parse_mesh_faults(
        "mesh:drop@level=3;mesh:kill@level=5;mesh:delay@level=1,ms=50"
    )
    assert [f.action for f in faults] == ["drop", "kill", "delay"]
    assert faults[0].at_level == 3
    assert faults[2].ms == 50
    assert parse_mesh_faults("") == [] and parse_mesh_faults(None) == []


@pytest.mark.parametrize(
    "bad",
    [
        "mesh:drop",  # no trigger
        "mesh:drop@ms=5",  # missing level=
        "mesh:explode@level=1",  # unknown action
        "plane:drop@level=1",  # wrong link
        "mesh:drop@level=-1",  # negative level
        "garbage",
    ],
)
def test_parse_mesh_faults_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_mesh_faults(bad)


def test_mesh_chaos_clauses_fire_once():
    """A fired clause must not re-trigger on the recovery re-run of the
    same level (the injector's twin of the proxy's consumed severs)."""

    class Session:  # minimal stand-in for what a kill clobbers
        frontier = object()
        _children = None

    chaos = MeshChaos([MeshFaultSpec("drop", 2)])
    chaos.before_level(Session(), 0)  # below the trigger: nothing
    with pytest.raises(MeshFaultError) as ei:
        chaos.before_level(Session(), 2)
    assert not ei.value.state_lost
    chaos.before_level(Session(), 2)  # the re-run proceeds
    assert chaos.fired == [("drop", 2)]


# ---------------------------------------------------------------------------
# protocol-level resilience: sessions, dedup, budgets
# ---------------------------------------------------------------------------


def _cfg(port_base, **kw):
    defaults = dict(
        data_len=5,
        n_dims=1,
        ball_size=1,
        addkey_batch_size=8,
        num_sites=4,
        threshold=0.2,
        zipf_exponent=1.03,
        server0=f"127.0.0.1:{port_base}",
        server1=f"127.0.0.1:{port_base + 10}",
        distribution="zipf",
        f_max=32,
    )
    defaults.update(kw)
    return Config(**defaults)


def _client_keys(rng, L, n):
    pts = np.concatenate(
        [np.full(n - 4, 11), rng.integers(0, 1 << L, size=4)]
    )[:, None]
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    return ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")


async def _start_servers(cfg, port_base, ckpt_dir=None):
    s0 = rpc.CollectorServer(0, cfg, ckpt_dir=ckpt_dir)
    s1 = rpc.CollectorServer(1, cfg, ckpt_dir=ckpt_dir)
    t1 = asyncio.create_task(
        s1.start("127.0.0.1", port_base + 10, "127.0.0.1", port_base + 11)
    )
    await asyncio.sleep(0.05)
    t0 = asyncio.create_task(
        s0.start("127.0.0.1", port_base, "127.0.0.1", port_base + 11)
    )
    await asyncio.gather(t0, t1)
    return s0, s1


def test_session_replay_answers_from_cache():
    """The idempotent-replay contract at the frame level: resending the
    SAME (session, req_id) does not re-execute the verb — the second
    response comes from the dedup cache (stateful add_keys appends once)."""
    port = BASE_PORT

    async def run():
        cfg = _cfg(port)
        s0, s1 = await _start_servers(cfg, port)
        r, w = await asyncio.open_connection("127.0.0.1", port)
        await rpc._send(w, (1, "__hello__", {"session": "t-sess", "epoch": 1}))
        hello = await rpc._recv(r)
        assert hello[0] == 1 and "boot_id" in hello[1]
        await rpc._send(w, (2, "reset", {}))
        assert (await rpc._recv(r))[1] is True
        k0, _ = _client_keys(np.random.default_rng(7), 5, 6)
        chunk = tuple(np.asarray(x) for x in k0)
        frame = (3, "add_keys", {"keys": chunk, "n": 6, "lo": 0})
        await rpc._send(w, frame)
        assert (await rpc._recv(r))[1] is True
        await rpc._send(w, frame)  # replay: same req_id, same session
        assert (await rpc._recv(r))[1] is True
        # applied ONCE: rows [0, 6) written once (a second write would
        # refuse the crawl as an overlap), and their bytes placed once
        assert s0.key_planes.written == [(0, 6)]
        assert s0.obs.counter_value("keys_placed_bytes") == sum(
            x.nbytes for x in chunk
        )
        await rpc._send(w, (4, "status", {}))
        st = (await rpc._recv(r))[1]
        assert st["dedup_hits"] == 1
        # a replayed ERROR response is also served from cache
        await rpc._send(w, (5, "tree_restore", {"level": 0}))
        e1 = (await rpc._recv(r))[1]
        await rpc._send(w, (5, "tree_restore", {"level": 0}))
        e2 = (await rpc._recv(r))[1]
        assert "__error__" in e1 and e1 == e2
        w.close()
        await s0.aclose()
        await s1.aclose()

    asyncio.run(run())


def test_client_reconnects_and_replays_across_sever():
    """Sever the response direction (verb EXECUTED, response lost): the
    client redials through the same proxy and replays; the server answers
    from the dedup cache — visible as a dedup hit, and reset ran once."""
    port, pxport = BASE_PORT + 100, BASE_PORT + 101

    async def run():
        cfg = _cfg(port)
        s0, s1 = await _start_servers(cfg, port)
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:sever@msg=2,dir=s2c"), link="ctl0",
        ).start()
        c0 = await rpc.CollectorClient.connect("127.0.0.1", pxport)
        # frame 1 s2c = hello response; frame 2 s2c = reset response: the
        # reset executes, its response is severed, the client replays
        assert await c0.call("reset") is True
        st = await c0.call("status")
        assert c0.epoch == 2  # reconnected exactly once
        assert st["dedup_hits"] == 1  # the replayed reset hit the cache
        await px.stop()
        await c0.aclose()
        await s0.aclose()
        await s1.aclose()

    asyncio.run(run())


def test_reset_clears_stale_checkpoints(tmp_path):
    """A new collection must not be resumable from the previous one's
    checkpoint files: reset wipes this server's level-stamped blobs
    (regression: the keep=0 prune path once sliced to the empty list)."""
    s = rpc.CollectorServer(0, _cfg(BASE_PORT + 300), ckpt_dir=str(tmp_path))
    for lvl in (1, 3):
        (tmp_path / f"fhh_server0_l{lvl}.npz").write_bytes(b"x")
    (tmp_path / "fhh_server1_l1.npz").write_bytes(b"x")  # peer's: untouched
    asyncio.run(s.reset({}))
    left = sorted(p.name for p in tmp_path.iterdir())
    assert left == ["fhh_server1_l1.npz"]


def test_session_cache_is_byte_bounded():
    """Bulky responses must not pin unbounded memory: the dedup cache
    evicts by BYTES as well as count, but always keeps the newest entry
    (its own replay needs it)."""
    sess = rpc._Session()
    big = np.zeros(rpc._SESSION_CACHE_BYTES // 4, np.uint8)  # ~32 MB each
    for i in range(1, 8):
        sess.put(i, {"shares": big})
    assert len(sess.cache) < 7  # byte bound evicted old entries
    assert 7 in sess.cache  # newest always survives
    assert sess.bytes_total <= rpc._SESSION_CACHE_BYTES + big.nbytes
    one = rpc._Session()
    one.put(1, np.zeros(rpc._SESSION_CACHE_BYTES + 1024, np.uint8))
    assert 1 in one.cache  # over-cap singleton survives


def test_run_supervised_malicious_requires_sketch_material(rng):
    """Malicious mode IS supervisable now (the challenge ratchet), but
    only with the sketch key batches along — without them the crawl
    would silently run semi-honest, so the refusal comes before any
    server is touched."""
    cfg = _cfg(BASE_PORT + 310, malicious=True)
    k0, k1 = _client_keys(rng, 5, 6)

    async def run():
        from types import SimpleNamespace

        stub = SimpleNamespace()  # never dialed: the refusal comes first
        lead = RpcLeader(cfg, stub, SimpleNamespace())
        await lead.run_supervised(6, k0, k1)

    with pytest.raises(ValueError, match="malicious"):
        asyncio.run(run())


def test_blackhole_exhausts_verb_budget_loudly():
    """Frames silently dropped (no FIN/RST): the per-verb wall-clock
    budget converts the would-be infinite hang into TimeoutError."""
    port, pxport = BASE_PORT + 120, BASE_PORT + 121

    async def run():
        cfg = _cfg(port)
        s0, s1 = await _start_servers(cfg, port)
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:blackhole@msg=2,count=99"), link="ctl0",
        ).start()
        c0 = await rpc.CollectorClient.connect(
            "127.0.0.1", pxport,
            budgets=respolicy.VerbBudgets(default_s=0.6, per_verb={}),
        )
        with pytest.raises(TimeoutError):
            await c0.call("reset")
        await px.stop()
        await c0.aclose()
        await s0.aclose()
        await s1.aclose()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# e2e recovery: the acceptance scenario
# ---------------------------------------------------------------------------


async def _crawl_with_chaos(cfg, k0, k1, nreqs, *, ckpt_dir, ctl0_proxy=None,
                            assassin=None, checkpoint_every=2,
                            sk0=None, sk1=None, budgets=None):
    """One supervised crawl with optional chaos: a proxy on the
    leader↔server0 control link and/or an assassin coroutine (given the
    live servers dict + leader) that kills/restarts servers mid-crawl.
    ``sk0``/``sk1`` ride along for malicious (sketch) mode; ``budgets``
    overrides the clients' per-verb wall-clock budgets.
    Returns (result, leader, (c0, c1), live-servers dict)."""
    host0, p0 = cfg.server0.rsplit(":", 1)
    host1, p1 = cfg.server1.rsplit(":", 1)
    p0, p1 = int(p0), int(p1)
    live = {}
    live["s0"], live["s1"] = await _start_servers(cfg, p0, ckpt_dir=ckpt_dir)
    dial0 = (host0, p0)
    if ctl0_proxy is not None:
        dial0 = (ctl0_proxy.listen_host, ctl0_proxy.listen_port)
    c0 = await rpc.CollectorClient.connect(*dial0, budgets=budgets)
    c1 = await rpc.CollectorClient.connect(host1, p1, budgets=budgets)
    lead = RpcLeader(cfg, c0, c1)
    kill_task = (
        asyncio.create_task(assassin(live, lead))
        if assassin is not None
        else None
    )
    res = await lead.run_supervised(
        nreqs, k0, k1, sk0, sk1, checkpoint_every=checkpoint_every
    )
    if kill_task is not None:
        await kill_task
    return res, lead, (c0, c1), live


async def _teardown(clients, live, *proxies):
    for px in proxies:
        await px.stop()
    for c in clients:
        await c.aclose()
    for s in live.values():
        await s.aclose()


def _hitters(res):
    return {
        tuple(int(v) for v in r): int(c)
        for r, c in zip(res.decode_ints(), res.counts)
    }


def _kill_and_restart_s1_at_first_checkpoint(cfg, port, ck):
    """Assassin: the moment the leader banks its first checkpoint
    (level 1 with checkpoint_every=2), kill server 1 — every-loop-tick
    polling on the leader's own counter, so the kill always lands
    mid-crawl — and bring a FRESH CollectorServer up on the same ports
    shortly after (the in-process equivalent of process death: all
    in-memory protocol state gone, checkpoint files survive)."""

    async def assassin(live, lead):
        while lead.obs.counter_value("crawl_checkpoints") < 1:
            await asyncio.sleep(0)
        await live["s1"].aclose()
        await asyncio.sleep(0.3)
        live["s1"] = rpc.CollectorServer(1, cfg, ckpt_dir=str(ck))
        await live["s1"].start(
            "127.0.0.1", port + 10, "127.0.0.1", port + 11
        )

    return assassin


@pytest.mark.parametrize("secure", [False, True], ids=["trusted", "secure"])
def test_e2e_chaos_recovery_bit_identical(rng, tmp_path, secure):
    """THE acceptance scenario: a crawl whose leader↔server0 control link
    is severed mid-crawl (response direction: the verb executed, its
    response was lost — forcing a true idempotent replay) AND whose
    server 1 is killed and restarted at a checkpoint boundary completes
    bit-identical to a fault-free run, with no verb double-applied (the
    dedup-hit counter proves the replay came from cache; set equality
    proves nothing applied twice).  The secure variant runs the full
    GC+OT data plane and re-keys it on recovery (fresh base-OT via
    _plane_handshake)."""
    L, n = 5, 12
    port = BASE_PORT + (140 if secure else 180)
    pxport = port + 20
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port, secure_exchange=secure)
    ck = tmp_path / "ckpt"
    ck_ff = tmp_path / "ckpt_ff"
    ck.mkdir(), ck_ff.mkdir()

    async def faulty():
        # sever the s2c (response) direction mid-crawl: the severed verb
        # has already executed server-side, so the post-reconnect resend
        # MUST be answered from the dedup cache, not re-applied
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:sever@msg=9,dir=s2c"), link="ctl0",
        ).start()
        res, lead, (c0, c1), live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=str(ck), ctl0_proxy=px,
            assassin=_kill_and_restart_s1_at_first_checkpoint(cfg, port, ck),
        )
        st0 = await c0.call("status")
        epochs = (c0.epoch, c1.epoch)
        await _teardown((c0, c1), live, px)
        return res, lead, st0, epochs

    async def fault_free():
        res, lead, (c0, c1), live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=str(ck_ff)
        )
        await _teardown((c0, c1), live)
        return res

    res_ff = asyncio.run(fault_free())
    res, lead, st0, epochs = asyncio.run(faulty())

    # bit-identical results: faulty == fault-free == colocated oracle
    want_res = driver.Leader(
        *driver.make_servers(k0, k1), n_dims=1, data_len=L, f_max=cfg.f_max
    ).run(nreqs=n, threshold=cfg.threshold)
    assert _hitters(res) == _hitters(res_ff) == _hitters(want_res)
    assert _hitters(res)  # non-empty: the stacked clients clear threshold
    np.testing.assert_array_equal(res.paths, res_ff.paths)
    np.testing.assert_array_equal(res.counts, res_ff.counts)

    # the faults actually happened AND were survived:
    assert epochs[0] >= 2  # leader↔s0 reconnected across the sever
    assert st0["dedup_hits"] >= 1  # replayed verb answered from cache
    assert lead.obs.counter_value("recoveries") >= 1  # s1 restart recovered


def test_supervised_without_ckpt_dir_degrades_gracefully(rng, tmp_path):
    """Servers without FHH_CKPT_DIR refuse tree_checkpoint; supervision
    must degrade (checkpointing disabled after one warn) and still
    complete the crawl."""
    L, n = 5, 12
    port = BASE_PORT + 220
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port)

    async def run():
        res, lead, clients, live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=None
        )
        await _teardown(clients, live)
        return res, lead

    res, lead = asyncio.run(run())
    want_res = driver.Leader(
        *driver.make_servers(k0, k1), n_dims=1, data_len=L, f_max=cfg.f_max
    ).run(nreqs=n, threshold=cfg.threshold)
    assert _hitters(res) == _hitters(want_res)
    assert lead.obs.counter_value("crawl_checkpoints") == 0


# ---------------------------------------------------------------------------
# challenge ratchet: unit semantics + restartable sketch crawls
# ---------------------------------------------------------------------------


def test_ratchet_seed_deterministic_and_sensitive():
    """The restartability contract: identical (root, level, transcript)
    -> identical challenge; ANY divergence -> a different challenge.
    Bucket padding must not perturb the transcript (min_bucket varies
    between hosts but the crawl is the same crawl)."""
    from fuzzyheavyhitters_tpu.protocol import sketch as sketchmod

    root = np.arange(4, dtype=np.uint32)
    d0 = sketchmod.transcript_init()
    a = sketchmod.ratchet_seed(root, 3, d0)
    assert a.dtype == np.uint32 and a.shape == (4,)
    np.testing.assert_array_equal(a, sketchmod.ratchet_seed(root, 3, d0))
    assert not np.array_equal(a, sketchmod.ratchet_seed(root, 4, d0))
    assert not np.array_equal(
        a, sketchmod.ratchet_seed(root ^ np.uint32(1), 3, d0)
    )
    parent = np.array([0, 0], np.int32)
    bits = np.array([[True], [False]])
    d1 = sketchmod.transcript_absorb(d0, 0, parent, bits, 1)
    assert d1 != d0
    assert not np.array_equal(a, sketchmod.ratchet_seed(root, 3, d1))
    # only the REAL survivor entries are absorbed: padding is invisible
    padded = sketchmod.transcript_absorb(
        d0, 0, np.array([0, 99], np.int32),
        np.array([[True], [True]]), 1,
    )
    assert padded == d1


def test_e2e_sketch_recovery_bit_identical(rng, tmp_path):
    """THE sketch acceptance scenario: a MALICIOUS-mode crawl whose
    leader↔server0 control link is severed mid-crawl AND whose server 1
    is killed and restarted at a checkpoint boundary completes
    bit-identically to a fault-free malicious run — cheater exclusion
    included (the ratchet replays each recovered level's challenge
    exactly, so re-opened Beaver slabs reveal nothing new and honest
    clients' liveness flags land identically), with the recovery
    distinguishable in the run report."""
    from fuzzyheavyhitters_tpu.obs import report as obsreport
    from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
    from fuzzyheavyhitters_tpu.protocol import sketch as sketchmod

    L, n = 5, 12
    port = BASE_PORT + 340
    pxport = port + 20
    pts = np.array([[11]] * 8 + [[25], [2], [50], [60]])
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")
    seeds = rng.integers(0, 2**32, size=(n, 2, 4), dtype=np.uint32)
    cseed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    sk0, sk1 = sketchmod.gen(seeds, pts_bits[:, 0, :], FE62, F255, cseed)
    # client 3 forges its level-2 payload (handed identically to both):
    # its exclusion must SURVIVE the recovery re-runs
    bad = np.asarray(sk0.key.cw_val).copy()
    bad[3, 0, 2, 0] = (int(bad[3, 0, 2, 0]) + 1) % FE62.P
    import jax.numpy as jnp

    j = jnp.asarray(bad)
    sk0 = sk0._replace(key=sk0.key._replace(cw_val=j))
    sk1 = sk1._replace(key=sk1.key._replace(cw_val=j))
    cfg = _cfg(
        port, malicious=True, threshold=0.5, addkey_batch_size=12
    )
    ck, ck_ff = tmp_path / "ckpt", tmp_path / "ckpt_ff"
    ck.mkdir(), ck_ff.mkdir()

    async def faulty():
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:sever@msg=9,dir=s2c"), link="ctl0",
        ).start()
        res, lead, clients, live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=str(ck), ctl0_proxy=px,
            assassin=_kill_and_restart_s1_at_first_checkpoint(cfg, port, ck),
            sk0=sk0, sk1=sk1,
        )
        alive = live["s0"].alive_keys.copy()
        rep = obsreport.run_report(
            [lead.obs, live["s0"].obs, live["s1"].obs]
        )
        epochs = clients[0].epoch
        await _teardown(clients, live, px)
        return res, lead, alive, rep, epochs

    async def fault_free():
        res, lead, clients, live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=str(ck_ff), sk0=sk0, sk1=sk1
        )
        alive = live["s0"].alive_keys.copy()
        rep = obsreport.run_report(
            [lead.obs, live["s0"].obs, live["s1"].obs]
        )
        await _teardown(clients, live)
        return res, alive, rep

    res_ff, alive_ff, rep_ff = asyncio.run(fault_free())
    res, lead, alive, rep, epochs = asyncio.run(faulty())

    # bit-identical results AND liveness: the cheater (client 3) stays
    # excluded, every honest client stays alive, counts match exactly
    want_alive = np.ones(n, bool)
    want_alive[3] = False
    np.testing.assert_array_equal(alive, want_alive)
    np.testing.assert_array_equal(alive_ff, want_alive)
    assert _hitters(res) == _hitters(res_ff) == {(10,): 7, (11,): 7, (12,): 7}
    np.testing.assert_array_equal(res.paths, res_ff.paths)
    np.testing.assert_array_equal(res.counts, res_ff.counts)

    # the faults happened, were survived, and are visible in the report
    assert epochs >= 2  # leader↔s0 reconnected across the sever
    assert lead.obs.counter_value("recoveries") >= 1
    assert rep["recovery"]["count"] >= 1
    assert rep["recovery"]["levels_rerun"] >= 1
    assert rep["recovery"]["dedup_hits"] >= 1
    assert rep["recovery"]["dedup_hit_rate"] > 0
    assert rep_ff["recovery"]["count"] == 0  # distinguishable


def test_sketch_recover_refuses_scratch_restart(rng):
    """Stash-less recovery in sketch mode must refuse BEFORE touching any
    server: re-uploading the same Beaver triple shares under a freshly
    coin-flipped ratchet root opens the same slabs under two challenges —
    the <r - r', x> leak the ratchet exists to prevent."""
    from types import SimpleNamespace

    from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
    from fuzzyheavyhitters_tpu.protocol import sketch as sketchmod

    cfg = _cfg(BASE_PORT + 520, malicious=True)
    k0, k1 = _client_keys(rng, 5, 6)
    seeds = rng.integers(0, 2**32, size=(6, 1, 2, 4), dtype=np.uint32)
    cseed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    sk0, sk1 = sketchmod.gen(
        seeds, rng.integers(0, 2, size=(6, 1, 5)).astype(bool),
        FE62, F255, cseed,
    )
    lead = RpcLeader(cfg, SimpleNamespace(), SimpleNamespace())  # no dials

    async def run():
        await lead._recover(k0, k1, sk0, sk1, None)

    with pytest.raises(ValueError, match="fresh sketch keys"):
        asyncio.run(run())


def test_sketch_early_fault_recovers_via_init_checkpoint(rng, tmp_path):
    """A sketch-mode fault BEFORE any level checkpoint must roll back to
    the init (level -1) checkpoint — committed root, empty transcript —
    and replay from level 0 bit-identically, never restart from scratch.
    checkpoint_every=5 at L=5 means the init checkpoint is the ONLY one,
    so the restore path is deterministic."""
    from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
    from fuzzyheavyhitters_tpu.protocol import sketch as sketchmod

    L, n = 5, 12
    port = BASE_PORT + 540
    pts = np.array([[11]] * 8 + [[25], [2], [50], [60]])
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")
    seeds = rng.integers(0, 2**32, size=(n, 2, 4), dtype=np.uint32)
    cseed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    sk0, sk1 = sketchmod.gen(seeds, pts_bits[:, 0, :], FE62, F255, cseed)
    cfg = _cfg(port, malicious=True, threshold=0.5, addkey_batch_size=12)
    ck = tmp_path / "ckpt"
    ck.mkdir()

    def kill_after_level0(cfg, port, ck):
        async def assassin(live, lead):
            # level 0 done (paths grew) but no level checkpoint exists:
            # the only rollback point is the init (-1) blob
            while lead.paths is None or lead.paths.shape[-1] < 1:
                await asyncio.sleep(0)
            await live["s1"].aclose()
            await asyncio.sleep(0.3)
            live["s1"] = rpc.CollectorServer(1, cfg, ckpt_dir=str(ck))
            await live["s1"].start(
                "127.0.0.1", port + 10, "127.0.0.1", port + 11
            )

        return assassin

    async def run():
        res, lead, clients, live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=str(ck), sk0=sk0, sk1=sk1,
            checkpoint_every=5,
            assassin=kill_after_level0(cfg, port, ck),
        )
        alive = live["s0"].alive_keys.copy()
        await _teardown(clients, live)
        return res, lead, alive

    res, lead, alive = asyncio.run(run())
    assert (ck / "fhh_server0_l-1.npz").exists()  # the init checkpoint
    assert lead.obs.counter_value("recoveries") >= 1
    # honest batch (8 clients at 11, nobody forged): all 8 count
    assert _hitters(res) == {(10,): 8, (11,): 8, (12,): 8}
    assert alive.all()  # nobody excluded by the replayed challenges


# ---------------------------------------------------------------------------
# sharded mid-level retry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("secure", [False, True], ids=["trusted", "secure"])
def test_sharded_crawl_matches_unsharded(rng, secure):
    """crawl_shard_nodes splits every level into per-span verbs; with no
    faults the assembled counts must be bit-identical to the one-verb
    crawl (mask rows, children and leaf shares all reassemble exactly)."""
    L, n = 5, 12
    port = BASE_PORT + (400 if secure else 440)
    k0, k1 = _client_keys(rng, L, n)

    async def run(shard_nodes, port_base):
        cfg = _cfg(
            port_base, secure_exchange=secure, crawl_shard_nodes=shard_nodes
        )
        s0, s1 = await _start_servers(cfg, port_base)
        c0 = await rpc.CollectorClient.connect("127.0.0.1", port_base)
        c1 = await rpc.CollectorClient.connect("127.0.0.1", port_base + 10)
        lead = RpcLeader(cfg, c0, c1)
        await lead._both("reset")
        await lead.upload_keys(k0, k1)
        res = await lead.run(n)
        await _teardown((c0, c1), {"s0": s0, "s1": s1})
        return res

    res_sharded = asyncio.run(run(1, port))
    res_whole = asyncio.run(run(0, port + 30))
    assert _hitters(res_sharded) == _hitters(res_whole) and _hitters(res_whole)
    np.testing.assert_array_equal(res_sharded.counts, res_whole.counts)
    np.testing.assert_array_equal(res_sharded.paths, res_whole.paths)


def test_e2e_mid_level_shard_loss_bit_identical(rng, tmp_path):
    """The mid-level acceptance scenario: one crawl-shard request is
    black-holed mid-level (no FIN — the verb budget converts it into a
    loud timeout), and the leader re-runs ONLY that shard (fresh data
    plane, same span) instead of rolling the level back.  Results are
    bit-identical to the fault-free run; the shard re-run is counted in
    the run report."""
    from fuzzyheavyhitters_tpu.obs import report as obsreport

    L, n = 5, 12
    port = BASE_PORT + 480
    pxport = port + 20
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port, crawl_shard_nodes=1)
    ck, ck_ff = tmp_path / "ckpt", tmp_path / "ckpt_ff"
    ck.mkdir(), ck_ff.mkdir()
    # generous enough for a warm level, small enough to keep the test
    # quick: level 0 (the compile) runs before the fault ordinal
    budgets = respolicy.VerbBudgets(default_s=10.0, per_verb={})

    async def faulty():
        # c2s frame 9 is a level-1 shard request (hello, reset, 2x
        # add_keys, tree_init, L0 crawl, L0 prune, then the level-1
        # spans): drop exactly one — the leader must re-run that span
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:blackhole@msg=9,count=1"), link="ctl0",
        ).start()
        res, lead, clients, live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=str(ck), ctl0_proxy=px, budgets=budgets
        )
        rep = obsreport.run_report([lead.obs, live["s0"].obs, live["s1"].obs])
        await _teardown(clients, live, px)
        return res, lead, rep

    async def fault_free():
        res, lead, clients, live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=str(ck_ff), budgets=budgets
        )
        await _teardown(clients, live)
        return res

    res_ff = asyncio.run(fault_free())
    res, lead, rep = asyncio.run(faulty())

    want_res = driver.Leader(
        *driver.make_servers(k0, k1), n_dims=1, data_len=L, f_max=cfg.f_max
    ).run(nreqs=n, threshold=cfg.threshold)
    assert _hitters(res) == _hitters(res_ff) == _hitters(want_res)
    assert _hitters(res)
    np.testing.assert_array_equal(res.counts, res_ff.counts)

    # the shard — not the level, not the crawl — was the retry unit
    assert lead.obs.counter_value("shards_rerun") >= 1
    assert lead.obs.counter_value("levels_rerun") == 0
    assert rep["recovery"]["shards_rerun"] >= 1
    assert rep["recovery"]["levels_rerun"] == 0


# ---------------------------------------------------------------------------
# checkpoint negative paths + prune ordering
# ---------------------------------------------------------------------------


def _server_with_ckpt(tmp_path, level=1, seed=7, port_off=500):
    """A lone server with keys, a root frontier, and one checkpoint at
    ``level`` (checkpoint/restore never touch the data plane, so no peer
    or listener is needed)."""
    s = rpc.CollectorServer(0, _cfg(BASE_PORT + port_off), ckpt_dir=str(tmp_path))
    k0, _ = _client_keys(np.random.default_rng(seed), 5, 6)

    async def go():
        await s.add_keys({"keys": tuple(np.asarray(x) for x in k0)})
        await s.tree_init({})
        await s.tree_checkpoint({"level": level})

    asyncio.run(go())
    return s


def test_tree_restore_rejects_mismatched_key_fingerprint(tmp_path):
    """A checkpoint written under one key batch must refuse to restore
    under another — and leave the refusing server's state untouched."""
    _server_with_ckpt(tmp_path, seed=7)
    other = rpc.CollectorServer(
        0, _cfg(BASE_PORT + 502), ckpt_dir=str(tmp_path)
    )
    k_other, _ = _client_keys(np.random.default_rng(8), 5, 6)

    async def go():
        await other.add_keys({"keys": tuple(np.asarray(x) for x in k_other)})
        with pytest.raises(RuntimeError, match="different key batch"):
            await other.tree_restore({"level": 1})

    asyncio.run(go())
    assert other.frontier is None  # nothing mutated on the failed path


def test_tree_restore_rejects_truncated_npz(tmp_path):
    """A torn/partially-written blob (crash mid-write of a NON-atomic
    copy, disk-full tail loss) must fail loudly as corruption and leave
    the live frontier exactly as it was."""
    s = _server_with_ckpt(tmp_path, port_off=504)
    path = s._ckpt_path(1)
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: len(data) // 2])
    frontier_before = s.frontier
    alive_before = s.alive_keys.copy()

    async def go():
        with pytest.raises(RuntimeError, match="corrupt or truncated"):
            await s.tree_restore({"level": 1})

    asyncio.run(go())
    assert s.frontier is frontier_before
    np.testing.assert_array_equal(s.alive_keys, alive_before)


def test_tree_restore_rejects_deeper_level_than_tree(tmp_path):
    """A blob stamped deeper than this key batch's tree (data_len=5 ->
    deepest resumable level is 3) is a wrong-collection artifact, not a
    resume point."""
    s = _server_with_ckpt(tmp_path, level=7, port_off=506)

    async def go():
        with pytest.raises(RuntimeError, match="deeper than"):
            await s.tree_restore({"level": 7})

    asyncio.run(go())


def test_tree_restore_rejects_renamed_level_stamp(tmp_path):
    """The filename stamp and the blob's recorded level must agree — a
    renamed (or mis-copied) checkpoint restores the WRONG level's
    frontier otherwise."""
    import os as _os

    s = _server_with_ckpt(tmp_path, level=1, port_off=508)
    _os.rename(s._ckpt_path(1), s._ckpt_path(3))

    async def go():
        with pytest.raises(RuntimeError, match="records level"):
            await s.tree_restore({"level": 3})

    asyncio.run(go())


def test_ckpt_prune_and_latest_order_numerically(tmp_path):
    """Regression for levels >= 10: the keep-2 prune and the ckpt_levels
    listing must order level stamps NUMERICALLY — lexicographic ordering
    would rank l9 above l10/l11 and prune the two newest checkpoints."""
    s = rpc.CollectorServer(0, _cfg(BASE_PORT + 510), ckpt_dir=str(tmp_path))
    for lvl in (2, 9, 10, 11):
        (tmp_path / f"fhh_server0_l{lvl}.npz").write_bytes(b"x")
    assert s._ckpt_levels() == [2, 9, 10, 11]
    s._ckpt_prune(keep=2)
    left = sorted(p.name for p in tmp_path.iterdir())
    assert left == ["fhh_server0_l10.npz", "fhh_server0_l11.npz"]
    assert s._ckpt_levels() == [10, 11]


@pytest.mark.slow
def test_e2e_chaos_storm_multiple_faults(rng, tmp_path):
    """Stress variant (redundant coverage of the same recovery paths at a
    nastier schedule): a data-plane sever AND a server kill+restart AND a
    second control-link sever in one crawl."""
    L, n = 5, 12
    port = BASE_PORT + 260
    pxport = port + 20
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port)
    ck = tmp_path / "ckpt"
    ck.mkdir()

    async def run():
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:sever@msg=7,dir=s2c;ctl0:sever@msg=10"),
            link="ctl0",
        ).start()
        base = _kill_and_restart_s1_at_first_checkpoint(cfg, port, ck)

        async def assassin(live, lead):
            # cut the data plane out from under the live crawl first
            while lead.obs.counter_value("crawl_checkpoints") < 1:
                await asyncio.sleep(0)
            if live["s0"]._peer is not None:
                live["s0"]._peer.close()
            await base(live, lead)

        res, lead, clients, live = await _crawl_with_chaos(
            cfg, k0, k1, n, ckpt_dir=str(ck), ctl0_proxy=px,
            assassin=assassin,
        )
        await _teardown(clients, live, px)
        return res, lead

    res, lead = asyncio.run(run())
    want_res = driver.Leader(
        *driver.make_servers(k0, k1), n_dims=1, data_len=L, f_max=cfg.f_max
    ).run(nreqs=n, threshold=cfg.threshold)
    assert _hitters(res) == _hitters(want_res) and _hitters(res)
    assert lead.obs.counter_value("recoveries") >= 1
