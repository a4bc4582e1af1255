"""Streaming ingest front door: admission control, backpressure, load
shedding, windowed crawls, and window-consistent recovery.

The acceptance surface of the overload-robustness layer: a windowed crawl
over a frozen ingest window is BIT-EXACT vs a batch crawl over the same
admitted key set — with ingest running concurrently, under a duplicate-
delivery (flood) chaos schedule, and across a server kill/restart
mid-window.  Overload never corrupts: a flooding client is rejected
(retryable Overloaded) or its submissions shed into a seeded reservoir
sample; other clients' keys all land; every verdict is idempotent per
``sub_id`` so at-least-once delivery never double-admits.

Shapes mirror tests/test_resilience.py (L=5, d=1) so the crawl kernels
compile once across the suites.
"""

import asyncio

import numpy as np
import pytest

from fuzzyheavyhitters_tpu import native
from fuzzyheavyhitters_tpu.obs import report as obsreport
from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.ops.ibdcf import IbDcfKeyBatch
from fuzzyheavyhitters_tpu.protocol import driver, rpc
from fuzzyheavyhitters_tpu.protocol.leader_rpc import (
    IngestOverloadedError,
    RpcLeader,
    WindowedIngest,
)
from fuzzyheavyhitters_tpu.resilience import admission
from fuzzyheavyhitters_tpu.resilience import policy as respolicy
from fuzzyheavyhitters_tpu.resilience.chaos import ChaosProxy, parse_faults
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 23231


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """CPU backend: the front door is host-side glue over the same crawl
    kernels the other protocol suites compile."""
    yield


# ---------------------------------------------------------------------------
# admission: token bucket, quotas, shed policies (pure units)
# ---------------------------------------------------------------------------


def test_token_bucket_deterministic_under_manual_clock():
    """The rate limit is a pure function of the (clock, take) sequence —
    the determinism the gate/mirror protocol and the tests stand on."""
    clock = admission.ManualClock()
    tb = admission.TokenBucket(rate_per_s=10.0, burst=5, clock=clock)
    takes = [tb.try_take(1) for _ in range(7)]
    assert takes == [True] * 5 + [False, False]  # burst spent, no refill
    assert tb.wait_s(1) == pytest.approx(0.1)
    clock.advance(0.35)  # 3.5 tokens back
    assert [tb.try_take(1) for _ in range(4)] == [True, True, True, False]
    clock.advance(100.0)  # refill caps at burst
    assert tb.tokens <= 5 or tb.try_take(5)
    # an identical second run makes identical decisions
    clock2 = admission.ManualClock()
    tb2 = admission.TokenBucket(rate_per_s=10.0, burst=5, clock=clock2)
    takes2 = [tb2.try_take(1) for _ in range(7)]
    assert takes2 == takes


def test_admission_quota_and_capacity_verdicts():
    ctl = admission.AdmissionController(
        max_window_keys=10, client_quota=4, shed="reject", seed=1
    )
    wa = ctl.window(0)
    assert ctl.admit(wa, "a", 3).admitted
    v = ctl.admit(wa, "a", 3)  # 6 > quota 4
    assert not v.admitted and v.scope == "quota"
    assert ctl.admit(wa, "b", 4).admitted
    assert ctl.admit(wa, "c", 3).admitted  # 10/10
    v = ctl.admit(wa, "d", 1)
    assert not v.admitted and v.scope == "capacity"


def test_admission_rate_verdict_carries_retry_hint():
    clock = admission.ManualClock()
    ctl = admission.AdmissionController(
        max_window_keys=1000, rate_keys_per_s=10.0, burst_keys=4,
        shed="reject", seed=1, clock=clock,
    )
    wa = ctl.window(0)
    assert ctl.admit(wa, "a", 4).admitted
    v = ctl.admit(wa, "a", 4)
    assert not v.admitted and v.scope == "rate" and v.retry_after_s > 0
    clock.advance(v.retry_after_s)
    assert ctl.admit(wa, "a", 4).admitted  # the hint was honest


def test_quota_rejection_never_drains_the_shared_bucket():
    """A quota-doomed flooder's retries must not convert into `rate`
    rejections for honest clients: the quota precheck runs before any
    tokens are spent."""
    clock = admission.ManualClock()
    ctl = admission.AdmissionController(
        max_window_keys=1000, rate_keys_per_s=10.0, burst_keys=10,
        client_quota=4, shed="reject", seed=1, clock=clock,
    )
    wa = ctl.window(0)
    assert ctl.admit(wa, "flooder", 4).admitted  # quota spent (4 tokens)
    for _ in range(50):  # futile flood: every retry is quota-rejected
        assert ctl.admit(wa, "flooder", 4).scope == "quota"
    v = ctl.admit(wa, "honest", 4)  # 6 tokens still there
    assert v.admitted, v


def test_burst_oversize_chunk_gets_distinct_scope():
    """n_keys > burst can never fit the bucket: the verdict says so
    (scope 'burst') instead of promising a refill horizon that cannot
    be kept."""
    ctl = admission.AdmissionController(
        max_window_keys=10**6, rate_keys_per_s=100.0, burst_keys=8,
        shed="reject", seed=1, clock=admission.ManualClock(),
    )
    wa = ctl.window(0)
    v = ctl.admit(wa, "a", 9)
    assert not v.admitted and v.scope == "burst"


def test_reservoir_mode_rejects_mismatched_chunk_size():
    """The slot-table pool bound rests on uniform chunks: a mismatched
    size is capacity-rejected BEFORE any sampler draw, so the sampling
    stream is untouched by the refusal."""
    ctl = admission.AdmissionController(
        max_window_keys=4, shed="reservoir", seed=3
    )
    wa = ctl.window(0)
    for i in range(6):  # engage the reservoir with 1-key chunks
        ctl.admit(wa, f"c{i}", 1)
    seen_before = wa.reservoir.seen
    v = ctl.admit(wa, "big", 2)
    assert not v.admitted and v.scope == "capacity"
    assert wa.reservoir.seen == seen_before  # no draw consumed
    # an oversized FIRST submission is rejected too (never an IndexError)
    wa2 = ctl.window(1)
    v2 = ctl.admit(wa2, "huge", 99)
    assert not v2.admitted and v2.scope == "capacity"


def test_reservoir_shed_is_seed_reproducible():
    """Same seed + same offer sequence -> identical slot decisions (and
    the native library, when present, matches the pure-Python twin
    bit-for-bit)."""
    def run(seed):
        ctl = admission.AdmissionController(
            max_window_keys=4, shed="reservoir", seed=seed
        )
        wa = ctl.window(0)
        out = []
        for i in range(20):
            v = ctl.admit(wa, f"c{i}", 1)
            out.append((v.admitted, v.slot, v.shed))
        return out

    a, b = run(7), run(7)
    assert a == b
    assert a[:4] == [(True, None, False)] * 4  # fill phase appends
    assert any(s is not None for _, s, _ in a[4:])  # replacements happened
    assert run(8) != a  # a different seed samples differently


def test_native_reservoir_matches_python_twin_and_state_roundtrip():
    r = native.Reservoir(4, 12345)
    slots = r.offer(40)
    py = native.Reservoir.__new__(native.Reservoir)
    py.k, py._lib, py._handle = 4, None, None
    py._py, py._seen = native._PyXoshiro256(12345), 0
    np.testing.assert_array_equal(slots, py.offer(40))
    # state round-trips mid-stream: the restored sampler continues the
    # SAME stream (what the checkpoint carries across a server restart)
    st = r.state()
    cont = native.Reservoir.from_state(st)
    fresh = native.Reservoir(4, 12345)
    fresh.offer(40)
    np.testing.assert_array_equal(cont.offer(25), fresh.offer(25))


# ---------------------------------------------------------------------------
# chaos grammar: flood + slowclient
# ---------------------------------------------------------------------------


def test_parse_faults_flood_and_slowclient():
    faults = parse_faults(
        "ctl0:flood@msg=3,count=4;ctl0:slowclient@msg=1,ms=40,count=3"
    )
    assert [f.action for f in faults] == ["flood", "slowclient"]
    assert faults[0].count == 4 and faults[1].ms == 40


def test_chaos_flood_duplicates_the_frame():
    """A flood clause delivers the trigger frame 1 + count times — the
    at-least-once pathology the dedup machinery must absorb."""
    port_s, port_p = BASE_PORT + 70, BASE_PORT + 71

    async def run():
        got = []

        async def sink(reader, writer):
            try:
                while True:
                    got.append(await rpc._recv(reader))
            except (asyncio.IncompleteReadError, ConnectionResetError):
                pass
            finally:
                writer.close()  # srv.wait_closed() waits for this end

        srv = await asyncio.start_server(sink, "127.0.0.1", port_s)
        px = await ChaosProxy(
            "127.0.0.1", port_p, "127.0.0.1", port_s,
            parse_faults("t:flood@msg=2,count=2"), link="t",
        ).start()
        r, w = await asyncio.open_connection("127.0.0.1", port_p)
        await rpc._send(w, "one")
        await rpc._send(w, "two")  # duplicated twice -> arrives 3x
        await rpc._send(w, "three")
        await asyncio.sleep(0.3)
        assert got == ["one", "two", "two", "two", "three"]
        assert ("flood", "c2s", 2) in px.fired
        w.close()
        await px.stop()
        srv.close()
        await srv.wait_closed()

    asyncio.run(run())


def test_chaos_slowclient_trickles_frames():
    port_s, port_p = BASE_PORT + 72, BASE_PORT + 73

    async def run():
        async def echo(reader, writer):
            while True:
                await rpc._send(writer, await rpc._recv(reader))

        srv = await asyncio.start_server(echo, "127.0.0.1", port_s)
        px = await ChaosProxy(
            "127.0.0.1", port_p, "127.0.0.1", port_s,
            parse_faults("t:slowclient@msg=1,ms=80,count=2"), link="t",
        ).start()
        r, w = await asyncio.open_connection("127.0.0.1", port_p)
        loop = asyncio.get_event_loop()
        t0 = loop.time()
        for m in ("a", "b", "c"):
            await rpc._send(w, m)
            assert await rpc._recv(r) == m
        # two frames trickled ~80 ms each; the third was full speed
        assert loop.time() - t0 >= 0.15
        assert [f[0] for f in px.fired] == ["slowclient", "slowclient"]
        await px.stop()
        srv.close()
        await srv.wait_closed()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# protocol harness
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


async def _bring_up(cfg, port, ckpt_dir=None, dial0=None, budgets=None):
    live = {}
    live["s0"], live["s1"] = await _start_servers(cfg, port, ckpt_dir)
    d0 = ("127.0.0.1", port) if dial0 is None else dial0
    c0 = await rpc.CollectorClient.connect(*d0, budgets=budgets)
    c1 = await rpc.CollectorClient.connect(
        "127.0.0.1", port + 10, budgets=budgets
    )
    lead = RpcLeader(cfg, c0, c1)
    await lead._both("reset")
    return lead, c0, c1, live


async def _teardown(clients, live, *proxies):
    for px in proxies:
        await px.stop()
    for c in clients:
        await c.aclose()
    for s in live.values():
        await s.aclose()


def _chunk(k, sl):
    return tuple(np.asarray(x)[sl] for x in k)


def _hitters(res):
    return {
        tuple(int(v) for v in r): int(c)
        for r, c in zip(res.decode_ints(), res.counts)
    }


async def _batch_crawl(cfg, port, k0, k1, idx):
    """Reference: a batch (upload_keys + run) crawl over the key subset
    ``idx`` — what every windowed result must be bit-exact against."""
    lead, c0, c1, live = await _bring_up(cfg, port)
    await lead.upload_keys(
        IbDcfKeyBatch(*(np.asarray(x)[idx] for x in k0)),
        IbDcfKeyBatch(*(np.asarray(x)[idx] for x in k1)),
    )
    res = await lead.run(len(idx))
    await _teardown((c0, c1), live)
    return res


# ---------------------------------------------------------------------------
# submit_keys semantics: idempotency, Overloaded retry, shed
# ---------------------------------------------------------------------------


def test_replayed_submit_admits_exactly_once():
    """At-least-once delivery never double-admits: the same frame
    re-sent under its req_id is answered from the session dedup cache,
    and a NEW request reusing the sub_id (a recovery journal replay)
    answers the recorded verdict — one pool entry either way."""
    port = BASE_PORT

    async def run():
        cfg = _cfg(port)
        s0, s1 = await _start_servers(cfg, port)
        k0, _ = _client_keys(np.random.default_rng(3), 5, 6)
        chunk = _chunk(k0, slice(0, 2))
        r, w = await asyncio.open_connection("127.0.0.1", port)
        await rpc._send(w, (1, "__hello__", {"session": "ing", "epoch": 1}))
        await rpc._recv(r)
        frame = (
            2,
            "submit_keys",
            {"window": 0, "sub_id": "s-1", "client_id": "c", "keys": chunk},
        )
        await rpc._send(w, frame)
        first = (await rpc._recv(r))[1]
        assert first["admitted"] is True
        await rpc._send(w, frame)  # transport replay: same req_id
        assert (await rpc._recv(r))[1] == first
        # journal-style replay: NEW req_id, same sub_id
        await rpc._send(
            w,
            (3, "submit_keys",
             {"window": 0, "sub_id": "s-1", "client_id": "c",
              "keys": chunk}),
        )
        again = (await rpc._recv(r))[1]
        assert again["admitted"] is True and again.get("dup") is True
        assert len(s0._ingest_pools[0].entries) == 1  # admitted ONCE
        w.close()
        await s0.aclose()
        await s1.aclose()

    asyncio.run(run())


def test_overloaded_is_retryable_and_lands(monkeypatch):
    """Quota-free rate limiting: a burst over the bucket gets a
    retryable Overloaded verdict; the driver's backoff lands every key
    (counters prove rejections happened)."""
    port = BASE_PORT + 20

    async def run():
        cfg = _cfg(port)
        lead, c0, c1, live = await _bring_up(cfg, port)
        # a tight REAL-clock bucket on the gate: 2-key burst, 200 keys/s
        live["s0"]._admission = admission.AdmissionController(
            max_window_keys=10_000, rate_keys_per_s=200.0, burst_keys=2,
            shed="reject", seed=1,
        )
        k0, k1 = _client_keys(np.random.default_rng(3), 5, 12)
        wi = WindowedIngest(lead, checkpoint=False)
        for i in range(6):
            sl = slice(2 * i, 2 * i + 2)
            await wi.submit("c", _chunk(k0, sl), _chunk(k1, sl))
        stats = await wi.seal_window()
        rejected = wi.obs.counter_value("ingest_rejected")
        await _teardown((c0, c1), live)
        return stats, rejected

    stats, rejected = asyncio.run(run())
    assert stats["keys"] == 12  # every key landed eventually
    assert rejected >= 1  # ...through at least one backed-off retry


def test_flooding_client_is_limited_others_land():
    """Per-client quotas isolate a flooder: its submissions exhaust the
    quota and fail with IngestOverloadedError after the backoff budget,
    while the honest clients' keys ALL land and the window crawls
    bit-exact vs batch over exactly the admitted set."""
    port = BASE_PORT + 40
    rng = np.random.default_rng(7)
    k0, k1 = _client_keys(rng, 5, 12)

    async def run():
        cfg = _cfg(port, ingest_client_quota=4)
        lead, c0, c1, live = await _bring_up(cfg, port)
        wi = WindowedIngest(
            lead,
            checkpoint=False,
            # quota rejections never clear within a window: keep the
            # flooder's futile backoff short
            policy=respolicy.RetryPolicy(
                base_s=0.001, cap_s=0.002, attempts=3, rand=lambda: 0.0
            ),
        )
        # honest clients: 8 keys in 4 submissions, 2 clients
        for i in range(4):
            sl = slice(2 * i, 2 * i + 2)
            await wi.submit(f"honest{i % 2}", _chunk(k0, sl), _chunk(k1, sl))
        # the flooder: quota 4, tries to push 4 chunks of 2
        flooded = 0
        for i in range(4, 6):
            sl = slice(2 * i, 2 * i + 2)
            await wi.submit("flooder", _chunk(k0, sl), _chunk(k1, sl))
        for i in range(4):
            sl = slice(8, 10)
            try:
                await wi.submit("flooder", _chunk(k0, sl), _chunk(k1, sl))
            except IngestOverloadedError:
                flooded += 1
        stats = await wi.seal_window()
        res = await wi.crawl_window(0)
        rejected = wi.obs.counter_value("ingest_rejected")
        await _teardown((c0, c1), live)
        return res, stats, flooded, rejected

    res, stats, flooded, rejected = asyncio.run(run())
    assert flooded == 4  # every over-quota push failed loudly
    assert rejected >= 4
    assert stats["keys"] == 12  # honest 8 + flooder's first quota-ful 4
    want = asyncio.run(
        _batch_crawl(_cfg(port + 60), port + 60, k0, k1, list(range(12)))
    )
    assert _hitters(res) == _hitters(want)


def test_reservoir_shed_window_is_reproducible_sample(tmp_path):
    """Reservoir shed mode: over capacity the pool becomes a seeded
    uniform sample; the admitted slot table is exactly what a local
    reservoir with the same seed predicts, and the windowed crawl is
    bit-exact vs a batch crawl over that predicted sample."""
    port = BASE_PORT + 100
    rng = np.random.default_rng(11)
    k0, k1 = _client_keys(rng, 5, 12)
    cap = 6  # keys; submissions are 1 key each -> 6 slots

    async def run():
        cfg = _cfg(
            port, ingest_window_keys=cap, ingest_shed="reservoir",
            ingest_seed=42,
        )
        lead, c0, c1, live = await _bring_up(cfg, port)
        wi = WindowedIngest(lead, checkpoint=False)
        for i in range(12):
            await wi.submit(
                f"c{i}", _chunk(k0, slice(i, i + 1)),
                _chunk(k1, slice(i, i + 1)),
            )
        stats = await wi.seal_window()
        res = await wi.crawl_window(0)
        await _teardown((c0, c1), live)
        return res, stats

    res, stats = asyncio.run(run())
    assert stats["keys"] == cap and stats["shed_keys"] == 12 - cap
    # predict the slot table with the same per-window seed derivation
    ctl = admission.AdmissionController(
        max_window_keys=cap, shed="reservoir", seed=42
    )
    wa = ctl.window(0)
    table = {}
    for i in range(12):
        v = ctl.admit(wa, f"c{i}", 1)
        if v.admitted:
            table[len(table) if v.slot is None else v.slot] = i
    idx = [table[s] for s in range(cap)]
    want = asyncio.run(_batch_crawl(_cfg(port + 40), port + 40, k0, k1, idx))
    assert _hitters(res) == _hitters(want)
    np.testing.assert_array_equal(res.counts, want.counts)


# ---------------------------------------------------------------------------
# windowed crawls: concurrency, status, report
# ---------------------------------------------------------------------------


def test_windowed_crawl_concurrent_ingest_bit_exact():
    """THE streaming contract: window 0's crawl runs on the frozen
    snapshot WHILE window 1 ingests (submit_keys bypasses the verb
    lock); both windows' results are bit-exact vs batch crawls over the
    same key subsets, the status verb reports front-door health, and
    the run report grows the ingest section."""
    port = BASE_PORT + 140
    rng = np.random.default_rng(7)
    k0, k1 = _client_keys(rng, 5, 12)

    async def run():
        cfg = _cfg(port)
        lead, c0, c1, live = await _bring_up(cfg, port)
        wi = WindowedIngest(lead, checkpoint=False)
        for i in range(6):
            await wi.submit(
                f"c{i}", _chunk(k0, slice(i, i + 1)),
                _chunk(k1, slice(i, i + 1)),
            )
        await wi.seal_window()
        crawl = asyncio.create_task(wi.crawl_window(0))
        submitted_during = 0
        for i in range(6, 12):
            await wi.submit(
                f"c{i}", _chunk(k0, slice(i, i + 1)),
                _chunk(k1, slice(i, i + 1)),
            )
            if not crawl.done():
                submitted_during += 1
        res0 = await crawl
        st = await c0.call("status")
        await wi.seal_window()
        res1 = await wi.crawl_window(1)
        rep = obsreport.run_report([wi.obs])
        await _teardown((c0, c1), live)
        return res0, res1, st, rep, submitted_during

    res0, res1, st, rep, submitted_during = asyncio.run(run())
    want0 = asyncio.run(
        _batch_crawl(_cfg(port + 40), port + 40, k0, k1, list(range(6)))
    )
    want1 = asyncio.run(
        _batch_crawl(_cfg(port + 80), port + 80, k0, k1, list(range(6, 12)))
    )
    np.testing.assert_array_equal(res0.counts, want0.counts)
    np.testing.assert_array_equal(res0.paths, want0.paths)
    np.testing.assert_array_equal(res1.counts, want1.counts)
    np.testing.assert_array_equal(res1.paths, want1.paths)
    assert submitted_during >= 1  # ingest genuinely overlapped the crawl
    # status: front-door health
    ing = st["ingest"]
    assert ing["windows"]["1"]["sealed"] is False
    assert ing["queue_depth"] >= 1
    # run report: the ingest section
    assert rep["ingest"]["admitted"] == 12
    assert rep["ingest"]["windows"] == 2
    assert rep["ingest"]["keys_per_sec"] is None or (
        rep["ingest"]["keys_per_sec"] > 0
    )
    assert rep["ingest"]["window_crawl_seconds"] > 0


def test_window_seal_idempotent_and_sealed_window_refuses():
    port = BASE_PORT + 180

    async def run():
        cfg = _cfg(port)
        s0, s1 = await _start_servers(cfg, port)
        k0, _ = _client_keys(np.random.default_rng(3), 5, 6)
        await s0.submit_keys(
            {"window": 0, "sub_id": "a", "client_id": "c",
             "keys": _chunk(k0, slice(0, 2))}
        )
        st1 = await s0.window_seal({"window": 0})
        st2 = await s0.window_seal({"window": 0})  # idempotent
        assert st1 == st2
        with pytest.raises(RuntimeError, match="sealed"):
            await s0.submit_keys(
                {"window": 0, "sub_id": "b", "client_id": "c",
                 "keys": _chunk(k0, slice(2, 4))}
            )
        # live-window bound refuses loudly, never grows silently
        for w in range(1, s0.cfg.ingest_windows_retained):
            await s0.submit_keys(
                {"window": w, "sub_id": f"w{w}", "client_id": "c",
                 "keys": _chunk(k0, slice(0, 1))}
            )
        with pytest.raises(RuntimeError, match="live-window bound"):
            await s0.submit_keys(
                {"window": 99, "sub_id": "x", "client_id": "c",
                 "keys": _chunk(k0, slice(0, 1))}
            )
        await s0.aclose()
        await s1.aclose()

    asyncio.run(run())


def test_ingest_report_section_absent_without_streaming():
    from fuzzyheavyhitters_tpu.obs import metrics as obsmetrics

    reg = obsmetrics.Registry("t-ing-absent")
    reg.count("keys_uploaded", 5)
    assert "ingest" not in obsreport.run_report([reg])


# ---------------------------------------------------------------------------
# recovery: ingest checkpoint/restore + kill mid-window
# ---------------------------------------------------------------------------


def test_ingest_pools_ride_checkpoint_restore(tmp_path):
    """The server-side recovery contract in isolation: pools (entries,
    recorded verdicts, reservoir RNG state) round-trip an ingest-only
    checkpoint; a replayed submit after restore admits exactly once and
    the shed stream continues seed-identically."""
    port = BASE_PORT + 220
    rng = np.random.default_rng(5)
    k0, _ = _client_keys(rng, 5, 12)

    async def run():
        cfg = _cfg(
            port, ingest_window_keys=4, ingest_shed="reservoir",
            ingest_seed=9,
        )
        s = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        for i in range(8):
            await s.submit_keys(
                {"window": 0, "sub_id": f"s{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
        await s.window_seal({"window": 0})
        await s.submit_keys(
            {"window": 1, "sub_id": "w1", "client_id": "c",
             "keys": _chunk(k0, slice(0, 1))}
        )
        await s.tree_checkpoint({"level": -1, "ingest_only": True})

        s2 = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        await s2.tree_restore({"level": -1})
        # identical pools
        for w in (0, 1):
            p1, p2 = s._ingest_pools[w], s2._ingest_pools[w]
            assert p1.stats() == p2.stats()
            for e1, e2 in zip(p1.entries, p2.entries):
                for a, b in zip(e1, e2):
                    np.testing.assert_array_equal(a, b)
        # replay dedups; fresh offers continue the SAME sampler stream
        dup = await s2.submit_keys(
            {"window": 1, "sub_id": "w1", "client_id": "c",
             "keys": _chunk(k0, slice(0, 1))}
        )
        assert dup.get("dup") is True
        for srv in (s, s2):
            for i in range(8, 12):
                await srv.submit_keys(
                    {"window": 1, "sub_id": f"n{i}", "client_id": "c",
                     "keys": _chunk(k0, slice(i, i + 1))}
                )
        st1 = await s.window_seal({"window": 1})
        st2 = await s2.window_seal({"window": 1})
        assert st1 == st2
        p1, p2 = s._ingest_pools[1], s2._ingest_pools[1]
        for e1, e2 in zip(p1.entries, p2.entries):
            for a, b in zip(e1, e2):
                np.testing.assert_array_equal(a, b)

    asyncio.run(run())


def test_restored_gate_reservoir_stream_survives_journal_replay(tmp_path):
    """The shed stream is window-consistent across a GATE restart: a
    restored gate rebuilt from the checkpoint + a mirror-form journal
    replay of the post-checkpoint submissions makes the SAME live
    decisions afterwards as the never-faulted gate (the replayed draws
    advance the restored sampler)."""
    port = BASE_PORT + 340
    rng = np.random.default_rng(5)
    k0, _ = _client_keys(rng, 5, 12)

    async def run():
        cfg = _cfg(
            port, ingest_window_keys=4, ingest_shed="reservoir",
            ingest_seed=21,
        )
        s = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        for i in range(6):  # fill + engage
            await s.submit_keys(
                {"window": 0, "sub_id": f"s{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
        await s.tree_checkpoint({"level": -1, "ingest_only": True})
        # post-checkpoint traffic (the journal's tail) + future verdicts
        # on the never-faulted gate
        journal = []
        for i in range(6, 9):
            r = await s.submit_keys(
                {"window": 0, "sub_id": f"s{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
            journal.append((f"s{i}", i, r))
        want_future = [
            await s.submit_keys(
                {"window": 0, "sub_id": f"f{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
            for i in range(9, 12)
        ]
        # the restarted gate: restore + mirror-form journal replay
        s2 = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        await s2.tree_restore({"level": -1})
        for sub_id, i, r in journal:
            await s2.submit_keys(
                {"window": 0, "sub_id": sub_id, "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1)),
                 "mirror": {"slot": r.get("slot"),
                            "shed": bool(r.get("shed"))}}
            )
        got_future = [
            await s2.submit_keys(
                {"window": 0, "sub_id": f"f{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
            for i in range(9, 12)
        ]
        assert got_future == want_future
        st1 = await s.window_seal({"window": 0})
        st2 = await s2.window_seal({"window": 0})
        assert st1 == st2

    asyncio.run(run())


def test_gate_reservoir_stream_survives_replay_without_engaged_checkpoint(
    tmp_path,
):
    """The harder recovery case: the reservoir engaged only AFTER the
    last checkpoint, so there is no RNG state to restore — the replayed
    draws are banked (pending_draws) and the re-engagement fast-forwards
    past them, keeping the live stream identical to the fault-free
    gate's."""
    port = BASE_PORT + 360
    rng = np.random.default_rng(5)
    k0, _ = _client_keys(rng, 5, 12)

    async def run():
        cfg = _cfg(
            port, ingest_window_keys=3, ingest_shed="reservoir",
            ingest_seed=33,
        )
        s = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        for i in range(2):  # fill only: reservoir NOT engaged yet
            await s.submit_keys(
                {"window": 0, "sub_id": f"s{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
        await s.tree_checkpoint({"level": -1, "ingest_only": True})
        journal = []
        for i in range(2, 8):  # fill completes + engages post-checkpoint
            r = await s.submit_keys(
                {"window": 0, "sub_id": f"s{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
            journal.append((f"s{i}", i, r))
        want = [
            await s.submit_keys(
                {"window": 0, "sub_id": f"f{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
            for i in range(8, 12)
        ]
        s2 = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        await s2.tree_restore({"level": -1})
        for sub_id, i, r in journal:
            await s2.submit_keys(
                {"window": 0, "sub_id": sub_id, "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1)),
                 "mirror": {"slot": r.get("slot"),
                            "shed": bool(r.get("shed"))}}
            )
        got = [
            await s2.submit_keys(
                {"window": 0, "sub_id": f"f{i}", "client_id": "c",
                 "keys": _chunk(k0, slice(i, i + 1))}
            )
            for i in range(8, 12)
        ]
        assert got == want
        assert (await s.window_seal({"window": 0})) == (
            await s2.window_seal({"window": 0})
        )

    asyncio.run(run())


def test_idle_sealed_windows_are_evicted_not_wedged():
    """A quiet stretch — many consecutive EMPTY sealed windows — must
    not exhaust the live-window bound: sealed empty pools (never
    window_load-ed) evict oldest-first when a new window needs the
    slot."""
    port = BASE_PORT + 380

    async def run():
        cfg = _cfg(port, ingest_windows_retained=3)
        s = rpc.CollectorServer(0, cfg)
        for w in range(8):  # far past the bound: every seal is idle
            st = await s.window_seal({"window": w})
            assert st["keys"] == 0 and st["sealed"]
        k0, _ = _client_keys(np.random.default_rng(3), 5, 6)
        r = await s.submit_keys(
            {"window": 8, "sub_id": "a", "client_id": "c",
             "keys": _chunk(k0, slice(0, 2))}
        )
        assert r["admitted"] is True
        assert len(s._ingest_pools) <= 3

    asyncio.run(run())


def test_restore_refuses_torn_ingest_tail(tmp_path):
    """Validate-before-mutate covers the ing_* fields: a blob whose
    ingest tail is truncated refuses loudly and leaves live state
    untouched."""
    port = BASE_PORT + 260
    rng = np.random.default_rng(5)
    k0, _ = _client_keys(rng, 5, 6)

    async def run():
        cfg = _cfg(port)
        s = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        await s.submit_keys(
            {"window": 0, "sub_id": "a", "client_id": "c",
             "keys": _chunk(k0, slice(0, 2))}
        )
        await s.tree_checkpoint({"level": -1, "ingest_only": True})
        path = s._ckpt_path(-1)
        with np.load(path) as z:
            blob = {k: z[k] for k in z.files}
        del blob["ing0_sub_codes"]  # tear the verdict table
        with open(path, "wb") as f:
            np.savez(f, **blob)
        s2 = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        with pytest.raises(RuntimeError, match="ingest|truncated"):
            await s2.tree_restore({"level": -1})
        assert s2._ingest_pools == {}  # nothing mutated
        await s.aclose()

    asyncio.run(run())


def test_e2e_kill_mid_window_under_flood_bit_exact(rng, tmp_path):
    """THE acceptance scenario: sustained ingest concurrent with a
    windowed crawl, a duplicate-delivery flood on the gate link, and
    server 1 killed + restarted MID-WINDOW — the window results stay
    bit-exact vs fault-free batch crawls over the same admitted sets,
    and the recovery + ingest counters land in the run report."""
    L, n = 5, 12
    port = BASE_PORT + 300
    pxport = port + 20
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port)
    ck = tmp_path / "ck"
    ck.mkdir()

    async def run():
        # flood: duplicate an early gate-bound frame 3x (at-least-once
        # delivery made real; the session dedup absorbs it)
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:flood@msg=6,count=3"), link="ctl0",
        ).start()
        lead, c0, c1, live = await _bring_up(
            cfg, port, ckpt_dir=str(ck), dial0=("127.0.0.1", pxport)
        )
        wi = WindowedIngest(lead)  # checkpointing ON
        for i in range(6):
            await wi.submit(
                f"c{i}", _chunk(k0, slice(i, i + 1)),
                _chunk(k1, slice(i, i + 1)),
            )
        await wi.seal_window()

        async def assassin():
            # kill s1 the moment the window-0 crawl is underway (its
            # frontier roots at tree_init, right after window_load)
            while live["s1"].frontier is None:
                await asyncio.sleep(0.01)
            await live["s1"].aclose()
            await asyncio.sleep(0.3)
            live["s1"] = rpc.CollectorServer(1, cfg, ckpt_dir=str(ck))
            await live["s1"].start(
                "127.0.0.1", port + 10, "127.0.0.1", port + 11
            )

        kill = asyncio.create_task(assassin())
        crawl = asyncio.create_task(wi.crawl_window(0))
        for i in range(6, 12):
            await wi.submit(
                f"c{i}", _chunk(k0, slice(i, i + 1)),
                _chunk(k1, slice(i, i + 1)),
            )
            await asyncio.sleep(0.02)  # sustained, not a burst
        res0 = await crawl
        await kill
        await wi.seal_window()
        res1 = await wi.crawl_window(1)
        rep = obsreport.run_report([wi.obs, lead.obs, live["s0"].obs])
        await _teardown((c0, c1), live, px)
        return res0, res1, rep, px.fired

    res0, res1, rep, fired = asyncio.run(run())
    want0 = asyncio.run(
        _batch_crawl(_cfg(port + 40), port + 40, k0, k1, list(range(6)))
    )
    want1 = asyncio.run(
        _batch_crawl(_cfg(port + 60), port + 60, k0, k1, list(range(6, 12)))
    )
    np.testing.assert_array_equal(res0.counts, want0.counts)
    np.testing.assert_array_equal(res0.paths, want0.paths)
    np.testing.assert_array_equal(res1.counts, want1.counts)
    np.testing.assert_array_equal(res1.paths, want1.paths)
    assert any(f[0] == "flood" for f in fired)  # the flood actually fired
    # the kill actually happened AND was recovered, visibly
    assert rep["ingest"]["admitted"] == n
    assert rep["ingest"]["windows"] == 2
    ing_reg = rep["registries"]["ingest"]["counters"]
    assert ing_reg["ingest_recoveries"]["total"] >= 1
    assert ing_reg["ingest_journal_replays"]["total"] >= 1
