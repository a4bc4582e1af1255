"""Multi-tenant collection sessions: the per-collection session
subsystem (protocol/sessions.py), the tenant scheduler + shared warmup
ladder (protocol/tenancy.py), and the multi-collection driver
(protocol/leader_rpc.MultiCollectionDriver).

The acceptance surface (ISSUE 12): N=4 concurrent collections on ONE
server pair each produce heavy-hitter sets BIT-IDENTICAL to their solo
single-session runs — trusted AND secure — with per-session ingest
gates isolating a flooding tenant, session-namespaced checkpoints
refusing cross-namespace blobs, and the tenant-isolation chaos leg
(flood tenant A + kill/restart s1 mid-crawl of tenant B's window)
green; scripts/chaos.sh re-runs that leg under FHH_DEBUG_GUARDS=1.

Shapes mirror tests/test_resilience.py (L=5, d=1) so the crawl kernels
compile once across the suites.
"""

import asyncio
import os

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.obs import report as obsreport
from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.ops.ibdcf import IbDcfKeyBatch
from fuzzyheavyhitters_tpu.protocol import rpc, sessions, tenancy
from fuzzyheavyhitters_tpu.protocol.leader_rpc import (
    MultiCollectionDriver,
    RpcLeader,
    WindowedIngest,
)
from fuzzyheavyhitters_tpu.resilience import policy as respolicy
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 26431


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """CPU backend: session plumbing over the same crawl kernels the
    other protocol suites compile."""
    yield


def _cfg(port, **kw):
    base = dict(
        data_len=5, n_dims=1, ball_size=1, addkey_batch_size=64,
        num_sites=4, threshold=0.05, zipf_exponent=1.0,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=16, backend="cpu",
    )
    base.update(kw)
    return Config(**base)


def _client_keys(seed, L, n):
    r = np.random.default_rng(seed)
    sites = r.integers(0, 1 << L, size=4)
    pts = sites[r.integers(0, 4, size=n)]
    pts_bits = (
        ((pts[:, None, None] >> np.arange(L - 1, -1, -1)) & 1) > 0
    )
    return ibdcf.gen_l_inf_ball(pts_bits, 1, r, engine="np")


def _chunk(k, sl):
    return tuple(np.asarray(x)[sl] for x in k)


async def _start_pair(cfg, port, ckpt_dir=None):
    s0 = rpc.CollectorServer(0, cfg, ckpt_dir=ckpt_dir)
    s1 = rpc.CollectorServer(1, cfg, ckpt_dir=ckpt_dir)
    t1 = asyncio.create_task(
        s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11)
    )
    await asyncio.sleep(0.05)
    t0 = asyncio.create_task(
        s0.start("127.0.0.1", port, "127.0.0.1", port + 11)
    )
    await asyncio.gather(t0, t1)
    return s0, s1


async def _solo_run(cfg, port, k0, k1, n):
    """Reference: one collection alone on a fresh pair (the default
    session — exactly the pre-multi-tenant deployment)."""
    s0, s1 = await _start_pair(cfg, port)
    c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
    c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
    lead = RpcLeader(cfg, c0, c1)
    await lead._both("reset")
    await lead.upload_keys(k0, k1)
    res = await lead.run(n)
    for c in (c0, c1):
        await c.aclose()
    for s in (s0, s1):
        await s.aclose()
    return res


# ---------------------------------------------------------------------------
# units: session table, scheduler, warm ladder, plane mux
# ---------------------------------------------------------------------------


def test_session_table_bound_eviction_and_bad_keys():
    cfg = _cfg(1, collection_sessions_max=2)
    table = sessions.SessionTable(0, cfg, None, None)

    async def run():
        a = table.get("a")
        table.get("b")
        # at the cap: an IDLE session (a: nothing uploaded) evicts
        # oldest-first, so c fits
        table.get("c")
        assert sorted(table.keys()) == ["b", "c"]
        # both live sessions busy -> a new collection refuses loudly
        table.get("b").keys_parts.append("x")
        table.get("c").keys_parts.append("x")
        with pytest.raises(RuntimeError, match="session bound"):
            table.get("d")
        # key validation: filename/channel safety ("" is NOT invalid —
        # it resolves to the default collection by design)
        for bad in ("a/b", "x" * 65, "sp ace", "tab\t"):
            with pytest.raises(ValueError):
                table.get(bad)
        assert a.key == "a"
        # a session with a live connection BINDING is never idle-evicted,
        # even with no state yet (evicting it would orphan the bound
        # leader and let a same-key successor share its plane channel)
        table.get("b").keys_parts.clear()
        table.get("b").bound += 1
        with pytest.raises(RuntimeError, match="session bound"):
            table.get("e")
        table.get("b").bound -= 1
        table.get("e")  # unbound + stateless again: evictable
        assert "b" not in table.keys()

    asyncio.run(run())


def test_tenant_scheduler_counts_stall_fills():
    sched = tenancy.TenantScheduler()

    async def run():
        async with sched.device_turn("a"):
            pass  # nobody on the wire: a plain turn
        with sched.wire_wait("a"):
            async with sched.device_turn("b"):
                pass  # b dispatched while a waited: a stall fill
            with sched.wire_wait("b"):
                async with sched.device_turn("a"):
                    pass  # and symmetrically
        sched.note_dispatch("c")  # nobody waiting anymore

    asyncio.run(run())
    st = sched.stats()
    assert st["device_turns"] == 4
    assert st["stall_fills"] == 2
    assert st["fills_by_session"] == {"a": 1, "b": 1}
    assert st["fill_ratio"] == pytest.approx(0.5)


def test_warm_ladder_marks_and_skips():
    tenancy.ladder_reset()
    key = ("warm", (4, 1, 5, 2, 4), 2, 5, False, True, "auto", 0, 0, True)
    assert not tenancy.warmed(key)
    tenancy.mark_warmed(key)
    assert tenancy.warmed(key)
    assert tenancy.ladder_size() == 1
    tenancy.ladder_reset()
    assert not tenancy.warmed(key)


def test_plane_mux_demux_fifo_and_failure():
    """Frames interleaved across channels demux into per-channel FIFO
    order; a plane's death surfaces to every blocked recv as
    ConnectionError; attach() supersedes the old plane, whose late
    frames and late loss are dropped by their epoch."""

    async def run():
        mux = sessions.PlaneMux()
        epoch = mux.attach()
        for chan, payload in (("Axy", 1), ("Bzz", 9), ("Axy", 2)):
            mux.route(epoch, 4, (chan, payload))
        assert await mux.recv("Axy") == 1
        assert await mux.recv("Bzz") == 9
        assert await mux.recv("Axy") == 2
        # a blocked recv learns of the plane's death
        waiter = asyncio.ensure_future(mux.recv("Axy"))
        await asyncio.sleep(0)
        mux.lost(epoch, EOFError())
        with pytest.raises(ConnectionError):
            await waiter
        # and later recvs on ANY channel fail too, until re-attach
        with pytest.raises(ConnectionError):
            await mux.recv("Bzz")
        mux.route(epoch, 4, ("Bzz", 3))  # a dead plane routes nothing
        epoch2 = mux.attach()
        assert epoch2 == 2
        mux.route(epoch, 4, ("Axy", 5))  # the replaced plane's stragglers
        mux.lost(epoch, EOFError())
        mux.route(epoch2, 4, ("Axy", 7))
        assert await mux.recv("Axy") == 7
        mux.close()
        with pytest.raises(ConnectionError):
            await mux.recv("Axy")

    asyncio.run(run())


# ---------------------------------------------------------------------------
# session-namespaced checkpoints
# ---------------------------------------------------------------------------


def test_session_namespaced_checkpoints_and_cross_session_refusal(tmp_path):
    """Each collection checkpoints into its own filename namespace; a
    blob renamed across namespaces refuses at the session stamp, and a
    restore refuses a torn session tail — all BEFORE any state mutates
    (the PR-4 validate-before-mutate contract, extended)."""
    port = BASE_PORT
    cfg = _cfg(port)
    k0, k1 = _client_keys(11, 5, 6)

    async def run():
        s = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        ca = s._table.get("tenA")
        cb = s._table.get("tenB")
        # tree_init needs the live data plane (coin flip) — this is a
        # one-server unit, so build the crawl state through the session
        # helpers instead
        from fuzzyheavyhitters_tpu.protocol import collect

        for cs in (ca, cb):
            await s.add_keys({"keys": _chunk(k0, slice(0, 6))}, cs)
            cs.concat_keys()
            cs.alive_keys = np.ones(6, bool)
            cs.frontier = collect.tree_init(cs.keys, 1)
        await s.tree_checkpoint({"level": 1}, ca)
        await s.tree_checkpoint({"level": 1}, cb)
        # distinct namespaces, legacy name untouched for the default
        assert os.path.exists(tmp_path / "fhh_server0_ctenA_l1.npz")
        assert os.path.exists(tmp_path / "fhh_server0_ctenB_l1.npz")
        assert ca.ckpt_levels() == [1] and cb.ckpt_levels() == [1]
        # cross-namespace rename: refused at the session stamp, state
        # untouched
        os.replace(
            tmp_path / "fhh_server0_ctenA_l1.npz",
            tmp_path / "fhh_server0_ctenB_l1.npz",
        )
        frontier_before = cb.frontier
        with pytest.raises(RuntimeError, match="stamped for collection"):
            await s.tree_restore({"level": 1}, cb)
        assert cb.frontier is frontier_before
        # torn session tail: a session-namespaced blob whose ingest tail
        # is torn refuses before any pool mutates
        pool = cb.ingest_pool(0)
        pool.apply(
            "sub1", _chunk(k0, slice(0, 2)),
            cb._admission.admit(pool.wa, "c", 2),
        )
        await s.tree_checkpoint({"level": 2}, cb)
        path = cb.ckpt_path(2)
        blob = dict(np.load(path))
        del blob["ing0_lens"]  # tear the ingest tail
        with open(path, "wb") as f:
            np.savez(f, **blob)
        pools_before = dict(cb._ingest_pools)
        with pytest.raises(RuntimeError, match="missing ingest fields"):
            await s.tree_restore({"level": 2}, cb)
        assert cb._ingest_pools == pools_before
        await s.aclose()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# THE acceptance: N=4 concurrent collections bit-identical to solo
# ---------------------------------------------------------------------------


def _multi_vs_solo(port, cfg_kw, n, n_collections=4, supervised=False):
    cfgs = _cfg(port, **cfg_kw)
    keysets = {
        f"t{i}": _client_keys(100 + i, 5, n) for i in range(n_collections)
    }

    async def solo_all():
        out = {}
        for i, (key, (k0, k1)) in enumerate(keysets.items()):
            out[key] = await _solo_run(
                _cfg(port + 100 + 20 * i, **cfg_kw),
                port + 100 + 20 * i, k0, k1, n,
            )
        return out

    async def multi():
        s0, s1 = await _start_pair(cfgs, port)
        drv = MultiCollectionDriver(
            cfgs, "127.0.0.1", port, "127.0.0.1", port + 10
        )
        jobs = [
            {"collection": key, "nreqs": n, "keys0": k0, "keys1": k1}
            for key, (k0, k1) in keysets.items()
        ]
        res = await drv.run_collections(jobs, supervised=supervised)
        # telemetry: status sessions section + run-report sessions rollup
        st = await drv.leaders["t0"].c0.call("status")
        regs = [ld.obs for ld in drv.leaders.values()]
        regs += [s0.obs, s1.obs]
        regs += [cs.obs for _, cs in s0._table.items()]
        regs += [cs.obs for _, cs in s1._table.items()]
        rep = obsreport.run_report(regs)
        await drv.close()
        for s in (s0, s1):
            await s.aclose()
        return res, st, rep

    solo = asyncio.run(solo_all())
    got, st, rep = asyncio.run(multi())
    for key in keysets:
        res = got[key]
        assert not isinstance(res, BaseException), (key, res)
        np.testing.assert_array_equal(res.counts, solo[key].counts)
        np.testing.assert_array_equal(res.paths, solo[key].paths)
    return st, rep


def test_multi_tenant_trusted_n4_bit_identical_to_solo():
    st, rep = _multi_vs_solo(BASE_PORT + 40, {}, n=48, n_collections=4)
    sess = st["sessions"]
    assert sess["count"] == 4
    assert sess["scheduler"]["device_turns"] > 0
    # every tenant appears in the per-session status rows
    assert sorted(sess["per_session"]) == ["t0", "t1", "t2", "t3"]
    for row in sess["per_session"].values():
        assert set(row) >= {
            "phase", "level", "queue_depth", "dedup_entries", "ckpt_levels"
        }
    # run-report sessions rollup: the four tenants' crawl seconds land
    rsess = rep["sessions"]
    assert rsess["count"] == 4
    assert rsess["device_turns"] > 0
    assert all(
        rsess["per_session"][k]["crawl_seconds"] > 0 for k in rsess["per_session"]
    )


def test_multi_tenant_secure_n4_bit_identical_to_solo():
    """Secure 2PC: four independent OT/GC transcripts interleaved on one
    demuxed data plane, each tenant's heavy hitters bit-identical to its
    solo run."""
    st, rep = _multi_vs_solo(
        BASE_PORT + 400, {"secure_exchange": True}, n=24, n_collections=4
    )
    assert st["sessions"]["count"] == 4


def test_multi_tenant_stall_fills_observed():
    """The scheduler actually observes cross-tenant fill: with two
    tenants crawling concurrently, some device turns run while the
    other tenant waits on the GC/OT wire."""
    st, _rep = _multi_vs_solo(
        BASE_PORT + 700, {"secure_exchange": True}, n=16, n_collections=2
    )
    sched = st["sessions"]["scheduler"]
    assert sched["stall_fills"] > 0
    assert 0 < sched["fill_ratio"] <= 1


# ---------------------------------------------------------------------------
# per-session ingest gates: a flooding tenant cannot starve another
# ---------------------------------------------------------------------------


def test_per_session_gates_flooding_tenant_isolated():
    """Tenant A floods its rate bucket dry; tenant B's submissions all
    admit — the buckets are PER SESSION (each collection has its own
    AdmissionController), so A's rejections never consume B's tokens."""
    port = BASE_PORT + 140
    cfg = _cfg(
        port,
        ingest_rate_keys_per_s=64.0,
        ingest_burst_keys=8,
    )
    kA = _client_keys(21, 5, 64)
    kB = _client_keys(22, 5, 8)

    async def run():
        s0, s1 = await _start_pair(cfg, port)
        drv = MultiCollectionDriver(
            cfg, "127.0.0.1", port, "127.0.0.1", port + 10
        )
        leadA = await drv.open("ta")
        leadB = await drv.open("tb")
        wiA = WindowedIngest(
            leadA, checkpoint=False,
            policy=respolicy.RetryPolicy(
                base_s=0.001, cap_s=0.002, factor=1.0, attempts=2
            ),
        )
        wiB = WindowedIngest(leadB, checkpoint=False)
        rejA = 0

        async def flood():
            nonlocal rejA
            from fuzzyheavyhitters_tpu.protocol.leader_rpc import (
                IngestOverloadedError,
            )

            for i in range(0, 64, 8):
                try:
                    await wiA.submit(
                        "flooder", _chunk(kA[0], slice(i, i + 8)),
                        _chunk(kA[1], slice(i, i + 8)),
                    )
                except IngestOverloadedError:
                    rejA += 1

        async def honest():
            for i in range(8):
                await wiB.submit(
                    f"b{i}", _chunk(kB[0], slice(i, i + 1)),
                    _chunk(kB[1], slice(i, i + 1)),
                )
                await asyncio.sleep(0.005)

        await asyncio.gather(flood(), honest())
        stA = await wiA.seal_window()
        stB = await wiB.seal_window()
        await drv.close()
        for s in (s0, s1):
            await s.aclose()
        return rejA, stA, stB

    rejA, stA, stB = asyncio.run(run())
    # the flood hit A's OWN bucket: per-attempt rejections recorded at
    # A's gate (rejA counts only submissions that exhausted every
    # backoff — the hint-honoring retry usually lands, so the gate-side
    # counter is the reliable signal)
    assert stA["rejected"] > 0
    assert stB["keys"] == 8 and stB["rejected"] == 0  # B untouched


# ---------------------------------------------------------------------------
# tenant-isolation chaos: flood A + kill/restart s1 mid-crawl of B
# (scripts/chaos.sh re-runs this leg under FHH_DEBUG_GUARDS=1)
# ---------------------------------------------------------------------------


def test_tenant_isolation_flood_and_kill_restart_mid_crawl(tmp_path):
    """THE tenant-isolation scenario: tenant A floods its gate while
    tenant B runs a windowed crawl; server 1 is killed and restarted
    MID-CRAWL.  Tenant B's window stays bit-exact vs a fault-free batch
    crawl over the same admitted keys, and B's admission counters are
    untouched by A's flood (no rejections leak across gates)."""
    port = BASE_PORT + 200
    L, nB = 5, 10
    cfg = _cfg(
        port,
        ingest_rate_keys_per_s=200.0,
        ingest_burst_keys=16,
    )
    kA = _client_keys(31, L, 96)
    kB = _client_keys(32, L, nB)
    ck = tmp_path / "ck"
    ck.mkdir()

    async def run():
        live = {}
        live["s0"], live["s1"] = await _start_pair(
            cfg, port, ckpt_dir=str(ck)
        )
        drv = MultiCollectionDriver(
            cfg, "127.0.0.1", port, "127.0.0.1", port + 10
        )
        leadA = await drv.open("ta")
        leadB = await drv.open("tb")
        wiA = WindowedIngest(
            leadA, checkpoint=False,
            policy=respolicy.RetryPolicy(
                base_s=0.001, cap_s=0.002, factor=1.0, attempts=2
            ),
        )
        wiB = WindowedIngest(leadB)  # checkpointing ON
        # B's window 0 fills, seals, and crawls
        for i in range(nB):
            await wiB.submit(
                f"b{i}", _chunk(kB[0], slice(i, i + 1)),
                _chunk(kB[1], slice(i, i + 1)),
            )
        await wiB.seal_window()

        async def assassin():
            # kill s1 once tenant B's window crawl is actually underway
            # on it (its tb session starts billing fss seconds)
            while True:
                cs = live["s1"]._table.peek("tb")
                if cs is not None and cs.obs.timer_seconds("fss") > 0:
                    break
                await asyncio.sleep(0.01)
            await live["s1"].aclose()
            await asyncio.sleep(0.3)
            live["s1"] = rpc.CollectorServer(1, cfg, ckpt_dir=str(ck))
            await live["s1"].start(
                "127.0.0.1", port + 10, "127.0.0.1", port + 11
            )

        async def flood():
            from fuzzyheavyhitters_tpu.protocol.leader_rpc import (
                IngestOverloadedError,
            )

            rej = 0
            for i in range(0, 96, 8):
                try:
                    await wiA.submit(
                        "flooder", _chunk(kA[0], slice(i, i + 8)),
                        _chunk(kA[1], slice(i, i + 8)),
                    )
                except (IngestOverloadedError,
                        *respolicy.TRANSIENT_ERRORS, RuntimeError):
                    rej += 1  # Overloaded or mid-kill transport loss
                await asyncio.sleep(0.01)
            return rej

        kill = asyncio.create_task(assassin())
        fl = asyncio.create_task(flood())
        resB = await wiB.crawl_window(0, max_recoveries=8)
        await kill
        await fl
        stB = await leadB.c0.call("status")
        stA = await leadA.c0.call("status")
        await drv.close()
        for s in live.values():
            await s.aclose()
        return resB, stA, stB

    resB, stA, stB = asyncio.run(run())
    # fault-free reference over the same admitted set
    want = asyncio.run(
        _solo_run(
            _cfg(port + 60), port + 60,
            IbDcfKeyBatch(*_chunk(kB[0], slice(0, nB))),
            IbDcfKeyBatch(*_chunk(kB[1], slice(0, nB))),
            nB,
        )
    )
    np.testing.assert_array_equal(resB.counts, want.counts)
    np.testing.assert_array_equal(resB.paths, want.paths)
    # B's gate never rejected anything: A's flood hit only A's bucket
    ingB = stB["ingest"]
    assert ingB["rejected"] == 0
    assert ingB["admitted"] == nB
    # ...and A's own gate actually rejected (the flood was real)
    assert stA["ingest"]["rejected"] > 0


# ---------------------------------------------------------------------------
# shared warmup ladder
# ---------------------------------------------------------------------------


def test_warmup_ladder_shared_across_tenants():
    """A second collection with the same batch shape pays ZERO fresh
    warm executions: the process-level WarmLadder answers its warmup
    from the first tenant's pass (the compiled programs are already in
    the process jit cache)."""
    port = BASE_PORT + 340
    cfg = _cfg(port)
    k0, k1 = _client_keys(41, 5, 8)

    async def run():
        tenancy.ladder_reset()
        s = rpc.CollectorServer(0, cfg)
        ca = s._table.get("wa")
        cb = s._table.get("wb")
        for cs in (ca, cb):
            await s.add_keys({"keys": _chunk(k0, slice(0, 8))}, cs)
        r1 = await s.warmup({"f_buckets": [1, 2]}, ca)
        r2 = await s.warmup({"f_buckets": [1, 2]}, cb)
        await s.aclose()
        return r1, r2

    r1, r2 = asyncio.run(run())
    assert r1["shapes"] > 0 and r1["ladder_hits"] == 0
    assert r2["shapes"] == 0 and r2["ladder_hits"] == r1["shapes"]
