"""fhh-trace + SLO-histogram suite: distributed tracing across the
leader and both collector servers, the fixed-bucket latency histograms,
the status/run-report ``slo`` surfaces, trace behavior under faults
(reconnect replays record each span ONCE; a severed data plane marks
the open span error=true), the chip-profiler gating, and the
zero-cost-when-disabled contract (pinned like FHH_DEBUG_GUARDS).

Shapes mirror tests/test_resilience.py (L=5, d=1) so the crawl kernels
compile once across the suites.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from fuzzyheavyhitters_tpu import obs
from fuzzyheavyhitters_tpu.obs import hist as histmod
from fuzzyheavyhitters_tpu.obs import metrics as obsmetrics
from fuzzyheavyhitters_tpu.obs import report as obsreport
from fuzzyheavyhitters_tpu.obs import trace as tracemod
from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.protocol import driver, rpc
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader, WindowedIngest
from fuzzyheavyhitters_tpu.resilience.chaos import ChaosProxy, parse_faults
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 24731


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(5)


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """Arm fhh-trace into a per-test directory; disarm + re-resolve on
    the way out so no other test sees a writer."""
    d = tmp_path / "trace"
    monkeypatch.setenv(tracemod.ENV_DIR, str(d))
    tracemod._refresh()
    yield d
    monkeypatch.delenv(tracemod.ENV_DIR, raising=False)
    tracemod._refresh()


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


async def _bring_up(cfg, port, ckpt_dir=None, dial0=None):
    live = {}
    live["s0"], live["s1"] = await _start_servers(cfg, port, ckpt_dir)
    d0 = ("127.0.0.1", port) if dial0 is None else dial0
    c0 = await rpc.CollectorClient.connect(*d0)
    c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
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


def _events(trace_dir):
    tracemod.flush()
    return tracemod.load_events(str(trace_dir))


# ---------------------------------------------------------------------------
# histograms (obs/hist.py)
# ---------------------------------------------------------------------------


def test_hist_quantiles_and_exact_max():
    h = histmod.Histogram()
    for v in (0.001, 0.002, 0.004, 0.1, 0.1, 0.1, 5.0):
        h.observe(v)
    assert h.count == 7 and h.max == 5.0
    # quantile estimates are good to ~one bucket width (58%)
    assert 0.05 <= h.quantile(0.5) <= 0.16
    assert h.quantile(0.99) <= 5.0
    assert h.quantile(0.95) <= 5.0
    s = h.summary()
    assert s["count"] == 7 and s["max_s"] == 5.0
    assert histmod.Histogram().quantile(0.5) is None  # empty = None


def test_hist_merge_is_bucketwise_and_order_free():
    a, b = histmod.Histogram(), histmod.Histogram()
    for v in (0.01, 0.02, 0.03):
        a.observe(v)
    for v in (1.0, 2.0):
        b.observe(v)
    m1 = histmod.Histogram.merged([a, b])
    m2 = histmod.Histogram.merged([b, a, None])  # None tolerated
    assert m1.count == m2.count == 5
    assert m1.counts == m2.counts
    assert m1.quantile(0.95) == m2.quantile(0.95)


def test_hist_snapshot_round_trip_and_negative_clamp():
    h = histmod.Histogram()
    h.observe(-1.0)  # clamped, not a crash
    h.observe(float("nan"))
    h.observe(0.25)
    h2 = histmod.Histogram.from_snapshot(h.snapshot())
    assert h2.count == h.count and h2.counts == h.counts
    assert h2.quantile(0.99) == pytest.approx(h.quantile(0.99))


def test_registry_observe_reset_and_report_shape():
    reg = obsmetrics.Registry("t-hist")
    assert reg.report() == {"counters": {}, "gauges": {}, "phases": {}}
    reg.observe("level_latency", 0.05)
    reg.observe("rpc:tree_crawl", 0.002)
    rep = reg.report()
    assert rep["hists"]["level_latency"]["count"] == 1
    assert json.loads(json.dumps(rep))  # still json-serializable
    summ = reg.hists_summary()
    assert set(summ) == {"level_latency", "rpc:tree_crawl"}
    assert summ["level_latency"]["p95_s"] is not None
    reg.reset()
    # the hists key disappears with the histograms (pre-SLO shape)
    assert reg.report() == {"counters": {}, "gauges": {}, "phases": {}}


def test_report_slo_section_merges_across_registries():
    a = obsmetrics.Registry("t-slo-a")
    b = obsmetrics.Registry("t-slo-b")
    for v in (0.1, 0.2):
        a.observe("level_latency", v)
    b.observe("level_latency", 0.4)
    a.observe("rpc:status", 0.001)
    doc = obsreport.run_report([a, b])
    slo = doc["slo"]
    assert slo["level_latency"]["count"] == 3  # bucketwise merge
    assert set(slo["level_latency"]["by_registry"]) == {"t-slo-a", "t-slo-b"}
    assert slo["verbs"]["status"]["count"] == 1
    # no histograms anywhere -> no section at all
    empty = obsmetrics.Registry("t-slo-empty")
    assert "slo" not in obsreport.run_report([empty])


# ---------------------------------------------------------------------------
# zero-cost when disabled (the FHH_DEBUG_GUARDS contract)
# ---------------------------------------------------------------------------


def test_trace_disabled_is_structurally_zero_cost(tmp_path, monkeypatch):
    monkeypatch.delenv(tracemod.ENV_DIR, raising=False)
    tracemod._refresh()
    assert tracemod.enabled() is False
    reg = obsmetrics.Registry("t-off")
    with tracemod.root("crawl") as tid:
        assert tid is None  # no trace minted
        with reg.span("level", level=0):
            pass
    # no writer, no context, no files — the span path touched nothing
    assert tracemod._WRITER is None
    assert tracemod.current_ids() is None
    assert not list(tmp_path.iterdir())
    # and the per-span overhead is ONE flag read: span_begin is never
    # called (the _SpanCtx gate is trace.enabled())
    assert tracemod.wire_ctx() is None
    # no profiler either: obs imports jax.profiler at the first TRACED
    # span, and enters no annotation before that
    monkeypatch.setattr(tracemod, "_ANNOTATION", None)
    with reg.span("gc_ot", level=0):
        assert tracemod.annotate("t-off", "wire_read") is None
    tracemod.span_at("wire_read", "t-off", 0.0, 1.0)  # no context: no-op
    assert tracemod._ANNOTATION is None and tracemod._WRITER is None
    assert not list(tmp_path.iterdir())
    assert "jax" not in vars(tracemod) and "jax" not in vars(obsmetrics)


def test_trace_bad_dir_degrades_without_killing_telemetry(monkeypatch):
    monkeypatch.setenv(tracemod.ENV_DIR, "/proc/noexist/denied")
    tracemod._refresh()
    reg = obsmetrics.Registry("t-bad-dir")
    with tracemod.root("crawl"):
        with reg.span("level", level=0):
            pass  # must not raise
    assert reg.timer_seconds("level") >= 0  # metrics still recorded
    monkeypatch.delenv(tracemod.ENV_DIR, raising=False)
    tracemod._refresh()


# ---------------------------------------------------------------------------
# span recording: parent chains, error marking, ring rotation
# ---------------------------------------------------------------------------


def test_span_parent_chain_and_error_flag(trace_dir):
    reg = obsmetrics.Registry("t-spans")
    with tracemod.root("crawl") as tid:
        assert tid is not None
        with reg.span("level", level=3):
            with reg.span("fss", level=3):
                pass
        with pytest.raises(ConnectionError):
            with reg.span("gc_ot", level=3):
                raise ConnectionError("data plane down")
    evs = _events(trace_dir)
    by_name = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(by_name) == {"level", "fss", "gc_ot"}
    assert by_name["fss"]["parent"] == by_name["level"]["span"]
    assert by_name["level"].get("parent") is None  # trace root
    assert by_name["gc_ot"].get("error") is True
    assert by_name["fss"].get("error") is None
    assert all(e["trace"] == tid for e in by_name.values())
    v = tracemod.validate(evs)
    assert v["ok"], v["errors"]


def test_nested_root_reuses_the_outer_trace(trace_dir):
    with tracemod.root("window") as outer:
        with tracemod.root("crawl") as inner:
            assert inner == outer  # one trace per outermost root
    with tracemod.root("crawl") as fresh:
        assert fresh != outer


def test_ring_rotation_bounds_the_segment(trace_dir, monkeypatch):
    monkeypatch.setenv(tracemod.ENV_RING, "2048")  # min clamp applies
    tracemod._refresh()
    reg = obsmetrics.Registry("t-ring")
    with tracemod.root("crawl"):
        for i in range(2500):
            with reg.span("fss", level=0):
                pass
    tracemod.flush()
    names = sorted(p.name for p in trace_dir.iterdir())
    assert any(n.endswith(".jsonl.1") for n in names)  # rotated once
    evs = tracemod.load_events(str(trace_dir))
    assert 0 < len(evs) <= 2 * 2048  # bounded at two segments


def test_merge_applies_clock_offsets(trace_dir):
    reg = obsmetrics.Registry("server0")
    with tracemod.root("crawl"):
        with reg.span("level", level=0):
            pass
    tracemod.note_clock("server0", offset_s=100.0, rtt_s=0.01)
    lead = obsmetrics.Registry("leader")
    with tracemod.root("crawl"):
        with lead.span("level", level=0):
            pass
    evs = _events(trace_dir)
    doc = tracemod.to_chrome(evs)
    assert doc["otherData"]["clock_offsets"] == {"server0": 100.0}
    comps = {
        e["pid"]: e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    s0 = next(e for e in xs if comps[e["pid"]] == "server0")
    ld = next(e for e in xs if comps[e["pid"]] == "leader")
    # uncorrected both spans share ~one wall-clock; corrected, server0's
    # sits ~100 s earlier on the merged (leader-time) timeline
    assert ld["ts"] - s0["ts"] > 90e6
    # a per-session registry corrects by its base component's offset
    assert tracemod._offset_for("server0:tenant", {"server0": 7.0}) == 7.0


# ---------------------------------------------------------------------------
# e2e: a supervised secure crawl produces ONE valid merged trace
# ---------------------------------------------------------------------------


def test_e2e_supervised_secure_crawl_trace_and_slo(rng, tmp_path, trace_dir):
    """THE acceptance scenario: leader + both socket servers under
    FHH_TRACE_DIR produce a merged Perfetto trace that validates —
    every span parented under ONE crawl trace id, leader and server
    components present, otext/eval/b2a secure-kernel child spans per
    level, clock-offset records measured — while ``status`` and the run
    report carry the level-latency/per-verb SLO histograms."""
    L, n = 5, 12
    port = BASE_PORT
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port, secure_exchange=True)

    async def run():
        lead, c0, c1, live = await _bring_up(cfg, port)
        res = await lead.run_supervised(n, k0, k1)
        st = await c0.call("status")
        await _teardown((c0, c1), live)
        return res, st

    res, st = asyncio.run(run())
    assert _hitters(res)  # the crawl found its hitters

    evs = _events(trace_dir)
    verdict = tracemod.validate(evs)
    assert verdict["ok"], verdict["errors"]
    crawl_traces = [t for t in verdict["traces"] if t.startswith("crawl-")]
    assert len(crawl_traces) == 1  # ONE trace id for the whole crawl
    tid = crawl_traces[0]
    spans = [e for e in evs if e["ph"] == "X" and e.get("trace") == tid]
    comps = {e["comp"] for e in spans}
    assert {"leader", "server0", "server1"} <= comps
    # secure-kernel child spans present per level on the server tracks
    for name in ("otext", "b2a", "gc_ot", "fss", "field"):
        levels = {
            e.get("level")
            for e in spans
            if e["name"] == name and e["comp"].startswith("server")
        }
        assert levels >= set(range(L)), (name, levels)
    # every server phase span has a parent that exists (transitively up
    # to the leader's call span) — spot-check the chain shape
    by_id = {e["span"]: e for e in spans}
    otext = next(e for e in spans if e["name"] == "otext")
    chain = []
    cur = otext
    while cur.get("parent") is not None:
        cur = by_id[cur["parent"]]
        chain.append(cur["name"])
    assert any(c.startswith("verb:") for c in chain)  # server verb span
    assert chain[-1] == "level"  # rooted at the leader's level span
    assert by_id[otext["parent"]]["comp"] == otext["comp"]
    # clock handshake happened for both servers
    clocks = {e["comp"] for e in evs if e["ph"] == "C"}
    assert {"server0", "server1"} <= clocks

    # merged trace loads as Chrome JSON with per-component tracks
    out = tmp_path / "trace.json"
    verdict2 = tracemod.merge(str(trace_dir), str(out))
    assert verdict2["ok"]
    doc = json.loads(out.read_text())
    names = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert {"leader", "server0", "server1"} <= names

    # SLO surfaces: status + run report
    slo = st["slo"]
    assert slo["level_latency"]["count"] >= L
    assert slo["level_latency"]["p95_s"] is not None
    assert any(k.startswith("rpc:") for k in slo)
    assert st["sessions"]["per_session"]["default"]["last_progress_s"] >= 0
    assert "clock" in st
    doc = obsreport.run_report()
    assert doc["slo"]["level_latency"]["p95_s"] is not None
    assert "tree_crawl" in doc["slo"]["verbs"]


# ---------------------------------------------------------------------------
# faults: replays record once; severed planes mark spans error=true
# ---------------------------------------------------------------------------


def test_trace_under_chaos_replay_records_each_span_once(
    rng, tmp_path, trace_dir
):
    """The PR-3 e2e chaos scenario with tracing ON: the leader↔s0 link
    is severed in the response direction (verb executed, response lost
    — the reconnect replays the SAME req_id AND the same trace span id)
    and s1 is killed/restarted at the first checkpoint.  The merged
    trace must validate, each server-side verb execution must appear
    EXACTLY once per (trace, parent, name) — the replay was answered
    from the dedup cache, not re-recorded — and the severed data plane
    leaves error=true spans, never dangling opens."""
    L, n = 5, 12
    port = BASE_PORT + 40
    pxport = port + 20
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port)
    ck = tmp_path / "ckpt"
    ck.mkdir()

    async def run():
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:sever@msg=9,dir=s2c"), link="ctl0",
        ).start()
        live = {}
        live["s0"], live["s1"] = await _start_servers(
            cfg, port, ckpt_dir=str(ck)
        )
        c0 = await rpc.CollectorClient.connect("127.0.0.1", pxport)
        c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
        lead = RpcLeader(cfg, c0, c1)

        async def assassin():
            while lead.obs.counter_value("crawl_checkpoints") < 1:
                await asyncio.sleep(0)
            await live["s1"].aclose()
            await asyncio.sleep(0.3)
            live["s1"] = rpc.CollectorServer(1, cfg, ckpt_dir=str(ck))
            await live["s1"].start(
                "127.0.0.1", port + 10, "127.0.0.1", port + 11
            )

        kill = asyncio.create_task(assassin())
        res = await lead.run_supervised(n, k0, k1, checkpoint_every=2)
        await kill
        st0 = await c0.call("status")
        await _teardown((c0, c1), live, px)
        return res, lead, st0

    res, lead, st0 = asyncio.run(run())

    # the faults happened and the crawl still matched the oracle
    assert st0["dedup_hits"] >= 1
    assert lead.obs.counter_value("recoveries") >= 1
    want = driver.Leader(
        *driver.make_servers(k0, k1), n_dims=1, data_len=L, f_max=cfg.f_max
    ).run(nreqs=n, threshold=cfg.threshold)
    assert _hitters(res) == _hitters(want)

    evs = _events(trace_dir)
    verdict = tracemod.validate(evs)
    assert verdict["ok"], verdict["errors"]
    # replay dedup: a server-side verb execution is keyed by its parent
    # (the client call span, which replays VERBATIM) — if the severed
    # verb had re-executed, its (trace, parent, name) would repeat
    seen = {}
    for e in evs:
        if e["ph"] != "X" or not e["name"].startswith("verb:"):
            continue
        key = (e.get("trace"), e.get("parent"), e["name"], e["comp"])
        seen[key] = seen.get(key, 0) + 1
    assert seen, "no verb spans recorded"
    dupes = {k: c for k, c in seen.items() if c > 1}
    assert not dupes, f"replayed verbs re-recorded: {dupes}"
    # the killed server's data plane failed mid-exchange somewhere: the
    # unwound spans carry error=true instead of dangling open
    errs = [e for e in evs if e["ph"] == "X" and e.get("error")]
    assert errs, "no error-marked spans despite a sever + kill"


# ---------------------------------------------------------------------------
# windowed SLO: seal-to-hitters + ingest admit latency
# ---------------------------------------------------------------------------


def test_windowed_seal_to_hitters_histograms(rng, trace_dir):
    L, n = 5, 12
    port = BASE_PORT + 80
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port)

    async def run():
        lead, c0, c1, live = await _bring_up(cfg, port)
        wi = WindowedIngest(lead, checkpoint=False)
        for i in range(n):
            await wi.submit(
                f"c{i % 4}", _chunk(k0, slice(i, i + 1)),
                _chunk(k1, slice(i, i + 1)),
            )
        await wi.seal_window()
        res = await wi.crawl_window(0)
        st = await c0.call("status")
        s0 = live["s0"]
        driver_h = wi.obs.hist("seal_to_hitters")
        admit_h = wi.obs.hist("ingest_admit")
        server_h = s0.obs.hist("seal_to_hitters")
        await _teardown((c0, c1), live)
        return res, st, driver_h, admit_h, server_h

    res, st, driver_h, admit_h, server_h = asyncio.run(run())
    assert _hitters(res)
    # driver-side: one sealed window crawled -> one observation; admits
    # were counted per submission
    assert driver_h is not None and driver_h.count == 1
    assert driver_h.max > 0
    assert admit_h is not None and admit_h.count == n
    # server-side twin (final_shares observes from the pool's seal
    # instant), and it reaches the status slo section
    assert server_h is not None and server_h.count == 1
    assert st["slo"]["seal_to_hitters"]["count"] == 1
    # the report slo section rolls both views up
    doc = obsreport.run_report()
    assert doc["slo"]["seal_to_hitters"]["count"] >= 2
    assert doc["slo"]["ingest_admit"]["p95_s"] is not None
    # the window trace is distinct from nothing — one window trace id
    evs = _events(trace_dir)
    wins = {e.get("trace") for e in evs if str(e.get("trace", "")).startswith("window-")}
    assert len(wins) == 1


# ---------------------------------------------------------------------------
# per-session heartbeat gap (satellite: last_progress_s)
# ---------------------------------------------------------------------------


def test_last_progress_gap_names_the_wedged_tenant(rng):
    """A second collection uploads keys then goes idle; a later probe
    from ANOTHER session's connection shows tenant t2's
    ``last_progress_s`` growing while the probing session's stays ~0 —
    the wedged-tenant signal the satellite asks for, visible from
    ``status`` without reading logs."""
    port = BASE_PORT + 120
    k0, _k1 = _client_keys(rng, 5, 8)
    cfg = _cfg(port)

    async def run():
        lead, c0, c1, live = await _bring_up(cfg, port)
        ct = await rpc.CollectorClient.connect(
            "127.0.0.1", port, collection="t2"
        )
        await ct.call("add_keys", {"keys": _chunk(k0, slice(0, 4))})
        await asyncio.sleep(0.3)  # t2 idles (its last verb completed)
        # a REAL verb progresses default; the status probes below must
        # NOT (a probe resetting the gap would mask the wedge signal)
        await c0.call("add_keys", {"keys": _chunk(k0, slice(4, 6))})
        await c0.call("status")
        st = await c0.call("status")  # probe on the DEFAULT session
        rows = st["sessions"]["per_session"]
        s0 = live["s0"]
        ts = s0.obs.gauge_value("last_progress_ts")
        await ct.aclose()
        await _teardown((c0, c1), live)
        return rows, ts

    rows, ts = asyncio.run(run())
    assert rows["t2"]["last_progress_s"] >= 0.25  # the gap grew
    assert rows["default"]["last_progress_s"] < 0.25  # real verb just ran
    assert ts is not None and abs(time.time() - ts) < 60
    # the run report's per-session row carries the age too — only for
    # NAMED collections (the default session rides the bare registries)
    reg = obsmetrics.Registry("server0:tenantX")
    reg.count("tenant_device_turns")
    reg.gauge("last_progress_ts", time.time() - 3.0)
    reg.timer_add("fss", 0.1, level=0)
    doc = obsreport.run_report([reg])
    row = doc["sessions"]["per_session"]["tenantX"]
    assert 2.0 <= row["last_progress_s"] <= 60.0


def test_status_probe_does_not_reset_the_gap_or_flood_verbs(rng):
    """Review regression: polling status must neither reset
    ``last_progress_s`` (it would mask the wedged-tenant signal it
    exists to read) nor pile probe counts into the rpc:* verbs table."""
    port = BASE_PORT + 160
    k0, _k1 = _client_keys(rng, 5, 8)
    cfg = _cfg(port)

    async def run():
        lead, c0, c1, live = await _bring_up(cfg, port)
        await c0.call("add_keys", {"keys": _chunk(k0, slice(0, 2))})
        await asyncio.sleep(0.25)
        for _ in range(5):
            await c0.call("status")
        st = await c0.call("status")
        await _teardown((c0, c1), live)
        return st

    st = asyncio.run(run())
    # six probes later the gap still measures from the add_keys
    assert st["sessions"]["per_session"]["default"]["last_progress_s"] >= 0.2
    assert "rpc:status" not in st["slo"]
    assert "rpc:add_keys" in st["slo"]


def test_call_span_marks_server_error_responses(rng, trace_dir):
    """Review regression: a verb the SERVER failed (an __error__
    response, not a transport loss) must close the client call span
    error=true — filtering the merged timeline by error has to surface
    server-side failures too."""
    port = BASE_PORT + 200
    cfg = _cfg(port)

    async def run():
        lead, c0, c1, live = await _bring_up(cfg, port)
        with tracemod.root("crawl"):
            with pytest.raises(RuntimeError, match="tree_init before"):
                await c0.call("tree_init", {})  # no keys: server refuses
        await _teardown((c0, c1), live)

    asyncio.run(run())
    evs = _events(trace_dir)
    call = next(e for e in evs if e.get("name") == "call:tree_init")
    assert call.get("error") is True
    verb = next(e for e in evs if e.get("name") == "verb:tree_init")
    assert verb.get("error") is True  # the span unwound by the raise


def test_clock_offsets_prefer_the_tightest_rtt():
    """Review regression: a chaos-era clock sample measured across a
    reconnect (huge rtt, bogus midpoint) must lose to a tight one."""
    evs = [
        {"ph": "C", "comp": "server0", "off": 40.0, "rtt": 80.0},
        {"ph": "C", "comp": "server0", "off": 0.002, "rtt": 0.001},
        {"ph": "C", "comp": "server0", "off": 39.0, "rtt": 78.0},
    ]
    assert tracemod.clock_offsets(evs) == {"server0": 0.002}
    # no rtt anywhere: median fallback
    evs = [
        {"ph": "C", "comp": "s", "off": v} for v in (1.0, 5.0, 9.0)
    ]
    assert tracemod.clock_offsets(evs) == {"s": 5.0}


def test_sealed_at_survives_ingest_checkpoint_round_trip(rng, tmp_path):
    """Review regression: the seal instant rides the ingest checkpoint,
    so a recovered window still observes its seal-to-hitters latency
    (the replayed seal verb is a no-op on an already-sealed pool and
    must not restamp the clock)."""
    port = BASE_PORT + 240
    k0, _k1 = _client_keys(rng, 5, 8)
    cfg = _cfg(port)
    s = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))

    async def go():
        await s.submit_keys({
            "window": 0, "sub_id": "a", "client_id": "c",
            "keys": _chunk(k0, slice(0, 4)),
        })
        await s.window_seal({"window": 0})
        sealed_at = s._default()._ingest_pools[0].sealed_at
        await s.tree_checkpoint({"level": -1, "ingest_only": True})
        fresh = rpc.CollectorServer(0, cfg, ckpt_dir=str(tmp_path))
        await fresh.tree_restore({"level": -1})
        pool = fresh._default()._ingest_pools[0]
        return sealed_at, pool

    sealed_at, pool = asyncio.run(go())
    assert sealed_at is not None
    assert pool.sealed and pool.sealed_at == sealed_at


# ---------------------------------------------------------------------------
# chip-profiler gating (FHH_PROFILE / FHH_PROFILE_LEVELS)
# ---------------------------------------------------------------------------


class _FakeProfiler:
    def __init__(self):
        self.calls = []

    def start_trace(self, d):
        self.calls.append(("start", d))

    def stop_trace(self):
        self.calls.append(("stop", None))


def test_profile_capture_gating(tmp_path, monkeypatch):
    import jax

    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)

    # unset: a no-op
    monkeypatch.delenv(tracemod.ENV_PROFILE, raising=False)
    with tracemod.profile_capture("crawl") as live:
        assert live is False
    assert fake.calls == []

    prof_dir = tmp_path / "prof"
    monkeypatch.setenv(tracemod.ENV_PROFILE, str(prof_dir))
    # whole-crawl mode: crawl captures, per-level hooks stand down
    with tracemod.profile_capture("level", level=3) as live:
        assert live is False
    with tracemod.profile_capture("crawl") as live:
        assert live is True
    assert fake.calls == [("start", str(prof_dir)), ("stop", None)]

    # level mode: only the named levels capture; crawl stands down
    fake.calls.clear()
    monkeypatch.setenv(tracemod.ENV_PROFILE_LEVELS, "2,5")
    with tracemod.profile_capture("crawl") as live:
        assert live is False
    with tracemod.profile_capture("level", level=3) as live:
        assert live is False
    with tracemod.profile_capture("level", level=5) as live:
        assert live is True
    assert fake.calls == [("start", str(prof_dir)), ("stop", None)]

    # captures recorded with kind/level and surfaced by the report
    caps = tracemod.profile_captures()
    assert len(caps) >= 2
    assert caps[-1]["kind"] == "level" and caps[-1]["level"] == 5
    doc = obsreport.run_report([obsmetrics.Registry("t-prof")])
    assert doc["slo"]["profile"][-1]["level"] == 5


def test_profile_capture_survives_profiler_failure(tmp_path, monkeypatch):
    import jax

    def boom(_d):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    monkeypatch.setenv(tracemod.ENV_PROFILE, str(tmp_path / "p"))
    monkeypatch.delenv(tracemod.ENV_PROFILE_LEVELS, raising=False)
    with tracemod.profile_capture("crawl") as live:
        assert live is False  # degraded, never raised


# ---------------------------------------------------------------------------
# the host work inside a level: transfer, codec and socket spans
# ---------------------------------------------------------------------------

# what _send/_dp_send/_recv/_fetch/PlaneMux.recv and the crawl paths
# record on the side doing the work; wire_wait is the parent of the three
# receive spans
_WIRE_SPANS = (
    "d2h", "wire_pickle", "wire_queue", "wire_write", "wire_wait",
    "peer_wait", "wire_read", "wire_unpickle", "h2d",
)
# spans with no span of their own inside.  The data plane's two
# directions have a stream and a thread each, so what the reader thread
# stamped may overlap anything else (the duplex swap sends and receives at
# once); everything else within one gc_ot is disjoint, and so are the
# reader's three among themselves
_RECV_LEAVES = ("peer_wait", "wire_read", "wire_unpickle")
_LEAVES = tuple(
    n for n in _WIRE_SPANS if n != "wire_wait" and n not in _RECV_LEAVES
) + ("otext", "b2a", "garble", "eval")
_EPS = 5e-6  # ts and dur are rounded to the microsecond


def test_span_inherits_level_and_span_at_parents(trace_dir):
    reg = obsmetrics.Registry("t-inherit")
    with tracemod.root("crawl"):
        with reg.span("gc_ot", level=4):
            with reg.span("wire_pickle") as sp:
                assert sp.level == 4  # like a counter inside a span
            t = time.time()
            tracemod.span_at("wire_read", reg.name, t - 0.5, 0.25, level=4)
    assert reg.report()["phases"]["wire_pickle"]["by_level"].keys() == {"4"}
    by_name = {e["name"]: e for e in _events(trace_dir) if e["ph"] == "X"}
    assert by_name["wire_pickle"]["level"] == 4
    rd = by_name["wire_read"]
    assert rd["parent"] == by_name["gc_ot"]["span"] and rd["dur"] == 0.25
    assert rd["ts"] < by_name["gc_ot"]["ts"]  # as stamped, not as recorded
    assert tracemod.validate(_events(trace_dir))["ok"]


@pytest.mark.parametrize("secure", [False, True], ids=["trusted", "secure"])
def test_wire_and_transfer_spans_every_level(rng, trace_dir, secure):
    """One crawl on the CPU pair under FHH_TRACE_DIR: every wire and
    transfer span is in both servers' timers and in the JSONL at every
    level, the trace validates, and on each server the leaves inside one
    gc_ot neither overlap nor sum to more than it."""
    L, n = 5, 12
    port = BASE_PORT + (320 if secure else 280)
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port, secure_exchange=secure)

    async def run():
        lead, c0, c1, live = await _bring_up(cfg, port)
        res = await lead.run_supervised(n, k0, k1)
        phases = {
            name: s.obs.report()["phases"] for name, s in live.items()
        }
        lead_phases = lead.obs.report()["phases"]
        await _teardown((c0, c1), live)
        return res, phases, lead_phases

    res, phases, lead_phases = asyncio.run(run())
    assert _hitters(res)
    levels = {str(lv) for lv in range(L)}
    for srv, ph in phases.items():
        for name in _WIRE_SPANS:
            assert name in ph, (srv, name)
            assert set(ph[name]["by_level"]) >= levels, (srv, name)
        # what opens the crawl in no level: the wait for the upload's last
        # placement and the root frontier; a bulk upload concatenates nothing
        assert ph["key_place"]["count"] == ph["frontier_init"]["count"] == 1
        assert "concat_keys" not in ph
    # the leader's own work of a level, and its side of the wire
    for name in ("reconstruct", "threshold", "prune", "paths",
                 "wire_pickle", "wire_write", "wire_read", "wire_unpickle"):
        assert set(lead_phases[name]["by_level"]) >= levels, name
    assert "level.phases" not in json.dumps(sorted(phases["s0"]))

    evs = _events(trace_dir)
    verdict = tracemod.validate(evs)
    assert verdict["ok"], verdict["errors"]
    assert any(e["name"] == "plane_recv" for e in evs if e["ph"] == "i")
    spans = [e for e in evs if e["ph"] == "X"]
    by_id = {e["span"]: e for e in spans}
    for comp in ("server0", "server1"):
        mine = [e for e in spans if e["comp"] == comp]
        for name in _WIRE_SPANS:
            got = {e.get("level") for e in mine if e["name"] == name}
            assert got >= set(range(L)), (comp, name, got)
        for name in ("peer_wait", "wire_read", "wire_unpickle"):
            assert all(
                by_id[e["parent"]]["name"] == "wire_wait"
                for e in mine if e["name"] == name
            ), name
        outer = [e for e in mine if e["name"] == "gc_ot"]
        assert len(outer) == L
        for g in outer:
            lo, hi = g["ts"] - _EPS, g["ts"] + g["dur"] + _EPS
            for leaves, needed in (
                (_LEAVES, {"wire_pickle", "wire_queue", "wire_write"}),
                # (a read the thread took before this gc_ot began lies
                # before it: only the wait is always inside)
                (_RECV_LEAVES, {"peer_wait"}),
            ):
                inside = sorted(
                    (e["ts"], e["ts"] + e["dur"], e["name"]) for e in mine
                    if e["name"] in leaves
                    and lo <= e["ts"] and e["ts"] + e["dur"] <= hi
                )
                assert needed <= {nm for _, _, nm in inside}
                for (_, end, a), (start, _, b) in zip(inside, inside[1:]):
                    assert start >= end - _EPS, (comp, g["level"], a, b)
                total = sum(end - start for start, end, _ in inside)
                assert total <= g["dur"] + _EPS * len(inside), (comp, g["level"])


def test_chunk_spans_carry_the_level_and_lie_inside_gc_ot(rng, trace_dir, monkeypatch):
    """A secure level that crosses in K = 4 chunks (the frame budget
    patched to one planar block): every span of a chunk says which, says
    the level, and lies inside the level's gc_ot on its server; the
    leaves of different chunks may overlap, so their sum is no longer
    held under gc_ot; a level that goes whole labels nothing."""
    from fuzzyheavyhitters_tpu.ops import gc_pallas
    from fuzzyheavyhitters_tpu.protocol import secure

    L, n, K = 5, 4096, 4
    port = BASE_PORT + 440
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port, secure_exchange=True, addkey_batch_size=1024)
    block = gc_pallas.R_BLK * gc_pallas.GROUP
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", block * 32)

    async def run():
        lead, c0, c1, live = await _bring_up(cfg, port)
        await lead.upload_keys(k0, k1)
        with tracemod.root("crawl"):
            await lead._both("tree_init", {"root_bucket": 4})  # B = 4 blocks
            await lead._both("tree_crawl", {"level": 0, "garbler": 0})
            monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", 1 << 40)
            await lead._both("tree_crawl", {"level": 1, "garbler": 1})
        ks = {
            name: s._default().obs.report()["counters"]["secure_chunks"]
            for name, s in live.items()
        }
        rep = obsreport.run_report([s._default().obs for s in live.values()])
        await _teardown((c0, c1), live)
        return ks, rep

    ks, rep = asyncio.run(run())
    for srv in ("s0", "s1"):
        assert ks[srv]["by_level"] == {"0": K, "1": 1}
    assert rep["secure_kernels"]["chunks_by_level"] == {"0": K, "1": 1}
    # one device program inside each ``otext`` and each ``b2a`` span
    assert rep["secure_kernels"]["chunk_programs_by_level"] == {
        "0": 2 * K, "1": 2}
    # the evaluator's gauge of what it held, and the index's high word
    held = rep["secure_kernels"]["t_rows_held_bytes_by_level"]
    assert set(held) == {"0", "1"} and held["0"] > 0 and held["1"] > 0
    assert rep["secure_kernels"]["ot_index_high"] == 0
    assert (rep["secure_kernels"]["string_bits"],
            rep["secure_kernels"]["child_patterns"],
            rep["secure_kernels"]["payload_words"]) == (2, 2, [2])
    evs = _events(trace_dir)
    assert tracemod.validate(evs)["ok"]
    spans = [e for e in evs if e["ph"] == "X"]
    by_id = {e["span"]: e for e in spans}
    assert not [e for e in spans if "chunk" in e and e["level"] != 0]
    for comp in ("server0", "server1"):
        mine = [e for e in spans if e["comp"] == comp and e.get("level") == 0]
        (g,) = [e for e in mine if e["name"] == "gc_ot"]
        lo, hi = g["ts"] - _EPS, g["ts"] + g["dur"] + _EPS
        chunked = [e for e in mine if "chunk" in e]
        # every chunk, in each span a role records once a chunk
        for name in ("otext", "b2a", "ot2s", "d2h", "h2d", "wire_queue",
                     "wire_write", "wire_wait", "peer_wait", "wire_read"):
            got = sorted(e["chunk"] for e in chunked if e["name"] == name)
            assert got == list(range(K)), (comp, name, got)
        for e in chunked:
            if e["name"] == "ot2s":
                # the 2^S table or its opening: inside its chunk's b2a
                up = by_id[e["parent"]]
                assert (up["name"], up["chunk"]) == ("b2a", e["chunk"])
                assert up["ts"] - _EPS <= e["ts"]
                assert e["ts"] + e["dur"] <= up["ts"] + up["dur"] + _EPS
                continue
            assert e["name"] in _LEAVES + _RECV_LEAVES + ("wire_wait",), e
            # (a frame's read is stamped by the reader thread, which may
            # take it off the socket before this server's gc_ot began)
            if e["name"] not in _RECV_LEAVES:
                assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi, (comp, e)
            # under the gc_ot: directly, or through its wire_wait
            up = by_id[e["parent"]]
            if up["name"] == "wire_wait":
                assert up["chunk"] == e["chunk"]
                up = by_id[up["parent"]]
            assert up is g, (comp, e["name"])
        # nothing of the level's exchange went unlabelled
        assert not [
            e for e in mine if e["name"] in ("otext", "b2a", "wire_wait")
            and "chunk" not in e
        ]
    # scripts/trace_spans.py: what the leaves cover is the union of
    # their intervals (their sum may pass the span), and the chunks count
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(
            os.path.dirname(os.path.dirname(__file__)), "scripts", "trace_spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod._union([(0, 2), (1, 3), (5, 6), (5.5, 5.75)]) == 4
    for row in mod.gc_ot_cover(spans).values():
        assert row["gc_ot_spans"] == 2 and row["chunked_spans"] == 1
        assert row["chunks_median"] == K
        assert 0 < row["share_min"] <= row["share_median"] <= 1 + 1e-3
        assert row["busy_share_median"] >= row["share_median"] - 1e-9
        assert {"otext", "b2a", "d2h"} <= set(row["chunk_leaf_ms_median"])
    # one ``secure_level`` instant a level and server: K, the programs of
    # its ``otext`` + ``b2a`` spans, the held bytes and the index's high
    # word, for the span log that carries no gauges or counters
    rows = mod.secure_levels(evs)
    assert set(rows) == {"server0", "server1"}
    for row in rows.values():
        assert row["levels"] == 2 and row["chunks_max"] == K
        assert row["secure_chunk_programs_max"] == 2 * K
        assert row["ot_index_high"] == 0
        assert (row["string_bits_max"], row["child_patterns_max"],
                row["payload_words"]) == (2, 2, [2])
    assert max(r["t_rows_held_bytes_max"] for r in rows.values()) == max(
        held.values())
    # the streams by the span log against the registries' own account:
    # the frames handed over behind another, and what the writer thread
    # waited between a level's chunks (the timer ``stream_gap``, from
    # the ``wire_write`` spans that name a chunk)
    streams = mod.plane_streams(evs)
    assert set(streams) == set(rep["plane"]) == {"server0", "server1"}
    for comp, row in streams.items():
        mine = rep["plane"][comp]
        assert row["frames"] == mine["stream_frames"] == mine["msgs_sent"]
        assert row["sends_overlapped"] == mine["sends_overlapped"] <= K - 1
        assert row["send_queue_high"] == mine["send_queue_high"]
        gaps = sum(
            st.get("stream_gap_seconds", 0.0) for st in
            rep["secure_kernels"]["stages"][comp]["by_stage"].values())
        assert row["stream_gap_seconds"] == pytest.approx(gaps, abs=1e-4)


def test_wire_spans_of_a_frame_with_two_out_of_band_buffers(trace_dir, monkeypatch):
    """One data-plane frame of two raw buffers over loopback, through
    the real plane (two streams, a thread on each): the sender's
    ``wire_pickle``, ``wire_queue`` and ``wire_write`` (the writer
    thread's clock), the receiver's ``peer_wait`` -> ``wire_read`` ->
    ``wire_unpickle`` (the reader thread's) end to end under its
    ``wire_wait``, ``wire_read`` ending once the LAST buffer is held,
    and the ``wire_oob`` and ``plane_send`` instants that
    ``scripts/trace_spans.py`` sums."""
    import importlib.util
    import os
    import socket

    from fuzzyheavyhitters_tpu.protocol import wire

    held = []
    real = wire._recv_exact

    def spy(sock, *bufs):  # on the reader threads
        real(sock, *bufs)
        held.extend(time.time() for b in bufs if isinstance(b, np.ndarray))

    monkeypatch.setattr(wire, "_recv_exact", spy)
    port = BASE_PORT + 400
    s0, s1 = (rpc.CollectorServer(i, _cfg(port)) for i in (0, 1))
    tx, rx = s0.obs, s1.obs
    a = np.arange(1 << 18, dtype=np.uint32)
    b = np.arange(1 << 17, dtype=np.uint64)

    async def run():
        lsock = socket.create_server(("127.0.0.1", port))
        conns = []
        for _ in range(2):
            dialed = socket.create_connection(("127.0.0.1", port))
            conns.append((dialed, lsock.accept()[0]))
        lsock.close()
        (a_send, b_recv), (a_recv, b_send) = conns
        s0._attach_plane(a_send, a_recv)
        s1._attach_plane(b_send, b_recv)
        with tracemod.root("crawl"):
            async def receive():
                with rx.span("gc_ot", level=3):
                    return await s1._dp_recv(s1._default())

            pending = asyncio.ensure_future(receive())
            await asyncio.sleep(0.05)  # the receiver waits: peer_wait > 0
            with tx.span("gc_ot", level=3):
                await s0._dp_send(s0._default(), (a, b))
            got = await asyncio.wait_for(pending, 10)
        await s0.aclose()
        await s1.aclose()
        return got

    got = asyncio.run(run())
    assert np.array_equal(got[0], a) and np.array_equal(got[1], b)
    assert len(held) == 2
    oob = a.nbytes + b.nbytes
    assert tx.counter_value("wire_oob_bytes", level=3) == oob
    assert oob > 0.999 * tx.counter_value("data_bytes_sent")
    evs = _events(trace_dir)
    assert tracemod.validate(evs)["ok"]
    by = {(e["comp"], e["name"]): e for e in evs if e["ph"] == "X"}
    for key in (("server0", "wire_pickle"), ("server0", "wire_queue"),
                ("server0", "wire_write"),
                ("server1", "peer_wait"), ("server1", "wire_read"),
                ("server1", "wire_unpickle")):
        assert by[key]["level"] == 3, key
    end = lambda e: e["ts"] + e["dur"]
    pw, rd, up = (by["server1", n] for n in ("peer_wait", "wire_read", "wire_unpickle"))
    wait = by["server1", "wire_wait"]
    assert pw["parent"] == rd["parent"] == up["parent"] == wait["span"]
    assert pw["dur"] >= 0.04  # asked before the frame was sent
    assert abs(end(pw) - rd["ts"]) <= _EPS and abs(end(rd) - up["ts"]) <= _EPS
    # wire_read: header read -> BOTH buffers held, and no longer
    assert rd["ts"] - _EPS <= held[0] <= held[1] <= end(rd) + _EPS
    assert end(up) <= end(wait) + _EPS
    # the sender pickled no array: its wire_pickle precedes the hand-over
    # to the writer thread, whose send begins where the frame's wait in
    # the stream's queue ends (a free stream: the thread hop alone)
    pk, qu, wr = (by["server0", n] for n in ("wire_pickle", "wire_queue", "wire_write"))
    assert end(pk) <= qu["ts"] + _EPS and abs(end(qu) - wr["ts"]) <= _EPS
    assert qu["dur"] < 0.05 and wr["ts"] <= end(rd)
    assert pk["parent"] == qu["parent"] == wr["parent"]
    assert tx.timer_seconds("wire_write", level=3) == pytest.approx(wr["dur"], abs=1e-5)
    assert tx.timer_seconds("wire_queue", level=3) == pytest.approx(qu["dur"], abs=1e-5)
    # every frame went through the stream's thread, one at a time
    assert tx.counter_value("plane_stream_frames", level=3) == 1
    assert tx.counter_value("data_msgs_sent") == 1
    assert tx.gauge_value("plane_send_queue_high", level=3) == 1
    inst = [e for e in evs if e["ph"] == "i" and e["name"] == "wire_oob"]
    assert [e["args"]["oob"] for e in inst] == [oob]
    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(
            os.path.dirname(os.path.dirname(__file__)), "scripts", "trace_spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.plane_streams(evs) == {
        "server0": {"frames": 1, "sends_overlapped": 0, "send_queue_high": 1,
                    "stream_gap_seconds": 0.0}}
    assert tx.counter_value("plane_sends_overlapped", level=3) == 0
    row = mod.wire_oob(evs)["server0"]
    assert row["frames"] == 1 and row["wire_oob_bytes"] == oob
    assert row["framed_bytes"] == tx.counter_value("data_bytes_sent")


def test_profiler_capture_holds_the_program_spans(rng, tmp_path, trace_dir):
    """A jax.profiler capture around a crawl carries the program's
    spans as ``<comp>:<name>`` annotations on the profiler's own clock,
    the pump's live read among them."""
    import glob

    import jax

    L, n = 5, 12
    port = BASE_PORT + 360
    k0, k1 = _client_keys(rng, L, n)
    cfg = _cfg(port)
    prof = tmp_path / "prof"

    async def run():
        lead, c0, c1, live = await _bring_up(cfg, port)
        await lead.upload_keys(k0, k1)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(prof), profiler_options=options)
        try:
            res = await lead.run(n)
        finally:
            jax.profiler.stop_trace()
        await _teardown((c0, c1), live)
        return res

    assert _hitters(asyncio.run(run()))
    files = glob.glob(str(prof / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert files
    data = jax.profiler.ProfileData.from_file(files[-1])
    names = {
        ev.name.split("#", 1)[0]
        for plane in data.planes for line in plane.lines for ev in line.events
    }
    assert {"server0:gc_ot", "server1:wire_read", "server0:d2h",
            "leader:level"} <= names, sorted(names)[:40]


def test_trace_spans_reports_busy_time_by_device_plane(tmp_path):
    """``scripts/trace_spans.py --capture``: the seconds each device plane
    ran anything inside the capture's window of levels, plane by plane
    (the benchmark's ``busy_s`` is their mean), on the recorded capture
    the benchmark's own tests reduce."""
    import importlib.util
    import os

    from jax.profiler import ProfileData

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(root, "scripts", "trace_spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    data = os.path.join(root, "benchmark", "tests", "data")
    with open(os.path.join(data, "trusted_capture.textproto"), encoding="utf-8") as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / "cut.xplane.pb"
    path.write_bytes(blob)
    out = mod.device_busy(str(path))
    assert list(out["busy_s"]) == ["/device:TPU:0"]
    import trace_reduce

    want = trace_reduce.reduce(trace_reduce.read_capture(str(path)))
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["busy_s"]["/device:TPU:0"] == pytest.approx(want["busy_s"])
