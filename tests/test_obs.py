"""Telemetry-layer tests: registry semantics, structured log round-trips,
heartbeat lifecycle, the end-to-end run report on a trusted AND a secure
crawl (both socket servers in one process, so the two sides' data-plane
accounting can be asserted consistent against each other), and the guard
that no crawl-path module falls back to bare ``print`` telemetry."""

import asyncio
import gc
import io
import json
import os
import time

import numpy as np
import pytest

from fuzzyheavyhitters_tpu import obs
from fuzzyheavyhitters_tpu.obs import heartbeat as hbmod
from fuzzyheavyhitters_tpu.obs import logs as logsmod
from fuzzyheavyhitters_tpu.obs import metrics as obsmetrics
from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.protocol import driver, rpc
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

_PKG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fuzzyheavyhitters_tpu",
)


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """Unit-scale telemetry tests stay on the CPU backend (conftest)."""
    yield


@pytest.fixture
def log_sink():
    """Route emits into a StringIO for the duration of one test, then
    restore the env-derived defaults."""
    sink = io.StringIO()
    old = dict(logsmod._cfg)
    logsmod.configure(fmt="json", stream=sink, min_severity="debug")
    yield sink
    with logsmod._lock:
        logsmod._cfg.update(old)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_totals_and_levels():
    reg = obsmetrics.Registry("t-counters")
    reg.count("bytes", 10, level=0)
    reg.count("bytes", 5, level=0)
    reg.count("bytes", 7, level=3)
    reg.count("bytes", 1)  # no level, no active span: total-only
    assert reg.counter_value("bytes") == 23
    assert reg.counter_value("bytes", level=0) == 15
    assert reg.counter_value("bytes", level=3) == 7
    assert reg.counter_value("missing") == 0


def test_span_level_inheritance():
    """A counter incremented inside a span lands on the span's level —
    the mechanism that attributes data-plane bytes deep in the wire
    helpers to the level whose exchange sent them."""
    reg = obsmetrics.Registry("t-inherit")
    with reg.span("gc_ot", level=7):
        reg.count("data_bytes_sent", 100)
        with reg.span("inner"):  # level-less inner span: still level 7
            reg.count("data_bytes_sent", 11)
    assert reg.counter_value("data_bytes_sent", level=7) == 111


def test_span_timer_accumulation_and_current_span():
    reg = obsmetrics.Registry("t-timers")
    assert reg.current_span() is None
    with reg.span("fss", level=2) as sp:
        time.sleep(0.01)
        cur = reg.current_span()
        assert cur is sp and cur.name == "fss" and cur.level == 2
        assert cur.elapsed() > 0
    assert reg.current_span() is None
    assert reg.timer_seconds("fss") >= 0.01
    assert reg.timer_seconds("fss", level=2) >= 0.01
    with reg.span("fss", level=2):
        pass
    rep = reg.report()
    assert rep["phases"]["fss"]["count"] == 2
    assert set(rep["phases"]["fss"]["by_level"]) == {"2"}


def test_gauge_last_write_wins_and_reset():
    reg = obsmetrics.Registry("t-gauges")
    reg.gauge("survivors", 64, level=0)
    reg.gauge("survivors", 16, level=1)
    rep = reg.report()
    assert rep["gauges"]["survivors"]["last"] == 16
    assert rep["gauges"]["survivors"]["by_level"] == {"0": 64, "1": 16}
    reg.reset()
    assert reg.report() == {"counters": {}, "gauges": {}, "phases": {}}


def test_run_report_disambiguates_same_named_registries():
    """Two same-named registries (a second driver.Leader after a
    checkpoint restore) must both survive into the aggregate report,
    keyed deterministically by registration order — not silently
    overwrite each other."""
    a = obsmetrics.Registry("t-dup")
    b = obsmetrics.Registry("t-dup")
    a.count("writes", 1)
    b.count("writes", 2)
    doc = obs.run_report([a, b])
    assert doc["registries"]["t-dup"]["counters"]["writes"]["total"] == 1
    assert doc["registries"]["t-dup#2"]["counters"]["writes"]["total"] == 2
    # all_registries keeps name ties in registration order
    regs = [r for r in obsmetrics.all_registries() if r.name == "t-dup"]
    assert regs == [a, b]


def test_dropped_registry_final_snapshot_survives_into_report():
    """A registry whose owner is dropped still reaches the no-arg run
    report via its retained final snapshot — and retention is bounded,
    with overflow surfaced as ``dropped_registries`` (a long-lived
    process constructing one leader per collection must not grow the
    registry set or the report without bound)."""
    reg = obsmetrics.Registry("t-dropped")
    reg.count("writes", 5, level=3)
    seq = reg.seq
    del reg
    gc.collect()
    assert any(
        n == "t-dropped" and s == seq
        for n, s, _ in obsmetrics.final_snapshots()
    )
    doc = obs.run_report()
    keys = [k for k in doc["registries"] if k.split("#")[0] == "t-dropped"]
    assert keys, sorted(doc["registries"])
    snap = doc["registries"][keys[-1]]
    assert snap["counters"]["writes"]["total"] == 5
    assert snap["counters"]["writes"]["by_level"] == {"3": 5}

    # blow past the retention bound: the oldest snapshots fall off and
    # the report says how many (the cap is never silent)
    before = obsmetrics.final_dropped()
    for i in range(obsmetrics._MAX_FINAL + 5):
        r = obsmetrics.Registry("t-churn")
        r.count("n", i)
        del r
    gc.collect()
    assert len(obsmetrics.final_snapshots()) <= obsmetrics._MAX_FINAL
    assert obsmetrics.final_dropped() > before
    assert obs.run_report()["dropped_registries"] == obsmetrics.final_dropped()


def test_report_is_json_serializable():
    reg = obsmetrics.Registry("t-json")
    reg.count("n", np.int64(3), level=int(np.int32(1)))
    with reg.span("p", level=0):
        pass
    rt = json.loads(json.dumps(reg.report()))
    assert rt["counters"]["n"]["total"] == 3


def test_session_registry_churn_stays_bounded():
    """Satellite regression (PR 13): a long-lived multi-tenant server
    whose collections churn creates one ``server{N}:{key}`` registry per
    session — every dropped one lands in the SAME bounded final-snapshot
    retention as process registries (obs.metrics._MAX_FINAL, oldest
    discarded + counted), so neither the snapshot list nor the no-arg
    run report can grow without bound, and the report stays writable."""
    cap = obsmetrics._MAX_FINAL
    before_live = len(obsmetrics.all_registries())
    for i in range(cap + 40):
        r = obsmetrics.Registry(f"server0:churn{i}")
        r.count("pool_admitted_keys", i, level=0)
        r.observe("level_latency", 0.01)  # hists retained too
        del r
    gc.collect()
    snaps = obsmetrics.final_snapshots()
    assert len(snaps) <= cap
    # the newest churned sessions survived, the oldest fell off COUNTED
    names = [n for n, _s, _r in snaps]
    assert f"server0:churn{cap + 39}" in names
    assert obsmetrics.final_dropped() > 0
    doc = obs.run_report()
    # bounded report: at most cap retained snapshots + the live set
    assert len(doc["registries"]) <= cap + before_live + 8
    assert doc["dropped_registries"] == obsmetrics.final_dropped()
    # a retained per-session snapshot still carries its accounting
    # (counters AND the new latency histograms) into the report
    key = next(
        k for k in doc["registries"]
        if k.startswith(f"server0:churn{cap + 39}")
    )
    snap = doc["registries"][key]
    assert snap["counters"]["pool_admitted_keys"]["total"] == cap + 39
    assert snap["hists"]["level_latency"]["count"] == 1
    # and the sessions rollup keyed them without unbounded growth either
    assert len(doc["sessions"]["per_session"]) <= cap + 8


# ---------------------------------------------------------------------------
# structured logs
# ---------------------------------------------------------------------------


def test_json_lines_round_trip(log_sink):
    obs.emit("crawl.done", seconds=3.21, level=np.int64(5), n=np.uint32(7))
    obs.emit("level.phases", severity="debug", fss_s=np.float64(0.125))
    lines = log_sink.getvalue().strip().splitlines()
    assert len(lines) == 2
    recs = [json.loads(l) for l in lines]
    assert recs[0]["event"] == "crawl.done"
    assert recs[0]["seconds"] == 3.21
    assert recs[0]["level"] == 5 and recs[0]["n"] == 7  # numpy coerced
    assert recs[1]["sev"] == "debug" and recs[1]["fss_s"] == 0.125
    assert all("ts" in r for r in recs)


def test_severity_gating(log_sink):
    logsmod.configure(min_severity="warn")
    obs.emit("quiet", severity="info")
    obs.emit("loud", severity="error", code=1)
    lines = log_sink.getvalue().strip().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["event"] == "loud"


def test_bad_log_stream_path_degrades_to_stderr(monkeypatch):
    """A misconfigured FHH_LOG_STREAM path degrades logging to stderr
    (warned once) — it must never raise out of emit() and take down the
    crawl that telemetry exists to observe."""
    fake_err = io.StringIO()
    monkeypatch.setattr(logsmod.sys, "stderr", fake_err)
    old_cfg = dict(logsmod._cfg)
    old_opened = dict(logsmod._opened)
    logsmod._opened.update({"path": None, "file": None})
    logsmod.configure(
        fmt="json", stream="/nonexistent-dir/x.log", min_severity="info"
    )
    try:
        obs.emit("survives", code=1)
        obs.emit("survives.again", code=2)  # later emits don't re-raise
    finally:
        with logsmod._lock:
            logsmod._cfg.update(old_cfg)
        logsmod._opened.update(old_opened)
    out = fake_err.getvalue()
    assert out.count("cannot open log stream") == 1  # once, not per emit
    recs = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    assert {r["event"] for r in recs} == {"survives", "survives.again"}


def test_human_format_line():
    sink = io.StringIO()
    old = dict(logsmod._cfg)
    logsmod.configure(fmt="human", stream=sink, min_severity="info")
    try:
        obs.emit("keygen.report", n_keys=8, seconds=1.5)
    finally:
        with logsmod._lock:
            logsmod._cfg.update(old)
    line = sink.getvalue()
    assert "keygen.report" in line and "n_keys=8" in line


# ---------------------------------------------------------------------------
# heartbeat
# ---------------------------------------------------------------------------


def test_heartbeat_names_active_span_and_stops(log_sink):
    reg = obsmetrics.Registry("t-hb")
    hb = hbmod.Heartbeat(interval=0.02)
    hb.start()
    try:
        with reg.span("gc_ot", level=311):
            time.sleep(0.1)
    finally:
        hb.stop()
    hb.join(timeout=2)
    assert not hb.is_alive()  # stops cleanly, not just daemon-abandoned
    recs = [json.loads(l) for l in log_sink.getvalue().strip().splitlines()]
    beats = [
        r for r in recs
        if r["event"] == "heartbeat" and r.get("registry") == "t-hb"
    ]
    assert beats, recs  # a wedged span IS named in the log trail
    assert beats[0]["span"] == "gc_ot" and beats[0]["level"] == 311
    assert beats[0]["elapsed_s"] >= 0


def test_per_process_report_path_and_claim(monkeypatch):
    """Multi-process deployments (socket servers, 2-process mesh) inherit
    ONE FHH_RUN_REPORT path; each party claims a suffixed sibling so the
    last exiter cannot clobber the others' reports."""
    assert obs.per_process_report_path("/tmp/r.json", "s0") == "/tmp/r.s0.json"
    assert obs.per_process_report_path("/tmp/report", "p1") == "/tmp/report.p1"
    monkeypatch.setenv("FHH_RUN_REPORT", "/tmp/r.json")
    obs.claim_report_path("s1")
    assert os.environ["FHH_RUN_REPORT"] == "/tmp/r.s1.json"
    monkeypatch.delenv("FHH_RUN_REPORT")
    obs.claim_report_path("s1")  # no-op when unset
    assert "FHH_RUN_REPORT" not in os.environ


def test_exit_report_sigterm_contract(tmp_path, monkeypatch):
    """The binaries' shared exit contract: inside obs.exit_report() the
    SIGTERM disposition raises SystemExit(143) (so finally blocks run),
    and the run report is written on the way out — including an
    exceptional exit."""
    import signal

    path = tmp_path / "exit_report.json"
    monkeypatch.setenv("FHH_RUN_REPORT", str(path))
    monkeypatch.setenv("FHH_HEARTBEAT_S", "0")  # no thread for this test
    old = signal.getsignal(signal.SIGTERM)
    try:
        with pytest.raises(SystemExit) as e:
            with obs.exit_report():
                handler = signal.getsignal(signal.SIGTERM)
                handler(signal.SIGTERM, None)  # what a real TERM triggers
        assert e.value.code == 143
    finally:
        signal.signal(signal.SIGTERM, old)
    doc = json.loads(path.read_text())
    assert doc["schema"] == "fhh-run-report/1"


def test_start_heartbeat_env_disable(monkeypatch):
    monkeypatch.setenv("FHH_HEARTBEAT_S", "0")
    assert obs.start_heartbeat() is None


def test_start_heartbeat_singleton_and_stop(monkeypatch):
    monkeypatch.setenv("FHH_HEARTBEAT_S", "60")
    try:
        hb1 = obs.start_heartbeat()
        hb2 = obs.start_heartbeat()
        assert hb1 is hb2 and hb1.is_alive()
    finally:
        obs.stop_heartbeat()


# ---------------------------------------------------------------------------
# end-to-end run reports: trusted colocated driver + both socket modes
# ---------------------------------------------------------------------------


def _keys(L, n):
    rng = np.random.default_rng(7)
    pts = np.concatenate([np.full(n - 3, 5), rng.integers(0, 1 << L, 3)])[
        :, None
    ]
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    return ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")


def test_trusted_driver_run_report(tmp_path, monkeypatch):
    """The colocated driver's registry carries per-level phase seconds,
    fetch counts, and survivor gauges — and FHH_RUN_REPORT lands it all
    in one machine-readable document."""
    L, n = 2, 8
    k0, k1 = _keys(L, n)
    s0, s1 = driver.make_servers(k0, k1)
    lead = driver.Leader(s0, s1, n_dims=1, data_len=L, f_max=16)
    res = lead.run(nreqs=n, threshold=0.3)
    assert res.paths.shape[0] >= 1

    rep = lead.obs.report()
    for phase in ("level", "fss", "field", "advance"):
        by_level = rep["phases"][phase]["by_level"]
        assert set(by_level) == {"0", "1"}, (phase, by_level)
        assert all(v >= 0 for v in by_level.values())
    # one counts fetch per level
    assert rep["counters"]["device_fetches"]["total"] == L
    assert set(rep["gauges"]["survivors"]["by_level"]) == {"0", "1"}

    path = tmp_path / "report.json"
    monkeypatch.setenv("FHH_RUN_REPORT", str(path))
    assert obs.maybe_write_run_report([lead.obs]) == str(path)
    doc = json.loads(path.read_text())
    assert doc["schema"] == "fhh-run-report/1"
    assert doc["registries"]["driver"]["phases"]["fss"]["by_level"]["1"] >= 0


@pytest.mark.parametrize("secure_exchange", [False, True], ids=["trusted", "secure"])
def test_socket_run_report_two_servers_consistent(secure_exchange):
    """Both collector servers in one process over real sockets: the run
    report's per-level phase keys, device-fetch counts, and data-plane
    byte counts are populated on BOTH sides, and one side's bytes sent
    equal the other's bytes received (same framed stream)."""
    L, n = 2, 12
    port = 32151 if secure_exchange else 32131  # a range of its own (21871 is test_ops' E2E_PORT; 31131 and 31151 are test_secure_chunks')
    k0, k1 = _keys(L, n)
    cfg = Config(
        data_len=L, n_dims=1, ball_size=1, addkey_batch_size=8,
        num_sites=4, threshold=0.2, zipf_exponent=1.03,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=16, secure_exchange=secure_exchange,
    )

    async def run():
        s0 = rpc.CollectorServer(0, cfg)
        s1 = rpc.CollectorServer(1, cfg)
        t1 = asyncio.create_task(
            s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11)
        )
        await asyncio.sleep(0.05)
        t0 = asyncio.create_task(
            s0.start("127.0.0.1", port, "127.0.0.1", port + 11)
        )
        c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
        c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
        await asyncio.gather(t0, t1)
        lead = RpcLeader(cfg, c0, c1)
        await asyncio.gather(c0.call("reset"), c1.call("reset"))
        await lead.upload_keys(k0, k1)
        res = await lead.run(n)
        # close everything: a leaked listener (held alive by reference
        # cycles until a gc pass) keeps its PORT bound for an arbitrary
        # stretch of the suite — test_resilience's +220 scenario shares
        # this port family and failed EADDRINUSE on exactly that
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()
        return res, lead, s0, s1

    res, lead, s0, s1 = asyncio.run(run())
    assert res.paths.shape[0] >= 1

    r0, r1 = s0.obs.report(), s1.obs.report()
    levels = {str(l) for l in range(L)}
    for rep in (r0, r1):
        for phase in ("fss", "gc_ot", "field"):
            assert levels <= set(rep["phases"][phase]["by_level"]), (
                phase, rep["phases"][phase]
            )
        assert rep["counters"]["device_fetches"]["total"] > 0
        assert rep["counters"]["data_bytes_sent"]["total"] > 0
        if secure_exchange:
            assert rep["counters"]["gc_tests"]["total"] > 0
            assert rep["gauges"]["ot_batch_size"]["last"] > 0
    # the two ends of one framed stream must agree byte-for-byte
    s0_sent = r0["counters"]["data_bytes_sent"]["total"]
    s1_recv = r1["counters"]["data_bytes_recv"]["total"]
    s1_sent = r1["counters"]["data_bytes_sent"]["total"]
    s0_recv = r0["counters"]["data_bytes_recv"]["total"]
    assert s0_sent == s1_recv and s1_sent == s0_recv
    if secure_exchange:  # both sides run the same per-level test batch
        assert (
            r0["counters"]["gc_tests"]["by_level"]
            == r1["counters"]["gc_tests"]["by_level"]
        )
    # leader-side registry: a level span per crawl level
    assert levels <= set(lead.obs.report()["phases"]["level"]["by_level"])
    # the aggregate document carries every component
    doc = obs.run_report([s0.obs, s1.obs, lead.obs])
    assert set(doc["registries"]) >= {"server0", "server1", "leader"}


# ---------------------------------------------------------------------------
# guard: no bare print() telemetry in crawl-path modules
# ---------------------------------------------------------------------------


def test_no_bare_print_in_package():
    """Crawl-path telemetry goes through obs.emit — a bare print() in the
    package is either a debug leftover or a regression to the stdout
    scraping this layer replaced.

    This guard's AST walk was generalized into fhh-lint's ``bare-print``
    rule; delegating keeps ONE allowlist (pyproject ``[tool.fhh-lint]``
    ``print_allowed``) instead of a drifting copy here.  The self-lint
    test in test_analysis.py enforces the full rule set; this asserts the
    specific print contract survives any baseline/severity tuning."""
    from fuzzyheavyhitters_tpu.analysis import lint_paths, load_config
    from fuzzyheavyhitters_tpu.analysis.rules import RULES_BY_NAME

    repo = os.path.dirname(_PKG)
    findings, errors = lint_paths(
        ["fuzzyheavyhitters_tpu"], load_config(repo), repo,
        rules=[RULES_BY_NAME["bare-print"]],
    )
    assert errors == []
    assert not findings, (
        "bare print() telemetry found (use fuzzyheavyhitters_tpu.obs.emit): "
        + ", ".join(f"{f.path}:{f.line}" for f in findings)
    )
