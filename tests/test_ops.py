"""fhh-ops suite: the live /metrics exporter, device-memory/compile
telemetry, the alert engine and the ``ops top`` CLI.

Three layers, cheapest first:

- pure units (render families, bucket round-trip, alert fire-once,
  devmem sampling) — no sockets beyond an ephemeral loopback exporter;
- an in-process supervised bring-up proving the ``status`` verb and the
  trace ring carry a fired alert;
- process-level acceptance: the README run shape with the exporter live
  on leader + both servers (scrapes match the servers' own run-report
  registries, an injected tenant stall fires exactly once across every
  surface) and a disabled-exporter server binding no telemetry socket.

The histogram round-trip pins the tentpole invariant: a Prometheus
scrape carries EXACTLY the information the run report computes its SLO
quantiles from (shared fixed buckets, obs/hist.py).
"""

import asyncio
import glob
import json
import os
import re
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from fuzzyheavyhitters_tpu import obs
from fuzzyheavyhitters_tpu.obs import alerts, devmem, exporter
from fuzzyheavyhitters_tpu.obs import ops as fhhops
from fuzzyheavyhitters_tpu.obs import trace as tracemod
from fuzzyheavyhitters_tpu.obs.hist import Histogram
from fuzzyheavyhitters_tpu.obs.metrics import Registry, default_registry
from fuzzyheavyhitters_tpu.protocol import rpc
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.utils.config import Config

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE_PORT = 22170  # in-process status test
E2E_PORT = 21871  # subprocess acceptance run (rpc plane)
E2E_METRICS = 21891  # subprocess acceptance run (/metrics plane)


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    """Every test starts and ends with a dark telemetry plane: no
    exporter, no fired alerts, warmup flag down.  (The compile listener
    itself is one-way per process and stays installed — it only counts.)"""
    monkeypatch.delenv(exporter.ENV_PORT, raising=False)
    monkeypatch.delenv(exporter.ENV_HOST, raising=False)
    exporter.stop()
    alerts._reset_for_tests()
    devmem._reset_for_tests()
    yield
    exporter.stop()
    alerts._reset_for_tests()
    devmem._reset_for_tests()


def _get(port, path="/metrics"):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read().decode()


# ---------------------------------------------------------------------------
# exporter: rendering
# ---------------------------------------------------------------------------


def test_render_families_types_and_labels():
    r = Registry("rtexp")
    r.count("rt_frames", 3)
    r.gauge("rt_depth_keys", 7)
    r.count("fresh_compiles:level")  # colon -> key label
    r.timer_add("rt_phase", 1.5)
    r.observe("level_latency", 0.01)
    t = Registry("server7:acme")  # per-session registry -> collection label
    t.gauge("rt_depth_keys", 9)
    text = exporter.render()
    samples = fhhops.parse_prometheus(text)
    by = {}
    for name, lb, v in samples:
        by.setdefault(name, []).append((lb, v))

    def one(name, **want):
        return [
            v for lb, v in by.get(name, [])
            if all(lb.get(k) == wv for k, wv in want.items())
        ]

    assert one("fhh_rt_frames_total", registry="rtexp") == [3.0]
    assert one("fhh_rt_depth_keys", registry="rtexp") == [7.0]
    assert one("fhh_rt_depth_keys", registry="server7", collection="acme") == [9.0]
    assert one("fhh_fresh_compiles_total", registry="rtexp", key="level") == [1.0]
    assert one("fhh_rt_phase_seconds_total", registry="rtexp") == [1.5]
    assert one("fhh_rt_phase_runs_total", registry="rtexp") == [1.0]
    # histogram family: cumulative buckets + +Inf + sum/count
    buckets = one("fhh_level_latency_seconds_bucket", registry="rtexp")
    assert buckets and buckets[-1] == 1.0
    infs = [
        v for lb, v in by["fhh_level_latency_seconds_bucket"]
        if lb.get("registry") == "rtexp" and lb.get("le") == "+Inf"
    ]
    assert infs == [1.0]
    assert one("fhh_level_latency_seconds_count", registry="rtexp") == [1.0]
    assert one("fhh_level_latency_seconds_sum", registry="rtexp") == [
        pytest.approx(0.01)
    ]
    # one TYPE header per family no matter how many registries contribute
    assert text.count("# TYPE fhh_rt_depth_keys gauge") == 1


def test_hist_bucket_roundtrip_matches_run_report_slo():
    """The satellite invariant: scrape both 'servers', rebuild each
    histogram from its ``_bucket`` series, merge bucketwise, and land on
    the same quantiles the run report computes by merging the live
    histograms themselves (shared BUCKET_BOUNDS make this exact)."""
    r0, r1 = Registry("hrt_s0"), Registry("hrt_s1")
    for v in (0.0003, 0.002, 0.015, 0.04, 0.09):
        r0.observe("level_latency", v)
    for v in (0.0008, 0.004, 0.02, 0.06, 0.1):
        r1.observe("level_latency", v)
    samples = fhhops.parse_prometheus(exporter.render())
    rebuilt = []
    for regname in ("hrt_s0", "hrt_s1"):
        buckets = [
            (lb, v) for name, lb, v in samples
            if name == "fhh_level_latency_seconds_bucket"
            and lb.get("registry") == regname
        ]
        (sum_s,) = [
            v for name, lb, v in samples
            if name == "fhh_level_latency_seconds_sum"
            and lb.get("registry") == regname
        ]
        (count,) = [
            v for name, lb, v in samples
            if name == "fhh_level_latency_seconds_count"
            and lb.get("registry") == regname
        ]
        assert count == 5.0
        rebuilt.append(fhhops.hist_from_series(buckets, sum_s, int(count)))
    merged = Histogram.merged(rebuilt)
    slo = obs.run_report(registries=[r0, r1])["slo"]["level_latency"]
    assert merged.count == slo["count"] == 10
    assert merged.sum == pytest.approx(slo["sum_s"], abs=1e-6)
    for q, key in ((0.5, "p50_s"), (0.95, "p95_s"), (0.99, "p99_s")):
        assert merged.quantile(q) == pytest.approx(slo[key], abs=1e-6)


def test_producers_prune_and_exception_isolation():
    calls = []

    def live():
        calls.append(1)
        return ["fhh_probe_total 1"]

    exporter.add_producer(live)
    exporter.add_producer(lambda: None)  # dead owner -> pruned
    def boom():
        raise RuntimeError("producer crash")
    exporter.add_producer(boom)
    text = exporter.render()
    assert "fhh_probe_total 1" in text
    text2 = exporter.render()  # pruned producer gone, crasher skipped again
    assert "fhh_probe_total 1" in text2
    assert len(calls) == 2


# ---------------------------------------------------------------------------
# exporter: lifecycle
# ---------------------------------------------------------------------------


def test_exporter_lifecycle_bind_scrape_stop(monkeypatch):
    monkeypatch.setenv(exporter.ENV_PORT, "0")  # ephemeral: tests never collide
    port = exporter.maybe_start("s0")
    assert port and exporter.running() and exporter.port() == port
    assert exporter.maybe_start("s0") == port  # idempotent
    status, ctype, body = _get(port)
    assert status == 200
    assert ctype.startswith("text/plain; version=0.0.4")
    assert body.startswith("# TYPE fhh_")
    with pytest.raises(urllib.error.HTTPError):
        _get(port, "/other")
    exporter.stop()
    assert not exporter.running() and exporter.port() is None
    exporter.stop()  # second stop is a no-op


def test_exporter_disabled_without_env():
    assert exporter.maybe_start("s0") is None
    assert not exporter.running()


def test_exporter_degrades_on_bad_port(monkeypatch):
    monkeypatch.setenv(exporter.ENV_PORT, "not-a-port")
    assert exporter.maybe_start("leader") is None
    assert not exporter.running()


def test_exporter_degrades_on_bind_conflict(monkeypatch):
    blocker = socket.socket()
    try:
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        taken = blocker.getsockname()[1]
        monkeypatch.setenv(exporter.ENV_PORT, str(taken))
        assert exporter.maybe_start("leader") is None  # +0 offset == taken
        assert not exporter.running()
    finally:
        blocker.close()


# ---------------------------------------------------------------------------
# devmem: memory sampling + compile attribution
# ---------------------------------------------------------------------------


def test_devmem_sample_watermark_and_tree_nbytes():
    r = Registry("rtmem")
    x = jax.numpy.arange(1024, dtype=jax.numpy.int32)
    x.block_until_ready()
    in_use = devmem.sample(r, phase="rt_keygen")
    assert in_use >= 0
    assert r.gauge_value("hbm_in_use_bytes") == in_use
    assert r.gauge_value("hbm_watermark_bytes") >= in_use
    assert r.gauge_value("hbm_watermark_bytes:rt_keygen") >= in_use
    wm = r.gauge_value("hbm_watermark_bytes")
    devmem.sample(r, phase="rt_keygen")
    assert r.gauge_value("hbm_watermark_bytes") >= wm  # monotone
    del x
    assert devmem.tree_nbytes(None) == 0
    assert devmem.tree_nbytes(np.zeros((2, 3), np.float32)) == 24
    tree = {"a": np.zeros(4, np.int8), "b": [np.zeros(2, np.float64)]}
    assert devmem.tree_nbytes(tree) == 4 + 16


def test_compile_listener_attribution_and_warmup_alert():
    devmem.install_compile_listener()
    reg = default_registry()
    base_all = reg.counter_value("fresh_compiles")
    base_span = reg.counter_value("fresh_compiles:rt_compile_probe")
    with reg.span("rt_compile_probe"):
        # a FRESH jit callable always backend-compiles: the in-memory
        # cache is per-callable and tiny programs stay under the
        # persistent cache's 0.3 s floor (conftest)
        # fhh-lint: disable=recompile-churn (the recompile IS the fixture)
        jax.jit(lambda v: v * 2 + 1)(np.arange(8)).block_until_ready()
    assert reg.counter_value("fresh_compiles") > base_all
    assert reg.counter_value("fresh_compiles:rt_compile_probe") > base_span
    assert reg.timer_seconds("xla_compile") > 0
    # past the warmup ladder, a fresh compile is a named counted event
    # AND alert fodder
    base_post = reg.counter_value("fresh_compiles_post_warmup")
    devmem.note_warmup_done()
    assert devmem.warmup_done()
    with reg.span("rt_compile_probe"):
        # fhh-lint: disable=recompile-churn (the recompile IS the fixture)
        jax.jit(lambda v: v * 3 + 2)(np.arange(8)).block_until_ready()
    assert reg.counter_value("fresh_compiles_post_warmup") > base_post
    alerts.evaluate_registries([reg])
    assert any(rec["rule"] == "recompile_after_warmup" for rec in alerts.fired())


# ---------------------------------------------------------------------------
# alerts: rules + fire-once + surfaces
# ---------------------------------------------------------------------------


def test_tenant_stall_fires_once_across_evaluations(monkeypatch):
    monkeypatch.setenv(alerts.ENV_STALL_S[0], "0.5")
    rows = {
        "acme": {
            "last_progress_s": 2.0, "phase": "crawl",
            "level": 3, "queue_depth": 0,
        }
    }
    alerts.evaluate_sessions(rows, "server0")
    alerts.evaluate_sessions(rows, "server0")  # same (rule, subject): no-op
    fired = alerts.fired()
    assert len(fired) == 1
    rec = fired[0]
    assert rec["rule"] == "tenant_stall" and rec["subject"] == "server0/acme"
    assert rec["phase"] == "crawl" and rec["level"] == 3
    st = alerts.status_section()
    assert st["count"] == 1 and st["dropped"] == 0 and st["fired"] == fired
    lines = alerts.metrics_lines()
    assert 'fhh_alerts_fired_total{rule="tenant_stall"} 1' in lines
    assert sum("fhh_alert_active{" in ln for ln in lines) == 1
    # a DIFFERENT server's stall is its own subject
    alerts.evaluate_sessions(rows, "server1")
    assert len(alerts.fired()) == 2


def test_backlog_slo_and_hbm_rules(monkeypatch):
    monkeypatch.setenv(alerts.ENV_BACKLOG_KEYS[0], "10")
    alerts.evaluate_sessions(
        {"bulk": {"last_progress_s": 0.0, "queue_depth": 100}}, "server1"
    )
    r = Registry("rtslo")
    for _ in range(4):
        r.observe("level_latency", 5.0)  # p95 over the 2.0 s default budget
    r.gauge("hbm_in_use_bytes", 95.0)
    r.gauge("hbm_limit_bytes", 100.0)  # 0.95 > 0.9 default fraction
    alerts.evaluate_registries([r])
    rules = {rec["rule"] for rec in alerts.fired()}
    assert rules == {"ingest_backlog", "slo_burn", "hbm_high_water"}
    # the run report grows an alerts section only once something fired
    rep = obs.run_report(registries=[r])
    assert rep["alerts"]["count"] == 3
    assert {rec["rule"] for rec in rep["alerts"]["fired"]} == rules
    alerts._reset_for_tests()
    assert "alerts" not in obs.run_report(registries=[r])


# ---------------------------------------------------------------------------
# ops CLI: scrape -> merge -> one screen
# ---------------------------------------------------------------------------


def test_ops_top_renders_sessions_alerts_and_headlines(
    monkeypatch, capsys
):
    monkeypatch.setenv(exporter.ENV_PORT, "0")
    port = exporter.maybe_start("s0")
    r = Registry("rtops")
    r.count("data_bytes_sent", 4096)
    for v in (0.01, 0.02, 0.04):
        r.observe("level_latency", v)
    exporter.add_producer(lambda: [
        "# TYPE fhh_session_last_progress_seconds gauge",
        'fhh_session_last_progress_seconds{registry="rtops",collection="acme"} 3.5',
        'fhh_session_queue_depth_keys{registry="rtops",collection="acme"} 12',
    ])
    monkeypatch.setenv(alerts.ENV_STALL_S[0], "0.5")
    alerts.evaluate_sessions(
        {"acme": {"last_progress_s": 3.5, "queue_depth": 12}}, "rtops"
    )
    target = f"127.0.0.1:{port}"
    samples = fhhops.scrape(target)
    assert samples
    frame = fhhops.render_top({target: samples})
    assert frame.startswith("fhh-ops top")
    assert f"{target}(up)" in frame
    assert "!! tenant_stall" in frame and "rtops/acme" in frame
    assert "acme" in frame and "3.5s" in frame
    assert "fhh_data_bytes_sent_total 4096" in frame
    # the level-latency p95 column is reconstructed from the buckets
    # (the bare-registry histogram rides the "default" collection row)
    (hist_row,) = [
        ln for ln in frame.splitlines()
        if ln.startswith("rtops") and " default " in ln
    ]
    cols = hist_row.split()
    assert cols[4] == "3"  # three levels observed
    assert cols[5].endswith("s") and cols[5] != "-"
    # CLI: --once prints one frame; no targets is an error, not a hang
    assert fhhops.main(["top", "--targets", target, "--once"]) == 0
    out = capsys.readouterr().out
    assert "fhh-ops top" in out
    monkeypatch.setenv(exporter.ENV_PORT, "0")  # base 0 -> no default targets
    assert fhhops.main(["top", "--once"]) == 2
    assert fhhops.scrape("127.0.0.1:1") == []  # dead target -> row gap


# ---------------------------------------------------------------------------
# status verb + trace ring carry a fired alert (in-process bring-up)
# ---------------------------------------------------------------------------


def test_status_and_trace_carry_alert(cpu_default, monkeypatch, tmp_path):
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(tracemod.ENV_DIR, str(trace_dir))
    tracemod._refresh()
    monkeypatch.setenv(alerts.ENV_STALL_S[0], "0.0")
    cfg = Config(
        data_len=5, n_dims=1, ball_size=1, addkey_batch_size=8,
        num_sites=4, threshold=0.2, zipf_exponent=1.03,
        server0=f"127.0.0.1:{BASE_PORT}",
        server1=f"127.0.0.1:{BASE_PORT + 10}",
        distribution="zipf", f_max=32,
    )

    async def run():
        s0 = rpc.CollectorServer(0, cfg)
        s1 = rpc.CollectorServer(1, cfg)
        t1 = asyncio.create_task(
            s1.start("127.0.0.1", BASE_PORT + 10, "127.0.0.1", BASE_PORT + 11)
        )
        await asyncio.sleep(0.05)
        t0 = asyncio.create_task(
            s0.start("127.0.0.1", BASE_PORT, "127.0.0.1", BASE_PORT + 11)
        )
        await asyncio.gather(t0, t1)
        c0 = await rpc.CollectorClient.connect("127.0.0.1", BASE_PORT)
        c1 = await rpc.CollectorClient.connect("127.0.0.1", BASE_PORT + 10)
        lead = RpcLeader(cfg, c0, c1)
        await lead._both("reset")  # binds the default session on both
        await asyncio.sleep(0.02)  # any nonzero gap beats the 0.0 budget
        st = await c0.call("status")
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()
        return st

    try:
        st = asyncio.run(run())
        assert st["sessions"]["count"] >= 1
        stall = [
            rec for rec in st["alerts"]["fired"]
            if rec["rule"] == "tenant_stall"
        ]
        assert stall, st["alerts"]
        tracemod.flush()
        evs = tracemod.load_events(str(trace_dir))
        assert any(e.get("name") == "alert:tenant_stall" for e in evs)
    finally:
        monkeypatch.delenv(tracemod.ENV_DIR, raising=False)
        tracemod._refresh()


# ---------------------------------------------------------------------------
# process-level acceptance
# ---------------------------------------------------------------------------

E2E_CFG = {
    "data_len": 16,
    "n_dims": 2,
    "ball_size": 2,
    "addkey_batch_size": 16,
    "num_sites": 4,
    "threshold": 0.06,
    "zipf_exponent": 1.03,
    "server0": f"127.0.0.1:{E2E_PORT}",
    "server1": f"127.0.0.1:{E2E_PORT + 10}",
    "distribution": "rides",
    "f_max": 512,
    "backend": "cpu",
}
N_REQS = 32


def _e2e_env(tmp_path, **extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_backend_optimization_level=1"
    ).strip()
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _spawn(mod, cfg_path, tmp_path, env, *args):
    return subprocess.Popen(
        [sys.executable, "-m", mod, "--config", str(cfg_path), *args],
        cwd=tmp_path, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )


@pytest.mark.slow  # ~35 s: three subprocess JAX boots + a real stall window
def test_ops_e2e_exporters_and_tenant_stall(tmp_path):
    """THE acceptance scenario: a supervised crawl through the binaries
    with the exporter live on all three processes.  Scraped series match
    the servers' own run-report registries; a tenant stall injected via
    a 0.5 s budget on server0 fires exactly once and shows up in the
    logs, the /metrics plane, and server0's run report."""
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(E2E_CFG))
    report_path = tmp_path / "leader_report.json"
    trace_dir = tmp_path / "trace"
    common = dict(
        FHH_RUN_REPORT=report_path,
        FHH_METRICS_PORT=E2E_METRICS,
        FHH_TRACE_DIR=trace_dir,
        # CPU levels can be seconds each (compiles): keep slo_burn out of
        # this scenario so tenant_stall is the ONLY deterministic alert
        FHH_ALERT_LEVEL_P95_S="1000",
    )
    env = _e2e_env(tmp_path, **common)
    env_s0 = _e2e_env(tmp_path, **common, FHH_ALERT_STALL_S="0.5")
    srv = "fuzzyheavyhitters_tpu.bin.server"
    s1 = _spawn(srv, cfg_path, tmp_path, env, "--server_id", "1")
    s0 = _spawn(srv, cfg_path, tmp_path, env_s0, "--server_id", "0")
    lead = None
    try:
        lead = _spawn(
            "fuzzyheavyhitters_tpu.bin.leader", cfg_path, tmp_path, env,
            "-n", str(N_REQS),
        )
        # scrape the LEADER while it is alive: its exporter binds before
        # arg validation, so samples appear as soon as python is up
        leader_seen = False
        deadline = time.monotonic() + 540
        while lead.poll() is None and time.monotonic() < deadline:
            samples = fhhops.scrape(f"127.0.0.1:{E2E_METRICS}")
            if any(lb.get("registry") == "leader" for _n, lb, _v in samples):
                leader_seen = True
                break
            time.sleep(0.25)
        out, _ = lead.communicate(timeout=540)
        assert lead.returncode == 0, f"leader failed:\n{out[-4000:]}"
        assert leader_seen, "never scraped a leader-registry series mid-run"
        assert "metrics.listening" in out
        time.sleep(1.0)  # idle past server0's 0.5 s stall budget
        t_s0 = f"127.0.0.1:{E2E_METRICS + 1}"
        t_s1 = f"127.0.0.1:{E2E_METRICS + 2}"
        # scrape 1 IS the evaluation tick that fires the stall; its alert
        # lines render before the session producer runs, so the fired
        # alert becomes visible from scrape 2 on — and stays at ONE
        fhhops.scrape(t_s0)
        scrape2 = fhhops.scrape(t_s0)
        scrape3 = fhhops.scrape(t_s0)
        for sc in (scrape2, scrape3):
            stalls = [
                (lb, v) for name, lb, v in sc
                if name == "fhh_alert_active"
                and lb.get("rule") == "tenant_stall"
            ]
            assert len(stalls) == 1, stalls
            assert stalls[0][0]["subject"].startswith("server0/")
            (fired_n,) = [
                v for name, lb, v in sc
                if name == "fhh_alerts_fired_total"
                and lb.get("rule") == "tenant_stall"
            ]
            assert fired_n == 1.0
        fhhops.scrape(t_s1)  # tick server1's evaluation too
        s1_samples = fhhops.scrape(t_s1)
        assert s1_samples  # exporter live on the second server too
        # server1 runs the default 120 s budget: no stall there (other
        # rules — e.g. recompile_after_warmup on a CPU run — may fire)
        assert not [
            1 for name, lb, _v in s1_samples
            if name == "fhh_alert_active" and lb.get("rule") == "tenant_stall"
        ]
        # counters on the wire == counters in the registry: compare the
        # scrape against the run report server0 writes at SIGTERM (the
        # data plane is idle between the two, so totals are stable)
        for p in (s0, s1):
            p.terminate()
        outs = {}
        for sid, p in (("s0", s0), ("s1", s1)):
            outs[sid], _ = p.communicate(timeout=60)
        # fhh-lint: disable=metric-naming (str.count over a log line, not a counter)
        assert outs["s0"].count("alert.tenant_stall") == 1
        assert "alert.tenant_stall" not in outs["s1"]
        for sid in ("s0", "s1"):
            assert "metrics.listening" in outs[sid]
        srep = json.loads((tmp_path / "leader_report.s0.json").read_text())
        rules = [rec["rule"] for rec in srep["alerts"]["fired"]]
        assert rules.count("tenant_stall") == 1
        want = {
            name: ent["total"]
            for name, ent in srep["registries"]["server0"]["counters"].items()
            if ":" not in name
        }
        got = {
            name[len("fhh_"):-len("_total")]: v
            for name, lb, v in scrape2
            # fhh-lint: disable=metric-naming (family-name prefix, not a series)
            if name.endswith("_total") and not name.startswith("fhh_alert")
            and lb.get("registry") == "server0" and "collection" not in lb
            and "key" not in lb and name.count("seconds_total") == 0
            and name.count("runs_total") == 0
        }
        shared = set(want) & set(got)
        assert shared, (sorted(want), sorted(got))
        for name in shared:
            assert got[name] == pytest.approx(want[name]), name
    finally:
        for p in (s0, s1, lead):
            if p is not None and p.poll() is None:
                p.kill()
    # the crawl itself was not disturbed: the README CSV landed
    assert (tmp_path / "data" / "ride_heavy_hitters.csv").exists()


def test_ops_e2e_disabled_binds_no_socket(tmp_path):
    """Without FHH_METRICS_PORT a server claims no telemetry socket at
    all — the metrics port stays connection-refused while the rpc plane
    is up, and no listening line is logged."""
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(E2E_CFG))
    env = _e2e_env(tmp_path)
    env.pop("FHH_METRICS_PORT", None)
    srv = "fuzzyheavyhitters_tpu.bin.server"
    s1 = _spawn(srv, cfg_path, tmp_path, env, "--server_id", "1")
    s0 = _spawn(srv, cfg_path, tmp_path, env, "--server_id", "0")
    try:
        deadline = time.monotonic() + 120
        up = False
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", E2E_PORT), 0.5).close()
                up = True
                break
            except OSError:
                if s0.poll() is not None:
                    break
                time.sleep(0.25)
        assert up, "server0 rpc plane never came up"
        for off in (0, 1, 2):
            with pytest.raises(OSError):
                socket.create_connection(
                    ("127.0.0.1", E2E_METRICS + off), 0.5
                ).close()
        for p in (s0, s1):
            p.terminate()
        for p in (s0, s1):
            out, _ = p.communicate(timeout=60)
            assert "metrics.listening" not in out
    finally:
        for p in (s0, s1):
            if p.poll() is None:
                p.kill()


_SWEEP_LEXICON = {
    # the unambiguous subset of the lint secret_lexicon: "delta" and
    # "label"/"labels" are legitimate ops vocabulary on the telemetry
    # plane (fhh_hbm_delta_bytes; Prometheus labels) — the rest may
    # never name an exported series, label, or report row
    "seed", "seeds", "cw", "cws", "cwf", "cwv", "mac", "secret", "triples",
}


def _lexicon_hits(text):
    segs = [s for s in re.split(r"[^a-z0-9]+", str(text).lower()) if s]
    return [s for s in segs if s in _SWEEP_LEXICON]


def _sweep_json(doc, path=""):
    hits = []
    if isinstance(doc, dict):
        for k, v in doc.items():
            hits += [(f"{path}.{k}", h) for h in _lexicon_hits(k)]
            hits += _sweep_json(v, f"{path}.{k}")
    elif isinstance(doc, (list, tuple)):
        for i, v in enumerate(doc):
            hits += _sweep_json(v, f"{path}[{i}]")
    elif isinstance(doc, str):
        hits += [(path, h) for h in _lexicon_hits(doc)]
    return hits


@pytest.mark.slow  # ~40 s: three subprocess JAX boots (secure data plane)
def test_ops_e2e_taint_sweep_secure_crawl(tmp_path):
    """The fhh-taint acceptance sweep: a live three-process SECURE crawl
    under ``FHH_DEBUG_TAINT=1`` — every source constructor registers its
    buffer in the server processes and every obs sink boundary asserts
    in-process (a registered byte image crossing any exported surface
    would crash the crawl) — then the scraped /metrics planes and the
    run reports are swept from the OUTSIDE: no exported metric name,
    label key, label value, or report row may match the secret lexicon.
    The small resilience-suite shape keeps the CPU data plane fast."""
    port, mport = E2E_PORT + 40, E2E_METRICS + 6
    cfg = {
        "data_len": 5, "n_dims": 1, "ball_size": 1, "addkey_batch_size": 64,
        "num_sites": 4, "threshold": 0.05, "zipf_exponent": 1.0,
        "server0": f"127.0.0.1:{port}", "server1": f"127.0.0.1:{port + 10}",
        "distribution": "zipf", "f_max": 16, "backend": "cpu",
        "secure_exchange": True,
    }
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(cfg))
    report_path = tmp_path / "leader_report.json"
    common = dict(
        FHH_DEBUG_TAINT=1,
        FHH_RUN_REPORT=report_path,
        FHH_METRICS_PORT=mport,
        FHH_ALERT_LEVEL_P95_S="1000",
    )
    env = _e2e_env(tmp_path, **common)
    srv = "fuzzyheavyhitters_tpu.bin.server"
    s1 = _spawn(srv, cfg_path, tmp_path, env, "--server_id", "1")
    s0 = _spawn(srv, cfg_path, tmp_path, env, "--server_id", "0")
    lead = None
    try:
        lead = _spawn(
            "fuzzyheavyhitters_tpu.bin.leader", cfg_path, tmp_path, env,
            "-n", "16",
        )
        out, _ = lead.communicate(timeout=540)
        # the in-process half of the sweep: with the sanitizer live on
        # all three processes, a registered buffer reaching ANY sink
        # boundary raises TaintViolation and the crawl dies
        assert lead.returncode == 0, f"leader failed:\n{out[-4000:]}"
        assert "TaintViolation" not in out
        scrapes = {
            sid: fhhops.scrape(f"127.0.0.1:{mport + 1 + i}")
            for i, sid in enumerate(("s0", "s1"))
        }
        for p in (s0, s1):
            p.terminate()
        outs = {}
        for sid, p in (("s0", s0), ("s1", s1)):
            outs[sid], _ = p.communicate(timeout=60)
            assert "TaintViolation" not in outs[sid]
        # the outside half: sweep every exported surface for lexicon
        # matches — a series or label NAMED like key material is a leak
        # in the making even when today's bytes are clean
        for sid, samples in scrapes.items():
            assert samples, f"no samples scraped from {sid}"
            for name, labels, _v in samples:
                assert not _lexicon_hits(name), (sid, name)
                for k, v in labels.items():
                    assert not _lexicon_hits(k), (sid, name, k)
                    assert not _lexicon_hits(v), (sid, name, k, v)
        # and the session rows the servers persisted at SIGTERM
        for sid in ("s0", "s1"):
            srep_path = tmp_path / f"leader_report.{sid}.json"
            srep = json.loads(srep_path.read_text())
            assert "registries" in srep
            hits = _sweep_json(srep)
            assert not hits, (sid, hits[:5])
    finally:
        for p in (s0, s1, lead):
            if p is not None and p.poll() is None:
                p.kill()
