"""Pipelined secure crawl: parity, depth sweep, quiesce chaos, warmup,
report schema, and where the compile cache lands.

The pipeline (protocol/leader_rpc.py `_crawl_level_pipelined` + the
server-side expand/open stage split in protocol/rpc.py) is a pure
scheduling change: up to ``crawl_pipeline_depth`` span verbs in flight
with in-order reassembly, span k+1's FSS expansion dispatched at frame
arrival while span k's GC/OT exchange rides the data plane.  Every test
here pins the contract that matters: results are BIT-IDENTICAL to the
sequential PR-4 path in all three modes, depth 1 IS the sequential path,
and a mid-flight fault quiesces into the sequential retry with the
recovery counters visible in the run report.
"""

import asyncio
import os

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.obs import metrics as obsmetrics
from fuzzyheavyhitters_tpu.obs import report as obsreport
from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.protocol import rpc
from fuzzyheavyhitters_tpu.protocol import sketch as sketchmod
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.resilience import policy as respolicy
from fuzzyheavyhitters_tpu.resilience.chaos import ChaosProxy, parse_faults
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 20431


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    # protocol-shape tests: every program is tiny — pin to the first
    # CPU device like the other suites
    yield


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
    return pts_bits, ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")


async def _start_servers(cfg, port_base):
    s0 = rpc.CollectorServer(0, cfg)
    s1 = rpc.CollectorServer(1, cfg)
    t1 = asyncio.create_task(
        s1.start("127.0.0.1", port_base + 10, "127.0.0.1", port_base + 11)
    )
    await asyncio.sleep(0.05)
    t0 = asyncio.create_task(
        s0.start("127.0.0.1", port_base, "127.0.0.1", port_base + 11)
    )
    await asyncio.gather(t0, t1)
    return s0, s1


async def _run_crawl(cfg, port, k0, k1, sk0=None, sk1=None, nreqs=12,
                     dial0=None, budgets=None, warmup=False):
    """One unsupervised crawl; returns (result, leader, servers)."""
    s0, s1 = await _start_servers(cfg, port)
    host0, p0 = ("127.0.0.1", port) if dial0 is None else dial0
    c0 = await rpc.CollectorClient.connect(host0, p0, budgets=budgets)
    c1 = await rpc.CollectorClient.connect(
        "127.0.0.1", port + 10, budgets=budgets
    )
    lead = RpcLeader(cfg, c0, c1)
    await lead._both("reset")
    await lead.upload_keys(k0, k1, sk0, sk1)
    if warmup:
        await lead.warmup()
    res = await lead.run(nreqs)
    for c in (c0, c1):
        await c.aclose()
    return res, lead, (s0, s1)


async def _teardown(servers):
    for s in servers:
        await s.aclose()


def _crawl(cfg, port, k0, k1, **kw):
    async def go():
        res, lead, servers = await _run_crawl(cfg, port, k0, k1, **kw)
        await _teardown(servers)
        return res, lead

    return asyncio.run(go())


@pytest.mark.parametrize(
    "mode", ["trusted", "secure", "sketch"],
)
def test_pipelined_matches_sequential_bit_identical(rng, mode):
    """THE parity contract: a pipelined sharded crawl returns bit-identical
    paths and counts to the sequential whole-level crawl — in trusted,
    secure, and malicious (sketch) modes."""
    L, n = 5, 12
    base = BASE_PORT + {"trusted": 0, "secure": 60, "sketch": 120}[mode]
    pts_bits, (k0, k1) = _client_keys(rng, L, n)
    sk0 = sk1 = None
    kw = {}
    if mode == "secure":
        # secure_whole_level=False: this test exercises the SHARDED
        # secure pipeline (the whole-level default collapses a secure
        # level to one span — covered by test_secure_kernels.py)
        kw.update(secure_exchange=True, secure_whole_level=False)
    if mode == "sketch":
        kw.update(malicious=True, threshold=0.5, addkey_batch_size=12)
        seeds = rng.integers(0, 2**32, size=(n, 2, 4), dtype=np.uint32)
        cseed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
        sk0, sk1 = sketchmod.gen(seeds, pts_bits[:, 0, :], FE62, F255, cseed)

    res_seq, _ = _crawl(
        _cfg(base, crawl_shard_nodes=0, **kw), base, k0, k1,
        sk0=sk0, sk1=sk1,
    )
    res_pipe, lead = _crawl(
        _cfg(base + 20, crawl_shard_nodes=1, crawl_pipeline_depth=3, **kw),
        base + 20, k0, k1, sk0=sk0, sk1=sk1,
    )
    assert res_seq.counts.size  # the crawl found hitters: a real compare
    np.testing.assert_array_equal(res_pipe.counts, res_seq.counts)
    np.testing.assert_array_equal(res_pipe.paths, res_seq.paths)
    # the pipeline actually engaged (levels with >= 2 spans exist at L=5)
    assert lead.obs.counter_value("pipeline_faults") == 0
    assert lead.obs.timer_seconds("pipeline_overlap") >= 0.0
    rep = obsreport.run_report([lead.obs])
    # last-write-wins gauge, clamped to the final level's span count
    assert 2 <= rep["pipeline"]["depth"] <= 3
    assert rep["pipeline"]["faults"] == 0


def test_depth_one_is_the_sequential_path(rng):
    """crawl_pipeline_depth=1 must BE the PR-4 sequential path: identical
    results AND none of the pipeline telemetry (no pipeline section in
    the run report), so depth 1 deployments are provably unchanged."""
    L, n = 5, 12
    base = BASE_PORT + 180
    _, (k0, k1) = _client_keys(rng, L, n)
    res_whole, _ = _crawl(_cfg(base), base, k0, k1)
    res_d1, lead = _crawl(
        _cfg(base + 20, crawl_shard_nodes=1, crawl_pipeline_depth=1),
        base + 20, k0, k1,
    )
    np.testing.assert_array_equal(res_d1.counts, res_whole.counts)
    np.testing.assert_array_equal(res_d1.paths, res_whole.paths)
    assert lead.obs.timer_seconds("pipeline_overlap") == 0.0
    assert "pipeline" not in obsreport.run_report([lead.obs])


@pytest.mark.parametrize("depth", [2, 4, 8])
def test_pipeline_depth_sweep(rng, depth):
    """Every depth reassembles the same bits (the window size must only
    change scheduling, never data)."""
    L, n = 5, 12
    base = BASE_PORT + 240 + 40 * depth
    _, (k0, k1) = _client_keys(rng, L, n)
    res_seq, _ = _crawl(_cfg(base), base, k0, k1)
    res, _ = _crawl(
        _cfg(base + 20, crawl_shard_nodes=1, crawl_pipeline_depth=depth),
        base + 20, k0, k1,
    )
    np.testing.assert_array_equal(res.counts, res_seq.counts)
    np.testing.assert_array_equal(res.paths, res_seq.paths)


def test_pipeline_fault_quiesces_to_sequential(rng):
    """THE chaos contract: a span request black-holed mid-flight inside a
    pipelined level times out, the pipeline quiesces (plane_break on both
    servers -> plane_reset), the level re-runs sequentially, and the
    results are bit-identical to the fault-free crawl — with the fault
    and re-runs visible in the counters (pipeline_faults >= 1,
    shards_rerun >= 1)."""
    L, n = 5, 12
    port = BASE_PORT + 620
    pxport = port + 25
    _, (k0, k1) = _client_keys(rng, L, n)
    cfg = _cfg(port, crawl_shard_nodes=1, crawl_pipeline_depth=3)
    budgets = respolicy.VerbBudgets(default_s=8.0, per_verb={})

    res_ff, _ = _crawl(
        _cfg(port + 40, crawl_shard_nodes=1, crawl_pipeline_depth=3),
        port + 40, k0, k1,
    )

    async def faulty():
        # c2s frames on ctl0: 1 hello, 2 reset, 3-4 add_keys, 5 tree_init,
        # 6 L0 crawl (1 span), 7 L0 prune, then level 1's spans (8, 9):
        # black-hole the SECOND span of the first pipelined level
        px = await ChaosProxy(
            "127.0.0.1", pxport, "127.0.0.1", port,
            parse_faults("ctl0:blackhole@msg=9,count=1"), link="ctl0",
        ).start()
        res, lead, servers = await _run_crawl(
            cfg, port, k0, k1, dial0=("127.0.0.1", pxport), budgets=budgets
        )
        counters = {
            "faults": lead.obs.counter_value("pipeline_faults"),
            "shards_rerun": lead.obs.counter_value("shards_rerun"),
            "breaks": sum(
                s.obs.counter_value("plane_breaks") for s in servers
            ),
        }
        rep = obsreport.run_report(
            [lead.obs, servers[0].obs, servers[1].obs]
        )
        await px.stop()
        await _teardown(servers)
        return res, counters, rep

    res, counters, rep = asyncio.run(faulty())
    np.testing.assert_array_equal(res.counts, res_ff.counts)
    np.testing.assert_array_equal(res.paths, res_ff.paths)
    assert counters["faults"] >= 1
    assert counters["shards_rerun"] >= 1
    assert counters["breaks"] >= 2  # both servers' planes were broken
    assert rep["pipeline"]["faults"] >= 1
    assert rep["recovery"]["shards_rerun"] >= 1


def test_warmup_verb_compiles_without_touching_state(rng):
    """The per-f_bucket warmup runs the whole kernel chain on throwaway
    sessions: results after warmup are identical to a cold crawl, and
    warmup before add_keys is a loud server error."""
    L, n = 5, 12
    # inside this file's own range (.. BASE_PORT + 699): + 700 is
    # tests/test_rpc.py's BASE_PORT, which runs beside this file under xdist
    base = BASE_PORT + 440
    _, (k0, k1) = _client_keys(rng, L, n)
    res_cold, _ = _crawl(
        _cfg(base, secure_exchange=True), base, k0, k1
    )
    res_warm, lead = _crawl(
        _cfg(base + 20, secure_exchange=True), base + 20, k0, k1,
        warmup=True,
    )
    np.testing.assert_array_equal(res_warm.counts, res_cold.counts)
    np.testing.assert_array_equal(res_warm.paths, res_cold.paths)
    assert lead.obs.timer_seconds("warmup") > 0.0

    async def no_keys():
        cfg = _cfg(base + 40)
        s0, s1 = await _start_servers(cfg, base + 40)
        c0 = await rpc.CollectorClient.connect("127.0.0.1", base + 40)
        await c0.call("reset")
        with pytest.raises(RuntimeError, match="warmup before add_keys"):
            await c0.call("warmup", {"f_buckets": [1, 2]})
        await c0.aclose()
        await _teardown((s0, s1))

    asyncio.run(no_keys())


def test_pipeline_report_section_schema():
    """run_report rolls the pipeline metrics into a top-level section
    with per-level {depth, overlap_seconds, stalls} — and omits the
    section entirely when no pipelined crawl ran."""
    reg = obsmetrics.Registry("leader-test")
    reg.gauge("pipeline_depth", 4, level=3)
    reg.timer_add("pipeline_overlap", 1.5, level=3)
    reg.count("pipeline_stalls", 2, level=3)
    reg.count("pipeline_faults", 1, level=3)
    rep = obsreport.run_report([reg])
    pipe = rep["pipeline"]
    assert pipe["depth"] == 4
    assert pipe["overlap_seconds"] == pytest.approx(1.5)
    assert pipe["stalls"] == 2 and pipe["faults"] == 1
    assert pipe["by_level"]["3"] == {
        "depth": 4, "overlap_seconds": 1.5, "stalls": 2,
    }
    clean = obsmetrics.Registry("leader-clean")
    clean.count("recoveries", 0)
    assert "pipeline" not in obsreport.run_report([clean])


def test_compile_cache_enable(tmp_path, monkeypatch):
    """Cache placement comes from outside (utils/compile_cache.py): with
    ``JAX_COMPILATION_CACHE_DIR`` set the code sets NO directory (JAX's
    own handling of the variable stands); unset, the cache lives at
    ``<checkout>/.jax_cache``.  The first enable wins (idempotent)."""
    import jax

    from fuzzyheavyhitters_tpu.utils import compile_cache

    # snapshot every jax.config knob enable() mutates and restore them
    # after: a cache left pointing elsewhere makes every module that
    # runs after this one recompile cold
    knobs = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    restore = {knob: getattr(jax.config, knob) for knob in knobs}
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        # env set -> the code sets nothing: whatever directory the
        # config held stays (only the thresholds drop)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "held"))
        monkeypatch.setattr(compile_cache, "_enabled", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
        assert compile_cache.enable() == str(tmp_path / "x")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "held")
        assert not (tmp_path / "x").exists()  # JAX creates it, not us
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1

        # env unset -> <checkout>/.jax_cache, the same on every run
        monkeypatch.setattr(compile_cache, "_enabled", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(checkout, ".jax_cache")
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.path.isdir(want)
        # idempotent: the established path wins over a later env change
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "y"))
        assert compile_cache.enable() == want
    finally:
        for knob, val in restore.items():
            jax.config.update(knob, val)
