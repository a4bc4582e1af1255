"""Multi-chip collector servers: client-axis sharding over each server's
local device mesh (parallel/server_mesh.py + protocol/rpc.py).

Exercised on the 8-device virtual CPU mesh (conftest forces
``--xla_force_host_platform_device_count=8``).  The contract under test:
sharding is a PHYSICAL layout — a sharded server is bit-identical to a
single-device one in every mode (trusted, secure on both equality-test
paths, malicious/sketch), the wire and the leader cannot tell them
apart, and a lost data device is recovered by re-sharding from the
host-side checkpoint (``shards_rerun``), never by a server-loss
recovery (``levels_rerun`` stays zero).
"""

import asyncio
import tempfile

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.obs import report as obsreport
from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.parallel import kernel_shard, server_mesh
from fuzzyheavyhitters_tpu.protocol import rpc, sketch
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.resilience.chaos import (
    MeshChaos,
    parse_mesh_faults,
)
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

# below the kernel's ephemeral source-port range (32768+) INCLUDING the
# +8200 top offset: a leader-side client's ephemeral socket must never
# land on a later test's hard-coded listener port (EADDRINUSE flakes)
BASE_PORT = 23810
# the fault cases further down (drop, delay, an unsharded kill, an
# ingest-only checkpoint) listen in a range of their own, which no other
# file's offsets reach: a crawl at FAULT_PORT + 40 * i, +10 and +11
# inside it (19131 .. 19462)
FAULT_PORT = 19131

L, N_CLIENTS, D = 5, 12, 1


def _cfg(port_base, **kw):
    # f_max=8 keeps the per-bucket program ladder small on XLA:CPU (the
    # sharded variants each compile their own SPMD programs)
    defaults = dict(
        data_len=L,
        n_dims=D,
        ball_size=1,
        addkey_batch_size=12,
        num_sites=4,
        threshold=0.2,
        zipf_exponent=1.03,
        server0=f"127.0.0.1:{port_base}",
        server1=f"127.0.0.1:{port_base + 10}",
        distribution="zipf",
        f_max=8,
    )
    defaults.update(kw)
    return Config(**defaults)


@pytest.fixture(scope="module")
def client_keys():
    rng = np.random.default_rng(77)
    pts = np.concatenate(
        [np.full((N_CLIENTS - 4, D), 11),
         rng.integers(0, 1 << L, size=(4, D))]
    )
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    return pts_bits, ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")


@pytest.fixture(scope="module")
def sketch_keys(client_keys):
    rng = np.random.default_rng(78)
    pts_bits, _ = client_keys
    seeds = rng.integers(
        0, 2**32, size=(N_CLIENTS, D, 2, 4), dtype=np.uint32
    )
    cseed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    return sketch.gen(seeds, pts_bits, FE62, F255, cseed)


async def _crawl(cfg, port, k0, k1, sk0=None, sk1=None, *, warmup=False,
                 chaos=None, ckpt_dir=None, supervised=False,
                 n_clients=N_CLIENTS, before_run=None):
    s0 = rpc.CollectorServer(0, cfg, ckpt_dir=ckpt_dir, _mesh_chaos=chaos)
    s1 = rpc.CollectorServer(1, cfg, ckpt_dir=ckpt_dir)
    t1 = asyncio.create_task(
        s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11)
    )
    await asyncio.sleep(0.05)
    t0 = asyncio.create_task(
        s0.start("127.0.0.1", port, "127.0.0.1", port + 11)
    )
    await asyncio.gather(t0, t1)
    c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
    c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
    lead = RpcLeader(cfg, c0, c1)
    try:
        if supervised:
            res = await lead.run_supervised(
                n_clients, k0, k1, sk0, sk1, checkpoint_every=1,
                warmup=warmup,
            )
        else:
            await lead._both("reset")
            await lead.upload_keys(k0, k1, sk0, sk1)
            if warmup:
                await lead.warmup()
            if before_run is not None:
                await before_run(c0, c1)
            res = await lead.run(n_clients)
        status0 = await c0.call("status")
        report = obsreport.run_report([s0.obs, s1.obs, lead.obs])
    finally:
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()
    return res, status0, report


def _run(cfg, port, k0, k1, **kw):
    return asyncio.run(_crawl(cfg, port, k0, k1, **kw))


def _over_data_axis(fn, x, out_spec):
    """``fn`` on each of the eight shards of ``x``'s leading axis, as one
    program (eagerly, F255's carry chains dispatch op by op: 27 s)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    # fhh-lint: disable=recompile-churn (one program a test case)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P("data"), out_specs=out_spec,
        check_vma=False,
    ))(x)


@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
def test_field_psum_is_exact_where_a_raw_psum_overflows(field):
    """``parallel/mesh.field_psum`` — the one reduction every sharded
    stage ends in: eight shards each holding p - 1 (FE62: 8 (p - 1) is
    past 2^64; F255: every limb sum past 2^32 with 2^256 wraps to fold)
    sum to 8 (p - 1) mod p, like Python's integers."""
    from jax.sharding import PartitionSpec as P

    from fuzzyheavyhitters_tpu.parallel.mesh import field_psum

    vals = [field.P - 1 - i for i in range(8)]
    x = np.stack([np.asarray(field.from_int(v)) for v in vals])
    got = _over_data_axis(
        lambda v: field_psum(field, v[0], "data"), x, P()
    )
    want = np.asarray(field.from_int(sum(vals) % field.P))
    np.testing.assert_array_equal(np.asarray(field.canon(got)), want)


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
def test_psum_exact_keeps_every_bit_through_32_bit_collectives(dtype):
    """``_psum_exact``: 16-bit limbs through u32 all-reduces (the TPU
    lowers no 64-bit one) give the integer sum, to the last bit under
    2^64."""
    from jax.sharding import PartitionSpec as P

    from fuzzyheavyhitters_tpu.parallel.mesh import _psum_exact

    top = int(np.iinfo(dtype).max) if dtype is np.uint32 else (1 << 61) - 1
    vals = [top - 977 * i for i in range(8)]
    x = np.asarray(vals, dtype).reshape(8, 1)
    got = _over_data_axis(lambda v: _psum_exact(v[0], "data"), x, P())
    assert np.asarray(got).dtype == np.uint64
    assert int(np.asarray(got)[0]) == sum(vals)


def test_largest_divisor_shard_binding():
    """Shard counts must tile the client batch: a prime batch degrades
    to one shard, non-divisible requests fall to the largest divisor."""
    f = server_mesh._largest_divisor_leq
    assert f(12, 4) == 4
    assert f(12, 8) == 6
    assert f(13, 8) == 1
    assert f(12, 1) == 1
    m = server_mesh.ServerMesh(4).bind(6)
    assert m.shards == 3 and m.occupancy() == [2, 2, 2]
    m.bind(12)
    assert m.shards == 4 and m.occupancy() == [3, 3, 3, 3]


@pytest.mark.parametrize(
    "mode",
    [
        "trusted",
        "secure_ot2s",
        "secure_gc",
        # ~40 s on one core; sketch sharding parity is also covered
        # by test_sketch_shard — tier-1 keeps the other three modes
        pytest.param("sketch", marks=pytest.mark.slow),
    ],
)
def test_sharded_vs_single_device_bit_identical(mode, client_keys,
                                                sketch_keys):
    """THE multichip acceptance: data_shards ∈ {2, 4} crawls are
    bit-identical to the single-device crawl — trusted, secure on BOTH
    equality-test paths, and malicious (sketch) mode — and the sharded
    servers report mesh health through ``status`` and the run report."""
    _, (k0, k1) = client_keys
    sk0 = sk1 = None
    kw = {}
    if mode == "secure_ot2s":
        kw = dict(secure_exchange=True, ot_path="ot2s")
    elif mode == "secure_gc":
        kw = dict(secure_exchange=True, ot_path="gc")
    elif mode == "sketch":
        sk0, sk1 = sketch_keys
    port = BASE_PORT + 40 * (
        ["trusted", "secure_ot2s", "secure_gc", "sketch"].index(mode)
    )
    base = None
    for i, shards in enumerate((1, 2, 4)):
        cfg = _cfg(port + 1200 * i, server_data_devices=shards, **kw)
        res, status0, report = _run(
            cfg, port + 1200 * i, k0, k1, sk0=sk0, sk1=sk1
        )
        assert res.paths.shape[0] >= 1
        if shards == 1:
            base = res
            assert status0["mesh"] is None
            assert "mesh" not in report
            continue
        # bit-identity: the leader-visible result is byte-for-byte the
        # single-device one (sharding is a physical layout, the 2PC
        # transcript and reconstruction never change)
        np.testing.assert_array_equal(base.paths, res.paths)
        np.testing.assert_array_equal(base.counts, res.counts)
        # mesh health: status names devices/shards/occupancy and the
        # run report rolls the mesh section up
        m = status0["mesh"]
        assert m["data_shards"] == shards
        assert m["shard_clients"] == [N_CLIENTS // shards] * shards
        assert m["ici_reduce_seconds"] > 0
        assert report["mesh"]["data_shards"] == shards
        assert report["mesh"]["ici_reduce_seconds"] > 0
        assert report["mesh"]["reshards"] == 0
        levels = report["mesh"]["by_level"]
        assert set(levels) == {str(lv) for lv in range(L)}


def test_device_loss_reshards_not_restarts(client_keys):
    """Kill one simulated data device mid-level (the ``mesh:kill``
    chaos clause): the server re-shards its
    frontier from the host-side checkpoint IN PLACE and re-runs the
    level's crawl inside the same verb — results bit-identical, the
    recovery section counts a shard re-run and ZERO level re-runs (a
    lost device is not a lost server: no restart, no scratch restart,
    no leader recovery wave)."""
    _, (k0, k1) = client_keys
    port = BASE_PORT + 600
    base, _, _ = _run(
        _cfg(port, server_data_devices=1, secure_exchange=True), port,
        k0, k1,
    )
    chaos = MeshChaos(parse_mesh_faults("mesh:kill@level=3"))
    with tempfile.TemporaryDirectory() as td:
        res, status0, report = _run(
            _cfg(port + 1200, server_data_devices=2, secure_exchange=True),
            port + 1200, k0, k1,
            chaos=chaos, ckpt_dir=td, supervised=True,
        )
    assert chaos.fired == [("kill", 3)]
    np.testing.assert_array_equal(base.paths, res.paths)
    np.testing.assert_array_equal(base.counts, res.counts)
    # the recovery happened at DEVICE granularity: one shard re-run, no
    # completed level re-ran, no supervisor recovery wave fired
    rec = report["recovery"]
    assert rec["shards_rerun"] >= 1
    assert rec["levels_rerun"] == 0
    assert rec["count"] == 0
    assert report["mesh"]["reshards"] == 1
    assert report["mesh"]["faults"] == 1
    assert status0["mesh"]["reshards"] == 1


def test_device_loss_without_checkpoint_escalates(client_keys):
    """A lost device with no checkpoint to re-shard from must surface
    loudly to the leader (supervisor-level recovery owns it), never
    silently crawl on clobbered state."""
    _, (k0, k1) = client_keys
    port = BASE_PORT + 3200
    chaos = MeshChaos(parse_mesh_faults("mesh:kill@level=2"))
    cfg = _cfg(port, server_data_devices=2)
    with pytest.raises(RuntimeError, match="no level-1 checkpoint"):
        _run(cfg, port, k0, k1, chaos=chaos)


_FAULT_MODES = {
    "trusted": dict(),
    "secure_ot2s": dict(secure_exchange=True, ot_path="ot2s"),
    "secure_gc": dict(secure_exchange=True, ot_path="gc"),
}


@pytest.mark.parametrize("mode", list(_FAULT_MODES))
def test_suspect_collective_reruns_in_place(mode, client_keys):
    """``mesh:drop`` — a collective whose result cannot be trusted, the
    device state intact: the sharded server runs the level's crawl again
    inside the same verb and restores NOTHING (no checkpoint directory
    exists to restore from): one fault, one shard re-run, no re-shard,
    no level re-run, and the result is the single-device crawl's."""
    _, (k0, k1) = client_keys
    kw = _FAULT_MODES[mode]
    port = FAULT_PORT + 80 * list(_FAULT_MODES).index(mode)
    base, _, _ = _run(_cfg(port, server_data_devices=1, **kw), port, k0, k1)
    chaos = MeshChaos(parse_mesh_faults("mesh:drop@level=2"))
    res, status0, report = _run(
        _cfg(port + 40, server_data_devices=2, **kw), port + 40, k0, k1,
        chaos=chaos,
    )
    assert chaos.fired == [("drop", 2)]
    np.testing.assert_array_equal(base.paths, res.paths)
    np.testing.assert_array_equal(base.counts, res.counts)
    rec = report["recovery"]
    assert rec["shards_rerun"] == 1
    assert rec["levels_rerun"] == 0 and rec["count"] == 0
    assert report["mesh"]["faults"] == 1
    assert report["mesh"]["reshards"] == 0
    assert status0["mesh"]["reshards"] == 0
    assert report["registries"]["server0"]["counters"].get(
        "checkpoint_restores", 0) == 0


def test_slow_participant_is_not_a_fault(client_keys):
    """``mesh:delay`` — a participant that is late, not lost: the level
    takes the delay and NOTHING recovers (no fault counted, no shard
    re-run), result bit-identical."""
    _, (k0, k1) = client_keys
    port = FAULT_PORT + 240
    base, _, _ = _run(_cfg(port, server_data_devices=1), port, k0, k1)
    chaos = MeshChaos(parse_mesh_faults("mesh:delay@level=1,ms=400"))
    res, _, report = _run(
        _cfg(port + 40, server_data_devices=2), port + 40, k0, k1,
        chaos=chaos,
    )
    assert chaos.fired == [("delay", 1)]
    np.testing.assert_array_equal(base.paths, res.paths)
    np.testing.assert_array_equal(base.counts, res.counts)
    assert report["recovery"]["shards_rerun"] == 0
    assert report["mesh"]["faults"] == 0 and report["mesh"]["reshards"] == 0
    # the stall is where it was put: level 1, as the leader timed it
    level_s = report["registries"]["leader"]["phases"]["level"]["by_level"]
    assert level_s["1"] >= 0.4


def test_device_loss_on_an_unsharded_server_reaches_the_leader(client_keys):
    """In-place recovery belongs to the mesh: a server with ONE device
    has no shard to re-place, so the fault leaves ``tree_crawl`` as the
    verb's error and the leader sees it — it is not swallowed and the
    crawl does not go on over the clobbered frontier."""
    _, (k0, k1) = client_keys
    port = FAULT_PORT + 320
    chaos = MeshChaos(parse_mesh_faults("mesh:kill@level=2"))
    with pytest.raises(RuntimeError, match="tree_crawl.*killed mid-collective"):
        _run(_cfg(port, server_data_devices=1), port, k0, k1, chaos=chaos)
    assert chaos.fired == [("kill", 2)]


def test_device_loss_with_an_ingest_only_checkpoint_escalates(client_keys):
    """The newest checkpoint carries the front door's pools and no crawl
    state (a windowed server between windows): restoring it gives the
    lost device's shard nothing to re-place, and the server says so to
    the leader rather than crawl on from ``None``."""
    _, (k0, k1) = client_keys
    port = FAULT_PORT + 360

    async def pools_then_checkpoint(c0, c1):
        for c, keys in ((c0, k0), (c1, k1)):
            await c.call("submit_keys", {
                "window": 0, "sub_id": "w0-a", "client_id": "site0",
                "keys": tuple(np.asarray(a)[:2] for a in keys),
            })
            await c.call("tree_checkpoint", {"level": 1, "ingest_only": True})

    chaos = MeshChaos(parse_mesh_faults("mesh:kill@level=2"))
    with tempfile.TemporaryDirectory() as td:
        with pytest.raises(RuntimeError, match="level-1 checkpoint is ingest-only"):
            _run(_cfg(port, server_data_devices=2), port, k0, k1,
                 chaos=chaos, ckpt_dir=td, before_run=pools_then_checkpoint)
    assert chaos.fired == [("kill", 2)]


L_K, N_K = 4, 1024  # kernel-sharded e2e shape: the last level's
# bucket-8 rung puts 16384 tests on the planar frame (2 blocks), so
# the deep level runs the ROW-SHARDED kernel stage while the shallow
# ones degrade to the gather path — both layouts in one crawl


@pytest.fixture(scope="module")
def kernel_keys():
    rng = np.random.default_rng(99)
    sites = np.arange(8) * 2  # spread leaves: >= 8 distinct paths
    pts = sites[rng.integers(0, 8, size=N_K)]
    pts_bits = np.array(
        [[bitutils.int_to_bits(L_K, int(v)) for v in row]
         for row in pts[:, None]]
    )
    return ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")


def _kcfg(port, **kw):
    defaults = dict(
        data_len=L_K, n_dims=1, ball_size=1, addkey_batch_size=1024,
        num_sites=8, threshold=0.02, zipf_exponent=1.03,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=16, secure_exchange=True,
    )
    defaults.update(kw)
    return Config(**defaults)


def test_kernel_shard_binding_degrades():
    """A non-dividing planar batch degrades to fewer KERNEL shards
    instead of failing: the active count is the largest divisor of the
    block count that fits the budget, 1 = the gather path."""
    blk = kernel_shard.BLOCK
    assert kernel_shard.kernel_shards(8 * blk, 8) == 8
    assert kernel_shard.kernel_shards(6 * blk, 4) == 3  # 4 ∤ 6 -> 3
    assert kernel_shard.kernel_shards(3 * blk, 2) == 1  # prime-ish -> 1
    assert kernel_shard.kernel_shards(blk - 5, 8) == 1  # one block
    assert kernel_shard.kernel_shards(2 * blk, 0) == 1  # budget floor
    import jax

    devs = tuple(jax.devices()[:4])
    assert kernel_shard.bind(devs, blk, 2, 4) is None  # 1 shard = gather
    ks = kernel_shard.bind(devs, 2 * blk - 7, 2, 4)
    assert ks is not None and ks.k == 2 and ks.bp == 2 * blk


def test_kernel_sharded_crawl_bit_identical_with_device_kill(kernel_keys):
    """THE kernel-stage e2e: a crawl whose deep levels row-shard the
    secure kernels is bit-identical to the single-device crawl, shows
    the degradation ladder in the report (shallow levels gather at
    kernel_shards 1, deep levels shard at >= 2, kernel_gather ~ 0), and
    a device KILL at a kernel-sharded level recovers by in-server
    re-shard — levels_rerun stays ZERO."""
    k0, k1 = kernel_keys
    port = BASE_PORT + 5000
    base, st_b, _ = _run(
        _kcfg(port, server_data_devices=1), port, k0, k1, n_clients=N_K,
    )
    assert st_b["mesh"] is None
    chaos = MeshChaos(parse_mesh_faults("mesh:kill@level=3"))
    with tempfile.TemporaryDirectory() as td:
        res, status0, report = _run(
            _kcfg(port + 1200, server_data_devices=4), port + 1200,
            k0, k1, chaos=chaos, ckpt_dir=td, supervised=True,
            n_clients=N_K,
        )
    assert chaos.fired == [("kill", 3)]
    np.testing.assert_array_equal(base.paths, res.paths)
    np.testing.assert_array_equal(base.counts, res.counts)
    rec = report["recovery"]
    assert rec["shards_rerun"] >= 1
    assert rec["levels_rerun"] == 0
    mesh = report["mesh"]
    by = mesh["by_level"]
    # degradation ladder: level 0 (one node) gathers, the deep levels
    # run the sharded kernel stage
    assert by["0"]["kernel_shards"] == 1
    deep = max(v.get("kernel_shards", 0) for v in by.values())
    assert deep >= 2, f"kernel stage never sharded: {by}"
    assert mesh["kernel_shards"] >= 2  # last level's layout
    # the gather survives only on the shallow one-block levels: the
    # counter names them (the layout detector), and its cumulative
    # dispatch time must be noise, not a per-level stage
    assert mesh["kernel_gathers"] >= 1
    assert mesh["kernel_gather_seconds"] < 1.0
    assert status0["mesh"]["kernel_shards"] >= 2
    assert status0["mesh"]["kernel_shards_max"] >= 2
    assert status0["mesh"]["kernel_gather_seconds"] < 1.0
    sk = report["secure_kernels"]
    assert sk["kernel_shards"] >= 2
    assert sk["otext_seconds"] > 0 and sk["b2a_seconds"] > 0


@pytest.mark.slow  # ~27 s: same warm-ladder contract as the
# multichip/malicious warmed tests that stay in tier-1
def test_warmed_kernel_sharded_crawl_zero_fresh_compiles(kernel_keys):
    """The warmup contract extends to the ROW-SHARDED kernel ladder:
    after one warmed kernel-sharded secure crawl, a second identically-
    shaped warmed crawl triggers ZERO fresh XLA compiles — warmup
    compiles the sharded flat/extension/kernel/open/psum programs (both
    roles, both garbling signs) the live crawl dispatches."""
    from fuzzyheavyhitters_tpu.utils import compile_cache

    k0, k1 = kernel_keys
    port = BASE_PORT + 6000
    kw = dict(server_data_devices=4)
    _run(_kcfg(port, **kw), port, k0, k1, warmup=True, n_clients=N_K)
    before = compile_cache.backend_compiles()
    _, status0, _ = _run(_kcfg(port + 1200, **kw), port + 1200, k0, k1,
                         warmup=True, n_clients=N_K)
    fresh = compile_cache.backend_compiles() - before
    assert status0["mesh"]["kernel_shards"] >= 2  # the ladder engaged
    assert fresh == 0, (
        f"{fresh} fresh compiles in a warmed kernel-sharded crawl"
    )


def test_warmed_malicious_crawl_zero_fresh_compiles(client_keys,
                                                    sketch_keys):
    """The warmup contract extends to the MALICIOUS lane: after one
    warmed malicious (sketch) crawl on the sharded mesh, a second
    identically-shaped warmed crawl triggers ZERO fresh XLA compiles —
    warmup compiles the fused sharded cor/out/verdict chain per bucket
    rung, the level-0 full-width check, and the frontier-advance
    programs the live verify dispatches (rpc._warm_sketch +
    sketch_shard.warm_verify)."""
    from fuzzyheavyhitters_tpu.utils import compile_cache

    _, (k0, k1) = client_keys
    sk0, sk1 = sketch_keys
    port = BASE_PORT + 7000
    kw = dict(server_data_devices=2)
    _run(_cfg(port, **kw), port, k0, k1, sk0=sk0, sk1=sk1, warmup=True)
    before = compile_cache.backend_compiles()
    _, status0, rep = _run(
        _cfg(port + 1200, **kw), port + 1200, k0, k1, sk0=sk0, sk1=sk1,
        warmup=True,
    )
    fresh = compile_cache.backend_compiles() - before
    # the sharded verify engaged (2 data devices -> 2 sketch shards)
    assert status0["mesh"]["sketch_shards"] == 2
    assert rep["sketch"]["sketch_shards"] == 2
    assert rep["sketch"]["verify_seconds"] > 0
    assert fresh == 0, (
        f"{fresh} fresh compiles in a warmed malicious crawl"
    )


def test_warmed_multichip_crawl_zero_fresh_compiles(client_keys):
    """The warmup contract extends to the sharded ladder: after one
    warmed MULTI-CHIP secure crawl, a second identically-shaped warmed
    crawl (fresh servers, fresh sessions) triggers ZERO fresh XLA
    compiles — warmup compiles the sharded expand/reduce/2PC programs
    the live crawl dispatches, wire arrays round-tripped through host
    numpy exactly like the socket path."""
    from fuzzyheavyhitters_tpu.utils import compile_cache

    _, (k0, k1) = client_keys
    port = BASE_PORT + 4000
    kw = dict(server_data_devices=2, secure_exchange=True)
    _run(_cfg(port, **kw), port, k0, k1, warmup=True)
    before = compile_cache.backend_compiles()
    _run(_cfg(port + 1200, **kw), port + 1200, k0, k1, warmup=True)
    fresh = compile_cache.backend_compiles() - before
    assert fresh == 0, f"{fresh} fresh compiles in a warmed multichip crawl"


# -- two sharded servers co-resident on one host (the four-chip cell) -------


def test_server_devices_rule(monkeypatch):
    """THE placement rule: server ``i`` of a co-resident pair takes the
    ``k`` local devices from ``i * k`` where those exist, else the first
    ``k`` (a server alone on its host)."""
    import jax

    local = jax.local_devices()
    ids = lambda devs: [d.id for d in devs]
    assert ids(server_mesh.server_devices(0, 2)) == ids(local[0:2])
    assert ids(server_mesh.server_devices(1, 2)) == ids(local[2:4])
    assert ids(server_mesh.server_devices(1, 4)) == ids(local[4:8])
    assert ids(server_mesh.ServerMesh(2, 1).devices) == ids(local[2:4])
    # server 1 alone on a two-device view: no devices [2, 4) to take
    monkeypatch.setattr(jax, "local_devices", lambda: local[:2])
    assert ids(server_mesh.server_devices(1, 2)) == ids(local[:2])
    assert ids(server_mesh.ServerMesh(2, 1).devices) == ids(local[:2])


def _plain_reference():
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "references", "linf_ball_1d.py",
    )
    spec = importlib.util.spec_from_file_location("linf_ball_1d", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CO_RESIDENT_LANES = ["trusted", "secure", "trusted_planar", "secure_planar"]


@pytest.mark.parametrize("lane", CO_RESIDENT_LANES)
def test_co_resident_sharded_servers_keep_to_their_own_devices(
        lane, client_keys, monkeypatch):
    """Two ``CollectorServer``s with ``server_data_devices=2`` in ONE
    process (the four-chip benchmark cell's layout): server 0 holds
    devices [0, 1], server 1 holds [2, 3]; every array a level produces
    on a server — key planes, frontier, child cache, whatever it
    fetches (packed share bits, counts before the fetch) and the peer's
    bits placed back — lies on that server's own pair; and the whole
    crawl through ``RpcLeader`` equals the plain reference at every
    level, counts included.  The process's default device is a device
    of NEITHER server here, so a default-device placement shows.

    The ``_planar`` cases are the lanes as an accelerator host runs
    them: the plane-major layout with the fused Pallas expand once per
    shard under ``shard_map`` (``ServerMesh.expand_share_bits``) — here
    with the engine selector stood in for and the kernel in interpret
    mode, both in the test.  ``secure_planar`` is what
    ``chip_smoke.py --chips 4`` drives: the planar packed bits handed to
    the sharded 2PC stage (``kernel_shard``)."""
    import functools

    import jax
    from jax.experimental import pallas as pl

    from fuzzyheavyhitters_tpu.protocol import collect, sessions

    pts_bits, (k0, k1) = client_keys
    ref = _plain_reference()
    secure, planar = lane.startswith("secure"), lane.endswith("_planar")
    port = BASE_PORT + 8000 + 40 * CO_RESIDENT_LANES.index(lane)
    cfg = _cfg(port, server_data_devices=2, secure_exchange=secure)
    if planar:
        monkeypatch.setattr(collect, "_expand_engine", lambda: True)
        monkeypatch.setattr(
            pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True)
        )
    own = {"server0": {0, 1}, "server1": {2, 3}}
    seen = {"server0": [], "server1": []}  # (what, device ids)

    def note(reg, what, tree):
        for leaf in jax.tree.leaves(tree):
            if isinstance(leaf, jax.Array):
                seen[reg].append((what, {d.id for d in leaf.devices()}))

    real_fetch = rpc._fetch

    async def fetch(x, reg, level=None, waits=None):
        note(reg.name, "fetched", x)
        return await real_fetch(x, reg, level, waits)

    monkeypatch.setattr(rpc, "_fetch", fetch)
    real_h2d = rpc.CollectorServer._h2d

    def h2d(cs, level, x, spec=None):
        out = real_h2d(cs, level, x, spec)
        note(cs.obs.name, "h2d", out)
        return out

    monkeypatch.setattr(rpc.CollectorServer, "_h2d", staticmethod(h2d))
    real_stash = sessions.CollectionSession.stash_children

    def stash(self, level, shard, children):
        note(self.obs.name, "children", children)
        return real_stash(self, level, shard, children)

    monkeypatch.setattr(sessions.CollectionSession, "stash_children", stash)

    held = []  # (depth, {prefix: count}) after every level

    class TapLeader(RpcLeader):
        servers = ()

        async def _run_one_level(self, level, nreqs, thresh):
            counts, alive = await super()._run_one_level(level, nreqs, thresh)
            for s in self.servers:
                cs = s._default()
                note(s.obs.name, "keys", tuple(cs.keys))
                note(s.obs.name, "frontier", cs.frontier)
            if counts is not None:
                held.append((level + 1,
                             ref.crawl_frontier(self.paths, counts)))
            return counts, alive

    async def go():
        s0, s1 = rpc.CollectorServer(0, cfg), rpc.CollectorServer(1, cfg)
        assert s0.engine_tags()["mesh_devices"] == [0, 1]
        assert s1.engine_tags()["mesh_devices"] == [2, 3]
        assert s0.engine_tags()["expand"] == ("pallas" if planar else "xla")
        assert s0._default().planar() == planar
        t1 = asyncio.create_task(
            s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11))
        await asyncio.sleep(0.05)
        t0 = asyncio.create_task(
            s0.start("127.0.0.1", port, "127.0.0.1", port + 11))
        await asyncio.gather(t0, t1)
        c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
        c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
        lead = TapLeader(cfg, c0, c1)
        lead.servers = (s0, s1)
        try:
            await lead._both("reset")
            await lead.upload_keys(k0, k1)
            await lead.warmup()
            res = await lead.run(N_CLIENTS)
            meshes = [[d.id for d in s._mesh.devices] for s in (s0, s1)]
        finally:
            for c in (c0, c1):
                await c.aclose()
            for s in (s0, s1):
                await s.aclose()
        return res, meshes

    with jax.default_device(jax.devices()[7]):
        res, meshes = asyncio.run(go())
    assert meshes == [[0, 1], [2, 3]]
    for reg, notes in seen.items():
        kinds = {what for what, _ in notes}
        assert {"keys", "frontier", "children", "fetched"} <= kinds, kinds
        if not secure:
            assert "h2d" in kinds
        stray = [(what, ids) for what, ids in notes if not ids <= own[reg]]
        assert not stray, f"{reg} placed arrays off its own devices: {stray[:6]}"
    # every level against the plain reference, counts included
    thresh = max(1, int(cfg.threshold * N_CLIENTS))
    want = ref.frontiers(pts_bits, 1, thresh, L)
    assert [depth for depth, _ in held] == list(range(1, L + 1))
    for depth, got in held:
        assert got == want[depth], (depth, got, want[depth])
    assert res.paths.shape[0] == len(want[L])


def test_a_server_off_the_first_chip_gives_the_persistent_cache_up(monkeypatch):
    """Read on the chip in PR 30: a multi-chip program on chips [2, 3]
    runs when compiled in the process and halts the TPU when its
    executable comes back from the persistent cache.  So a process that
    takes on a sharded ``CollectorServer`` whose TPU devices do not start
    at the first local chip suspends the cache (``compile_cache.suspend``:
    reads and writes both; everything compiles fresh, as in the run that
    works) — decided where the process is composed, from the devices
    alone; never by a mesh, for a one-device server or on a CPU host."""
    import jax

    from fuzzyheavyhitters_tpu.utils import compile_cache

    class Chip:
        def __init__(self, i, platform="tpu"):
            self.id, self.platform = i, platform

    chips = [Chip(i) for i in range(4)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    ok = server_mesh.survives_cache
    assert ok(tuple(chips[0:2])) and ok(tuple(chips[0:4]))
    assert ok((chips[3],))                       # one-chip programs load fine
    assert not ok(tuple(chips[2:4])) and not ok(tuple(chips[1:3]))
    cpus = [Chip(i, "cpu") for i in range(4)]
    monkeypatch.setattr(jax, "local_devices", lambda: cpus)
    assert ok(tuple(cpus[2:4]))
    monkeypatch.undo()                           # the real (CPU) devices again
    said = []
    monkeypatch.setattr(compile_cache, "suspend", said.append)
    sharded = _cfg(BASE_PORT, server_data_devices=2)
    rpc.CollectorServer(1, sharded)              # virtual CPU devices [2, 3]
    assert said == []
    monkeypatch.setattr(server_mesh, "survives_cache", lambda devs: False)
    server_mesh.ServerMesh(2, 1)                 # a mesh decides nothing
    rpc.CollectorServer(1, _cfg(BASE_PORT))      # nor does a one-device server
    assert said == []
    rpc.CollectorServer(1, sharded)
    assert len(said) == 1 and "[2, 3]" in said[0]


def test_suspending_the_persistent_cache(tmp_path):
    """``compile_cache.suspend``: from there on the process neither
    reads nor writes the persistent cache (a fresh program leaves no
    entry behind), once, with one warning; ``enable()`` keeps its
    answer.  The test puts the cache back as it found it."""
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from fuzzyheavyhitters_tpu.utils import compile_cache

    was_dir = compile_cache.enable()
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    try:
        # fhh-lint: disable=recompile-churn (the fresh program IS the test: it must reach the cache)
        jax.jit(lambda x: x * 3 + 11)(jnp.arange(7)).block_until_ready()
        written = set(os.listdir(tmp_path))
        assert written  # the cache was live
        compile_cache.suspend("a test")
        compile_cache.suspend("again")  # idempotent
        assert compile_cache._suspended == "a test"
        assert not jax.config.jax_enable_compilation_cache
        # fhh-lint: disable=recompile-churn (a fresh program again: it must NOT reach the cache)
        jax.jit(lambda x: x * 5 + 13)(jnp.arange(9)).block_until_ready()
        assert set(os.listdir(tmp_path)) == written
        assert compile_cache.enable() == was_dir
    finally:
        compile_cache._suspended = None
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was_min)
        jax.config.update("jax_compilation_cache_dir", was_dir)
        compilation_cache.reset_cache()
