"""Keys go to the chip as they arrive (protocol/keyplanes.py): a bulk
upload that says where its batches go (``n``, ``lo``) is written to its
final rows on the server's device(s) batch by batch, the server keeps no
host copy unless it may have to re-place after a lost chip (a checkpoint
directory), and ``tree_init`` / ``warmup`` / ``tree_restore`` find the
planes where the upload left them.

On the CPU backend and its virtual devices: what is placed, where, how
often and what is let go.  How fast is the chip's to say (PERF.md).
"""

import asyncio
import gc
import weakref

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.protocol import rpc, wire
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 29431  # a range of its own (.. 29631)
L, N, BATCH = 5, 50, 7  # N is no multiple of BATCH; two shards meet at row 25


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    yield


def _cfg(port=1, **kw):
    base = dict(
        data_len=L, n_dims=1, ball_size=1, addkey_batch_size=BATCH,
        num_sites=4, threshold=0.05, zipf_exponent=1.0,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=16, backend="cpu",
    )
    base.update(kw)
    return Config(**base)


def _client_keys(seed, n):
    r = np.random.default_rng(seed)
    sites = r.integers(0, 1 << L, size=4)
    pts = sites[r.integers(0, 4, size=n)]
    bits = ((pts[:, None, None] >> np.arange(L - 1, -1, -1)) & 1) > 0
    return ibdcf.gen_l_inf_ball(bits, 1, r, engine="np")


@pytest.fixture(scope="module")
def keys():
    return _client_keys(5, N)


def _batches(k, n=N, size=BATCH):
    """``(lo, chunk)`` as ``RpcLeader.upload_keys`` cuts them."""
    return [
        (lo, tuple(np.asarray(x)[lo:lo + size] for x in k))
        for lo in range(0, n, size)
    ]


async def _upload(s, cs, batches, n=N):
    for lo, chunk in batches:
        assert await s.add_keys({"keys": chunk, "n": n, "lo": lo}, cs) is True


_ORDERS = {
    "in_order": lambda b: b,
    "reversed": lambda b: b[::-1],
    "shuffled": lambda b: [b[i] for i in np.random.default_rng(3).permutation(len(b))],
}


@pytest.mark.parametrize("order", sorted(_ORDERS))
@pytest.mark.parametrize("devices", [1, 2], ids=["one_device", "two_shards"])
def test_resident_planes_equal_the_concatenate(keys, devices, order):
    """The planes a bulk upload leaves resident are ``np.concatenate``
    of its batches bit for bit, whatever order they arrived in: on one
    device and on a two-chip ``ServerMesh``, with ``n`` no multiple of
    the batch and a batch ([21, 28)) that straddles the shard boundary."""
    k0, _ = keys

    async def run():
        s = rpc.CollectorServer(0, _cfg(server_data_devices=devices))
        cs = s._default()
        await _upload(s, cs, _ORDERS[order](_batches(k0)))
        assert cs.keys is None and not cs.keys_parts  # on their way, not held
        cs.ready_keys("tree_init")
        return cs

    cs = asyncio.run(run())
    for got, want in zip(cs.keys, k0):
        assert got.shape == np.shape(want) and got.dtype == np.asarray(want).dtype
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert len(cs.keys.cw_seed.sharding.device_set) == devices
    if devices == 2:
        assert cs._mesh.shards == 2
        assert [sh.data.shape[0] for sh in cs.keys.cw_seed.addressable_shards] == [25, 25]
    nbytes = sum(np.asarray(x).nbytes for x in k0)
    assert cs.obs.counter_value("keys_placed_bytes") == nbytes
    assert cs.obs.gauge_value("key_plane_bytes") == nbytes
    assert cs.obs.gauge_value("key_host_bytes_held") == 0


_BROKEN = {
    # what arrives -> what tree_init names
    "gap": ([0, 1, 2, 4, 5, 6, 7], r"rows \[21, 28\) never arrived"),
    "tail_gap": ([0, 1, 2, 3, 4, 5, 6], r"rows \[49, 50\) never arrived"),
    "overlap": ([0, 1, 2, 3, 3, 4, 5, 6, 7], r"rows \[21, 28\) arrived twice"),
}


@pytest.mark.parametrize("case", sorted(_BROKEN) + ["beyond_n"])
def test_an_upload_that_is_not_whole_raises_at_tree_init(keys, case):
    """The ``delivery`` guarantee, every uploaded key counted once: a
    gap, an overlap and a row beyond ``n`` each refuse the crawl, by
    name, where the planes would be handed over."""
    k0, _ = keys
    batches = _batches(k0)
    if case == "beyond_n":
        sent = batches[:-1] + [(N - 3, batches[0][1])]  # rows [47, 54) of 50
        match = r"rows \[47, 54\) lie outside \[0, 50\)"
    else:
        which, match = _BROKEN[case]
        sent = [batches[i] for i in which]

    async def run():
        s = rpc.CollectorServer(0, _cfg())
        cs = s._default()
        await _upload(s, cs, sent)
        with pytest.raises(RuntimeError, match=match):
            cs.ready_keys("tree_init")
        assert cs.keys is None

    asyncio.run(run())


def test_batches_with_and_without_a_total_do_not_mix(keys):
    k0, _ = keys
    (lo, chunk), *_ = _batches(k0)

    async def run():
        s = rpc.CollectorServer(0, _cfg())
        cs = s._default()
        await s.add_keys({"keys": chunk, "n": N, "lo": lo}, cs)
        with pytest.raises(RuntimeError, match="without a total"):
            await s.add_keys({"keys": chunk}, cs)
        with pytest.raises(RuntimeError, match="for 51 clients into an upload of 50"):
            await s.add_keys({"keys": chunk, "n": N + 1, "lo": lo}, cs)
        await s.reset({}, cs)
        await s.add_keys({"keys": chunk}, cs)  # the path with no total
        with pytest.raises(RuntimeError, match="after batches without one"):
            await s.add_keys({"keys": chunk, "n": N, "lo": lo}, cs)

    asyncio.run(run())


async def _pair(cfg, port, ckpt_dir=None):
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
    c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
    c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
    return s0, s1, c0, c1, RpcLeader(cfg, c0, c1)


async def _down(*ends):
    for e in ends:
        await e.aclose()


@pytest.mark.parametrize("devices", [1, 2], ids=["one_device", "two_shards"])
def test_a_second_crawl_finds_the_planes_and_reset_frees_them(keys, devices, tmp_path):
    """Through the served path: the first ``tree_init`` after an upload
    waits for the placement (one ``key_place``, no ``concat_keys``); a
    second crawl, a ``warmup`` and a ``tree_restore`` place nothing and
    count the same; ``reset`` lets the planes go; an upload after a
    crawl, for a larger ``n``, starts new planes."""
    port = BASE_PORT + 40 * devices
    cfg = _cfg(port, server_data_devices=devices)
    k0, k1 = keys
    more0, more1 = _client_keys(6, N + 14)

    async def run(ckpt_dir):
        s0, s1, c0, c1, lead = await _pair(cfg, port, ckpt_dir)
        try:
            await lead._both("reset")
            await lead.upload_keys(k0, k1)
            first = await lead.run(N)
            ctr = lambda name: [s.obs.counter_value(name) for s in (s0, s1)]
            placed, reused = ctr("keys_placed_bytes"), ctr("key_planes_reused")
            planes = [s.keys for s in (s0, s1)]
            assert placed[0] == sum(np.asarray(x).nbytes for x in k0)
            second = await lead.run(N)
            await lead.warmup(f_buckets=[1])
            await lead._both("tree_checkpoint", {"level": 0})
            await lead._both("tree_restore", {"level": 0})
            assert ctr("keys_placed_bytes") == placed
            assert ctr("key_planes_reused") == [r + 3 for r in reused]
            assert all(s.keys is p for s, p in zip((s0, s1), planes))
            phases = s0.obs.report()["phases"]
            assert phases["key_place"]["count"] == 1
            assert "concat_keys" not in phases
            # a larger key set after a crawl: new planes, the old ones let go
            await lead.upload_keys(more0, more1)
            assert s0.keys is None and s0.key_planes.n == N + 14
            third = await lead.run(N + 14)
            assert s0.keys.cw_seed.shape[0] == N + 14
            st = await c0.call("status")
            assert st["has_keys"]
            await lead._both("reset")
            assert all(s.keys is None and s.key_planes is None for s in (s0, s1))
            assert not (await c0.call("status"))["has_keys"]
            assert s0._default().idle() is False  # still bound by c0
        finally:
            await _down(c0, c1, s0, s1)
        return first, second, third

    first, second, third = asyncio.run(run(str(tmp_path)))
    assert len(first.paths) > 0
    assert np.array_equal(first.paths, second.paths)
    assert np.array_equal(first.counts, second.counts)
    assert third.counts.sum() != first.counts.sum() or len(third.paths) != len(first.paths)


@pytest.mark.parametrize("held", [False, True], ids=["no_ckpt_dir", "ckpt_dir"])
def test_only_a_session_that_may_re_place_keeps_host_batches(keys, held, tmp_path):
    """A lost chip is re-placed from the host, and only where there is a
    checkpoint to stand on: that session holds its host batches
    (``key_host_bytes_held`` their bytes) and writes them again when the
    planes are gone; any other holds none, and says so."""
    k0, _ = keys

    async def run():
        s = rpc.CollectorServer(
            0, _cfg(server_data_devices=2), ckpt_dir=str(tmp_path) if held else None
        )
        cs = s._default()
        await _upload(s, cs, _batches(k0))
        cs.ready_keys("tree_init")
        return cs

    cs = asyncio.run(run())
    nbytes = sum(np.asarray(x).nbytes for x in k0)
    assert cs.obs.gauge_value("key_host_bytes_held") == (nbytes if held else 0)
    cs.keys = None  # what _mesh_recover does: device-resident, lost with the shard
    if held:
        cs.ready_keys("tree_restore")
        for got, want in zip(cs.keys, k0):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        assert cs.obs.counter_value("keys_placed_bytes") == nbytes  # bytes that ARRIVED
    else:
        with pytest.raises(RuntimeError, match="kept no host copy"):
            cs.ready_keys("tree_restore")


def test_receive_buffers_go_back_once_their_rows_are_placed(monkeypatch):
    """An upload over the socket into a server with no checkpoint
    directory: once the rows are on the device nothing holds a received
    batch, and the frames' slabs are back on ``wire._free_slabs`` (a
    first touch of new memory is what the upload paid for)."""
    port = BASE_PORT + 120
    n, batch = 2048, 512  # cw_seed of a batch: 80 KiB, out of band
    cfg = _cfg(port, addkey_batch_size=batch)
    k0, k1 = _client_keys(9, n)
    monkeypatch.setattr(wire, "_SLAB_MIN", wire.OOB_MIN)
    wire._free_slabs.clear()
    seen = []
    add_keys = rpc.CollectorServer.add_keys

    async def tapped(self, req, cs=None):
        seen.extend(weakref.ref(a) for a in req["keys"])
        return await add_keys(self, req, cs)

    monkeypatch.setattr(rpc.CollectorServer, "add_keys", tapped)

    async def run():
        s0, s1, c0, c1, lead = await _pair(cfg, port)
        try:
            await lead._both("reset")
            await lead.upload_keys(k0, k1)
            # a serve loop holds the frame it read last until the next
            # one arrives: any verb after the upload takes its place
            await lead._both("status")
            for s in (s0, s1):
                s._default().ready_keys("tree_init")
            gc.collect()
            alive = sum(r() is not None for r in seen)
            slabs = len(wire._free_slabs)
            planes = np.asarray(s0.keys.cw_seed)
        finally:
            await _down(c0, c1, s0, s1)
        return alive, slabs, planes

    alive, slabs, planes = asyncio.run(run())
    assert len(seen) == 2 * 5 * (n // batch)
    assert alive == 0
    assert slabs >= 1
    assert np.array_equal(planes, np.asarray(k0.cw_seed))
