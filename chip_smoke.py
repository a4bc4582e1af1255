"""chip_smoke.py — the collector pair, end to end, on one TPU chip.

The quickest proof that the system still starts on the chip.  One process
holds a chip, so the three-process deployment (``bin/server`` x2 +
``bin/leader``) cannot share it; this script drives the SAME classes those
binaries wrap — ``rpc.CollectorServer`` x2, ``rpc.CollectorClient``,
``RpcLeader`` — in one process over real localhost sockets.

Deployment: the ``BASELINE.json`` flagship (1M clients x data_len=512,
zipf(10000, 1.03), ball 2, two servers) CUT TO ONE CHIP — the client count
N is the only cut (16 GB of HBM holds both servers' key planes at N=131072,
not 1M); data_len, n_dims, field, string width S and payload width W are
the flagship's.  ``f_max=256`` as in the shipped ``configs/secure.json``.
``threshold=0.005``: each site's clients spread over the 62 values of the
workload's 8 augmentation bits, so no single leaf holds 1% of the clients
and at the shipped 0.01 the crawl dies out around depth 505 with an empty
set; 0.005 keeps 20 sites alive down the tree (frontier <= 64 of f_max)
and ends with a non-empty set after all 512 levels.

Phases (one JSON line each on stdout, then the contract's final line):

  keygen     fused Pallas keygen, N=131072, keys stay on the device
  trusted    secure_exchange=false, N=131072, all 512 levels, twice —
             the second crawl must add no fresh compile
  secure     secure_exchange=true, ot_path=auto (ot2s at S=2), N=16384,
             all 512 levels through IKNP + 1-of-2^S OT + b2a
  secure_gc  ot_path=gc, N=16384, first 16 levels through the garbled-
             circuit kernels, compared with the secure lane at depth 16

Every heavy-hitter set and count is compared with a plain Python-int count
over the sampled points written here, independent of ``protocol/``.  Any
failed check raises: non-zero exit, no final ``ok`` line.  Without a TPU
the script fails before it runs anything.

``--n-dims 2`` runs ONLY a secure lane at the reference's two-dimensional
shapes (``configs/amazon.json``: data_len 64, ball 8; strings of S = 4 bits,
a 1-of-16 table a test, four child patterns a node), N=16384: the crawl to
its own end (it dies out near depth 59: no box narrower than 2^6 a side
holds 0.5% of the clients), compared with the plain count at depth 16 and
at the depth it ended.  By hand, once a change to what d > 1 runs.

``--chips 4`` runs ONLY the sharded-server comparison: the secure lane
with each server's client axis sharded over its own half of the host's
chips (``server_data_devices=2`` there: server 0 on chips 0-1, server 1 on
chips 2-3, by ``server_mesh.server_devices``, the rule the servers and
the benchmark's four-chip cell place by; the lane line prints the pairs
as ``mesh_devices``) against the same keys on one device.  N is cut to
2048 there (planned: 65536) by the 15-minute cold wall clock: PR 25's four-chip run at N=16384 took 1468 s
for the two lanes — 605 s of backend compiles, which do not shrink with
N, and 862 s of crawling (0.88 and 0.80 s per level on that host), which
is taken to shrink with N x frontier.  That leaves ~110 s of crawling
and ~780 s in all at N=2048.  On four chips the sharded lane at N=2048
read 205 s (141 s in compiles, 0.124 s per level outside them); the
one-device lane has not finished there yet (PERF.md, PR 25).
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import contextlib
import dataclasses
import gc
import itertools
import json
import sys
import time

import numpy as np

import jax

from fuzzyheavyhitters_tpu import native
from fuzzyheavyhitters_tpu.ops import ibdcf, prg
from fuzzyheavyhitters_tpu.protocol import collect, rpc, secure
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.utils import compile_cache
from fuzzyheavyhitters_tpu.utils.config import Config
from fuzzyheavyhitters_tpu.workloads import sample_points

DATA_LEN = 512
N_TRUSTED = 131072
N_SECURE = 16384
N_SHARDED = 2048  # --chips 4; planned N_SHARDED_PLANNED, see the docstring
N_SHARDED_PLANNED = 65536
GC_LEVELS = 16
DATA_LEN_ND = 64  # --n-dims > 1: configs/amazon.json's data_len and ball
BALL_SIZE_ND = 8
NUM_SITES = 10000
ZIPF_EXPONENT = 1.03
BALL_SIZE = 2
THRESHOLD = 0.005
F_MAX = 256
BASE_PORT = 28431


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


# -- the platform and engine checks (a test patches these two, here never) --


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; fails unless it is a TPU (and, for
    ``--chips 4``, exactly four of them)."""
    devs = jax.devices()
    _check(devs[0].platform == "tpu",
           f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    _check(chips == 1 or len(devs) == chips,
           f"--chips {chips} needs {chips} devices, JAX sees {len(devs)}")
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def check_engines(data_len: int) -> dict:
    """Every stage's engine is the chip's, and one expand level really
    lowers to a Mosaic kernel (not an interpret-mode or XLA-twin program)."""
    # the process-wide selectors: best_engine / _expand_engine /
    # _ot2s_pallas_engine / _engine("gc"); what each lane's servers then
    # ran is CollectorServer.engine_tags(), printed with the lane
    tags = rpc.engine_tags()
    _check(tags == dict(platform="tpu", keygen="pallas", expand="pallas",
                        ot2s="pallas", gc="pallas"),
           f"not the chip's engines: {tags}")
    rng = np.random.default_rng(0)
    pts = rng.integers(0, 2, size=(1024, 1, data_len)).astype(bool)
    k0, _ = ibdcf.gen_l_inf_ball(pts, BALL_SIZE, rng, engine="np")
    text = collect._expand_share_bits_jit.lower(
        k0, collect.tree_init(k0, 1), 0, prg.DERIVED_BITS, True, True
    ).as_text()
    _check("tpu_custom_call" in text,
           "an expand level lowered without a tpu_custom_call")
    return tags


# -- the plain reference --------------------------------------------------


def _points_to_ints(pts_bits: np.ndarray) -> list:
    """bool[N, d, L] MSB-first -> Python ints (d = 1) or tuples of d."""
    n, d, _ = pts_bits.shape
    pad = (-pts_bits.shape[2]) % 8
    if pad:
        pts_bits = np.pad(pts_bits, ((0, 0), (0, 0), (pad, 0)))
    packed = np.packbits(pts_bits, axis=2)
    vals = [[int.from_bytes(packed[i, j].tobytes(), "big") for j in range(d)]
            for i in range(n)]
    return [v[0] if d == 1 else tuple(v) for v in vals]


def plain_count(pts_bits, ball: int, depth: int, thresh: int) -> dict:
    """{prefix: count} over every ``depth``-bit prefix (a tuple of one a
    dimension where there are several) that at least ``thresh`` clients'
    saturating balls [p - ball, p + ball] touch — what a crawl must hold
    after ``depth`` levels (counts only shrink down the tree, so the
    crawl's earlier prunes remove nothing this keeps).  Candidates: every
    distinct point's ball; Python ints throughout."""
    d, L = pts_bits.shape[1:]
    top = (1 << L) - 1
    shift = L - depth
    counts = collections.Counter()
    for p, k in collections.Counter(_points_to_ints(pts_bits)).items():
        spans = [range(max(0, v - ball) >> shift, (min(top, v + ball) >> shift) + 1)
                 for v in ((p,) if d == 1 else p)]
        for q in itertools.product(*spans):
            counts[q[0] if d == 1 else q] += k
    return {q: k for q, k in counts.items() if k >= thresh}


def _as_dict(paths: np.ndarray, counts: np.ndarray) -> dict:
    """A crawl's (paths bool[H, d, depth], counts[H]) -> {prefix: count}."""
    vals = _points_to_ints(paths) if paths.shape[0] else []
    out = dict(zip(vals, (int(c) for c in counts)))
    _check(len(out) == len(vals), "a crawl returned a duplicate path")
    return out


# -- one lane: two servers + leader over localhost sockets ----------------


class _TapLeader(RpcLeader):
    """RpcLeader that keeps the frontier (paths, counts) it held after
    ``tap`` bit-levels and, with ``stop``, ends the crawl there (the
    leader has no partial crawl of its own)."""

    class Stop(Exception):
        pass

    tap: int | None = None
    stop: bool = False
    tapped: tuple | None = None

    async def _run_one_level(self, level, nreqs, thresh):
        counts, alive = await super()._run_one_level(level, nreqs, thresh)
        if level + 1 == self.tap and counts is not None:
            self.tapped = (self.paths.copy(), np.array(counts))
            if self.stop:
                raise self.Stop
        return counts, alive


def _hbm_watermark() -> int | None:
    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return None  # XLA:CPU keeps no memory stats
    return max(int(ms["peak_bytes_in_use"]) for ms in stats)


@contextlib.contextmanager
def _timed(rec: dict):
    """Wall seconds of the block into ``rec``, with the fresh compiles
    inside it and the seconds left when their time is taken out."""
    n0 = compile_cache.backend_compiles()
    s0 = compile_cache.backend_compile_seconds()
    t = time.perf_counter()
    yield rec
    rec["seconds"] = time.perf_counter() - t
    spent = compile_cache.backend_compile_seconds() - s0
    rec["fresh_compiles"] = compile_cache.backend_compiles() - n0
    rec["compile_seconds"] = spent
    rec["seconds_excluding_compile"] = rec["seconds"] - spent


def _bytes_in_use() -> list:
    return [
        None if ms is None else int(ms["bytes_in_use"])
        for ms in (d.memory_stats() for d in jax.local_devices())
    ]


async def _lane(cfg: Config, port: int, k0, k1, n: int, *, crawls: int = 1,
                tap: int | None = None, stop: bool = False) -> dict:
    """Bring the pair up (s1 listens on the data plane, then s0 dials —
    bin/server's ordering), upload, crawl ``crawls`` times, tear down.
    Returns the per-crawl results and what the servers held."""
    cfg = dataclasses.replace(
        cfg, server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}"
    )
    s0, s1 = rpc.CollectorServer(0, cfg), rpc.CollectorServer(1, cfg)
    clients = []
    try:
        t1 = asyncio.create_task(
            s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11)
        )
        await asyncio.sleep(0.05)
        t0 = asyncio.create_task(
            s0.start("127.0.0.1", port, "127.0.0.1", port + 11)
        )
        clients.append(await rpc.CollectorClient.connect("127.0.0.1", port))
        clients.append(await rpc.CollectorClient.connect("127.0.0.1", port + 10))
        await asyncio.gather(t0, t1)
        lead = _TapLeader(cfg, *clients)
        lead.tap, lead.stop = tap, stop
        await asyncio.gather(*(c.call("reset") for c in clients))
        t = time.perf_counter()
        await lead.upload_keys(k0, k1)
        out = {"upload_seconds": time.perf_counter() - t, "crawls": [],
               "bytes_in_use_after_ingest": _bytes_in_use()}
        for _ in range(crawls):
            with _timed({"result": None}) as crawl:
                try:
                    crawl["result"] = await lead.run(n)
                except _TapLeader.Stop:
                    pass
            out["crawls"].append(crawl)
        out["tapped"] = lead.tapped
        # the servers place their key planes at the first crawl step, not
        # at upload: only this second sample shows where they live
        out["bytes_in_use_keys_resident"] = _bytes_in_use()
        e0, e1 = s0.engine_tags(), s1.engine_tags()
        same = lambda e: {k: v for k, v in e.items() if k != "mesh_devices"}
        _check(same(e0) == same(e1),
               f"the two servers ran different engines: {e0} {e1}")
        out["engines"] = e0
        out["key_devices"] = [
            sorted(d.id for d in leaf.sharding.device_set)
            for s in (s0, s1) for leaf in s.keys
        ]
        out["mesh_devices"] = [
            None if s._mesh is None else [d.id for d in s._mesh.devices]
            for s in (s0, s1)
        ]
        out["key_plane_bytes"] = sum(
            int(leaf.nbytes) for s in (s0, s1) for leaf in s.keys
        )
        # drop the servers' device state before the next lane's arrives
        await asyncio.gather(*(c.call("reset") for c in clients))
        return out
    finally:
        for c in clients:
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()


def _run_lane(*a, **kw) -> dict:
    out = asyncio.run(_lane(*a, **kw))
    gc.collect()  # the servers' device state goes with them
    return out


def _config(data_len: int, num_sites: int, threshold: float,
            f_max: int, n_dims: int = 1, ball: int = BALL_SIZE) -> Config:
    return Config(
        data_len=data_len, n_dims=n_dims, ball_size=ball,
        addkey_batch_size=1024, num_sites=num_sites, threshold=threshold,
        zipf_exponent=ZIPF_EXPONENT, server0="", server1="",
        distribution="zipf", f_max=f_max, server_data_devices=1,
    )


def _compare(name: str, got: dict, want: dict) -> None:
    _check(got == want,
           f"{name}: {len(got)} hitters disagree with the plain count's "
           f"{len(want)} (in one only: {sorted(set(got) ^ set(want))[:4]}, "
           f"counts differ: {[q for q in got if q in want and got[q] != want[q]][:4]})")


def _keygen(pts, rng, ball: int = BALL_SIZE) -> tuple:
    k0, k1 = ibdcf.gen_l_inf_ball(pts, ball, rng, engine=ibdcf.best_engine())
    jax.block_until_ready((k0, k1))
    return k0, k1


def _check_lane_engines(name: str, lane: dict, data_devices: int) -> dict:
    """What the lane's servers said they ran: the process's expand
    engine, on one device or (once per shard) on a server's mesh."""
    tags = lane["engines"]
    want = rpc.engine_tags()["expand"]
    _check(tags["data_devices"] == data_devices and tags["expand"] == want,
           f"{name}: servers ran {tags}, want expand={want} on "
           f"{data_devices} device(s)")
    return tags


def _crawl_fields(crawl: dict) -> dict:
    return {k: v for k, v in crawl.items() if k != "result"}


def _check_crawl(name: str, crawl: dict, data_len: int, want: dict) -> None:
    res = crawl["result"]
    _check(res.paths.shape[-1] == data_len,
           f"{name} crawl ended at depth {res.paths.shape[-1]} of {data_len}")
    _compare(name, _as_dict(res.paths, res.counts), want)


def _start(chips: int, data_len: int, seed: int, threshold: float,
           f_max: int) -> tuple:
    """What every run does first: the platform check before anything else,
    the compile cache, the engine checks; prints the start line (with the
    process-wide engine selectors).  Returns (device for the final line,
    fields every phase line carries)."""
    device = require_tpu(chips)
    cache_dir = compile_cache.enable()
    _check(cache_dir is not None,
           "the persistent compile cache could not be set up")
    if jax.default_backend() != "cpu":
        prg.CHACHA_UNROLL = True  # as bin/server does on an accelerator
    _emit(phase="start", device=device, seed=seed,
          engines=check_engines(data_len),
          deployment="BASELINE.json flagship (1M clients x data_len=512, "
          "zipf(10000, 1.03), ball 2, two servers) cut to one chip by N only",
          threshold=threshold, f_max=f_max)
    return device, {
        "data_len": data_len,
        "compile_cache_dir": cache_dir,
        "native_reservoir": native.available(),
    }


def run_phases(n_trusted: int, n_secure: int, data_len: int, gc_levels: int,
               *, seed: int = 0, num_sites: int = NUM_SITES,
               threshold: float = THRESHOLD, f_max: int = F_MAX,
               port: int = BASE_PORT) -> dict:
    """The four phases at the given sizes; one JSON line each.  Returns
    the device (for the final line).  Raises on any failed check."""
    device, common = _start(1, data_len, seed, threshold, f_max)
    base = _config(data_len, num_sites, threshold, f_max)
    rng = np.random.default_rng(seed)
    pts = sample_points(base, n_trusted, rng)

    def phase(name, **fields):
        _emit(phase=name, **fields, hbm_watermark_bytes=_hbm_watermark(),
              **common)

    # keygen: keys stay where the engine made them — on the device for
    # the accelerator's engine (the host twin returns NumPy)
    with _timed({}) as gen:
        k0, k1 = _keygen(pts, rng)
    on_device = all(isinstance(x, jax.Array) for x in (*k0, *k1))
    _check(on_device == (ibdcf.best_engine() == "pallas"),
           "the Pallas keygen's keys did not stay on the device")
    phase("keygen", n=n_trusted, **gen, engine=ibdcf.best_engine(),
          keys_on_device=on_device,
          key_bytes_per_server=sum(int(x.nbytes) for x in k0))

    # trusted: all levels, twice; the second crawl compiles nothing.  The
    # leader's copy of the keys leaves the device first (the fetch the
    # upload would make anyway): 16 GB holds the servers' two key planes
    # and frontiers, not a third and fourth plane beside them
    thresh = max(1, int(threshold * n_trusted))
    want = plain_count(pts, BALL_SIZE, data_len, thresh)
    with _timed({}) as fetch:
        k0, k1 = jax.device_get((k0, k1))
    lane = _run_lane(base, port, k0, k1, n_trusted, crawls=2)
    del k0, k1
    first, second = lane["crawls"]
    _check_crawl("trusted", first, data_len, want)
    _check_crawl("trusted (second crawl)", second, data_len, want)
    _check(second["fresh_compiles"] == 0,
           f"second trusted crawl compiled {second['fresh_compiles']} programs")
    _check(all(len(ds) == 1 for ds in lane["key_devices"]),
           f"key planes not on one device each: {lane['key_devices']}")
    phase("trusted", n=n_trusted, levels=data_len, threshold_count=thresh,
          hitters=len(want), key_fetch_seconds=fetch["seconds"],
          upload_seconds=lane["upload_seconds"],
          **_crawl_fields(first), second_crawl=_crawl_fields(second),
          key_plane_bytes=lane["key_plane_bytes"],
          engines=_check_lane_engines("trusted", lane, 1))

    # secure: the first n_secure of the same points, own keys
    pts_s = pts[:n_secure]
    thresh_s = max(1, int(threshold * n_secure))
    want_s = plain_count(pts_s, BALL_SIZE, data_len, thresh_s)
    k0, k1 = _keygen(pts_s, rng)
    sec = dataclasses.replace(base, secure_exchange=True)
    lane = _run_lane(sec, port + 40, k0, k1, n_secure, tap=gc_levels)
    (crawl,) = lane["crawls"]
    _check_crawl("secure", crawl, data_len, want_s)
    _check(lane["tapped"] is not None,
           f"secure lane died before depth {gc_levels}")
    at_gc = _as_dict(*lane["tapped"])
    _compare(f"secure at depth {gc_levels}", at_gc,
             plain_count(pts_s, BALL_SIZE, gc_levels, thresh_s))
    tags = _check_lane_engines("secure", lane, 1)
    _check(tags["ot_path"] == secure.ot_path(2, "auto"),
           f"secure lane took ot_path {tags['ot_path']}")
    phase("secure", n=n_secure, levels=data_len, threshold_count=thresh_s,
          hitters=len(want_s), upload_seconds=lane["upload_seconds"],
          **_crawl_fields(crawl), engines=tags)

    # secure_gc: the same keys through the garbled-circuit path, to the
    # depth the secure lane was tapped at
    lane = _run_lane(dataclasses.replace(sec, ot_path="gc"), port + 80,
                     k0, k1, n_secure, tap=gc_levels, stop=True)
    (crawl,) = lane["crawls"]
    _check(lane["tapped"] is not None,
           f"secure_gc lane died before depth {gc_levels}")
    _compare("secure_gc", _as_dict(*lane["tapped"]), at_gc)
    tags = _check_lane_engines("secure_gc", lane, 1)
    _check(tags["ot_path"] == "gc",
           f"secure_gc lane took ot_path {tags['ot_path']}")
    phase("secure_gc", n=n_secure, levels=gc_levels, threshold_count=thresh_s,
          frontier=len(at_gc), **_crawl_fields(crawl), engines=tags)
    return device


def run_sharded(n: int, data_len: int, *, seed: int = 0,
                data_devices: int = 0, num_sites: int = NUM_SITES,
                threshold: float = THRESHOLD, f_max: int = F_MAX,
                port: int = BASE_PORT + 120) -> dict:
    """``--chips 4``: the secure lane with each server's client axis
    sharded over its own half of the local devices (the two servers on
    disjoint chips; ``secure_kernel_shards`` auto), then the same keys
    on one device; hitters and counts identical, and the key planes
    really spread.  Returns the device (for the final line)."""
    device, common = _start(4, data_len, seed, threshold, f_max)
    n_dev = len(jax.local_devices()) // 2
    if data_devices > 0:  # a rehearsal names its count
        n_dev = min(n_dev, data_devices)
    base = dataclasses.replace(
        _config(data_len, num_sites, threshold, f_max), secure_exchange=True
    )
    rng = np.random.default_rng(seed)
    pts = sample_points(base, n, rng)
    thresh = max(1, int(threshold * n))
    want = plain_count(pts, BALL_SIZE, data_len, thresh)
    k0, k1 = _keygen(pts, rng)
    for name, dd, spread, p in (("sharded", n_dev, n_dev, port),
                                ("one_device", 1, 1, port + 40)):
        lane = _run_lane(
            dataclasses.replace(base, server_data_devices=dd), p, k0, k1, n
        )
        (crawl,) = lane["crawls"]
        _check_crawl(name, crawl, data_len, want)
        _check(all(len(ds) == spread for ds in lane["key_devices"]),
               f"{name}: key planes on devices {lane['key_devices']}, "
               f"want {spread} each")
        if spread > 1:
            m0, m1 = lane["mesh_devices"]
            _check(m0 is not None and m1 is not None
                   and len(set(m0)) == len(set(m1)) == spread
                   and not set(m0) & set(m1),
                   f"ServerMesh.devices: {lane['mesh_devices']}, want two "
                   f"disjoint sets of {spread}")
        _emit(
            phase=name, n=n, n_planned=N_SHARDED_PLANNED, levels=data_len,
            threshold_count=thresh, hitters=len(want),
            server_data_devices=dd, mesh_devices=lane["mesh_devices"],
            key_plane_devices=lane["key_devices"][0],
            bytes_in_use_per_device_after_ingest=lane[
                "bytes_in_use_after_ingest"],
            bytes_in_use_per_device_keys_resident=lane[
                "bytes_in_use_keys_resident"],
            upload_seconds=lane["upload_seconds"],
            **_crawl_fields(crawl), hbm_watermark_bytes=_hbm_watermark(),
            engines=_check_lane_engines(name, lane, spread), **common,
        )
    return device


def run_secure_nd(n: int, data_len: int, n_dims: int, ball: int, tap: int,
                  *, seed: int = 0, num_sites: int = NUM_SITES,
                  threshold: float = THRESHOLD, f_max: int = F_MAX,
                  port: int = BASE_PORT + 200) -> dict:
    """``--n-dims d`` (d > 1): ONLY the secure lane, ``ot_path=auto``, on
    points of ``d`` strings: S = 2d bits a test, 2^d child patterns a
    node.  The crawl runs to its own end and is compared with the plain
    count at depth ``tap`` and at the depth it ended (the whole
    ``data_len``, or where it died out: the plain count there is empty
    too).  Returns the device (for the final line)."""
    device, common = _start(1, data_len, seed, threshold, f_max)
    cfg = dataclasses.replace(
        _config(data_len, num_sites, threshold, f_max, n_dims, ball),
        secure_exchange=True,
    )
    rng = np.random.default_rng(seed)
    pts = sample_points(cfg, n, rng)
    thresh = max(1, int(threshold * n))
    k0, k1 = _keygen(pts, rng, ball)
    lane = _run_lane(cfg, port, k0, k1, n, tap=tap)
    (crawl,) = lane["crawls"]
    res = crawl["result"]
    depth = res.paths.shape[-1]
    _check(lane["tapped"] is not None, f"secure lane died before depth {tap}")
    at_tap = _as_dict(*lane["tapped"])
    _compare(f"secure at depth {tap}", at_tap,
             plain_count(pts, ball, tap, thresh))
    _check(depth == data_len or not res.paths.shape[0],
           f"crawl ended at depth {depth} of {data_len} with hitters")
    _compare(f"secure at depth {depth}", _as_dict(res.paths, res.counts),
             plain_count(pts, ball, depth, thresh))
    tags = _check_lane_engines("secure", lane, 1)
    _check(tags["ot_path"] == secure.ot_path(2 * n_dims, "auto"),
           f"secure lane took ot_path {tags['ot_path']}")
    _emit(phase="secure", n=n, n_dims=n_dims, ball=ball, levels=depth,
          threshold_count=thresh, frontier_at_tap=len(at_tap),
          hitters=int(res.paths.shape[0]),
          upload_seconds=lane["upload_seconds"], **_crawl_fields(crawl),
          key_plane_bytes=lane["key_plane_bytes"], engines=tags,
          hbm_watermark_bytes=_hbm_watermark(), **common)
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--n-dims", type=int, choices=(1, 2, 3), default=1)
    args = p.parse_args(argv)
    if args.n_dims > 1:
        if args.chips != 1:
            p.error("--n-dims runs on one chip")
        device = run_secure_nd(N_SECURE, DATA_LEN_ND, args.n_dims,
                               BALL_SIZE_ND, GC_LEVELS, seed=args.seed)
    elif args.chips == 4:
        device = run_sharded(N_SHARDED, DATA_LEN, seed=args.seed)
    else:
        device = run_phases(N_TRUSTED, N_SECURE, DATA_LEN, GC_LEVELS,
                            seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
