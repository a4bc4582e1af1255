"""Leader binary: client simulation + protocol driver (ref: src/bin/leader.rs).

::

    python -m fuzzyheavyhitters_tpu.bin.leader --config configs/config.json -n 1000

Flow (leader.rs:300-440): keygen throughput report, distribution-specific
client sampling (zipf site strings with 8-bit augmentation, RideAustin
coordinates, or COVID-geo), batched key upload, level loop, heavy-hitter CSV.

Telemetry rides the obs layer (fuzzyheavyhitters_tpu/obs): structured log
events instead of prints (JSON-lines via ``FHH_LOG_FORMAT=json``), a
heartbeat thread naming the active phase/level, and — when
``FHH_RUN_REPORT`` is set — an end-of-run machine-readable report with
per-level phase seconds and data-plane accounting.
"""

from __future__ import annotations

import asyncio
import os
import time

import jax
import numpy as np

from .. import obs
from ..ops import ibdcf
from ..protocol.leader_rpc import RpcLeader
from ..protocol.rpc import CollectorClient
from ..utils import compile_cache, require_accelerator
from ..utils import config as configmod
from ..workloads import OUTPUT_CSV, rides, sample_points, strings


def _split(addr: str) -> tuple[str, int]:
    host, port = addr.rsplit(":", 1)
    return host, int(port)


async def _emit_window(res, window: int) -> None:
    """Per-window heavy-hitter lines for the streaming (FHH_WINDOWS)
    mode — each tumbling window reports its own hitter set."""
    for row, c in zip(res.decode_ints(), res.counts):
        obs.emit(
            "hitter", window=window, value=str(row.tolist()), count=int(c)
        )


def keygen_report(cfg, rng, engine: str) -> None:
    """Key-size / keys-per-second report (ref: leader.rs:90-104, 319-329).

    Runs on the fast engine for the backend (ibdcf.best_engine) with one
    untimed warmup call so the report measures throughput, not the one-off
    XLA compile."""
    n = min(cfg.num_sites, 1000)
    pts = np.stack(
        [strings.generate_random_bit_vectors(rng, cfg.data_len, cfg.n_dims) for _ in range(n)]
    )
    if engine != "np":  # numpy has no compile step to warm
        k0, _ = ibdcf.gen_l_inf_ball(pts, 1, rng, engine=engine)
        jax.block_until_ready(k0)
    t0 = time.perf_counter()
    k0, _ = ibdcf.gen_l_inf_ball(pts, 1, rng, engine=engine)
    jax.block_until_ready(k0)
    dt = time.perf_counter() - t0
    per_client = sum(np.asarray(x)[0].nbytes for x in k0)
    obs.emit(
        "keygen.report",
        engine=engine,
        key_bytes=per_client,
        n_keys=n,
        seconds=round(dt, 3),
        sec_per_key=round(dt / n, 6),
    )


async def amain() -> None:
    import contextlib

    cfg, _, nreqs = configmod.get_args("Leader", get_n_reqs=True)
    # persistent XLA compile cache (utils/compile_cache.py): repeat runs
    # skip the per-bucket program compiles entirely
    compile_cache.enable()
    rng = np.random.default_rng()

    # backend knob, like bin/server.py: "cpu" pins every uncommitted array
    # op (keygen here) onto the host backend; "tpu" with no accelerator
    # resolved refuses to run
    require_accelerator(cfg.backend)
    ctx = (
        jax.default_device(jax.devices("cpu")[0])
        if cfg.backend == "cpu"
        else contextlib.nullcontext()
    )
    with ctx:
        await _run(cfg, nreqs, rng)


async def _run(cfg, nreqs: int, rng) -> None:
    # fast keygen engine for the backend (amain's default_device(cpu)
    # context is visible to best_engine via utils.effective_platform)
    engine = ibdcf.best_engine()
    reg = obs.default_registry()
    keygen_report(cfg, rng, engine)

    # each setup stage gets its own phase so the run report's keygen
    # seconds mean keygen, not keygen+sampling+sketch
    obs.emit("sampling", distribution=cfg.distribution, n=nreqs)
    with reg.span("sampling"):
        pts = sample_points(cfg, nreqs, rng)
    with reg.span("keygen"):
        k0, k1 = ibdcf.gen_l_inf_ball(pts, cfg.ball_size, rng, engine=engine)

    sk0 = sk1 = None
    if cfg.malicious:
        # malicious-security material: per-dimension MAC'd payload DPFs
        # over the client's point + Beaver triples (protocol/sketch.py;
        # ref north star names the resurrected sketch.rs path).  Works
        # for the flagship fuzzy multi-dim workloads: one DPF per dim
        # sharing the client's MAC key, verified per dim.
        from ..ops.fields import F255, FE62
        from ..protocol import sketch as sketchmod

        seeds = rng.integers(
            0, 2**32, size=(nreqs, cfg.n_dims, 2, 4), dtype=np.uint32
        )
        cseed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
        sk0, sk1 = sketchmod.gen(seeds, pts, FE62, F255, cseed)

    h0, p0 = _split(cfg.server0)
    h1, p1 = _split(cfg.server1)
    # multi-tenant collection sessions: FHH_COLLECTION names the
    # server-side session this leader's crawl runs in (protocol/
    # sessions.py) — N leaders with distinct collections share one
    # server pair concurrently; unset = the default session
    collection = os.environ.get("FHH_COLLECTION") or None
    c0 = await CollectorClient.connect(h0, p0, collection=collection)
    c1 = await CollectorClient.connect(h1, p1, collection=collection)

    lead = RpcLeader(cfg, c0, c1)
    # per-f_bucket compile warmup (FHH_WARMUP=0 opts out): bucket
    # recompiles run now — and land in the persistent compile cache —
    # instead of billing into the crawl itself.  Needs the key shapes on
    # the servers, so it rides after the upload in both paths below.
    warm = os.environ.get("FHH_WARMUP", "1") != "0"

    async def _maybe_warm():
        if not warm:
            return
        t_w = time.perf_counter()
        info = await lead.warmup()
        obs.emit(
            "warmup.done",
            seconds=round(time.perf_counter() - t_w, 2),
            f_buckets=info["f_buckets"],
        )

    # streaming-ingest mode (FHH_WINDOWS=N, N > 1): instead of one bulk
    # upload, the fabricated clients submit their key shares continuously
    # through the admission-controlled front door (submit_keys) in N
    # tumbling windows; each window seals at its boundary and its frozen
    # snapshot is crawled while the next window keeps ingesting.  The
    # production shape on the ROADMAP, driven here from one process.
    windows = max(1, int(os.environ.get("FHH_WINDOWS", "1")))
    if windows > 1:
        # malicious mode streams too: the clients' sketch material rides
        # each submission and every sealed window commits its own
        # challenge root (protocol/rpc.py window_seal)
        import jax as _jax

        from ..protocol.leader_rpc import WindowedIngest

        sk0_leaves = None if sk0 is None else _jax.tree.leaves(sk0)
        sk1_leaves = None if sk1 is None else _jax.tree.leaves(sk1)
        t0 = time.perf_counter()
        await asyncio.gather(c0.call("reset"), c1.call("reset"))
        wi = WindowedIngest(lead)
        per_w = (nreqs + windows - 1) // windows
        bs = max(1, cfg.addkey_batch_size)
        crawl_task = None
        for w in range(windows):
            lo_w, hi_w = w * per_w, min((w + 1) * per_w, nreqs)
            for chunk_no, lo in enumerate(range(lo_w, hi_w, bs)):
                sl = slice(lo, min(lo + bs, hi_w))
                await wi.submit(
                    f"site{chunk_no % max(1, cfg.num_sites)}",
                    tuple(np.asarray(x)[sl] for x in k0),
                    tuple(np.asarray(x)[sl] for x in k1),
                    sk0_chunk=(
                        None if sk0_leaves is None
                        else [np.asarray(x)[sl] for x in sk0_leaves]
                    ),
                    sk1_chunk=(
                        None if sk1_leaves is None
                        else [np.asarray(x)[sl] for x in sk1_leaves]
                    ),
                )
            stats = await wi.seal_window()
            if crawl_task is not None:
                await _emit_window(await crawl_task, w - 1)
            if stats["keys"]:
                crawl_task = asyncio.create_task(wi.crawl_window(w))
            else:
                crawl_task = None
        if crawl_task is not None:
            await _emit_window(await crawl_task, windows - 1)
        obs.emit("crawl.done", seconds=round(time.perf_counter() - t0, 2))
        return

    # supervised crawl (FHH_SUPERVISE=0 opts out), malicious mode
    # included — the per-level challenge ratchet makes sketch crawls
    # restartable (see protocol/sketch.py): the leader checkpoints every
    # FHH_CKPT_EVERY levels and, on any transport loss or server restart,
    # restores both servers and re-runs only the lost levels
    supervise = os.environ.get("FHH_SUPERVISE", "1") != "0"
    t0 = time.perf_counter()
    if supervise:
        res = await lead.run_supervised(
            nreqs, k0, k1, sk0, sk1,
            checkpoint_every=int(os.environ.get("FHH_CKPT_EVERY", "16")),
            warmup=warm,
        )
    else:
        await asyncio.gather(c0.call("reset"), c1.call("reset"))
        await lead.upload_keys(k0, k1, sk0, sk1)
        obs.emit("addkeys.done", seconds=round(time.perf_counter() - t0, 2))
        await _maybe_warm()
        t0 = time.perf_counter()
        res = await lead.run(nreqs)
    obs.emit("crawl.done", seconds=round(time.perf_counter() - t0, 2))

    for row, c in zip(res.decode_ints(), res.counts):
        obs.emit("hitter", value=str(row.tolist()), count=int(c))
    if cfg.distribution == "rides" and res.paths.shape[0]:
        os.makedirs(os.path.dirname(OUTPUT_CSV), exist_ok=True)
        rides.save_heavy_hitters(res.paths, OUTPUT_CSV)
        obs.emit("csv.written", path=OUTPUT_CSV, hitters=int(res.paths.shape[0]))


def main() -> None:
    # /metrics exporter claims its port FIRST (before amain's arg
    # validation) so even a leader that dies on a config error was
    # scrapeable; bind failure degrades with a structured warn
    # (obs.exporter — zero-cost when FHH_METRICS_PORT is unset)
    obs.exporter.maybe_start("leader")
    # fresh-compile telemetry: compiles attribute to the active phase
    obs.devmem.install_compile_listener()
    # shared exit contract (obs.exit_report): SIGTERM -> SystemExit so the
    # run report is still written — a timed-out run leaves per-level
    # phase/byte accounting up to the level it died in (plus the
    # heartbeat trail naming it).  The leader keeps the bare
    # $FHH_RUN_REPORT path; the servers claim .s0/.s1 siblings.
    # Likewise for the distributed-trace ring (FHH_TRACE_DIR): the
    # leader's segment is named for it, and exit_report flushes it.
    obs.trace.claim_tag("leader")
    with obs.exit_report():
        asyncio.run(amain())


if __name__ == "__main__":
    main()
