"""Collector server binary (ref: src/bin/server.rs).

Run one per party::

    python -m fuzzyheavyhitters_tpu.bin.server --config configs/config.json --server_id 0
    python -m fuzzyheavyhitters_tpu.bin.server --config configs/config.json --server_id 1

Startup order mirrors the reference (server.rs:344-354): the data-plane
socket between the two servers is established BEFORE the leader-facing RPC
listener binds, server1 listening / server0 dialing with retries.

Fault tolerance: set ``FHH_CKPT_DIR`` to a writable directory to enable
the ``tree_checkpoint``/``tree_restore`` verbs — a supervised leader
(``FHH_SUPERVISE``, bin/leader.py) then rolls a faulted crawl back to the
last checkpoint instead of restarting it; without the dir the server
still reconnect-dedups replayed verbs, and recovery degrades to
restart-from-scratch.
"""

from __future__ import annotations

import asyncio
import json
import os
import time

from .. import obs
from ..protocol.rpc import CollectorServer
from ..utils import compile_cache, require_accelerator
from ..utils import config as configmod


def _split(addr: str) -> tuple[str, int]:
    host, port = addr.rsplit(":", 1)
    return host, int(port)


def _fleet_register(server, server_id: int, host: str, port: int) -> None:
    """Drop this server half's registration row into the shared fleet
    directory (``FHH_FLEET`` names the dir; ``FHH_FLEET_PAIR`` names the
    host pair, default ``pair0``).  ``FleetDirectory.scan`` folds the two
    ``<pair>_s<id>.json`` halves into one :class:`HostPair` row; the boot
    id is what the supervisor's liveness probe compares against, so a
    restarted process re-registers as a NEW boot.  Atomic tmp+rename: a
    scan never reads a torn row."""
    fleet_dir = os.environ.get("FHH_FLEET")
    if not fleet_dir:
        return
    os.makedirs(fleet_dir, exist_ok=True)
    pair = os.environ.get("FHH_FLEET_PAIR") or "pair0"
    row = {
        "pair": pair,
        "server_id": server_id,
        "host": host,
        "port": port,
        "boot_id": server._boot_id,
        "capacity": int(os.environ.get("FHH_FLEET_CAPACITY", "4")),
        "ts": round(time.time(), 3),
    }
    path = os.path.join(fleet_dir, f"{pair}_s{server_id}.json")
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(row, f)
    os.replace(tmp, path)
    obs.emit("fleet.registered", pair=pair, server=server_id,
             boot_id=server._boot_id)


async def amain(cfg, server_id: int) -> None:
    import contextlib

    import jax

    host0, port0 = _split(cfg.server0)
    host1, port1 = _split(cfg.server1)
    my_host, my_port = (host0, port0) if server_id == 0 else (host1, port1)
    # data plane rides on server1's port + 1 (ref: server.rs:41, 208-233)
    peer_host = host1 if server_id == 0 else my_host
    peer_port = port1 + 1

    # cfg.backend selects the aggregation device: "cpu" pins every
    # uncommitted array op onto the host backend (the opt-out where no
    # accelerator is attached); "tpu" (default) keeps JAX's default device,
    # REFUSES to start when no accelerator resolved (no silent XLA:CPU
    # fallback), and switches the PRG to its unrolled round loop (faster
    # chip execution; on XLA:CPU it only inflates compiles — ops/prg.py).
    require_accelerator(cfg.backend)
    if cfg.backend != "cpu":
        from ..ops import prg

        prg.CHACHA_UNROLL = True
    ctx = (
        jax.default_device(jax.devices("cpu")[0])
        if cfg.backend == "cpu"
        else contextlib.nullcontext()
    )
    with ctx:
        ckpt_dir = os.environ.get("FHH_CKPT_DIR") or None
        if ckpt_dir is not None:
            os.makedirs(ckpt_dir, exist_ok=True)
        # multi-chip client sharding: FHH_DATA_DEVICES overrides the
        # config knob (0 = auto: all local devices on an accelerator
        # host), and FHH_MESH_FAULTS arms the consumed-once device-loss
        # schedule (resilience.chaos mesh grammar: mesh:kill@level=N ...)
        # for recovery drills against a live server
        dd = os.environ.get("FHH_DATA_DEVICES")
        if dd is not None:
            cfg.server_data_devices = int(dd)
        mesh_chaos = None
        faults = os.environ.get("FHH_MESH_FAULTS")
        if faults:
            from ..resilience.chaos import MeshChaos, parse_mesh_faults

            mesh_chaos = MeshChaos(parse_mesh_faults(faults))
        server = CollectorServer(
            server_id, cfg, ckpt_dir=ckpt_dir, _mesh_chaos=mesh_chaos
        )
        srv = await server.start(my_host, my_port, peer_host, peer_port)
        # fleet directory registration (protocol/fleet.py): after start so
        # the row only ever advertises a pair that is actually listening
        _fleet_register(server, server_id, my_host, my_port)
        obs.emit("server.serving", server=server_id, host=my_host, port=my_port)
        async with srv:
            await srv.serve_forever()


def _peek_server_id(argv: list[str]) -> int | None:
    """Cheap pre-parse of ``--server_id`` so the /metrics port claim can
    happen FIRST (see main); full validation still belongs to get_args."""
    for i, a in enumerate(argv):
        if a == "--server_id" and i + 1 < len(argv):
            try:
                return int(argv[i + 1])
            except ValueError:
                return None
        if a.startswith("--server_id="):
            try:
                return int(a.split("=", 1)[1])
            except ValueError:
                return None
    return None


def main() -> None:
    import sys

    # /metrics exporter claims its port FIRST — before arg validation —
    # so a server that dies on a config error is still scrapeable for
    # the seconds it lives, and a port conflict surfaces immediately at
    # startup rather than after an expensive setup.  Bind failure is a
    # structured warn, never a crash (the PR 1 report-path discipline);
    # FHH_METRICS_PORT unset costs one getenv.
    sid = _peek_server_id(sys.argv[1:])
    obs.exporter.maybe_start(f"s{sid}" if sid in (0, 1) else "server")
    # arg validation runs BEFORE the exit-report contract: a server that
    # dies here has no identity yet, and writing the run report to the
    # bare shared $FHH_RUN_REPORT path would clobber the leader's
    cfg, server_id, _ = configmod.get_args("Server", get_server_id=True)
    if server_id not in (0, 1):
        raise SystemExit(f"server_id must be 0 or 1, got {server_id}")
    # persistent XLA compile cache (utils/compile_cache.py): a restarted
    # server re-reads its crawl programs instead of recompiling them —
    # recovery cost stays network + restore, not compile churn
    compile_cache.enable()
    # fresh-compile telemetry (obs.devmem): every backend compile counts
    # under the active phase; past the warmup ladder it is alert fodder
    obs.devmem.install_compile_listener()
    # both servers + the leader inherit ONE $FHH_RUN_REPORT from the shared
    # environment; the leader keeps the bare path, each server claims a
    # .s<id> sibling so the last exiter can't clobber the others' reports
    obs.claim_report_path(f"s{server_id}")
    # ... and names its distributed-trace ring segment the same way
    # (FHH_TRACE_DIR; `python -m fuzzyheavyhitters_tpu.obs.trace merge`
    # folds all three processes' rings into one Perfetto timeline)
    obs.trace.claim_tag(f"s{server_id}")
    # shared exit contract (obs.exit_report): SIGTERM -> SystemExit, so a
    # drained/killed server still leaves its run report (phase seconds,
    # data-plane bytes, fetch counts) + a heartbeat trail for the postmortem
    with obs.exit_report():
        asyncio.run(amain(cfg, server_id))


if __name__ == "__main__":
    main()
