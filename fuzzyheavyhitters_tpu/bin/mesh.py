"""Mesh-deployment binary: the whole collection on one device mesh.

The socket deployment (bin/server.py x2 + bin/leader.py) maps the
reference's two-EC2-host shape; THIS binary is the pod shape — both
parties and all client data parallelism live on a ``jax.sharding.Mesh``
(2 x k: servers x data), the entire per-level 2PC rides ``ppermute`` /
``psum`` collectives, and the host runs only the leader's threshold loop
(parallel/mesh.py).  Single trust domain by construction — see
``init_distributed``'s note; use the socket binaries when the two
parties are separate administrative domains.

All three workload distributions run here (zipf site strings, RideAustin
i16 lat/lon, COVID f64-bit coords) via the same shared sampler as the
leader binary; the rides flow is deterministically sampled (seed 42, as
in the leader) and writes the same heavy-hitter CSV as the socket
deployment on the same config.
``malicious`` mode is a documented refusal: sketch verification needs
Beaver-triple rounds between SEPARATE trust domains, and the mesh is one
trust domain — its threat model already includes both parties, so run
the socket binaries (which implement the full sketch+MPC path) when
malicious clients are in scope.

::

    python -m fuzzyheavyhitters_tpu.bin.mesh --config configs/config.json -n 1000

Multi-host: set ``--processes N --process_id I --coordinator HOST:PORT``
on each host; process i supplies only party i's keys when N == 2
(MeshRunner.from_process_local).
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

from .. import obs
from ..ops import ibdcf
from ..parallel import mesh as meshmod
from ..utils import compile_cache, require_accelerator
from ..utils import config as configmod
from ..workloads import OUTPUT_CSV, rides, sample_points


def main() -> None:
    p = argparse.ArgumentParser(
        prog="Mesh", description="TPU-mesh private fuzzy heavy hitters."
    )
    p.add_argument("-c", "--config", required=True)
    p.add_argument("-n", "--num_requests", type=int, required=True)
    p.add_argument("--coordinator", default=None)
    p.add_argument("--processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    p.add_argument("--devices", type=int, default=None,
                   help="use only the first N devices (default: all)")
    p.add_argument("--platform", default=None,
                   help='pin the JAX platform (e.g. "cpu" for a virtual '
                        "host-device mesh; must be set before backend init)")
    args = p.parse_args()
    cfg = configmod.load_config(args.config)
    if cfg.malicious:
        raise SystemExit(
            "mesh binary: malicious mode refused — the mesh co-locates both "
            "parties in one trust domain, so sketch verification adds no "
            "security there; use the socket binaries (bin/server.py x2 + "
            "bin/leader.py), which run the full sketch+MPC path."
        )

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    else:
        # no explicit pin: a "tpu" config on a host with no accelerator
        # refuses to run (opt out with backend: "cpu" or --platform cpu)
        require_accelerator(cfg.backend)
    # persistent XLA compile cache (utils/compile_cache.py) — after the
    # platform pin so the cache keys against the platform actually used
    compile_cache.enable()
    if args.processes:
        meshmod.init_distributed(
            args.coordinator, args.processes, args.process_id
        )
        if args.processes > 1:
            # both processes inherit the same $FHH_RUN_REPORT and write
            # atomically at exit — the last exiter would clobber the other
            # party's report; give each process its own .p<id> file
            obs.claim_report_path(f"p{args.process_id}")

    # shared exit contract (obs.exit_report): SIGTERM -> SystemExit so a
    # timed-out mesh run still writes its report, with per-level
    # accounting up to the level it died in
    with obs.exit_report():
        _run(cfg, args, jax)


def _run(cfg, args, jax) -> None:
    rng = np.random.default_rng()
    n = args.num_requests
    reg = obs.default_registry()
    obs.emit("sampling", distribution=cfg.distribution, n=n)
    with reg.span("sampling"):
        pts = sample_points(cfg, n, rng)
    with reg.span("keygen"):
        t0 = time.perf_counter()
        k0, k1 = ibdcf.gen_l_inf_ball(
            pts, cfg.ball_size, rng, engine=ibdcf.best_engine(),
        )
    obs.emit(
        "keygen.report", seconds=round(time.perf_counter() - t0, 2), n_keys=n
    )

    mesh = meshmod.make_mesh(args.devices)
    if args.processes == 2:
        my = k0 if jax.process_index() == 0 else k1
        runner = meshmod.MeshRunner.from_process_local(
            mesh, my, cfg.f_max, secure_exchange=cfg.secure_exchange
        )
    else:
        runner = meshmod.MeshRunner(
            mesh, k0, k1, cfg.f_max, secure_exchange=cfg.secure_exchange
        )
    t0 = time.perf_counter()
    res = meshmod.MeshLeader(runner).run(nreqs=n, threshold=cfg.threshold)
    obs.emit("crawl.done", seconds=round(time.perf_counter() - t0, 2))
    for row, c in zip(res.decode_ints(), res.counts):
        obs.emit("hitter", value=str(row.tolist()), count=int(c))
    if cfg.distribution == "rides" and res.paths.shape[0]:
        # identical CSV contract as the socket deployment (bin/leader.py)
        os.makedirs(os.path.dirname(OUTPUT_CSV), exist_ok=True)
        rides.save_heavy_hitters(res.paths, OUTPUT_CSV)
        obs.emit(
            "csv.written", path=OUTPUT_CSV, hitters=int(res.paths.shape[0])
        )


if __name__ == "__main__":
    main()
