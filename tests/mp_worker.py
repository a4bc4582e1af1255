"""Multi-process mesh worker (launched by test_mesh_multiprocess.py).

One OS process per mesh ROW: process p joins the distributed runtime,
supplies ONLY party p's key batch (MeshRunner.from_process_local), runs
the full crawl as SPMD host code, and prints the heavy hitters as a JSON
line.  With ``secure`` mode the GC+OT 2PC runs across the two processes'
devices with session material agreed from process 0.

Invoked as:  python tests/mp_worker.py <pid> <nproc> <coordinator> <secure>
(env must carry JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=<devices per process>).
"""

import json
import sys

import numpy as np


def main() -> None:
    pid, nproc, coord, secure = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4] == "1"
    )
    import jax

    # pin the platform before any backend initializes (conftest.py does
    # the same for the main test process)
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=nproc, process_id=pid
    )
    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.parallel import mesh as meshmod
    from fuzzyheavyhitters_tpu.utils import bits as bitutils

    # the same deterministic scenario on both processes; each process KEEPS
    # only its own party's batch (the other party's keys never exist here)
    rng = np.random.default_rng(7)
    L, d, n = 6, 2, 32
    centers = rng.integers(0, 1 << L, size=(3, d))
    pts = centers[rng.integers(0, 3, size=n)] + rng.integers(-1, 2, size=(n, d))
    pts = np.clip(pts, 0, (1 << L) - 1)
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine="np")
    my_keys = k0 if pid == 0 else k1

    mesh = meshmod.make_mesh(devices=jax.devices())
    assert mesh.shape == {"servers": nproc, "data": len(jax.devices()) // nproc}
    runner = meshmod.MeshRunner.from_process_local(
        mesh, my_keys, f_max=128, secure_exchange=secure, min_bucket=8
    )
    res = meshmod.MeshLeader(runner).run(nreqs=n, threshold=0.1)
    out = {
        "pid": pid,
        "hitters": sorted(
            [[int(v) for v in row] + [int(c)]
             for row, c in zip(res.decode_ints(), res.counts)]
        ),
    }
    print("RESULT " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
