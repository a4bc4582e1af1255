"""Process-level end-to-end smoke test: the README run shape.

Launches ``bin/server.py`` twice and ``bin/leader.py`` as REAL OS
processes on a rides-distribution config (the flagship i16 lat/lon
workload), then asserts the heavy-hitter CSV the leader wrote matches
the in-process driver oracle on the same deterministic client points.
The binaries are otherwise the one surface no test executes
(ref: README.md:38-60 run shape)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.protocol import driver
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.workloads import rides

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_REQS = 32
# a range of its own (21701 lay inside test_resilience's and
# test_secure_kernels' offsets): lane i of three at PORT + 40 * i, +10 and
# +11 inside it
PORT = 30531
CFG = {
    "data_len": 16,
    "n_dims": 2,
    "ball_size": 2,
    "addkey_batch_size": 16,
    "num_sites": 4,
    "threshold": 0.06,
    "zipf_exponent": 1.03,
    "server0": f"127.0.0.1:{PORT}",
    "server1": f"127.0.0.1:{PORT + 10}",
    "distribution": "rides",
    "f_max": 512,
    "backend": "cpu",
}


def _expected_csv(tmp_path):
    """Oracle: the colocated driver on the same deterministic points
    (tmp cwd has no RideAustin CSV -> the seed-42 synthetic sampler,
    exactly what the leader binary will sample)."""
    coords = rides.load_or_synthesize_locations(
        str(tmp_path / "nonexistent.csv"), N_REQS, seed=42
    )
    pts_bits = np.stack(
        [
            np.stack([bitutils.i16_to_ob_bits(int(v)) for v in row])
            for row in coords
        ]
    )
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, CFG["ball_size"], np.random.default_rng(5), engine="np")
    with jax.default_device(jax.devices("cpu")[0]):
        s0, s1 = driver.make_servers(k0, k1)
        lead = driver.Leader(
            s0, s1, n_dims=2, data_len=16, f_max=CFG["f_max"]
        )
        res = lead.run(nreqs=N_REQS, threshold=CFG["threshold"])
    assert res.paths.shape[0] >= 1  # non-degenerate scenario
    out = tmp_path / "expected.csv"
    rides.save_heavy_hitters(res.paths, str(out))
    return out.read_text()


def _run_binaries(tmp_path, cfg, **leader_env):
    """``bin/server`` x2 + ``bin/leader`` as OS processes on ``cfg``:
    the CSV the leader wrote, with the run reports checked on the way."""
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ)
    env.update(leader_env)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # eight virtual devices a process: a sharded server takes its own
    # ``server_data_devices`` of them (server 1 the second run of them)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
        + " --xla_backend_optimization_level=1"
    ).strip()

    def spawn(mod, *args, log=None):
        # only a process whose pipe is READ while it runs may write to one:
        # the servers log to files (nobody drains them until the leader is
        # done, and a full 64 KB pipe blocks the writer — XLA:CPU prints a
        # line for every persistent-cache load)
        return subprocess.Popen(
            [sys.executable, "-m", mod, "--config", str(cfg_path), *args],
            cwd=tmp_path, env=env,
            stdout=subprocess.PIPE if log is None else open(log, "w"),
            stderr=subprocess.STDOUT, text=True,
        )

    report_path = tmp_path / "leader_report.json"
    env["FHH_RUN_REPORT"] = str(report_path)  # one shared path: the leader
    # keeps it bare, each server claims a .s<id> sibling at startup
    s1 = spawn("fuzzyheavyhitters_tpu.bin.server", "--server_id", "1",
               log=tmp_path / "s1.log")
    s0 = spawn("fuzzyheavyhitters_tpu.bin.server", "--server_id", "0",
               log=tmp_path / "s0.log")
    lead = None
    try:
        lead = spawn("fuzzyheavyhitters_tpu.bin.leader", "-n", str(N_REQS))
        out, _ = lead.communicate(timeout=540)
        assert lead.returncode == 0, f"leader failed:\n{out[-4000:]}"
        assert "crawl.done" in out  # obs-layer telemetry line
        rep = json.loads(report_path.read_text())
        assert rep["schema"] == "fhh-run-report/1"
        assert "level" in rep["registries"]["leader"]["phases"]
        csv_path = tmp_path / "data" / "ride_heavy_hitters.csv"
        assert csv_path.exists(), out[-2000:]
        got = csv_path.read_text()
        # drain the servers: SIGTERM -> SystemExit(143) -> each writes its
        # OWN suffixed report instead of clobbering the leader's
        for p in (s0, s1):
            p.terminate()
        for p in (s0, s1):
            p.communicate(timeout=60)
        sreps = []
        for sid in (0, 1):
            srep = json.loads(
                (tmp_path / f"leader_report.s{sid}.json").read_text()
            )
            assert f"server{sid}" in srep["registries"], sorted(
                srep["registries"]
            )
            sreps.append(srep)
        assert json.loads(report_path.read_text()) == rep  # not clobbered
    finally:
        for p in (s0, s1, lead):
            if p is not None and p.poll() is None:
                p.kill()
    return got, sreps


def _lane_cfg(lane: int, **kw):
    port = PORT + 40 * lane
    return dict(CFG, server0=f"127.0.0.1:{port}",
                server1=f"127.0.0.1:{port + 10}", **kw)


@pytest.mark.parametrize("data_devices", [1, 2])
def test_binaries_end_to_end(tmp_path, data_devices):
    """One device a server, and the co-resident multi-chip shape: each
    server process shards its clients over two of its devices
    (``server_data_devices`` 2) and says so in its own report; the CSV
    is the driver oracle's either way."""
    cfg = _lane_cfg(data_devices - 1, server_data_devices=data_devices)
    got, sreps = _run_binaries(tmp_path, cfg)
    for srep in sreps:
        assert srep.get("mesh", {}).get("data_shards") == (
            None if data_devices == 1 else data_devices
        )
    assert got == _expected_csv(tmp_path)


def test_binaries_malicious_lane_on_sharded_servers(tmp_path):
    """The malicious lane (sketch verification of every client's keys)
    through the sharded pair: honest clients all pass, so the CSV is
    still the oracle's, and each server's report shows the sharded
    verify ran."""
    cfg = _lane_cfg(2, server_data_devices=2, malicious=True)
    # no warm-up ladder: on XLA:CPU it RUNS the sketch chain at every
    # bucket up to f_max (100 s of this case's 117), and what it warms is
    # tests/test_multichip.py::test_warmed_malicious_crawl_zero_fresh_compiles'
    got, sreps = _run_binaries(tmp_path, cfg, FHH_WARMUP="0")
    for srep in sreps:
        assert srep["mesh"]["data_shards"] == 2
        assert srep["sketch"]["sketch_shards"] == 2
        assert srep["sketch"]["verify_seconds"] > 0
    assert got == _expected_csv(tmp_path)


@pytest.mark.parametrize(
    "argv",
    [
        ["fuzzyheavyhitters_tpu.bin.server", "--server_id", "0"],
        ["fuzzyheavyhitters_tpu.bin.leader", "-n", "4"],
    ],
    ids=["server", "leader"],
)
def test_binaries_refuse_a_tpu_config_without_an_accelerator(tmp_path, argv):
    """No hidden XLA:CPU fallback: with ``backend: "tpu"`` (the config
    default) and no accelerator resolved, every binary exits naming the
    ``backend: "cpu"`` opt-out instead of carrying on on the host."""
    cfg = {k: v for k, v in CFG.items() if k != "backend"}
    cfg_path = tmp_path / "tpu.json"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", argv[0], "--config", str(cfg_path), *argv[1:]],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"backend": "cpu"' in out.stderr, out.stderr[-2000:]
