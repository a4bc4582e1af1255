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
PORT = 30531  # a range of its own: 21701 lay inside test_resilience's and test_secure_kernels' offsets
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


def test_binaries_end_to_end(tmp_path):
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(CFG))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_backend_optimization_level=1"
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
        for sid in (0, 1):
            srep = json.loads(
                (tmp_path / f"leader_report.s{sid}.json").read_text()
            )
            assert f"server{sid}" in srep["registries"], sorted(
                srep["registries"]
            )
        assert json.loads(report_path.read_text()) == rep  # not clobbered
    finally:
        for p in (s0, s1, lead):
            if p is not None and p.poll() is None:
                p.kill()
    want = _expected_csv(tmp_path)
    assert got == want


def test_mesh_binary_rides_matches_socket_csv(tmp_path):
    """The pod entry point on the flagship rides workload writes the SAME
    heavy-hitter CSV as the socket deployment on identical client points
    (both sample seed-42 synthetic coords via the shared workloads
    sampler)."""
    cfg = dict(CFG)
    del cfg["backend"]  # mesh binary pins its platform via --platform
    cfg_path = tmp_path / "rides_mesh.json"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
        + " --xla_backend_optimization_level=1"
    ).strip()
    out = subprocess.run(
        [sys.executable, "-m", "fuzzyheavyhitters_tpu.bin.mesh",
         "--config", str(cfg_path), "-n", str(N_REQS), "--platform", "cpu",
         "--devices", "4"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    csv_path = tmp_path / "data" / "ride_heavy_hitters.csv"
    assert csv_path.exists(), out.stdout[-2000:]
    assert csv_path.read_text() == _expected_csv(tmp_path)


@pytest.mark.parametrize(
    "argv",
    [
        ["fuzzyheavyhitters_tpu.bin.server", "--server_id", "0"],
        ["fuzzyheavyhitters_tpu.bin.leader", "-n", "4"],
        ["fuzzyheavyhitters_tpu.bin.mesh", "-n", "4"],
    ],
    ids=["server", "leader", "mesh"],
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


def test_mesh_binary_refuses_malicious(tmp_path):
    """malicious mode on the mesh is a DOCUMENTED refusal (one trust
    domain — sketch verification adds nothing there; the socket binaries
    carry the real path)."""
    cfg = dict(CFG, malicious=True)
    cfg_path = tmp_path / "mal.json"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "fuzzyheavyhitters_tpu.bin.mesh",
         "--config", str(cfg_path), "-n", "4", "--platform", "cpu"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert "malicious mode refused" in out.stderr


def test_mesh_binary_smoke(tmp_path):
    """The pod-deployment entry point (bin/mesh.py) runs a zipf collection
    on the virtual 2x4 CPU mesh and prints heavy hitters."""
    cfg = {
        "data_len": 8,
        "n_dims": 1,
        "ball_size": 1,
        "addkey_batch_size": 16,
        "num_sites": 4,
        "threshold": 0.1,
        "zipf_exponent": 1.03,
        "server0": "127.0.0.1:1",
        "server1": "127.0.0.1:2",
        "distribution": "zipf",
        "f_max": 64,
    }
    cfg_path = tmp_path / "mesh.json"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
        + " --xla_backend_optimization_level=1"
    ).strip()
    out = subprocess.run(
        [sys.executable, "-m", "fuzzyheavyhitters_tpu.bin.mesh",
         "--config", str(cfg_path), "-n", "32", "--platform", "cpu"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "crawl.done" in out.stdout + out.stderr  # obs telemetry line
    # NB no hitter-count assertion: the zipf workload appends 8 random
    # augmentation bits per request (leader.rs:331 parity), so leaf-level
    # hitters are luck at smoke scale; hitter correctness is pinned by the
    # driver-oracle tests, this test pins that the BINARY runs end to end
