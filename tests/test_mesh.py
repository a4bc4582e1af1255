"""Multi-chip sharding tests on the virtual 8-device CPU mesh (conftest):
the sharded 2×4 (servers × data) protocol must produce byte-identical heavy
hitters to the in-process colocated driver.

Everything in this file — including the colocated reference driver — runs
on the CPU backend; the driver is only the parity oracle for the mesh here
(its own behaviour is covered by tests/test_protocol.py)."""

import jax
import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.parallel import mesh as meshmod
from fuzzyheavyhitters_tpu.protocol import driver
from fuzzyheavyhitters_tpu.utils import bits as bitutils


@pytest.fixture(scope="module")
def client_batch():
    rng = np.random.default_rng(7)
    L, d, n = 6, 2, 32
    centers = rng.integers(0, 1 << L, size=(3, d))
    pts = centers[rng.integers(0, 3, size=n)] + rng.integers(-1, 2, size=(n, d))
    pts = np.clip(pts, 0, (1 << L) - 1)
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    # host-side keygen: the jax engine's lax.scan compiles slowly on XLA:CPU,
    # and these tests exercise the mesh crawl, not keygen — gen_pair_np is
    # bit-identical (pinned by test_ibdcf.py::test_gen_pair_np_matches_gen_pair)
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine="np")
    return pts, k0, k1, L, d, n


def _as_dict(res):
    return {
        tuple(int(v) for v in row): int(c)
        for row, c in zip(res.decode_ints(), res.counts)
    }


@pytest.fixture(scope="module")
def colocated_result(client_batch, cpu_devices):
    """Reference counts from the in-process driver, computed on CPU."""
    pts, k0, k1, L, d, n = client_batch
    with jax.default_device(cpu_devices[0]):
        s0, s1 = driver.make_servers(k0, k1)
        lead = driver.Leader(s0, s1, n_dims=d, data_len=L, f_max=128)
        return _as_dict(lead.run(nreqs=n, threshold=0.1))


def test_mesh_matches_colocated_driver(client_batch, colocated_result, cpu_devices):
    _, k0, k1, _, _, n = client_batch
    assert colocated_result  # non-degenerate scenario

    m = meshmod.make_mesh(devices=cpu_devices)
    assert m.shape == {"servers": 2, "data": 4}
    runner = meshmod.MeshRunner(m, k0, k1, f_max=128)
    got = _as_dict(meshmod.MeshLeader(runner).run(nreqs=n, threshold=0.1))
    assert got == colocated_result


@pytest.mark.slow
def test_mesh_two_devices(client_batch, colocated_result, cpu_devices):
    """Minimal mesh: just the 2-server axis, no data parallelism — the
    2-chip deployment shape from BASELINE.md's north star.  Marked slow:
    it re-compiles the whole crawl kernel family for a second mesh shape;
    the 2x4 mesh parity test covers the same code path."""
    _, k0, k1, _, _, n = client_batch
    m = meshmod.make_mesh(devices=cpu_devices[:2])
    runner = meshmod.MeshRunner(m, k0, k1, f_max=128)
    got = _as_dict(meshmod.MeshLeader(runner).run(nreqs=n, threshold=0.1))
    assert got == colocated_result


def test_mesh_secure_matches_trusted(
    client_batch, colocated_result, cpu_devices, monkeypatch
):
    """The GC+OT 2PC on the 2×4 mesh (four ppermute transfers per level on
    the servers axis, FE62 inner levels + F255 last level) reconstructs the
    exact trusted-mode heavy hitters.  Same scenario as the trusted parity
    test, so the oracle and the trusted kernel family compile once for the
    module.  EQ_OT4 is forced OFF: at this n_dims=2 shape the default
    engine is now the 1-of-2^S table (covered by the ot4 test below and
    the socket suite), and THIS test is what keeps the mesh GC branch —
    the required path for S > secure.OT2S_MAX_S — exercised."""
    from fuzzyheavyhitters_tpu.protocol import secure

    monkeypatch.setattr(secure, "EQ_OT4", False)
    _, k0, k1, _, _, n = client_batch
    assert colocated_result

    m = meshmod.make_mesh(devices=cpu_devices)
    runner = meshmod.MeshRunner(m, k0, k1, f_max=128, secure_exchange=True)
    got = _as_dict(meshmod.MeshLeader(runner).run(nreqs=n, threshold=0.1))
    assert got == colocated_result

    # regression pin (round-4 review finding): the ALTERNATING garbler's
    # per-level gc/b2a seeds must land in its own mesh row — with a zero
    # seed on the odd-level (garbler=1) side, the b2a share stream repeats
    # identically across crawls (the OT pads cancel out of the shares)
    sh_a = runner.level_count_shares(1)
    sh_b = runner.level_count_shares(1)
    assert not np.array_equal(sh_a, sh_b)


def test_mesh_secure_ot4_matches_trusted(cpu_devices):
    """n_dims = 1 -> S = 2: the mesh secure body takes the 1-of-4
    chosen-payload-OT fast path (2 ppermutes per level, no garbled
    circuit; secure.EQ_OT4) and must still reconstruct the exact
    trusted-mode heavy hitters, with the garbler alternating per level."""
    rng = np.random.default_rng(11)
    L, d, n = 5, 1, 32
    centers = rng.integers(0, 1 << L, size=(3, d))
    pts = np.clip(
        centers[rng.integers(0, 3, size=n)] + rng.integers(-1, 2, size=(n, d)),
        0, (1 << L) - 1,
    )
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")

    with jax.default_device(cpu_devices[0]):
        s0, s1 = driver.make_servers(k0, k1)
        lead = driver.Leader(s0, s1, n_dims=d, data_len=L, f_max=64)
        want = _as_dict(lead.run(nreqs=n, threshold=0.1))
    assert want

    from fuzzyheavyhitters_tpu.protocol import secure

    assert secure._ot4_use(2 * d)  # the default engine for 1-dim crawls
    m = meshmod.make_mesh(devices=cpu_devices)
    runner = meshmod.MeshRunner(m, k0, k1, f_max=64, secure_exchange=True)
    got = _as_dict(meshmod.MeshLeader(runner).run(nreqs=n, threshold=0.1))
    assert got == want


def test_odd_device_count_rejected(cpu_devices):
    with pytest.raises(AssertionError, match="even"):
        meshmod.make_mesh(devices=cpu_devices[:3])
