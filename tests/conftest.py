"""Test harness: everything runs on XLA:CPU with 8 virtual devices.

Multi-chip sharding (each server's client axis over its own devices)
is exercised on virtual CPU devices, per the reference's in-process
integration-test shape (two servers' state machines in one process,
ref: tests/collect_test.rs).  The suite never touches an accelerator: the
Pallas engines run here only in interpret mode (tests/test_secure_kernels.py,
tests/test_kernel_shard.py), their Mosaic compiles are pinned
by tests/test_chip_compile.py against a DESCRIBED v5e topology, and the
chip itself is reached only by ``python chip_smoke.py`` through the
builder's chip tool.

XLA_FLAGS is read lazily at first backend init, so the device-count and
optimization flags set here do land.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    # optimization_level=1: XLA:CPU's default pipeline takes minutes to
    # compile a lax.scan whose body contains the ChaCha expansion (253 s vs
    # 1.4 s measured); level 1 sidesteps the pathological pass.
    # use_fusion_emitters=false: a Pallas kernel in interpret mode is one
    # while-loop whose body XLA:CPU fuses into a single ~760-op loop
    # fusion (the in-kernel ChaCha carries no optimization_barrier).
    # With the installed jaxlib's fusion emitters, compile() returns in
    # a second and the first call then spins one core without returning
    # (20 minutes for a 40-test ot2s encrypt, at every optimization
    # level); with them off the same call takes 6 ms (PR 25).
    os.environ["XLA_FLAGS"] = (
        xla_flags
        + " --xla_force_host_platform_device_count=8"
        + " --xla_backend_optimization_level=1"
        + " --xla_cpu_use_fusion_emitters=false"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite is compile-bound (many small
# programs), so repeat runs should pay XLA compile costs once per checkout.
# Placement is compile_cache.enable()'s ($JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache); only the write threshold differs here — the
# suite's thousands of sub-0.3 s programs are cheaper to recompile than
# to write.
from fuzzyheavyhitters_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def has_tpu() -> bool:
    """Shared probe for the three bit-exactness modules of the compiled
    Pallas engines (test_keygen_pallas / test_expand_pallas /
    test_gc_pallas).  Always False under the cpu pin above — they skip
    in tier-1; on the chip those engines are checked by chip_smoke.py
    against its own plain count."""
    return jax.default_backend() == "tpu"


def _listening_inodes():
    """Socket inodes this process holds that are in LISTEN state, via
    /proc (None where /proc is unavailable — the guard degrades to a
    no-op off Linux).  Two joins: /proc/self/fd names our socket
    inodes, /proc/net/tcp{,6} names the machine's listeners (state 0A);
    the intersection is exactly 'sockets WE are listening on'."""
    try:
        fds = os.listdir("/proc/self/fd")
    except OSError:
        return None
    ours = set()
    for fd in fds:
        try:
            tgt = os.readlink(os.path.join("/proc/self/fd", fd))
        except OSError:
            continue  # fd closed between listdir and readlink
        if tgt.startswith("socket:["):
            ours.add(tgt[len("socket:["):-1])
    listening = set()
    seen_table = False
    for table in ("/proc/net/tcp", "/proc/net/tcp6"):
        try:
            with open(table) as f:
                lines = f.readlines()[1:]
        except OSError:
            continue
        seen_table = True
        for line in lines:
            parts = line.split()
            if len(parts) > 9 and parts[3] == "0A":  # TCP_LISTEN
                listening.add(parts[9])
    if not seen_table:
        return None
    return ours & listening


@pytest.fixture(autouse=True)
def no_leaked_listeners():
    """Every test must close the listening sockets it opens — the
    regression guard for the EADDRINUSE class where a leaked server
    socket poisons a later test's bind of the same port.  First in the
    fixture stack (conftest autouse), so per-test server fixtures tear
    down BEFORE the post-check; a leak surviving gc.collect() fails the
    leaking test itself, not the innocent victim that binds next."""
    before = _listening_inodes()
    yield
    if before is None:
        return
    after = _listening_inodes()
    if after is None:
        return
    leaked = after - before
    if leaked:
        import gc

        gc.collect()  # drop listeners kept alive only by cycles
        after = _listening_inodes()
        leaked = (after or set()) - before
    assert not leaked, (
        f"test leaked {len(leaked)} listening socket(s) "
        f"(/proc/net inode(s) {sorted(leaked)}) — close servers in the "
        "test (exporter tests: obs.exporter.stop(); asyncio servers: "
        "srv.close() + wait_closed())"
    )


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def cpu_default(cpu_devices):
    """Pin a test to the first CPU device explicitly (engine selection
    reads the effective default device, utils.effective_platform)."""
    with jax.default_device(cpu_devices[0]):
        yield


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) == 8
    return devs
