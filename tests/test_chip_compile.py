"""AOT compiles for a DESCRIBED v5e chip: every kernel chip_smoke.py hits,
at its shapes, through the TPU compiler installed here — no chip attached.

Tier-1 runs the Pallas engines in interpret mode only, which cannot see
what Mosaic refuses (an unaligned slice, too much VMEM, a kernel that
cannot be partitioned) nor whether a program fits 16 GB of HBM.  These
compiles can.  Nothing runs, so they say nothing about results or times;
a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import
(one process at a time may load libtpu; under xdist only the worker that is
handed this file does), and the persistent compile cache is off around the
compiles (an entry written here cannot be read back without a chip).
"""

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from fuzzyheavyhitters_tpu.ops import gc_pallas, keygen_pallas, otext, otext_pallas
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.ops.ibdcf import EvalState, IbDcfKeyBatch
from fuzzyheavyhitters_tpu.parallel import kernel_shard, server_mesh
from fuzzyheavyhitters_tpu.parallel.server_mesh import DATA
from fuzzyheavyhitters_tpu.protocol import collect, keyplanes, secure

# chip_smoke.py's table
L = 512
L_2D = 64  # configs/amazon.json: data_len 64, n_dims 2
N_TRUSTED = 131072
N_SECURE = 16384
F = 64  # the widest frontier bucket the smoke's crawl reaches
S = 2  # 1-dim L-inf string pair
W = secure.payload_words(FE62)  # an FE62 payload's u32 words on the wire: 2
B_SECURE = F * 2 * N_SECURE  # (node, child, client) tests of one level (--chips 4: the same N over four chips)

HBM_BYTES = 16 * 1024**3

# the four-chip benchmark cell (flagship-trusted-4chip): N clients a server,
# client axis sharded over the server's two chips
N_4CHIP = 524288


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # fhh-lint: disable=broad-except (whatever the plugin raises where no TPU compiler is installed means: skip)
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding):
    """Shape constructor bound to one placement (nothing can be put on a
    described device, so every argument is a ShapeDtypeStruct)."""
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)


def _compile(fn, *args, temp_under=None, **static):
    compiled = fn.lower(*args, **static).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert total < HBM_BYTES, f"program needs {total / 2**30:.1f} GiB of HBM"
    if temp_under is not None:
        assert mem.temp_size_in_bytes < temp_under, (
            f"{mem.temp_size_in_bytes / 2**20:.0f} MiB of temporaries")
    return text


@pytest.mark.parametrize("d,length", [(1, L), (2, L_2D)], ids=["flagship", "amazon2d"])
def test_keygen(one_chip, d, length):
    # one server pair's keys: N clients x (dim, side) = 2dN ibDCF keys
    n = 2 * d * N_TRUSTED
    sds = _sds(one_chip)
    text = _compile(
        keygen_pallas._gen_pallas,
        sds((n, 2, 4), jnp.uint32), sds((n, length), jnp.bool_), sds((n,), jnp.bool_),
        derived_bits=True,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d,length", [(1, L), (2, L_2D)], ids=["flagship", "amazon2d"])
@pytest.mark.parametrize("want_children", [True, False])
def test_expand_level(one_chip, want_children, d, length):
    """One whole expand level as the server jits it — the resident key
    batch's level slice, the cw pack, and ``expand_pallas.expand_packed``
    on the plane-major frontier — at the trusted lane's widest bucket,
    and at the two-dimensional deployment's (``configs/amazon.json``:
    four child patterns a node, a child cache twice as wide a client)."""
    n = N_TRUSTED
    sds = _sds(one_chip)
    keys = IbDcfKeyBatch(
        key_idx=sds((n, d, 2), jnp.bool_),
        root_seed=sds((n, d, 2, 4), jnp.uint32),
        cw_seed=sds((n, d, 2, length, 4), jnp.uint32),
        cw_bits=sds((n, d, 2, length, 2), jnp.bool_),
        cw_y_bits=sds((n, d, 2, length, 2), jnp.bool_),
    )
    frontier = collect.Frontier(
        states=EvalState(
            seed=sds((4, d, 2, F, n), jnp.uint32),
            bit=sds((d, 2, F, n), jnp.bool_),
            y_bit=sds((d, 2, F, n), jnp.bool_),
        ),
        alive=sds((F,), jnp.bool_),
    )
    text = _compile(
        collect._expand_share_bits_jit, keys, frontier, sds((), jnp.int32),
        derived_bits=True, want_children=want_children, use_pallas=True,
    )
    assert "tpu_custom_call" in text


def test_ot2s_encrypt_decrypt(one_chip):
    b = B_SECURE
    sds = _sds(one_chip)
    idx = sds((), jnp.uint32)
    text = _compile(
        otext_pallas._enc_planar,
        sds((b, S, 4), jnp.uint32), sds((4,), jnp.uint32),
        sds((b, S), jnp.bool_), sds((b, W), jnp.uint32),
        sds((b, W), jnp.uint32), idx,
        S=S, W=W, domain=secure._OT2S_DOMAIN, interpret=False,
    )
    assert "tpu_custom_call" in text
    msg = (1 << S) * W * gc_pallas.padded_tests(b)
    text = _compile(
        otext_pallas._dec_planar,
        sds((b, S, 4), jnp.uint32), sds((b, S), jnp.bool_),
        sds((msg,), jnp.uint32), idx,
        S=S, W=W, domain=secure._OT2S_DOMAIN, interpret=False,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("words", [W, 8], ids=["FE62", "F255"])
def test_gc_garble_eval(one_chip, words):
    """The whole-level wire the servers exchange on the GC path
    (gc.garble/eval_equality_payload_packed), at both payload widths:
    every level's FE62 message and the leaf level's F255 one."""
    b = B_SECURE
    sds = _sds(one_chip)
    idx = sds((), jnp.uint32)
    text = _compile(
        gc_pallas._garble_packed,
        sds((4,), jnp.uint32), sds((b, S, 4), jnp.uint32),
        sds((b, S, 4), jnp.uint32), sds((b,), jnp.uint32),
        sds((b, S), jnp.bool_), sds((b, words), jnp.uint32),
        sds((b, words), jnp.uint32), idx,
        S=S, W=words, interpret=False,
    )
    assert "tpu_custom_call" in text
    text = _compile(
        gc_pallas._eval_packed,
        sds((gc_pallas.packed_msg_words(b, S, words),), jnp.uint32),
        sds((b, S, 4), jnp.uint32), idx,
        S=S, W=words, interpret=False,
    )
    assert "tpu_custom_call" in text


def test_iknp_extension(one_chip):
    """The packed butterfly transpose inside both extension roles, at one
    secure level's OT count."""
    m = B_SECURE * S
    w = m // 32
    sds = _sds(one_chip)
    seeds = sds((128, 4), jnp.uint32)
    off = sds((), jnp.uint32)
    _compile(otext._transpose_pack, sds((128, w), jnp.uint32), m=m)
    _compile(otext._sender_extend, seeds, sds((128,), jnp.bool_),
             sds((128, w), jnp.uint32), off, m=m)
    _compile(otext._receiver_extend, seeds, seeds, sds((m,), jnp.bool_),
             off, m=m)


@pytest.mark.parametrize("S,b,k", [
    (S, 32 * 2 * N_SECURE, 2), (S, 32 * 2 * N_TRUSTED, 16),
    (4, 32 * 4 * N_TRUSTED, 128),
], ids=["flagship", "hbm", "amazon2d"])
@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
def test_secure_level_chunk_programs(one_chip, field, S, b, k):
    """What one chunk of a secure level hands the device on a server
    (``rpc._ev_chunks`` / ``_gb_chunks``), one program a span, at the
    chunk ``secure.level_chunks`` cuts from the benchmark's steady level
    (bucket 32 at N=16,384 and, ``hbm``, at N=131,072: n = 524,288 tests
    a chunk either way, K = 2 and 16; the leaf level's F255 table is four
    times as wide, so its chunk is a quarter of the tests): the
    evaluator's slice + extension
    (``otext``) and open + field (``b2a``), the garbler's extension
    (``otext``) and share pair + table (``b2a``) at both signs.  The
    chunk's first test, offsets and pad indices are traced scalars, so
    these are every chunk's programs from bucket 16 up.  ``amazon2d`` is
    the two-dimensional deployment's steady level (S = 4, four patterns
    a node, N=131,072): a 1-of-16 table of 128 bytes a test, 131,072
    tests a frame (K = 128; 512 chunks of 32,768 at its F255 leaf).  No
    program keeps a lane-padded copy of the extension's rows among its
    temporaries: neither the 32 times their bytes of rows cut as
    ``[n, S, 4]`` (PR 38) nor the 4 S times of an untiled de-interleave
    in ``otext._transpose_planes``, which at these chunks (1M and 512K
    extension rows) is 128 MiB and no longer fits VMEM (PR 47)."""
    words = secure.payload_words(field)
    chunks = secure.level_chunks(b, S, words, "ot2s")
    assert len(chunks) == (k if field is FE62 else 4 * k)
    n = chunks[0][1]
    assert all(c[1] == n for c in chunks) and n % kernel_shard.BLOCK == 0
    sds = _sds(one_chip)
    at = sds((), jnp.int64)  # a Python int of the level's plan
    m = n * S
    rows_bytes = 16 * m
    seeds = sds((128, 4), jnp.uint32)
    flat = sds((b, S), jnp.bool_)
    planes = sds((S * 4, n), jnp.uint32)
    _compile(secure._ev_extend, seeds, seeds, flat, at, at, n=n,
             temp_under=8 * rows_bytes)
    _compile(otext._sender_extend, seeds, sds((128,), jnp.bool_),
             sds((128, m // 32), jnp.uint32), at, m=m, S=S,
             temp_under=8 * rows_bytes)
    for garbler in (0, 1):
        text = _compile(
            secure._gb_table,
            sds((4,), jnp.uint32), planes, flat, sds((4,), jnp.uint32), at, at,
            field=field, garbler=garbler, n=n, pallas=True,
            temp_under=16 * rows_bytes + (32 << 20),
        )
        assert "tpu_custom_call" in text
    text = _compile(
        secure._ev_open,
        planes, sds((n, S), jnp.bool_),
        sds(((1 << S) * words * n,), jnp.uint32), at,
        field=field, pallas=True, temp_under=8 * rows_bytes,
    )
    assert "tpu_custom_call" in text


def test_gc_chunk_garble(one_chip):
    """The garbled-circuit path's chunk (no cell runs it): tests
    ``[t0, t0 + n)`` of a level's packed garble, the labels carved from
    the level's one draw at a traced offset."""
    from fuzzyheavyhitters_tpu.ops import gc

    b = 32 * 2 * N_SECURE
    n = secure.level_chunks(b, S, W, "gc")[0][1]
    sds = _sds(one_chip)
    at = sds((), jnp.int64)
    text = _compile(
        gc._garble_rows_packed,
        sds((4,), jnp.uint32), sds((n, S, 4), jnp.uint32),
        sds((4,), jnp.uint32), sds((n, S), jnp.bool_),
        sds((n, W), jnp.uint32), sds((n, W), jnp.uint32),
        idx_offset=at, t0=at, n_words=W, B=b, pallas=True,
    )
    assert "tpu_custom_call" in text
    # the chunk's ``garble`` step as the servers call it: the cut of the
    # level's flat strings and the extension's planes turned to rows
    # inside the one program
    text = _compile(
        secure._gb_garble,
        sds((4,), jnp.uint32), sds((S * 4, n), jnp.uint32),
        sds((4,), jnp.uint32), sds((b, S), jnp.bool_),
        sds((n, W), jnp.uint32), sds((n, W), jnp.uint32), at, at,
        W=W, n=n, pallas=True,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "path,field", [("ot2s", "FE62"), ("ot2s", "F255"), ("gc", "FE62")]
)
def test_kernel_shard_bodies_on_four_chips(topo, path, field):
    """The row-sharded secure kernel stage (parallel/kernel_shard.py) as
    ONE program across the four chips of a 2x2 host: the shard_map bodies
    wrap the Pallas engines, so they must partition, and the share-sum
    reduce must lower (the TPU has no 64-bit all-reduce).  F255 is the
    last level's field."""
    devices = tuple(topo.devices[:4])
    b = B_SECURE
    w = secure.payload_words(kernel_shard._FIELDS[field])
    limb = kernel_shard._FIELDS[field].limb_shape
    ks = kernel_shard.KernelShard(devices, b, S)
    sds = lambda shape, dt, spec: _sds(NamedSharding(ks.mesh, spec))(shape, dt)
    bp, rows = ks.bp, ks.bp // kernel_shard.GROUP
    seeds = sds((128, 4), jnp.uint32, P())
    scalar = sds((), jnp.uint32, P())
    seed4 = sds((4,), jnp.uint32, P())
    q = sds((bp * S, 4), jnp.uint32, P(DATA, None))
    flat = sds((bp, S), jnp.bool_, P(DATA, None))
    n_planes = kernel_shard.n_msg_planes(path, S, w)
    planes = sds((n_planes, rows, kernel_shard.SUB, kernel_shard.LANES),
                 jnp.uint32, P(None, DATA, None, None))

    _compile(kernel_shard._snd_extend_fn(devices, b, S), seeds,
             sds((128,), jnp.bool_, P()),
             sds((128, bp * S // 32), jnp.uint32, P(None, DATA)), scalar)
    _compile(kernel_shard._rcv_extend_fn(devices, b, S),
             seeds, seeds, flat, scalar)
    text = _compile(
        kernel_shard._gb_kernel_fn(devices, field, b, S, w, path, 0, "pallas"),
        q, seed4, flat, seed4, seed4, scalar,
    )
    assert "tpu_custom_call" in text
    text = _compile(
        kernel_shard._ev_open_fn(devices, field, b, S, w, path, "pallas"),
        planes, q, flat, scalar,
    )
    assert "tpu_custom_call" in text
    vals = (sds((bp,), jnp.uint64, P(DATA)) if limb == ()
            else sds((bp,) + limb, jnp.uint32, P(DATA, None)))
    text = _compile(
        kernel_shard._share_sums_fn(devices, field, F, 2, N_SECURE, b, bp),
        vals, sds((F, 2, N_SECURE), jnp.bool_, P()),
    )
    assert "all-reduce" in text


@pytest.mark.parametrize("bucket", [32, 64, 128])
def test_sharded_trusted_level_on_a_two_chip_submesh(topo, bucket):
    """The sharded trusted lane's level step at the four-chip cell's
    shapes (N=524,288 a server, its client axis over two of the 2x2
    host's chips; the steady bucket, the widest usual one, and the one
    that one seed in ten meets for a level): the fused Pallas expand
    once per shard under ``shard_map`` (it must partition) with and
    without the child cache, the prune's gather from that cache, and
    the counts with their psum over the local data axis — each as the
    server dispatches it (``CollectionSession.expand``, ``tree_prune``,
    ``ServerMesh.counts_by_pattern``), each beside the resident key
    planes within one chip's HBM.  At bucket 64 the interleaved XLA
    expand this replaced needs 15.0 GB a chip by the same analysis, and
    at 128 it does not compile (16.6 GB)."""
    devices = tuple(topo.devices[2:4])  # server 1's pair
    mesh = server_mesh._mesh_for(devices)
    n, d, f = N_4CHIP, 1, bucket
    sds = lambda shape, dt, *spec: _sds(NamedSharding(mesh, P(*spec)))(shape, dt)
    last = (None,) * 3 + (DATA,)  # [d, 2, F, N]: the clients last
    keys = IbDcfKeyBatch(
        key_idx=sds((n, d, 2), jnp.bool_, DATA),
        root_seed=sds((n, d, 2, 4), jnp.uint32, DATA),
        cw_seed=sds((n, d, 2, L, 4), jnp.uint32, DATA),
        cw_bits=sds((n, d, 2, L, 2), jnp.bool_, DATA),
        cw_y_bits=sds((n, d, 2, L, 2), jnp.bool_, DATA),
    )
    key_bytes = n * d * 2 * (L * 20 + 17) // len(devices)  # a chip's share
    frontier = collect.Frontier(
        states=EvalState(
            seed=sds((4, d, 2, f, n), jnp.uint32, None, *last),
            bit=sds((d, 2, f, n), jnp.bool_, *last),
            y_bit=sds((d, 2, f, n), jnp.bool_, *last),
        ),
        alive=sds((f,), jnp.bool_),
    )

    def fits(compiled, resident=0):
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes + resident)
        assert total < 15e9, f"{total / 1e9:.1f} GB a chip"
        return compiled.as_text()

    for want_children in (True, False):
        text = fits(server_mesh._expand_fn(devices, True, want_children).lower(
            keys, frontier, sds((), jnp.int32)).compile())  # keys among its arguments
        assert "tpu_custom_call" in text  # the Pallas engine, per shard
        assert "all-gather" not in text and "all-reduce" not in text
    children = collect.PlanarChildren(
        seed=sds((2, 4, d, 2, f, n), jnp.uint32, None, None, *last),
        flags=sds((d, 2, f, n), jnp.uint32, *last),
    )
    text = fits(collect._advance_children_jit.lower(
        children, sds((f,), jnp.int32), sds((f, d), jnp.bool_),
        sds((), jnp.int32), planar=True,
    ).compile(), key_bytes + frontier.states.seed.size * 18 // 16 // len(devices))
    assert "all-gather" not in text  # the gather is over nodes, a shard's own
    packed = sds((f, n), jnp.uint32, None, DATA)
    text = fits(server_mesh._counts_fn(devices).lower(
        packed, packed, sds((1 << d,), jnp.uint32),
        sds((n,), jnp.bool_, DATA), sds((f,), jnp.bool_),
    ).compile(), key_bytes)
    assert "all-reduce" in text


def test_keygen_spread_over_four_chips(topo):
    """The four-chip cell's key batch (2 x 524,288 keys of 512 levels:
    21.5 GB, more than one chip holds) as ``ibdcf._gen_batch`` makes it:
    ONE program over the host's four chips, the fused keygen kernel once
    per chip on its own quarter of the clients, nothing between chips,
    the parties' shared correction words returned once."""
    from fuzzyheavyhitters_tpu.ops import ibdcf

    devices = tuple(topo.devices)
    fn = ibdcf._spread_gen("pallas", devices)
    try:
        mesh = jax.sharding.Mesh(devices, ("clients",))
        sds = _sds(NamedSharding(mesh, P("clients")))
        n, d = N_4CHIP, 1
        text = _compile(
            fn, sds((n, d, 2, 2, 4), jnp.uint32), sds((n, d, 2, L), jnp.bool_),
            sds((n, d, 2), jnp.bool_),
        )
    finally:
        ibdcf._spread_gen.cache_clear()  # keyed on described devices
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text
    assert "collective-permute" not in text and "all-to-all" not in text


def test_sharded_secure_level_hands_planar_bits_to_the_kernel_stage(topo):
    """What ``chip_smoke.py --chips 4`` runs since PR 30, on server 1's
    pair of chips at the smoke's sharded shapes: the plane-major Pallas
    expand once per shard, the flat equality strings built from its
    packed share bits where they lie (client axis sharded), and the
    garbling kernel row-sharded over the same two chips."""
    from chip_smoke import N_SHARDED

    devices = tuple(topo.devices[2:4])
    mesh = server_mesh._mesh_for(devices)
    n, d = N_SHARDED, 1
    sds = lambda shape, dt, *spec: _sds(NamedSharding(mesh, P(*spec)))(shape, dt)
    last = (None,) * 3 + (DATA,)
    keys = IbDcfKeyBatch(
        key_idx=sds((n, d, 2), jnp.bool_, DATA),
        root_seed=sds((n, d, 2, 4), jnp.uint32, DATA),
        cw_seed=sds((n, d, 2, L, 4), jnp.uint32, DATA),
        cw_bits=sds((n, d, 2, L, 2), jnp.bool_, DATA),
        cw_y_bits=sds((n, d, 2, L, 2), jnp.bool_, DATA),
    )
    frontier = collect.Frontier(
        states=EvalState(
            seed=sds((4, d, 2, F, n), jnp.uint32, None, *last),
            bit=sds((d, 2, F, n), jnp.bool_, *last),
            y_bit=sds((d, 2, F, n), jnp.bool_, *last),
        ),
        alive=sds((F,), jnp.bool_),
    )
    text = _compile(server_mesh._expand_fn(devices, True, True),
                    keys, frontier, sds((), jnp.int32))
    assert "tpu_custom_call" in text
    b = F * 2 * n
    ks = kernel_shard.KernelShard(devices, b, S)
    _compile(kernel_shard._flat_fn(d, F, n, ks.bp, 1),
             sds((F, n), jnp.uint32, None, DATA))
    seed4, scalar = sds((4,), jnp.uint32), sds((), jnp.uint32)
    text = _compile(
        kernel_shard._gb_kernel_fn(devices, "FE62", b, S, W, "ot2s", 0, "pallas"),
        sds((ks.bp * S, 4), jnp.uint32, DATA, None), seed4,
        sds((ks.bp, S), jnp.bool_, DATA, None), seed4, seed4, scalar,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "chip,rows",
    [(0, N_TRUSTED), (2, N_4CHIP // 2), (3, N_4CHIP // 2)],
    ids=["one_chip_server", "two_chip_server_chip2", "two_chip_server_chip3"],
)
def test_key_rows_are_written_in_place(topo, chip, rows):
    """The row write of an arriving key batch (``keyplanes._write_rows``)
    at the cells' sizes: the whole plane of ``flagship-trusted`` on one
    chip, and a chip's half of ``flagship-trusted-4chip`` on each chip of
    server 1 (per-chip buffers, one-chip programs).  Every plane is aliased
    to its output and the program holds no second plane: its temporaries
    are smaller than one batch."""
    sds = _sds(SingleDeviceSharding(topo.devices[chip]))
    d, batch = 1, 100  # addkey_batch_size of the three configurations

    def key_batch(n, flat=False):
        def leaf(shape, dtype):  # a batch crosses flat, [B, -1] a leaf
            return sds((n, math.prod(shape)) if flat else (n, *shape), dtype)

        return IbDcfKeyBatch(
            key_idx=leaf((d, 2), jnp.bool_),
            root_seed=leaf((d, 2, 4), jnp.uint32),
            cw_seed=leaf((d, 2, L, 4), jnp.uint32),
            cw_bits=leaf((d, 2, L, 2), jnp.bool_),
            cw_y_bits=leaf((d, 2, L, 2), jnp.bool_),
        )

    compiled = keyplanes._write_rows.lower(
        key_batch(rows), key_batch(batch, flat=True), sds((), jnp.int32)
    ).compile()
    mem = compiled.memory_analysis()
    plane_bytes = rows * d * 2 * (1 + 16 + L * 16 + L * 2 + L * 2)
    batch_bytes = batch * d * 2 * (1 + 16 + L * 16 + L * 2 + L * 2)
    # the planes as the device lays them out: no smaller than their values,
    # and what one chip of the cell is sized for (PERF.md section 4)
    assert plane_bytes <= mem.output_size_in_bytes < 1.05 * plane_bytes
    # (the output's own bytes beyond the aliased planes: its tuple table)
    assert 0 <= mem.output_size_in_bytes - mem.alias_size_in_bytes < 4096
    assert mem.temp_size_in_bytes < batch_bytes
    text = compiled.as_text()
    assert len(re.findall(r"(?:may|must)-alias", text)) >= 5  # one a plane
    assert "dynamic-update-slice" in text
