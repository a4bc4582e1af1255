"""ibDCF tests.

Three layers, mirroring the reference's FSS unit suite (SURVEY.md §4,
ref: tests/ibdcf_tests.rs) but with real assertions:

1. bit-exact parity of the batched JAX keygen/eval against the pure-Python
   spec oracle with the SAME ChaCha PRG injected;
2. semantic full-domain sweeps (share XOR == strict comparisons; interval
   membership; multi-dim ball membership) on the JAX path alone;
3. both PRG bit modes (reference-observed constants and derived bits).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import oracle
import pytest

from fuzzyheavyhitters_tpu.ops import ibdcf, prg
from fuzzyheavyhitters_tpu.ops.ibdcf import IbDcfKeyBatch
from fuzzyheavyhitters_tpu.utils import bits as bitutils


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """Unit-scale module: run on the CPU backend (see conftest)."""
    yield



def key_from_oracle(k: oracle.IbDcfKey) -> ibdcf.IbDcfKeyBatch:
    return ibdcf.IbDcfKeyBatch(
        key_idx=np.asarray(k.key_idx),
        root_seed=prg.seeds_from_bytes(k.root_seed)[0],
        cw_seed=np.stack([prg.seeds_from_bytes(c.seed)[0] for c in k.cor_words]),
        cw_bits=np.array([c.bits for c in k.cor_words]),
        cw_y_bits=np.array([c.y_bits for c in k.cor_words]),
    )


def int_bits(L, x):
    return bitutils.int_to_bits(L, x)


def test_keygen_matches_oracle_bit_exact(rng):
    L = 12
    for side in (True, False):
        alpha = rng.integers(0, 2, size=L).astype(bool)
        seeds = rng.integers(0, 2**32, size=(2, 4), dtype=np.uint32)
        # oracle with identical roots + chacha prg
        o_rng = _FixedSeeds([prg.seed_to_bytes(seeds[0]), prg.seed_to_bytes(seeds[1])])
        ok0, ok1 = oracle.gen_ibdcf(alpha, side, o_rng, prg=prg.np_expand_bytes)
        jk0, jk1 = ibdcf.gen_pair(seeds, alpha, side)
        for ok, jk in ((ok0, jk0), (ok1, jk1)):
            ek = key_from_oracle(ok)
            np.testing.assert_array_equal(np.asarray(jk.root_seed), ek.root_seed)
            np.testing.assert_array_equal(np.asarray(jk.cw_seed), ek.cw_seed)
            np.testing.assert_array_equal(np.asarray(jk.cw_bits), ek.cw_bits)
            np.testing.assert_array_equal(np.asarray(jk.cw_y_bits), ek.cw_y_bits)


def test_gen_pair_np_matches_gen_pair(rng):
    """The host-side keygen mirror must stay bit-identical to the device
    scan — mesh tests and client simulators depend on interchangeability."""
    n, d, L = 5, 2, 9
    alpha = rng.integers(0, 2, size=(n, d, L)).astype(bool)
    seeds = rng.integers(0, 2**32, size=(n, d, 2, 4), dtype=np.uint32)
    side = rng.integers(0, 2, size=(n, d)).astype(bool)
    for derived in (False, True):
        jk = ibdcf._gen_pair_jit(seeds, alpha, side, derived)
        nk = ibdcf.gen_pair_np(seeds, alpha, side, derived)
        for p in range(2):
            for name in IbDcfKeyBatch._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(jk[p], name)),
                    np.asarray(getattr(nk[p], name)),
                    err_msg=f"party {p} field {name} derived={derived}",
                )


class _FixedSeeds:
    """np.random.Generator stand-in feeding predetermined 16-byte seeds."""

    def __init__(self, seeds):
        self._seeds = list(seeds)

    def bytes(self, n):
        assert n == 16
        return self._seeds.pop(0)


def test_eval_matches_oracle_bit_exact(rng):
    L = 10
    alpha = rng.integers(0, 2, size=L).astype(bool)
    seeds = rng.integers(0, 2**32, size=(2, 4), dtype=np.uint32)
    o_rng = _FixedSeeds([prg.seed_to_bytes(seeds[0]), prg.seed_to_bytes(seeds[1])])
    ok0, ok1 = oracle.gen_ibdcf(alpha, True, o_rng, prg=prg.np_expand_bytes)
    jk0, jk1 = ibdcf.gen_pair(seeds, alpha, True)
    for x in rng.integers(0, 1 << L, size=32):
        xb = int_bits(L, int(x))
        for ok, jk in ((ok0, jk0), (ok1, jk1)):
            os = oracle.eval_prefix(ok, xb, prg=prg.np_expand_bytes)
            js = ibdcf.eval_full(jk, xb)
            assert prg.seed_to_bytes(js.seed) == os.seed
            assert bool(js.bit) == os.bit
            assert bool(js.y_bit) == os.y_bit


@pytest.mark.parametrize("derived", [False, True])
def test_semantics_full_domain(rng, derived, monkeypatch):
    """XOR of share bits == [x < b] (side=True) / [x > b] (side=False), every
    (bound, input) pair in a 6-bit domain — the JAX twin of the oracle's
    pinned semantics (ref model: tests/ibdcf_tests.rs:4-39)."""
    monkeypatch.setattr(prg, "DERIVED_BITS", derived)
    L = 6
    n = 1 << L
    bounds = np.arange(n)
    # batch all bounds at once: alpha [n, L]
    alpha = np.stack([int_bits(L, int(b)) for b in bounds])
    seeds = rng.integers(0, 2**32, size=(n, 2, 4), dtype=np.uint32)
    xs = np.stack([int_bits(L, x) for x in range(n)])  # [n_x, L]
    for side in (True, False):
        k0, k1 = ibdcf.gen_pair(seeds, alpha, np.full(n, side))
        sweep = jax.vmap(
            lambda xb, k: ibdcf.share_bit(
                ibdcf.eval_full(k, jnp.broadcast_to(xb, (n, L)))
            ),
            in_axes=(0, None),
        )
        got = np.asarray(sweep(xs, k0)) ^ np.asarray(sweep(xs, k1))  # [n_x, n]
        want = (
            np.arange(n)[:, None] < bounds[None, :]
            if side
            else np.arange(n)[:, None] > bounds[None, :]
        )
        np.testing.assert_array_equal(got, want)


def test_interval_membership(rng):
    """Share-bit equality across parties == inclusive interval membership
    (ref model: tests/ibdcf_tests.rs:294-356 incl. single-point and edge
    intervals)."""
    L = 6
    cases = [(3, 17), (0, 63), (5, 5), (0, 0), (63, 63), (10, 40)]
    lo = np.stack([int_bits(L, a) for a, _ in cases])
    hi = np.stack([int_bits(L, b) for _, b in cases])
    (l0, r0), (l1, r1) = ibdcf.gen_interval(lo, hi, rng)
    nc = len(cases)
    xs = np.stack([int_bits(L, x) for x in range(1 << L)])
    sweep = jax.vmap(
        lambda xb, k: ibdcf.share_bit(
            ibdcf.eval_full(k, jnp.broadcast_to(xb, (nc, L)))
        ),
        in_axes=(0, None),
    )
    bits0 = np.stack([np.asarray(sweep(xs, k)) for k in (l0, r0)], axis=-1)
    bits1 = np.stack([np.asarray(sweep(xs, k)) for k in (l1, r1)], axis=-1)
    inside = np.all(bits0 == bits1, axis=-1)  # [n_x, nc]
    want = np.array(
        [[a <= x <= b for a, b in cases] for x in range(1 << L)]
    )
    np.testing.assert_array_equal(inside, want)


def test_ball_bounds_saturation():
    L = 8
    pts = np.stack([int_bits(L, v) for v in (0, 3, 128, 250, 255)])
    lo, hi = ibdcf.ball_bounds(pts, 10)
    lo_i = [bitutils.bits_to_int(r) for r in lo]
    hi_i = [bitutils.bits_to_int(r) for r in hi]
    assert lo_i == [0, 0, 118, 240, 245]
    assert hi_i == [10, 13, 138, 255, 255]


def test_l_inf_ball_membership(rng):
    """2-dim ball: share-string equality over (dim, side) == all dims within
    ball — the fuzzy-membership predicate the servers evaluate
    (ref: ibDCF.rs:175-188, collect.rs:393-410)."""
    L = 5
    pts = np.array([[7, 9], [0, 31], [16, 16]])  # [N, n_dims]
    size = 3
    pts_bits = np.stack(
        [np.stack([int_bits(L, int(v)) for v in row]) for row in pts]
    )  # [N, 2, L]
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, size, rng)
    assert k0.batch_shape == (3, 2, 2)
    n = 1 << L
    grid = np.array([(x, y) for x in range(n) for y in range(n)])  # [n², 2]
    qs = np.stack(
        [np.stack([int_bits(L, int(v)) for v in row]) for row in grid]
    )  # [n², 2, L]
    sweep = jax.vmap(
        lambda q, k: ibdcf.share_bit(
            ibdcf.eval_full(
                k, jnp.broadcast_to(q[None, :, None, :], (3, 2, 2, L))
            )
        ),
        in_axes=(0, None),
    )
    s0 = np.asarray(sweep(qs, k0))  # [n², 3, 2, 2]
    s1 = np.asarray(sweep(qs, k1))
    inside = np.all(s0 == s1, axis=(2, 3))  # [n², 3]
    # saturating bounds: clamp expected window at domain edges
    lo = np.clip(pts - size, 0, n - 1)
    hi = np.clip(pts + size, 0, n - 1)
    want = np.all(
        (grid[:, None, :] >= lo[None]) & (grid[:, None, :] <= hi[None]), axis=2
    )
    np.testing.assert_array_equal(inside, want)


def test_coords_ball_roundtrip(rng):
    """i16 coords variant: negative coordinates, clamping at the i16 edges
    (ref: ibDCF.rs:189-205); queries use the same offset-binary encoding."""
    coords = np.array([[-100, 200], [32760, -32765]])
    k0, k1 = ibdcf.gen_l_inf_ball_from_coords(coords, 16, rng)
    assert k0.batch_shape == (2, 2, 2)
    assert k0.data_len == 16
    enc = lambda v: bitutils.i16_to_ob_bits(int(v))
    q = np.stack([np.stack([enc(v) for v in row]) for row in coords])  # [N,d,16]
    qb = np.repeat(q[:, :, None, :], 2, axis=2)
    s0 = np.asarray(ibdcf.share_bit(ibdcf.eval_full(k0, qb)))
    s1 = np.asarray(ibdcf.share_bit(ibdcf.eval_full(k1, qb)))
    assert np.all(np.all(s0 == s1, axis=(1, 2)))


def test_coords_ball_zero_crossing(rng):
    """A ball whose interval crosses zero must contain its center and respect
    its edges — broken under the reference's raw two's-complement encoding
    (negatives sort above positives lexicographically), fixed here by
    offset-binary."""
    coords = np.array([[5]])
    k0, k1 = ibdcf.gen_l_inf_ball_from_coords(coords, 16, rng)
    member = []
    for q in (-12, -11, 5, 21, 22, 0):
        qb = bitutils.i16_to_ob_bits(q)[None, None, None, :].repeat(2, axis=2)
        s0 = np.asarray(ibdcf.share_bit(ibdcf.eval_full(k0, qb)))
        s1 = np.asarray(ibdcf.share_bit(ibdcf.eval_full(k1, qb)))
        member.append(bool(np.all(s0 == s1)))
    assert member == [False, True, True, True, False, True]


def test_ob_codec_roundtrip():
    for v in (-32768, -1, 0, 1, 32767, -12345):
        assert bitutils.ob_bits_to_i16(bitutils.i16_to_ob_bits(v)) == v
    # order-preservation: encoding order == signed order
    vals = [-32768, -100, -1, 0, 1, 99, 32767]
    encs = [bitutils.bits_to_int(bitutils.i16_to_ob_bits(v)) for v in vals]
    assert encs == sorted(encs)


def test_prefix_semantics_internal_levels(rng):
    """At internal levels the share XOR of a single left key equals the
    strict prefix comparison — the property the tree crawl relies on level by
    level (ref: collect.rs:94-119; oracle docstring)."""
    L = 6
    b = 0b101101
    alpha = int_bits(L, b)
    seeds = rng.integers(0, 2**32, size=(2, 4), dtype=np.uint32)
    k0, k1 = ibdcf.gen_pair(seeds, alpha, True)
    for plen in range(2, L + 1):
        n = 1 << plen
        xb = np.stack([int_bits(plen, x) for x in range(n)])  # [n, plen]
        shares = []
        for k in (k0, k1):
            st = ibdcf.EvalState(
                seed=jnp.broadcast_to(k.root_seed, (n, 4)),
                bit=jnp.broadcast_to(k.key_idx, (n,)),
                y_bit=jnp.broadcast_to(k.key_idx, (n,)),
            )
            for lvl in range(plen):
                st = ibdcf.eval_bit(ibdcf.level_cw(k, lvl), st, xb[:, lvl])
            shares.append(np.asarray(ibdcf.share_bit(st)))
        got = shares[0] ^ shares[1]
        want = np.arange(n) < (b >> (L - plen))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("engine", ["jax", "pallas"])
def test_l_inf_ball_spread_over_devices_is_bit_identical(
        engine, monkeypatch, request):
    """A key batch one device cannot hold is generated one span of the
    client axis a device, by one program (ibdcf._gen_batch), and comes
    back as ONE array a leaf, client axis sharded: bit-identical to the
    one-device call for the same seed, the parties' shared correction
    words one array still, and taken by ``block_until_ready`` /
    ``device_get`` as the one-device batch is.  The decision reads the batch's bytes and
    the devices' ``memory_stats`` (none on XLA:CPU: never spread here
    unless the probe is stood in for, as this test does)."""
    L, n = 6, 8
    pts = np.random.default_rng(5).integers(0, 1 << L, size=(n, 1))
    pts_bits = np.stack(
        [np.stack([int_bits(L, int(v)) for v in row]) for row in pts]
    )  # [N, 1, L]
    if engine == "pallas":  # the chip's engine, its kernel in interpret mode
        from fuzzyheavyhitters_tpu.ops import keygen_pallas

        monkeypatch.setattr(
            keygen_pallas, "gen_pair_pallas",
            functools.partial(keygen_pallas.gen_pair_pallas, interpret=True),
        )
        ibdcf._spread_gen.cache_clear()  # the program is built from the engine
        request.addfinalizer(ibdcf._spread_gen.cache_clear)
    one = ibdcf.gen_l_inf_ball(pts_bits, 2, np.random.default_rng(9), engine)
    assert all(len(a.devices()) == 1 for a in jax.tree.leaves(one))

    devices = jax.local_devices()[:4]
    monkeypatch.setattr(ibdcf, "_spread_devices", lambda n_, b_: devices)
    k0, k1 = spread = ibdcf.gen_l_inf_ball(
        pts_bits, 2, np.random.default_rng(9), engine)
    jax.block_until_ready(spread)
    assert all(a.devices() == set(devices) for a in jax.tree.leaves(spread))
    assert k0.cw_seed.shape == one[0].cw_seed.shape == (n, 1, 2, L, 4)
    assert {s.data.shape[0] for s in k0.cw_seed.addressable_shards} == {n // 4}
    assert k1.cw_seed is k0.cw_seed and k1.root_seed is not k0.root_seed
    for got, want in zip(jax.tree.leaves(jax.device_get(spread)),
                         jax.tree.leaves(jax.device_get(one))):
        assert isinstance(got, np.ndarray) and got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_keygen_spread_decision(monkeypatch):
    """One device while the working set (3x the batch's bytes) fits what
    it has free; else every local device that tiles the client axis."""
    class Dev:
        def __init__(self, limit, used=0):
            self._st = {"bytes_limit": limit, "bytes_in_use": used}

        def memory_stats(self):
            return self._st

    gib = 1 << 30
    four = [Dev(int(15.75 * gib)) for _ in range(4)]
    monkeypatch.setattr(jax, "local_devices", lambda: four)
    per_client = 2 * 512 * 20  # n_dims=1: two keys of 512 levels a client
    pick = lambda n: len(ibdcf._spread_devices(n, n * per_client))
    assert pick(131072) == 1     # the one-chip cell: 8.05 GB of 16.9
    assert pick(262144) == 1     # 16.1 GB: fits an empty chip
    assert pick(524288) == 4     # the four-chip cell: 32.2 GB
    assert pick(524286) == 3     # ... tiled by what divides the batch
    four[0]._st["bytes_in_use"] = 2 * gib
    assert pick(262144) == 4     # the same batch beside 2 GiB of tenants
    monkeypatch.undo()
    # XLA:CPU keeps no memory stats: host memory, never spread
    assert ibdcf._spread_devices(1 << 21, 1 << 40) == jax.local_devices()[:1]
