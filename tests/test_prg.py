"""PRG tests: JAX/NumPy bit-exactness, reference semantics (mask quirk,
length-doubling interface), statistical sanity (ref test model: prg.rs:337-373
non-degeneracy tests)."""

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import prg


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """Unit-scale module: run on the CPU backend (see conftest)."""
    yield



def test_jax_matches_numpy_block(rng):
    blocks = rng.integers(0, 2**32, size=(64, 4), dtype=np.uint32)
    out_np = prg.np_chacha_block(blocks)
    out_jax = np.asarray(prg.chacha_block(blocks))
    np.testing.assert_array_equal(out_np, out_jax)


def test_unrolled_rounds_bit_exact(rng):
    """CHACHA_UNROLL (the TPU hot-path form, bin/server.py) and
    the default scan form compute identical blocks."""
    import jax

    blocks = rng.integers(0, 2**32, size=(8, 4), dtype=np.uint32)
    want = prg.np_chacha_block(blocks)
    old = prg.CHACHA_UNROLL
    try:
        prg.CHACHA_UNROLL = True
        # fresh trace: chacha_block reads the flag at trace time
        # fhh-lint: disable=recompile-churn (a fresh trace IS the test)
        got = np.asarray(jax.jit(lambda b: prg.chacha_block(b))(blocks))
    finally:
        prg.CHACHA_UNROLL = old
    np.testing.assert_array_equal(got, want)


def test_expand_matches_bytes_interface(rng):
    for _ in range(8):
        seed = rng.bytes(16)
        s_l, s_r, bits, y_bits = prg.np_expand_bytes(seed)
        arr = prg.seeds_from_bytes(seed)[0]
        jl, jr, jb, jy = prg.expand(arr)
        assert prg.seed_to_bytes(jl) == s_l
        assert prg.seed_to_bytes(jr) == s_r
        assert tuple(np.asarray(jb)) == bits
        assert tuple(np.asarray(jy)) == y_bits


def test_rfc8439_quarter_round():
    # RFC 8439 §2.1.1 test vector for the quarter round.
    import jax.numpy as jnp

    a = jnp.uint32(0x11111111)
    b = jnp.uint32(0x01020304)
    c = jnp.uint32(0x9B8D6F43)
    d = jnp.uint32(0x01234567)
    a, b, c, d = prg._quarter_round(a, b, c, d)
    assert int(a) == 0xEA2A92F4
    assert int(b) == 0xCB1CF8CE
    assert int(c) == 0x4581472E
    assert int(d) == 0x5881C4BB


def test_mask_quirk(rng):
    """Seeds differing only in the low nibble of byte 0 expand identically
    (prg.rs:97), and the observed-mode t/y bits are the constants (1,1)
    (prg.rs:103-104)."""
    seed = rng.integers(0, 2**32, size=(4,), dtype=np.uint32)
    seed2 = seed.copy()
    seed2[0] ^= np.uint32(0x0000000B)  # flip masked-away bits
    l1, r1, b1, y1 = prg.expand(seed, derived_bits=False)
    l2, r2, b2, y2 = prg.expand(seed2, derived_bits=False)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert np.all(np.asarray(b1)) and np.all(np.asarray(y1))
    # the seed mask applies in BOTH modes (prg.rs:97 masks before expanding)
    ld, _, _, _ = prg.expand(seed, derived_bits=True)
    ld2, _, _, _ = prg.expand(seed2, derived_bits=True)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(ld2))


def test_children_differ_and_nondegenerate(rng):
    """Left/right children differ from each other and the parent; bit balance
    across many seeds is ~50% (ref: prg.rs:337-373)."""
    seeds = rng.integers(0, 2**32, size=(4096, 4), dtype=np.uint32)
    s_l, s_r, _, _ = prg.expand(seeds)
    s_l, s_r = np.asarray(s_l), np.asarray(s_r)
    assert not np.any(np.all(s_l == s_r, axis=-1))
    assert not np.any(np.all(s_l == seeds, axis=-1))
    # per-bit balance over the batch
    bits = np.unpackbits(np.ascontiguousarray(s_l).view(np.uint8), axis=-1)
    frac = bits.mean(axis=0)
    assert np.all(np.abs(frac - 0.5) < 0.05)


def test_derived_bits_mode(rng):
    seeds = rng.integers(0, 2**32, size=(2048, 4), dtype=np.uint32)
    _, _, bits, y_bits = prg.expand(seeds, derived_bits=True)
    for arr in (np.asarray(bits), np.asarray(y_bits)):
        frac = arr.mean(axis=0)
        assert np.all(np.abs(frac - 0.5) < 0.08)


def test_stream_words(rng):
    seed = rng.integers(0, 2**32, size=(4,), dtype=np.uint32)
    w = np.asarray(prg.stream_words(seed, 40))
    assert w.shape == (40,)
    # deterministic and prefix-consistent
    w2 = np.asarray(prg.stream_words(seed, 16))
    np.testing.assert_array_equal(w[:16], w2)
    # distinct seeds -> distinct streams
    seed2 = seed.copy()
    seed2[3] ^= np.uint32(1)
    assert not np.array_equal(w, np.asarray(prg.stream_words(seed2, 40)))


def test_oracle_accepts_chacha_prg(rng):
    """The spec oracle runs unchanged with the ChaCha PRG injected —
    the device PRG is a drop-in for the protocol semantics."""
    import oracle

    alpha = rng.integers(0, 2, size=8).astype(bool)
    k0, k1 = oracle.gen_ibdcf(alpha, True, rng, prg=prg.np_expand_bytes)
    for x in range(256):
        xb = np.array([(x >> (7 - i)) & 1 == 1 for i in range(8)])
        s0 = oracle.eval_prefix(k0, xb, prg=prg.np_expand_bytes)
        s1 = oracle.eval_prefix(k1, xb, prg=prg.np_expand_bytes)
        alpha_int = int("".join("1" if b else "0" for b in alpha), 2)
        assert (oracle.share_bit(s0) ^ oracle.share_bit(s1)) == (x < alpha_int)
