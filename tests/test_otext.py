"""IKNP OT-extension tests: Δ-OT invariant, chosen-payload delivery,
stream-counter lockstep, and receiver privacy basics."""

import jax
import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import otext


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """All tests in this module run on the CPU backend (see conftest)."""
    yield


@pytest.fixture(scope="module")
def pair():
    return otext.inprocess_pair()


def test_delta_ot_invariant(pair, rng):
    """T_j == Q_j ^ r_j*s — rows are correlated exactly by the sender's s
    (the free-XOR/Δ-OT contract the GC layer builds on)."""
    snd, rcv = pair
    m = 64
    r = rng.integers(0, 2, size=m).astype(bool)
    u, t = rcv.extend(r)
    q = snd.extend(m, np.asarray(u))
    s = snd.s_block
    want = np.where(r[:, None], np.asarray(q) ^ s, np.asarray(q))
    np.testing.assert_array_equal(np.asarray(t), want)


def test_chosen_payload_roundtrip(pair, rng):
    snd, rcv = pair
    m = 64
    r = rng.integers(0, 2, size=m).astype(bool)
    idx0 = rcv._recv
    u, t = rcv.extend(r)
    q = snd.extend(m, np.asarray(u))
    p0, p1 = snd.pads(q, 4, idx0)
    pr = rcv.pads(t, 4, idx0)
    m0 = rng.integers(0, 2**32, size=(m, 4), dtype=np.uint32)
    m1 = rng.integers(0, 2**32, size=(m, 4), dtype=np.uint32)
    c0 = m0 ^ np.asarray(p0)
    c1 = m1 ^ np.asarray(p1)
    got = np.where(r[:, None], c1, c0) ^ np.asarray(pr)
    np.testing.assert_array_equal(got, np.where(r[:, None], m1, m0))


def test_unchosen_pad_unlearnable(pair, rng):
    """The receiver's pad never matches the sender's other-message pad —
    (statistically: 2^-128 collision) — so the unchosen payload stays hidden."""
    snd, rcv = pair
    m = 64
    r = rng.integers(0, 2, size=m).astype(bool)
    idx0 = rcv._recv
    u, t = rcv.extend(r)
    q = snd.extend(m, np.asarray(u))
    p0, p1 = snd.pads(q, 4, idx0)
    pr = np.asarray(rcv.pads(t, 4, idx0))
    other = np.where(r[:, None], np.asarray(p0), np.asarray(p1))
    assert not np.any(np.all(pr == other, axis=1))


def test_counter_lockstep(pair, rng):
    """Back-to-back extensions stay correct (column streams advance in
    lockstep) and produce fresh correlations."""
    snd, rcv = pair
    outs = []
    for m in (64, 64, 64):  # same shape -> one compiled program, three stream windows
        r = rng.integers(0, 2, size=m).astype(bool)
        u, t = rcv.extend(r)
        q = snd.extend(m, np.asarray(u))
        want = np.where(r[:, None], np.asarray(q) ^ snd.s_block, np.asarray(q))
        np.testing.assert_array_equal(np.asarray(t), want)
        outs.append(np.asarray(q)[:7])
    assert not np.array_equal(outs[0], outs[1])
    assert not np.array_equal(outs[1], outs[2])


@pytest.mark.slow
def test_ragged_extend_sizes(pair, rng):
    """Non-block-multiple m values exercise the partial-word padding and
    counter-advance rounding directly (the default run covers the ragged
    path via the GC delta test's m=528; this sweeps it explicitly)."""
    snd, rcv = pair
    for m in (33, 32, 7, 77):
        r = rng.integers(0, 2, size=m).astype(bool)
        u, t = rcv.extend(r)
        q = snd.extend(m, np.asarray(u))
        want = np.where(r[:, None], np.asarray(q) ^ snd.s_block, np.asarray(q))
        np.testing.assert_array_equal(np.asarray(t), want)


@pytest.mark.parametrize("S,n", [
    (1, 100), (2, 800), (2, 8192), (4, 1024), (4, 1000), (6, 115), (6, 512),
    (10, 77), (2, 40000), (4, 20001), (6, 12000),
])
def test_rows_as_planes_are_the_rows(pair, rng, S, n):
    """``extend_rows(..., S)`` / ``_receiver_extend(..., S=S)`` give the
    rows of the plain extension as the planes the equality kernels read:
    plane ``s*4 + k`` is word k of rows ``s, S + s, ...`` (``_planarize``
    of the rows as ``[n, S, 4]``), for widths that divide a 32-row word
    tile, that do not (6, 10), for batches that end inside one, and for
    batches of several tiles of the de-interleave
    (``otext.PLANE_TILE_ROWS``; the last three, the last tile ragged)."""
    snd, rcv = pair
    m = n * S
    r = rng.integers(0, 2, size=m).astype(bool)
    off_r, off_s = rcv.stream_offset, snd.stream_offset
    u, t = rcv.extend(r)
    q = snd.extend(m, np.asarray(u))
    u2, t_planes = otext._receiver_extend(
        *rcv.shard_state, r, off_r, m, S
    )
    q_planes = snd.extend_rows(m, np.asarray(u), off_s, 0, S)
    np.testing.assert_array_equal(np.asarray(u2), np.asarray(u))
    for rows, planes in ((t, t_planes), (q, q_planes)):
        assert planes.shape == (S * 4, n)
        np.testing.assert_array_equal(
            np.asarray(planes), np.asarray(rows).reshape(n, S * 4).T
        )


def test_pack_unpack_roundtrip(rng):
    for m in (1, 31, 32, 33, 128, 129):
        bits = rng.integers(0, 2, size=m).astype(bool)
        words = np.asarray(otext.pack_bits(bits))
        assert words.shape == (-(-m // 32),)
        np.testing.assert_array_equal(
            np.asarray(otext.unpack_bits(words, m)), bits
        )


def test_fresh_s_bits_lsb_forced():
    s = otext.fresh_s_bits()
    assert s.shape == (128,) and s[0]
    assert otext.s_to_block(s)[0] & 1 == 1


# -- the pad index is 64 bits wide -------------------------------------------

EDGE = 1 << 32


_index_base_jit = jax.jit(otext.index_base)


def test_index_base_splits_python_ints_and_traced_scalars():
    import jax.numpy as jnp

    for v in (0, 7, EDGE - 1, EDGE, EDGE + 5, (3 << 32) + 9):
        assert [int(w) for w in otext.index_base(v)] == [v % EDGE, v >> 32]
        assert [int(w) for w in _index_base_jit(v)] == [v % EDGE, v >> 32]
    assert [int(w) for w in otext.index_base(jnp.uint32(EDGE - 1))] == [EDGE - 1, 0]
    lo, hi = otext.index_words(
        otext.index_base(EDGE - 2), jnp.arange(4, dtype=jnp.uint32)
    )
    assert [int(w) for w in lo] == [EDGE - 2, EDGE - 1, 0, 1]
    assert [int(w) for w in hi] == [0, 0, 1, 1]


def test_ot_hash_index_does_not_wrap_at_2_32(rng):
    """A batch that straddles 2^32 hashes each row under its own 64-bit
    index: the rows under the boundary as a 32-bit index hashed them,
    the rows past it unlike the wrapped index, and a batch that starts
    past it like the tail of the one that straddles."""
    rows = rng.integers(0, 2**32, size=(8, 4), dtype=np.uint32)
    across = np.asarray(otext.ot_hash(rows, 4, EDGE - 3))
    low = np.asarray(otext.ot_hash(rows[:3], 4, np.uint32(EDGE - 3)))
    np.testing.assert_array_equal(across[:3], low)
    past = np.asarray(otext.ot_hash(rows[3:], 4, EDGE))
    np.testing.assert_array_equal(across[3:], past)
    wrapped = np.asarray(otext.ot_hash(rows[3:], 4, 0))
    assert not (past == wrapped).all(axis=1).any()
    # the high word counts on: 2^33 is neither 2^32 nor 0
    far = np.asarray(otext.ot_hash(rows[3:], 4, 2 * EDGE))
    assert not (far == past).all(axis=1).any()
    assert not (far == wrapped).all(axis=1).any()


@pytest.mark.parametrize("idx0", [EDGE - 5000, EDGE + 17, (5 << 32) - 9000],
                         ids=["straddles", "past", "straddles_5"])
def test_planar_kernels_match_their_twins_across_2_32(rng, idx0):
    """Both Pallas kernel pairs (interpret mode) against their XLA twins
    with a pad index base at, past and across a multiple of 2^32, the
    carry falling inside a planar block: byte-identical messages, and
    they open to the payloads."""
    import jax.numpy as jnp

    from fuzzyheavyhitters_tpu.ops import gc, gc_pallas, otext_pallas
    from fuzzyheavyhitters_tpu.ops.fields import FE62
    from fuzzyheavyhitters_tpu.protocol import secure

    B, S = 9000, 2  # two planar blocks, the second mostly padding
    W = secure.payload_words(FE62)
    s = np.asarray(otext.s_to_block(otext.fresh_s_bits()))
    qr = rng.integers(0, 2**32, size=(B, S, 4), dtype=np.uint32)
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    y[::3] = ~y[::3]
    m0 = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    m1 = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    eq = np.all(x == y, axis=1)
    # the 1-of-2^S table
    msg_x = np.asarray(secure._ot2s_encrypt_packed_xla(
        jnp.asarray(qr), jnp.asarray(s), jnp.asarray(x), jnp.asarray(m0),
        jnp.asarray(m1), W, idx0,
    ))
    msg_p = np.asarray(otext_pallas.ot2s_encrypt(
        qr, s, x, m0, m1, W, idx0, domain=secure._OT2S_DOMAIN, interpret=True
    ))
    np.testing.assert_array_equal(msg_x, msg_p)
    tr = np.where(y[..., None], qr ^ s, qr)
    pay_p = np.asarray(otext_pallas.ot2s_decrypt(
        tr, y, msg_p, W, idx0, domain=secure._OT2S_DOMAIN, interpret=True
    ))
    pay_x = np.asarray(secure._ot2s_decrypt_packed_xla(
        jnp.asarray(tr), jnp.asarray(y), jnp.asarray(msg_x), S, W, idx0
    ))
    np.testing.assert_array_equal(pay_x, pay_p)
    np.testing.assert_array_equal(pay_p, np.where(eq[:, None], m1, m0))
    # an index 2^32 lower is another table
    low = np.asarray(otext_pallas.ot2s_encrypt(
        qr, s, x, m0, m1, W, idx0 - EDGE, domain=secure._OT2S_DOMAIN,
        interpret=True,
    )) if idx0 >= EDGE else None
    assert low is None or not np.array_equal(low, msg_p)
    # the packed garbled batch
    seed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    gmsg_x, _ = gc._garble_equality_payload_packed_xla(
        jnp.asarray(s), jnp.asarray(qr), jnp.asarray(seed), jnp.asarray(x),
        jnp.asarray(m0), jnp.asarray(m1), W, idx0,
    )
    gmsg_p, _ = gc_pallas.garble_equality_payload_packed(
        s, qr, seed, x, m0, m1, W, idx0, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(gmsg_x), np.asarray(gmsg_p))
    ev = qr ^ np.where(x[..., None], s, np.zeros(4, np.uint32))
    _, gpay_p = gc_pallas.eval_equality_payload_packed(
        np.asarray(gmsg_p), ev, W, idx0, interpret=True
    )
    _, gpay_x = gc._eval_equality_payload_packed_xla(
        gmsg_x, jnp.asarray(ev), S, W, idx0
    )
    np.testing.assert_array_equal(np.asarray(gpay_x), np.asarray(gpay_p))
    np.testing.assert_array_equal(np.asarray(gpay_p), m1)  # y == x here
