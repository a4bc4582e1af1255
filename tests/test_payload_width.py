"""A payload travels at the width of its field (``secure.payload_words``:
FE62 in two u32 words, F255 in eight) and no share moved when the FE62
payload lost the two words of zeros of its 128-bit ``Block``.

The oracle is local to this file: the same XLA twins run at the OLD
four-word width (``FE62.to_blocks`` / ``from_blocks``, the reference's
Block codec, stay in ops/fields.py and are what it encodes with).  Held
against it, bit for bit: the sender's ``r1`` and the receiver's opened
values of a chunk that does not begin the level (``gb_chunk_table`` /
``ev_chunk_open``, ``gb_chunk_pair`` + ``gb_chunk_garble`` /
``ev_chunk_eval`` + ``ev_chunk_field``) and of the whole-level pair
(``gb_step_level`` / ``ev_open_level``), on both equality paths; and the
message itself: every plane the narrow wire keeps is the four-word
wire's plane, so the kept ciphertext words are unchanged.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import gc, gc_pallas, otext, prg
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.parallel.kernel_shard import n_msg_planes
from fuzzyheavyhitters_tpu.protocol import secure

BLOCK = gc_pallas.R_BLK * gc_pallas.GROUP
# the width every payload had before: F255's is its own still
OLD_WORDS = {FE62: 4, F255: 8}


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    yield


@pytest.fixture(scope="module")
def ot_pair():
    return otext.inprocess_pair()


def _old_words(field, v):
    b = field.to_blocks(v)
    return b.reshape(b.shape[:-2] + (8,)) if field is F255 else b


def _old_field(field, w):
    if field is F255:
        return field.from_blocks(w.reshape(w.shape[:-1] + (2, 4)))
    return field.from_blocks(w)


def _oracle(field, path, s_block, q_planes, t_planes, x, y, gc_seed, b2a_seed,
            garbler, idx, B, t0):
    """Tests ``[t0, t0 + n)`` at the old width: (r1, the message, the
    receiver's values).  The share pair is drawn as ``b2a_payload_pair``
    always drew it, four stream words a test for FE62 and eight for
    F255, from block ``t0 * D // 16`` of the level's one stream."""
    n, S = x.shape
    W = D = OLD_WORDS[field]  # the draw's stride was the wire's width
    r_words = prg.stream_words(
        jnp.asarray(b2a_seed, jnp.uint32), n * D, t0 * D // 16
    ).reshape(n, D)
    r0 = field.sample(r_words)
    one = field.from_int(1)
    r1 = field.sub(r0, one) if garbler else field.add(r0, one)
    w0, w1 = _old_words(field, r0), _old_words(field, r1)
    q_rows, t_rows = secure._test_rows(q_planes, S), secure._test_rows(t_planes, S)
    if path == "ot2s":
        msg = secure._ot2s_encrypt_packed_xla(q_rows, s_block, x, w1, w0, W, idx)
        w = secure._ot2s_decrypt_packed_xla(t_rows, y, msg, S, W, idx)
    else:
        msg = gc._garble_rows_packed(
            s_block, q_rows, jnp.asarray(gc_seed, jnp.uint32), x, w1, w0, W,
            idx, B, t0, False,
        )
        w = gc._eval_equality_payload_packed_xla(msg, t_rows, S, W, idx)[1]
    return r1, np.asarray(msg), _old_field(field, w)


def _kept_planes(field, path, S):
    """Planes of the four-word message that the message at the field's
    width is made of, in its order."""
    W, old = secure.payload_words(field), OLD_WORDS[field]
    if path == "ot2s":
        return [c * old + w for c in range(1 << S) for w in range(W)]
    head = (S - 1) * 8 + 4 * S + 1  # tables | gb_labels | decode
    return list(range(head)) + [
        head + c * old + w for c in range(2) for w in range(W)
    ]


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("path", ["ot2s", "gc"])
@pytest.mark.parametrize("S", [2, 4])
@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
def test_no_share_moved_against_the_four_word_oracle(ot_pair, rng, field, S, path):
    snd, rcv = ot_pair
    B, t0, n = 2 * BLOCK + 40, BLOCK, BLOCK + 40  # the chunk that ends the level
    W = secure.payload_words(field)
    assert W == (8 if field is F255 else 2)
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    y[::3, rng.integers(0, S)] ^= True
    eq = np.all(x == y, axis=1)
    gc_seed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    b2a_seed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    garbler = int(rng.integers(0, 2))
    s_block = jnp.asarray(snd.s_block)
    fx, fy = jnp.asarray(x), jnp.asarray(y)
    # the level's one extension, from where the sessions stand (the
    # chunk functions move no cursor: the whole level below starts there)
    idx0, off_r, off_s = rcv.consumed, rcv.stream_offset, snd.stream_offset
    assert idx0 == snd.consumed

    def rows(c0, cn):
        u, t, _ = secure.ev_chunk_extend(rcv, fy, off_r, c0, cn)
        return secure.gb_chunk_extend(snd, np.asarray(u), S, off_s, c0, cn), t

    # -- the chunk [t0, t0 + n) ------------------------------------------
    q, t = rows(t0, n)
    want_r1, want_msg, want_open = _oracle(
        field, path, s_block, q, t, fx[t0:], fy[t0:], gc_seed, b2a_seed,
        garbler, idx0 + t0, B, t0,
    )
    if path == "ot2s":
        msg, r1 = secure.gb_chunk_table(
            field, b2a_seed, q, fx, s_block, idx0, t0, n, garbler)
        got = secure.ev_chunk_open(field, t, fy[t0:], np.asarray(msg), idx0, t0)
    else:
        r1, w0, w1 = secure.gb_chunk_pair(b2a_seed, t0, field, garbler, n)
        assert w0.shape == w1.shape == (n, W)
        msg = secure.gb_chunk_garble(
            s_block, q, gc_seed, fx, w0, w1, W, idx0, t0, n)
        got = secure.ev_chunk_field(field, secure.ev_chunk_eval(
            t, fy[t0:], np.asarray(msg), W, idx0, t0))
    _same(r1, want_r1)
    _same(got, want_open)
    # the message: n_msg_planes planes, each the four-word message's
    bp = gc_pallas.padded_tests(n)
    planes = n_msg_planes(path, S, W)
    assert np.asarray(msg).size == planes * bp
    if path == "ot2s":
        assert planes == (1 << S) * W
    _same(
        np.asarray(msg).reshape(planes, bp),
        want_msg.reshape(n_msg_planes(path, S, OLD_WORDS[field]), bp)[
            _kept_planes(field, path, S)],
    )
    # and the shares are shares of the predicate, whoever garbled
    v = (r1, got) if garbler == 0 else (got, r1)
    diff = np.asarray(field.canon(field.sub(*v)))
    np.testing.assert_array_equal(
        diff[:, 0] if field is F255 else diff, eq[t0:].astype(diff.dtype))

    # -- the whole level, from the same state ------------------------------
    q, t = rows(0, B)
    want_r1, want_msg, want_open = _oracle(
        field, path, s_block, q, t, fx, fy, gc_seed, b2a_seed, garbler,
        idx0, B, 0,
    )
    u, t_rows, idx = secure.ev_step1_fused(rcv, fy)
    assert idx == idx0
    msg, r1 = secure.gb_step_level(
        snd, np.asarray(u), fx, gc_seed, b2a_seed, field, garbler, path=path)
    got = secure.ev_open_level(
        t_rows, fy, np.asarray(msg), B, S, field, idx0, path=path)
    _same(r1, want_r1)
    _same(got, want_open)
    bp = gc_pallas.padded_tests(B)
    _same(
        np.asarray(msg).reshape(planes, bp),
        want_msg.reshape(-1, bp)[_kept_planes(field, path, S)],
    )


@pytest.mark.parametrize("S", [2, 4])
def test_an_fe62_table_is_the_low_half_of_each_block_of_the_old_one(rng, S):
    """(2^S x 2 planes: planes c*4 + {0, 1} of the four-word table, whose
    planes c*4 + {2, 3} were pad words over zeros: the sender's own
    H(Q ^ o_c) words 2 and 3 in clear, for every choice c.)"""
    B = 24
    s = np.asarray(otext.s_to_block(otext.fresh_s_bits()))
    qr = jnp.asarray(rng.integers(0, 2**32, size=(B, S, 4), dtype=np.uint32))
    x = jnp.asarray(rng.integers(0, 2, size=(B, S)).astype(bool))
    v0 = FE62.sample(rng.integers(0, 2**32, size=(B, 4), dtype=np.uint32))
    v1 = FE62.add(v0, FE62.from_int(1))
    new = np.asarray(secure._ot2s_encrypt_packed_xla(
        qr, s, x, secure.field_to_words(FE62, v0),
        secure.field_to_words(FE62, v1), 2, 7,
    )).reshape((1 << S) * 2, -1)
    old = np.asarray(secure._ot2s_encrypt_packed_xla(
        qr, s, x, FE62.to_blocks(v0), FE62.to_blocks(v1), 4, 7,
    )).reshape((1 << S) * 4, -1)
    assert n_msg_planes("ot2s", S, secure.payload_words(FE62)) == len(new)
    pads = np.asarray(otext.ot_hash(
        otext.gf128_comb(qr)[None] ^ otext.gf128_offsets(jnp.asarray(s), S)[:, None, :],
        4, 7, domain=secure._OT2S_DOMAIN,
    ))  # [2^S, B, 4]
    for c in range(1 << S):
        np.testing.assert_array_equal(new[2 * c: 2 * c + 2], old[4 * c: 4 * c + 2])
        # what went: the upper pad words themselves, payload zero under them
        np.testing.assert_array_equal(
            old[4 * c + 2: 4 * c + 4, :B], pads[c, :, 2:].T)


def test_fe62_words_round_trip_at_the_edges():
    """``words_to_field(field_to_words(v))`` is ``v`` (canonical) over
    FE62's edge values: two words hold all 62 bits, and the way back is
    the one ``_bit_reduce`` that ``from_blocks`` gives its low half."""
    p = FE62.P
    raw = np.array(
        [0, 1, (1 << 32) - 1, 1 << 32, (1 << 61) + 12345, p - 1, p, p + 1,
         (1 << 62) - 1, 1 << 62], np.uint64)  # the last four: before canon
    words = secure.field_to_words(FE62, jnp.asarray(raw))
    assert words.shape == (len(raw), 2) and words.dtype == jnp.uint32
    back = np.asarray(secure.words_to_field(FE62, words))
    want = np.array([int(v) % p for v in raw], np.uint64)
    np.testing.assert_array_equal(back, want)
    np.testing.assert_array_equal(np.asarray(FE62.canon(jnp.asarray(raw))), want)
    # the same value as the Block codec's, from the Block's low half
    blocks = FE62.to_blocks(jnp.asarray(raw))
    np.testing.assert_array_equal(np.asarray(blocks[:, :2]), np.asarray(words))
    assert not np.asarray(blocks[:, 2:]).any()
    np.testing.assert_array_equal(np.asarray(FE62.from_blocks(blocks)), back)
    # every bit of two words survives: 2^64 - 1 reduces like any u64
    top = secure.words_to_field(FE62, jnp.full((1, 2), 0xFFFFFFFF, jnp.uint32))
    assert int(FE62.canon(top)[0]) == ((1 << 64) - 1) % p


@pytest.mark.parametrize("field,W,D", [(FE62, 2, 4), (F255, 8, 8)],
                         ids=["FE62", "F255"])
def test_the_draw_keeps_its_stride_whatever_the_wire(field, W, D):
    """``b2a_payload_pair`` draws ``field.SAMPLE_WORDS`` stream words a test
    (FE62: four, 126 uniform bits) and sends ``payload_words``: a chunk
    that seeks to test ``t0`` reads the words a draw from 0 holds there."""
    assert (secure.payload_words(field), field.SAMPLE_WORDS) == (W, D)
    seed = np.arange(4, dtype=np.uint32) + 5
    r1, w0, w1 = secure.b2a_payload_pair(field, seed, 64, 1)
    assert w0.shape == w1.shape == (64, W)
    c1, c0, _ = secure.b2a_payload_pair(field, seed, 32, 1, 32)
    _same(c1, r1[32:])
    _same(c0, w0[32:])
    words = prg.stream_words(jnp.asarray(seed), 64 * D).reshape(64, D)
    r0 = field.sample(words)
    _same(secure.words_to_field(field, w0), field.canon(r0))
    _same(field.canon(r1), field.canon(field.sub(r0, field.from_int(1))))
