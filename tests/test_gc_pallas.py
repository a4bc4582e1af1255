"""Fused Pallas garble/eval kernels vs the XLA engine — bit-exact on the
real chip (same TPU-only gating rationale as test_keygen_pallas.py).

The Pallas pair is the DEFAULT payload-GC engine on real chips
(ops/gc.GC_PALLAS), and it draws the garbler's labels + mask from the
same PRG stream as the XLA form, so the whole planar message must match
word-for-word: tables (tree order), active input labels, decode bits,
payload ciphertexts, and the evaluator's opened payloads.  Shapes
cover the production case (S=2, the 1-dim L∞ string pair), the covid
shape (S=4), an odd tree (S=3), both payload widths (FE62 W=4, F255
W=8), and non-block-multiple batch sizes (the pad path).
tests/test_secure_kernels.py::test_gc_packed_engine_parity is the same
comparison in interpret mode.
"""

import numpy as np
import pytest

from conftest import has_tpu as _has_tpu


pytestmark = pytest.mark.skipif(not _has_tpu(), reason="needs a TPU backend")


@pytest.mark.parametrize(
    "B,S,W", [(1000, 2, 4), (4096, 2, 8), (300, 4, 4), (513, 3, 4)]
)
def test_payload_engines_bit_exact(rng, B, S, W):
    from fuzzyheavyhitters_tpu.ops import gc, gc_pallas

    R = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    R[0] |= 1  # lsb(R) = 1 (free-XOR point-and-permute)
    Y0 = rng.integers(0, 2**32, size=(B, S, 4), dtype=np.uint32)
    seed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    mv0 = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    mv1 = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    idx0 = 977

    msg_x, mx = gc._garble_equality_payload_packed_xla(
        R, Y0, seed, x, mv0, mv1, W, idx0
    )
    msg_p, mp = gc_pallas.garble_equality_payload_packed(
        R, Y0, seed, x, mv0, mv1, W, idx0
    )
    np.testing.assert_array_equal(np.asarray(msg_x), np.asarray(msg_p))
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(mp))
    assert np.asarray(msg_x).size == gc_pallas.packed_msg_words(B, S, W)

    # evaluator: active labels for a random peer string y
    y = rng.integers(0, 2, size=(B, S)).astype(bool)
    evl = np.asarray(Y0) ^ (y[..., None] * np.asarray(R))
    ex, px = gc._eval_equality_payload_packed_xla(msg_x, evl, S, W, idx0)
    ep, pp = gc_pallas.eval_equality_payload_packed(msg_x, evl, W, idx0)
    np.testing.assert_array_equal(np.asarray(ex), np.asarray(ep))
    np.testing.assert_array_equal(np.asarray(px), np.asarray(pp))

    # protocol semantics survive the engine: mask ^ e == [x == y]
    eq = (x == y).all(axis=1)
    np.testing.assert_array_equal(np.asarray(mx) ^ np.asarray(ep), eq)
    np.testing.assert_array_equal(
        np.asarray(pp), np.where(eq[:, None], mv1, mv0)
    )


def test_dispatcher_selects_pallas_on_chip(rng):
    """gc.garble_equality_payload_packed routes through the kernel engine
    on a real chip by default, and the flag restores the XLA path."""
    from fuzzyheavyhitters_tpu.ops import gc

    assert gc.GC_PALLAS and gc._pallas_engine()
    B, S, W = 64, 2, 4
    R = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    R[0] |= 1
    Y0 = rng.integers(0, 2**32, size=(B, S, 4), dtype=np.uint32)
    seed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    mv = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    msg1, m1 = gc.garble_equality_payload_packed(R, Y0, seed, x, mv, mv, W, 0)
    gc.GC_PALLAS = False
    try:
        msg2, m2 = gc.garble_equality_payload_packed(
            R, Y0, seed, x, mv, mv, W, 0
        )
    finally:
        gc.GC_PALLAS = True
    np.testing.assert_array_equal(np.asarray(msg1), np.asarray(msg2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
