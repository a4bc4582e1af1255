"""Row-sharded secure kernel stage (parallel/kernel_shard.py): the
byte-identical-wire contract.

The multi-chip kernel stage partitions the whole-level planar test batch
along its row/block axis and runs IKNP extension + equality kernels +
b2a per mesh shard.  The contract under test: at EVERY shard count the
wire — the receiver's u-matrix and the sender's planar frame — is
byte-for-byte the single-device output (pad region included), the b2a
share values match per test, and the OT session cursors stay in
lockstep with a single-device peer.  Exercised on the conftest 8-device
CPU mesh; the Pallas engines run under shard_map in interpret mode
against the XLA twins (the per-shard parity oracle).
"""

import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from fuzzyheavyhitters_tpu.ops import baseot, gc, otext
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.parallel import kernel_shard
from fuzzyheavyhitters_tpu.protocol import secure

# 8 planar blocks with a real pad region in play: every shard count in
# {2, 4, 8} divides the block count, the last shard carries the global
# pad slots, and B*S straddles a u-matrix word boundary
B = 8 * kernel_shard.BLOCK - 1234
S = 2  # n_dims = 1: the cheapest planar shape (the width is a static
# of every program; wider S re-runs the same sharding math per plane)


@pytest.fixture(scope="module")
def ot_material():
    s_bits = otext.fresh_s_bits()
    seeds0, seeds1, chosen = baseot.exchange(s_bits)
    return s_bits, seeds0, seeds1, chosen


def _pair(m):
    s_bits, seeds0, seeds1, chosen = m
    return (
        otext.OtExtSender(s_bits, chosen),
        otext.OtExtReceiver(seeds0, seeds1),
    )


@pytest.fixture(scope="module")
def flat_bits():
    rng = np.random.default_rng(0)
    return rng.integers(0, 2, size=(B, S)).astype(bool)


_SEEDZ = np.zeros(4, np.uint32)
GSEED = secure.derive_seed(_SEEDZ, 1, 0)
BSEED = secure.derive_seed(_SEEDZ, 2, 0)

# single-device references, one per (path, field) — shared across the
# shard-count legs (the reference is the expensive half of each case)
_refs: dict = {}


def _reference(m, flat, path, field):
    key = (path, field.__name__)
    if key not in _refs:
        snd, rcv = _pair(m)
        u, t_rows, idx0 = secure.ev_step1_fused(rcv, flat)
        u_np = np.asarray(u)
        msg, vals_s = secure.gb_step_level(
            snd, u_np, flat, GSEED, BSEED, field, 0, path=path
        )
        msg_np = np.asarray(msg)
        vals_r = secure.ev_open_level(
            t_rows, flat, msg_np, B, S, field, idx0, path=path
        )
        _refs[key] = (
            u_np, msg_np,
            np.asarray(field.canon(vals_s)), np.asarray(field.canon(vals_r)),
            snd.consumed, snd.stream_offset, rcv.consumed,
        )
    return _refs[key]


def _sharded_flat(ks, flat):
    fp = np.zeros((ks.bp, S), bool)
    fp[:B] = flat
    return jax.device_put(fp, ks.sharding(P(kernel_shard.DATA, None)))


@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
@pytest.mark.parametrize("path", ["ot2s", "gc"])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_wire_byte_identity(k, path, field, ot_material, flat_bits):
    """THE kernel-sharding acceptance: u-matrix and planar frame
    byte-identical to the single-device wire at shards {1, 2, 4, 8} on
    both equality paths and both fields, share values equal per test,
    session cursors in lockstep."""
    u_ref, msg_ref, vs_ref, vr_ref, s_cons, s_off, r_cons = _reference(
        ot_material, flat_bits, path, field
    )
    if k == 1:
        # k = 1 IS the reference path (bind refuses a 1-shard kernel
        # mesh; the server keeps the gather layout) — pin the refusal
        assert kernel_shard.bind(
            tuple(jax.devices()[:2]), B, S, budget=1
        ) is None
        return
    ks = kernel_shard.bind(tuple(jax.devices()[:k]), B, S, budget=k)
    assert ks is not None and ks.k == k
    snd, rcv = _pair(ot_material)
    fdev = _sharded_flat(ks, flat_bits)
    u_np, msg_np, vals_s, vals_r = kernel_shard.run_level_pair(
        ks, snd, rcv, fdev, fdev, GSEED, BSEED, field, 0, path
    )
    np.testing.assert_array_equal(u_np, u_ref)
    np.testing.assert_array_equal(msg_np, msg_ref)
    np.testing.assert_array_equal(
        np.asarray(field.canon(vals_s))[:B], vs_ref
    )
    np.testing.assert_array_equal(
        np.asarray(field.canon(vals_r))[:B], vr_ref
    )
    # lockstep: a sharded endpoint must present the same session cursors
    # as a single-device peer (the stream reads past the cursor for pad
    # rows never consume)
    assert snd.consumed == s_cons and snd.stream_offset == s_off
    assert rcv.consumed == r_cons


@pytest.mark.parametrize("path", ["ot2s", "gc"])
def test_pallas_under_shard_map_parity(path, ot_material):
    """shard_map-Pallas vs XLA-twin per-shard parity (interpret mode):
    the fused planar kernels run per shard under shard_map and emit the
    byte-identical wire — the engine contract of gc_pallas/otext_pallas
    extended to the sharded stage."""
    rng = np.random.default_rng(1)
    b = 2 * kernel_shard.BLOCK
    flat = rng.integers(0, 2, size=(b, S)).astype(bool)
    ks = kernel_shard.bind(tuple(jax.devices()[:2]), b, S, budget=2)
    fdev = jax.device_put(flat, ks.sharding(P(kernel_shard.DATA, None)))
    outs = {}
    for eng in ("xla", "pallas_interpret"):
        snd, rcv = _pair(ot_material)
        u_np, msg_np, _, vals_r = kernel_shard.run_level_pair(
            ks, snd, rcv, fdev, fdev, GSEED, BSEED, FE62, 0, path,
            engine=eng,
        )
        outs[eng] = (u_np, msg_np, np.asarray(FE62.canon(vals_r))[:b])
    for got, want in zip(outs["pallas_interpret"], outs["xla"]):
        np.testing.assert_array_equal(got, want)


def test_extend_rows_match_full_extension(ot_material):
    """Row-sharded extension slices: ``sender/receiver_extend_rows``
    reproduce exactly rows [row0, row0 + m) of a full extend — the
    32-word/16-block CTR alignment the planar shard layout guarantees."""
    m_total = 4096
    flat = np.zeros(m_total, bool)
    flat[::3] = True
    snd, rcv = _pair(ot_material)
    u, t = rcv.extend(flat)
    q = snd.extend(m_total, np.asarray(u))
    snd2, rcv2 = _pair(ot_material)
    for row0 in (0, 512, 2048):
        m = 1024
        w0 = row0 // 32
        u_slice, t_slice = otext.receiver_extend_rows(
            *rcv2.shard_state, flat[row0 : row0 + m], 0, row0, m
        )
        np.testing.assert_array_equal(
            np.asarray(t_slice), np.asarray(t)[row0 : row0 + m]
        )
        np.testing.assert_array_equal(
            np.asarray(u_slice), np.asarray(u)[:, w0 : w0 + m // 32]
        )
        q_slice = otext.sender_extend_rows(
            *snd2.shard_state, np.asarray(u)[:, w0 : w0 + m // 32], 0,
            row0, m,
        )
        np.testing.assert_array_equal(
            np.asarray(q_slice), np.asarray(q)[row0 : row0 + m]
        )


def test_carve_label_words_shard_slices():
    """Shard label/mask carving seeks the CTR stream to the exact words
    of the full draw — including the mask region's static intra-block
    offset (an odd B puts it mid-block) and the zero pad tests."""
    b, s = 20001, 2  # B*S*4 % 16 = 8: mask region starts mid-block
    bp = 3 * kernel_shard.BLOCK
    seed = np.arange(4, dtype=np.uint32)
    _, (X0,), mask = gc._carve_label_words(seed, b, s, 1, with_r=False)
    X0, mask = np.asarray(X0), np.asarray(mask)
    for t0, bloc in ((0, kernel_shard.BLOCK), (kernel_shard.BLOCK, 2 * kernel_shard.BLOCK)):
        X0s, masks = gc._carve_label_words_shard(seed, b, s, t0, bloc)
        X0s, masks = np.asarray(X0s), np.asarray(masks)
        hi = min(t0 + bloc, b)
        np.testing.assert_array_equal(X0s[: hi - t0], X0[t0:hi])
        np.testing.assert_array_equal(masks[: hi - t0], mask[t0:hi])
        # pad tests carve to zero (the wire's planar pad contract)
        assert not X0s[hi - t0 :].any() and not masks[hi - t0 :].any()
    assert t0 + bloc == bp  # the loop covered the whole padded frame
