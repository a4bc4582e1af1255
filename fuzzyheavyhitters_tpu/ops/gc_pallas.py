"""Fused Pallas garbling/evaluation — the secure level's dominant chip op.

``gc._garble_equality_payload_packed_xla`` / ``gc._eval_..._packed_xla``
(the output-label-b2a flow every secure deployment path ships) are
glue-bound as XLA programs, exactly like the round-4 expand engine was:
the hash math is a handful of ChaCha permutations per test, but every
stacked ``_hash_many`` call, ``_maskw`` select, table stack, and pad XOR
materializes another ``[B, 4]`` tensor in HBM.  Measured on-chip
(round 4 of the plug-in era): garbling cost is nearly flat in
the ChaCha round count — i.e. it is bandwidth, not cipher arithmetic.

This module runs the WHOLE garble (resp. eval) batch as one kernel in
the expand engine's layout family (ops/expand_pallas.py): tests spread
over (row, sublane, lane), every label word a full ``[R_BLK*8, LANES]``
vreg, the AND-tree unrolled over wire planes in-kernel:

- garbler: XNOR relabel, half-gates tree (4 hashes/gate), output decode,
  and the b2a payload ciphertexts under the output-wire labels — all
  without leaving VMEM;
- evaluator: tree eval (2 hashes/gate), decode share, payload-pad open.

Randomness stays OUTSIDE the kernel: the garbler's own labels + mask
bits come from the same ``gc._carve_label_words`` stream draw as the XLA
engine, so both engines are BIT-EXACT for identical inputs — the parity
tests compare the whole planar message (tests/test_secure_kernels.py in
interpret mode, tests/test_gc_pallas.py compiled), and a mid-crawl
engine switch is sound (the wire format does not change).

Ref seam: src/equalitytest.rs:25-191 (the per-core swanky garbler this
batched kernel replaces) driven from src/collect.rs:419-482.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import gc, otext
from .keygen_pallas import LANES, SUB, _chacha16

R_BLK = 8  # row-groups per grid step
GROUP = SUB * LANES  # tests per row


def padded_tests(B: int) -> int:
    """Tests per kernel invocation round up to the grid block (R_BLK
    row-groups of SUB*LANES tests).  The planar WIRE format (the packed
    whole-level message below) carries this padding — a deterministic
    function of B, so both endpoints agree on sizes without negotiation."""
    blk = R_BLK * GROUP
    return B + (-B) % blk


def packed_msg_words(B: int, S: int, W: int) -> int:
    """u32 words of one packed whole-level garbled message (plane order
    tables | gb_labels | decode | cts, each plane ``padded_tests(B)``
    words)."""
    return ((S - 1) * 8 + 4 * S + 1 + 2 * W) * padded_tests(B)


def _sel(bit, a, b):
    """bit ? a : b on u32 vregs (bit is a 0/1 word)."""
    return b ^ ((jnp.uint32(0) - bit) & (a ^ b))


def _gate_hash(label, gid: int, half: int):
    """In-kernel twin of gc._hash_many for ONE label set: label is a list
    of 4 word-vregs; tweak words (gid, half, T2, T3) XOR in before the
    fixed-key ChaCha permutation; returns the first 4 output words."""
    g = jnp.uint32(gid)
    h = jnp.uint32(half)
    blk = [
        label[0] ^ g,
        label[1] ^ h,
        label[2] ^ jnp.uint32(gc._TWEAK2),
        label[3] ^ jnp.uint32(gc._TWEAK3),
    ]
    return _chacha16(blk)[:4]


def _test_idx(sc_ref, pos: int, sh2):
    """Per-test OT pad index, (low, high) word vregs: the test's place in
    the batch plus the batch's 64-bit base (SMEM words ``pos`` and
    ``pos + 1``, otext.index_base) — the planar twin of
    ``otext.index_words(base, arange(B))``."""
    from jax.experimental import pallas as pl

    off = (
        jnp.uint32(pl.program_id(0) * R_BLK * SUB * LANES)
        + jax.lax.broadcasted_iota(jnp.uint32, sh2, 0) * jnp.uint32(LANES)
        + jax.lax.broadcasted_iota(jnp.uint32, sh2, 1)
    )
    return otext.index_words((sc_ref[pos], sc_ref[pos + 1]), off)


def _ot_pad(rows, idx, n_words: int):
    """In-kernel twin of otext.ot_hash: rows = 4 word-vregs, idx = the
    per-test OT index as (low, high) word vregs (:func:`_test_idx`)."""
    blk = [
        rows[0] ^ idx[0],
        rows[1] ^ jnp.uint32(otext._OT_TWEAK1),
        rows[2] ^ jnp.uint32(otext._OT_TWEAK2) ^ idx[1],
        rows[3] ^ jnp.uint32(otext._OT_TWEAK3),
    ]
    return _chacha16(blk)[:n_words]


def _lsb01(w):
    return w & jnp.uint32(1)


def _garble_kernel(S: int, W: int, sc_ref,
                   x0_ref, y0_ref, xb_ref, mask_ref, mv0_ref, mv1_ref,
                   tab_ref, gbl_ref, dec_ref, cts_ref):
    """One row block of B equality tests, all S wire planes.

    Planar blocks (leading plane axis, then [R_BLK, 8, LANES] rows):
    x0/y0 ``u32[4*S]`` planes at index ``s*4 + w``; xb ``u32[S]`` 0/1
    planes; mask ``u32`` 0/1; mv0/mv1 ``u32[W]``; tables
    ``u32[(S-1)*2*4]`` at ``(gate*2 + t)*4 + w`` (tree order, exactly
    _and_tree_garble's concatenation); gbl ``u32[4*S]``; dec ``u32`` 0/1;
    cts ``u32[2*W]`` at ``c*W + w``.  sc_ref (SMEM u32[6]): R words 0..3,
    idx_offset's low and high words at 4 and 5.
    """
    sh2 = (R_BLK * SUB, LANES)
    sh3 = (R_BLK, SUB, LANES)
    R = [sc_ref[w] for w in range(4)]

    # wires: Z0_s = X0_s ^ Y0_s ^ R  (free XNOR relabel)
    wires = [
        [x0_ref[s * 4 + w].reshape(sh2) ^ y0_ref[s * 4 + w].reshape(sh2) ^ R[w]
         for w in range(4)]
        for s in range(S)
    ]
    # half-gates AND-tree, python-unrolled (gate order = _and_tree_garble)
    gate = 0
    while len(wires) > 1:
        k = len(wires) // 2
        nxt = []
        for i in range(k):
            A0, B0 = wires[2 * i], wires[2 * i + 1]
            pa, pb = _lsb01(A0[0]), _lsb01(B0[0])
            HA0 = _gate_hash(A0, gate + i, 0)
            HA1 = _gate_hash([a ^ r for a, r in zip(A0, R)], gate + i, 0)
            HB0 = _gate_hash(B0, gate + i, 1)
            HB1 = _gate_hash([b ^ r for b, r in zip(B0, R)], gate + i, 1)
            pbm = jnp.uint32(0) - pb
            pam = jnp.uint32(0) - pa
            C0 = []
            for w in range(4):
                TG = HA0[w] ^ HA1[w] ^ (pbm & R[w])
                WG = HA0[w] ^ (pam & TG)
                TE = HB0[w] ^ HB1[w] ^ A0[w]
                WE = HB0[w] ^ (pbm & (TE ^ A0[w]))
                tab_ref[((gate + i) * 2 + 0) * 4 + w] = TG.reshape(sh3)
                tab_ref[((gate + i) * 2 + 1) * 4 + w] = TE.reshape(sh3)
                C0.append(WG ^ WE)
            nxt.append(C0)
        gate += k
        wires = nxt + wires[2 * k:]
    out0 = wires[0]

    # output decode bit (pre-masked) + the garbler's active input labels
    dec_ref[0] = (_lsb01(out0[0]) ^ mask_ref[0].reshape(sh2)).reshape(sh3)
    for s in range(S):
        xm = jnp.uint32(0) - xb_ref[s].reshape(sh2)
        for w in range(4):
            gbl_ref[s * 4 + w] = (
                x0_ref[s * 4 + w].reshape(sh2) ^ (xm & R[w])
            ).reshape(sh3)

    # b2a payload ciphertexts under the two output labels (gc.garble_
    # equality_payload): pad_v = H_ot(out0 [^ R], idx); ct slot = select bit
    idx = _test_idx(sc_ref, 4, sh2)
    pad0 = _ot_pad(out0, idx, W)
    pad1 = _ot_pad([o ^ r for o, r in zip(out0, R)], idx, W)
    p = _lsb01(out0[0])
    for w in range(W):
        c0 = mv0_ref[w].reshape(sh2) ^ pad0[w]
        c1 = mv1_ref[w].reshape(sh2) ^ pad1[w]
        cts_ref[0 * W + w] = _sel(p, c1, c0).reshape(sh3)
        cts_ref[1 * W + w] = _sel(p, c0, c1).reshape(sh3)


def _eval_kernel(S: int, W: int, sc_ref,
                 gbl_ref, evl_ref, tab_ref, dec_ref, cts_ref,
                 e_ref, pay_ref):
    """Evaluator twin: active labels in, XOR share + opened payload out."""
    sh2 = (R_BLK * SUB, LANES)
    sh3 = (R_BLK, SUB, LANES)
    wires = [
        [gbl_ref[s * 4 + w].reshape(sh2) ^ evl_ref[s * 4 + w].reshape(sh2)
         for w in range(4)]
        for s in range(S)
    ]
    gate = 0
    while len(wires) > 1:
        k = len(wires) // 2
        nxt = []
        for i in range(k):
            A, B = wires[2 * i], wires[2 * i + 1]
            HA = _gate_hash(A, gate + i, 0)
            HB = _gate_hash(B, gate + i, 1)
            am = jnp.uint32(0) - _lsb01(A[0])
            bm = jnp.uint32(0) - _lsb01(B[0])
            C = []
            for w in range(4):
                TG = tab_ref[((gate + i) * 2 + 0) * 4 + w].reshape(sh2)
                TE = tab_ref[((gate + i) * 2 + 1) * 4 + w].reshape(sh2)
                WG = HA[w] ^ (am & TG)
                WE = HB[w] ^ (bm & (TE ^ A[w]))
                C.append(WG ^ WE)
            nxt.append(C)
        gate += k
        wires = nxt + wires[2 * k:]
    out = wires[0]

    s_bit = _lsb01(out[0])
    e_ref[0] = (s_bit ^ dec_ref[0].reshape(sh2)).reshape(sh3)
    idx = _test_idx(sc_ref, 0, sh2)
    pad = _ot_pad(out, idx, W)
    for w in range(W):
        ct = _sel(s_bit, cts_ref[1 * W + w].reshape(sh2),
                  cts_ref[0 * W + w].reshape(sh2))
        pay_ref[w] = (ct ^ pad[w]).reshape(sh3)


def _planes(a, bp: int):
    """Test-minor [k, B] -> planar u32[k, rows, 8, LANES], zero past B."""
    a = jnp.asarray(a, jnp.uint32)
    k, B = a.shape
    if bp != B:
        a = jnp.concatenate(
            [a, jnp.zeros((k, bp - B), jnp.uint32)], axis=-1
        )
    return a.reshape(k, bp // GROUP, SUB, LANES)


def _planarize(a, B: int, bp: int):
    """[B, ...trailing] -> planar u32[prod(trailing), rows, 8, LANES]."""
    a = jnp.asarray(a, jnp.uint32)
    k = int(np.prod(a.shape[1:])) if a.ndim > 1 else 1
    return _planes(a.reshape(B, k).T, bp)


def _unplanarize(a, B: int):
    """planar u32[k, rows, 8, LANES] -> [B, k]."""
    k = a.shape[0]
    return a.reshape(k, -1).T[:B]


def _garble_call(R, Y0, X0, mask, x_bits, m_v0, m_v1, idx_offset,
                 S: int, W: int, interpret: bool):
    """Shared pallas_call builder: planarize inputs, run the garble
    kernel, return the RAW planar outputs [tables, gb_labels, decode,
    cts] — the packed wire path ravels them as-is."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = x_bits.shape[0]
    bp = padded_tests(B)
    rows = bp // GROUP

    sc = jnp.concatenate([
        jnp.asarray(R, jnp.uint32), otext.index_base(idx_offset),
    ])
    ops = [
        _planarize(X0, B, bp),
        _planarize(Y0, B, bp),
        _planarize(jnp.asarray(x_bits, jnp.uint32), B, bp),
        _planarize(jnp.asarray(mask, jnp.uint32), B, bp),
        _planarize(m_v0, B, bp),
        _planarize(m_v1, B, bp),
    ]
    z = np.int32(0)
    spec = lambda k: pl.BlockSpec((k, R_BLK, SUB, LANES),
                                  lambda j: (z, j, z, z))
    n_tab = (S - 1) * 2 * 4
    # explicit i32 index map: the package enables x64, and Mosaic rejects
    # the i64 indices an auto-generated trivial map would return
    sc_spec = pl.BlockSpec((6,), lambda j: (z,), memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        partial(_garble_kernel, S, W),
        grid=(rows // R_BLK,),
        in_specs=[sc_spec,
                  spec(4 * S), spec(4 * S), spec(S), spec(1),
                  spec(W), spec(W)],
        out_specs=[spec(n_tab), spec(4 * S), spec(1), spec(2 * W)],
        out_shape=[
            jax.ShapeDtypeStruct((n_tab, rows, SUB, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((4 * S, rows, SUB, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((1, rows, SUB, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((2 * W, rows, SUB, LANES), jnp.uint32),
        ],
        interpret=interpret,
    )(sc, *ops)
    return outs


@partial(jax.jit, static_argnames=("S", "W", "interpret"))
def _garble_packed(R, Y0, X0, mask, x_bits, m_v0, m_v1, idx_offset,
                   S: int, W: int, interpret: bool):
    """Whole-level fused garble→pack: the kernel's planar outputs ravel
    straight into the wire buffer — no unplanarize transposes, no
    test-major re-pack; one concatenation is the only copy between the
    garble kernel and the data-plane fetch."""
    outs = _garble_call(R, Y0, X0, mask, x_bits, m_v0, m_v1, idx_offset,
                        S, W, interpret)
    return jnp.concatenate([jnp.ravel(o) for o in outs])


def _eval_call(sc, gbl, evl, tab, dec, cts, S: int, W: int,
               interpret: bool):
    """Shared pallas_call builder for the eval kernel: all inputs already
    planar ``[k, rows, SUB, LANES]``; returns (e planes, payload planes)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = gbl.shape[1]
    n_tab = (S - 1) * 2 * 4
    z = np.int32(0)
    spec = lambda k: pl.BlockSpec((k, R_BLK, SUB, LANES),
                                  lambda j: (z, j, z, z))
    sc_spec = pl.BlockSpec((2,), lambda j: (z,), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        partial(_eval_kernel, S, W),
        grid=(rows // R_BLK,),
        in_specs=[sc_spec,
                  spec(4 * S), spec(4 * S), spec(n_tab), spec(1),
                  spec(2 * W)],
        out_specs=[spec(1), spec(W)],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows, SUB, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((W, rows, SUB, LANES), jnp.uint32),
        ],
        interpret=interpret,
    )(sc, gbl, evl, tab, dec, cts)


def _split_packed(msg, B: int, S: int, W: int):
    """Packed wire buffer -> the four planar plane stacks (pure reshapes
    of contiguous slices — no transposes)."""
    bp = padded_tests(B)
    rows = bp // GROUP
    n_tab = (S - 1) * 2 * 4
    sizes = [n_tab, 4 * S, 1, 2 * W]
    parts, base = [], 0
    for k in sizes:
        parts.append(msg[base : base + k * bp].reshape(k, rows, SUB, LANES))
        base += k * bp
    return parts


@partial(jax.jit, static_argnames=("S", "W", "interpret"))
def _eval_packed(msg, ev_labels, idx_offset, S: int, W: int,
                 interpret: bool):
    """Whole-level fused unpack→eval: the wire buffer's planes feed the
    kernel directly (reshape-slices, no unplanarize) — only the
    evaluator's OWN labels planarize, once."""
    B = ev_labels.shape[0]
    bp = padded_tests(B)
    tab, gbl, dec, cts = _split_packed(jnp.asarray(msg, jnp.uint32), B, S, W)
    sc = otext.index_base(idx_offset)
    outs = _eval_call(
        sc, gbl, _planarize(ev_labels, B, bp), tab, dec, cts,
        S, W, interpret,
    )
    e = _unplanarize(outs[0], B).reshape(B) != 0
    pay = _unplanarize(outs[1], B).reshape(B, W)
    return e, pay


def garble_equality_payload_packed(R, Y0, seed, x_bits, m_v0, m_v1,
                                   n_words: int, idx_offset,
                                   interpret: bool = False):
    """Whole-level garble with the PACKED planar wire output: returns
    (msg u32[packed_msg_words(B, S, W)], mask bool[B]).  The message is
    the kernel's plane stack raveled in place — no intermediate label
    tensor ever re-transposes to test-major layout between garbling and
    the data-plane fetch.  Byte-identical to the XLA twin
    (gc._garble_equality_payload_packed_xla)."""
    x_bits = jnp.asarray(x_bits, bool)
    B, S = x_bits.shape
    if S < 2:
        raise ValueError("gc_pallas requires S >= 2 wire strings")
    _, (X0,), mask = gc._carve_label_words(seed, B, S, 1, with_r=False)
    msg = _garble_packed(
        jnp.asarray(R, jnp.uint32), jnp.asarray(Y0, jnp.uint32), X0, mask,
        x_bits, jnp.asarray(m_v0, jnp.uint32), jnp.asarray(m_v1, jnp.uint32),
        idx_offset, S, n_words, interpret,
    )
    return msg, mask


def eval_equality_payload_packed(msg, ev_labels, n_words: int, idx_offset,
                                 interpret: bool = False):
    """Whole-level unpack→eval twin: consumes the packed planar wire
    buffer directly.  Returns (e bool[B], payload u32[B, n_words])."""
    ev_labels = jnp.asarray(ev_labels, jnp.uint32)
    B, S = ev_labels.shape[:2]
    if S < 2:
        raise ValueError("gc_pallas requires S >= 2 wire strings")
    return _eval_packed(msg, ev_labels, idx_offset, S, n_words, interpret)


# -- row-sharded (shard_map) entries ----------------------------------------
#
# Under the multi-chip kernel stage (parallel/kernel_shard.py) each mesh
# shard garbles/evaluates its own whole-planar-block slice of the level:
# inputs arrive ALREADY sliced and zero-padded (labels + mask from
# gc._carve_label_words_shard, Y0 from the row-sharded extension), and
# ``idx_offset`` is the session base PLUS the shard's global test offset
# — a TRACED value (lax.axis_index), which the kernels already accept
# (it rides SMEM).  Because each shard's extent is a whole number of
# R_BLK*GROUP blocks, the pallas grid and the planar layout need no
# per-shard padding, and the per-shard buffers concatenate along the row
# axis into the byte-identical single-device wire.


def garble_packed_planes(R, Y0, X0, mask, x_bits, m_v0, m_v1,
                         n_words: int, idx_offset, interpret: bool = False):
    """Presliced packed garble (the per-shard form of
    :func:`garble_equality_payload_packed`): the caller supplies the
    garbler labels + mask instead of a seed.  Returns the raveled planar
    buffer for this extent."""
    return _garble_packed(
        jnp.asarray(R, jnp.uint32), jnp.asarray(Y0, jnp.uint32),
        jnp.asarray(X0, jnp.uint32), jnp.asarray(mask, jnp.uint32),
        jnp.asarray(x_bits, bool), jnp.asarray(m_v0, jnp.uint32),
        jnp.asarray(m_v1, jnp.uint32), idx_offset,
        jnp.asarray(x_bits, bool).shape[1], n_words, interpret,
    )
