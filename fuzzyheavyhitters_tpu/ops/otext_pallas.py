"""Fused Pallas OT-extension payload kernels (the 1-of-2^S hot stage).

Where the per-level OT cost actually lives after the whole-level
restructure (protocol/secure.py): not in the IKNP matrix itself — the
column PRG, u-XOR, and packed butterfly transpose already run as ONE
jitted XLA program per extension (``otext._receiver_extend`` /
``_sender_extend``) — but in the chosen-payload stage that multiplies per
test: the 1-of-2^S equality OT hashes 2^S pads per test and builds the
ciphertext table, which as glue-bound XLA ops materializes a fresh
``[2^S, B, ...]`` tensor per step (comb, offsets broadcast, pads,
select, XOR — five HBM passes at the flagship batch).

The butterfly transpose stays in XLA deliberately: it is a cross-lane
bit permutation (32×32 tile shuffles), which Mosaic's vreg model prices
as relayouts per stage, while the measured packed-XLA form is already
~5x cheaper than the naive transpose and a single fused program.  The
kernels here take the transposed rows and run everything AFTER them —
GF(2^128) row-combine (Horner doubling ladder), 2^S offset pads, the
payload select, and the ciphertext XOR — in one VMEM-resident pass, in
the expand/gc_pallas planar layout family (tests spread over
(row, sublane, lane); every 128-bit block word a full vreg plane).

Engine contract, exactly like ops/gc_pallas.py: the XLA twins in
protocol/secure.py (``ot2s_encrypt``/``ot2s_decrypt``) compute identical
bits — the planar wire buffers are word-for-word engine-independent, and
tests/test_secure_kernels.py pins parity in interpret mode on CPU.

Row-sharded use (parallel/kernel_shard.py): both kernels are presliced-
input programs already — each mesh shard calls :func:`ot2s_encrypt` /
:func:`ot2s_decrypt` on its own whole-planar-block slice of the level
under ``shard_map``, with ``idx_offset`` = session base + the shard's
global test offset (a traced ``lax.axis_index`` expression; it rides
SMEM).  Shard extents are whole R_BLK*GROUP blocks, so ``padded_tests``
is the identity per shard and the per-shard planar buffers concatenate
along the row axis into the byte-identical single-device wire.

Ref seam: ocelot's chosen-payload OT consumption in src/collect.rs:439-471,
generalized from per-wire 1-of-2 to the per-test 1-of-2^S equality table.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import otext
from .gc_pallas import (
    GROUP, R_BLK, _ot_pad, _planes, _test_idx, padded_tests,
)
from .keygen_pallas import LANES, SUB


def _dbl(a):
    """In-kernel gf128_double on a 4-word-vreg list (otext.gf128_double's
    shift-with-carry, word-planar form)."""
    hi = a[3] >> 31
    out = [(a[0] << 1) ^ (hi * jnp.uint32(0x87))]
    for k in (1, 2, 3):
        out.append((a[k] << 1) | (a[k - 1] >> 31))
    return out


def _comb(rows):
    """In-kernel gf128_comb over a list of 4-word-vreg labels (Horner)."""
    acc = rows[-1]
    for j in range(len(rows) - 2, -1, -1):
        acc = [c ^ r for c, r in zip(_dbl(acc), rows[j])]
    return acc


def _ot2s_enc_kernel(S: int, W: int, sc_ref,
                     q_ref, x_ref, mv0_ref, mv1_ref, cts_ref):
    """Grid step (row block j, choice c): comb the S Q-rows, hash choice
    c's offset pad, select payload m_{[x == c]}, XOR — writing choice
    c's W ciphertext planes.  The choice axis rides the GRID (not an
    unrolled in-kernel loop): one hash per kernel body keeps the program
    2^S times smaller (an unrolled S=6 body — 64 inlined ChaCha
    permutations — compiled pathologically slowly), and the q/x/m block
    index maps are constant along c, so the inputs stay VMEM-resident
    across the inner c steps (one HBM read per row block, not 2^S).

    Planar blocks: q ``u32[4*S]`` planes at ``s*4 + w``; x ``u32[S]`` 0/1
    planes; mv0/mv1 ``u32[W]``; out block = choice c's ``u32[W]`` planes
    of the ``u32[2^S * W]``-plane ciphertext stack (plane ``c*W + w``).
    sc_ref (SMEM u32[4*2^S + 2]): the offset table ``o_c`` words at
    ``4*c + w`` (otext.gf128_offsets order), then idx_offset's low and
    high words."""
    from jax.experimental import pallas as pl

    sh2 = (R_BLK * SUB, LANES)
    sh3 = (R_BLK, SUB, LANES)
    c = pl.program_id(1)
    rows = [
        [q_ref[s * 4 + w].reshape(sh2) for w in range(4)] for s in range(S)
    ]
    comb = _comb(rows)
    x_int = x_ref[0].reshape(sh2)
    for j in range(1, S):
        x_int = x_int | (x_ref[j].reshape(sh2) << j)
    idx = _test_idx(sc_ref, 4 * (1 << S), sh2)
    off = [sc_ref[4 * c + w] for w in range(4)]
    pad = _ot_pad([cw ^ ow for cw, ow in zip(comb, off)], idx, W)
    eqm = jnp.uint32(0) - (x_int == c.astype(jnp.uint32)).astype(jnp.uint32)
    for w in range(W):
        m0 = mv0_ref[w].reshape(sh2)
        m1 = mv1_ref[w].reshape(sh2)
        mw = m0 ^ (eqm & (m0 ^ m1))  # x == c ? m1 : m0
        cts_ref[w] = (mw ^ pad[w]).reshape(sh3)


def _ot2s_dec_kernel(S: int, W: int, sc_ref,
                     t_ref, y_ref, cts_ref, pay_ref):
    """Receiver twin: comb the T-rows (= Q-comb ^ o_y), one pad, one-hot
    XOR-select of ciphertext slot y, open.  sc_ref (SMEM u32[2]): idx0's
    low and high words.

    Like the encrypt kernel, the 2^S choice axis rides the GRID: the cts
    input block is ONE choice's W planes per step (at S=6/W=8 the full
    stack is 2^S·W = 512 planes — 16 MiB per block, past VMEM), and the
    output block's index map is constant along c, so the payload planes
    stay VMEM-resident and XOR-accumulate the one-hot select across the
    inner c steps; the final step opens the pad."""
    from jax.experimental import pallas as pl

    sh2 = (R_BLK * SUB, LANES)
    sh3 = (R_BLK, SUB, LANES)
    c = pl.program_id(1)
    # program_id-derived values hoisted OUT of the pl.when branches
    # (interpret mode resolves the primitive only at kernel top level)
    idx = _test_idx(sc_ref, 0, sh2)
    y_int = y_ref[0].reshape(sh2)
    for j in range(1, S):
        y_int = y_int | (y_ref[j].reshape(sh2) << j)
    eqm = jnp.uint32(0) - (y_int == c.astype(jnp.uint32)).astype(jnp.uint32)
    contrib = [eqm & cts_ref[w].reshape(sh2) for w in range(W)]

    @pl.when(c == 0)
    def _init():
        for w in range(W):
            pay_ref[w] = contrib[w].reshape(sh3)

    @pl.when(c != 0)
    def _accumulate():
        # exactly one c matches per test, so XOR-accumulation selects it
        for w in range(W):
            pay_ref[w] = (
                pay_ref[w].reshape(sh2) ^ contrib[w]
            ).reshape(sh3)

    @pl.when(c == (1 << S) - 1)
    def _open():
        rows = [
            [t_ref[s * 4 + w].reshape(sh2) for w in range(4)]
            for s in range(S)
        ]
        pad = _ot_pad(_comb(rows), idx, W)
        for w in range(W):
            pay_ref[w] = (pay_ref[w].reshape(sh2) ^ pad[w]).reshape(sh3)


@partial(jax.jit, static_argnames=("S", "W", "domain", "interpret"))
def _enc_planes(q, s_block, x, m_v0, m_v1, idx_offset,
                S: int, W: int, domain: int, interpret: bool):
    """The sender's table from TEST-MINOR operands: q ``u32[S*4, B]``
    (plane ``s*4 + w``, the extension's ``otext._transpose_planes``),
    x ``[S, B]`` 0/1, m_v0 / m_v1 ``u32[W, B]``.  No transposition on
    the way into the kernel: pad to whole blocks, cut the test axis
    into (row, sublane, lane)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = x.shape[1]
    bp = padded_tests(B)
    rows = bp // GROUP
    # gc_pallas._ot_pad hashes with the FIXED tweak word 1; the XLA
    # ot_hash XORs ``domain`` into that same word.  The offset table XORs
    # into the identical hash-input word, so folding the domain into
    # word 1 of every offset (c = 0's offset becomes (0, domain, 0, 0))
    # reproduces ot_hash(comb ^ o_c, domain=domain) bit-exactly.
    offs = otext.gf128_offsets(s_block, S)
    offs = offs.at[:, 1].set(offs[:, 1] ^ jnp.uint32(domain))
    sc = jnp.concatenate([jnp.ravel(offs), otext.index_base(idx_offset)])
    ops = [_planes(a, bp) for a in (q, x, m_v0, m_v1)]
    z = np.int32(0)
    spec = lambda k: pl.BlockSpec((k, R_BLK, SUB, LANES),
                                  lambda j, c: (z, j, z, z))
    sc_spec = pl.BlockSpec(
        (4 * (1 << S) + 2,), lambda j, c: (z,), memory_space=pltpu.SMEM
    )
    n_cts = (1 << S) * W
    # choice axis on the grid (innermost): the out block's plane index
    # follows c while every input block index stays put — Pallas then
    # keeps the inputs VMEM-resident across the 2^S inner steps
    out_spec = pl.BlockSpec((W, R_BLK, SUB, LANES),
                            lambda j, c: (c, j, z, z))
    (cts,) = pl.pallas_call(
        partial(_ot2s_enc_kernel, S, W),
        grid=(rows // R_BLK, 1 << S),
        in_specs=[sc_spec, spec(4 * S), spec(S), spec(W), spec(W)],
        out_specs=[out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_cts, rows, SUB, LANES), jnp.uint32)
        ],
        interpret=interpret,
    )(sc, *ops)
    return jnp.ravel(cts)


def _test_minor(a):
    """[B, ...trailing] -> [prod(trailing), B] (``_planarize``'s order)."""
    a = jnp.asarray(a, jnp.uint32)
    return a.reshape(a.shape[0], -1).T


@partial(jax.jit, static_argnames=("S", "W", "domain", "interpret"))
def _enc_planar(q_rows, s_block, x_bits, m_v0, m_v1, idx_offset,
                S: int, W: int, domain: int, interpret: bool):
    return _enc_planes(
        _test_minor(q_rows), s_block, _test_minor(x_bits),
        _test_minor(m_v0), _test_minor(m_v1), idx_offset, S, W, domain,
        interpret,
    )


@partial(jax.jit, static_argnames=("S", "W", "domain", "interpret"))
def _dec_planes(t, y, msg, idx_offset,
                S: int, W: int, domain: int, interpret: bool):
    """The receiver's open from TEST-MINOR operands (see
    :func:`_enc_planes`): t ``u32[S*4, B]``, y ``[S, B]`` 0/1.  Returns
    the payload words test-minor too, ``u32[W, B]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = y.shape[1]
    bp = padded_tests(B)
    rows = bp // GROUP
    n_cts = (1 << S) * W
    sc = otext.index_base(idx_offset)
    # receiver-side domain fold: the kernel hashes comb(t) under the
    # fixed tweak; comb is linear with coefficient x^0 = 1 on row 0, so
    # XORing the domain into row 0's word 1 (plane 1) lands it on comb's
    # word 1 — the same place the XLA ot_hash tweak puts it.
    t = jnp.asarray(t, jnp.uint32)
    t = t.at[1].set(t[1] ^ jnp.uint32(domain))
    ops = [
        _planes(t, bp), _planes(y, bp),
        jnp.asarray(msg, jnp.uint32).reshape(n_cts, rows, SUB, LANES),
    ]
    z = np.int32(0)
    spec = lambda k: pl.BlockSpec((k, R_BLK, SUB, LANES),
                                  lambda j, c: (z, j, z, z))
    sc_spec = pl.BlockSpec((2,), lambda j, c: (z,),
                           memory_space=pltpu.SMEM)
    # choice axis on the grid: the cts block follows c (one choice's W
    # planes in VMEM at a time), the payload output block does not (it
    # accumulates across the inner c steps)
    cts_spec = pl.BlockSpec((W, R_BLK, SUB, LANES),
                            lambda j, c: (c, j, z, z))
    (pay,) = pl.pallas_call(
        partial(_ot2s_dec_kernel, S, W),
        grid=(rows // R_BLK, 1 << S),
        in_specs=[sc_spec, spec(4 * S), spec(S), cts_spec],
        out_specs=[spec(W)],
        out_shape=[
            jax.ShapeDtypeStruct((W, rows, SUB, LANES), jnp.uint32)
        ],
        interpret=interpret,
    )(sc, *ops)
    return pay.reshape(W, -1)[:, :B]


@partial(jax.jit, static_argnames=("S", "W", "domain", "interpret"))
def _dec_planar(t_rows, y_bits, msg, idx_offset,
                S: int, W: int, domain: int, interpret: bool):
    return _dec_planes(
        _test_minor(t_rows), _test_minor(y_bits), msg, idx_offset, S, W,
        domain, interpret,
    ).T


def ot2s_encrypt(q_rows, s_block, x_flat, m_v0, m_v1, n_words: int,
                 idx_offset, domain: int, interpret: bool = False):
    """Planar-wire 1-of-2^S sender table — bit-exact with the XLA form in
    protocol/secure.py.  Returns the raveled planar ciphertext planes
    ``u32[(2^S·n_words)·padded_tests(B)]``."""
    q_rows = jnp.asarray(q_rows, jnp.uint32)
    B, S = q_rows.shape[0], q_rows.shape[1]
    return _enc_planar(
        q_rows, jnp.asarray(s_block, jnp.uint32), jnp.asarray(x_flat, bool),
        jnp.asarray(m_v0, jnp.uint32), jnp.asarray(m_v1, jnp.uint32),
        idx_offset, S, n_words, domain, interpret,
    )


def ot2s_decrypt(t_rows, y_flat, msg, n_words: int, idx_offset,
                 domain: int, interpret: bool = False):
    """Planar-wire 1-of-2^S receiver open — returns uint32[B, n_words]."""
    t_rows = jnp.asarray(t_rows, jnp.uint32)
    B, S = t_rows.shape[0], t_rows.shape[1]
    return _dec_planar(
        t_rows, jnp.asarray(y_flat, bool), jnp.asarray(msg, jnp.uint32),
        idx_offset, S, n_words, domain, interpret,
    )


def ot2s_encrypt_planes(q, s_block, x, m_v0, m_v1, n_words: int,
                        idx_offset, domain: int, interpret: bool = False):
    """:func:`ot2s_encrypt` from test-minor operands (a chunk's step,
    protocol/secure.py): q ``u32[S*4, B]`` as
    ``otext._transpose_planes`` leaves the extension's rows, x
    ``[S, B]``, payloads ``u32[n_words, B]``.  The same planar wire."""
    return _enc_planes(
        q, jnp.asarray(s_block, jnp.uint32), x, m_v0, m_v1, idx_offset,
        x.shape[0], n_words, domain, interpret,
    )


def ot2s_decrypt_planes(t, y, msg, n_words: int, idx_offset, domain: int,
                        interpret: bool = False):
    """:func:`ot2s_decrypt` from test-minor operands, returning the
    payload words test-minor too: ``u32[n_words, B]``."""
    return _dec_planes(
        t, y, jnp.asarray(msg, jnp.uint32), idx_offset, y.shape[0],
        n_words, domain, interpret,
    )
