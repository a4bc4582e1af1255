"""IKNP OT extension as batched device tensor ops.

The reference consumes OT extension through ocelot's ``AlszSender`` /
``AlszReceiver`` (ref: src/collect.rs:10-11, 454-461) — per-thread Rust
state machines over TCP channels.  The TPU-native redesign observes that the
whole IKNP03 extension is three tensor primitives — column PRG expansion,
bit-matrix transpose, and XOR — plus one correlation-robust hash, all of
which batch perfectly on device:

- 128 **base OTs** (ops/baseot.py, Chou-Orlandi on the host) seed the
  extension; the extension *sender* played base-OT *receiver* with its
  secret choice vector ``s`` and vice versa (the standard IKNP role flip).
- To extend to ``m`` OTs: the receiver, with choice bits ``r``, derives
  column streams ``t_i = G(k0_i)`` and sends ``u_i = t_i ^ G(k1_i) ^ r``;
  the sender derives ``q_i = G(k_{s_i}) ^ s_i·u_i``.  Row-wise,
  ``Q_j = T_j ^ r_j·s`` — a 1-of-2 correlated OT on 128-bit rows.
- **Δ-OT view** (no hash): ``T_j`` IS the receiver's choice-selected label
  when the sender uses ``Q_j`` as its zero-label with global offset ``s``.
  The GC layer exploits this by setting its free-XOR offset ``R = s`` —
  evaluator input labels then arrive with zero extra messages (ops/gc.py).
- **Chosen-payload view**: pads ``H(j, Q_j)`` / ``H(j, Q_j ^ s)`` encrypt
  arbitrary per-OT payloads (the b2a field blocks of collect.rs:439-471);
  the receiver recovers its choice with ``H(j, T_j)``.  H is the fixed-key
  ChaCha hash (ops/prg.py) with an OT-specific tweak.

Semi-honest security, matching the reference's use (its Alsz instantiation
is the malicious-OT variant of IKNP, but the surrounding protocol is
semi-honest; ref: equalitytest.rs uses twopac semi-honest garbling).

Both parties must call ``extend`` the same number of times with the same
``m`` — the PRG stream counters advance in lockstep (like the shared
channel position in the reference's ocelot session).
"""

from __future__ import annotations

import math
import secrets
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import taint_guard
from . import baseot, prg

KAPPA = 128  # security parameter: base-OT count == row width in bits

# OT-hash tweak constants (words 1..3); word 0 carries the OT index's low
# word, and its high word is XORed into word 2 (:func:`index_words`), so
# an index under 2^32 hashes exactly as it did when the index was 32 bits
# wide.  Distinct from the GC gate-hash tweak (ops/gc.py) by construction.
_OT_TWEAK1 = 0x4F545F31
_OT_TWEAK2 = 0xB7E15162
_OT_TWEAK3 = 0x8AED2A6B


def pack_bits(bits: jax.Array) -> jax.Array:
    """bool[..., m] -> uint32[..., ceil(m/32)] little-endian bit packing."""
    bits = jnp.asarray(bits, bool)
    m = bits.shape[-1]
    w = -(-m // 32)
    pad = jnp.zeros(bits.shape[:-1] + (w * 32 - m,), bool)
    b = jnp.concatenate([bits, pad], axis=-1).reshape(bits.shape[:-1] + (w, 32))
    return jnp.sum(
        b.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32), axis=-1,
        dtype=jnp.uint32,
    )


def unpack_bits(words: jax.Array, m: int) -> jax.Array:
    """uint32[..., w] -> bool[..., m] (inverse of :func:`pack_bits`)."""
    words = jnp.asarray(words, jnp.uint32)
    idx = jnp.arange(m)
    return ((words[..., idx // 32] >> (idx % 32).astype(jnp.uint32)) & 1).astype(bool)


def _butterfly(cols: jax.Array) -> jax.Array:
    """Column-major bit matrix uint32[128, W] (bit j of cols[i] is entry
    (row j, column i)) -> uint32[4, 32, W]: ``x[k, r, wj]`` is word k of
    the packed 128-bit row ``j = wj*32 + r``.

    PACKED 32x32 butterfly transpose (the Hacker's Delight 7-3 network,
    little-endian orientation, vectorized over all word tiles): 5 stages
    of shift/mask/XOR on u32 words.  The data never unpacks to booleans —
    the naive unpack->T->pack form materialized a [128, m] bool matrix
    (128 MB at the 1M-OT production batch) and was the single most
    expensive op of the secure level (measured: extension 31.8 ms of a
    44 ms level at m=1M; packed form ~5x cheaper end-to-end).
    """
    w = cols.shape[1]
    x = jnp.asarray(cols, jnp.uint32).reshape(4, 32, w)
    for j, msk in ((16, 0x0000FFFF), (8, 0x00FF00FF), (4, 0x0F0F0F0F),
                   (2, 0x33333333), (1, 0x55555555)):
        # pair word k (bit-rows) with word k+j; swap the complementary
        # j-wide bit blocks between them
        x = x.reshape(4, 32 // (2 * j), 2, j, w)
        a0, a1 = x[:, :, 0], x[:, :, 1]
        t = ((a0 >> j) ^ a1) & jnp.uint32(msk)
        a0 = a0 ^ (t << j)
        a1 = a1 ^ t
        x = jnp.stack([a0, a1], axis=2).reshape(4, 32, w)
    return x


@partial(jax.jit, static_argnames=("m",))
def _transpose_pack(cols: jax.Array, m: int) -> jax.Array:
    """Column-major bit matrix -> packed 128-bit rows uint32[m, 4]: row
    j's 128 column bits packed into 4 words (:func:`_butterfly`)."""
    w = cols.shape[1]
    # x[k, r, wj] -> out[j = wj*32 + r, word k]
    return jnp.transpose(_butterfly(cols), (2, 1, 0)).reshape(w * 32, 4)[:m]


# Extension rows :func:`_transpose_planes` turns at a time.  Its
# de-interleave's last transposition has a minor dimension of 32g / S
# words and a butterfly stage fed straight from the column streams one of
# 16, which the TPU pads to its 128 lanes: 4S / g and 8 times the rows'
# bytes.  The compiler keeps such a temporary in VMEM (128 MiB on a v5e)
# only while it fits: at a chunk of 1M rows (S = 2) or 512K (S = 4) it
# was 128 MiB itself and went through HBM (PERF.md section 6, PR 47).  A
# tile's is 4 MiB at S = 2 and 8 at S = 4, whatever the chunk.
PLANE_TILE_ROWS = 1 << 15


def _deinterleave(x: jax.Array, g: int, S: int) -> jax.Array:
    """``x[k, r, wj]`` of :func:`_butterfly` (``wj`` a multiple of ``g``
    words) -> uint32[S*4, wj * 32 // S], plane ``s*4 + k``."""
    w = x.shape[2]
    # x[k, r, wj] -> [k, wj // g, (wj % g)*32 + r = t_lo*S + s]
    #             -> [s, k, t = (wj // g)*(32g/S) + t_lo]
    x = jnp.transpose(x.reshape(4, 32, w // g, g), (0, 2, 3, 1))
    x = jnp.transpose(x.reshape(4, w // g, 32 * g // S, S), (3, 0, 1, 2))
    return x.reshape(S * 4, w * 32 // S)


@partial(jax.jit, static_argnames=("m", "S"))
def _transpose_planes(cols: jax.Array, m: int, S: int) -> jax.Array:
    """The rows of :func:`_transpose_pack` as the planes the equality
    kernels read (ops/gc_pallas.py ``_planarize`` of the rows as
    ``[m // S, S, 4]``): uint32[S*4, m // S], plane ``s*4 + k`` holding
    word k of rows ``s, S + s, 2S + s, ...`` — bit ``s`` of test
    ``t = j // S`` for a batch whose row ``j = t*S + s`` is a test's
    string bit.  ``m`` is a multiple of ``S``.

    One transposition instead of two: rows as ``[m, 4]`` have a minor
    dimension of 4, and cutting them into ``[m // S, S, 4]`` afterwards
    sends a lane-padded copy of them (32 times their bytes) through HBM
    in whichever program does it (PERF.md section 5, PR 38).  The
    butterfly and the de-interleave run a tile of whole tests at a time
    (``PLANE_TILE_ROWS``) so their own lane-padded temporaries stay in
    VMEM at every chunk size."""
    g = math.lcm(S, 32) // 32  # words of a column that hold whole tests
    w = cols.shape[1]
    if w % g:
        cols = jnp.pad(cols, ((0, 0), (0, g - w % g)))
        w = cols.shape[1]
    tw = max(PLANE_TILE_ROWS // 32 // g, 1) * g
    tiles = [
        _deinterleave(_butterfly(cols[:, i : i + tw]), g, S)
        for i in range(0, w, tw)
    ]
    return jnp.concatenate(tiles, axis=1)[:, : m // S]


@partial(jax.jit, static_argnames=("w",))
def _col_words(seeds: jax.Array, w: int, offset) -> jax.Array:
    """Per-column PRG streams: uint32[128, 4] seeds -> uint32[128, w]."""
    nb = -(-w // 16)
    blocks = prg.stream_blocks(seeds, nb, offset)  # [128, nb, 16]
    return blocks.reshape(128, nb * 16)[:, :w]


def _pack_rows(cols, m: int, S):
    """The transposed rows: uint32[m, 4], or with a string width ``S``
    the test-planar uint32[S*4, m // S] of :func:`_transpose_planes`."""
    return _transpose_pack(cols, m) if S is None else _transpose_planes(cols, m, S)


def _receiver_extend_core(seeds0, seeds1, choices, offset, m, S=None):
    w = -(-m // 32)
    t = _col_words(seeds0, w, offset)
    g1 = _col_words(seeds1, w, offset)
    r_words = pack_bits(jnp.asarray(choices, bool))  # [w]
    u = t ^ g1 ^ r_words[None, :]
    return u, _pack_rows(t, m, S)


def _sender_extend_core(seeds, s_bits, u, offset, m, S=None):
    w = -(-m // 32)
    g = _col_words(seeds, w, offset)
    q = g ^ jnp.where(jnp.asarray(s_bits, bool)[:, None], u, jnp.uint32(0))
    return _pack_rows(q, m, S)


_receiver_extend = partial(jax.jit, static_argnames=("m", "S"))(
    _receiver_extend_core
)
_sender_extend = partial(jax.jit, static_argnames=("m", "S"))(
    _sender_extend_core
)


# Row-sharded extension (the multi-chip kernel stage,
# parallel/kernel_shard.py): the column PRG streams are CTR-mode and the
# packed butterfly transpose is word-local, so rows [row0, row0 + m) of a
# full-width extension are computable independently given only the
# matching column-word slice — shard i of a shard_mapped extension calls
# these with its own (row0, m) and reproduces EXACTLY the rows a
# single-device extend of the whole batch would have produced (the wire
# and every pad index stay byte-identical; tier-1 asserts it).
#
# Alignment contract: ``row0`` must be a multiple of 512 rows (= 16
# stream words = one ChaCha block per column), so the per-shard stream
# reads start on a block boundary; ``m`` must be a multiple of 32 so the
# column-word slice is exact.  Both are static facts of the planar shard
# layout (shards are whole 8192-test planar blocks and S >= 1), checked
# by the caller — row0 itself may be a TRACED value (lax.axis_index).
# Rows past the session's real batch read stream blocks the cursor has
# not consumed yet (the uniform per-shard shape covers the planar pad
# region); callers MUST zero-mask those rows before anything derived
# from them becomes wire-visible, and the session cursor only ever
# advances by the real batch (:meth:`OtExtSender.advance`).


def sender_extend_rows(seeds, s_bits, u_cols, base_off, row0, m: int):
    """Q rows [row0, row0 + m) of a full extension: ``u_cols`` is the
    column-word slice ``u[:, row0//32 : row0//32 + m//32]``; ``base_off``
    is the session's pre-batch stream block offset
    (:attr:`OtExtSender.stream_offset`)."""
    return _sender_extend_core(seeds, s_bits, u_cols, base_off + row0 // 512, m)


def receiver_extend_rows(seeds0, seeds1, choices, base_off, row0, m: int):
    """(u column-word slice, T rows) for rows [row0, row0 + m): the
    receiver twin of :func:`sender_extend_rows` (``choices`` is the
    shard's own m choice bits)."""
    return _receiver_extend_core(
        seeds0, seeds1, choices, base_off + row0 // 512, m
    )


def index_base(idx_offset) -> jax.Array:
    """uint32[2], the (low, high) words of a pad index base.

    An extension session's index counts every OT it has extended and
    never resets (``OtExtSender.consumed``): at the flagship's 131,072
    clients a crawl extends 16.8M rows a level, 4.3e9 by its leaf level,
    so the index is 64 bits wide.  ``idx_offset`` is a Python int or a
    traced integer scalar (the package enables x64: a Python int reaches
    a jitted function as int64)."""
    i = jnp.asarray(idx_offset).astype(jnp.uint64)
    return jnp.stack([i.astype(jnp.uint32), (i >> 32).astype(jnp.uint32)])


def index_words(base, off):
    """(low, high) uint32 words of ``base + off``: ``base`` the uint32[2]
    of :func:`index_base` (or any two words), ``off`` uint32 offsets of
    the batch's OTs.  The carry out of the low word is the one place a
    batch can straddle 2^32; in 32-bit lanes, so the Pallas kernels share
    it (ops/gc_pallas.py ``_test_idx``)."""
    lo = base[0] + off
    return lo, base[1] + (lo < base[0]).astype(jnp.uint32)


@partial(jax.jit, static_argnames=("n_words", "domain"))
def ot_hash(rows: jax.Array, n_words: int, idx_offset=0,
            domain: int = 0) -> jax.Array:
    """Correlation-robust hash of 128-bit rows -> uint32[..., n_words] pads.

    The per-row OT index (64 bits: :func:`index_base`) is folded into
    the tweak so identical rows at different positions hash
    independently (the `H(j, ·)` of IKNP).
    ``domain`` separates distinct protocol uses that might share an index
    range (e.g. the 1-of-4 per-TEST pads vs per-ROW Δ-OT pads of the same
    extension batch); it XORs into tweak word 1.
    """
    rows = jnp.asarray(rows, jnp.uint32)
    m = rows.shape[-2]
    lo, hi = index_words(
        index_base(idx_offset), jnp.arange(m, dtype=jnp.uint32)
    )
    shape = rows.shape[:-1]
    tweak = jnp.stack(
        [
            jnp.broadcast_to(lo, shape),
            jnp.full(shape, _OT_TWEAK1 ^ domain, jnp.uint32),
            jnp.broadcast_to(jnp.uint32(_OT_TWEAK2) ^ hi, shape),
            jnp.full(shape, _OT_TWEAK3, jnp.uint32),
        ],
        axis=-1,
    )
    # fusion fence before slicing (see prg._expand_jit's rationale)
    return jax.lax.optimization_barrier(prg.chacha_block(rows ^ tweak))[..., :n_words]


def gf128_double(x: jax.Array) -> jax.Array:
    """Multiply 128-bit blocks by x in GF(2^128) (poly x^128+x^7+x^2+x+1).

    Blocks are uint32[..., 4] little-endian (bit 0 = lsb of word 0 — the
    :func:`pack_bits` orientation).  One shift-with-carry across the four
    words plus a conditional XOR of the reduction constant 0x87.  Used to
    combine S Δ-OT rows into one hash input with distinct coefficients
    (the 1-of-2^S chosen-payload OT of protocol/secure.py): the 2^S
    sender offsets ``⊕_j c_j·x^j·s`` are pairwise distinct for any
    s != 0 because the map c -> Σ c_j x^j is injective on polynomials of
    degree < 128 and multiplication by s is invertible (see
    :func:`gf128_offsets`).
    """
    x = jnp.asarray(x, jnp.uint32)
    hi = x[..., 3] >> 31  # the outgoing x^127 bit
    shifted = (x << 1) | jnp.concatenate(
        [jnp.zeros_like(x[..., :1]), x[..., :3] >> 31], axis=-1
    )
    return shifted.at[..., 0].set(shifted[..., 0] ^ hi * jnp.uint32(0x87))


def gf128_comb(rows: jax.Array) -> jax.Array:
    """Combine S stacked 128-bit rows with distinct GF(2^128) coefficients:
    uint32[..., S, 4] -> ``⊕_j x^j · rows[..., j, :]`` as uint32[..., 4].

    Horner form — S-1 doublings total, no 2^S table.  This is the
    receiver/sender row-combine of the 1-of-2^S chosen-payload OT
    (protocol/secure.py): for Δ-OT rows ``t_j = q_j ^ y_j·s`` the
    combination satisfies ``comb(t) = comb(q) ^ o_y`` with ``o_y`` the
    offset :func:`gf128_offsets` assigns to choice ``y``.
    """
    rows = jnp.asarray(rows, jnp.uint32)
    S = rows.shape[-2]
    acc = rows[..., S - 1, :]
    for j in range(S - 2, -1, -1):
        acc = gf128_double(acc) ^ rows[..., j, :]
    return acc


def gf128_offsets(s_block: jax.Array, S: int) -> jax.Array:
    """uint32[2^S, 4] — every linear combination ``o_c = ⊕_j c_j·x^j·s``
    of the doubling ladder of ``s`` (bit j of c, little-endian, picks
    ``x^j·s``).  Pairwise distinct for any s != 0: ``o_c ^ o_c' =
    (Σ (c_j ^ c'_j) x^j)·s`` and a nonzero polynomial of degree < 128
    evaluated at x is a nonzero field element (x's minimal polynomial has
    degree 128), so the product with an invertible s cannot vanish.
    Generalizes the 1-of-4 table {0, s, 2s, s^2s} to arbitrary S."""
    s = jnp.asarray(s_block, jnp.uint32)
    pows = [s]
    for _ in range(S - 1):
        pows.append(gf128_double(pows[-1]))
    c = jnp.arange(1 << S, dtype=jnp.uint32)
    offs = jnp.zeros((1 << S, 4), jnp.uint32)
    for j in range(S):
        pick = ((c >> j) & 1).astype(bool)[:, None]
        offs = offs ^ jnp.where(pick, pows[j][None, :], jnp.uint32(0))
    return offs


def s_to_block(s_bits: np.ndarray) -> np.ndarray:
    """bool[128] -> uint32[4] — the sender's ``s`` as a label-sized block."""
    return np.asarray(pack_bits(np.asarray(s_bits, bool)))


class OtExtSender:
    """Extension sender: holds ``s`` and the base seeds chosen by ``s``.

    ``s_bits[0]`` is forced to 1 so ``s`` doubles as a free-XOR offset R
    with lsb(R)=1 (point-and-permute; ops/gc.py garbles with R = s).
    """

    def __init__(self, s_bits: np.ndarray, seeds: np.ndarray):
        s_bits = np.asarray(s_bits, bool)
        if s_bits.shape != (KAPPA,) or not s_bits[0]:
            raise ValueError("need 128 choice bits with lsb(s) = 1")
        if seeds.shape != (KAPPA, 4):
            # interpolate the precomputed shape, not the seed array: key
            # material must never reach exception messages (fhh-lint
            # secret-to-sink)
            got_shape = tuple(int(x) for x in seeds.shape)
            raise ValueError(f"need uint32[128, 4] base seeds, got {got_shape}")
        taint_guard.register("OtExtSender.s_bits", s_bits)
        taint_guard.register("OtExtSender._seeds", np.asarray(seeds))
        self.s_bits = s_bits
        self.s_block = s_to_block(s_bits)  # uint32[4]
        self._seeds = jnp.asarray(seeds, jnp.uint32)
        self._s_dev = jnp.asarray(s_bits)
        self._off = 0
        self._sent = 0

    @property
    def consumed(self) -> int:
        """Total OTs extended so far — the pad-tweak index base for the next
        batch (both endpoints' ``consumed`` advance in lockstep)."""
        return self._sent

    @property
    def stream_offset(self) -> int:
        """Per-column stream position in ChaCha blocks — the ``base_off``
        a row-sharded extension (:func:`sender_extend_rows`) seeks from."""
        return self._off

    @property
    def shard_state(self) -> tuple:
        """(seeds, s_bits device array) — the raw extension state a
        shard_mapped row-sharded extend consumes (parallel/kernel_shard)."""
        return self._seeds, self._s_dev

    def advance(self, m: int) -> None:
        """Advance the session cursors past an ``m``-row batch extended
        OUT-OF-BAND (the row-sharded extension computes the rows itself
        from :attr:`shard_state`): identical bookkeeping to
        :meth:`extend`, so a sharded endpoint stays in lockstep with a
        single-device peer."""
        w = -(-m // 32)
        self._off += -(-w // 16)  # blocks consumed from each column stream
        self._sent += m

    def extend_rows(self, m: int, u_cols, base_off: int, row0: int,
                    S: int | None = None) -> jax.Array:
        """Q rows ``[row0, row0 + m)`` of the batch that began at stream
        offset ``base_off``, from the matching column words of the
        peer's u-matrix; the cursors do not move (the caller
        moves them past the whole batch with :meth:`advance`).  ``row0`` is a
        multiple of 512 (see :func:`sender_extend_rows`); it is a traced
        scalar of the one program :meth:`extend` runs, so a later row
        range of a batch compiles nothing new.  With a string width
        ``S`` the rows come as the planes the equality kernels read,
        uint32[S*4, m // S] (:func:`_transpose_planes`)."""
        return _sender_extend(
            self._seeds, self._s_dev, jnp.asarray(u_cols),
            base_off + row0 // 512, m, S,
        )

    def extend(self, m: int, u_msg) -> jax.Array:
        """Peer's u-matrix -> Q rows uint32[m, 4] (Q_j = T_j ^ r_j·s)."""
        q = self.extend_rows(m, u_msg, self._off, 0)
        self.advance(m)
        return q

    def pads(self, q_rows: jax.Array, n_words: int, idx_offset: int):
        """(pad0, pad1) uint32[m, n_words] for chosen-payload OT."""
        p0 = ot_hash(q_rows, n_words, idx_offset)
        p1 = ot_hash(q_rows ^ jnp.asarray(self.s_block), n_words, idx_offset)
        return p0, p1


class OtExtReceiver:
    """Extension receiver: holds both base-seed columns (it played base-OT
    sender), produces the u message and its T rows per batch."""

    def __init__(self, seeds0: np.ndarray, seeds1: np.ndarray):
        if seeds0.shape != (KAPPA, 4) or seeds1.shape != (KAPPA, 4):
            raise ValueError("need two uint32[128, 4] base-seed columns")
        taint_guard.register("OtExtReceiver._seeds0", np.asarray(seeds0))
        taint_guard.register("OtExtReceiver._seeds1", np.asarray(seeds1))
        self._seeds0 = jnp.asarray(seeds0, jnp.uint32)
        self._seeds1 = jnp.asarray(seeds1, jnp.uint32)
        self._off = 0
        self._recv = 0

    @property
    def consumed(self) -> int:
        """Total OTs extended so far (see OtExtSender.consumed)."""
        return self._recv

    @property
    def stream_offset(self) -> int:
        """Stream position in blocks (see OtExtSender.stream_offset)."""
        return self._off

    @property
    def shard_state(self) -> tuple:
        """(seeds0, seeds1) for a row-sharded extend
        (:func:`receiver_extend_rows`, parallel/kernel_shard)."""
        return self._seeds0, self._seeds1

    def advance(self, m: int) -> None:
        """Out-of-band cursor bookkeeping (see OtExtSender.advance)."""
        w = -(-m // 32)
        self._off += -(-w // 16)
        self._recv += m

    def extend(self, choices) -> tuple[jax.Array, jax.Array]:
        """choices bool[m] -> (u message uint32[128, ceil(m/32)],
        T rows uint32[m, 4]).  T_j is the Δ-OT label for choice r_j."""
        u, t = self.extend_rows(choices, self._off, 0)
        self.advance(t.shape[0])
        return u, t

    def extend_rows(self, choices, base_off: int, row0: int):
        """(u column words, T rows) for rows ``[row0, row0 + m)`` of the
        batch that began at stream offset ``base_off``, ``choices`` being
        those rows' m choice bits: the receiver twin of
        :meth:`OtExtSender.extend_rows` (same alignment, same program
        as :meth:`extend`, cursors untouched)."""
        choices = jnp.asarray(choices, bool)
        return _receiver_extend(
            self._seeds0, self._seeds1, choices, base_off + row0 // 512,
            choices.shape[0],
        )

    def pads(self, t_rows: jax.Array, n_words: int, idx_offset: int) -> jax.Array:
        """uint32[m, n_words] — the receiver's chosen pad H(j, T_j)."""
        return ot_hash(t_rows, n_words, idx_offset)


def fresh_s_bits(rng: secrets.SystemRandom | None = None) -> np.ndarray:
    """Random sender choice vector with lsb forced to 1 (free-XOR ready)."""
    rand = rng or secrets.SystemRandom()
    bits = np.array([bool(rand.getrandbits(1)) for _ in range(KAPPA)])
    bits[0] = True
    return bits


def inprocess_pair() -> tuple[OtExtSender, OtExtReceiver]:
    """Run the base-OT setup in-process (tests, the secure warm-up)."""
    s_bits = fresh_s_bits()
    seeds0, seeds1, chosen = baseot.exchange(s_bits)
    return OtExtSender(s_bits, chosen), OtExtReceiver(seeds0, seeds1)
