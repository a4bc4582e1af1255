"""Garbled-circuit equality tests as batched TPU tensor kernels.

The reference garbles per-string equality circuits with the swanky
``fancy-garbling`` stack over per-core TCP channels (ref:
src/equalitytest.rs:25-191, driven from src/collect.rs:419-437).  Its
circuit is bitwise XNOR + an AND-tree, with the garbler XOR-masking each
result by a random bit so the output is XOR-shared between the parties
(equalitytest.rs:38-43, 148-161).

TPU-native redesign — nothing is per-gate or per-wire at runtime; a whole
batch of B equality tests over S-bit strings garbles/evaluates as a handful
of fused tensor ops:

- **Wire labels** are 128-bit blocks ``uint32[..., 4]`` drawn from the
  ChaCha stream (ops/prg.py) — the same substrate the reference's AES-128
  labels live on.
- **Free-XOR** (Kolesnikov-Schneider): a global offset ``R`` with
  ``lsb(R)=1``; XOR and NOT gates cost nothing.  XNOR(x_i, y_i) is the
  free relabeling ``Z0_i = X0_i ^ Y0_i ^ R``.
- **Half-gates AND** (Zahur-Rosulek-Evans 2015): two ciphertexts per AND
  gate, hashed with the fixed-key ChaCha block function as the
  correlation-robust hash ``H(label, tweak)`` — the TPU analogue of the
  fixed-key-AES garbling hash.  The S-leaf AND-tree runs as ``ceil(log2 S)``
  *batched* gate layers.
- **Masked output**: instead of feeding the garbler's mask bit as an extra
  circuit input wire (the reference's extra wire per test,
  equalitytest.rs:38-43, 153-160), the mask folds into the output decode
  bit — identical XOR-share semantics, zero extra gates.

The evaluator receives the garbler's input labels directly and its own via
OT, exactly the reference's wire-exchange split (equalitytest.rs:68-82,
109-125).  Two delivery modes:

- ``garble_equality`` draws everything (R, X0, Y0, masks) from a seed; the
  evaluator label pairs come back in ``GarblerSecrets`` for an explicit
  payload OT — the self-contained form (tests, small batches).
- ``garble_equality_delta`` takes ``R`` and the evaluator zero-labels
  ``Y0`` externally, for the Δ-OT fusion with IKNP extension
  (ops/otext.py): the garbler sets ``R = s`` and ``Y0_j = Q_j``, so the
  receiver's ``T_j = Q_j ^ y_j·s`` *is* its active input label — labels
  arrive with zero messages beyond the extension's u-matrix.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import prg

LABEL_WORDS = 4  # 128-bit labels

# Engine for the payload garble/eval pair (the hot ops of every secure
# deployment path): True (the default on real chips) routes them through
# the fused word-planar Pallas kernels (ops/gc_pallas.py) — BIT-EXACT
# with the XLA form (the garbler's labels come from the same stream
# draw), so the wire format and every test vector are engine-independent.
# False (and any CPU host — no Mosaic there) keeps the XLA programs.
GC_PALLAS: bool = True


def _pallas_engine() -> bool:
    from ..utils import effective_platform

    return GC_PALLAS and effective_platform() != "cpu"

# hash-tweak constants (words 2/3 of the tweak block): arbitrary fixed
# odd constants so GC hashing never collides with the PRG's other uses
_TWEAK2 = 0x9E3779B9
_TWEAK3 = 0x7F4A7C15


def _hash_many(labels: jax.Array, gate_ids: jax.Array, halves) -> jax.Array:
    """Correlation-robust hash H(label, tweak) over m stacked label sets.

    labels: uint32[m, ..., 4]; halves: length-m ints (per-set half-gate
    selector).  tweak = (gate id, half selector, const, const) XORed into
    each label block before the fixed-key ChaCha permutation; the
    feed-forward add makes the map non-invertible (the Davies-Meyer role,
    as in fixed-key-AES garbling).  One stacked call per gate layer keeps
    the ChaCha op count — the dominant XLA compile cost of GC programs —
    at one block-function instance per layer instead of m.
    """
    labels = jnp.asarray(labels, jnp.uint32)
    g = jnp.asarray(gate_ids, jnp.uint32)  # [k], right-aligned broadcast
    tweak = jnp.stack(
        [g, jnp.zeros_like(g), jnp.full_like(g, _TWEAK2), jnp.full_like(g, _TWEAK3)],
        axis=-1,
    )  # [k, 4]
    m = labels.shape[0]
    h = jnp.asarray(halves, jnp.uint32).reshape((m,) + (1,) * (labels.ndim - 2))
    x = labels ^ tweak
    x = x.at[..., 1].set(x[..., 1] ^ h)  # half selector = tweak word 1
    # fusion fence before slicing (see prg._expand_jit's rationale)
    return jax.lax.optimization_barrier(prg.chacha_block(x))[..., :4]


def _maskw(bit: jax.Array, block: jax.Array) -> jax.Array:
    """bit ? block : 0, broadcasting bit over the trailing word axis."""
    return jnp.where(bit[..., None], block, jnp.zeros_like(block))


def _lsb(label: jax.Array) -> jax.Array:
    return (label[..., 0] & 1).astype(bool)


class GarbledEqBatch(NamedTuple):
    """Everything the evaluator needs except its own input labels.

    tables:    uint32[B, S-1, 2, 4] — (T_G, T_E) per AND gate, tree order;
    gb_labels: uint32[B, S, 4]      — the garbler's active input labels;
    decode:    bool[B]              — output decode bit, pre-XORed with the
                                      garbler's random mask (share 0).
    """

    tables: jax.Array
    gb_labels: jax.Array
    decode: jax.Array


class GarblerSecrets(NamedTuple):
    """Garbler-side secrets: its output share + the evaluator label pairs
    to feed the label OT (choice bit = evaluator's input bit)."""

    mask: jax.Array  # bool[B] — garbler's XOR share of each result
    ev_label0: jax.Array  # uint32[B, S, 4] — labels for y_i = 0
    ev_label1: jax.Array  # uint32[B, S, 4] — labels for y_i = 1


def _and_tree_garble(wires0, R):
    """AND-reduce zero-labels [B, S, 4] -> ([B, 4], tables [B, S-1, 2, 4])."""
    tables = []
    gate = 0
    while wires0.shape[-2] > 1:
        k = wires0.shape[-2] // 2
        A0 = wires0[..., 0 : 2 * k : 2, :]
        B0 = wires0[..., 1 : 2 * k : 2, :]
        gids = jnp.arange(gate, gate + k, dtype=jnp.uint32)
        pa, pb = _lsb(A0), _lsb(B0)
        Rb = R[..., None, :]
        HA0, HA1, HB0, HB1 = _hash_many(
            jnp.stack([A0, A0 ^ Rb, B0, B0 ^ Rb]), gids, (0, 0, 1, 1)
        )
        TG = HA0 ^ HA1 ^ _maskw(pb, Rb)
        WG = HA0 ^ _maskw(pa, TG)
        TE = HB0 ^ HB1 ^ A0
        WE = HB0 ^ _maskw(pb, TE ^ A0)
        C0 = WG ^ WE
        tables.append(jnp.stack([TG, TE], axis=-2))  # [B, k, 2, 4]
        gate += k
        wires0 = jnp.concatenate([C0, wires0[..., 2 * k :, :]], axis=-2)
    if not tables:  # S == 1: a bare XNOR, no AND gates
        tables = [jnp.zeros(wires0.shape[:-2] + (0, 2, 4), jnp.uint32)]
    return wires0[..., 0, :], jnp.concatenate(tables, axis=-3)


def _and_tree_eval(wires, tables):
    """Evaluator twin of :func:`_and_tree_garble` on active labels."""
    gate = 0
    while wires.shape[-2] > 1:
        k = wires.shape[-2] // 2
        A = wires[..., 0 : 2 * k : 2, :]
        B = wires[..., 1 : 2 * k : 2, :]
        gids = jnp.arange(gate, gate + k, dtype=jnp.uint32)
        TG = tables[..., gate : gate + k, 0, :]
        TE = tables[..., gate : gate + k, 1, :]
        HA, HB = _hash_many(jnp.stack([A, B]), gids, (0, 1))
        WG = HA ^ _maskw(_lsb(A), TG)
        WE = HB ^ _maskw(_lsb(B), TE ^ A)
        C = WG ^ WE
        gate += k
        wires = jnp.concatenate([C, wires[..., 2 * k :, :]], axis=-2)
    return wires[..., 0, :]


def _carve_label_words(seed, B: int, S: int, n_label_sets: int, with_r: bool):
    """Draw [optional R] + ``n_label_sets`` [B, S, 4] label blocks + B mask
    bits from the PRG stream — the shared randomness layout of both garble
    entry points."""
    r_words = 4 if with_r else 0
    n_words = r_words + n_label_sets * B * S * 4 + ((B + 31) // 32)
    words = prg.stream_words(jnp.asarray(seed, jnp.uint32), n_words)
    R = words[:4].at[0].set(words[0] | 1) if with_r else None  # lsb(R) = 1
    base = r_words
    sets = [
        words[base + k * B * S * 4 : base + (k + 1) * B * S * 4].reshape(B, S, 4)
        for k in range(n_label_sets)
    ]
    mask_words = words[base + n_label_sets * B * S * 4 :]
    mask = (
        (mask_words[jnp.arange(B) // 32] >> (jnp.arange(B) % 32)) & 1
    ).astype(bool)
    return R, sets, mask


def _carve_label_words_shard(seed, B: int, S: int, t0, bloc: int):
    """Tests [t0, t0 + bloc) of the ``with_r=False`` single-set draw of
    :func:`_carve_label_words` — the row-sharded kernel stage's slice of
    the garbler's label/mask randomness (parallel/kernel_shard.py).

    The stream is CTR-mode (prg.stream_blocks seeks by block), so the
    slice is computed without materializing the full draw: the label
    region of shard i starts at stream word ``t0*S*4`` and the mask-bit
    region at word ``B*S*4 + t0//32`` — ``t0`` (which may be TRACED:
    lax.axis_index × a static shard extent) must be a multiple of 512
    tests so both regions start block-aligned after the static
    intra-block offset of the mask region is folded in.  Tests at or past
    ``B`` (the planar pad region) come back ZERO, exactly matching the
    single-device twin's ``_pad_tests`` padding — byte-identity of the
    packed wire holds shard-for-shard.

    Returns (X0 uint32[bloc, S, 4], mask bool[bloc]).
    """
    seed = jnp.asarray(seed, jnp.uint32)
    # int64 (the package enables x64): the label-region word seek below
    # multiplies t0 by S*4 — int32 wraps at ~134M padded tests at S=4,
    # inside the 1M-client flagship scale
    t0 = jnp.asarray(t0, jnp.int64)
    live = t0 + jnp.arange(bloc) < B  # global pad tests carve to zero
    # label region: words [t0*S*4, (t0+bloc)*S*4) — t0*S*4 ≡ 0 (mod 16)
    nb = bloc * S * 4 // 16
    lab = prg.stream_blocks(seed, nb, t0 * (S * 4) // 16)
    X0 = lab.reshape(bloc, S, 4)
    X0 = jnp.where(live[:, None, None], X0, jnp.uint32(0))
    # mask region: starts at global word M0 = B*S*4 (static, any residue
    # mod 16); the shard needs words [M0 + t0//32, M0 + t0//32 + bloc//32)
    # — t0//32 is a multiple of 16, so the intra-block offset is the
    # STATIC M0 % 16 and the blocks seek from (M0 - M0%16)//16 + t0//512
    M0 = B * S * 4
    intra = M0 % 16
    cw = (bloc + 31) // 32
    nb2 = -(-(intra + cw) // 16)
    mwords = prg.stream_blocks(
        seed, nb2, (M0 - intra) // 16 + t0 // 512
    ).reshape(nb2 * 16)[intra : intra + cw]
    i = jnp.arange(bloc)
    mask = ((mwords[i // 32] >> (i % 32).astype(jnp.uint32)) & 1).astype(bool)
    return X0, mask & live


def _garble_core(R, X0, Y0, mask, x_bits):
    """Shared garbling core: labels + offset in, (batch, output zero-labels)
    out — ``out0`` is what payload delivery hashes (see
    :func:`_garble_packed_planes_xla`)."""
    B = x_bits.shape[0]
    Z0 = X0 ^ Y0 ^ R  # XNOR relabel (free): Z0_i = X0_i ^ Y0_i ^ R
    out0, tables = _and_tree_garble(Z0, jnp.broadcast_to(R, (B, 4)))
    decode = _lsb(out0) ^ mask
    gb_labels = X0 ^ _maskw(x_bits, R)
    return GarbledEqBatch(tables=tables, gb_labels=gb_labels, decode=decode), out0


@jax.jit
def garble_equality(
    seed: jax.Array, x_bits: jax.Array
) -> tuple[GarbledEqBatch, GarblerSecrets]:
    """Garble B equality tests over S-bit strings in one batched program.

    seed:   uint32[4] fresh randomness seed (labels + offset + masks);
    x_bits: bool[B, S] the garbler's share-bit strings.

    The result's XOR shares are (secrets.mask, evaluator's decoded bit):
    ``mask ^ decoded == [x == y]`` — the contract of the reference's
    ``multiple_gb/ev_equality_test`` pair (equalitytest.rs:25-106).
    """
    x_bits = jnp.asarray(x_bits, bool)
    B, S = x_bits.shape
    # label material: R + X0[B,S] + Y0[B,S] labels + B mask bits
    R, (X0, Y0), mask = _carve_label_words(seed, B, S, 2, with_r=True)
    batch, _ = _garble_core(R, X0, Y0, mask, x_bits)
    return batch, GarblerSecrets(mask=mask, ev_label0=Y0, ev_label1=Y0 ^ R)


@jax.jit
def garble_equality_delta(
    R: jax.Array, Y0: jax.Array, seed: jax.Array, x_bits: jax.Array
) -> tuple[GarbledEqBatch, jax.Array]:
    """Garble with Δ-OT-supplied evaluator labels (see module docstring).

    R:      uint32[4] global offset = the OT-extension sender's ``s``
            (lsb must be 1 — otext.fresh_s_bits guarantees it);
    Y0:     uint32[B, S, 4] evaluator zero-labels = the extension's Q rows;
    seed:   uint32[4] randomness for the garbler's own labels + masks;
    x_bits: bool[B, S].

    Returns (batch, mask): ``mask`` is the garbler's XOR output share.
    """
    x_bits = jnp.asarray(x_bits, bool)
    B, S = x_bits.shape
    _, (X0,), mask = _carve_label_words(seed, B, S, 1, with_r=False)
    R = jnp.asarray(R, jnp.uint32)
    batch, _ = _garble_core(R, X0, jnp.asarray(Y0, jnp.uint32), mask, x_bits)
    return batch, mask


@jax.jit
def eval_equality(batch: GarbledEqBatch, ev_labels: jax.Array) -> jax.Array:
    """Evaluate a garbled batch with the evaluator's OT-received labels.

    ev_labels: uint32[B, S, 4].  Returns bool[B] — the evaluator's XOR
    share of each equality result (= eq ^ garbler mask).
    """
    z = batch.gb_labels ^ ev_labels  # active labels of the XNOR wires
    out = _and_tree_eval(z, batch.tables)
    return _lsb(out) ^ batch.decode


@partial(jax.jit, static_argnames=("n_words",))
def _eval_equality_payload_xla(batch: GarbledEqBatch, ev_labels, cts,
                               n_words: int, idx_offset):
    """Evaluate and open the output-label payload in one pass.

    Returns (e bool[B] — the evaluator's XOR share, payload uint32[B,
    n_words] — m_v for the actual output value v, which the evaluator
    learns without learning v)."""
    from .otext import ot_hash

    z = batch.gb_labels ^ jnp.asarray(ev_labels, jnp.uint32)
    out = _and_tree_eval(z, batch.tables)
    s = _lsb(out)
    pad = ot_hash(out, n_words, idx_offset)
    ct = jnp.where(s[:, None], cts[1], cts[0])
    return s ^ batch.decode, ct ^ pad


# ---------------------------------------------------------------------------
# Whole-level PACKED flow: the planar wire format (gc_pallas layout)
# ---------------------------------------------------------------------------
#
# The packed entry points emit/consume the garbled message as the planar
# plane stack of ops/gc_pallas.py (``tables | gb_labels | decode | cts``
# planes, each ``padded_tests(B)`` words).  On the Pallas engine that
# buffer is the kernel's output raveled in place — the garble→pack and
# unpack→eval transposes of the test-major wire format disappear.  The
# XLA twins here planarize explicitly and are BYTE-IDENTICAL, so the wire
# format (like every GC test vector) stays engine-independent and a
# CPU-engine endpoint interoperates with a Pallas-engine one.


def _pad_tests(a, bp: int):
    """Zero-pad the leading (test) axis to ``bp`` — the XLA twin garbles
    the padded slots exactly like the Pallas kernel does (zero-padded
    planar inputs), so the packed wire buffers are BYTE-identical
    engine-to-engine, padding included.  The receiver discards the pad
    slots either way."""
    B = a.shape[0]
    if bp == B:
        return a
    return jnp.concatenate(
        [a, jnp.zeros((bp - B,) + a.shape[1:], a.dtype)]
    )


def _garble_packed_planes_xla(R, Y0, X0, mask, x_bits, m_v0, m_v1,
                              n_words: int, idx_offset):
    """The packed-garble math AFTER label carving: every input already at
    the full planar extent (``x_bits.shape[0]`` a multiple of the planar
    block, pad slots zero).  Payload delivery rides the OUTPUT wire
    labels: the evaluator's garbled output label IS its 1-of-2 OT choice,
    so no separate b2a OT round is needed.  m_v0/m_v1 uint32[bp,
    n_words] are what the evaluator must learn when the output wire
    carries semantic value 0 / 1 (value 1 = strings equal); the two
    ciphertexts are indexed by the label's select (lsb) bit and encrypted
    under ``H(out_label, idx)`` with the OT-domain hash — the same
    circular-correlation-robustness assumption the Δ-OT pads already
    rest on (labels differ by R = s).  ``idx_offset`` must be unique per
    (session, batch) like any OT pad index; the caller uses the extension
    session's consumed counter.  Shared by the single-device twin below
    (which carves then pads) and the row-sharded kernel stage
    (parallel/kernel_shard.py — each shard feeds its
    :func:`_carve_label_words_shard` slice and a TRACED ``idx_offset``),
    so the planar wire bytes come from exactly one defining form.
    Returns the raveled planar buffer (tables | gb_labels | decode |
    cts planes)."""
    from . import gc_pallas
    from .otext import ot_hash

    bp = x_bits.shape[0]
    batch, out0 = _garble_core(R, X0, Y0, mask, x_bits)
    h0 = ot_hash(out0, n_words, idx_offset)
    h1 = ot_hash(out0 ^ R, n_words, idx_offset)
    c_v0 = jnp.asarray(m_v0, jnp.uint32) ^ h0
    c_v1 = jnp.asarray(m_v1, jnp.uint32) ^ h1
    p = _lsb(out0)[:, None]
    cts = jnp.stack([jnp.where(p, c_v1, c_v0), jnp.where(p, c_v0, c_v1)])
    parts = [
        gc_pallas._planarize(batch.tables, bp, bp),
        gc_pallas._planarize(batch.gb_labels, bp, bp),
        gc_pallas._planarize(jnp.asarray(batch.decode, jnp.uint32), bp, bp),
        gc_pallas._planarize(jnp.transpose(cts, (1, 0, 2)), bp, bp),
    ]
    return jnp.concatenate([jnp.ravel(p_) for p_ in parts])


@partial(jax.jit, static_argnames=("n_words",))
def _garble_equality_payload_packed_xla(R, Y0, seed, x_bits, m_v0, m_v1,
                                        n_words: int, idx_offset):
    from . import gc_pallas

    x_bits = jnp.asarray(x_bits, bool)
    B, S = x_bits.shape
    bp = gc_pallas.padded_tests(B)
    # the garbler's own labels + mask are drawn for the REAL B tests
    # (the same stream draw as every other engine/flow), then padded —
    # matching the kernel's zero-padded planar inputs bit for bit
    _, (X0,), mask = _carve_label_words(seed, B, S, 1, with_r=False)
    R = jnp.asarray(R, jnp.uint32)
    msg = _garble_packed_planes_xla(
        R, _pad_tests(jnp.asarray(Y0, jnp.uint32), bp), _pad_tests(X0, bp),
        _pad_tests(mask, bp), _pad_tests(x_bits, bp),
        _pad_tests(jnp.asarray(m_v0, jnp.uint32), bp),
        _pad_tests(jnp.asarray(m_v1, jnp.uint32), bp),
        n_words, idx_offset,
    )
    return msg, mask


@partial(jax.jit, static_argnames=("S", "n_words"))
def _eval_equality_payload_packed_xla(msg, ev_labels, S: int,
                                      n_words: int, idx_offset):
    from . import gc_pallas

    ev_labels = jnp.asarray(ev_labels, jnp.uint32)
    B = ev_labels.shape[0]
    tab, gbl, dec, ctsp = gc_pallas._split_packed(
        jnp.asarray(msg, jnp.uint32), B, S, n_words
    )
    batch = GarbledEqBatch(
        tables=gc_pallas._unplanarize(tab, B).reshape(B, S - 1, 2, 4),
        gb_labels=gc_pallas._unplanarize(gbl, B).reshape(B, S, 4),
        decode=gc_pallas._unplanarize(dec, B).reshape(B) != 0,
    )
    cts = gc_pallas._unplanarize(ctsp, B).reshape(B, 2, n_words)
    cts = jnp.transpose(cts, (1, 0, 2))
    return _eval_equality_payload_xla(
        batch, ev_labels, cts, n_words, idx_offset
    )


def garble_equality_payload_packed(R, Y0, seed, x_bits, m_v0, m_v1,
                                   n_words: int, idx_offset):
    """Engine dispatcher for the whole-level packed garble (byte-identical
    planar wire either way).  Returns (msg, mask)."""
    if jnp.asarray(x_bits).shape[1] >= 2 and _pallas_engine():
        from . import gc_pallas

        return gc_pallas.garble_equality_payload_packed(
            R, Y0, seed, x_bits, m_v0, m_v1, n_words, idx_offset
        )
    return _garble_equality_payload_packed_xla(
        R, Y0, seed, x_bits, m_v0, m_v1, n_words, idx_offset
    )


@partial(jax.jit, static_argnames=("n_words", "B", "pallas"))
def _garble_rows_packed(R, Y0, seed, x_bits, m_v0, m_v1, n_words: int,
                        idx_offset, B: int, t0, pallas: bool):
    from . import gc_pallas

    x_bits = jnp.asarray(x_bits, bool)
    n, S = x_bits.shape
    bp = gc_pallas.padded_tests(n)
    # this range's slice of the level's ONE label/mask draw, zero past
    # the level's last test like the whole-level twin's _pad_tests
    X0, mask = _carve_label_words_shard(seed, B, S, t0, bp)
    args = (
        jnp.asarray(R, jnp.uint32), _pad_tests(jnp.asarray(Y0, jnp.uint32), bp),
        X0, mask, _pad_tests(x_bits, bp),
        _pad_tests(jnp.asarray(m_v0, jnp.uint32), bp),
        _pad_tests(jnp.asarray(m_v1, jnp.uint32), bp), n_words, idx_offset,
    )
    if pallas:
        return gc_pallas.garble_packed_planes(*args)
    return _garble_packed_planes_xla(*args)


def garble_equality_payload_packed_rows(R, Y0, seed, x_bits, m_v0, m_v1,
                                        n_words: int, idx_offset, B: int,
                                        t0):
    """Tests ``[t0, t0 + n)`` of a ``B``-test level's packed garble
    (:func:`garble_equality_payload_packed`), every input already cut
    to those ``n`` tests and ``idx_offset`` the level's pad index base
    plus ``t0``: the label and mask words are that range's slice of the
    level's one stream draw (:func:`_carve_label_words_shard`), so the
    ranges' buffers, each its own planar blocks, are the level's
    message block for block.  ``t0`` is a multiple of the planar block
    and may be traced; ``t0 = 0, n = B`` is the whole level."""
    S = jnp.asarray(x_bits).shape[1]
    return _garble_rows_packed(
        R, Y0, jnp.asarray(seed, jnp.uint32), x_bits, m_v0, m_v1, n_words,
        idx_offset, B, t0, S >= 2 and _pallas_engine(),
    )


def eval_equality_payload_packed(msg, ev_labels, n_words: int, idx_offset):
    """Engine dispatcher twin of :func:`garble_equality_payload_packed`.
    Returns (e bool[B], payload uint32[B, n_words])."""
    ev_labels = jnp.asarray(ev_labels, jnp.uint32)
    S = ev_labels.shape[1]
    if S >= 2 and _pallas_engine():
        from . import gc_pallas

        return gc_pallas.eval_equality_payload_packed(
            msg, ev_labels, n_words, idx_offset
        )
    return _eval_equality_payload_packed_xla(
        msg, ev_labels, S, n_words, idx_offset
    )
