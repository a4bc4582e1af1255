"""Secure server↔server data plane: batched GC equality + OT b2a conversion.

This is the 2PC core the reference runs inside ``tree_crawl``
(ref: src/collect.rs:419-482 driving src/equalitytest.rs:25-191 and ocelot
OT): per (node, client), the two servers hold share-bit strings that agree
on every compared position iff the client's ball contains the node's box;
a garbled-circuit equality test XOR-shares that predicate, and a 1-of-2 OT
converts each XOR share into an additive field share via the ``r1 - r0 = 1``
trick (collect.rs:439-471), so per-node counts can be summed as field
shares neither server can open alone.

TPU-native shape: everything is batched device tensors —

- strings for ALL (node, child-pattern, client) triples come from one
  bit-extraction on the packed share-bit tensor (``child_strings``);
- one ``garble_equality_delta`` garbles the whole batch; evaluator input
  labels ride the IKNP Δ-OT (ops/otext.py) with ``R = s``, so label
  delivery costs one u-matrix message (vs the reference's per-wire OT);
- the b2a payloads travel under chosen-payload OT pads from the same
  extension session, each at the width of its field
  (:func:`payload_words`): an FE62 payload is two u32 words, an F255
  payload eight.  (The reference sends a 128-bit ``Block`` and a
  ``BlockPair``, collect.rs:439-471 vs 775-916; FE62's Block is two
  words of value and two of zeros, and a pad is cut to the payload's
  length, so the zeros and the pad words over them stay home);
- per-node share sums are alive-gated field reductions on device
  (collect.rs:487-501's ``add_lazy`` loop as one ``field.sum``).

The step functions here are sans-IO, and the level has TWO forms, both
emitting the PLANAR wire (the 1-of-2^S payload table for S ≤
``OT2S_MAX_S``, else the packed garbled batch with the b2a payloads
riding the output labels):

- the CHUNK functions (``level_chunks``; the receiver's
  ``ev_chunk_extend`` and ``ev_chunk_open``, the sender's
  ``gb_chunk_extend`` and ``gb_chunk_table``; on the garbled-circuit
  path ``gb_chunk_pair`` + ``gb_chunk_garble`` and ``ev_chunk_eval`` +
  ``ev_chunk_field``) are what protocol/rpc.py dispatches: a level
  crosses the data-plane socket as K frames a direction, each a run of
  whole planar blocks (K = 1 is one round trip of one message a side),
  and each function is ONE jitted device program, one inside each of a
  chunk's spans, with the chunk's first test traced (the servers'
  counter ``secure_chunk_programs`` reads 2 x K a level);
- the WHOLE-LEVEL trio (``ev_step1_fused``, ``gb_step_level`` /
  ``ev_open_level``) is the same level as ONE message from one call a
  side.  Nothing in the package calls it: it is the oracle the chunk
  tests (tests/test_secure_chunks.py), the engine-parity tests
  (tests/test_secure_kernels.py) and the row-shard tests
  (tests/test_kernel_shard.py) hold the served functions to, bit for
  bit.

Wire-share semantics: the garbler's per-test share is ``r1 = r0 ± 1``
(+1 when server 0 garbles, −1 when server 1 does — the garbler flips per
level, ref rpc.rs:20-23); the evaluator receives ``r0`` when the strings
are equal, else ``r1`` — so ``v0 - v1 = [x == y]`` per test REGARDLESS
of which side garbled, and summed shares reconstruct counts exactly like
``keep_values`` (collect.rs:945-964).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import gc, otext, prg
from ..ops.fields import F255, FE62

# ---------------------------------------------------------------------------
# String extraction: packed share bits -> per-(node, pattern, client) strings
# ---------------------------------------------------------------------------


def _string_positions(d: int) -> np.ndarray:
    """uint32[2^d, 2d] — packed-bit positions of child pattern c's compared
    string, ordered (dim-major, side minor): the tensor twin of the
    reference's left||right bit-string layout (collect.rs:393-410), reading
    direction ``(c >> j) & 1`` per dim (child order: lib.rs:125-129)."""
    out = np.empty((1 << d, 2 * d), np.uint32)
    for c in range(1 << d):
        k = 0
        for j in range(d):
            r = (c >> j) & 1
            for s in range(2):
                out[c, k] = j * 4 + s * 2 + r
                k += 1
    return out


@partial(jax.jit, static_argnames=("d",))
def child_strings(packed: jax.Array, d: int) -> jax.Array:
    """uint32[F, N] packed share bits -> bool[F, 2^d, N, 2d] strings."""
    pos = jnp.asarray(_string_positions(d))  # [C, S]
    return ((packed[:, None, :, None] >> pos[None, :, None, :]) & 1).astype(bool)


def _string_positions_radix(d: int, radix: int) -> np.ndarray:
    """uint32[2^(radix*d), 2*d*radix] — packed-bit positions of fused child
    pattern c's compared string under the radix layout (collect.py
    ``_radix_positions``).  The fused string is the step-major concatenation
    of the per-depth membership strings along c's path: column
    k = t*2d + j*2 + s holds dim j / side s of the depth-(t+1) node reached
    by steps 0..t.  Equality over the concatenation == AND of the per-depth
    equalities, which is what makes fused pruning identical to k sequential
    radix-1 prunes.  Reduces to ``_string_positions`` columns at radix=1."""
    if radix == 1:
        return _string_positions(d)
    T = (1 << (radix + 1)) - 2  # packed bits per (dim, side)
    C = 1 << (radix * d)
    out = np.empty((C, 2 * d * radix), np.uint32)
    for c in range(C):
        node = [0] * d
        k = 0
        for t in range(radix):
            base = (2 << t) - 2  # offset of depth-(t+1) nodes in the subtree
            for j in range(d):
                node[j] |= ((c >> (t * d + j)) & 1) << t
                for s in range(2):
                    out[c, k] = j * 2 * T + s * T + base + node[j]
                    k += 1
    return out


@partial(jax.jit, static_argnames=("d", "radix"))
def _child_strings_radix_jit(packed: jax.Array, d: int, radix: int) -> jax.Array:
    pos = jnp.asarray(_string_positions_radix(d, radix))  # [C, S']
    return ((packed[:, None, :, None] >> pos[None, :, None, :]) & 1).astype(bool)


def child_strings_radix(packed: jax.Array, d: int, radix: int) -> jax.Array:
    """uint32[F, N] radix-packed share bits -> bool[F, 2^(radix*d), N,
    2*d*radix] fused strings.  radix=1 delegates to ``child_strings`` so a
    k=1 crawl hits the exact compiled program it always has."""
    if radix == 1:
        return child_strings(packed, d)
    return _child_strings_radix_jit(packed, d, radix)


# ---------------------------------------------------------------------------
# Field payload codecs: a payload travels at the width of its field —
# FE62 in two u32 words ``[lo, hi]``, F255 in eight.  (The reference sends
# FE62 as a 128-bit ``Block`` whose upper half is zeros, fastfield.rs:
# 414-431, here ``FE62.to_blocks``: a pad is cut to the payload's length,
# so the wire carries the Block's value half alone.)
# ---------------------------------------------------------------------------


def payload_words(field) -> int:
    """u32 words of one OT payload of ``field`` on the wire."""
    return 8 if field is F255 else 2


def field_to_words(field, v) -> jax.Array:
    b = field.to_blocks(v)
    if field is F255:
        return b.reshape(b.shape[:-2] + (8,))
    return b[..., :2]


def words_to_field(field, w) -> jax.Array:
    if field is F255:
        return field.from_blocks(w.reshape(w.shape[:-1] + (2, 4)))
    w = jnp.asarray(w, jnp.uint64)
    return field.new(w[..., 0] | (w[..., 1] << 32))


def derive_seed(base: np.ndarray, purpose: int, level: int, ctr: int = 0) -> np.ndarray:
    """Per-(purpose, level, crawl-counter) PRG seed from a session seed."""
    s = np.array(base, np.uint32, copy=True)
    s[1] ^= np.uint32(ctr)
    s[2] ^= np.uint32(purpose)
    s[3] ^= np.uint32(level)
    return s


# ---------------------------------------------------------------------------
# The b2a share pair.  Roles: the garbler/sender flips per level (ref:
# leader.rs:204-205 pins the role per request), the peer evaluates/receives.
# ---------------------------------------------------------------------------


def b2a_payload_pair(field, b2a_seed, B: int, garbler: int, t0: int = 0):
    """The sender's b2a share pair, in ONE place for every flow: sample
    ``r0`` from the seed stream, keep ``r1 = r0 ± 1`` — +1 when server 0
    is the sender, −1 when server 1 is — so the leader's uniform
    ``v0 - v1`` reconstruction holds whichever server sends
    (collect.rs:439-456's ordering with the alternating-garbler sign).
    ``t0`` seeks: the pair of tests ``[t0, t0 + B)`` of the seed's
    stream (test ``t`` owns words ``[t*D, (t+1)*D)``, ``D`` =
    ``field.SAMPLE_WORDS`` — the draw's stride (FE62 folds 126 uniform
    bits into its 62), not the wire's; ``t0*D`` a
    multiple of 16, as every multiple of a planar block is), the same
    words a draw from 0 holds there.
    Returns (r1 — the sender's additive shares, w0, w1 — the two payloads
    as OT words, :func:`payload_words` each)."""
    D = field.SAMPLE_WORDS
    r_words = prg.stream_words(
        jnp.asarray(b2a_seed, jnp.uint32), B * D, t0 * D // 16
    ).reshape(B, D)
    r0 = field.sample(r_words)
    one = field.from_int(1)
    r1 = field.sub(r0, one) if garbler else field.add(r0, one)
    return r1, field_to_words(field, r0), field_to_words(field, r1)


# ---------------------------------------------------------------------------
# 1-of-2^S fast path: equality via chosen-payload OT (no garbled circuit)
# ---------------------------------------------------------------------------
#
# Each equality test compares S = 2·n_dims bits (two interval sides per
# dim).  The full GC machinery (S-1 AND gates, 4 garble + 2 eval hashes
# per gate, tables + labels + decode on the wire) exists to compute
# [x == y] for S-bit x, y.  But an S-bit y is a 1-of-2^S choice, and the
# test's S Δ-OT rows (t_j = q_j ^ y_j·s) already encode it: combining
# the rows with distinct GF(2^128) coefficients, T = ⊕_j x^j·t_j =
# Q ^ o_y where Q = ⊕_j x^j·q_j and o_c = ⊕_j c_j·x^j·s, gives the
# receiver exactly ONE of the 2^S sender-computable pads H(Q ^ o_c) —
# the offsets are pairwise distinct for any s != 0 because the doubling
# ladder is a basis of an S-dimensional subspace (otext.gf128_offsets).
# The sender encrypts payload m_{[x == c]} under pad c; the receiver
# opens pad y and learns m_{[x == y]} — the whole equality test +
# payload b2a in 2^S + 1 hashes/test and ZERO garbling, so
# multi-dimensional crawls skip the garbled circuit entirely on the
# equality test.  This is the classic 1-of-N OT-extension pad
# construction (Kolesnikov-Kumaresan 2013 shape) under the same
# circular-correlation-robust-hash assumption the Δ-OT pads and the GC
# fused payload already rest on (every pad offset is a fixed GF(2^128)-
# linear function of s).
#
# Cost crossover: per test the table costs 2^S·W ciphertext words vs the
# GC batch's (S-1)·8 + 4S + 1 + 2W, W the payload's words (2 for FE62,
# 8 for F255).  Inner levels (W = 2): at S = 2 the table is 8 words to
# the batch's 21 (~40%) and 5 vs 9 hashes; at S = 4 it is 32 to 45; at
# S = 6 128 to 69 (~1.9x the bytes) but still ~1/3 the hash count and no
# tree.  The leaf (W = 8) pays 2^S·8 against the batch's 17 + 8(S-1) +
# 4S: 32 to 33 at S = 2, 128 to 57 at S = 4 — ``OT2S_MAX_S`` caps the
# auto path at the point where the 2^S table stops paying (beyond it the
# GC path, whose wire is linear in S, takes over).  The GC path also
# remains the arbitrary-S fallback and the reference-parity oracle;
# ``Config.ot_path = "gc"`` turns the fast path off entirely.

# auto-path ceiling for the 1-of-2^S table (S = 2·n_dims; 6 covers the
# 3-dim roadmap workloads).  Protocol-legal up to 128; the 2^S·W wire
# and HBM growth is why the default stops at 6.
OT2S_MAX_S: int = 6

# Engine flag for the planar ot2s kernels (ops/otext_pallas.py), exactly
# like gc.GC_PALLAS: True routes the packed encrypt/decrypt through the
# fused Pallas kernels on a real chip; CPU hosts always run the XLA
# twins.  Wire bytes are engine-independent (parity-tested).
OT2S_PALLAS: bool = True

_OT2S_DOMAIN = 0x0F4E4F54  # ot_hash tweak-domain of the per-test pads


def _ot2s_pallas_engine() -> bool:
    from ..utils import effective_platform

    return OT2S_PALLAS and effective_platform() != "cpu"


def ot_path(S: int, override: str = "auto") -> str:
    """Which equality-test engine a level of string width ``S`` runs:
    ``"ot2s"`` (1-of-2^S chosen-payload OT) or ``"gc"`` (garbled
    circuit).  ``override`` is the config knob (utils/config.Config
    ``ot_path``): "auto" picks ot2s for 2 <= S <= OT2S_MAX_S,
    "ot2s"/"gc" force a path — forcing ot2s past the ceiling is
    a loud error rather than a silent 2^S blowup.  Both servers derive
    the path from the same (cfg, S), so the wire format always agrees."""
    if override == "gc":
        return "gc"
    if override == "ot2s":
        if S > OT2S_MAX_S:
            raise ValueError(
                f"ot_path='ot2s' forced at S={S}: the 1-of-2^S table is "
                f"capped at S={OT2S_MAX_S} (2^S ciphertexts per test) — "
                "use the GC path for wider strings"
            )
        return "ot2s"
    if override != "auto":
        raise ValueError(f"unknown ot_path {override!r}")
    return "ot2s" if 2 <= S <= OT2S_MAX_S else "gc"


@partial(jax.jit, static_argnames=("n_words",))
def ot2s_encrypt(q_rows, s_block, x_flat, m_v0, m_v1, n_words: int,
                 idx_offset):
    """Sender side: q_rows uint32[B, S, 4] (this batch's extension rows),
    x_flat bool[B, S] (the sender's share-bit strings), payloads
    m_v0/m_v1 uint32[B, n_words] for result 0 / 1.  Returns cts
    uint32[2^S, B, n_words] indexed by the receiver's string as a
    little-endian S-bit integer c = Σ y_j·2^j."""
    q_rows = jnp.asarray(q_rows, jnp.uint32)
    x_flat = jnp.asarray(x_flat, bool)
    S = q_rows.shape[1]
    comb = otext.gf128_comb(q_rows)  # [B, 4] = ⊕ x^j·q_j
    offs = otext.gf128_offsets(jnp.asarray(s_block, jnp.uint32), S)
    x_int = jnp.zeros(x_flat.shape[0], jnp.uint32)
    for j in range(S):
        x_int = x_int | (x_flat[:, j].astype(jnp.uint32) << j)
    pads = otext.ot_hash(
        comb[None] ^ offs[:, None, :], n_words, idx_offset,
        domain=_OT2S_DOMAIN,
    )  # [2^S, B, n_words]
    eq = jnp.arange(1 << S, dtype=jnp.uint32)[:, None] == x_int[None]
    m = jnp.where(
        eq[..., None], jnp.asarray(m_v1, jnp.uint32)[None],
        jnp.asarray(m_v0, jnp.uint32)[None],
    )
    return m ^ pads


@partial(jax.jit, static_argnames=("n_words",))
def ot2s_decrypt(t_rows, y_flat, cts, n_words: int, idx_offset):
    """Receiver side: t_rows uint32[B, S, 4], y_flat bool[B, S] (its own
    share-bit strings — the extension's choice bits), cts uint32[2^S, B,
    n_words].  Returns uint32[B, n_words] = m_{[x == y]} per test."""
    t_rows = jnp.asarray(t_rows, jnp.uint32)
    y_flat = jnp.asarray(y_flat, bool)
    S = t_rows.shape[1]
    comb = otext.gf128_comb(t_rows)  # [B, 4] = Q ^ o_y
    pad = otext.ot_hash(comb, n_words, idx_offset, domain=_OT2S_DOMAIN)
    y_int = jnp.zeros(y_flat.shape[0], jnp.uint32)
    for j in range(S):
        y_int = y_int | (y_flat[:, j].astype(jnp.uint32) << j)
    # one-hot select instead of take_along_axis: the gather lowers poorly
    # on TPU (measured 1.5x slower at the flagship 524288-test batch)
    sel = (
        jnp.arange(1 << S, dtype=jnp.uint32)[:, None] == y_int[None]
    ).astype(jnp.uint32)
    ct = jnp.sum(
        jnp.asarray(cts, jnp.uint32) * sel[..., None], axis=0,
        dtype=jnp.uint32,
    )
    return ct ^ pad


# ---------------------------------------------------------------------------
# WHOLE-LEVEL packed flow: one device program per side, planar wire
# ---------------------------------------------------------------------------
#
# The level as ONE message (the oracle of the chunked flow below, see the
# module docstring): every (node, pattern, client) test of a level rides
# one message built by one fused device program per side — the 1-of-2^S
# table for S <= OT2S_MAX_S, the packed garbled batch beyond it.  In the
# garbled batch the b2a payloads ride the GC OUTPUT LABELS: the
# evaluator's b2a choice bit is exactly its GC output share, and its
# garbled output label already encodes that choice 1-of-2 (labels differ
# by R with the select bit in the lsb), so encrypting the two payloads
# under the two possible output labels delivers the b2a OT inside the
# batch — one protocol round trip a level where the reference's
# GC-then-OT structure (collect.rs:419-482) takes two.  Security rests on
# the same circular-correlation-robust hash assumption as the Δ-OT pads
# (labels differ by R = s).
# The wire format is the PLANAR plane layout of ops/gc_pallas.py /
# ops/otext_pallas.py, padded to ``padded_tests(B)`` tests: on a real
# chip the buffer is the fused kernel's output raveled in place (no
# test-major transposes between garbling and the fetch, none between the
# upload and evaluation), and the XLA twins emit byte-identical planes so
# the format is engine-independent.  ``idx0`` is the extension session's
# pre-batch consumed counter on BOTH sides, as everywhere else.
#
# Security note on the PAD SLOTS: the padded tests garble/encrypt
# zero-valued inputs, so the wire's pad region publishes hashes of
# offset-only inputs (H(o_c, idx) for the ot2s table; a degenerate
# known-input garbled instance for the GC batch).  Both are query shapes
# the circular-correlation-robust-hash assumption already covers — the
# receiver's legitimate queries have the same linear-in-s structure —
# and the receiver discards the slots; B itself is public protocol
# state, so the pad boundary reveals nothing new.


@partial(jax.jit, static_argnames=("n_words",))
def _ot2s_encrypt_packed_xla(q_rows, s_block, x_flat, m_v0, m_v1,
                             n_words: int, idx_offset):
    from ..ops import gc_pallas
    from ..ops.gc import _pad_tests

    B = q_rows.shape[0]
    bp = gc_pallas.padded_tests(B)
    # encrypt the zero-padded slots too (exactly the kernel's padded
    # planar inputs), so the wire buffer is byte-identical per engine
    cts = ot2s_encrypt(
        _pad_tests(q_rows, bp), s_block, _pad_tests(x_flat, bp),
        _pad_tests(m_v0, bp), _pad_tests(m_v1, bp), n_words, idx_offset,
    )
    p = gc_pallas._planarize(jnp.transpose(cts, (1, 0, 2)), bp, bp)
    return jnp.ravel(p)


@partial(jax.jit, static_argnames=("S", "n_words"))
def _ot2s_decrypt_packed_xla(t_rows, y_flat, msg, S: int, n_words: int,
                             idx_offset):
    from ..ops import gc_pallas

    B = t_rows.shape[0]
    bp = gc_pallas.padded_tests(B)
    planes = jnp.asarray(msg, jnp.uint32).reshape(
        (1 << S) * n_words, bp // gc_pallas.GROUP,
        gc_pallas.SUB, gc_pallas.LANES,
    )
    cts = gc_pallas._unplanarize(planes, B).reshape(B, 1 << S, n_words)
    return ot2s_decrypt(
        t_rows, y_flat, jnp.transpose(cts, (1, 0, 2)), n_words, idx_offset
    )


def ot2s_encrypt_packed(q_rows, s_block, x_flat, m_v0, m_v1,
                        n_words: int, idx_offset):
    """Engine dispatcher: planar-wire 1-of-2^S sender table (fused Pallas
    kernel on a real chip, byte-identical XLA twin elsewhere)."""
    q_rows = jnp.asarray(q_rows, jnp.uint32)
    if _ot2s_pallas_engine():
        from ..ops import otext_pallas

        return otext_pallas.ot2s_encrypt(
            q_rows, s_block, x_flat, m_v0, m_v1, n_words, idx_offset,
            domain=_OT2S_DOMAIN,
        )
    return _ot2s_encrypt_packed_xla(
        q_rows, jnp.asarray(s_block, jnp.uint32), jnp.asarray(x_flat, bool),
        jnp.asarray(m_v0, jnp.uint32), jnp.asarray(m_v1, jnp.uint32),
        n_words, idx_offset,
    )


def ot2s_decrypt_packed(t_rows, y_flat, msg, n_words: int, idx_offset):
    """Engine dispatcher twin: open the planar 1-of-2^S table ->
    uint32[B, n_words]."""
    t_rows = jnp.asarray(t_rows, jnp.uint32)
    if _ot2s_pallas_engine():
        from ..ops import otext_pallas

        return otext_pallas.ot2s_decrypt(
            t_rows, y_flat, msg, n_words, idx_offset, domain=_OT2S_DOMAIN
        )
    return _ot2s_decrypt_packed_xla(
        t_rows, jnp.asarray(y_flat, bool), msg, t_rows.shape[1], n_words,
        idx_offset,
    )


def ev_step1_fused(rcv: otext.OtExtReceiver, y_flat):
    """Evaluator round 1: request input labels.  y_flat bool[B, S] ->
    (u message, T rows uint32[B*S, 4] — the Δ-OT labels-to-be, idx0 —
    the pre-extension consumed counter, the payload-pad index base the
    garbler captures too).  ``y_flat`` may stay a DEVICE array —
    fetching it first is a blocking device->host fetch and the extension
    consumes it on device anyway."""
    B, S = y_flat.shape
    idx0 = rcv.consumed
    u, t = rcv.extend(jnp.reshape(jnp.asarray(y_flat), (B * S,)))
    return u, t, idx0


def gb_step_level(snd: otext.OtExtSender, u_msg, x_flat, gc_seed, b2a_seed,
                  field, garbler: int = 0, path: str = "auto"):
    """Garbler/sender whole-level step: extend the Δ-OT, derive the b2a
    share pair, and build the level's ONE planar message — the 1-of-2^S
    table or the packed garbled batch, by :func:`ot_path`.

    Returns (msg, vals — the sender's additive shares r1 = r0 ± 1)."""
    x_flat = jnp.asarray(x_flat, bool)
    B, S = x_flat.shape
    p = ot_path(S, path)
    idx0 = snd.consumed
    q = snd.extend(B * S, u_msg)
    W = payload_words(field)
    r1, w0, w1 = b2a_payload_pair(field, b2a_seed, B, garbler)
    # result 1 (strings equal) -> receiver learns r0 (collect.rs:439-456)
    if p == "ot2s":
        msg = ot2s_encrypt_packed(
            q.reshape(B, S, 4), jnp.asarray(snd.s_block), x_flat, w1, w0,
            W, idx0,
        )
    else:
        msg, _ = gc.garble_equality_payload_packed(
            jnp.asarray(snd.s_block), q.reshape(B, S, 4),
            jnp.asarray(gc_seed), x_flat, w1, w0, W, idx0,
        )
    return msg, r1


def ev_open_level(t_rows, y_flat, msg, B: int, S: int, field, idx0: int,
                  path: str = "auto"):
    """Evaluator/receiver whole-level twin: open the planar message with
    the Δ-OT T rows -> field values [B] (r0 where equal, else r1)."""
    p = ot_path(S, path)
    W = payload_words(field)
    if p == "ot2s":
        w = ot2s_decrypt_packed(
            jnp.asarray(t_rows).reshape(B, S, 4), y_flat, msg, W, idx0
        )
    else:
        _, w = gc.eval_equality_payload_packed(
            msg, jnp.asarray(t_rows).reshape(B, S, 4), W, idx0
        )
    return words_to_field(field, w)


# ---------------------------------------------------------------------------
# Alive-gated per-node share sums (collect.rs:487-501)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("field",))
def node_share_sums(field, vals, weight) -> jax.Array:
    """vals: field elements [F, C, N(, limbs)]; weight: bool[F, C, N].
    Returns per-(node, pattern) share sums [F, C(, limbs)].  Dead clients
    and dead nodes are gated to zero — identically on both servers, since
    liveness flags and the frontier alive mask are public protocol state
    (ref: collect.rs:495)."""
    if field.limb_shape:
        vals = jnp.where(weight[..., None], vals, 0)
        return field.sum(vals, axis=2)
    vals = jnp.where(weight, vals, 0)
    return field.sum(vals, axis=2)


def alive_weight(alive_nodes, alive_keys, C: int) -> np.ndarray:
    """bool[F, C, N] gating weight from the public liveness masks."""
    a_n = np.asarray(alive_nodes, bool)
    a_k = np.asarray(alive_keys, bool)
    return np.broadcast_to(
        a_n[:, None, None] & a_k[None, None, :], (a_n.shape[0], C, a_k.shape[0])
    )


# ---------------------------------------------------------------------------
# The level as a stream of row chunks
# ---------------------------------------------------------------------------
#
# protocol/rpc.py sends a level's two messages as K frames each, so that
# kernel, fetch, socket and the peer's kernel of consecutive chunks
# overlap.  A chunk is a run of whole planar blocks of the level's test
# batch (the last one takes the rest, padded to a block like the level
# itself), so chunk k's frames are the level's planar blocks in order
# and the u-matrix's column words in order.  It uses rows
# ``[t0*S, (t0+n)*S)`` of the level's ONE extension
# (``OtExtSender.extend_rows`` / ``OtExtReceiver.extend_rows``), tests
# ``[t0, t0+n)`` of its one b2a stream (:func:`b2a_payload_pair`) and
# label draw (``gc.garble_equality_payload_packed_rows``), and pad
# indices from ``idx0 + t0``: every share is the whole-level flow's, bit
# for bit, and the whole level is the plan of one chunk.

# What the LARGER of a chunk's two frames may weigh.  Both servers cut a
# level by it from dimensions they already agree on; measured on the
# chip at 8, 16 and 32 MiB (PERF.md section 6, PR 31).  At 16 MiB and
# under, every fetch and receive buffer also stays below the 32 MiB from
# which glibc maps new pages for each request (protocol/wire.py).  Which
# frame is the larger goes by the level's field: over FE62 at S = 2 the
# u rows (16·S bytes a test) and the table (4·2^S·2) weigh the same 32,
# from S = 4 up and at every F255 leaf it is the table.
CHUNK_FRAME_BYTES: int = 16 << 20


def level_chunks(B: int, S: int, W: int, path: str) -> list:
    """``[(t0, n)]``: the test ranges a ``B``-test level of string width
    ``S`` and payload width ``W`` crosses in, on equality path ``path``.
    One range is the whole level."""
    from ..ops import gc_pallas
    from ..parallel.kernel_shard import n_msg_planes

    block = gc_pallas.R_BLK * gc_pallas.GROUP
    # bytes a test adds to the receiver's u-matrix (S rows of 128 bits)
    # and to the sender's planar message
    per_test = max(16 * S, 4 * n_msg_planes(path, S, W))
    n = max(1, CHUNK_FRAME_BYTES // (per_test * block)) * block
    return [(t0, min(n, B - t0)) for t0 in range(0, B, n)]


# Each device step of a chunk is ONE jitted program, one inside each of
# the chunk's spans (protocol/rpc.py ``_ev_chunks`` / ``_gb_chunks``):
# the row cut of the level's flat strings, the share pair and the way
# from the extension's rows to the kernels' planes are traced into the
# program that needs them, with the chunk's first test ``t0`` and its
# pad index traced scalars, so the K chunks of a level and every later
# level of the bucket run one executable a span.  Nothing is dispatched
# eagerly between them, and the extension hands its rows over as the
# planes the kernels read (``otext._transpose_planes``, uint32[S*4, n]):
# rows as uint32[n*S, 4] cut into [n, S, 4] send a lane-padded copy of
# themselves, 32 times their bytes, through HBM, in a program of their
# own or inside the kernel's (PERF.md section 5, PR 38).  A level gone
# whole is the chunk ``t0 = 0, n = B``.


def _test_rows(planes, S: int):
    """The extension's planes uint32[S*4, n] as rows uint32[n, S, 4], for
    the XLA twins and the garbled-circuit path."""
    return jnp.transpose(planes.reshape(S, 4, -1), (2, 0, 1))


@partial(jax.jit, static_argnames=("n",))
def _ev_extend(seeds0, seeds1, flat, block_off, t0, n: int):
    S = flat.shape[1]
    y = jax.lax.dynamic_slice_in_dim(flat, t0, n)
    u, t_planes = otext._receiver_extend_core(
        seeds0, seeds1, y.reshape(n * S), block_off, n * S, S
    )
    return u, t_planes, y


def ev_chunk_extend(rcv: otext.OtExtReceiver, flat, base_off: int, t0: int,
                    n: int):
    """The receiver's ``otext`` step for tests ``[t0, t0 + n)`` of the
    level's flat strings bool[B, S]: their rows of the level's one
    extension, which began at stream offset ``base_off`` (the cursors do
    not move: ``OtExtReceiver.extend_rows``).  Returns (u column words,
    T rows as planes uint32[S*4, n], the range's strings bool[n, S])."""
    S = flat.shape[1]
    return _ev_extend(
        *rcv.shard_state, flat, base_off + t0 * S // 512, t0, n
    )


def gb_chunk_extend(snd: otext.OtExtSender, u_cols, S: int, base_off: int,
                    t0: int, n: int):
    """The sender's ``otext`` step: Q rows of tests ``[t0, t0 + n)`` from
    those rows' column words of the peer's u-matrix, as planes
    uint32[S*4, n] (``OtExtSender.extend_rows``)."""
    return snd.extend_rows(n * S, u_cols, base_off, t0 * S, S)


@partial(jax.jit, static_argnames=("field", "garbler", "n"))
def gb_chunk_pair(b2a_seed, t0, field, garbler: int, n: int):
    """:func:`b2a_payload_pair` of tests ``[t0, t0 + n)`` as a program of
    its own: the garbled-circuit path's ``b2a`` step (its message is the
    ``garble`` span's, :func:`gb_chunk_garble`)."""
    return b2a_payload_pair(field, b2a_seed, n, garbler, t0)


@partial(jax.jit, static_argnames=("field", "garbler", "n", "pallas"))
def _gb_table(b2a_seed, q, flat, s_block, idx, t0, field, garbler: int,
              n: int, pallas: bool):
    S, W = flat.shape[1], payload_words(field)
    r1, w0, w1 = b2a_payload_pair(field, b2a_seed, n, garbler, t0)
    x = jax.lax.dynamic_slice_in_dim(flat, t0, n)
    # result 1 (strings equal) -> receiver learns r0 (collect.rs:439-456)
    if pallas:
        from ..ops import otext_pallas

        msg = otext_pallas.ot2s_encrypt_planes(
            q, s_block, x.T, w1.T, w0.T, W, idx, domain=_OT2S_DOMAIN
        )
    else:
        msg = _ot2s_encrypt_packed_xla(
            _test_rows(q, S), s_block, x, w1, w0, W, idx
        )
    return msg, r1


def gb_chunk_table(field, b2a_seed, q, flat, s_block, idx0: int, t0: int,
                   n: int, garbler: int):
    """The sender's ``b2a`` step on the 1-of-2^S path for tests
    ``[t0, t0 + n)`` of the level's flat strings: the share pair of those
    tests (:func:`b2a_payload_pair`) and their planar payload table, from
    their extension rows ``q`` (planes uint32[S*4, n],
    :func:`gb_chunk_extend`).  Returns (the table, r1 — the sender's
    additive shares)."""
    return _gb_table(
        b2a_seed, q, flat, s_block, idx0 + t0, t0, field, garbler, n,
        _ot2s_pallas_engine(),
    )


@partial(jax.jit, static_argnames=("W", "n", "pallas"))
def _gb_garble(s_block, q, gc_seed, flat, w1, w0, idx, t0, W: int, n: int,
               pallas: bool):
    B, S = flat.shape
    return gc._garble_rows_packed(
        s_block, _test_rows(q, S), gc_seed,
        jax.lax.dynamic_slice_in_dim(flat, t0, n), w1, w0, W, idx, B, t0,
        pallas,
    )


def gb_chunk_garble(s_block, q, gc_seed, flat, w0, w1, W: int, idx0: int,
                    t0: int, n: int):
    """The sender's ``garble`` step for tests ``[t0, t0 + n)``: their
    planar range of the level's packed garbled batch
    (``gc.garble_equality_payload_packed_rows``), the payload pair of
    :func:`gb_chunk_pair` riding the output labels."""
    # result 1 (strings equal) -> receiver learns r0 (collect.rs:439-456)
    return _gb_garble(
        s_block, q, gc_seed, flat, w1, w0, idx0 + t0, t0, W, n,
        flat.shape[1] >= 2 and gc._pallas_engine(),
    )


@partial(jax.jit, static_argnames=("S", "W", "pallas"))
def _ev_eval(t_planes, msg, idx, S: int, W: int, pallas: bool):
    t_rows = _test_rows(t_planes, S)
    if pallas:
        from ..ops import gc_pallas

        return gc_pallas.eval_equality_payload_packed(msg, t_rows, W, idx)[1]
    return gc._eval_equality_payload_packed_xla(msg, t_rows, S, W, idx)[1]


def ev_chunk_eval(t_planes, y, msg, W: int, idx0: int, t0: int):
    """The receiver's ``eval`` step on the garbled-circuit path: the
    payload words uint32[n, W] of tests ``[t0, t0 + n)``, from that
    range's planar message and its T rows (planes uint32[S*4, n])."""
    S = y.shape[1]
    return _ev_eval(
        t_planes, msg, idx0 + t0, S, W, S >= 2 and gc._pallas_engine()
    )


@partial(jax.jit, static_argnames=("field", "pallas"))
def _ev_open(t_planes, y, msg, idx, field, pallas: bool):
    S, W = y.shape[1], payload_words(field)
    if pallas:
        from ..ops import otext_pallas

        w = otext_pallas.ot2s_decrypt_planes(
            t_planes, y.T, msg, W, idx, domain=_OT2S_DOMAIN
        ).T
    else:
        w = _ot2s_decrypt_packed_xla(
            _test_rows(t_planes, S), y, msg, S, W, idx
        )
    return words_to_field(field, w)


def ev_chunk_open(field, t_planes, y, msg, idx0: int, t0: int):
    """The receiver's ``b2a`` step on the 1-of-2^S path: opens the planar
    table of tests ``[t0, t0 + n)`` with their T rows (planes
    uint32[S*4, n], :func:`ev_chunk_extend`) -> field values
    [n(, limbs)] (r0 where the strings are equal, else r1)."""
    return _ev_open(
        t_planes, y, msg, idx0 + t0, field, _ot2s_pallas_engine()
    )


# the garbled-circuit path's ``b2a`` step on the receiver's side
ev_chunk_field = jax.jit(words_to_field, static_argnums=0)


# ---------------------------------------------------------------------------
# Warmup: compile the per-shape kernel chain without touching live state
# ---------------------------------------------------------------------------


# one process-wide throwaway OT session for ALL warmup calls: the
# session exists only to drive compiles, and a fresh Chou-Orlandi base
# exchange costs ~1.5 s of host-side scalar crypto — paying it once per
# (bucket, level-kind, server) made warmup base-OT-bound.  Reuse is
# sound: every warm call is self-consistent (its own u/t/idx0), the
# counters just keep advancing, and nothing derived from the session
# ever leaves warm_level_kernels.
_warm_sessions: tuple | None = None


def _warm_pair():
    global _warm_sessions
    if _warm_sessions is None:
        _warm_sessions = otext.inprocess_pair()
    return _warm_sessions


def warm_level_kernels(packed, d: int, field, path: str = "auto",
                       share_sums=None, radix: int = 1,
                       put=jax.device_put) -> None:
    """Run the WHOLE per-level 2PC kernel chain — string extraction,
    then for each of the level's chunks (:func:`level_chunks`) the
    programs of its spans (``ev_chunk_extend``, the sender's rows of the
    Δ-OT extension, ``gb_chunk_table`` at both garbling signs or
    ``gb_chunk_pair`` + ``gb_chunk_garble``, whichever :func:`ot_path`
    picks for this shape under the config's ``path`` knob, and
    ``ev_chunk_open``), then
    the alive-gated share sums — on a THROWAWAY in-process OT
    session, so every jit program a real level of this shape will
    dispatch is compiled (and lands in the persistent compile cache,
    utils/compile_cache) before measured crawl time starts.  The live OT
    sessions and the data plane are never touched; the outputs are
    discarded.

    The wire arrays (ev u-matrix, the sender's planar message) ROUND
    TRIP through host numpy exactly like the live socket path: jit
    executables key on input shardings/placements, so feeding the
    consumer the producer's still-on-device output would warm programs a
    live crawl — whose wire inputs arrive as host pickles — never
    dispatches.  Single-device runs happened to tolerate the mismatch;
    a multi-chip server (parallel/server_mesh.py) does not, because its
    device-side outputs carry mesh shardings the live host inputs lack.

    ``share_sums`` overrides the share-sum reduction (the multi-chip
    server passes its ICI-psum form, ``ServerMesh.node_share_sums``, so
    the sharded reduction program is warmed too); None = the
    single-device :func:`node_share_sums`.

    ``put`` places the level's constants (the b2a seed, the sender's
    ``s``) as the live level does: ``jax.device_put``, or a multi-chip
    server's ``ServerMesh.gather`` (its own first chip).

    ``radix`` > 1 warms the fused radix-2^k shapes: the string stage
    reads the radix packed layout and the equality chain runs at the
    fused width S' = 2*d*radix (which may route through the GC ladder
    where the radix-1 shape took ot2s — :func:`ot_path` decides from
    S' exactly as the live crawl does)."""
    strs = child_strings_radix(packed, d, radix)
    F_, C, N, S = strs.shape
    B = F_ * C * N
    flat = strs.reshape(B, S)
    snd, rcv = _warm_pair()
    zero = np.zeros(4, np.uint32)
    gseed, bseed = derive_seed(zero, 1, 0), derive_seed(zero, 2, 0)
    W, p = payload_words(field), ot_path(S, path)
    # the live level's chunks, each through the live level's calls
    # (protocol/rpc.py ``_crawl_counts_secure``): one set of programs a
    # chunk size, the same for every bucket that cuts into whole chunks
    idx0, off_r, off_s = rcv.consumed, rcv.stream_offset, snd.stream_offset
    rcv.advance(B * S)
    snd.advance(B * S)
    # a level's constants are on the device before its first chunk
    bseed, s_block = put(bseed), put(snd.s_block)
    vals, warmed = [], set()
    for t0, n in level_chunks(B, S, W, p):
        u, t_rows, y = ev_chunk_extend(rcv, flat, off_r, t0, n)
        # fhh-lint: disable=chunked-device-readback,host-sync-in-hot-loop (warmup only: the live level's per-chunk wire round trip, see docstring)
        u = np.asarray(u)
        q = gb_chunk_extend(snd, u, S, off_s, t0, n)
        # the real crawl alternates the garbler per level, so each
        # server runs BOTH signs of the share pair (r0 + 1 and r0 - 1),
        # and the sign is a static of the program that draws it: the
        # other sign's program once a chunk size
        signs = (0,) if n in warmed else (1, 0)
        warmed.add(n)
        for g in signs:
            if p == "ot2s":
                msg, _ = gb_chunk_table(
                    field, bseed, q, flat, s_block, idx0, t0, n, g
                )
            else:
                # fhh-lint: disable=recompile-churn (warmup only: the two signs and each chunk size are the compiles it is here to make)
                _, w0, w1 = gb_chunk_pair(bseed, t0, field, g, n)
                msg = gb_chunk_garble(
                    s_block, q, gseed, flat, w0, w1, W, idx0, t0, n
                )
        # fhh-lint: disable=chunked-device-readback,host-sync-in-hot-loop (as above)
        msg = np.asarray(msg)
        if p == "ot2s":
            vals.append(ev_chunk_open(field, t_rows, y, msg, idx0, t0))
        else:
            vals.append(ev_chunk_field(
                field, ev_chunk_eval(t_rows, y, msg, W, idx0, t0)
            ))
    vals = vals[0] if len(vals) == 1 else jnp.concatenate(vals)
    w = jnp.ones((F_, C, N), bool)
    reduce_fn = node_share_sums if share_sums is None else share_sums
    jax.block_until_ready(
        reduce_fn(field, vals.reshape((F_, C, N) + field.limb_shape), w)
    )


def warm_level_kernels_sharded(ks, packed, d: int, F: int, N: int, field,
                               path: str = "auto", radix: int = 1) -> None:
    """The :func:`warm_level_kernels` contract for a ROW-SHARDED kernel
    level (parallel/kernel_shard.py): compile the sharded flat builder,
    both roles of the row-sharded extension, the per-shard equality
    kernel at BOTH garbling signs (the live crawl alternates the garbler
    per level and the sign is a static of the compiled program), the
    per-shard open, and the scatter + ICI-psum share-sum program — on
    the same throwaway OT session, with the u-matrix and the planar
    frame round-tripping through host numpy exactly like the live
    socket path (per-shard assembly + re-upload included, so the
    device_put placements match live).  ``packed`` arrives in its live
    mesh sharding (the client-axis expansion layout)."""
    from ..parallel import kernel_shard

    flat = kernel_shard.shard_flat(ks, packed, d, F, N, radix)
    snd, rcv = _warm_pair()
    zero = np.zeros(4, np.uint32)
    gseed, bseed = derive_seed(zero, 1, 0), derive_seed(zero, 2, 0)
    p = ot_path(2 * d * radix, path)
    vals_r = None
    for g in (0, 1):
        _, _, _, vals_r = kernel_shard.run_level_pair(
            ks, snd, rcv, flat, flat, gseed, bseed, field, g, p
        )
    C = 1 << (d * radix)
    w = np.ones((F, C, N), bool)
    jax.block_until_ready(
        kernel_shard.share_sums(ks, field, vals_r, w, F, C, N)
    )
