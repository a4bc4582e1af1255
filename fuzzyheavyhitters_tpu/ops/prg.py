"""Fixed-key length-doubling PRG on 128-bit seeds, as batched TPU array ops.

The reference implements this with a fixed-key AES-128 in Davies-Meyer mode
(``AES_0(seed) ^ seed`` with the seed loaded as the counter; ref:
src/prg.rs:92-122, 199-270, with 8-way block batching for throughput).  AES
without AES-NI is a table-lookup cipher — gathers are the worst op class on a
TPU's vector unit — so the TPU-native design swaps the primitive, not the
construction: a fixed-key **ChaCha** permutation with the 128-bit seed as the
input block, feed-forward add (the ChaCha block function's built-in
Davies-Meyer structure), which is pure 32-bit add/xor/rotate — exactly what
the VPU executes at full width.  Every (client, dim, side, level) expansion is
one batched call; there is no per-key loop anywhere.

Semantics preserved from the reference (pinned by tests/oracle.py):

- length-doubling ``expand``: seed -> (left child seed, right child seed,
  2 "t" bits, 2 "y" bits)  (prg.rs:92-122);
- the seed's low 4 bits of byte 0 are masked to zero before expansion
  (prg.rs:97: ``key_short``), so seeds carry 124 bits of entropy;
- the reference then derives the t/y bits from the *masked* byte
  (prg.rs:103-104), making them the constants (1,1)/(1,1).  Honest
  seed-derived bits (``DERIVED_BITS = True``) are the production default
  here; setting it False reproduces the reference's constant-bit quirk for
  parity work.  Protocol correctness holds either way (the bits cancel in
  correction words), and the test-suite runs both.
- a CTR-mode stream over the same fixed-key block function for sampling
  field elements / random bytes (prg.rs:184-270 ``FixedKeyPrgStream``).

Security note: fixed-key ChaCha here plays the role fixed-key AES plays in
the reference — a correlation-robust hash for FSS (Guo et al. 2020 model).
``N_ROUNDS = 8`` matches the margin philosophy of the reference's 10-round
fixed-key AES (a reduced-round fixed-key cipher as CR hash): the best
public distinguisher on ChaCha is on 7 rounds, so 8 keeps a one-round
margin in a model where the adversary does not even control the key.
Measured cost of more margin (v5e, round 4 of the plug-in era):
in the GC/OT hash role garbling is bandwidth-bound, so 12/20 rounds cost
only +3% / +6% (18.7 -> 19.3 / 19.8 ms per 262144-wire garble) — an
operator wanting standard-cipher margins can raise ``N_ROUNDS`` to 20
nearly free there; the FSS keygen/expand kernels are cipher-bound and pay
~linearly (~1.5x/2.5x), which is why 8 stays the default for the PRG
role.  ``N_ROUNDS`` is read at trace time (one global; all roles move
together — a per-role split is deliberate non-complexity until someone
needs it).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SEED_WORDS = 4  # 128-bit seeds as uint32[..., 4], little-endian word order
N_ROUNDS = 8  # ChaCha double-round count = N_ROUNDS // 2

# Round-loop form, read at TRACE time (set before the first jit call in the
# process; bin/server.py sets it for the TPU backend):
#   False -> lax.scan over double-rounds: ~4x smaller HLO per call site, the
#            right default on compile-bound hosts (XLA:CPU on small cores);
#   True  -> unrolled rounds: ~6% faster keygen on the TPU chip.
# Both forms compute identical bits (same math, one loop rolled).
CHACHA_UNROLL = False

# "expand 32-byte k" — the standard ChaCha constant words.
_SIGMA = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)
# Fixed 256-bit key, public by construction (nothing-up-my-sleeve: the
# reference hardcodes its AES key too — the PRG's security is in the seed,
# the key only needs to be fixed and independent of the data).
_FIXED_KEY = (
    0x243F6A88, 0x85A308D3, 0x13198A2E, 0x03707344,
    0xA4093822, 0x299F31D0, 0x082EFA98, 0xEC4E6C89,
)  # first 8 words of pi's fractional part (as in Blowfish's P-array)

# Production default: honest seed-derived t/y bits.  False reproduces the
# reference's observed constant-bit quirk (prg.rs:103-104 reads the masked
# byte, so its bits are the constants (1,1)/(1,1)) and remains the
# parity/test mode — the suite pins both settings.  Protocol correctness
# holds either way (the bits cancel in correction words); derived bits are
# the evidently *intended* construction, so they ship as the default.
DERIVED_BITS = True

def _rotl(x, n: int):
    return (x << n) | (x >> (32 - n))


def _quarter_round(a, b, c, d):
    a = a + b
    d = _rotl(d ^ a, 16)
    c = c + d
    b = _rotl(b ^ c, 12)
    a = a + b
    d = _rotl(d ^ a, 8)
    c = c + d
    b = _rotl(b ^ c, 7)
    return a, b, c, d


def chacha_block(block: jax.Array) -> jax.Array:
    """ChaCha block function on uint32[..., 4] input blocks -> uint32[..., 16].

    State = 4 constant words | 8 fixed-key words | the 4 input-block words,
    permuted N_ROUNDS rounds, with the standard feed-forward addition (which
    makes the map non-invertible — the Davies-Meyer role of prg.rs:120).

    Column-vectorized (SIMD ChaCha): the 4x4 state's rows live in one
    ``uint32[4, 4, ...]`` tensor; a column round is ONE quarter-round whose
    ops each span all 4 columns, and the diagonal round is the same after
    rolling row k by k — so a block call is ~8 wide quarter-rounds of HLO
    instead of 32 scalar-lane ones.  This quarters both the compile-time
    footprint of every kernel that embeds the PRG (the whole suite is
    compile-bound on 1-core XLA:CPU hosts) and the op-dispatch count at
    runtime.  The math (and thus every output bit) is unchanged.
    """
    block = jnp.asarray(block, jnp.uint32)
    if block.shape[-1] != SEED_WORDS:
        raise ValueError(f"input blocks must be uint32[..., 4], got {block.shape}")
    shape = block.shape[:-1]
    const = jnp.asarray(_SIGMA + _FIXED_KEY, jnp.uint32)
    # XOR with a zero derived from the input: a no-op numerically, but it
    # makes the constant rows data-dependent on `block`, so under shard_map
    # they carry the same varying-axes annotation as the input and the
    # round scan's carry types line up (scan-vma rule).
    rows = jnp.broadcast_to(const, shape + (12,)) ^ (block[..., :1] & jnp.uint32(0))
    # state rows: [..., 4 cols] each; row r holds words 4r..4r+3
    a = rows[..., 0:4]
    b = rows[..., 4:8]
    c = rows[..., 8:12]
    d = block
    init = (a, b, c, d)

    def _double_round(state, _):
        a, b, c, d = state
        # column round: one QR across all 4 columns at once
        a, b, c, d = _quarter_round(a, b, c, d)
        # diagonalize: row k rolls left by k -> diagonal round is a column
        # round on the rolled rows (standard SIMD ChaCha row rotation)
        b = jnp.roll(b, -1, axis=-1)
        c = jnp.roll(c, -2, axis=-1)
        d = jnp.roll(d, -3, axis=-1)
        a, b, c, d = _quarter_round(a, b, c, d)
        b = jnp.roll(b, 1, axis=-1)
        c = jnp.roll(c, 2, axis=-1)
        d = jnp.roll(d, 3, axis=-1)
        return (a, b, c, d), None

    if CHACHA_UNROLL:
        for _ in range(N_ROUNDS // 2):
            (a, b, c, d), _ = _double_round((a, b, c, d), None)
    else:
        (a, b, c, d), _ = jax.lax.scan(
            _double_round, (a, b, c, d), None, length=N_ROUNDS // 2
        )
    return jnp.concatenate(
        [x + y for x, y in zip((a, b, c, d), init)], axis=-1
    )


def mask_seed(seed: jax.Array) -> jax.Array:
    """Clear the low 4 bits of seed byte 0 (prg.rs:97 ``key_short``)."""
    seed = jnp.asarray(seed, jnp.uint32)
    return seed.at[..., 0].set(seed[..., 0] & jnp.uint32(0xFFFFFFF0))


def expand(seed: jax.Array, derived_bits: bool | None = None):
    """Length-doubling expansion of uint32[..., 4] seeds.

    Returns ``(s_l, s_r, bits, y_bits)``: child seeds uint32[..., 4] and
    bool[..., 2] t/y bit pairs, exactly the reference's ``PrgOutput``
    (prg.rs:56-60, 92-122).
    """
    # Resolve the module-global default *eagerly* (outside the jitted core) so
    # toggling DERIVED_BITS is never baked into a cached trace.
    if derived_bits is None:
        derived_bits = DERIVED_BITS
    return _expand_jit(seed, derived_bits)


@partial(jax.jit, static_argnames=("derived_bits",))
def _expand_jit(seed: jax.Array, derived_bits: bool):
    seed = mask_seed(seed)
    out = chacha_block(seed)
    # Fusion fence on the child-seed slices: without it, XLA:CPU's
    # loop-fusion emitter re-evaluates the whole ChaCha DAG once per
    # consumer output element when a consumer slices the block (measured: a
    # shard_mapped expand->slice at [128,32,2,2] hung >300 s compiling,
    # 2.5 s with the fence).  The fence sits HERE, not in chacha_block, so
    # that callers which consume only the t/y bits (the packed share-bit
    # expansion in default bit mode — the bits are constants there) let
    # the entire dead cipher evaluation fall to DCE.
    out = jax.lax.optimization_barrier(out)
    s_l = out[..., 0:4]
    s_r = out[..., 4:8]
    if derived_bits:
        w = out[..., 8]
        bits = jnp.stack([w & 1 == 0, w & 2 == 0], axis=-1)
        y_bits = jnp.stack([w & 4 == 0, w & 8 == 0], axis=-1)
    else:
        # prg.rs:103-104 reads the masked byte -> constants (True, True).
        bits = jnp.ones(seed.shape[:-1] + (2,), bool)
        y_bits = jnp.ones(seed.shape[:-1] + (2,), bool)
    return s_l, s_r, bits, y_bits


@partial(jax.jit, static_argnames=("n_blocks",))
def stream_blocks(seed: jax.Array, n_blocks: int, offset=0) -> jax.Array:
    """CTR-mode stream: uint32[..., 4] seed -> uint32[..., n_blocks, 16].

    The seed is the starting counter block; successive blocks increment word 0
    (mod 2^32 — fine for any stream < 256 GiB), mirroring the reference's
    AES-CTR ``FixedKeyPrgStream`` (prg.rs:184-270) with the seed loaded as the
    initial counter (prg.rs:199-232).  Unlike :func:`expand`, the stream path
    uses the seed **unmasked** — the reference masks only in ``expand_dir``
    (prg.rs:97), not in its CTR stream (prg.rs:136).

    ``offset`` (scalar, may be traced) starts the counter ``offset`` blocks
    in — session streams (OT extension) consume the stream incrementally.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    ctr = jnp.arange(n_blocks, dtype=jnp.uint32) + jnp.asarray(offset, jnp.uint32)
    blocks = jnp.broadcast_to(
        seed[..., None, :], seed.shape[:-1] + (n_blocks, 4)
    )
    blocks = blocks.at[..., 0].add(ctr)
    # same fusion fence as _expand_jit: stream consumers slice the block
    return jax.lax.optimization_barrier(chacha_block(blocks))


def stream_words(seed: jax.Array, n_words: int, offset=0) -> jax.Array:
    """uint32[..., 4] seed -> uint32[..., n_words] pseudorandom words,
    from block ``offset`` of the stream on (:func:`stream_blocks`)."""
    n_blocks = -(-n_words // 16)
    out = stream_blocks(seed, n_blocks, offset)
    return out.reshape(out.shape[:-2] + (n_blocks * 16,))[..., :n_words]


# ---------------------------------------------------------------------------
# NumPy mirror (bit-exact, used by the test oracle and host-side tooling)
# ---------------------------------------------------------------------------


def _np_rotl(x, n):
    x = x.astype(np.uint32)
    return ((x << np.uint32(n)) | (x >> np.uint32(32 - n))).astype(np.uint32)


def np_chacha_block(block: np.ndarray) -> np.ndarray:
    """NumPy twin of :func:`chacha_block` (same shapes, bit-exact)."""
    block = np.asarray(block, np.uint32)
    if block.shape[-1] != SEED_WORDS:
        raise ValueError(f"input blocks must be uint32[..., 4], got {block.shape}")
    shape = block.shape[:-1]
    x = [np.broadcast_to(np.uint32(w), shape).copy() for w in _SIGMA + _FIXED_KEY]
    x += [block[..., i].copy() for i in range(4)]
    init = [v.copy() for v in x]

    def qr(a, b, c, d):
        a = (a + b).astype(np.uint32)
        d = _np_rotl(d ^ a, 16)
        c = (c + d).astype(np.uint32)
        b = _np_rotl(b ^ c, 12)
        a = (a + b).astype(np.uint32)
        d = _np_rotl(d ^ a, 8)
        c = (c + d).astype(np.uint32)
        b = _np_rotl(b ^ c, 7)
        return a, b, c, d

    with np.errstate(over="ignore"):  # u32 wraparound is the cipher's add
        for _ in range(N_ROUNDS // 2):
            x[0], x[4], x[8], x[12] = qr(x[0], x[4], x[8], x[12])
            x[1], x[5], x[9], x[13] = qr(x[1], x[5], x[9], x[13])
            x[2], x[6], x[10], x[14] = qr(x[2], x[6], x[10], x[14])
            x[3], x[7], x[11], x[15] = qr(x[3], x[7], x[11], x[15])
            x[0], x[5], x[10], x[15] = qr(x[0], x[5], x[10], x[15])
            x[1], x[6], x[11], x[12] = qr(x[1], x[6], x[11], x[12])
            x[2], x[7], x[8], x[13] = qr(x[2], x[7], x[8], x[13])
            x[3], x[4], x[9], x[14] = qr(x[3], x[4], x[9], x[14])
        return np.stack(
            [(a + b).astype(np.uint32) for a, b in zip(x, init)], axis=-1
        )


def np_expand(seed: np.ndarray, derived_bits: bool | None = None):
    """NumPy twin of :func:`expand` — same shapes and bit-exact outputs.

    Lets host-only paths (client simulators, CPU-mesh dryruns) build key
    material without compiling the device program: XLA:CPU compiles the
    keygen scan pathologically slowly (see tests/conftest.py), and the
    NumPy mirror sidesteps the device entirely.
    """
    if derived_bits is None:
        derived_bits = DERIVED_BITS
    seed = np.array(seed, np.uint32, copy=True)
    seed[..., 0] &= np.uint32(0xFFFFFFF0)  # mask_seed (prg.rs:97)
    out = np_chacha_block(seed)
    s_l = out[..., 0:4]
    s_r = out[..., 4:8]
    if derived_bits:
        w = out[..., 8]
        bits = np.stack([w & 1 == 0, w & 2 == 0], axis=-1)
        y_bits = np.stack([w & 4 == 0, w & 8 == 0], axis=-1)
    else:
        bits = np.ones(seed.shape[:-1] + (2,), bool)
        y_bits = np.ones(seed.shape[:-1] + (2,), bool)
    return s_l, s_r, bits, y_bits


def np_expand_bytes(seed: bytes, derived_bits: bool | None = None):
    """bytes-interface twin of :func:`expand` for the spec oracle.

    seed: 16 bytes -> (s_l bytes, s_r bytes, (t0,t1), (y0,y1)).
    """
    if derived_bits is None:
        derived_bits = DERIVED_BITS
    words = np.frombuffer(bytes([seed[0] & 0xF0]) + seed[1:], dtype="<u4")
    out = np_chacha_block(words)
    s_l = out[0:4].astype("<u4").tobytes()
    s_r = out[4:8].astype("<u4").tobytes()
    if derived_bits:
        w = int(out[8])
        bits = (w & 1 == 0, w & 2 == 0)
        y_bits = (w & 4 == 0, w & 8 == 0)
    else:
        bits = (True, True)
        y_bits = (True, True)
    return s_l, s_r, bits, y_bits


def np_stream_words(seed: np.ndarray, n_words: int) -> np.ndarray:
    """NumPy twin of :func:`stream_words` (seed unmasked, ctr in word 0)."""
    seed = np.asarray(seed, np.uint32)
    n_blocks = -(-n_words // 16)
    ctr = np.arange(n_blocks, dtype=np.uint32)
    blocks = np.broadcast_to(
        seed[..., None, :], seed.shape[:-1] + (n_blocks, 4)
    ).copy()
    with np.errstate(over="ignore"):
        blocks[..., 0] += ctr
    out = np_chacha_block(blocks)
    return out.reshape(out.shape[:-2] + (n_blocks * 16,))[..., :n_words]


def seeds_from_bytes(data: bytes) -> np.ndarray:
    """16-byte chunks -> uint32[n, 4] seed array."""
    assert len(data) % 16 == 0
    return np.frombuffer(data, dtype="<u4").reshape(-1, 4)


def seed_to_bytes(seed) -> bytes:
    return np.asarray(seed, dtype="<u4").tobytes()
