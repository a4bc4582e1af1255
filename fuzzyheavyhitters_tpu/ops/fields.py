"""Prime fields as JAX dtype modules.

Two fields, mirroring the reference's dual-field design (inner tree levels in
a fast 62-bit field, final level in a 255-bit field):

- ``FE62``: p = 2^62 - 2^30 - 1 on ``uint64`` tensors with the same lazy
  bit-reduction representation as the reference (ref: src/fastfield.rs:24-107)
  — shifts and masks only, no division, XLA/TPU-friendly.
- ``F255``: p = 2^255 - 19 on ``uint32[..., 8]`` little-endian limb tensors
  (ref: src/field.rs:19 — its comment says 2^255-10 but the hex constant
  ``7fff...ffed`` is 2^255-19; we match the constant).  Values are kept
  canonical (< p); ops are fixed 8-limb carry chains.

Both expose the same functional surface (zeros/from_int/add/sub/neg/canon/
ge/sample/pack...), so the aggregation engine is generic over the level
field.  ``sample`` maps uniform random words to near-uniform field elements
with O(2^-62) statistical bias — data-independent shapes (no rejection
loops), unlike the reference's host-side rejection sampling
(ref: src/field.rs:251-264), which cannot be expressed as a fixed-shape
device program.
"""

from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

_M62 = (1 << 62) - 1
_P62 = (1 << 62) - (1 << 30) - 1


class FE62:
    """p = 2^62 - 2^30 - 1 over uint64, lazily reduced (val <= ~2^62)."""

    P = _P62
    dtype = jnp.uint64
    limb_shape = ()  # scalar per element
    SAMPLE_WORDS = 4  # uniform u32 words one :meth:`sample` draw consumes

    @staticmethod
    def _bit_reduce(v):
        # 2^62 === 2^30 + 1 (mod p)   (fastfield.rs:86-95)
        excess = v >> 62
        low = v & jnp.uint64(_M62)
        return low + excess + (excess << 30)

    @classmethod
    def new(cls, v):
        return cls._bit_reduce(jnp.asarray(v, jnp.uint64))

    @classmethod
    def zeros(cls, shape):
        return jnp.zeros(shape, jnp.uint64)

    @classmethod
    def from_int(cls, x: int):
        return jnp.asarray(x % cls.P, jnp.uint64)

    @classmethod
    def canon(cls, v):
        """Fully-reduced value in [0, p)  (fastfield.rs:100-107, 147-152)."""
        v = cls._bit_reduce(cls._bit_reduce(v))
        return jnp.where(v >= cls.P, v - cls.P, v)

    @classmethod
    def add(cls, a, b):
        return cls._bit_reduce(a + b)

    @classmethod
    def neg(cls, a):
        return cls._bit_reduce(jnp.uint64(2 * cls.P) - a)

    @classmethod
    def sub(cls, a, b):
        return cls.add(a, cls.neg(b))

    @classmethod
    def mul(cls, a, b):
        """Full 124-bit product reduced mod p, u64 ops only."""
        a = cls._bit_reduce(cls._bit_reduce(a))  # < 2^62
        b = cls._bit_reduce(cls._bit_reduce(b))
        mask32 = jnp.uint64(0xFFFFFFFF)
        a0, a1 = a & mask32, a >> 32  # a1 < 2^30
        b0, b1 = b & mask32, b >> 32
        t0 = a0 * b0
        t1 = a0 * b1 + a1 * b0  # < 2^63
        t2 = a1 * b1  # < 2^60
        t1 = t1 + (t0 >> 32)
        c0 = t0 & mask32
        t2 = t2 + (t1 >> 32)  # < 2^61
        c1 = t1 & mask32
        # product = c0 + c1*2^32 + t2*2^64 ; split at bit 62
        low = ((c1 & jnp.uint64(0x3FFFFFFF)) << 32) | c0
        high = (t2 << 2) | (c1 >> 30)
        # product === low + high*(2^30 + 1) (mod p); split high to keep u64
        h0, h1 = high & mask32, high >> 32
        r = cls._bit_reduce(low + high)
        r = cls._bit_reduce(r + (h0 << 30))
        r = cls._bit_reduce(r + (h1 << 30))
        return cls._bit_reduce(r + h1)

    @classmethod
    def pow_const(cls, a, e: int):
        """a^e for a Python-int exponent: square-and-multiply as a
        ``lax.scan`` over the exponent bits (LSB-first), so the compiled
        graph is one square + one select-multiply regardless of exponent
        size — an unrolled chain at 255-bit exponents is a ~10^5-op graph
        that the TPU compiler cannot digest."""
        return _pow_scan(cls, jnp.asarray(a, jnp.uint64), e)

    @classmethod
    def recip(cls, a):
        """Multiplicative inverse by Fermat: a^(p-2)  (ref: fastfield.rs:154
        ``recip`` — same exponentiation-by-squaring construction).
        recip(0) = 0 (garbage-in convention, as in the reference)."""
        return cls.pow_const(a, cls.P - 2)

    @classmethod
    def ge(cls, a, b):
        return cls.canon(a) >= cls.canon(b)

    @classmethod
    def eq(cls, a, b):
        return cls.canon(a) == cls.canon(b)

    # -- Block codec (OT payloads travel as 128-bit blocks; ref:
    # fastfield.rs:414-431 Block (de)serialization) ----------------------

    @classmethod
    def to_blocks(cls, v) -> "jax.Array":
        """[...] canonical values -> uint32[..., 4] little-endian blocks."""
        v = cls.canon(v)
        lo = (v & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (v >> 32).astype(jnp.uint32)
        zeros = jnp.zeros_like(lo)
        return jnp.stack([lo, hi, zeros, zeros], axis=-1)

    @classmethod
    def from_blocks(cls, blocks) -> "jax.Array":
        """uint32[..., 4] blocks -> field values (upper words ignored mod p)."""
        blocks = jnp.asarray(blocks, jnp.uint64)
        lo = blocks[..., 0] | (blocks[..., 1] << 32)
        hi = blocks[..., 2] | (blocks[..., 3] << 32)
        return cls.add(
            cls._bit_reduce(lo), cls.mul(cls.new(hi), cls.from_int((1 << 64) % cls.P))
        )

    @classmethod
    def sample(cls, words):
        """uniform uint32[..., 4] -> near-uniform field elements [...]."""
        words = jnp.asarray(words, jnp.uint64)
        lo = (words[..., 0] | (words[..., 1] << 32)) & jnp.uint64(_M62)
        hi = words[..., 2] | (words[..., 3] << 32)
        # value = hi*2^62 + lo (mod p): 126 uniform bits -> bias ~2^-64
        mask32 = jnp.uint64(0xFFFFFFFF)
        h0, h1 = hi & mask32, hi >> 32
        r = cls._bit_reduce(lo + hi)
        r = cls._bit_reduce(r + (h0 << 30))
        r = cls._bit_reduce(r + (h1 << 30))
        return cls._bit_reduce(r + h1)

    @classmethod
    def sum(cls, v, *, axis):
        """Modular sum along ``axis`` for up to ~2^31 canonical terms.

        Splits into 32-bit halves so the plain integer sums cannot overflow
        u64, then recombines mod p — one reduction for the whole axis instead
        of the reference's per-element add chain (collect.rs:487-501).
        """
        v = cls._bit_reduce(cls._bit_reduce(v))  # < 2^62
        mask32 = jnp.uint64(0xFFFFFFFF)
        lo = jnp.sum(v & mask32, axis=axis)
        hi = jnp.sum(v >> 32, axis=axis)
        return cls.add(cls._bit_reduce(lo), cls.mul(cls.new(hi), cls.from_int(1 << 32)))

    @classmethod
    def to_numpy_ints(cls, v) -> np.ndarray:
        # cls.canon is already jitted at import (_jit_field_methods);
        # re-wrapping it here built a fresh compile cache per call
        return np.asarray(cls.canon(v), dtype=np.uint64)

    # -- host (NumPy) twins: bit-identical math with no device round trip,
    # for per-level host-side derivations (the shared wire masks in
    # protocol/rpc.py) where a device sample would add a device->host fetch --

    @staticmethod
    def _np_bit_reduce(v: np.ndarray) -> np.ndarray:
        excess = v >> np.uint64(62)
        low = v & np.uint64(_M62)
        return low + excess + (excess << np.uint64(30))

    @classmethod
    def np_add(cls, a, b) -> np.ndarray:
        return cls._np_bit_reduce(
            np.asarray(a, np.uint64) + np.asarray(b, np.uint64)
        )

    @classmethod
    def np_sample(cls, words) -> np.ndarray:
        """Host twin of :meth:`sample` (same bit-for-bit mapping)."""
        w = np.asarray(words, np.uint64)
        lo = (w[..., 0] | (w[..., 1] << np.uint64(32))) & np.uint64(_M62)
        hi = w[..., 2] | (w[..., 3] << np.uint64(32))
        mask32 = np.uint64(0xFFFFFFFF)
        h0, h1 = hi & mask32, hi >> np.uint64(32)
        r = cls._np_bit_reduce(lo + hi)
        r = cls._np_bit_reduce(r + (h0 << np.uint64(30)))
        r = cls._np_bit_reduce(r + (h1 << np.uint64(30)))
        return cls._np_bit_reduce(r + h1)


_P255 = (1 << 255) - 19
_P255_LIMBS = tuple((_P255 >> (32 * i)) & 0xFFFFFFFF for i in range(8))


class F255:
    """p = 2^255 - 19 over uint32[..., 8] little-endian limbs, canonical."""

    P = _P255
    dtype = jnp.uint32
    limb_shape = (8,)
    SAMPLE_WORDS = 8  # uniform u32 words one :meth:`sample` draw consumes

    @classmethod
    def zeros(cls, shape):
        return jnp.zeros(tuple(shape) + (8,), jnp.uint32)

    @classmethod
    def from_int(cls, x: int):
        x %= cls.P
        return jnp.array([(x >> (32 * i)) & 0xFFFFFFFF for i in range(8)], jnp.uint32)

    @staticmethod
    def _carry_chain(limbs64):
        """[..., 8] uint64 partial sums -> (uint32 limbs, carry_out uint64)."""
        out = []
        carry = jnp.zeros_like(limbs64[..., 0])
        for i in range(8):
            s = limbs64[..., i] + carry
            out.append(s & jnp.uint64(0xFFFFFFFF))
            carry = s >> 32
        return jnp.stack(out, axis=-1), carry

    @classmethod
    def _sub_p_if(cls, limbs, cond):
        """Conditionally subtract p (borrow chain); cond broadcast over limbs."""
        p = jnp.array(_P255_LIMBS, jnp.uint64)
        out = []
        borrow = jnp.zeros_like(limbs[..., 0].astype(jnp.uint64))
        for i in range(8):
            d = limbs[..., i].astype(jnp.uint64) - p[i] - borrow
            out.append(d & jnp.uint64(0xFFFFFFFF))
            borrow = (d >> 63) & jnp.uint64(1)  # underflow wraps high bit
        sub = jnp.stack(out, axis=-1).astype(jnp.uint32)
        return jnp.where(cond[..., None], sub, limbs)

    @classmethod
    def _geq_p(cls, limbs):
        ge = jnp.ones(limbs.shape[:-1], bool)
        decided = jnp.zeros(limbs.shape[:-1], bool)
        for i in reversed(range(8)):
            li = limbs[..., i]
            pi = jnp.uint32(_P255_LIMBS[i])
            gt = ~decided & (li > pi)
            lt = ~decided & (li < pi)
            ge = jnp.where(lt, False, jnp.where(gt, True, ge))
            decided = decided | gt | lt
        return ge

    @classmethod
    def add(cls, a, b):
        s64 = a.astype(jnp.uint64) + b.astype(jnp.uint64)
        limbs, carry = cls._carry_chain(s64)
        # carry*2^256 === carry*38 (mod p); carry <= 1 so one more chain settles
        limbs = cls._carry_chain(limbs.astype(jnp.uint64).at[..., 0].add(carry * 38))[0]
        limbs = limbs.astype(jnp.uint32)
        return cls._sub_p_if(limbs, cls._geq_p(limbs))

    @classmethod
    def neg(cls, a):
        p = jnp.array(_P255_LIMBS, jnp.uint64)
        out = []
        borrow = jnp.zeros_like(a[..., 0].astype(jnp.uint64))
        for i in range(8):
            d = p[i] - a[..., i].astype(jnp.uint64) - borrow
            out.append(d & jnp.uint64(0xFFFFFFFF))
            borrow = (d >> 63) & jnp.uint64(1)
        r = jnp.stack(out, axis=-1).astype(jnp.uint32)
        # p - 0 = p === 0: canonicalize
        return cls._sub_p_if(r, cls._geq_p(r))

    @classmethod
    def sub(cls, a, b):
        return cls.add(a, cls.neg(b))

    @classmethod
    def mul(cls, a, b):
        """Schoolbook 8x8 limb product + 2^256 === 38 folding (ref:
        field.rs:339-343 ``mul`` over BigUint; here a fixed-width carry
        network of u64 ops only, no bignums, XLA-friendly).

        Column sums split each 64-bit partial product into lo/hi words so no
        intermediate exceeds u64 (max 8 terms of < 2^32 each per column).
        """
        a64 = jnp.asarray(a, jnp.uint32).astype(jnp.uint64)
        b64 = jnp.asarray(b, jnp.uint32).astype(jnp.uint64)
        mask32 = jnp.uint64(0xFFFFFFFF)
        batch = jnp.broadcast_shapes(a64.shape[:-1], b64.shape[:-1])
        cols_lo = [jnp.zeros(batch, jnp.uint64) for _ in range(17)]
        cols_hi = [jnp.zeros(batch, jnp.uint64) for _ in range(17)]
        for i in range(8):
            for j in range(8):
                p = a64[..., i] * b64[..., j]
                k = i + j
                cols_lo[k] = cols_lo[k] + (p & mask32)
                cols_hi[k + 1] = cols_hi[k + 1] + (p >> 32)
        # carry-propagate into 16 product limbs (value < 2^512)
        limbs16 = []
        carry = jnp.zeros(batch, jnp.uint64)
        for k in range(16):
            s = cols_lo[k] + cols_hi[k] + carry
            limbs16.append(s & mask32)
            carry = s >> 32
        # fold: product = L + 2^256*H === L + 38*H (mod p)
        out = []
        carry = jnp.zeros(batch, jnp.uint64)
        for k in range(8):
            s = limbs16[k] + limbs16[k + 8] * jnp.uint64(38) + carry
            out.append(s & mask32)
            carry = s >> 32
        # carry < 103; fold 38*carry back in, twice: the first re-fold can
        # itself overflow 2^256 only when the value was within 38*103 of it,
        # leaving a wrapped value < 4000 — so the second re-fold cannot carry.
        for _ in range(2):
            c2 = carry * jnp.uint64(38)
            limbs = []
            for k in range(8):
                s = out[k] + c2
                limbs.append(s & mask32)
                c2 = s >> 32
            out, carry = limbs, c2
        r = jnp.stack(out, axis=-1).astype(jnp.uint32)
        r = cls._sub_p_if(r, cls._geq_p(r))
        return cls._sub_p_if(r, cls._geq_p(r))

    @classmethod
    def pow_const(cls, a, e: int):
        """a^e for a Python-int exponent (scan over exponent bits, see
        FE62.pow_const for why scan rather than unrolling)."""
        return _pow_scan(cls, jnp.asarray(a, jnp.uint32), e)

    @classmethod
    def recip(cls, a):
        """Multiplicative inverse by Fermat: a^(p-2); recip(0) = 0.  The
        reference's FieldElm has no inverse (field.rs) — added here for the
        sketch/MPC layer's field-law completeness."""
        return cls.pow_const(a, cls.P - 2)

    @classmethod
    def canon(cls, a):
        return a

    @classmethod
    def ge(cls, a, b):
        """a >= b on canonical values, limbwise big-endian compare."""
        ge = jnp.ones(a.shape[:-1], bool)
        decided = jnp.zeros(a.shape[:-1], bool)
        for i in reversed(range(8)):
            gt = ~decided & (a[..., i] > b[..., i])
            lt = ~decided & (a[..., i] < b[..., i])
            ge = jnp.where(lt, False, jnp.where(gt, True, ge))
            decided = decided | gt | lt
        return ge

    @classmethod
    def eq(cls, a, b):
        return jnp.all(a == b, axis=-1)

    @classmethod
    def sample(cls, words):
        """uniform uint32[..., 8] -> field elements [..., 8] (bias ~2^-250)."""
        limbs = jnp.asarray(words, jnp.uint32)
        limbs = cls._sub_p_if(limbs, cls._geq_p(limbs))
        limbs = cls._sub_p_if(limbs, cls._geq_p(limbs))
        return limbs

    # -- host (NumPy) twins (see FE62: per-level host derivations must not
    # cost a device round trip) --------------------------------------------

    @classmethod
    def _np_geq_p(cls, limbs: np.ndarray) -> np.ndarray:
        ge = np.ones(limbs.shape[:-1], bool)
        decided = np.zeros(limbs.shape[:-1], bool)
        for i in reversed(range(8)):
            li = limbs[..., i]
            pi = np.uint32(_P255_LIMBS[i])
            gt = ~decided & (li > pi)
            lt = ~decided & (li < pi)
            ge = np.where(lt, False, np.where(gt, True, ge))
            decided = decided | gt | lt
        return ge

    @classmethod
    def _np_sub_p_if(cls, limbs: np.ndarray, cond: np.ndarray) -> np.ndarray:
        p = np.array(_P255_LIMBS, np.uint64)
        out = np.zeros(limbs.shape, np.uint64)
        borrow = np.zeros(limbs.shape[:-1], np.uint64)
        for i in range(8):
            d = limbs[..., i].astype(np.uint64) - p[i] - borrow
            out[..., i] = d & np.uint64(0xFFFFFFFF)
            borrow = (d >> np.uint64(63)) & np.uint64(1)
        return np.where(cond[..., None], out.astype(np.uint32), limbs)

    @staticmethod
    def _np_carry_chain(limbs64: np.ndarray):
        out = np.zeros(limbs64.shape, np.uint64)
        carry = np.zeros(limbs64.shape[:-1], np.uint64)
        for i in range(8):
            s = limbs64[..., i] + carry
            out[..., i] = s & np.uint64(0xFFFFFFFF)
            carry = s >> np.uint64(32)
        return out, carry

    @classmethod
    def np_add(cls, a, b) -> np.ndarray:
        s64 = np.asarray(a, np.uint32).astype(np.uint64) + np.asarray(
            b, np.uint32
        ).astype(np.uint64)
        limbs, carry = cls._np_carry_chain(s64)
        limbs[..., 0] += carry * np.uint64(38)  # 2^256 === 38 (mod p)
        limbs = cls._np_carry_chain(limbs)[0].astype(np.uint32)
        return cls._np_sub_p_if(limbs, cls._np_geq_p(limbs))

    @classmethod
    def np_sample(cls, words) -> np.ndarray:
        """Host twin of :meth:`sample` (same bit-for-bit mapping)."""
        limbs = np.asarray(words, np.uint32)
        limbs = cls._np_sub_p_if(limbs, cls._np_geq_p(limbs))
        return cls._np_sub_p_if(limbs, cls._np_geq_p(limbs))

    @classmethod
    def sum(cls, v, *, axis):
        """Modular sum along ``axis`` via pairwise tree reduction."""
        axis = axis % (v.ndim - 1)
        v = jnp.moveaxis(v, axis, 0)
        while v.shape[0] > 1:
            n = v.shape[0]
            if n % 2:
                v = jnp.concatenate([v, cls.zeros((1,) + v.shape[1:-1])], axis=0)
                n += 1
            v = cls.add(v[: n // 2], v[n // 2 :])
        return v[0]

    @classmethod
    def to_numpy_ints(cls, v) -> np.ndarray:
        limbs = np.asarray(v, dtype=np.uint64)
        flat = limbs.reshape(-1, 8)
        out = np.array(
            [sum(int(row[i]) << (32 * i) for i in range(8)) for row in flat],
            dtype=object,
        )
        return out.reshape(limbs.shape[:-1])

    # -- BlockPair codec (ref: field.rs:465-492 — F255 OT payloads travel
    # as two 128-bit blocks) ---------------------------------------------

    @classmethod
    def to_blocks(cls, v) -> "jax.Array":
        """[..., 8] limbs -> uint32[..., 2, 4] block pairs (low block first,
        little-endian words — our canonical layout; the reference uses
        big-endian bytes, a serialization detail with no protocol effect)."""
        v = jnp.asarray(v, jnp.uint32)
        return v.reshape(v.shape[:-1] + (2, 4))

    @classmethod
    def from_blocks(cls, blocks) -> "jax.Array":
        """uint32[..., 2, 4] block pairs -> [..., 8] limbs (mod-p folded)."""
        blocks = jnp.asarray(blocks, jnp.uint32)
        limbs = blocks.reshape(blocks.shape[:-2] + (8,))
        limbs = cls._sub_p_if(limbs, cls._geq_p(limbs))
        return cls._sub_p_if(limbs, cls._geq_p(limbs))


def _pow_scan(field, a, e: int):
    """Shared square-and-multiply scan over the bits of a Python int."""
    if e == 0:
        one = field.from_int(1)
        return jnp.broadcast_to(one, a.shape[: a.ndim - len(field.limb_shape)] + one.shape)
    bits = jnp.asarray([(e >> i) & 1 for i in range(e.bit_length())], bool)
    one = jnp.broadcast_to(
        field.from_int(1), a.shape[: a.ndim - len(field.limb_shape)] + field.limb_shape
    ).astype(a.dtype)

    def step(carry, bit):
        result, base = carry
        taken = field.mul(result, base)
        result = jnp.where(bit, taken, result)
        return (result, field.mul(base, base)), None

    (result, _), _ = jax.lax.scan(step, (one, a), bits)
    return result


_P63 = (1 << 63) - 25
_M63 = (1 << 63) - 1


class U63:
    """p = 2^63 - 25 on uint64, canonical values — the reference's ``Group``
    impl for u64 (ref: field.rs:25-26, 128-188: MODULUS_64 = 2^63 - 25)."""

    P = _P63
    dtype = jnp.uint64
    limb_shape = ()
    SAMPLE_WORDS = 4  # uniform u32 words one :meth:`sample` draw consumes

    @staticmethod
    def _reduce63(v):
        # 2^63 === 25 (mod p); one bit of excess folds in 25 at a time
        return (v & jnp.uint64(_M63)) + jnp.uint64(25) * (v >> 63)

    @classmethod
    def canon(cls, v):
        v = cls._reduce63(cls._reduce63(v))
        return jnp.where(v >= cls.P, v - cls.P, v)

    @classmethod
    def zeros(cls, shape):
        return jnp.zeros(shape, jnp.uint64)

    @classmethod
    def from_int(cls, x: int):
        return jnp.asarray(x % cls.P, jnp.uint64)

    @classmethod
    def add(cls, a, b):
        # canonical inputs sum below 2^64; settle back to canonical
        return cls.canon(jnp.asarray(a, jnp.uint64) + jnp.asarray(b, jnp.uint64))

    @classmethod
    def neg(cls, a):
        return cls.canon(jnp.uint64(cls.P) - jnp.asarray(a, jnp.uint64))

    @classmethod
    def sub(cls, a, b):
        return cls.add(a, cls.neg(b))

    @classmethod
    def mul(cls, a, b):
        """126-bit product via 32-bit split, folded with 2^64 === 50."""
        a = cls.canon(jnp.asarray(a, jnp.uint64))
        b = cls.canon(jnp.asarray(b, jnp.uint64))
        mask32 = jnp.uint64(0xFFFFFFFF)
        a0, a1 = a & mask32, a >> 32  # a1 < 2^31
        b0, b1 = b & mask32, b >> 32
        t0 = a0 * b0
        t1 = a0 * b1 + a1 * b0  # < 2^64 - 2^33
        t2 = a1 * b1  # < 2^62
        t1 = t1 + (t0 >> 32)
        t2 = t2 + (t1 >> 32)
        t_low = (t0 & mask32) | ((t1 & mask32) << 32)  # product mod 2^64
        # product = t_low + t2*2^64 === t_low + 50*t2; decompose t2 to stay
        # in u64: 50*t2 = 50*t2l + (50*t2h mod p)*2^32-ish chains below
        t2l, t2h = t2 & mask32, t2 >> 32  # t2h < 2^30
        u = jnp.uint64(50) * t2h  # < 2^36
        ul, uh = u & mask32, u >> 32  # uh < 2^4
        r = cls._reduce63(cls._reduce63(t_low))
        r = cls.add(r, cls.canon(jnp.uint64(50) * t2l))
        r = cls.add(r, cls.canon(ul << 32))
        return cls.add(r, jnp.uint64(50) * uh)

    @classmethod
    def eq(cls, a, b):
        return cls.canon(a) == cls.canon(b)

    @classmethod
    def sample(cls, words):
        """uniform uint32[..., 4] -> near-uniform field elements (shaped
        device sampling; the reference rejection-samples host-side,
        field.rs:168-175)."""
        words = jnp.asarray(words, jnp.uint64)
        lo = (words[..., 0] | (words[..., 1] << 32)) & jnp.uint64(_M63)
        hi = words[..., 2] | (words[..., 3] << 32)
        return cls.add(cls._reduce63(lo), cls.mul(cls.canon(hi), cls.from_int(1 << 32)))

    @classmethod
    def sum(cls, v, *, axis):
        v = cls.canon(jnp.asarray(v, jnp.uint64))
        mask32 = jnp.uint64(0xFFFFFFFF)
        lo = jnp.sum(v & mask32, axis=axis)
        hi = jnp.sum(v >> 32, axis=axis)
        return cls.add(cls.canon(lo), cls.mul(cls.canon(hi), cls.from_int(1 << 32)))

    @classmethod
    def to_numpy_ints(cls, v) -> np.ndarray:
        # cls.canon is already jitted at import (_jit_field_methods)
        return np.asarray(cls.canon(v), dtype=np.uint64)


class Dummy:
    """The reference's no-op group (ref: field.rs:44-126): every op returns
    zero; used to stub a field slot out of a generic protocol."""

    P = 1
    dtype = jnp.uint32
    limb_shape = ()

    zeros = staticmethod(lambda shape: jnp.zeros(shape, jnp.uint32))
    from_int = staticmethod(lambda x: jnp.uint32(0))
    canon = staticmethod(lambda v: jnp.zeros_like(v))
    add = staticmethod(lambda a, b: jnp.zeros_like(a))
    sub = staticmethod(lambda a, b: jnp.zeros_like(a))
    neg = staticmethod(lambda a: jnp.zeros_like(a))
    mul = staticmethod(lambda a, b: jnp.zeros_like(a))
    eq = staticmethod(lambda a, b: jnp.ones(jnp.asarray(a).shape, bool))
    sample = staticmethod(lambda words: jnp.zeros(jnp.asarray(words).shape[:-1], jnp.uint32))

    @staticmethod
    def sum(v, *, axis):
        return jnp.zeros(tuple(np.delete(np.asarray(v.shape), axis)), jnp.uint32)


def _jit_field_methods():
    """Jit the eager entry points once per class; composing jitted calls inside
    a larger jit still inlines and fuses (XLA treats them as nested calls)."""
    for klass, names in (
        (
            FE62,
            ["new", "canon", "add", "neg", "sub", "mul", "recip", "ge", "eq",
             "sample", "to_blocks", "from_blocks"],
        ),
        (
            F255,
            ["add", "neg", "sub", "mul", "recip", "ge", "eq", "sample",
             "to_blocks", "from_blocks"],
        ),
        (U63, ["canon", "add", "neg", "sub", "mul", "eq", "sample"]),
    ):
        for name in names:
            # fhh-lint: disable=recompile-churn (runs once, at import)
            setattr(klass, name, staticmethod(jax.jit(getattr(klass, name))))
        setattr(
            klass,
            "sum",
            # fhh-lint: disable=recompile-churn (runs once, at import)
            staticmethod(jax.jit(getattr(klass, "sum"), static_argnames=("axis",))),
        )


_jit_field_methods()
