"""ibDCF — interval-bound Distributed Comparison Functions as tensor batches.

The reference implements one key at a time with per-bit Rust loops
(ref: src/ibDCF.rs:84-164 keygen, 208-236 eval).  Here a *batch* of keys is a
pytree of arrays with arbitrary leading batch dims (clients × dims × sides…):
keygen is one ``lax.scan`` over the ``data_len`` levels with every key in the
batch advancing together, and the per-level incremental eval
(ref: ibDCF.rs:208-227) is one fused batched expression — the per-key loops of
the reference become single device programs.

Key material layout (SURVEY.md §7 data layout):

- ``root_seed``  uint32[..., 4]          (128-bit seed per key)
- ``cw_seed``    uint32[..., L, 4]       (per-level correction seeds)
- ``cw_bits``    bool[..., L, 2]         (t-bit corrections, left/right)
- ``cw_y_bits``  bool[..., L, 2]         (y-bit corrections, left/right)
- ``key_idx``    bool[...]               (which party: False=0, True=1)

Semantics (pinned by tests/oracle.py and its full-domain sweeps): with keys on
bound ``b``, XOR of the two parties' share bits (``y_bit ^ bit``) after
evaluating MSB-first input ``x`` is ``[x < b]`` for a side=True ("left") key
and ``[x > b]`` for side=False ("right"); share-string equality over
(dim × {left,right}) therefore encodes inclusive L∞-ball membership.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import prg
from ..utils import bits as bitutils


class IbDcfKeyBatch(NamedTuple):
    """A batch of ibDCF keys for ONE party (ref: ibDCF.rs:17-21)."""

    key_idx: jax.Array  # bool[...]
    root_seed: jax.Array  # uint32[..., 4]
    cw_seed: jax.Array  # uint32[..., L, 4]
    cw_bits: jax.Array  # bool[..., L, 2]
    cw_y_bits: jax.Array  # bool[..., L, 2]

    @property
    def data_len(self) -> int:
        return self.cw_seed.shape[-2]

    @property
    def batch_shape(self):
        return self.cw_seed.shape[:-2]


class EvalState(NamedTuple):
    """Per-key incremental evaluation state (ref: ibDCF.rs:25-30).

    The level index lives with the caller (the whole batch is always at the
    same level, so it is a host-side scalar, not a tensor).
    """

    seed: jax.Array  # uint32[..., 4]
    bit: jax.Array  # bool[...]
    y_bit: jax.Array  # bool[...]


def _bxor(a, b):
    return jnp.logical_xor(a, b)


def gen_pair(
    init_seeds: jax.Array, alpha_bits: jax.Array, side: jax.Array
) -> tuple[IbDcfKeyBatch, IbDcfKeyBatch]:
    """Generate both parties' key batches in one scan over levels.

    init_seeds: uint32[..., 2, 4] fresh random root seeds (party axis of 2);
    alpha_bits: bool[..., L] MSB-first bound per key;
    side:       bool[...] True = "left"/less-than key (ref: ibDCF.rs:138-164).

    Returns (party0 batch, party1 batch) sharing identical correction words
    (ref: ibDCF.rs:84-119 ``gen_cor_word`` — the per-level recurrence).
    """
    # PRG bit mode resolved eagerly so it participates in the jit cache key
    # (a trace must never bake in a stale prg.DERIVED_BITS).
    return _gen_pair_jit(init_seeds, alpha_bits, side, prg.DERIVED_BITS)


@partial(jax.jit, static_argnames=("derived_bits",))
def _gen_pair_jit(init_seeds, alpha_bits, side, derived_bits):
    init_seeds = jnp.asarray(init_seeds, jnp.uint32)
    alpha_bits = jnp.asarray(alpha_bits, bool)
    side = jnp.broadcast_to(jnp.asarray(side, bool), alpha_bits.shape[:-1])
    batch = alpha_bits.shape[:-1]
    assert init_seeds.shape == batch + (2, 4), (init_seeds.shape, batch)

    def step(carry, alpha_bit):
        seeds, tbits = carry  # uint32[..., 2, 4], bool[..., 2]
        s_l, s_r, d_bits, d_y = prg.expand(seeds, derived_bits)  # [..., 2, 4]
        keep = alpha_bit  # bool[...]
        k = keep[..., None]
        # lose-direction child seeds XOR across parties (ibDCF.rs:95-97)
        cw_seed = jnp.where(
            k, s_l[..., 0, :] ^ s_l[..., 1, :], s_r[..., 0, :] ^ s_r[..., 1, :]
        )
        cw_bits = jnp.stack(
            [
                _bxor(_bxor(d_bits[..., 0, 0], d_bits[..., 1, 0]), ~keep),
                _bxor(_bxor(d_bits[..., 0, 1], d_bits[..., 1, 1]), keep),
            ],
            axis=-1,
        )  # (ibDCF.rs:99-101: t_l ^= !bit… here bit^1 on left, bit on right)
        cw_y_bits = jnp.stack(
            [
                _bxor(_bxor(d_y[..., 0, 0], d_y[..., 1, 0]), keep & ~side),
                _bxor(_bxor(d_y[..., 0, 1], d_y[..., 1, 1]), ~keep & side),
            ],
            axis=-1,
        )  # (ibDCF.rs:103-108: side-dependent payload bits)
        # each party keeps the alpha-direction child (ibDCF.rs:109-117)
        kept_seed = jnp.where(k[..., None, :], s_r, s_l)  # [..., 2, 4]
        kept_bit = jnp.where(k, d_bits[..., 1], d_bits[..., 0])  # [..., 2]
        t = tbits[..., None]  # correction applies iff party's t-bit set
        new_seeds = jnp.where(t, kept_seed ^ cw_seed[..., None, :], kept_seed)
        cw_keep_bit = jnp.where(keep, cw_bits[..., 1], cw_bits[..., 0])
        new_tbits = _bxor(kept_bit, tbits & cw_keep_bit[..., None])
        return (new_seeds, new_tbits), (cw_seed, cw_bits, cw_y_bits)

    init_tbits = jnp.broadcast_to(
        jnp.array([False, True]), batch + (2,)
    )  # party 0 starts t=0, party 1 t=1 (ibDCF.rs:143-146)
    alpha_first = jnp.moveaxis(alpha_bits, -1, 0)
    (_, _), (cw_seed, cw_bits, cw_y_bits) = jax.lax.scan(
        step, (init_seeds, init_tbits), alpha_first
    )
    # scan stacks the level axis first; move it to its [..., L, …] slot
    cw_seed = jnp.moveaxis(cw_seed, 0, -2)
    cw_bits = jnp.moveaxis(cw_bits, 0, -2)
    cw_y_bits = jnp.moveaxis(cw_y_bits, 0, -2)

    def mk(p: int) -> IbDcfKeyBatch:
        return IbDcfKeyBatch(
            key_idx=jnp.broadcast_to(jnp.asarray(bool(p)), batch),
            root_seed=init_seeds[..., p, :],
            cw_seed=cw_seed,
            cw_bits=cw_bits,
            cw_y_bits=cw_y_bits,
        )

    return mk(0), mk(1)


def gen_pair_np(
    init_seeds: np.ndarray,
    alpha_bits: np.ndarray,
    side: np.ndarray,
    derived_bits: bool | None = None,
) -> tuple[IbDcfKeyBatch, IbDcfKeyBatch]:
    """NumPy mirror of :func:`gen_pair` — bit-identical key batches.

    The level recurrence runs as a Python loop over ``L`` with every key in
    the batch advancing as vectorized numpy — no device, no compilation.
    Used by host-side client simulation and by CPU-mesh dryruns/tests, where
    compiling the keygen scan on XLA:CPU is pathologically slow
    (tests/conftest.py documents the measurement).
    """
    if derived_bits is None:
        derived_bits = prg.DERIVED_BITS
    init_seeds = np.asarray(init_seeds, np.uint32)
    alpha = np.asarray(alpha_bits, bool)
    batch = alpha.shape[:-1]
    side = np.broadcast_to(np.asarray(side, bool), batch)
    L = alpha.shape[-1]
    assert init_seeds.shape == batch + (2, 4), (init_seeds.shape, batch)

    seeds = init_seeds.copy()  # [..., 2, 4]
    tbits = np.broadcast_to(np.array([False, True]), batch + (2,)).copy()
    cw_seed = np.empty(batch + (L, 4), np.uint32)
    cw_bits = np.empty(batch + (L, 2), bool)
    cw_y = np.empty(batch + (L, 2), bool)
    for lvl in range(L):
        s_l, s_r, d_bits, d_y = prg.np_expand(seeds, derived_bits)
        keep = alpha[..., lvl]  # bool[...]
        k1 = keep[..., None]
        cw_seed[..., lvl, :] = np.where(
            k1, s_l[..., 0, :] ^ s_l[..., 1, :], s_r[..., 0, :] ^ s_r[..., 1, :]
        )
        cw_bits[..., lvl, 0] = d_bits[..., 0, 0] ^ d_bits[..., 1, 0] ^ ~keep
        cw_bits[..., lvl, 1] = d_bits[..., 0, 1] ^ d_bits[..., 1, 1] ^ keep
        cw_y[..., lvl, 0] = d_y[..., 0, 0] ^ d_y[..., 1, 0] ^ (keep & ~side)
        cw_y[..., lvl, 1] = d_y[..., 0, 1] ^ d_y[..., 1, 1] ^ (~keep & side)
        kept_seed = np.where(keep[..., None, None], s_r, s_l)  # [..., 2, 4]
        kept_bit = np.where(k1, d_bits[..., 1], d_bits[..., 0])  # [..., 2]
        seeds = np.where(
            tbits[..., None], kept_seed ^ cw_seed[..., lvl, None, :], kept_seed
        )
        cw_keep_bit = np.where(keep, cw_bits[..., lvl, 1], cw_bits[..., lvl, 0])
        tbits = kept_bit ^ (tbits & cw_keep_bit[..., None])

    def mk(p: int) -> IbDcfKeyBatch:
        return IbDcfKeyBatch(
            key_idx=np.broadcast_to(np.bool_(bool(p)), batch),
            root_seed=init_seeds[..., p, :],
            cw_seed=cw_seed,
            cw_bits=cw_bits,
            cw_y_bits=cw_y,
        )

    return mk(0), mk(1)


@jax.jit
def eval_init(key: IbDcfKeyBatch) -> EvalState:
    """Root state: seed = root seed, t = y = key_idx (ref: ibDCF.rs:229-236)."""
    return EvalState(
        seed=key.root_seed,
        bit=jnp.asarray(key.key_idx, bool),
        y_bit=jnp.asarray(key.key_idx, bool),
    )


def level_cw(key: IbDcfKeyBatch, level):
    """Correction word(s) at one level: (seed[...,4], bits[...,2], y[...,2]).

    ``level`` may be a traced scalar (for use under scan/while); concrete
    levels are bounds-checked here because JAX's dynamic gather would
    silently clamp an out-of-range index to the last level."""
    if isinstance(level, (int, np.integer)) and not 0 <= level < key.data_len:
        raise IndexError(f"level {level} out of range for data_len {key.data_len}")
    take = lambda a: jax.lax.dynamic_index_in_dim(a, level, axis=a.ndim - 2, keepdims=False)
    return take(key.cw_seed), take(key.cw_bits), take(key.cw_y_bits)


def eval_bit(cw, state: EvalState, direction: jax.Array) -> EvalState:
    """Advance every key in the batch one level (ref: ibDCF.rs:208-227).

    ``cw`` is the output of :func:`level_cw` for the current level;
    ``direction``: bool[...] — the input bit taken at this level (True=right).
    One PRG expansion + masked XORs; no branches, fully batched.
    """
    return _eval_bit_jit(cw, state, direction, prg.DERIVED_BITS)


@partial(jax.jit, static_argnames=("derived_bits",))
def _eval_bit_jit(cw, state: EvalState, direction, derived_bits) -> EvalState:
    cw_seed, cw_bits, cw_y = cw
    direction = jnp.asarray(direction, bool)
    s_l, s_r, tau_bits, tau_y = prg.expand(state.seed, derived_bits)
    d = direction[..., None]
    seed = jnp.where(d, s_r, s_l)
    new_bit = jnp.where(direction, tau_bits[..., 1], tau_bits[..., 0])
    new_y = jnp.where(direction, tau_y[..., 1], tau_y[..., 0])
    cw_bit_d = jnp.where(direction, cw_bits[..., 1], cw_bits[..., 0])
    cw_y_d = jnp.where(direction, cw_y[..., 1], cw_y[..., 0])
    t = state.bit
    seed = jnp.where(t[..., None], seed ^ cw_seed, seed)
    new_bit = _bxor(new_bit, t & cw_bit_d)
    new_y = _bxor(new_y, t & cw_y_d)
    new_y = _bxor(new_y, state.y_bit)  # y accumulates along the path
    return EvalState(seed=seed, bit=new_bit, y_bit=new_y)


def eval_full(key: IbDcfKeyBatch, idx_bits: jax.Array) -> EvalState:
    """Evaluate the whole MSB-first input in one scan (ref: ibDCF.rs:229-255
    ``eval`` / the per-level loop of eval_str at ibDCF.rs:120-131)."""
    return _eval_full_jit(key, idx_bits, prg.DERIVED_BITS)


@partial(jax.jit, static_argnames=("derived_bits",))
def _eval_full_jit(key: IbDcfKeyBatch, idx_bits, derived_bits) -> EvalState:
    idx_bits = jnp.asarray(idx_bits, bool)
    assert idx_bits.shape[-1] == key.data_len

    def step(state, inp):
        direction, cw_seed, cw_bits, cw_y = inp
        new = _eval_bit_jit((cw_seed, cw_bits, cw_y), state, direction, derived_bits)
        return new, None

    # level axis first so scan hands each step its own level's CWs directly
    xs = (
        jnp.moveaxis(idx_bits, -1, 0),
        jnp.moveaxis(key.cw_seed, -2, 0),
        jnp.moveaxis(key.cw_bits, -2, 0),
        jnp.moveaxis(key.cw_y_bits, -2, 0),
    )
    state, _ = jax.lax.scan(step, eval_init(key), xs)
    return state


def share_bit(state: EvalState) -> jax.Array:
    """Per-party FSS output share bit (ref: ibDCF.rs:249, collect.rs:399-404)."""
    return _bxor(state.y_bit, state.bit)


# ---------------------------------------------------------------------------
# Interval / L∞-ball key generation (client-side, host-facing API)
# ---------------------------------------------------------------------------


def _rng_seeds(rng: np.random.Generator, shape) -> np.ndarray:
    return rng.integers(0, 1 << 32, size=tuple(shape) + (2, 4), dtype=np.uint32)


def best_engine() -> str:
    """Fastest keygen engine for the current default backend: the fused
    Pallas kernel (ops/keygen_pallas.py) on an accelerator, the numpy
    mirror on host CPU (where the XLA:CPU scan compile dominates).  The
    deployment binaries (bin/leader.py, bin/mesh.py) select through this
    so the headline keygen throughput never ships on the slow scan engine."""
    from ..utils import effective_platform

    return "np" if effective_platform() == "cpu" else "pallas"


def _gen(engine: str):
    """Select the keygen implementation: "jax" (device scan), "np" (host),
    or "pallas" (the fused single-kernel TPU engine, ops/keygen_pallas.py —
    ~5x the scan engine's throughput on the chip)."""
    if engine == "jax":
        return gen_pair
    if engine == "np":
        return gen_pair_np
    if engine == "pallas":
        from .keygen_pallas import gen_pair_pallas

        return gen_pair_pallas
    raise ValueError(f"unknown keygen engine {engine!r}")


# A device keygen call's working set over the bytes of the batch it
# returns: the fused kernel writes every correction bit as a 32-bit word
# in its client-minor tile layout, and the relayout back to [N, L, k]
# holds both forms at once — 2.65x by the TPU compiler's memory analysis
# at 2^18 and 2^19 keys x 512 levels; 3 leaves room for what else the
# device holds.
_GEN_WORKING_SET = 3


def _spread_devices(n_clients: int, batch_bytes: int) -> list:
    """The local devices one keygen call's client axis is spread over:
    the first alone — one call on the default device, as ever — unless
    the call's working set would not fit what that device has free; then
    as many of the local devices as tile the client axis (the work is
    independent per client: more devices are more room and less time).
    Decided from the batch's bytes and the device's ``memory_stats``,
    never by a caller; XLA:CPU keeps no such stats (host memory), so a
    CPU host never spreads."""
    local = jax.local_devices()
    stats = local[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return local[:1]
    free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
    if _GEN_WORKING_SET * batch_bytes <= free:
        return local[:1]
    return local[: max(k for k in range(1, len(local) + 1) if n_clients % k == 0)]


@lru_cache(maxsize=None)
def _spread_gen(engine: str, devices: tuple):
    """``_gen(engine)`` as ONE program over ``devices``, client axis
    sharded: keygen is independent per client, so each device runs the
    one-device generator on its own span (``shard_map``, as the sharded
    expand does) and nothing crosses between them.  The parties' batches
    share every correction word, so the program returns those once."""
    gen = _gen(engine)
    spec = jax.sharding.PartitionSpec("clients")

    def body(init_seeds, alpha, side):
        k0, k1 = gen(init_seeds, alpha, side)
        return k0, k1.key_idx, k1.root_seed

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per engine and device set)
    return jax.jit(
        jax.shard_map(
            body, mesh=jax.sharding.Mesh(np.asarray(devices), ("clients",)),
            in_specs=spec, out_specs=spec,
            # pallas_call has no shard_map replication rule
            check_vma=False,
        )
    )


def _gen_batch(engine: str, init_seeds, alpha, side):
    """One client batch's keys, leading axis = clients: ``_gen(engine)``
    in one call, or — a batch one device cannot hold — the same
    generator once per device over equal spans of the client axis
    (:func:`_spread_gen`), every leaf ONE array with its client axis
    sharded over those devices.  The root seeds were drawn on the host,
    so the keys are bit-identical to the one-call batch;
    ``block_until_ready`` / ``device_get`` take the result as they take
    a one-device batch."""
    # per key and level: a 16-byte seed word and 2 + 2 one-byte bits
    devices = _spread_devices(alpha.shape[0], int(np.prod(alpha.shape)) * 20)
    if len(devices) == 1 or engine == "np":  # the host engine has no device
        return _gen(engine)(init_seeds, alpha, side)
    k0, idx1, root1 = _spread_gen(engine, tuple(devices))(init_seeds, alpha, side)
    return k0, k0._replace(key_idx=idx1, root_seed=root1)


def gen_interval(
    left_bits, right_bits, rng: np.random.Generator, engine: str = "jax"
) -> tuple[tuple[IbDcfKeyBatch, IbDcfKeyBatch], tuple[IbDcfKeyBatch, IbDcfKeyBatch]]:
    """Interval keys: (left-DCF side=True on the left bound, right-DCF
    side=False on the right bound), batched (ref: ibDCF.rs:166-173).

    left_bits/right_bits: bool[..., L].  Returns per-party
    ``((left0, right0), (left1, right1))`` key batches.
    """
    left_bits = np.asarray(left_bits, bool)
    right_bits = np.asarray(right_bits, bool)
    g = _gen(engine)
    l0, l1 = g(_rng_seeds(rng, left_bits.shape[:-1]), left_bits, True)
    r0, r1 = g(_rng_seeds(rng, right_bits.shape[:-1]), right_bits, False)
    return (l0, r0), (l1, r1)


def ball_bounds(points_bits, ball_size: int) -> tuple[np.ndarray, np.ndarray]:
    """Saturating ``point ∓ ball_size`` per dimension on MSB-first bitstrings.

    points_bits: bool[..., L].  Vectorized ripple carry/borrow over the L bit
    positions (host-side numpy; L ≤ 1024 so the Python loop is over bits, not
    clients).  Saturation at the domain edges replaces the reference's
    grow-on-carry / wraparound (ref: src/lib.rs:131-183) — see
    utils/bits.py for the rationale.
    """
    points = np.asarray(points_bits, bool)
    L = points.shape[-1]
    delta = bitutils.int_to_bits(L, min(ball_size, (1 << L) - 1))
    lo = np.empty_like(points)
    hi = np.empty_like(points)
    borrow = np.zeros(points.shape[:-1], bool)
    carry = np.zeros(points.shape[:-1], bool)
    for i in reversed(range(L)):  # LSB-first ripple
        p = points[..., i]
        d = bool(delta[i])
        diff = p ^ d ^ borrow
        borrow = (~p & (d | borrow)) | (d & borrow)
        lo[..., i] = diff
        s = p ^ d ^ carry
        carry = (p & d) | (carry & (p | d))
        hi[..., i] = s
    lo[borrow] = False  # saturate: point - size < 0  -> 0
    hi[carry] = True  # saturate: point + size >= 2^L -> 2^L - 1
    return lo, hi


def gen_l_inf_ball(
    points_bits, ball_size: int, rng: np.random.Generator, engine: str = "jax"
) -> tuple[IbDcfKeyBatch, IbDcfKeyBatch]:
    """L∞-ball keys around MSB-first points (ref: ibDCF.rs:175-188).

    points_bits: bool[N, n_dims, L].  Returns the two parties' key batches of
    shape [N, n_dims, 2] where the trailing axis is (left-DCF, right-DCF) —
    a client's full submission for one server, as one pytree.  A batch
    whose working set one device cannot hold is spread over the local
    devices (:func:`_gen_batch`): same keys, client axis sharded.
    """
    points = np.asarray(points_bits, bool)
    lo, hi = ball_bounds(points, ball_size)
    # stack (left bound w/ side=True, right bound w/ side=False) on axis -2
    alpha = np.stack([lo, hi], axis=-2)  # [N, n_dims, 2, L]
    side = np.broadcast_to(
        np.array([True, False]), alpha.shape[:-1]
    )  # left-DCF then right-DCF
    return _gen_batch(engine, _rng_seeds(rng, alpha.shape[:-1]), alpha, side)


def gen_l_inf_ball_from_coords(
    coords: np.ndarray, ball_size: int, rng: np.random.Generator, engine: str = "jax"
) -> tuple[IbDcfKeyBatch, IbDcfKeyBatch]:
    """i16 coordinate variant with clamping (ref: ibDCF.rs:189-205).

    coords: int array [N, n_dims] of i16 centidegree values; bounds are
    ``coord ∓ ball_size`` clamped to the i16 range, then encoded as 16-bit
    MSB-first **offset-binary** bitstrings (sign bit flipped — see
    utils/bits.py ``i16_to_ob_bits``).  Deliberate divergence from the
    reference, which feeds raw two's-complement bits
    (sample_driving_data.rs:25-29) into the lexicographic comparator; there,
    any interval crossing zero is unsatisfiable (negatives sort above
    positives as unsigned strings) — latent upstream because the RideAustin
    coordinates never cross zero.  Offset-binary makes unsigned string order
    equal signed order, so zero-crossing balls work; tree paths decode back
    via ``ob_bits_to_i16``.
    """
    coords = np.asarray(coords, np.int64)
    lo = np.clip(coords - ball_size, -(1 << 15), (1 << 15) - 1)
    hi = np.clip(coords + ball_size, -(1 << 15), (1 << 15) - 1)
    to_bits = lambda v: (
        (((v[..., None] & 0xFFFF) ^ 0x8000).astype(np.uint32)
         >> np.arange(15, -1, -1)) & 1
    ).astype(bool)
    alpha = np.stack([to_bits(lo), to_bits(hi)], axis=-2)  # [N, d, 2, 16]
    side = np.broadcast_to(np.array([True, False]), alpha.shape[:-1])
    return _gen_batch(engine, _rng_seeds(rng, alpha.shape[:-1]), alpha, side)
