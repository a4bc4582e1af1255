"""Row-sharded, device-resident sketch verification under ``shard_map``.

The malicious-secure sketch verify (protocol/rpc.py ``sketch_verify``)
used to run as a host loop: ``sketch_batch_size`` client chunks, each
chunk a fresh device dispatch + TWO wire round trips (cor exchange, out
exchange) — the exact shape every perf PR since the planar wire removed
from the semi-honest lane.  This module brings the sketch checks into
the same fast lane as the GC/OT kernel stage (parallel/kernel_shard.py):

- the per-level check batch — (client, dim) rows of the three MAC/square
  checks (protocol/mpc.py) — partitions along the CLIENT axis across the
  server's local ``data`` mesh; every per-row computation (sketch inner
  products, Beaver cor/out shares, the verdict) is client-parallel, so
  there is no cross-shard reduction at all;
- the challenge ratchet stream is absorbed PER SHARD deterministically:
  shard i derives exactly its (client·dim)-row slice of the single-device
  challenge stream by CTR seek (``sketch.challenge_rands`` — the same
  seek-by-offset discipline as ``otext.sender_extend_rows``), and the
  per-node r vector from the replicated seed, so shard outputs are the
  exact row slices of the single-device state;
- cor and out openings read back PER SHARD (``copy_to_host_async``
  double-buffering) and reassemble POSITIONALLY into a byte-identical
  wire — the peer cannot tell a sharded verifier from an unsharded one;
- the whole level is ONE fused program per stage per ``f_bucket`` rung
  (the stored pair shares are bucket-padded, so program identity is
  (bucket, batch, field) — ``level`` and the stream offsets enter as
  traced scalars and never recompile), with a single post-level readback
  of the verdict vector.

Shard binding: the active shard count is the largest divisor of the
client batch that fits the budget (``Config.sketch_shards``, auto = the
mesh's data shards) — a non-dividing batch DEGRADES to fewer shards, and
k = 1 (or a meshless server) runs the same fused math as ONE plain jit
program on the default device (:func:`bind` returns None).  Bit-identity
of the challenge stream, both wire messages, and the verdict vector at
every k is asserted in tier-1 (tests/test_sketch_shard.py) and gates the
``bench_sketch`` legs.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.fields import F255, FE62
from .kernel_shard import assemble, start_host_copies
from .server_mesh import DATA, _largest_divisor_leq, _mesh_for

_FIELDS = {"FE62": FE62, "F255": F255}


def sketch_shards(n_clients: int, budget: int) -> int:
    """Active shard count for an ``n_clients`` verify under a device
    budget: the largest divisor of the batch <= the budget (1 = the
    single-program path)."""
    return _largest_divisor_leq(n_clients, max(1, int(budget)))


@dataclass(frozen=True)
class SketchShard:
    """One level batch's sketch-verify binding: ``k`` mesh devices over
    an ``N``-client, ``d``-dim check batch."""

    devices: tuple
    N: int
    d: int

    @property
    def k(self) -> int:
        return len(self.devices)

    @property
    def mesh(self):
        return _mesh_for(self.devices)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def bind(devices: tuple, N: int, d: int, budget: int) -> SketchShard | None:
    """Bind the sketch verify to the leading mesh devices; ``None`` when
    only one shard fits (the caller runs the single fused program)."""
    k = sketch_shards(N, min(int(budget), len(devices)))
    if k < 2:
        return None
    return SketchShard(devices=tuple(devices[:k]), N=N, d=d)


def _state_specs():
    from ..protocol import mpc

    return mpc.MulStateBatch(
        xs=P(DATA), ys=P(DATA), zs=P(DATA), rs=P(DATA),
        triples=mpc.TripleBatch(a=P(DATA), b=P(DATA), c=P(DATA)),
    )


# ---------------------------------------------------------------------------
# Fused program factories (one compiled program per shape, shared
# process-wide — warm and live hit the same executables)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _cor_state_fn(devices: tuple, field_name: str, m: int, N: int, d: int):
    """Sharded stage 1: (pairs, triple slab, MAC shares, seed, level) ->
    (cor-share wire stack uint/field[2, N, d, CHECKS(, limbs)] sharded
    along clients, the per-shard check state — kept on device for stage
    2).  Each shard derives its own slice of the challenge stream."""
    from ..protocol import mpc, sketch as sketchmod

    field = _FIELDS[field_name]
    k = len(devices)
    n_loc = N // k

    def body(pairs_loc, ta, tb, tc, mk, mk2, seed, level):
        row0 = jax.lax.axis_index(DATA) * (n_loc * d)
        st = sketchmod.level_check_state(
            field, pairs_loc, mpc.TripleBatch(a=ta, b=tb, c=tc), mk, mk2,
            seed, level, row0,
        )
        return jnp.stack(mpc.cor_share(field, st)), st

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (devices, shape, field))
    return jax.jit(
        jax.shard_map(
            body, mesh=_mesh_for(devices),
            in_specs=(
                P(None, DATA), P(DATA), P(DATA), P(DATA), P(DATA), P(DATA),
                P(), P(),
            ),
            out_specs=(P(None, DATA), _state_specs()),
        )
    )


@lru_cache(maxsize=None)
def _cor_state_single_fn(field_name: str, m: int, N: int, d: int):
    """The k = 1 twin of :func:`_cor_state_fn`: the same fused math as
    one plain jit program on the default device (no mesh, no placement
    constraints — the meshless server's path and the bit-identity
    reference the sharded form is gated against)."""
    from ..protocol import mpc, sketch as sketchmod

    field = _FIELDS[field_name]

    def f(pairs, ta, tb, tc, mk, mk2, seed, level):
        st = sketchmod.level_check_state(
            field, pairs, mpc.TripleBatch(a=ta, b=tb, c=tc), mk, mk2,
            seed, level, 0,
        )
        return jnp.stack(mpc.cor_share(field, st)), st

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (shape, field))
    return jax.jit(f)


@lru_cache(maxsize=None)
def _out_fn(devices: tuple | None, field_name: str, N: int, d: int,
            server_idx: bool):
    """Stage 2: (state, own cor stack, peer cor stack) -> this party's
    out shares field[N, d(, limbs)].  The cor opening (add both stacks)
    fuses into the same program, so the peer's wire upload is the only
    host->device move of the stage."""
    from ..protocol import mpc

    field = _FIELDS[field_name]

    def body(xs, ys, zs, rs, ta, tb, tc, cor_mine, cor_peer):
        st = mpc.MulStateBatch(
            xs=xs, ys=ys, zs=zs, rs=rs,
            triples=mpc.TripleBatch(a=ta, b=tb, c=tc),
        )
        opened = (
            field.add(cor_mine[0], cor_peer[0]),
            field.add(cor_mine[1], cor_peer[1]),
        )
        return mpc.out_share(field, server_idx, st, opened)

    if devices is None:
        # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (shape, field, role))
        return jax.jit(body)
    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (devices, shape, field, role))
    return jax.jit(
        jax.shard_map(
            body, mesh=_mesh_for(devices),
            in_specs=(
                P(DATA), P(DATA), P(DATA), P(DATA), P(DATA), P(DATA),
                P(DATA), P(None, DATA), P(None, DATA),
            ),
            out_specs=P(DATA),
        )
    )


@lru_cache(maxsize=None)
def _verdict_fn(devices: tuple | None, field_name: str, N: int, d: int):
    """Stage 3: (own out shares, peer out shares) -> per-client verdict
    bool[N] (a client passes iff EVERY dim's checks sum to zero)."""
    from ..protocol import mpc

    field = _FIELDS[field_name]

    def body(o_mine, o_peer):
        return jnp.all(mpc.verify(field, o_mine, o_peer), axis=1)

    if devices is None:
        # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (shape, field))
        return jax.jit(body)
    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (devices, shape, field))
    return jax.jit(
        jax.shard_map(
            body, mesh=_mesh_for(devices),
            in_specs=(P(DATA), P(DATA)),
            out_specs=P(DATA),
        )
    )


# ---------------------------------------------------------------------------
# Protocol-step drivers (what protocol/rpc.py and warmup call)
# ---------------------------------------------------------------------------


def _put(ss: SketchShard | None, a, spec: P):
    """Canonical placement: sharded inputs land on their NamedSharding
    eagerly (the executable cache keys on input shardings — warm and
    live must hit ONE program per shape); the single-program path takes
    inputs as-is on the default device."""
    a = jnp.asarray(a)
    if ss is None:
        return a
    return jax.device_put(a, ss.sharding(spec))


def cor_state(ss: SketchShard | None, field, pairs, trip, mk, mk2, seed,
              level: int):
    """Stage 1 dispatch: returns (cor wire stack on device, the check
    state — device-resident, fed to :func:`out_shares`)."""
    m = int(pairs.shape[0])
    N, d = int(pairs.shape[1]), int(pairs.shape[2])
    args = (
        _put(ss, pairs, P(None, DATA)),
        _put(ss, trip.a, P(DATA)), _put(ss, trip.b, P(DATA)),
        _put(ss, trip.c, P(DATA)),
        _put(ss, mk, P(DATA)), _put(ss, mk2, P(DATA)),
        _put(ss, np.asarray(seed, np.uint32), P()),
        _put(ss, np.uint32(level), P()),
    )
    if ss is None:
        return _cor_state_single_fn(field.__name__, m, N, d)(*args)
    return _cor_state_fn(ss.devices, field.__name__, m, N, d)(*args)


def out_shares(ss: SketchShard | None, field, state, cor_mine, cor_peer_np,
               server_idx: bool):
    """Stage 2 dispatch: the peer's cor wire uploads row-sharded (host
    slices land directly per device) and opens against the carried
    state."""
    N, d = int(state.xs.shape[0]), int(state.xs.shape[1])
    fn = _out_fn(
        None if ss is None else ss.devices, field.__name__, N, d,
        bool(server_idx),
    )
    return fn(
        state.xs, state.ys, state.zs, state.rs,
        state.triples.a, state.triples.b, state.triples.c,
        cor_mine, _put(ss, np.asarray(cor_peer_np), P(None, DATA)),
    )


def verdicts(ss: SketchShard | None, field, o_mine, o_peer_np):
    """Stage 3 dispatch: device verdict vector bool[N] — the level's
    SINGLE post-level readback happens at the caller."""
    N, d = int(o_mine.shape[0]), int(o_mine.shape[1])
    fn = _verdict_fn(
        None if ss is None else ss.devices, field.__name__, N, d
    )
    return fn(o_mine, _put(ss, np.asarray(o_peer_np), P(DATA)))


def wire(arr) -> np.ndarray:
    """One wire message: per-shard device->host DMAs kicked off without
    blocking, then reassembled POSITIONALLY into the full frame — the
    sharded twin of one ``np.asarray``, byte-identical output (and
    exactly that for a single-device array: one shard, one copy)."""
    start_host_copies(arr)
    return assemble(arr)


# ---------------------------------------------------------------------------
# Test/bench surface: the trusted challenge stream per shard
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _stream_parts_fn(devices: tuple | None, field_name: str, m: int,
                     N: int, d: int):
    from ..protocol import sketch as sketchmod

    field = _FIELDS[field_name]

    def body(seed, level):
        if devices is None:
            row0 = 0
            n_loc = N
        else:
            n_loc = N // len(devices)
            row0 = jax.lax.axis_index(DATA) * (n_loc * d)
        r = sketchmod.challenge_r(field, seed, level, m)
        rands = sketchmod.challenge_rands(
            field, seed, level, m, row0, n_loc * d
        )
        return r, rands

    if devices is None:
        # fhh-lint: disable=recompile-churn (lru_cached factory: test/bench surface)
        return jax.jit(body)
    # fhh-lint: disable=recompile-churn (lru_cached factory: test/bench surface)
    return jax.jit(
        jax.shard_map(
            body, mesh=_mesh_for(devices), in_specs=(P(), P()),
            out_specs=(P(), P(DATA)),
        )
    )


def stream_parts(ss: SketchShard | None, field, seed, level: int, m: int,
                 N: int, d: int):
    """(r, rands) of one level's challenge stream, assembled across
    shards — the bit-identity surface tests and the bench gate compare
    against the single-device ``shared_r_stream`` draw."""
    fn = _stream_parts_fn(
        None if ss is None else ss.devices, field.__name__, m, N, d
    )
    r, rands = fn(
        _put(ss, np.asarray(seed, np.uint32), P()),
        _put(ss, np.uint32(level), P()),
    )
    return np.asarray(r), np.asarray(rands)


# ---------------------------------------------------------------------------
# Warmup: compile the fused verify chain without touching live state
# ---------------------------------------------------------------------------


def warm_verify(ss: SketchShard | None, field, m: int, N: int, d: int,
                server_idx: bool) -> None:
    """Run the whole fused cor -> out -> verdict chain on throwaway
    zero inputs at one (bucket ``m``, batch) rung, with both wire
    messages round-tripping through host numpy exactly like the live
    socket path (jit executables key on input placements — see
    ``secure.warm_level_kernels``), so a warmed malicious crawl
    dispatches ZERO fresh compiles at this shape."""
    from ..protocol import mpc

    pairs = field.zeros((m, N, d, 2))
    trip = mpc.TripleBatch(
        a=field.zeros((N, d, mpc.CHECKS)),
        b=field.zeros((N, d, mpc.CHECKS)),
        c=field.zeros((N, d, mpc.CHECKS)),
    )
    mk = field.zeros((N,))
    mk2 = field.zeros((N,))
    seed = np.zeros(4, np.uint32)
    cor, st = cor_state(ss, field, pairs, trip, mk, mk2, seed, 0)
    cor_np = wire(cor)
    o = out_shares(ss, field, st, cor, cor_np, server_idx)
    o_np = wire(o)
    ok = verdicts(ss, field, o, o_np)
    np.asarray(ok)  # the post-level verdict readback path
