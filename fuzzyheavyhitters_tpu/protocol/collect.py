"""Aggregation engine: the prefix-tree frontier crawl as device kernels.

TPU-first redesign of the reference's ``KeyCollection`` state machine
(ref: src/collect.rs:28-507).  The reference walks Rust object trees —
``TreeNode{path, key_states}`` per node, rayon loops over clients, and it
re-evaluates every client's FSS state once per child pattern
(``make_tree_node`` per search string, collect.rs:378-391), costing
``2^d × d×2`` PRG calls per (node, client).  Here:

- the frontier is a **padded tensor** ``[F, N, d, 2]`` of eval states with an
  alive-node mask (SURVEY.md §7 hard part 4) — no objects, no ragged shapes;
- one batched PRG expansion per (node, client, dim, side) yields BOTH
  children at once, serving all ``2^d`` child patterns — a ``2^d``-fold
  saving over the reference's per-pattern re-evaluation;
- each (node, client)'s both-direction share bits pack into ONE uint32
  (bit position ``j*4 + side*2 + dir`` for dim j ≤ 8), so per-pattern ball
  membership is a single ``(p0 ^ p1) & pattern_mask == 0`` — and that uint32
  is also the only thing the two servers ever need to exchange per level;
- paths live with the leader (host), not on device: expansion and prune
  orders are deterministic (child c of node f sits at ``f * 2^d + c``,
  pattern bit j = ``(c >> j) & 1``, matching the reference's
  ``all_bit_vectors`` child order, lib.rs:125-129), so the leader
  reconstructs paths from its own keep masks.

Work plan (round 4 — closing the padded-frontier waste the reference
never has because it walks only live nodes, collect.rs:378-391):

- **Bucketed frontier**: the padded node axis ``F`` is the smallest power
  of two ≥ the survivor count (``bucket_for``), not a fixed ``f_max``.
  Static shapes are preserved — each bucket size is its own compiled
  program, at most ``log2(f_max)+1`` of them per crawl — while dead-slot
  waste is bounded at 2× instead of ``f_max / n_alive`` (the round-3
  regime ran 64 slots for ~4-8 live nodes: ~8-16× wasted PRG work).
- **Child-state cache**: ``expand_share_bits`` already runs the PRG on
  every (node, client, dim, side); it now also returns BOTH directions'
  child :class:`EvalState`, so the post-prune ``advance_from_children``
  is a pure gather+select — the second PRG pass of the old ``advance``
  (another ``F' × N × d × 2`` expansions per level) is gone entirely.
  Cost: the cache materializes ``F × N × d × 2 × 2`` child states
  (~72 B per (node, client, dim, side)) in HBM between crawl and prune —
  at bucketed ``F`` this is MBs, not GBs, and it replaces compute with
  bandwidth the TPU has to spare.  ``advance`` (re-expand) remains for
  callers without a cache.

Per level the PRG cost is now ``F_bucket × N × d × 2`` expansions — one
pass, sized to survivors — and the peak HBM footprint is the parent
frontier plus the child cache plus the packed-bit tensor, independent of
``2^d``.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import ibdcf, prg
from ..ops.ibdcf import EvalState, IbDcfKeyBatch

MAX_DIMS = 8  # packed-u32 layout holds d*4 bits (radix 1; see check_radix)


def radix_subtree_nodes(radix: int) -> int:
    """Nodes in the depth-``radix`` binary subtree below one (dim, side)
    frontier state: 2 + 4 + … + 2^radix = 2^(radix+1) - 2.  The packed
    share-bit word stores ALL of them per (dim, side), so a fused level
    can compare any intermediate depth along a child pattern's path."""
    return (1 << (radix + 1)) - 2


def max_dims_for_radix(radix: int) -> int:
    """Dim cap keeping 2·d·radix_subtree_nodes(radix) packed bits in one
    uint32: 8 dims at radix 1 (== MAX_DIMS), 2 at radix 2, 1 at radix 3."""
    return 32 // (2 * radix_subtree_nodes(radix))


def check_radix(d: int, radix: int) -> None:
    """Validate a crawl radix against the packed-u32 layout — loud, at
    config-use time, instead of a silent bit collision mid-crawl."""
    if radix not in (1, 2, 3):
        raise ValueError(
            f"crawl_radix_bits={radix}: supported radices are 1, 2, 3"
        )
    cap = max_dims_for_radix(radix)
    if d > cap:
        raise ValueError(
            f"crawl_radix_bits={radix} supports at most {cap} dim(s): the "
            f"packed share-bit word needs 2·d·(2^(radix+1)-2) bits per "
            f"(node, client) and must fit one uint32; got n_dims={d}"
        )

# NOTE (round 5): the per-level eval kernel `ops/eval_pallas.py` that once
# served the RE-EXPANDING fallback `advance` was retired: every crawl path
# advances via the gather-based `advance_from_children` (strictly better
# than any kernel — zero PRG work), leaving the kernel production-dead.
# The fallback runs the plain XLA eval step; the kernel lives in git
# history (rounds 3-4) if a re-expanding consumer ever returns.

# Engine for the level expansion itself (the crawl's dominant op): True
# (the default on real chips) routes it through the fused pack-in-kernel
# Pallas engine (ops/expand_pallas.py) with PLANE-MAJOR frontier state
# (seeds u32[4, d, 2, F, N], bits bool[d, 2, F, N]); False keeps the XLA
# ChaCha with interleaved [F, N, d, 2, 4] seeds (the only engine on CPU,
# and what the mesh bodies pin).  History, recorded honestly: the round-4
# word-planar kernel beat XLA on the body (~5 ms vs ~14 ms at B = 1M
# states) but lost it all to unfused pack/cache glue at the pallas_call
# boundary (~19 ms end to end); THIS engine moves the share-bit pack and
# the flag handling INTO the kernel (packed u32 emitted in-kernel, cw
# broadcast over nodes via a modular BlockSpec index map) — the round-4
# prototype the round-4 VERDICT asked to land.  NB the shared chip's
# throughput swings ~4x by hour; only back-to-back A/Bs are meaningful.
# The engine — and with it the frontier state LAYOUT — is read at tree_init /
# expand / advance time and must not flip mid-crawl.
EXPAND_PALLAS: bool = True


def _expand_engine() -> bool:
    """Pallas engine iff enabled AND the effective default device is an
    accelerator — a ``jax.default_device(cpu)`` context (the test suite's
    way of pinning compile-bound tests to the host) must fall back to the
    XLA engine: Pallas has no CPU compile path."""
    from ..utils import effective_platform

    return EXPAND_PALLAS and effective_platform() != "cpu"


class Frontier(NamedTuple):
    """Per-server frontier state for ``F`` (bucket-padded) tree nodes.

    states: EvalState over ``[F, N, d, 2]`` (node, client, dim, left/right);
    alive:  bool[F] node-liveness mask (dead slots are padding).

    ``F`` is the current *bucket* — the smallest power of two holding the
    live nodes (see :func:`bucket_for`), not a global maximum.

    State LAYOUT depends on the expansion engine: the XLA engine keeps
    ``seed`` interleaved ``[F, N, d, 2, 4]`` with bits ``[F, N, d, 2]``;
    the Pallas engine keeps everything PLANE-MAJOR — seed
    ``[4, d, 2, F, N]``, bits ``[d, 2, F, N]`` — so one kernel block sees
    all ``d*2`` planes of a (node, client) row and packs the share bits
    in-kernel (ops/expand_pallas.py).
    """

    states: EvalState
    alive: jax.Array

    @property
    def f_bucket(self) -> int:
        return self.alive.shape[0]  # layout-independent (alive is always [F])


class PlanarChildren(NamedTuple):
    """Plane-major child-state cache from the Pallas engine.

    seed:  u32[2, 4, d, 2, F, N] — direction-major, t-corrected child seeds;
    flags: u32[d, 2, F, N] — packed child bits per direction
           (b_l | b_r<<1 | y_l<<2 | y_r<<3, y accumulated along the path).
    """

    seed: jax.Array
    flags: jax.Array


def bucket_for(n_alive: int, f_max: int, min_bucket: int = 1) -> int:
    """Smallest power of two ≥ ``n_alive`` (≥ ``min_bucket``), capped by
    ``f_max``.

    Sizing frontier tensors to the bucket keeps shapes static per bucket
    (a handful of compiles) while bounding dead-slot waste at 2× — the
    tensor analogue of the reference expanding only live nodes
    (collect.rs:378-391).  ``min_bucket`` lets compile-bound callers (the
    1-core-CPU test host, where every bucket is an XLA compile) pin a
    single shape; performance callers leave it at 1."""
    if n_alive > f_max:
        raise ValueError(
            f"{n_alive} surviving nodes exceed f_max={f_max}; "
            "raise f_max or the threshold"
        )
    b = 1 << max(0, int(np.ceil(np.log2(max(1, n_alive)))))
    return min(f_max, max(b, min_bucket))


def tree_init(
    keys: IbDcfKeyBatch, f_bucket: int = 1, planar: bool | None = None
) -> Frontier:
    """Root frontier: one alive node whose states are eval_init of every
    (client, dim, side) key (ref: collect.rs:67-92).  The root bucket is 1
    slot; it grows with the survivor count (``bucket_for``).

    ``planar`` selects the state layout (see :class:`Frontier`); None
    follows the process engine — callers that pin an engine (the mesh
    bodies pin XLA) must pin the matching layout here."""
    if planar is None:
        planar = _expand_engine()
    root = ibdcf.eval_init(keys)  # [N, d, 2]
    alive = jnp.zeros((f_bucket,), bool).at[0].set(True)
    if planar:
        # plane-major: [4, d, 2, F, N] seeds, [d, 2, F, N] bits (one
        # once-per-crawl transpose of [N, d, 2]-sized roots — tiny)
        seed = jnp.transpose(root.seed, (3, 1, 2, 0))  # [4, d, 2, N]
        seed = jnp.broadcast_to(
            seed[:, :, :, None], seed.shape[:3] + (f_bucket,) + seed.shape[3:]
        )
        pb = lambda a: jnp.broadcast_to(
            jnp.transpose(a, (1, 2, 0))[:, :, None],
            a.shape[1:] + (f_bucket, a.shape[0]),
        )
        states = EvalState(seed=seed, bit=pb(root.bit), y_bit=pb(root.y_bit))
    else:
        pad = lambda a: jnp.broadcast_to(a[None], (f_bucket,) + a.shape)
        states = EvalState(*[pad(x) for x in root])
    return Frontier(states=states, alive=alive)


def _bit_positions(d: int):
    """bit position of (dim j, side s, direction r) in the packed uint32."""
    j = np.arange(d)[:, None, None]
    s = np.arange(2)[None, :, None]
    r = np.arange(2)[None, None, :]
    return (j * 4 + s * 2 + r).astype(np.uint32)  # [d, 2, 2]


@lru_cache(maxsize=None)
def pattern_masks(d: int) -> np.ndarray:
    """uint32[2^d] — for child pattern c, the packed-bit positions that a
    membership test must compare: both sides of every dim, at direction
    ``(c >> j) & 1`` (child order: ref lib.rs:125-129).

    Cached (and returned read-only): every crawl level on every server
    asked for the same table, rebuilding a 2^d Python loop per level."""
    assert d <= MAX_DIMS
    pos = _bit_positions(d)
    masks = []
    for c in range(1 << d):
        m = np.uint32(0)
        for j in range(d):
            r = (c >> j) & 1
            m |= (np.uint32(1) << pos[j, 0, r]) | (np.uint32(1) << pos[j, 1, r])
        masks.append(m)
    out = np.array(masks, dtype=np.uint32)
    out.setflags(write=False)
    return out


def expand_share_bits(
    keys: IbDcfKeyBatch, frontier: Frontier, level, want_children: bool = True,
    use_pallas: bool | None = None,
):
    """One PRG expansion of the whole frontier -> packed share bits + the
    both-direction child-state cache.

    Returns ``(packed, children)``:

    - packed uint32[F, N]: for every (node, client), the share bits
      ``y_bit ^ bit`` of BOTH child directions of every (dim, side) key,
      packed at ``_bit_positions`` (the tensor twin of collect.rs:393-410's
      per-(node,client) left||right bit strings — ours carries both
      directions so all 2^d patterns read from it);
    - children: the fully-corrected child states of every slot, so the
      post-prune :func:`advance_from_children` is a gather, not a second
      PRG pass.  Its TYPE follows the engine: an :class:`EvalState` over
      ``[F, N, d, 2, 2]`` (trailing axis = direction) from the XLA engine,
      a :class:`PlanarChildren` from the Pallas engine (the default on
      real chips) — treat it as opaque and hand it back to
      :func:`advance_from_children`, which dispatches on the type.

    ``level`` may be traced; the same value must hold for the whole frontier
    (the crawl is level-synchronous, ref: leader.rs:417-440).

    ``want_children=False`` (the LAST level, which nothing advances past)
    skips materializing the cache — jit outputs are never dead-code
    eliminated, so the flag must be static, not a discarded return.

    ``use_pallas`` overrides the process engine (None follows it):
    callers that pin a frontier LAYOUT — the multi-chip server mesh pins
    interleaved/XLA, parallel/server_mesh.py — must pin the engine to
    match, exactly like ``tree_init``'s ``planar`` knob.
    """
    if use_pallas is None:
        use_pallas = _expand_engine()
    return _expand_share_bits_jit(
        keys, frontier, level, prg.DERIVED_BITS, want_children, use_pallas,
    )


@partial(jax.jit, static_argnames=("derived_bits", "want_children", "use_pallas"))
def _expand_share_bits_jit(keys, frontier, level, derived_bits,
                           want_children=True, use_pallas=False):
    cw = ibdcf.level_cw(keys, level)  # [N,d,2,(4|2)] each
    return _expand_body(cw, frontier, derived_bits, want_children, use_pallas)


def expand_share_bits_from_cw(cw, frontier: Frontier, want_children: bool = True):
    """:func:`expand_share_bits` for callers that hold the level's
    correction words directly instead of a device key batch — the
    HBM-overflow streaming mode (protocol/driver.py): keys live in host
    RAM and only the current level's cw slice rides to the device.

    ``cw`` = (cw_seed [N,d,2,4], cw_bits [N,d,2,2], cw_y [N,d,2,2]).
    """
    return _expand_cw_jit(
        cw, frontier, prg.DERIVED_BITS, want_children, _expand_engine()
    )


@partial(jax.jit, static_argnames=("derived_bits", "want_children", "use_pallas"))
def _expand_cw_jit(cw, frontier, derived_bits, want_children, use_pallas):
    return _expand_body(cw, frontier, derived_bits, want_children, use_pallas)


def _expand_body(cw, frontier, derived_bits, want_children, use_pallas):
    cw_seed, cw_bits, cw_y = cw
    st = frontier.states
    if use_pallas:
        # plane-major fused kernel: pack, flags, and cw broadcast all live
        # INSIDE the pallas_call (ops/expand_pallas.py); the only XLA prep
        # is one tiny per-level cw transpose+pack over [N, d, 2] arrays
        from ..ops import expand_pallas

        d, _, F, N = st.bit.shape  # [d, 2, F, N]
        d2, B = d * 2, F * N
        cws_n = jnp.transpose(
            jnp.asarray(cw_seed, jnp.uint32), (3, 1, 2, 0)
        ).reshape(4, d2, N)
        u32 = lambda a: jnp.transpose(a, (1, 2, 0)).astype(jnp.uint32)
        cwf_n = (
            u32(cw_bits[..., 0]) | (u32(cw_bits[..., 1]) << 1)
            | (u32(cw_y[..., 0]) << 2) | (u32(cw_y[..., 1]) << 3)
        ).reshape(d2, N)
        packed, oseeds, oflags = expand_pallas.expand_packed(
            st.seed.reshape(4, d2, B), st.bit.reshape(d2, B),
            st.y_bit.reshape(d2, B), cws_n, cwf_n, derived_bits,
            want_children,
        )
        packed = packed.reshape(F, N)
        if not want_children:
            return packed, None
        children = PlanarChildren(
            seed=oseeds.reshape(2, 4, d, 2, F, N),
            flags=oflags.reshape(d, 2, F, N),
        )
        return packed, children
    # one fully-batched XLA expansion over (node, client, dim, side)
    shp = st.bit.shape  # [F, N, d, 2]
    s_l, s_r, tau_b, tau_y = prg.expand(st.seed, derived_bits)
    t = st.bit[..., None]
    nb = jnp.where(t, tau_b ^ cw_bits, tau_b)  # cw broadcasts over F
    ny = jnp.where(t, tau_y ^ cw_y, tau_y)
    ny = ny ^ st.y_bit[..., None]
    share = nb ^ ny  # share bit = y ^ t per direction
    pos = jnp.asarray(_bit_positions(share.shape[-3]))  # [d, 2, 2]
    packed = jnp.sum(
        share.astype(jnp.uint32) << pos, axis=(-3, -2, -1), dtype=jnp.uint32
    )  # [F, N] uint32
    if not want_children:
        return packed, None
    # child-state cache: direction axis second-to-last (matching
    # nb/ny's trailing direction axis), seed correction per
    # ibDCF.rs:213-218 (the kernel applies it internally)
    seeds = jnp.stack([s_l, s_r], axis=-2)  # [F, N, d, 2, 2, 4]
    tc = st.bit[..., None, None]  # [F, N, d, 2, 1, 1]
    seeds = jnp.where(tc, seeds ^ cw_seed[..., None, :], seeds)
    children = EvalState(seed=seeds, bit=nb, y_bit=ny)
    return packed, children


def advance_from_children(
    children,
    parent_idx: jax.Array,
    pattern_bits: jax.Array,
    n_alive,
) -> Frontier:
    """Materialize the surviving children from the expand-time cache.

    ``children`` is whatever this level's :func:`expand_share_bits`
    returned — an :class:`EvalState` cache (XLA engine) or a
    :class:`PlanarChildren` (Pallas engine); the cache type selects the
    layout path, so a frontier never mixes layouts mid-crawl.

    parent_idx:   int32[F'] parent slot per surviving child (bucket-padded);
    pattern_bits: bool[F', d] child pattern per survivor;
    n_alive:      number of real entries (rest is padding).

    A gather over the node axis + a per-dim direction select — zero PRG
    work (the expansion already happened in :func:`expand_share_bits`).
    Both keys of a dim take the same direction bit: the interval pair
    walks together (ref: collect.rs:100, ibDCF.rs:120-131).
    """
    return _advance_children_jit(
        children, parent_idx, pattern_bits, n_alive,
        isinstance(children, PlanarChildren),
    )


@partial(jax.jit, static_argnames=("planar",))
def _advance_children_jit(children, parent_idx, pattern_bits, n_alive,
                          planar=False):
    if planar:
        # children: PlanarChildren(seed [2, 4, d, 2, F, N], flags [d, 2, F, N])
        seed_g = jnp.take(children.seed, parent_idx, axis=4)
        fl = jnp.take(children.flags, parent_idx, axis=2)  # [d, 2, F', N]
        one = jnp.uint32(1)
        bl, br = fl & one, (fl >> 1) & one
        yl, yr = (fl >> 2) & one, (fl >> 3) & one
        # per-plane direction: pattern bit of the dim, same for both sides
        dirp = jnp.transpose(pattern_bits)[:, None, :, None]  # [d, 1, F', 1]
        states = EvalState(
            seed=jnp.where(dirp[None], seed_g[1], seed_g[0]),
            bit=jnp.where(dirp, br, bl) != 0,
            y_bit=jnp.where(dirp, yr, yl) != 0,
        )
    else:
        dirb = pattern_bits[:, None, :, None]  # [F',1,d,1] -> bcast [F',N,d,2]
        ch = jax.tree.map(lambda a: a[parent_idx], children)  # [F', N, d, 2, 2, ..]
        states = EvalState(
            seed=jnp.where(dirb[..., None], ch.seed[..., 1, :], ch.seed[..., 0, :]),
            bit=jnp.where(dirb, ch.bit[..., 1], ch.bit[..., 0]),
            y_bit=jnp.where(dirb, ch.y_bit[..., 1], ch.y_bit[..., 0]),
        )
    alive = jnp.arange(parent_idx.shape[0]) < n_alive
    return Frontier(states=states, alive=alive)


@jax.jit
def counts_by_pattern(
    packed_self: jax.Array,
    packed_peer: jax.Array,
    masks: jax.Array,
    alive_keys: jax.Array,
    alive_nodes: jax.Array,
) -> jax.Array:
    """uint32[F, 2^d] per-child candidate counts.

    Membership of client i's ball in child (f, c) ⇔ the two servers' share
    bits agree on every compared position: ``(p0 ^ p1) & masks[c] == 0``
    (the plaintext of the GC equality test, ref: equalitytest.rs:130-146,
    reconstructed as the leader would, collect.rs:945-964).  Dead clients
    and dead nodes contribute zero (liveness gate, ref: collect.rs:495).
    """
    diff = packed_self ^ packed_peer  # [F, N]
    eq = (diff[:, :, None] & masks[None, None, :]) == 0  # [F, N, 2^d]
    eq = eq & alive_keys[None, :, None] & alive_nodes[:, None, None]
    return jnp.sum(eq, axis=1, dtype=jnp.uint32)  # [F, 2^d]


def to_interleaved(states: EvalState) -> EvalState:
    """Plane-major frontier state (seed [4, d, 2, F, N], bits [d, 2, F, N])
    -> interleaved ([F, N, d, 2, 4] / [F, N, d, 2]).  The single source of
    truth for the engine-edge transposes (used by :func:`advance` and the
    checkpoint restore's cross-engine conversion)."""
    return EvalState(
        seed=jnp.transpose(states.seed, (3, 4, 1, 2, 0)),
        bit=jnp.transpose(states.bit, (2, 3, 0, 1)),
        y_bit=jnp.transpose(states.y_bit, (2, 3, 0, 1)),
    )


def to_planar(states: EvalState) -> EvalState:
    """Inverse of :func:`to_interleaved` (the bit transpose is involutive;
    the seed one is its inverse permutation)."""
    return EvalState(
        seed=jnp.transpose(states.seed, (4, 2, 3, 0, 1)),
        bit=jnp.transpose(states.bit, (2, 3, 0, 1)),
        y_bit=jnp.transpose(states.y_bit, (2, 3, 0, 1)),
    )


def advance(
    keys: IbDcfKeyBatch,
    frontier: Frontier,
    level,
    parent_idx: jax.Array,
    pattern_bits: jax.Array,
    n_alive: jax.Array,
    use_pallas: bool | None = None,
) -> Frontier:
    """Re-expanding advance: the fallback for callers WITHOUT a child-state
    cache from :func:`expand_share_bits` (the crawl paths all have one and
    use :func:`advance_from_children` instead — zero PRG work).

    parent_idx:   int32[F'] parent slot per surviving child (padded);
    pattern_bits: bool[F', d] child pattern per survivor;
    n_alive:      number of real entries (rest is padding).

    Gathers the parents' states and advances one level with the pattern's
    per-dim direction (both keys of a dim take the same bit — the interval
    pair walks together, ref: collect.rs:100, ibDCF.rs:120-131).

    Layout note: the eval recurrence wants interleaved seeds; under the
    planar engine this rare path converts at the edges (tiny next to the
    PRG work it is about to redo).  ``use_pallas`` overrides the process
    engine (None follows it) for callers that pin a layout — see
    :func:`expand_share_bits`.
    """
    planar = _expand_engine() if use_pallas is None else use_pallas
    if planar:  # plane-major [4,d,2,F,N]/[d,2,F,N] -> interleaved
        frontier = frontier._replace(states=to_interleaved(frontier.states))
    out = _advance_jit(
        keys, frontier, level, parent_idx, pattern_bits, n_alive,
        prg.DERIVED_BITS,
    )
    if planar:
        out = out._replace(states=to_planar(out.states))
    return out


@partial(jax.jit, static_argnames=("derived_bits",))
def _advance_jit(keys, frontier, level, parent_idx, pattern_bits, n_alive,
                 derived_bits):
    cw = ibdcf.level_cw(keys, level)
    return _advance_body(cw, frontier, parent_idx, pattern_bits, n_alive,
                         derived_bits)


def _advance_body(cw, frontier, parent_idx, pattern_bits, n_alive, derived_bits):
    st = frontier.states
    parents = jax.tree.map(lambda a: a[parent_idx], st)  # [F', N, d, 2]
    direction = jnp.broadcast_to(
        pattern_bits[:, None, :, None], parents.bit.shape
    )  # child pattern bit of each dim, same for both keys of the dim
    states = ibdcf._eval_bit_jit(cw, parents, direction, derived_bits)
    f_max = parent_idx.shape[0]
    alive = jnp.arange(f_max) < n_alive
    return Frontier(states=states, alive=alive)


def advance_from_cw(cw, frontier: Frontier, parent_idx, pattern_bits, n_alive,
                    node_chunk: int | None = None) -> Frontier:
    """Re-expanding advance from an explicit cw slice — the streaming-mode
    twin of :func:`advance` (see :func:`expand_share_bits_from_cw`): the
    caller already uploaded this level's correction words, and the crawl
    runs WITHOUT a child cache (at wide frontiers the cache's
    ``F x N x d x 2`` x 36 B footprint is what breaks the HBM budget, so
    streaming crawls re-expand the survivors instead).

    Under the planar engine the whole step stays plane-major (gather
    surviving parents -> expand kernel -> direction select) — no layout
    transposes ever touch the multi-GB frontier — and ``node_chunk``
    bounds the transient: the child bucket is computed ``node_chunk``
    parent slots at a time inside one jit (fori_loop + in-place dynamic
    updates), so peak HBM is old frontier + new frontier + ONE chunk's
    expansion instead of + a full-bucket child cache.  The parent
    frontier is donated where XLA can use it.
    """
    F2 = parent_idx.shape[0]
    if _expand_engine():
        c = F2 if node_chunk is None else min(F2, node_chunk)
        if F2 % c:
            c = F2  # chunk must tile the bucket (both are powers of two)
        return _advance_cw_planar_jit(
            cw, frontier, parent_idx, pattern_bits, n_alive,
            prg.DERIVED_BITS, c,
        )
    return _advance_cw_jit(
        cw, frontier, parent_idx, pattern_bits, n_alive, prg.DERIVED_BITS
    )


@partial(jax.jit, static_argnames=("derived_bits",), donate_argnums=(1,))
def _advance_cw_jit(cw, frontier, parent_idx, pattern_bits, n_alive,
                    derived_bits):
    return _advance_body(cw, frontier, parent_idx, pattern_bits, n_alive,
                         derived_bits)


@partial(jax.jit, static_argnames=("derived_bits", "chunk"), donate_argnums=(1,))
def _advance_cw_planar_jit(cw, frontier, parent_idx, pattern_bits, n_alive,
                           derived_bits, chunk):
    st = frontier.states  # plane-major [4,d,2,F,N] / [d,2,F,N]
    F2 = parent_idx.shape[0]
    d = st.bit.shape[0]
    N = st.bit.shape[-1]

    def advance_slots(pidx, pbits):
        c = pidx.shape[0]
        par = EvalState(
            seed=jnp.take(st.seed, pidx, axis=3),
            bit=jnp.take(st.bit, pidx, axis=2),
            y_bit=jnp.take(st.y_bit, pidx, axis=2),
        )
        gf = Frontier(states=par, alive=jnp.ones(c, bool))
        _, children = _expand_body(cw, gf, derived_bits, True, True)
        return _advance_children_jit(
            children, jnp.arange(c), pbits, c, planar=True
        ).states

    if chunk == F2:
        states = advance_slots(parent_idx, pattern_bits)
    else:
        def body(i, acc):
            pidx = jax.lax.dynamic_slice_in_dim(parent_idx, i * chunk, chunk)
            pbits = jax.lax.dynamic_slice_in_dim(
                pattern_bits, i * chunk, chunk
            )
            ns = advance_slots(pidx, pbits)
            upd = lambda a, u, ax: jax.lax.dynamic_update_slice_in_dim(
                a, u, i * chunk, axis=ax
            )
            return EvalState(
                seed=upd(acc.seed, ns.seed, 3),
                bit=upd(acc.bit, ns.bit, 2),
                y_bit=upd(acc.y_bit, ns.y_bit, 2),
            )

        init = EvalState(
            seed=jnp.zeros((4, d, 2, F2, N), jnp.uint32),
            bit=jnp.zeros((d, 2, F2, N), bool),
            y_bit=jnp.zeros((d, 2, F2, N), bool),
        )
        states = jax.lax.fori_loop(0, F2 // chunk, body, init)
    alive = jnp.arange(F2) < n_alive
    return Frontier(states=states, alive=alive)


# ---------------------------------------------------------------------------
# Mid-level sharding (data-plane fault tolerance)
#
# A level's crawl can be split into deterministic spans over the frontier
# NODE axis: each span is its own RPC verb with its own request id, so a
# mid-level fault re-runs only the lost spans (protocol/leader_rpc.py's
# shard retry) instead of the whole level.  Spans must be identical on
# the leader and both servers — they are pure functions of (f_bucket,
# shard_nodes), both public.
# ---------------------------------------------------------------------------


def shard_spans(f_bucket: int, shard_nodes: int) -> list:
    """Deterministic node-axis spans ``[(lo, hi), ...]`` covering
    ``[0, f_bucket)``.  ``shard_nodes <= 0`` (the default) disables
    sharding: one span, the whole bucket."""
    if shard_nodes <= 0 or f_bucket <= shard_nodes:
        return [(0, f_bucket)]
    return [
        (lo, min(lo + shard_nodes, f_bucket))
        for lo in range(0, f_bucket, shard_nodes)
    ]


def frontier_slice(
    frontier: Frontier, lo: int, hi: int, planar: bool | None = None
) -> Frontier:
    """One shard's view of the frontier: node slots ``[lo, hi)`` of the
    states and the alive mask, layout-aware (the node axis sits at
    position 3/2 in the plane-major layout, 0 in the interleaved one)."""
    if planar is None:
        planar = _expand_engine()
    st = frontier.states
    if planar:
        states = EvalState(
            seed=st.seed[:, :, :, lo:hi],
            bit=st.bit[:, :, lo:hi],
            y_bit=st.y_bit[:, :, lo:hi],
        )
    else:
        states = jax.tree.map(lambda a: a[lo:hi], st)
    return Frontier(states=states, alive=frontier.alive[lo:hi])


def children_cat(parts: list):
    """Reassemble a full-level child-state cache from per-shard caches.

    ``parts``: list of ``(lo, children)`` in any order; children are
    whatever the engine's :func:`expand_share_bits` returned for each
    shard (all the same type).  Concatenates along the node axis in
    ``lo`` order — the exact inverse of :func:`frontier_slice`."""
    parts = [c for _, c in sorted(parts, key=lambda t: t[0])]
    if isinstance(parts[0], PlanarChildren):
        return PlanarChildren(
            seed=jnp.concatenate([p.seed for p in parts], axis=4),
            flags=jnp.concatenate([p.flags for p in parts], axis=2),
        )
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *parts)


# ---------------------------------------------------------------------------
# Host-side compaction helper (leader-side prune bookkeeping)
# ---------------------------------------------------------------------------


def compact_survivors(keep: np.ndarray, f_max: int, min_bucket: int = 1):
    """keep: bool[F, 2^d] -> (parent_idx int32[Fb], pattern int32[Fb],
    n_alive) zero-padded to the survivor bucket ``Fb = bucket_for(...)``.
    Raises if survivors exceed the ``f_max`` cap — the bucketed-frontier
    equivalent of the reference's unbounded Vec growth."""
    f, c = np.nonzero(keep)
    fb = bucket_for(len(f), f_max, min_bucket)
    parent = np.zeros(fb, np.int32)
    pattern = np.zeros(fb, np.int32)
    parent[: len(f)] = f
    pattern[: len(f)] = c
    return parent, pattern, len(f)


def pattern_to_bits(pattern: np.ndarray, d: int) -> np.ndarray:
    """int32[F'] child pattern ids -> bool[F', d] per-dim direction bits
    (bit j = (c >> j) & 1, ref: lib.rs:125-129)."""
    return ((pattern[:, None] >> np.arange(d)[None]) & 1).astype(bool)


# ---------------------------------------------------------------------------
# Radix-2^k level fusion: crawl ``radix`` bits per round trip.
#
# A fused level expands every frontier node by all 2^(radix·d) child
# patterns at once.  Pattern ids are STEP-MAJOR: c = Σ_t step_t << (t·d)
# with step_t the familiar per-dim pattern of bit-level (base + t), so
# dim j's direction at step t is ``(c >> (t·d + j)) & 1`` — at radix 1
# this is exactly the existing child order (lib.rs:125-129), and the
# fused child of node f sits at ``f·2^(radix·d) + c`` like before.
#
# The packed share-bit word generalizes the ``j*4 + s*2 + r`` layout: per
# (dim j, side s) it stores the share bit of EVERY node in the depth-
# ``radix`` subtree — depth i's 2^i nodes at offset ``2^i - 2``, node
# indices little-endian in step order (the child of node m via direction
# r at depth i+1 is ``m | r << i``) — at position
#
#     j·2T + s·T + (2^i - 2) + node_idx,      T = radix_subtree_nodes(radix)
#
# which reduces to the radix-1 layout verbatim (T = 2).  Membership of a
# fused child is the conjunction of its per-depth memberships (the
# interval predicate is monotone along a path), so equality over the
# concatenated per-depth strings — what pattern_masks_radix compares —
# counts exactly what radix-1 would count at the deepest level, and the
# word still fits u32 at the supported (d, radix) pairs (check_radix).
# Radix > 1 pins the interleaved/XLA engine: the child cache is a plain
# EvalState with a 2^radix-wide node axis (the radix-1 cache's direction
# axis, generalized), so sharding/concat/advance reuse the same paths.
# ---------------------------------------------------------------------------


def _radix_positions(d: int, radix: int, step: int) -> np.ndarray:
    """uint32[d, 2, 2^(step+1)] — packed-bit positions of every
    depth-(step+1) subtree node per (dim, side).  Reduces to
    :func:`_bit_positions` at (radix, step) = (1, 0)."""
    T = radix_subtree_nodes(radix)
    j = np.arange(d)[:, None, None]
    s = np.arange(2)[None, :, None]
    m = np.arange(2 << step)[None, None, :]
    return (j * (2 * T) + s * T + ((2 << step) - 2) + m).astype(np.uint32)


@lru_cache(maxsize=None)
def pattern_masks_radix(d: int, radix: int) -> np.ndarray:
    """uint32[2^(radix·d)] — for fused child pattern c, the packed-bit
    positions a membership test compares: both sides of every dim at
    EVERY depth 1..radix along c's path.  ``pattern_masks`` at radix 1."""
    if radix == 1:
        return pattern_masks(d)
    check_radix(d, radix)
    T = radix_subtree_nodes(radix)
    masks = []
    for c in range(1 << (radix * d)):
        m = np.uint32(0)
        node = [0] * d  # per-dim subtree node index along c's path
        for t in range(radix):
            base = (2 << t) - 2
            for j in range(d):
                r = (c >> (t * d + j)) & 1
                node[j] |= r << t
                p = np.uint32(j * 2 * T + base + node[j])
                m |= (np.uint32(1) << p) | (np.uint32(1) << (p + np.uint32(T)))
        masks.append(m)
    out = np.array(masks, dtype=np.uint32)
    out.setflags(write=False)
    return out


def expand_share_bits_radix(
    keys: IbDcfKeyBatch, frontier: Frontier, level, radix: int,
    want_children: bool = True, use_pallas: bool | None = None,
):
    """:func:`expand_share_bits` crawling ``radix`` bit-levels at once:
    packed uint32[F, N] carries the share bits of the whole depth-
    ``radix`` subtree per (node, client), and ``children`` is an
    :class:`EvalState` cache over ``[F, N, d, 2, 2^radix, …]`` (trailing
    node axis = the subtree leaves) for :func:`advance_from_children_radix`.

    ``level`` is the BASE bit-level of the fused step (the key batch's
    correction words at ``level .. level+radix-1`` are consumed); it may
    be traced, so one compiled program serves every fused level of a
    crawl.  ``radix`` is the ACTUAL width of this step — the tail level
    of a crawl whose data_len is not a radix multiple passes its shorter
    remainder.  Radix 1 delegates to :func:`expand_share_bits` verbatim
    (same compiled programs, bit-identical crawl)."""
    if radix == 1:
        return expand_share_bits(
            keys, frontier, level,
            want_children=want_children, use_pallas=use_pallas,
        )
    if use_pallas:
        raise ValueError(
            "radix > 1 pins the interleaved/XLA expand engine — the "
            "plane-major Pallas layout has no fused multi-level kernel"
        )
    return _expand_radix_jit(
        keys, frontier, level, prg.DERIVED_BITS, radix, want_children
    )


@partial(jax.jit, static_argnames=("derived_bits", "radix", "want_children"))
def _expand_radix_jit(keys, frontier, level, derived_bits, radix,
                      want_children):
    st = frontier.states  # interleaved [F, N, d, 2, …]
    d = st.bit.shape[-2]
    # walk the subtree breadth-first: ``cur`` holds ALL of depth t's
    # nodes on a trailing node axis M = 2^t, each step expanding every
    # node into both children (new index = direction·M + m — the
    # little-endian step order the mask/advance tables assume)
    cur = EvalState(
        seed=st.seed[..., None, :], bit=st.bit[..., None],
        y_bit=st.y_bit[..., None],
    )
    packed = jnp.zeros(st.bit.shape[:2], jnp.uint32)  # [F, N]
    for t in range(radix):
        cw_seed, cw_bits, cw_y = ibdcf.level_cw(keys, level + t)  # [N,d,2,…]
        s_l, s_r, tau_b, tau_y = prg.expand(cur.seed, derived_bits)
        tb = cur.bit[..., None]  # [F, N, d, 2, M, 1]
        nb = jnp.where(tb, tau_b ^ cw_bits[None, :, :, :, None, :], tau_b)
        ny = jnp.where(tb, tau_y ^ cw_y[None, :, :, :, None, :], tau_y)
        ny = ny ^ cur.y_bit[..., None]
        share = nb ^ ny  # [F, N, d, 2, M, 2] (trailing direction axis)
        M = share.shape[-2]
        swap = lambda a: jnp.swapaxes(a, -1, -2).reshape(
            a.shape[:-2] + (2 * M,)
        )  # node index = direction·M + m
        pos = jnp.asarray(_radix_positions(d, radix, t))  # [d, 2, 2M]
        packed = packed | jnp.sum(
            swap(share).astype(jnp.uint32) << pos,
            axis=(-3, -2, -1), dtype=jnp.uint32,
        )
        seeds = jnp.stack([s_l, s_r], axis=-3)  # [F, N, d, 2, 2, M, 4]
        tc = cur.bit[..., None, :, None]  # [F, N, d, 2, 1, M, 1]
        seeds = jnp.where(
            tc, seeds ^ cw_seed[None, :, :, :, None, None, :], seeds
        )
        cur = EvalState(
            seed=seeds.reshape(seeds.shape[:-3] + (2 * M, 4)),
            bit=swap(nb), y_bit=swap(ny),
        )
    return packed, (cur if want_children else None)


def advance_from_children_radix(
    children, parent_idx: jax.Array, pattern_bits: jax.Array, n_alive,
    radix: int,
) -> Frontier:
    """:func:`advance_from_children` for a fused level: gather the
    surviving fused children from the radix cache's subtree-leaf axis.

    pattern_bits: bool[F', radix, d] step-major fused patterns
    (:func:`pattern_to_bits_radix`).  Radix 1 delegates to the existing
    advance (same compiled programs)."""
    if radix == 1:
        return advance_from_children(
            children, parent_idx, pattern_bits[:, 0, :], n_alive
        )
    return _advance_children_radix_jit(
        children, parent_idx, pattern_bits, n_alive
    )


@jax.jit
def _advance_children_radix_jit(children, parent_idx, pattern_bits, n_alive):
    r = pattern_bits.shape[1]
    # per-dim subtree-leaf index, little-endian in step order
    w = (1 << jnp.arange(r, dtype=jnp.int32))[None, :, None]
    idx = jnp.sum(pattern_bits.astype(jnp.int32) * w, axis=1)  # [F', d]
    ch = jax.tree.map(lambda a: a[parent_idx], children)  # [F', N, d, 2, R, …]
    i5 = idx[:, None, :, None, None]
    states = EvalState(
        seed=jnp.take_along_axis(
            ch.seed, i5[..., None], axis=-2
        )[..., 0, :],
        bit=jnp.take_along_axis(ch.bit, i5, axis=-1)[..., 0],
        y_bit=jnp.take_along_axis(ch.y_bit, i5, axis=-1)[..., 0],
    )
    alive = jnp.arange(parent_idx.shape[0]) < n_alive
    return Frontier(states=states, alive=alive)


def pattern_to_bits_radix(pattern: np.ndarray, d: int, radix: int) -> np.ndarray:
    """int[F'] fused child ids -> bool[F', radix, d] per-step direction
    bits (dim j at step t = ``(c >> (t·d + j)) & 1`` — step-major).
    ``pattern_to_bits`` with a leading step axis at radix 1."""
    shift = np.arange(radix)[None, :, None] * d + np.arange(d)[None, None, :]
    return ((np.asarray(pattern)[:, None, None] >> shift) & 1).astype(bool)


@lru_cache(maxsize=None)
def radix_pattern_order(d: int, radix: int) -> np.ndarray:
    """int32[2^(radix*d)] — step-major fused pattern ids listed in the
    k=1 crawl's survivor VISIT order.  The radix-1 crawl emits a level's
    survivors sorted by per-level pattern with EARLIER levels most
    significant; the step-major fused id c = Σ_t p_t·2^(t·d) sorts the
    LAST step most significant, so a fused prune walked in ascending-c
    order would list the same survivor set in a different order (and,
    under f_max truncation, could keep a different subset).  Walking
    fused children as ``order[rank]`` with
    rank = Σ_t p_t·2^((radix−1−t)·d) restores the k=1 order exactly.
    Identity at radix=1."""
    C = 1 << (radix * d)
    mask = (1 << d) - 1
    out = np.empty(C, np.int32)
    for c in range(C):
        rank = 0
        for t in range(radix):
            rank |= ((c >> (t * d)) & mask) << ((radix - 1 - t) * d)
        out[rank] = c
    return out
