"""Row-sharded secure-equality kernel stage: IKNP + 1-of-2^S / GC under
``shard_map`` on the server's local ``data`` mesh.

PR 8 sharded the CLIENT axis across each server's mesh but stopped at
the 2PC boundary: the packed share bits gathered over ICI onto ONE
device before the whole-level kernels ran, so the dominant secure phase
— extension, equality encrypt/garble, payload open, b2a — stayed
single-device no matter how many chips the server had.  This module
makes the kernel stage itself mesh-parallel with a **byte-identical
wire**:

- the whole-level planar test batch partitions along its ROW/BLOCK axis
  (units of ``gc_pallas.R_BLK * GROUP`` = 8192 tests — whole planar
  blocks, so each shard's slice of every wire plane is contiguous and
  the pallas grid needs no per-shard padding);
- the IKNP extension row-shards with it: the column PRG streams are
  CTR-mode and the packed butterfly transpose is word-local, so shard i
  computes exactly OT rows ``[t0*S, (t0 + bloc)*S)`` from the seeds +
  the matching u column-word slice (``otext.sender_extend_rows`` /
  ``receiver_extend_rows``) — bit-identical to the corresponding rows
  of a single-device extend;
- every per-test stream draw (b2a payload pair, GC labels + masks)
  seeks to its shard's slice of the SAME per-level stream
  (``gc._carve_label_words_shard``, the b2a block seek below), and every
  per-test pad index enters as ``idx0 + t0`` (64 bits wide) — so shard
  outputs are the exact planar-row slices of the single-device buffers;
- rows at or past the real batch (the planar pad region, which the
  uniform per-shard shapes cover) are ZERO-masked before anything
  wire-visible, reproducing the single-device ``_pad_tests`` padding
  byte for byte.  Those rows read stream blocks the session cursor has
  not consumed, but nothing derived from them survives the mask, so no
  cross-level stream material can reach the wire;
- the planar Pallas engines run UNDER ``shard_map`` (per-shard block
  shapes): on accelerator hosts the mesh no longer forces a gather to
  feed them — each shard runs the fused kernel on its own block span,
  with the XLA twins remaining the per-shard bit-parity oracle (and the
  CPU/tier-1 engine);
- no step between FSS expansion and the wire serializes onto device 0:
  the sender's frame and the receiver's u-matrix are read back PER
  SHARD (``copy_to_host_async`` double-buffering, the PR 5 pattern) and
  reassembled positionally on the host; the b2a share outputs psum back
  over ICI (``parallel.mesh.field_psum``) like every other pre-wire
  reduction.

Shard-count binding: the planar batch has ``padded_tests(B) // 8192``
blocks; the active kernel shard count is the largest divisor of that
block count that fits the budget (``Config.secure_kernel_shards``,
auto = the mesh's data shards) — a non-dividing batch DEGRADES to fewer
kernel shards (ultimately to the PR 8 gather path at k = 1) instead of
failing.  Byte-identity at every k is asserted in tier-1
(tests/test_kernel_shard.py) and gates the bench legs.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops import gc, gc_pallas, otext
from ..ops.fields import F255, FE62
from ..ops.gc_pallas import GROUP, LANES, SUB, padded_tests
from .mesh import field_psum
from .server_mesh import DATA, _largest_divisor_leq, _mesh_for

# shard unit: one pallas grid step's worth of tests — the planar wire's
# natural block, so per-shard buffers concatenate along the row axis
BLOCK = gc_pallas.R_BLK * GROUP

# Test hook: force the Pallas engines (interpret mode) under shard_map
# on CPU hosts, where the engine flags normally fall back to the XLA
# twins — the per-shard parity oracle test flips this.
PALLAS_INTERPRET: bool = False

_FIELDS = {"FE62": FE62, "F255": F255}


def kernel_shards(B: int, budget: int) -> int:
    """Active kernel shard count for a ``B``-test level under a device
    ``budget``: the largest divisor of the planar block count <= budget
    (1 = the single-device gather path)."""
    nblk = padded_tests(B) // BLOCK
    return _largest_divisor_leq(nblk, max(1, int(budget)))


def _engine(path: str) -> str:
    """Static engine tag for the per-shard kernels: ``"pallas"`` on real
    chips when the module flags say so (gc.GC_PALLAS / secure.OT2S_PALLAS
    — the same dispatch the single-device packed entry points use),
    ``"pallas_interpret"`` under the test hook, else the XLA twins."""
    from ..protocol import secure
    from ..utils import effective_platform

    if PALLAS_INTERPRET:
        return "pallas_interpret"
    if effective_platform() == "cpu":
        return "xla"
    flag = gc.GC_PALLAS if path == "gc" else secure.OT2S_PALLAS
    return "pallas" if flag else "xla"


def n_msg_planes(path: str, S: int, W: int) -> int:
    """u32 planes of one whole-level wire message (each ``padded_tests``
    words): the 1-of-2^S ciphertext stack, or the packed garbled batch
    (tables | gb_labels | decode | cts)."""
    if path == "ot2s":
        return (1 << S) * W
    return (S - 1) * 2 * 4 + 4 * S + 1 + 2 * W


@dataclass(frozen=True)
class KernelShard:
    """One level's kernel-stage binding: ``k`` mesh devices, ``B`` real
    tests of string width ``S`` on a ``bp``-test planar frame."""

    devices: tuple
    B: int
    S: int

    @property
    def k(self) -> int:
        return len(self.devices)

    @property
    def bp(self) -> int:
        return padded_tests(self.B)

    @property
    def mesh(self):
        return _mesh_for(self.devices)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def bind(devices: tuple, B: int, S: int, budget: int) -> KernelShard | None:
    """Bind the kernel stage of a ``B``-test level to the leading mesh
    devices; ``None`` when only one shard fits (the caller keeps the
    single-device gather path)."""
    k = kernel_shards(B, min(int(budget), len(devices)))
    if k < 2:
        return None
    return KernelShard(devices=tuple(devices[:k]), B=B, S=S)


# ---------------------------------------------------------------------------
# Sharded program factories (one compiled SPMD program per shape, shared
# process-wide — warm and live hit the same executables, like
# server_mesh._counts_fn)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _flat_fn(d: int, F: int, N: int, bp: int, radix: int = 1):
    """packed u32[F, N] -> zero-padded flat strings bool[bp, 2*d*radix]
    (the whole-level test order (F, C, N), the planar frame extent).
    radix > 1 reads the fused radix layout: C = 2^(radix*d) children per
    frontier node, string width S' = 2*d*radix."""
    from ..protocol import secure

    def f(packed):
        strs = secure.child_strings_radix(packed, d, radix)  # [F, C, N, S']
        B = F * (1 << (d * radix)) * N
        flat = strs.reshape(B, 2 * d * radix)
        if bp != B:
            flat = jnp.concatenate(
                [flat, jnp.zeros((bp - B, 2 * d * radix), bool)]
            )
        return flat

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per level shape)
    return jax.jit(f)


def shard_flat(ks: KernelShard, packed, d: int, F: int, N: int,
               radix: int = 1):
    """The level's flat share-bit strings, row-sharded over the kernel
    mesh.  ``packed`` may carry any sharding (the client-axis mesh
    layout of the expansion): the flat build runs where packed lives and
    the result reshards onto the kernel submesh — an all-to-all-sized
    move of the SMALL pre-kernel tensor, never a gather onto one
    device."""
    flat = _flat_fn(d, F, N, ks.bp, radix)(packed)
    return jax.device_put(flat, ks.sharding(P(DATA, None)))


@lru_cache(maxsize=None)
def _snd_extend_fn(devices: tuple, B: int, S: int):
    """Row-sharded sender extension: (seeds, s_bits, u_pad, off) ->
    Q rows uint32[bp*S, 4] sharded along rows, global-pad rows zeroed."""
    ks = KernelShard(devices, B, S)
    k, bp = ks.k, ks.bp
    m_loc = bp * S // k
    m_real = B * S

    def body(seeds, s_bits, u_loc, off):
        row0 = jax.lax.axis_index(DATA).astype(jnp.int64) * m_loc
        q = otext.sender_extend_rows(seeds, s_bits, u_loc, off, row0, m_loc)
        live = (row0 + jnp.arange(m_loc)) < m_real
        return jnp.where(live[:, None], q, jnp.uint32(0))

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (devices, shape))
    return jax.jit(
        jax.shard_map(
            body, mesh=ks.mesh,
            in_specs=(P(), P(), P(None, DATA), P()),
            out_specs=P(DATA, None),
        )
    )


@lru_cache(maxsize=None)
def _rcv_extend_fn(devices: tuple, B: int, S: int):
    """Row-sharded receiver extension: (seeds0, seeds1, flat, off) ->
    (u columns uint32[128, bp*S/32] sharded along words, T rows
    uint32[bp*S, 4] sharded along rows)."""
    ks = KernelShard(devices, B, S)
    k, bp = ks.k, ks.bp
    m_loc = bp * S // k

    def body(seeds0, seeds1, flat_loc, off):
        row0 = jax.lax.axis_index(DATA).astype(jnp.int64) * m_loc
        choices = flat_loc.reshape(m_loc)
        return otext.receiver_extend_rows(
            seeds0, seeds1, choices, off, row0, m_loc
        )

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (devices, shape))
    return jax.jit(
        jax.shard_map(
            body, mesh=ks.mesh,
            in_specs=(P(), P(), P(DATA, None), P()),
            out_specs=(P(None, DATA), P(DATA, None)),
        )
    )


def _b2a_pair_shard(field, b2a_seed, B: int, bloc: int, t0, garbler: int):
    """Shard slice [t0, t0 + bloc) of :func:`secure.b2a_payload_pair`'s
    per-level stream draw (shards are whole planar blocks, so the seek
    is block-aligned).  Returns (r1 — the sender's additive shares, w0,
    w1 payload words), with the payload words ZEROED for global-pad tests
    (the single-device twin pads them the same way)."""
    from ..protocol import secure

    r1, w0, w1 = secure.b2a_payload_pair(field, b2a_seed, bloc, garbler, t0)
    live = (t0 + jnp.arange(bloc)) < B
    return r1, jnp.where(live[:, None], w0, 0), jnp.where(live[:, None], w1, 0)


@lru_cache(maxsize=None)
def _gb_kernel_fn(devices: tuple, field_name: str, B: int, S: int, W: int,
                  path: str, garbler: int, engine: str):
    """Row-sharded sender kernel: (q, s_block, flat, gc_seed, b2a_seed,
    idx0) -> (wire planes uint32[n_planes, rows, SUB, LANES] sharded
    along rows, vals — the sender's additive shares, test-sharded)."""
    from ..protocol import secure

    field = _FIELDS[field_name]
    ks = KernelShard(devices, B, S)
    k, bp = ks.k, ks.bp
    bloc = bp // k
    n_planes = n_msg_planes(path, S, W)
    interpret = engine == "pallas_interpret"

    def body(q_loc, s_block, flat_loc, gc_seed, b2a_seed, idx0):
        t0 = jax.lax.axis_index(DATA).astype(jnp.int64) * bloc
        q_rows = q_loc.reshape(bloc, S, 4)
        idx = idx0 + t0.astype(jnp.uint64)
        r1, w0, w1 = _b2a_pair_shard(field, b2a_seed, B, bloc, t0, garbler)
        # result 1 (strings equal) -> receiver learns r0, exactly
        # secure.gb_step_level's payload order (collect.rs:439-456)
        if path == "ot2s":
            if engine == "xla":
                msg = secure._ot2s_encrypt_packed_xla(
                    q_rows, s_block, flat_loc, w1, w0, W, idx
                )
            else:
                from ..ops import otext_pallas

                msg = otext_pallas.ot2s_encrypt(
                    q_rows, s_block, flat_loc, w1, w0, W, idx,
                    domain=secure._OT2S_DOMAIN, interpret=interpret,
                )
        else:
            X0, mask = gc._carve_label_words_shard(gc_seed, B, S, t0, bloc)
            if engine == "xla":
                msg = gc._garble_packed_planes_xla(
                    s_block, q_rows, X0, mask, flat_loc, w1, w0, W, idx
                )
            else:
                msg = gc_pallas.garble_packed_planes(
                    s_block, q_rows, X0, mask, flat_loc, w1, w0, W, idx,
                    interpret=interpret,
                )
        planes = msg.reshape(n_planes, bloc // GROUP, SUB, LANES)
        return planes, r1

    # pallas_call has no shard_map replication rule — drop the rep check
    # for the Pallas engines (the XLA twins keep it; specs are identical
    # either way and the parity test pins engine equality)
    kw = {} if engine == "xla" else {"check_vma": False}
    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (devices, shape, path, engine))
    return jax.jit(
        jax.shard_map(
            body, mesh=ks.mesh,
            in_specs=(P(DATA, None), P(), P(DATA, None), P(), P(), P()),
            out_specs=(
                P(None, DATA, None, None),
                P(DATA) if field.limb_shape == () else P(DATA, None),
            ),
            **kw,
        )
    )


@lru_cache(maxsize=None)
def _ev_open_fn(devices: tuple, field_name: str, B: int, S: int, W: int,
                path: str, engine: str):
    """Row-sharded receiver open: (msg planes, t_rows, flat, idx0) ->
    field vals, test-sharded (r0 where equal, else r1; pad slots
    garbage, discarded by the share-sum scatter)."""
    from ..protocol import secure

    field = _FIELDS[field_name]
    ks = KernelShard(devices, B, S)
    k, bp = ks.k, ks.bp
    bloc = bp // k
    interpret = engine == "pallas_interpret"

    def body(msg_loc, t_loc, flat_loc, idx0):
        t0 = jax.lax.axis_index(DATA).astype(jnp.int64) * bloc
        idx = idx0 + t0.astype(jnp.uint64)
        t_rows = t_loc.reshape(bloc, S, 4)
        msg = jnp.ravel(msg_loc)
        if path == "ot2s":
            if engine == "xla":
                pay = secure._ot2s_decrypt_packed_xla(
                    t_rows, flat_loc, msg, S, W, idx
                )
            else:
                from ..ops import otext_pallas

                pay = otext_pallas.ot2s_decrypt(
                    t_rows, flat_loc, msg, W, idx,
                    domain=secure._OT2S_DOMAIN, interpret=interpret,
                )
        else:
            if engine == "xla":
                _, pay = gc._eval_equality_payload_packed_xla(
                    msg, t_rows, S, W, idx
                )
            else:
                _, pay = gc_pallas.eval_equality_payload_packed(
                    msg, t_rows, W, idx, interpret=interpret
                )
        return secure.words_to_field(field, pay)

    # see _gb_kernel_fn: pallas_call has no replication rule
    kw = {} if engine == "xla" else {"check_vma": False}
    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (devices, shape, path, engine))
    return jax.jit(
        jax.shard_map(
            body, mesh=ks.mesh,
            in_specs=(P(None, DATA, None, None), P(DATA, None),
                      P(DATA, None), P()),
            out_specs=P(DATA) if field.limb_shape == () else P(DATA, None),
            **kw,
        )
    )


@lru_cache(maxsize=None)
def _share_sums_fn(devices: tuple, field_name: str, F: int, C: int, N: int,
                   B: int, bp: int):
    """Test-sharded b2a vals -> per-(node, pattern) share sums [F, C]:
    each shard scatters its flat slice into the (F, C, N) frame (zeros
    elsewhere — the additive identity), takes the alive-gated partial
    sum, and the partials fold with the overflow-safe split-limb
    ``field_psum`` over ICI.  Exact sum mod p, same value as the
    single-device ``secure.node_share_sums`` (addition mod p is order-
    independent; the leader canonicalizes on reconstruction like the
    PR 8 client-sharded reduction)."""
    from ..protocol import secure

    field = _FIELDS[field_name]
    ks_mesh = _mesh_for(devices)
    k = len(devices)
    bloc = bp // k
    limb = field.limb_shape

    def body(vals_loc, weight):
        t0 = jax.lax.axis_index(DATA).astype(jnp.int64) * bloc
        full = jnp.zeros((bp,) + limb, vals_loc.dtype)
        full = jax.lax.dynamic_update_slice(
            full, vals_loc, (t0,) + (0,) * len(limb)
        )
        v = full[:B].reshape((F, C, N) + limb)
        part = secure.node_share_sums(field, v, weight)
        return field_psum(field, part, DATA)

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per (devices, shape))
    return jax.jit(
        jax.shard_map(
            body, mesh=ks_mesh,
            in_specs=(
                P(DATA) if limb == () else P(DATA, None),
                P(),
            ),
            out_specs=P(),
        )
    )


# ---------------------------------------------------------------------------
# Protocol-step drivers (what protocol/rpc.py and warmup call)
# ---------------------------------------------------------------------------


def _u32(x) -> jax.Array:
    return jnp.asarray(np.uint32(x & 0xFFFFFFFF))


def _u64(x) -> jax.Array:
    """A pad index base: 64 bits wide (otext.index_base)."""
    return jnp.asarray(np.uint64(x))


def put_u(ks: KernelShard, u_np) -> jax.Array:
    """The peer's wire u-matrix padded to the planar word extent and
    placed column-sharded: the host->device half of :func:`snd_extend`,
    apart so the server can time it (its ``h2d`` span)."""
    u_np = np.asarray(u_np, np.uint32)
    u_pad = np.zeros((128, ks.bp * ks.S // 32), np.uint32)
    u_pad[:, : u_np.shape[1]] = u_np
    return jax.device_put(u_pad, ks.sharding(P(None, DATA)))


def snd_extend(ks: KernelShard, snd: otext.OtExtSender, u_dev):
    """Sender half of the row-sharded extension: extend per shard over
    the peer's u-matrix (:func:`put_u` of the wire array), advance the
    session cursor exactly like a single-device ``extend``.  Returns
    (Q rows sharded [bp*S, 4], idx0 — the pre-batch pad index base)."""
    B, S = ks.B, ks.S
    idx0 = snd.consumed
    off = snd.stream_offset
    seeds, s_bits = snd.shard_state
    q = _snd_extend_fn(ks.devices, B, S)(seeds, s_bits, u_dev, _u32(off))
    snd.advance(B * S)
    return q, idx0


def rcv_extend(ks: KernelShard, rcv: otext.OtExtReceiver, flat):
    """Receiver half: per-shard column streams + choices -> (u columns
    sharded [128, bp*S/32], T rows sharded [bp*S, 4], idx0).  The wire
    u-matrix is :func:`u_wire` of the first output."""
    B, S = ks.B, ks.S
    idx0 = rcv.consumed
    off = rcv.stream_offset
    seeds0, seeds1 = rcv.shard_state
    u, t = _rcv_extend_fn(ks.devices, B, S)(seeds0, seeds1, flat, _u32(off))
    rcv.advance(B * S)
    return u, t, idx0


def gb_kernel(ks: KernelShard, s_block, q, flat, gc_seed, b2a_seed, field,
              garbler: int, path: str, idx0: int, engine: str | None = None):
    """Sender whole-level kernel per shard (the 1-of-2^S table or the
    packed garbled batch): returns (wire plane stack sharded along rows,
    vals — the sender's additive shares r1 = r0 ± 1, test-sharded)."""
    from ..protocol import secure

    W = secure.payload_words(field)
    fn = _gb_kernel_fn(
        ks.devices, field.__name__, ks.B, ks.S, W, path, int(garbler),
        engine or _engine(path),
    )
    return fn(
        q, jnp.asarray(s_block, jnp.uint32), flat,
        jnp.asarray(gc_seed, jnp.uint32), jnp.asarray(b2a_seed, jnp.uint32),
        _u64(idx0),
    )


def put_msg(ks: KernelShard, msg_np, field, path: str) -> jax.Array:
    """The wire frame as its plane stack, placed row-sharded (host
    slices land directly on their devices — no single-device staging):
    the host->device half of :func:`ev_open`, apart so the server can
    time it (its ``h2d`` span)."""
    from ..protocol import secure

    planes = np.asarray(msg_np, np.uint32).reshape(
        n_msg_planes(path, ks.S, secure.payload_words(field)),
        ks.bp // GROUP, SUB, LANES,
    )
    return jax.device_put(planes, ks.sharding(P(None, DATA, None, None)))


def ev_open(ks: KernelShard, t_rows, flat, msg_dev, field, path: str,
            idx0: int, engine: str | None = None):
    """Receiver whole-level open per shard over the wire frame
    (:func:`put_msg` of it): opens each shard's slice.  Returns vals
    test-sharded."""
    from ..protocol import secure

    W = secure.payload_words(field)
    fn = _ev_open_fn(
        ks.devices, field.__name__, ks.B, ks.S, W, path,
        engine or _engine(path),
    )
    return fn(msg_dev, t_rows, flat, _u64(idx0))


def share_sums(ks: KernelShard, field, vals, weight, F: int, C: int, N: int):
    """Alive-gated per-(node, pattern) share sums of test-sharded b2a
    vals, psum-folded over ICI — replicated [F, C] out (the caller
    fetches once)."""
    w = jax.device_put(np.ascontiguousarray(weight), ks.sharding(P()))
    return _share_sums_fn(
        ks.devices, field.__name__, F, C, N, ks.B, ks.bp
    )(vals, w)


# ---------------------------------------------------------------------------
# Per-shard readback + positional frame assembly (the PR 5 double-buffer
# pattern, one D2H stream per device instead of a gather onto one)
# ---------------------------------------------------------------------------


def start_host_copies(arr) -> int:
    """Kick off every shard's device->host DMA without blocking; returns
    the shard count (the caller's fetch accounting)."""
    shards = arr.addressable_shards
    for s in shards:
        fn = getattr(s.data, "copy_to_host_async", None)
        if fn is not None:
            try:
                fn()
            except Exception:  # fhh-lint: disable=broad-except (pure prefetch hint: the sync np.asarray below still does the whole copy)
                pass
    return len(shards)

def assemble(arr) -> np.ndarray:
    """Per-shard device->host readbacks reassembled POSITIONALLY into
    the full frame: each shard's planar-row slice lands at its own index
    span of a preallocated host buffer — the sharded twin of one
    ``np.asarray`` on a single-device array, byte-identical output."""
    out = np.empty(arr.shape, arr.dtype)
    for s in arr.addressable_shards:
        # fhh-lint: disable=chunked-device-readback,host-sync-in-hot-loop (the sharded wire readback itself: one D2H per device, started async above, assembled positionally — this IS the sanctioned fetch)
        out[s.index] = np.asarray(s.data)
    return out


def u_wire(ks: KernelShard, u) -> np.ndarray:
    """Assembled wire u-matrix: the sharded padded columns cut back to
    the real extension width ceil(B*S/32) — byte-identical to the
    single-device ``extend``'s message."""
    wu = -(-ks.B * ks.S // 32)
    start_host_copies(u)
    return np.ascontiguousarray(assemble(u)[:, :wu])


def msg_wire(ks: KernelShard, planes) -> np.ndarray:
    """Assembled wire frame: the sharded plane stack raveled to the flat
    planar buffer (plane-major, rows concatenated in shard order) —
    byte-identical to the single-device packed message."""
    start_host_copies(planes)
    return assemble(planes).reshape(-1)


# ---------------------------------------------------------------------------
# In-process both-role driver (warmup + tests; the live socket path runs
# each half on its own server)
# ---------------------------------------------------------------------------


def run_level_pair(ks: KernelShard, snd: otext.OtExtSender,
                   rcv: otext.OtExtReceiver, flat_snd, flat_rcv,
                   gc_seed, b2a_seed, field, garbler: int, path: str,
                   engine: str | None = None):
    """One sharded whole-level 2PC, both roles in-process, wire arrays
    round-tripped through host numpy exactly like the socket path (jit
    executables key on input placements — see
    secure.warm_level_kernels).  Returns (u_np, msg_np, vals_snd,
    vals_rcv) with the vals still test-sharded on device."""
    u, t_rows, idx0_r = rcv_extend(ks, rcv, flat_rcv)
    u_np = u_wire(ks, u)
    q, idx0_s = snd_extend(ks, snd, put_u(ks, u_np))
    planes, vals_s = gb_kernel(
        ks, snd.s_block, q, flat_snd, gc_seed, b2a_seed, field, garbler,
        path, idx0_s, engine=engine,
    )
    msg_np = msg_wire(ks, planes)
    vals_r = ev_open(
        ks, t_rows, flat_rcv, put_msg(ks, msg_np, field, path), field, path,
        idx0_r, engine=engine,
    )
    return u_np, msg_np, vals_s, vals_r
