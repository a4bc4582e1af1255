"""Per-server multi-chip execution: the client axis sharded over a
LOCAL 1-D device mesh.

The multi-chip shape of the two-administrative-domain deployment
(protocol/rpc.py sockets): each :class:`CollectorServer` keeps its OWN
pjit mesh over its OWN chips and shards only the client axis across
them.  The paper's crawl cost is linear in clients per level (every
frontier node evaluates every client's ibDCF state, PAPER.md §0), and
the client axis is embarrassingly parallel until the per-node count
reduction — so:

- client key planes, ibDCF eval states, and the per-level FSS expansion
  shard along the client axis with ``NamedSharding`` (the existing jit
  programs partition under GSPMD — every op here is exact integer math,
  so the sharded programs are BIT-identical to the single-device ones);
- per-node partial count / field-share sums reduce across the local
  ``data`` axis over ICI (``shard_map`` bodies reusing
  :func:`parallel.mesh.field_psum`, the overflow-safe split-limb psum)
  BEFORE anything is fetched — the wire then carries the same few-KB
  per-level payloads as a single-device server;
- the GC/OT wire stage is deliberately NOT resharded: the planar wire
  message is the single-device layout by construction, so the 2PC
  transcript (and with it the peer server and the leader) cannot tell a
  sharded server from a single-device one.

This is what turns "millions of users" from a throughput statement into
a memory-capacity statement: per-chip HBM holds ``N / k`` clients'
frontier state, and k grows with the server's chip count.

Layout note: a sharded session keeps the layout its engine wants, as a
one-device session does (``sessions.planar_layout``).  On an accelerator
that is plane-major with the fused Pallas expand, run once per shard
under ``shard_map`` (:func:`_expand_fn`: ``pallas_call`` takes no sharded
operands, so GSPMD cannot partition it, but the expansion is independent
per client); on a CPU host, and under radix > 1, it is the interleaved
``[F, N, d, 2]`` layout with the XLA expand, which partitions under GSPMD
as a plain named axis.  Either way the prune's gather and every
reduction see the client axis sharded and nothing else changed.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import prg
from ..ops.ibdcf import EvalState, IbDcfKeyBatch
from .mesh import field_psum

DATA = "data"


@lru_cache(maxsize=None)
def _mesh_for(devices: tuple) -> Mesh:
    """One Mesh object per device tuple, shared by every ServerMesh in
    the process — the reduction kernels below are module-level jit
    objects keyed on it, so two servers (or a warm run and a live run)
    over the same devices share ONE compiled program per shape instead
    of compiling per instance."""
    # fhh-lint: disable=host-sync-in-hot-loop (an array of device OBJECTS, no transfer; lru_cached: once per device tuple)
    return Mesh(np.asarray(devices), (DATA,))


# client-axis shardings of a frontier's states, by layout (see
# protocol.collect.Frontier): interleaved ``[F, N, d, 2(, 4)]`` carries the
# clients second, plane-major ``[(4,) d, 2, F, N]`` last
_INTERLEAVED = EvalState(seed=P(None, DATA), bit=P(None, DATA),
                         y_bit=P(None, DATA))
_PLANAR = EvalState(seed=P(None, None, None, None, DATA),
                    bit=P(None, None, None, DATA),
                    y_bit=P(None, None, None, DATA))


@lru_cache(maxsize=None)
def _expand_fn(devices: tuple, derived_bits: bool, want_children: bool):
    """The level expansion of a PLANAR sharded session: the fused Pallas
    engine (ops/expand_pallas.py) once per shard under ``shard_map`` —
    ``pallas_call`` takes no sharded operands, but the expansion is
    independent per client, so each chip runs the one-device kernel on
    its own clients (per-shard block shapes, the way kernel_shard.py runs
    the 2PC stage) and nothing crosses ICI.  Same values as the
    one-device program by construction: the kernel sees a shorter client
    axis, not different clients."""
    from ..ops import ibdcf
    from ..protocol import collect

    def body(keys, frontier, level):
        return collect._expand_body(
            ibdcf.level_cw(keys, level), frontier, derived_bits,
            want_children, True,
        )

    children = (
        collect.PlanarChildren(
            seed=P(None, None, None, None, None, DATA),
            flags=P(None, None, None, DATA),
        )
        if want_children else None
    )
    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per device set and engine mode)
    return jax.jit(
        jax.shard_map(
            body, mesh=_mesh_for(devices),
            in_specs=(
                IbDcfKeyBatch(*[P(DATA)] * 5),
                collect.Frontier(states=_PLANAR, alive=P()),
                P(),
            ),
            out_specs=(P(None, DATA), children),
            # pallas_call has no shard_map replication rule
            check_vma=False,
        )
    )


@lru_cache(maxsize=None)
def _counts_fn(devices: tuple):
    from ..protocol import collect

    mesh = _mesh_for(devices)

    def body(ps, pp, m, ak, an):
        return jax.lax.psum(
            collect.counts_by_pattern(ps, pp, m, ak, an), DATA
        )

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per device set)
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, DATA), P(None, DATA), P(), P(DATA), P()),
            out_specs=P(),
        )
    )


@lru_cache(maxsize=None)
def _share_sums_fn(devices: tuple, field_name: str):
    from ..ops.fields import F255, FE62
    from ..protocol import secure

    field = {"FE62": FE62, "F255": F255}[field_name]
    mesh = _mesh_for(devices)

    def body(v, w):
        return field_psum(field, secure.node_share_sums(field, v, w), DATA)

    # fhh-lint: disable=recompile-churn (lru_cached factory: built once per device set and field)
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, DATA), P(None, None, DATA)),
            out_specs=P(),
        )
    )


def resolve_data_devices(requested: int) -> int:
    """How many local devices a server's mesh should span.

    ``requested`` 0 = auto: all visible local devices on an accelerator
    host, ONE on a CPU host (virtual host-platform devices exist for the
    test/bench meshes; a production CPU server gains nothing from
    sharding its own host).  Explicit requests are capped at the visible
    device count."""
    from ..utils import effective_platform

    avail = len(jax.local_devices())
    if requested <= 0:
        return avail if effective_platform() != "cpu" else 1
    return max(1, min(int(requested), avail))


def server_devices(server_id: int, k: int) -> tuple:
    """The ``k`` local devices of server ``server_id``'s mesh — the ONE
    placement rule, read by the sessions, ``CollectorServer.engine_tags``
    and ``chip_smoke.py`` alike: the ``k`` local devices starting at
    ``server_id * k`` where those exist (two servers co-resident on one
    host take DISJOINT chips: ``[0, k)`` and ``[k, 2k)``), else the first
    ``k`` (a server alone on its host).  Decided from what the process
    can observe — no option names a device."""
    local = jax.local_devices()
    k = max(1, int(k))
    lo = int(server_id) * k
    return tuple(local[lo:lo + k] if lo + k <= len(local) else local[:k])


def survives_cache(devices: tuple) -> bool:
    """Whether the programs of a mesh over ``devices`` may come back
    from the persistent compile cache.  Read on the chip (PR 30, TPU v5
    lite 2x2, this JAX): a multi-chip program on chips [2, 3] compiled
    in the process runs; the same executable loaded from the cache by a
    later process halts the TPU at its first execution
    (``FAILED_PRECONDITION: The program continuator has halted
    unexpectedly``), sharded ``tree_init`` transposes and Pallas bodies
    alike, with server 0's twins on chips [0, 1] — and one-chip programs
    on any chip — loading fine.  So: one chip, a CPU host, or a set that
    starts at the first local chip."""
    return (
        len(devices) == 1
        or devices[0].platform != "tpu"
        or devices[0] == jax.local_devices()[0]
    )


def _largest_divisor_leq(n: int, k: int) -> int:
    """Largest divisor of ``n`` that is <= ``k`` (shard counts must tile
    the client batch exactly — shard_map and the checkpoint re-shard
    path both require it)."""
    k = max(1, min(k, n)) if n > 0 else 1
    while n % k:
        k -= 1
    return k


class ServerMesh:
    """One collector server's local data-parallel mesh.

    Construct with the device budget (:func:`resolve_data_devices`),
    then :meth:`bind` to a client batch — the ACTIVE shard count is the
    largest divisor of the batch that fits the budget, so a prime-sized
    batch degrades to fewer shards instead of failing.  All placement
    helpers and the shard_map reduction kernels hang off the bound mesh;
    re-binding (a new collection's batch) rebuilds them.
    """

    def __init__(self, n_devices: int, server_id: int = 0):
        self.devices = server_devices(server_id, n_devices)
        self.n_devices = len(self.devices)
        self.shards = 1
        self.n_clients: int | None = None
        self.mesh: Mesh | None = None

    def bind(self, n_clients: int) -> "ServerMesh":
        """Fix the active mesh for an ``n_clients`` batch."""
        k = _largest_divisor_leq(n_clients, self.n_devices)
        self.mesh = _mesh_for(self.devices[:k])
        self.shards = k
        self.n_clients = int(n_clients)
        return self

    def occupancy(self) -> list:
        """Clients per shard (uniform by construction: the shard count
        divides the batch)."""
        if self.n_clients is None:
            return []
        return [self.n_clients // self.shards] * self.shards

    # -- placement --------------------------------------------------------

    def put(self, arr, spec: P):
        """``arr`` (host numpy or a device array) onto the bound mesh.
        Host arrays go straight to the mesh's devices: a detour through
        ``jnp.asarray`` would land them on the process's default device
        first — another server's chip once co-resident servers take
        disjoint ones."""
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def home(self):
        """Context for SYNCHRONOUS sections (never across an ``await``:
        the setting is the thread's) whose jitted helpers create arrays
        from nothing (``jnp.zeros``, constants): those land on this
        mesh's first device instead of the process default."""
        return jax.default_device(self.devices[0])

    def shard_keys(self, keys):
        """Key batch leaves ``[N, ...]`` -> client axis sharded."""
        return jax.tree.map(lambda a: self.put(a, P(DATA)), keys)

    def shard_frontier(self, frontier, planar: bool):
        """Frontier states -> client axis sharded (the second axis of
        the interleaved layout, the last of the plane-major one —
        ``planar`` names the layout, the shapes cannot), alive mask
        replicated.  Used at tree_init and checkpoint restore; mid-crawl
        the sharding propagates through the jitted expand and advance
        programs on its own."""
        specs = _PLANAR if planar else _INTERLEAVED
        return frontier._replace(
            states=EvalState(*map(self.put, frontier.states, specs)),
            alive=self.put(frontier.alive, P()),
        )

    def expand_share_bits(self, keys, frontier, level,
                          want_children: bool = True):
        """``collect.expand_share_bits`` of a PLANAR sharded session:
        the Pallas engine per shard (:func:`_expand_fn`).  ``level`` goes
        in as a host scalar, so one compiled program serves every level
        and nothing is placed on the process's default device."""
        return _expand_fn(
            self._active_devices(), prg.DERIVED_BITS, bool(want_children)
        )(keys, frontier, np.int32(level))

    def gather(self, arr):
        """Collapse a client-axis-sharded array back onto ONE device
        (the mesh's first).  Since PR 10 this is only the kernel stage's
        DEGRADED path: a level whose planar batch yields a single kernel
        shard (``kernel_bind`` -> None) still gathers the packed share
        bits over ICI before string extraction; any level with >= 2
        whole planar blocks runs the row-sharded kernel stage
        (parallel/kernel_shard.py) and never touches this.  A pure
        layout move: values are untouched, bit-identity holds by
        construction."""
        return jax.device_put(arr, self.devices[0])

    def kernel_budget(self, requested: int) -> int:
        """Device budget for the secure kernel stage
        (``Config.secure_kernel_shards``): 0 = auto follows the bound
        data shards; explicit requests cap at them (the kernel mesh is a
        leading submesh of the data mesh)."""
        if requested <= 0:
            return self.shards
        return max(1, min(int(requested), self.shards))

    def kernel_bind(self, B: int, S: int, requested: int):
        """Bind the row-sharded kernel stage for a ``B``-test level
        (parallel/kernel_shard.KernelShard), or None when the batch only
        fills one planar block per the budget — the caller then keeps
        the :meth:`gather` path.  Pure (lru-cached mesh machinery
        underneath): safe from the unlocked frame-arrival pre-expand."""
        from . import kernel_shard

        return kernel_shard.bind(
            self._active_devices(), B, S, self.kernel_budget(requested)
        )

    # -- ICI reductions (the pre-wire psum hooks) -------------------------

    def _active_devices(self) -> tuple:
        return self.devices[: self.shards]

    def counts_by_pattern(self, packed_self, packed_peer, masks,
                          alive_keys, alive_nodes):
        """Trusted-mode per-node counts with the client-axis sum taken
        PER SHARD and psum-reduced over ICI — the [F, 2^d] result is
        replicated, so the wire/leader sees the single-device value
        (uint32 sums are exact; order is irrelevant).

        Inputs are eagerly placed to their canonical shardings first:
        the executable cache keys on input shardings, and the producers
        vary (jit outputs, wire numpy, warmup twins) — normalizing here
        pins ONE compiled program per shape for warm and live alike."""
        return _counts_fn(self._active_devices())(
            self.put(packed_self, P(None, DATA)),
            self.put(packed_peer, P(None, DATA)),
            self.put(masks, P()),
            self.put(alive_keys, P(DATA)),
            self.put(alive_nodes, P()),
        )

    def node_share_sums(self, field, vals, weight):
        """Secure-mode per-(node, pattern) share sums: per-shard
        ``field.sum`` partials folded with the overflow-safe split-limb
        :func:`parallel.mesh.field_psum` over the local ``data`` axis.
        Bit-identical to the single-device ``secure.node_share_sums``
        (both are the exact sum mod p in canonical form).  Inputs are
        canonically placed first (see :meth:`counts_by_pattern`)."""
        return _share_sums_fn(self._active_devices(), field.__name__)(
            self.put(vals, P(None, None, DATA)),
            self.put(weight, P(None, None, DATA)),
        )
