"""Control plane: leader↔server RPC + server↔server data-plane socket.

Process topology mirrors the reference (SURVEY.md §2 "distributed
communication backend"):

- **Control plane** — leader connects to both servers and drives the 8-verb
  protocol of the reference's tarpc ``Collector`` service (ref: rpc.rs:56-66):
  ``reset, add_keys, tree_init, tree_crawl, tree_crawl_last, tree_prune,
  tree_prune_last, final_shares``.  Transport: length-prefixed frames over
  TCP via asyncio (the tarpc+bincode analogue).  A frame is ``<Q n>`` and
  a body of ``n`` bytes: ``<I k>``, ``k + 1`` lengths (``<Q``), the
  pickled metadata (protocol 5) and ``k`` raw buffers.  Array payloads of
  :data:`~.wire.OOB_MIN` bytes or more are never serialised: they cross
  as their own memory, copied once into the socket and once out of it,
  into the buffer the received array owns (protocol/wire.py has the
  layout and the endpoints).  A peer that still speaks the bare-pickle
  body is refused by the first frame (its lengths cannot sum: a
  ``FrameError`` here, an ``UnpicklingError`` there).
- **Data plane** — the server↔server exchange of a level's tensors
  (server1 listens on ``port+1``, server0 dials with retries — the
  reference's GC-mesh bootstrap order, server.rs:197-262, collapsed from
  ``num_cpus`` sockets because the exchange is batched tensors, not
  per-thread GC traffic): two one-way TCP streams, one a direction, each
  written by a thread at its sender and read by a thread at its receiver
  (:class:`~.wire.PlaneStreams`), so that the loop thread, which
  dispatches every kernel, copies none of the plane's bytes.  Server 0
  dials the port twice; the first frame on each connection (the hello)
  says which direction it carries.

Counts come back as **field-element shares**: both servers derive a common
pseudorandom mask stream from a shared seed — the reference hardcodes the
same PRG seed on both servers ("XXX This is bogus", server.rs:331-332) — so
server0 returns ``count + r`` and server1 returns ``r``, and the leader
reconstructs ``v0 - v1`` exactly as ``keep_values`` does
(ref: collect.rs:945-989).  Inner levels use FE62, the last level F255
(ref: rpc.rs:60-62 FE vs FieldElm).

Divergence, by design: the reference's ``tree_prune`` carries an alive list
and servers rebuild child nodes eagerly; here prune and child
materialization are fused — the leader sends (parent_idx, pattern, n_alive)
and the server advances only the survivors (see protocol/collect.py's
memory plan).

Fault tolerance (the resilience layer, this repo's addition — the
reference restarts the whole run on any socket error):

- the leader-side :class:`CollectorClient` RECONNECTS: on transport loss
  it redials under the shared backoff policy
  (``resilience.policy.DIAL_POLICY``), bumps its session *epoch*, and
  replays the calls whose responses never arrived;
- the server answers replays IDEMPOTENTLY: each leader session keeps a
  bounded ``(req_id) → response`` dedup cache plus an in-flight table, so
  a stateful verb (``tree_prune``, ``add_keys``) that already ran is
  answered from cache instead of double-applied;
- ``tree_checkpoint``/``tree_restore`` verbs persist/reload the server's
  crawl state at leader-chosen level boundaries, and ``plane_reset``
  re-establishes the server↔server data plane (redial + fresh
  ``_plane_handshake``) after a peer loss — together they let
  ``RpcLeader.run_supervised`` re-run only the lost levels.

Streaming ingestion (the online front door, ROADMAP "Streaming
ingestion"): instead of one bulk ``add_keys`` upload, clients submit key
chunks continuously via ``submit_keys`` into per-window append-only
pools, gated by resilience/admission.py (token-bucket rate limits,
per-client quotas, bounded pools, reject-vs-reservoir shedding);
``window_seal`` freezes a window at its boundary and ``window_load``
materializes the frozen snapshot as the crawl's key batch — the normal
level loop then runs on it while ingest keeps landing in later windows
(``submit_keys`` bypasses the verb lock like ``add_keys``).  Pools ride
``tree_checkpoint``/``tree_restore`` (entry slots, per-``sub_id``
verdicts, reservoir RNG state), so a kill mid-window neither loses nor
double-counts admitted keys.

Multi-tenant collection sessions (ROADMAP "Multi-host, multi-tenant
collector fleet", items b/c): every piece of per-collection state above
lives in a keyed :class:`~.sessions.CollectionSession` selected on the
wire by the ``collection`` field of the existing ``__hello__``
handshake — one server pair serves N independent collections at once,
each with its own frontier, sketch ratchet, expand cache, ingest gate
(token bucket + quotas + pools), replay-dedup namespace, checkpoint
namespace, OT endpoints, and verb lock.  The server↔server data plane
demultiplexes into per-collection channels (:class:`~.sessions.PlaneMux`
— frames are ``(collection, payload)``), so two tenants' 2PC exchanges
interleave on one plane without desynchronizing; per-session base-OT /
coin-flip handshakes run lazily over each session's channel.  Device
work interleaves across sessions through the
:class:`~.tenancy.TenantScheduler`: while tenant A's span waits on the
GC/OT wire, tenant B's expand stage takes a device turn (the
``pipeline_stalls`` gap a second tenant fills — counted as
``tenant_stall_fills``), and warmup's compiled-program ladder is shared
process-wide (:mod:`~.tenancy` WarmLadder) so a new collection on a
warmed shape pays zero fresh compiles.  A connection that never names a
collection works on the DEFAULT session; every single-tenant flow is
unchanged, including checkpoint file names.
"""

from __future__ import annotations

import asyncio
import collections as _collections
import concurrent.futures
import contextlib
import functools
import os
import pickle
import secrets as _secrets
import time
import typing
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..obs import alerts as obsalerts
from ..obs import devmem as obsdevmem
from ..obs import exporter as obsexporter
from ..obs import metrics as obsmetrics
from ..obs import trace as obstrace
from ..ops import baseot, dpf, otext
from ..ops.fields import F255, FE62
from ..ops.ibdcf import EvalState, IbDcfKeyBatch
from ..parallel import kernel_shard, server_mesh as smesh, sketch_shard
from ..resilience import chaos as reschaos
from ..resilience import policy as respolicy
from ..utils import compile_cache, guards, taint_guard
from ..utils.config import Config
from . import collect, mpc, secure, sessions, sketch as sketchmod, tenancy, wire
from .sessions import (  # noqa: F401  (re-exports: wire-format helpers kept importable as rpc.*)
    DEFAULT_COLLECTION,
    SHARED_MASK_SEED,
    CollectionSession,
    SessionTable,
    _SKETCH_TREEDEF,
    mask_f255,
    mask_fe62,
)

_HDR = wire.HDR
# the first frame of each data-plane connection, in words: hello,
# direction, dial token (``wire.hello_frame``).  The dialer (server 0)
# opens both streams and names them by the direction their bulk flows;
# the listener answers hello, "ok", token.  The digit is the plane
# protocol's generation: a peer that speaks another is refused
_PLANE_HELLO = b"fhh-plane/2"
_PLANE_DIRECTIONS = (b"0to1", b"1to0")


_NO_CTX = contextlib.nullcontext()


def _no_span(_name):
    return _NO_CTX


async def _send(writer: wire.FrameWriter, obj, reg=None, counter=None,
                flush: bool = True) -> None:
    """``reg``, when given, is the registry of the side doing the work:
    it times ``wire_pickle`` (building the frame's header and pickled
    metadata; arrays of ``wire.OOB_MIN`` bytes or more are not pickled
    but referenced) and ``wire_write`` (the pieces handed to the
    transport until ``drain()`` has returned; the hand-over alone on an
    unflushed frame), adds the framed byte size to its counter
    ``counter`` — the data-plane accounting hook — and the bytes that
    crossed as raw buffers to ``wire_oob_bytes``.  The transport's
    write queue holds views of ``obj``'s arrays until the kernel has
    them, which is when ``drain()`` returns; ``obj`` must stay
    unmodified until then (see :func:`wire.encode`: the caller of an
    unflushed send holds ``obj`` until its coalesced flush).
    ``flush=False`` skips the ``drain()`` backpressure wait: asyncio
    delivers the queued bytes regardless, so a burst of consecutive
    frames can coalesce into ONE drain on its final frame instead of
    one await per frame — but some frame in every burst MUST flush, or
    a dead peer lets the queue grow without bound."""
    pieces = _encode(obj, reg, counter)
    with (_no_span if reg is None else reg.span)("wire_write"):
        writer.writelines(pieces)
        if flush:
            await writer.drain()


def _encode(obj, reg=None, counter=None) -> list:
    """``obj`` as the pieces of one frame (``wire.encode``), timed and
    counted on ``reg`` as :func:`_send` says: the head that both planes'
    sends share."""
    with (_no_span if reg is None else reg.span)("wire_pickle"):
        pieces, nbytes, oob = wire.encode(obj)
    if counter is not None:
        reg.count(counter, nbytes)
        if oob:
            reg.count("wire_oob_bytes", oob)
            # the span log carries no counters: under fhh-trace the
            # engagement is an instant beside the frame's wire_write
            obstrace.instant("wire_oob", comp=reg.name, oob=oob, framed=nbytes)
    return pieces


async def _recv(reader: wire.FrameReader, reg=None, counter=None):
    """Frame reads are DELIBERATELY unbounded: serve/reader loops wait
    indefinitely for the next frame by design — response waits are
    bounded at the caller (per-verb ``Deadline`` on the pending future)
    and the data plane by TCP keepalive, not by a read timeout here.

    ``reg`` times ``wire_read`` (header read -> the metadata and the
    last out-of-band buffer are held: a serve loop waits for its next
    request without a span) and ``wire_unpickle`` (``pickle.loads``
    over the buffers, which the arrays then own: no copy), and counts
    the framed bytes under ``counter``."""
    span = _no_span if reg is None else reg.span
    # fhh-lint: disable=unbounded-await (see docstring)
    hdr = await reader.readexactly(_HDR.size)
    (n,) = _HDR.unpack(hdr)
    if counter is not None:
        reg.count(counter, n + _HDR.size)
    with span("wire_read"):
        # fhh-lint: disable=unbounded-await (see docstring)
        meta, bufs = await wire.read_body(reader, n, reg)
    with span("wire_unpickle"):
        return pickle.loads(meta, buffers=bufs)


async def _fetch(
    x, reg: obsmetrics.Registry, level: int | None = None, waits=None
) -> np.ndarray:
    """Device->host fetch OFF the event loop.  A bare ``np.asarray`` on a
    device array blocks the whole loop for a full device->host transfer
    — serializing the two servers' fetches when
    they share a process (the in-process bench/tests) and starving
    keepalives/concurrent verbs in any deployment.  np.asarray of distinct
    arrays is thread-safe in JAX; the GIL releases during the copy.

    ``reg`` counts the fetch: each one is a synchronous device->host
    round trip, so the fetch COUNT is a latency term of its own beside
    the byte count, and the run report carries both.  It also times it
    as a ``d2h`` span: ``copy_to_host_async`` issued -> the numpy array
    is held, so the wait for the device program that makes ``x`` and the
    thread hop are inside.  Three timers (no span-log records) say how
    the span splits: ``d2h_ready`` (on the thread, its first line ->
    ``block_until_ready`` returned: the program that makes ``x`` and the
    device queue ahead of it), ``d2h_copy`` (``np.asarray`` after it:
    the copy alone) and ``d2h_hop`` (the rest of the span: the hand-over
    to the thread and the finished thread's wait for the loop).
    ``level`` attributes the fetch when the call
    site sits outside any span (span-active callers inherit).  ``waits``
    is the calling server's :class:`_DeviceWaits`: the thread call parks
    in ``block_until_ready`` on a thread of that server's own; without
    one (a caller that is no server) asyncio's default executor runs it."""
    reg.count("device_fetches", level=level)
    call = asyncio.to_thread if waits is None else waits.call
    with reg.span("d2h", level=level) as sp:
        _start_host_copy(x)
        out, ready, copy, _ = await call(_fetch_on_thread, x)
    reg.timer_add("d2h_ready", ready, sp.level)
    reg.timer_add("d2h_copy", copy, sp.level)
    reg.timer_add("d2h_hop", sp.seconds - ready - copy, sp.level)
    return out


def _fetch_on_thread(x, waits=(), note=None) -> tuple:
    """(the numpy array, seconds until ``x`` was ready, seconds of the
    copy, wall-clock stamps): the fetch thread stamps its own clock as
    its last act and the loop does the subtraction.  ``waits`` are the
    outputs of device programs nobody has waited for, in the order they
    were handed to the device, each with the names of its span
    (``CollectorServer._fetch_behind``): this thread waits for each in
    turn and stamps when it was ready, so the stamps are its first
    line, one a wait, and the array held.  ``note(name)`` is the
    profiler annotation of a wait under fhh-trace."""
    stamps = [time.time()]
    for names, arr in waits:
        with contextlib.ExitStack() as notes:
            for name in names:
                notes.enter_context(note(name))
            jax.block_until_ready(arr)
        stamps.append(time.time())
    ready = _sync_on_thread(x)
    t0 = time.perf_counter()
    with note("d2h") if waits else _NO_CTX:
        out = np.asarray(x)
    return out, ready, time.perf_counter() - t0, stamps + [time.time()]


def _sync_on_thread(x) -> float:
    """Seconds this thread waited for ``x`` (``_phase_sync``, ``_fetch``)."""
    t0 = time.perf_counter()
    jax.block_until_ready(x)
    return time.perf_counter() - t0


def _start_host_copy(x) -> None:
    """Kick off the device->host DMA for ``x`` without blocking (the
    ``copy_to_host_async`` half of a double-buffered fetch): the copy
    proceeds while the caller does other work — another span's expand
    dispatch, a peer exchange — and the later ``np.asarray`` completes
    against an already-landed (or in-flight) buffer instead of starting
    the transfer then.  Best-effort: plain numpy inputs and JAX builds
    without the method fall through to the synchronous copy."""
    fn = getattr(x, "copy_to_host_async", None)
    if fn is None:
        return
    try:
        fn()
    except Exception:  # fhh-lint: disable=broad-except (pure prefetch hint: any failure means the sync np.asarray path simply does the whole copy)
        pass


class _DeviceWaits:
    """The threads on which ONE server's calls park in
    ``jax.block_until_ready`` (``_fetch_on_thread``, ``_sync_on_thread``):
    an executor of the server's own, so that a wait for the device
    never queues behind the process's other thread calls (asyncio's
    default executor, which keeps everything else and is a few workers
    on a small host), nor they behind it.  ``workers`` is what one
    level can have in flight (``CollectorServer.DEVICE_WAITS``); a call
    beyond that waits for a worker, and every parked call ends by
    itself, so none waits on another.  ``now`` / ``high`` count the
    calls handed over and not yet done, on the loop thread alone (the
    gauge ``device_waits_high``)."""

    def __init__(self, name: str, workers: int):
        self.workers = workers
        self._pool = concurrent.futures.ThreadPoolExecutor(
            workers, thread_name_prefix=f"{name}-device-wait"
        )
        self.now = self.high = 0

    def call(self, fn, *args) -> asyncio.Future:
        """``fn(*args)`` on one of the threads, begun now; the future is
        the running loop's."""
        fut = asyncio.get_running_loop().run_in_executor(
            self._pool, fn, *args
        )
        self.now += 1
        self.high = max(self.high, self.now)
        fut.add_done_callback(self._done)
        return fut

    def _done(self, _fut) -> None:
        self.now -= 1

    def take_high(self) -> int:
        """The most calls in flight since the last take."""
        high, self.high = self.high, self.now
        return high

    def close(self) -> None:
        """Calls not begun are cancelled; a parked one ends by itself."""
        self._pool.shutdown(wait=False, cancel_futures=True)


# -- the wait account of the secure level's chunk pipeline -------------------
#
# A secure level runs seven stages as tasks (``_ev_chunks`` /
# ``_gb_chunks`` / ``_chunk_senders``).  Each times what it waited for:
# input from the stage before it (``stage_starved:<stage>``), room in
# the stage after it (``stage_blocked:<stage>``), and its own life
# (``stage_wall:<stage>``, once a level).  A stage's busy seconds in a
# level are its wall less its two waits; the stage with the least of
# both waits sets the level's pace.  Timers alone (``Registry.timer_add``
# under the level): no span-log record, no profiler annotation.
#
# What fills a stage's busy seconds: a stage that dispatches (``build``,
# ``extend``) its ``h2d`` and its dispatches, ``open`` its leaf spans, a
# fetch stage what of the chunk's thread call no chunk ahead hid.  A
# send stage (``u_send``, ``msg_send``) keeps ``FRAMES_OUT`` frames with
# the plane's writer thread, so its leaf spans overlap (frame k+1's
# ``wire_queue`` runs beside frame k's ``wire_write``) and no longer sum
# to its busy seconds.  What holds instead is the thread's own account
# of the stage's frames: ``wire_write`` (the socket's seconds) plus
# ``stream_gap`` (the thread's waits between one frame's end and the
# next's start, by its own stamps) is the thread's life with them, and
# that life lies inside the stage's:
#
#   wire_write + stream_gap - starved - blocked
#     <= busy <=
#   wire_pickle + wire_write + stream_gap
#     + the first frame's wire_queue + the last frame's send_resume
#
# (``wire_write`` alone is no lower bound: a stage that starves while a
# frame is written is not busy then.  Where the stage sets the pace it
# hardly starves, and busy is ``wire_write`` + ``stream_gap`` and little
# else; at K = 1 both sides meet in the old sum, pickle + queue + write
# + resume.)  The counter ``plane_sends_overlapped`` says how many
# frames were handed over while the thread still held another.

# The stages: the evaluator's ``extend``, ``u_fetch``, ``u_send`` and
# ``open``, the garbler's ``build``, ``msg_fetch`` and ``msg_send``.

STAGE_TIMERS = ("stage_starved", "stage_blocked", "stage_wall")


async def _timed(reg: obsmetrics.Registry, name: str, level, aw):
    """``await aw``, its seconds added to timer ``name`` of ``reg``
    under ``level`` (a wait that is cancelled or fails counts too)."""
    t0 = time.perf_counter()
    try:
        return await aw
    finally:
        reg.timer_add(name, time.perf_counter() - t0, level)


class _Stage:
    """One stage of a level's chunk pipeline: ``with`` it around the
    task's body (``stage_wall``), ``await st.starved(q.get())`` where it
    waits for input and ``await st.blocked(q.put(x))`` where it waits
    for room.  All three timers exist from the first line on, so a stage
    that never waited reads 0 and not absent."""

    __slots__ = ("_reg", "_level", "_names", "_t0")

    def __init__(self, reg: obsmetrics.Registry, stage: str, level):
        self._reg, self._level = reg, level
        self._names = tuple(f"{t}:{stage}" for t in STAGE_TIMERS)

    def starved(self, aw):
        return _timed(self._reg, self._names[0], self._level, aw)

    def blocked(self, aw):
        return _timed(self._reg, self._names[1], self._level, aw)

    def __enter__(self) -> "_Stage":
        for name in self._names[:2]:
            self._reg.timer_add(name, 0.0, self._level)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._reg.timer_add(
            self._names[2], time.perf_counter() - self._t0, self._level
        )


class _FrameOut(typing.NamedTuple):
    """A data-plane frame with the writer thread, between
    ``_dp_send_begin`` and ``_dp_send_finish``: whose registry and level
    its spans go to, and what ``wire.PlaneStreams.hand_over`` gave."""

    reg: obsmetrics.Registry
    level: "int | None"
    tracing: bool
    done: asyncio.Future  # of (t_begin, t_end, t_seen)
    t_put: float
    held: int


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------

# dedup bounds: the cache must cover every req_id a client could still
# replay — at most its in-flight window (the key-upload window of 256 in
# leader_rpc.upload_keys is the largest) plus slack — and is bounded by
# BYTES as well as count: verb responses carry share arrays (tree_crawl,
# final_shares) that can each be MBs at production scale, and a
# count-only bound would pin ~1024 of them.  Sessions are bounded too, so
# a leader that reconnects under fresh session ids can't grow server
# memory without bound.
_SESSION_CACHE_CAP = 1024
_SESSION_CACHE_BYTES = 128 << 20
_SESSION_CAP = 8


def _resp_nbytes(resp) -> int:
    """Approximate retained size of a cached response (array payloads
    dominate; containers/scalars get a flat floor)."""
    if isinstance(resp, np.ndarray):
        return resp.nbytes + 64
    if isinstance(resp, dict):
        return 64 + sum(_resp_nbytes(v) for v in resp.values())
    if isinstance(resp, (list, tuple)):
        return 64 + sum(_resp_nbytes(v) for v in resp)
    return 64


class _Session:
    """One leader session's idempotent-replay state: responses already
    sent (``cache``) and verbs still executing (``inflight``).  A replay
    of a cached req_id is answered from the cache; a replay of an
    in-flight req_id awaits the SAME execution — the verb never runs
    twice either way."""

    __slots__ = (
        "epoch", "cache", "sizes", "bytes_total", "inflight", "last_seen",
        "collection",
    )

    def __init__(self):
        self.epoch = 0
        self.collection = DEFAULT_COLLECTION  # bound at __hello__
        self.cache: _collections.OrderedDict = _collections.OrderedDict()
        self.sizes: dict[int, int] = {}
        self.bytes_total = 0
        self.inflight: dict[int, asyncio.Future] = {}
        self.last_seen = time.monotonic()

    def put(self, req_id, resp) -> None:
        """Cache a response under the count AND byte bounds.  The newest
        entry always survives even when it alone exceeds the byte cap —
        replay correctness of the in-flight call beats the bound."""
        nb = _resp_nbytes(resp)
        self.cache[req_id] = resp
        self.sizes[req_id] = nb
        self.bytes_total += nb
        while len(self.cache) > 1 and (
            len(self.cache) > _SESSION_CACHE_CAP
            or self.bytes_total > _SESSION_CACHE_BYTES
        ):
            old, _ = self.cache.popitem(last=False)
            self.bytes_total -= self.sizes.pop(old, 0)


# Runtime twin of the fhh-race guard map — the "CollectorServer.*"
# entries of pyproject [tool.fhh-lint.guards] (server-INFRA state; the
# per-collection state moved to sessions.CollectionSession and its
# _SESSION_GUARDS twin).  Drift-tested in tests/test_concurrency.py.
_SERVER_GUARDS = {
    "_sessions": "_verb_lock",
}


def _session_metrics_producer(ref):
    """A /metrics producer bound to a CollectorServer by weakref: per
    scrape, publish live session rows as labeled gauges and run the
    session alert rules over the same snapshot.  Returns ``None`` once
    the server is gone so the exporter prunes it."""

    def produce():
        srv = ref()
        if srv is None:
            return None
        try:
            sess = srv._sessions_status()
        # fhh-lint: disable=broad-except (scrape-thread probe racing the
        # event loop: a torn dict iteration skips one frame, never 500s)
        except Exception:
            return []
        obsalerts.evaluate_sessions(
            sess["per_session"], source=f"server{srv.server_id}"
        )
        reg = f'registry="server{srv.server_id}"'
        lines = [
            "# TYPE fhh_session_last_progress_seconds gauge",
            "# TYPE fhh_session_queue_depth_keys gauge",
            "# TYPE fhh_session_dedup_entries gauge",
        ]
        for key, row in sorted(sess["per_session"].items()):
            lbl = f'{{{reg},collection="{obsexporter._esc(key)}"}}'
            lines.append(
                f"fhh_session_last_progress_seconds{lbl}"
                f" {row['last_progress_s']}"
            )
            lines.append(
                f"fhh_session_queue_depth_keys{lbl} {row['queue_depth']}"
            )
            lines.append(
                f"fhh_session_dedup_entries{lbl} {row['dedup_entries']}"
            )
        return lines

    return produce


def engine_tags() -> dict:
    """Which engine each stage resolves to in THIS process, as the
    selectors stand right now (they follow the effective platform: the
    Pallas kernels on an accelerator, the NumPy/XLA twins on a CPU
    host).  What a given server runs can be narrower — radix > 1 fusion
    pins the XLA expand — so a server's log line and chip_smoke.py's
    phase lines carry :meth:`CollectorServer.engine_tags`; chip_smoke.py
    asserts this process-wide set before it starts anything."""
    from ..ops import ibdcf
    from ..utils import effective_platform

    return {
        "platform": effective_platform(),
        "keygen": ibdcf.best_engine(),
        "expand": "pallas" if collect._expand_engine() else "xla",
        "ot2s": "pallas" if secure._ot2s_pallas_engine() else "xla",
        "gc": kernel_shard._engine("gc"),
    }


class CollectorServer:
    """One collector server process (ref: server.rs:44-172).

    ``server_id`` 0 dials the peer, 1 listens (ref: server.rs:208-233).

    Multi-tenant: the server itself holds only SHARED infrastructure —
    the control listeners, the peer data plane (demultiplexed per
    collection by :class:`~.sessions.PlaneMux`), the replay-dedup
    session table, the tenant scheduler, and the boot id.  Everything a
    single collection owns lives in its
    :class:`~.sessions.CollectionSession` (``self._table``), resolved
    per connection from the ``__hello__`` handshake; the attribute
    properties below delegate to the DEFAULT session so single-tenant
    callers (and the existing tests) see the exact pre-session surface.
    """

    def __init__(self, server_id: int, cfg: Config, *,
                 obs: obsmetrics.Registry | None = None,
                 ckpt_dir: str | None = None,
                 _mesh_chaos: object | None = None):
        self.server_id = server_id
        self.cfg = cfg
        self.ckpt_dir = ckpt_dir
        # decided here, where a process takes a server on, and not by a
        # mesh: JAX's cache switch is the process's.  The second server
        # of a co-resident sharded pair runs multi-chip programs off the
        # first local chip, and those do not survive the persistent
        # cache (smesh.survives_cache), so its process compiles anew
        n = smesh.resolve_data_devices(cfg.server_data_devices)
        if n > 1 and not smesh.survives_cache(
            smesh.server_devices(server_id, n)
        ):
            compile_cache.suspend(
                f"server {server_id}: multi-chip programs on devices "
                f"{[d.id for d in smesh.server_devices(server_id, n)]} do "
                f"not survive the persistent cache"
            )
        # telemetry: ONE registry per server for shared-plane accounting
        # (control bytes, replay dedup, plane resets, tenant scheduler);
        # the DEFAULT collection session shares it — so single-tenant
        # runs report exactly as before — and every other session gets
        # its own "server{id}:{collection}" registry (the heartbeat then
        # names the active (session, phase) pair).
        self.obs = obs if obs is not None else obsmetrics.Registry(
            f"server{self.server_id}"
        )
        # per-collection sessions, keyed on the wire (__hello__)
        self._table = SessionTable(server_id, cfg, self.obs, ckpt_dir)
        # device-work interleaving across sessions + stall-fill telemetry
        self._sched = tenancy.TenantScheduler(self.obs)
        # peer data plane: two one-way streams with a thread each
        # (wire.PlaneStreams), demuxed per collection; sends are
        # (collection, payload) frames, the mux routes receives
        self._peer: wire.PlaneStreams | None = None
        self._plane = sessions.PlaneMux(
            route_count=self._plane_count, tag=f"server{server_id}"
        )
        self._peer_addr: tuple | None = None
        # the threads this server's waits for the device park on
        self._waits = _DeviceWaits(f"server{server_id}", self.DEVICE_WAITS)
        # the listener's half-open plane: (token, {direction: socket})
        # of the dial whose second connection has not said hello yet
        self._plane_half: tuple | None = None
        # resilience state: boot id (reconnect vs restart), per-leader-
        # session replay dedup, control writers for aclose
        self._boot_id: str = _secrets.token_hex(8)
        self._sessions: dict = {}
        self._ctl_writers: set = set()
        # injected device-loss schedule (resilience.chaos.MeshChaos —
        # tests and bin/server wire FHH_MESH_FAULTS here); fires against
        # whichever session's crawl reaches the scheduled level first
        self._mesh_chaos = _mesh_chaos
        # server-infra lock: guards the replay-session table (and
        # serializes plane_reset); per-collection verbs serialize on
        # their session's OWN _verb_lock instead
        self._verb_lock = asyncio.Lock()
        # live /metrics plane (obs.exporter): when the exporter is up,
        # this server publishes its session rows per scrape and lets the
        # alert engine evaluate them.  Weakref producer: a dropped server
        # (tests construct hundreds) returns None and is pruned instead
        # of pinning its registries forever.  Gated on running() so the
        # disabled path registers nothing at all.
        if obsexporter.running():
            obsexporter.add_producer(_session_metrics_producer(weakref.ref(self)))
        # LAST: the sanitizer (a no-op unless FHH_DEBUG_GUARDS=1 or
        # cfg.debug_guards) wraps the already-constructed guarded state
        guards.install(self, _SERVER_GUARDS, force=self.cfg.debug_guards)

    # -- default-session delegation (single-tenant compat surface) --------
    #
    # The pre-session CollectorServer exposed its per-collection state as
    # plain attributes; tests, chaos harnesses, and operator tooling
    # read (and occasionally write) them.  Each property below is a
    # straight view onto the DEFAULT session's attribute.

    def _default(self) -> CollectionSession:
        return self._table.default()

    def engine_tags(self) -> dict:
        """What THIS server's collections run — :func:`engine_tags`
        narrowed by the session layout its config resolves to (the rules
        of ``CollectionSession.__init__``/``planar``, read without
        creating a session): the expand engine is the Pallas one only
        for a planar session (radix 1; a sharded server runs it once
        per shard, on the chips ``mesh_devices`` names), a sharded server's
        2PC kernels are ``kernel_shard``'s per-shard engines, and a
        secure server names the equality path its config picks.  Emitted
        once at start; ``keygen`` is left out (the leader's)."""
        n = smesh.resolve_data_devices(self.cfg.server_data_devices)
        radix = int(self.cfg.crawl_radix_bits)
        tags = {k: v for k, v in engine_tags().items() if k != "keygen"}
        tags["data_devices"] = n
        if n > 1:
            # which chips: the sessions' own rule (ServerMesh.__init__)
            tags["mesh_devices"] = [
                d.id for d in smesh.server_devices(self.server_id, n)
            ]
        tags["expand"] = "pallas" if sessions.planar_layout(radix) else "xla"
        if n > 1:
            tags["ot2s"] = kernel_shard._engine("ot2s")
            # the budget once keys are bound (ServerMesh.kernel_budget);
            # a level that fills fewer planar blocks runs on fewer
            req = int(self.cfg.secure_kernel_shards)
            tags["kernel_shards_max"] = n if req <= 0 else max(1, min(req, n))
        if self.cfg.secure_exchange:
            tags["ot_path"] = secure.ot_path(
                2 * self.cfg.n_dims * radix, self.cfg.ot_path
            )
        return tags

    @property
    def _mesh(self):
        return self._default()._mesh

    def _mk_state_prop(name):  # noqa: N805  (class-body helper, deleted below)
        def _get(self):
            return getattr(self._default(), name)

        def _set(self, value):
            setattr(self._default(), name, value)

        return property(_get, _set)

    keys = _mk_state_prop("keys")
    keys_parts = _mk_state_prop("keys_parts")
    key_planes = _mk_state_prop("key_planes")
    alive_keys = _mk_state_prop("alive_keys")
    frontier = _mk_state_prop("frontier")
    _children = _mk_state_prop("_children")
    _last_shares = _mk_state_prop("_last_shares")
    _expand_ready = _mk_state_prop("_expand_ready")
    _ingest_pools = _mk_state_prop("_ingest_pools")
    _admission = _mk_state_prop("_admission")
    _sketch = _mk_state_prop("_sketch")
    _sketch_seed = _mk_state_prop("_sketch_seed")
    _sketch_root = _mk_state_prop("_sketch_root")
    _ratchet_digest = _mk_state_prop("_ratchet_digest")
    _ot = _mk_state_prop("_ot")
    _ot_snd = _mk_state_prop("_ot_snd")
    _ot_rcv = _mk_state_prop("_ot_rcv")
    _sec_seed = _mk_state_prop("_sec_seed")
    del _mk_state_prop

    # checkpoint-namespace helpers, default-session view (tests/tooling)
    def _ckpt_path(self, level: int) -> str:
        return self._default().ckpt_path(level)

    def _ckpt_levels(self) -> list:
        return self._default().ckpt_levels()

    def _ckpt_prune(self, keep: int = 2) -> None:
        self._default().ckpt_prune(keep)

    def _ckpt_clear(self) -> None:
        self._default().ckpt_clear()

    # -- verbs (ref: rpc.rs:56-66) ---------------------------------------

    async def reset(self, _req, cs: CollectionSession | None = None) -> bool:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        cs = cs if cs is not None else self._default()
        # the DEFAULT session shares the SERVER registry: when other
        # tenants are live, its reset must not zero their shared-plane
        # accounting (scheduler fills, dedup hits, control bytes) —
        # per-session registries always wipe
        cs.reset_state(
            reset_obs=(
                cs.key != DEFAULT_COLLECTION or len(self._table) <= 1
            )
        )
        return True

    async def add_keys(self, req, cs: CollectionSession | None = None) -> bool:  # fhh-race: atomic (unlocked upload fast path: append-only, never suspends — many in-flight batches deserialize concurrently by design)
        """req: pytree-of-arrays key batch chunk [B, d, 2] (the tensor form
        of AddKeysRequest, ref: rpc.rs:13-15).  An optional ``sketch`` entry
        carries the clients' malicious-security material (MAC'd payload
        DPFs + triples, protocol/sketch.py).  A bulk upload says where the
        batch goes — ``n``, the collection's client count, and ``lo``,
        the batch's first row — and the batch is written to its place on
        the chip now (``CollectionSession.add_key_batch``: dispatch
        only); a batch with no total waits on the host for
        ``concat_keys``."""
        cs = cs if cs is not None else self._default()
        cs.add_key_batch(IbDcfKeyBatch(*req["keys"]), req.get("n"), req.get("lo"))
        if req.get("sketch") is not None:
            cs._sketch_parts.append(
                jax.tree.unflatten(
                    jax.tree.structure(_SKETCH_TREEDEF), req["sketch"]
                )
            )
        return True

    async def tree_init(self, req, cs: CollectionSession | None = None) -> bool:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        cs = cs if cs is not None else self._default()
        if cs.key_planes is None and not cs.keys_parts:
            raise RuntimeError("tree_init before add_keys")
        # the session's data-plane channel must be keyed (coin flip +
        # base-OT) before the ratchet root commits or any level crawls
        await self._ensure_session_plane(cs)
        root_bucket = int((req or {}).get("root_bucket", 1))
        # what opens every crawl in no level: the keys, which a bulk
        # upload left resident, and the root frontier (dispatch only
        # where nothing forces the device)
        cs.ready_keys("tree_init", again=True)
        n = cs.keys.cw_seed.shape[0]
        cs.alive_keys = np.ones(n, bool)
        with cs.obs.span("frontier_init"):
            cs.frontier = cs.init_frontier(root_bucket)
        cs._children = None
        cs._shard_children.clear()
        cs._shard_last.clear()
        cs._shard_level = None
        cs._expand_ready.clear()
        if cs._sketch_parts:
            cs.concat_sketch()
            root = dpf.eval_init(cs._sketch.key)  # [N, d]
            cs._sketch_states = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (1,) + a.shape), root
            )
            cs._sketch_pids = np.zeros(
                (1, cs._sketch.key.root_seed.shape[1]), np.int32
            )
            cs._sketch_depth = 0
            cs._sketch_pairs = None
            # commit the challenge ratchet: root = the coin flip of the
            # CURRENT data-plane session (unpredictable to clients, who
            # committed their keys before this point), transcript = empty.
            # Both are checkpointed with the frontier, so later plane
            # resets / restarts cannot perturb any level's challenge.
            # A loaded STREAMING window overrides the root with its own
            # seal-time commitment (sketch.window_root, installed by
            # window_load): the root then survives server restarts via
            # the seal stats + ingest checkpoint, so a recovered
            # window's re-run replays the identical challenge instead
            # of re-opening its slabs under a fresh coin flip.
            root_src = (
                cs._window_sketch_root
                if cs._window_sketch_root is not None
                else cs._sketch_seed
            )
            cs._sketch_root = np.asarray(root_src, np.uint32).copy()
            cs._ratchet_digest = sketchmod.transcript_init()
        return True

    def _sketch_bind(self, cs, n: int, d: int):
        """Resolve the sketch verify's shard binding for this session's
        batch: the data mesh's leading devices under the
        ``Config.sketch_shards`` budget (0 = auto follows the data
        shards), ``None`` on a meshless server or when only one shard
        fits — the single fused program then runs on the default device.
        Pure lru-cached machinery underneath (like ``kernel_bind``)."""
        if cs._mesh is None:
            return None
        budget = (
            cs._mesh.shards
            if self.cfg.sketch_shards <= 0
            else min(int(self.cfg.sketch_shards), cs._mesh.shards)
        )
        return sketch_shard.bind(
            cs._mesh._active_devices(), n, d, budget
        )

    async def sketch_verify(self, req, cs: CollectionSession | None = None) -> np.ndarray:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Malicious-security check (ref intent: the TreeSketchFrontier*
        verb vestiges rpc.rs:40-51, gate at collect.rs:495): sketch inner
        products + Beaver verification over the peer data plane, per
        (client, dim) — a client fails if ANY dim fails; failing clients'
        liveness flags flip before the gated counts are taken.

        Depth semantics: ``level == 0`` verifies the FULL depth-1 level
        (both children of every dim's root, evaluated on the fly) so the
        first threshold never acts on unverified counts; ``level >= 2``
        verifies the depth-``level`` frontier shares stored by the prune
        of ``level - 1``.  Depth 1's frontier re-verify is deliberately
        absent — its Beaver triples were consumed by the level-0 full
        check, and re-opening them under a second challenge would leak
        ``<r - r', x>`` (see protocol/sketch.py scope note).

        The challenge randomness comes from the per-level RATCHET
        (sketch.py): hash(coin-flipped root committed at ``tree_init`` ‖
        level ‖ crawl-transcript digest) — never a public constant (a
        client must not be able to predict r), and DETERMINISTIC given
        the crawl transcript, so a level re-run after checkpoint recovery
        replays the identical challenge instead of re-opening its Beaver
        triple slab under fresh randomness (which would leak
        ``<r - r', x>``)."""
        cs = cs if cs is not None else self._default()
        if cs._sketch is None:
            raise RuntimeError("sketch_verify without sketch keys")
        await self._ensure_session_plane(cs)
        level = int(req["level"])
        k = cs._sketch.key
        L = k.data_len
        n, d = k.root_seed.shape[0], k.root_seed.shape[1]
        if level == 0:
            if cs._sketch_depth != 0:
                # the root check must run before the first prune: the
                # frontier-following states have advanced past the root,
                # so a late call would verify garbage and corrupt honest
                # clients' liveness flags
                raise RuntimeError(
                    "level-0 full check called after the tree advanced"
                )
            # full-width depth-1 check: both children of the root per dim
            last = L == 1
            fld = F255 if last else FE62
            st = jax.tree.map(lambda a: a[0], cs._sketch_states)  # [N, d]
            cw = dpf.level_cw(k, 0)
            cwv = k.cw_val[..., 0, :] if not last else k.cw_val_last
            sides = []
            for c in (False, True):
                _, p = dpf.eval_bit(
                    cw, st, jnp.full((n, d), c), cwv, k.key_idx, fld,
                    sketchmod.LANES,
                )
                sides.append(p)
            pairs_fn = jnp.stack(sides)  # [2, N, d, LANES(, limbs)]
            checks = [(pairs_fn, 0, fld, 0)]
        else:
            if L == 1:
                # the level-0 full check already consumed triples_last; a
                # second opening under a fresh challenge leaks <r - r', x>
                raise RuntimeError(
                    "data_len=1: the leaf check is the level-0 full check"
                )
            if level == 1:
                # the server is the enforcement boundary, not the leader:
                # depth 1's triples (index 0) were consumed by the level-0
                # full check, and opening them again under level 1's
                # different challenge reveals <r - r', x> of honest
                # clients' payloads
                raise RuntimeError(
                    "depth 1 is covered by the level-0 full check; "
                    "re-verifying it would re-open its Beaver triples"
                )
            if cs._sketch_pairs is None or cs._sketch_pairs[-1][1] != level:
                raise RuntimeError(f"no stored sketch shares for depth {level}")
            # radix fusion: the latest prune stored one (pairs, depth,
            # field) entry per bit level it fused — verify each stored
            # depth under its OWN ratcheted challenge and Beaver slab
            # (every slab still opens exactly once).  Depth 1 is dropped:
            # the level-0 full check consumed its triples, and re-opening
            # them under a second challenge would leak <r - r', x>.
            checks = [
                (p, dep - 1, f, dep)
                for (p, dep, f) in cs._sketch_pairs
                if dep >= 2
            ]
        sk = cs._sketch
        ss = self._sketch_bind(cs, n, d)
        cs.obs.gauge(
            "sketch_shards", 1 if ss is None else ss.k, level=level
        )
        # device-resident, row-sharded verify (parallel/sketch_shard.py):
        # each stored depth's check batch runs as one fused program per
        # stage — sharded along the client axis across the data mesh
        # when one is bound — with the challenge stream derived PER
        # SHARD by CTR seek (bit-identical to the single-device draw),
        # per-shard readbacks reassembled positionally into a
        # byte-identical wire, and a single post-depth verdict readback.
        # Both servers walk the identical check list in depth order, so
        # the data-plane swap sequence stays matched.  The old
        # sketch_batch_size host loop (one dispatch + TWO wire round
        # trips per chunk) survives only in the spec helper
        # (sketch.verify_level).
        with cs.obs.span("sketch", level=level):
            ok_all = None
            for pairs_fn, dpf_level, fld, depth in checks:
                if fld is F255:
                    trip, mk, mk2 = (
                        sk.triples_last, sk.mac_key_last, sk.mac_key2_last
                    )
                else:
                    # host slab slice: sketch key leaves are host numpy
                    # (the uploaded chunks), so the per-level slab costs
                    # no dispatch
                    trip = mpc.level_slab(sk.triples, dpf_level)
                    mk, mk2 = sk.mac_key, sk.mac_key2
                challenge = cs.challenge_seed(depth)
                cor, state = sketch_shard.cor_state(
                    ss, fld, pairs_fn, trip, mk, mk2, challenge, depth
                )
                cs.obs.count(
                    "device_fetches", 1 if ss is None else ss.k, level=level
                )
                # cor exchange: per-shard D2H copies assembled positionally
                # into ONE wire message (sketch_shard.wire starts the DMAs)
                with cs.obs.span("d2h", level=level):
                    cor_np = await asyncio.to_thread(sketch_shard.wire, cor)
                peer_cor = await self._swap(cs, cor_np)
                o = sketch_shard.out_shares(
                    ss, fld, state, cor, peer_cor, bool(self.server_id)
                )
                cs.obs.count(
                    "device_fetches", 1 if ss is None else ss.k, level=level
                )
                with cs.obs.span("d2h", level=level):
                    o_np = await asyncio.to_thread(sketch_shard.wire, o)
                peer_o = await self._swap(cs, o_np)
                ok_dev = sketch_shard.verdicts(ss, fld, o, peer_o)
                # per-depth verdicts AND on device; exclusion is a
                # conjunction, so one batched readback after the loop is
                # bit-identical to a fetch per depth
                ok_all = ok_dev if ok_all is None else ok_all & ok_dev
            # the check batch's SINGLE post-verify readback: the verdict
            # vector (checks is never empty — level 0 contributes its
            # full check, a fused prune always stores its final depth)
            ok = await _fetch(ok_all, cs.obs, level, self._waits)
            cs.alive_keys &= ok
        if level != 0:
            # one-shot within a boot: each stored depth's pairs open once;
            # a same-boot duplicate call is answered by the session dedup
            # cache, and a post-recovery re-run reloads the pairs from the
            # checkpoint and replays the IDENTICAL ratcheted challenge
            # (same root, same transcript) — a replay, not a second
            # opening.  The level-0 path has no stored pairs and re-runs
            # under the same identical-challenge argument.
            cs._sketch_pairs = None
        return cs.alive_keys.copy()

    # data-plane framing with byte/message accounting; levels attribute
    # via the active span (obs.metrics.Registry.count).  Every frame is
    # (collection, payload): sends interleave freely across sessions
    # (the writer thread takes each frame whole), receives demux through
    # the PlaneMux so each session reads only its own FIFO channel.
    def _plane_count(self, chan: str, nbytes: int) -> None:
        """PlaneMux byte-accounting hook: received bytes land on the
        owning session's registry (whose active span attributes them to
        the level being exchanged), unknown channels on the server's."""
        cs = self._table.peek(chan)
        reg = cs.obs if cs is not None else self.obs
        reg.count("data_bytes_recv", nbytes)

    async def _dp_send(self, cs: CollectionSession, obj):
        """One frame to the peer, through the plane's writer thread:
        pickled and counted here on the loop (the pieces are views,
        never joined), written there, and "sent" when the kernel has
        every byte: :meth:`_dp_send_begin` and :meth:`_dp_send_finish`,
        one after the other.  (A chunk level's send stage calls the two
        apart and keeps a second frame behind the one on the socket,
        :meth:`_chunk_senders`; every other sender calls this.)"""
        return await self._dp_send_finish(await self._dp_send_begin(cs, obj))

    async def _dp_send_begin(self, cs: CollectionSession, obj) -> _FrameOut:
        """The hand-over of one frame: counted (``data_msgs_sent``,
        ``data_bytes_sent``), pickled (``wire_pickle``) and put on the
        writer thread's queue (``wire.PlaneStreams.hand_over``: after a
        wait for a slot there, none where fewer than ``SEND_DEPTH``
        frames are out).  Returns what :meth:`_dp_send_finish` takes;
        the frame's arrays are pinned until that returns, or until the
        future in it is cancelled and the thread is done with them."""
        reg = cs.obs
        reg.count("data_msgs_sent")
        # under fhh-trace the frame's session header carries this verb's
        # (trace_id, span_id), so the peer's arrival instant parents
        # under the sender's span in the merged timeline
        tracing = obstrace.enabled()
        hdr = obstrace.wire_tag() if tracing else None
        frame = (cs.key, obj) if hdr is None else (cs.key, obj, hdr)
        pieces = _encode(frame, reg, "data_bytes_sent")
        sp = reg.current_span()
        level = None if sp is None else sp.level
        peer = self._peer
        if peer is None:
            raise ConnectionResetError("no peer data plane")
        return _FrameOut(reg, level, tracing, *await peer.hand_over(pieces))

    async def _dp_send_finish(self, out: _FrameOut) -> tuple[float, float]:
        """Wait until the kernel has every byte of a frame handed over
        (:meth:`_dp_send_begin`'s ``out``), then turn the thread's clock
        into this registry's spans, as ``PlaneMux.recv`` does for a
        read: ``wire_queue`` (frame handed over -> its send begins; the
        thread hop alone where the stream was free, the rest of the
        frame ahead where it was not) and ``wire_write`` (the thread's
        send of this frame); the timer ``send_resume`` is the way back:
        the thread's send ended -> the loop heard of it, and from there,
        or from this call where it came later (a send stage was at its
        next frame meanwhile: no hop of the loop's), until this
        coroutine ran again; for a caller that waited all along, the
        thread's send ended -> this coroutine resumed.
        ``plane_stream_frames`` counts the frames that went this way
        (all of ``data_msgs_sent``), ``plane_sends_overlapped`` those
        of them handed over while the thread still held another, the
        gauge ``plane_send_queue_high`` the most frames the writer held
        at a hand-over of the level, this one included (1: free;
        ``wire.PlaneStreams.SEND_DEPTH``: at its bound).  Returns the
        thread's ``(t_begin, t_end)``; raises ``ConnectionError`` where
        the plane was cut first."""
        reg, level, tracing, done, t_put, held = out
        asked = time.time()
        # fhh-lint: disable=unbounded-await (resolved by the writer thread for every frame it was handed, sent or failed: bounded by the plane's TCP keepalive and by close(), wire.PlaneStreams.hand_over)
        t_begin, t_end, t_seen = await done
        # the hop back (``wire_queue`` is the hop in): the writer
        # thread's last stamp -> the loop hears, and -> this coroutine
        # runs again, less what it spent elsewhere between; a timer alone
        reg.timer_add(
            "send_resume",
            max(0.0, t_seen - t_end) + max(0.0, time.time() - max(t_seen, asked)),
            level,
        )
        reg.count("plane_stream_frames", level=level)
        reg.count("plane_sends_overlapped", int(held > 1), level=level)
        if held > (reg.gauge_value("plane_send_queue_high", level) or 0):
            reg.gauge("plane_send_queue_high", held, level=level)
        for name, a, b in (
            ("wire_queue", t_put, t_begin), ("wire_write", t_begin, t_end),
        ):
            reg.timer_add(name, b - a, level)
            if tracing:
                obstrace.span_at(name, reg.name, a, b - a, level)
        if tracing:
            # the span log carries no gauges (scripts/trace_spans.py
            # ``plane_streams``)
            obstrace.instant("plane_send", comp=reg.name, held=held)
        return t_begin, t_end

    async def _dp_recv(self, cs: CollectionSession):
        # the whole data-plane receive; its children peer_wait,
        # wire_read and wire_unpickle are recorded by the mux from the
        # times its pump stamped on the frame
        with cs.obs.span("wire_wait"):
            # wire waits are what a SECOND tenant's device work can
            # fill: mark them so the scheduler's stall-fill accounting
            # sees the gap
            with self._sched.wire_wait(cs.key):
                return await self._plane.recv(cs.key, cs.obs)

    async def _swap(self, cs: CollectionSession, obj):
        """Full-duplex data-plane exchange on this session's channel:
        both servers send and receive at once.  Each direction has a
        stream and a reader thread of its own that always drains into
        receive buffers, so two sends past the socket buffers cannot
        wait on each other (what the old order, server 0 writes first
        and server 1 reads first, existed to avoid on one loop)."""
        _, peer = await self._chunk_tasks(
            self._dp_send(cs, obj), self._dp_recv(cs)
        )
        return peer

    # -- expand stage (device) vs open stage (plane I/O) -----------------
    #
    # The per-span crawl is split so the DEVICE half can run ahead of the
    # PLANE half: ``_do_expand`` dispatches the FSS expansion (+ string
    # extraction in secure mode) and starts the host copy; the open stage
    # consumes it under the verb lock.  ``_maybe_pre_expand`` runs the
    # expand stage at FRAME ARRIVAL — while the previous span's open
    # stage is awaiting the data plane — which is what overlaps span k's
    # GC/OT network phase with span k+1's device compute (the leader
    # keeps both frames in flight via ``crawl_pipeline_depth``).

    def _do_expand(self, cs, level: int, last: bool, shard) -> dict:  # fhh-race: atomic (dispatch-only device work, never suspends; called both under the session's verb lock and from the frame-arrival pre-expand)
        """Device half of one crawl span: dispatch-only (no sync — a
        block_until_ready here would stall the caller for the whole
        expansion); pure function of
        (keys, frontier, level, span), so a shard re-run may reuse it
        bit-identically."""
        frontier = cs.shard_frontier_view(shard)
        # radix-2^k fusion: this span covers bit levels [level, level+r)
        # — r = 1 is exactly the pre-radix program (expand_share_bits_radix
        # and child_strings_radix delegate to the radix-1 entry points)
        r = cs.crawl_radix(level)
        packed, children = cs.expand(frontier, level, r, not last)
        out = {"packed": packed, "children": children, "frontier": frontier}
        if self.cfg.secure_exchange:
            d = cs.keys.cw_seed.shape[1]
            S = 2 * d * r  # fused equality string width S'
            if cs._mesh is not None:
                # row-sharded kernel stage (parallel/kernel_shard.py):
                # the whole-level planar test batch partitions along its
                # row/block axis across the data mesh — extension,
                # equality kernels, and b2a all run per shard with a
                # byte-identical wire, so nothing between FSS expansion
                # and the frame serializes onto one device
                F_, N = packed.shape
                C = 1 << (d * r)
                B = F_ * C * N
                ks = cs._mesh.kernel_bind(
                    B, S, self.cfg.secure_kernel_shards
                )
                if ks is not None:
                    out["flat"] = kernel_shard.shard_flat(
                        ks, packed, d, F_, N, r
                    )
                    out["dims"] = (F_, C, N, S)
                    out["kernel"] = ks
                    return out
                # degraded path (batch fills a single planar block, or
                # secure_kernel_shards pins 1): the pre-PR-10 gather of
                # the packed share bits over ICI onto one device.  The
                # counter is the layout detector (a fully sharded crawl
                # never increments it); the timer records DISPATCH time
                # only — device_put returns before the transfer, which
                # completes lazily under the level's later fetch, so a
                # sync here would block this (possibly frame-arrival)
                # context for the whole transfer
                t0 = time.monotonic()
                packed = cs._mesh.gather(packed)
                cs.obs.timer_add(
                    "kernel_gather", time.monotonic() - t0, level=int(level)
                )
                cs.obs.count("kernel_gathers", level=int(level))
            strs = secure.child_strings_radix(packed, d, r)  # [F, C, N, S']
            F_, C, N, S = strs.shape
            out["flat"] = strs.reshape(F_ * C * N, S)
            out["dims"] = (F_, C, N, S)
        else:
            # double-buffer: the D2H copy of the packed bits starts NOW
            # and lands while other work (the previous span's exchange)
            # holds the event loop
            _start_host_copy(packed)
        return out

    def _expand_stage(self, cs, level: int, last: bool, shard) -> dict:
        hit = cs._expand_ready.pop((bool(last), int(level), shard), None)
        if hit is not None:
            return hit
        return self._do_expand(cs, level, last, shard)

    def _maybe_pre_expand(self, cs, verb: str, req) -> None:  # fhh-race: atomic (frame-arrival prefetch: reads frontier/keys and stashes in one event-loop slice; every frontier mutation clears the stash before the next slice)
        """Frame-arrival hook (``_dispatch``, BEFORE the verb lock): run
        the expand stage for a sharded crawl verb while earlier spans
        still hold the lock.  Purely an overlap optimization — any
        refusal or failure here just means the verb recomputes (and
        surfaces the real error) under the lock."""
        if verb not in ("tree_crawl", "tree_crawl_last"):
            return
        shard = self._parse_shard(req)
        if shard is None or cs.keys is None or cs.frontier is None:
            return
        if shard[1] > cs.frontier.f_bucket:
            return  # span from another life (stale replay): let it fail
        level, last = int(req["level"]), verb == "tree_crawl_last"
        key = (last, level, shard)
        # bound the stash: depth-many entries live at a time in practice;
        # 32 is far above any sane pipeline depth
        if key in cs._expand_ready or len(cs._expand_ready) >= 32:
            return
        try:
            t0 = time.monotonic()
            cs._expand_ready[key] = self._do_expand(cs, level, last, shard)
            # a device dispatch that ran while ANOTHER tenant's span was
            # on the wire is exactly the gap multi-tenancy fills
            self._sched.note_dispatch(cs.key)
            # dispatch time only, attributed to the fss phase the verb
            # would otherwise have spent it in (no span: another verb's
            # span may be active on this registry right now)
            cs.obs.timer_add("fss", time.monotonic() - t0, level=level)
        except Exception:  # fhh-lint: disable=broad-except (prefetch only: the verb recomputes under the lock and surfaces the real error to the leader)
            cs._expand_ready.pop(key, None)

    async def _crawl_counts(
        self, cs, level: int, last: bool = False, shard=None
    ) -> np.ndarray:
        # per-level phase breakdown of the reference (collect.rs:412-503);
        # trusted mode's "GC and OT" slot is the plaintext exchange
        with cs.obs.span("fss", level=level) as sp_fss:
            # a device turn: serialized FIFO across tenants (one
            # accelerator), counted as a stall fill when it ran while
            # another session waited on the wire.  A pre-expanded span
            # already dispatched (and was counted) at frame arrival —
            # don't count the no-op turn, it would inflate the
            # fill-ratio denominator.  The turn covers DISPATCH only;
            # the fetch below blocks on device execution and must not
            # hold other tenants' dispatch out.
            pre = (bool(last), int(level), shard) in cs._expand_ready
            async with self._sched.device_turn(cs.key, count=not pre):
                ex = self._expand_stage(cs, level, last, shard)
            packed, children, frontier = (
                ex["packed"], ex["children"], ex["frontier"]
            )
            # forces the device work to finish
            packed_np = await _fetch(packed, cs.obs, waits=self._waits)
        with cs.obs.span("gc_ot", level=level) as sp_gc:
            # data plane: swap packed share bits with the peer server
            peer = await self._swap(cs, packed_np)
        with cs.obs.span("field", level=level) as sp_field:
            masks = collect.pattern_masks_radix(
                cs.keys.cw_seed.shape[1], cs.crawl_radix(level)
            )
            peer = self._h2d(cs, level, peer, smesh.P(None, smesh.DATA))
            counts = await self._reduced_fetch(
                cs, level, collect.counts_by_pattern,
                packed, peer, masks, cs.alive_keys, frontier.alive,
            )
        # per-level crawl latency histogram (SLO surface): this PASS's
        # three phases, not the registry total a re-run would inflate
        cs.obs.observe(
            "level_latency", sp_fss.seconds + sp_gc.seconds + sp_field.seconds
        )
        cs.stash_children(level, shard, children)
        return counts

    async def _reduced_fetch(self, cs, level: int, single_fn, *args):
        """The per-level reduction + host fetch shared by the trusted
        (``collect.counts_by_pattern``) and secure
        (``secure.node_share_sums``) crawl paths.  Under the multi-chip
        mesh the per-shard client-axis partials fold over ICI (psum)
        BEFORE the fetch — :class:`~..parallel.server_mesh.ServerMesh`
        mirrors the single-device reduction API by name, so the mesh
        form is found via ``single_fn.__name__`` — and the fetch-synced
        ``ici_reduce`` span is the reduction's cost instrument.  Either
        way the caller (and with it the wire) gets host values in the
        single-device layout."""
        if cs._mesh is not None:
            cs.obs.gauge("data_shards", cs._mesh.shards, level=level)
            with cs.obs.span("ici_reduce", level=level):
                out = getattr(cs._mesh, single_fn.__name__)(*args)
                return await _fetch(out, cs.obs, waits=self._waits)
        return await _fetch(single_fn(*args), cs.obs, waits=self._waits)

    @staticmethod
    def _h2d(cs, level: int, x: np.ndarray, spec=None):
        """Host->device copy of a received payload, made explicit so it
        has a span (``h2d``) of its own; it used to happen inside the
        first jitted call that took the numpy frame.  No sync: the span
        is the dispatch plus whatever of the copy the runtime does
        before ``device_put`` returns; the rest lands in the span of
        whoever first waits for the consumer."""
        with cs.obs.span("h2d", level=level):
            if cs._mesh is None:
                return jax.device_put(x)
            # a sharded server touches only its own chips: never via
            # the process's default device, which is another server's
            # chip once co-resident servers take disjoint ones.  With a
            # ``spec`` the payload lands sharded as its consumer takes
            # it; without, on the mesh's first device, where the
            # degraded one-device kernel stage runs
            if spec is None:
                return cs._mesh.gather(x)
            return cs._mesh.put(x, spec)

    async def _phase_sync(self, cs, level: int, x) -> None:
        """Device sync at a secure-kernel phase boundary (OFF the event
        loop — a bare block_until_ready would starve keepalives exactly
        like a bare np.asarray), for a program whose output NOTHING
        fetches: the evaluator's opening of a chunk (``_ev_chunks``) and
        the row-sharded kernel stage.  A chunk program whose output a
        fetch stage takes is waited for on that fetch's thread instead
        (``_fetch_behind``).  Gated by ``cfg.secure_phase_sync``: the
        phases are sequential data-dependent steps, so syncing costs only
        the dispatch-ahead slack, and buys the eval/b2a spans real device
        seconds instead of dispatch time.

        Two timers split the wait: ``program_device`` (on the thread,
        its first line -> ``block_until_ready`` returned: the program's
        remaining run time and the device queue ahead of it) and
        ``program_hop`` (the rest of this await: the hand-over to the
        thread and the finished thread's wait for the loop); both 0
        without the sync.  The counter ``secure_phase_waits`` counts the
        awaited syncs.  The thread is one of the server's device-wait
        threads (``_DeviceWaits``)."""
        device = hop = 0.0
        if self.cfg.secure_phase_sync:
            cs.obs.count("secure_phase_waits", level=level)
            t0 = time.perf_counter()
            device = await self._waits.call(_sync_on_thread, x)
            hop = time.perf_counter() - t0 - device
        cs.obs.timer_add("program_device", device, level)
        cs.obs.timer_add("program_hop", hop, level)

    def _fetch_behind(self, cs, level: int, x, marks: list, waits: tuple):
        """Begin the fetch of ``x``, the last output of the programs a
        chunk stage has JUST dispatched (``marks``: the wall clock before
        the first dispatch and after each), and return the future of the
        numpy array for the fetch stage to await (``_fetch_taken``); the
        stage that dispatched awaits nothing between its dispatch and
        its ``put``.  The host copy is queued behind the programs on the
        device, with no loop turn between, and ONE thread call, started
        here on the server's device-wait threads so that its stamps are
        the device's ends, waits for each output of ``waits`` (``(span
        names, output)`` in dispatch order), stamps when it was ready,
        and copies (``_fetch_on_thread``).  With
        ``cfg.secure_phase_sync`` off it waits for ``x`` alone, as
        ``_fetch`` does.  ``_fetched`` records the stamps on the loop
        and resolves the future, whatever the thread call came to."""
        _start_host_copy(x)
        held = asyncio.get_running_loop().create_future()
        self._waits.call(
            _fetch_on_thread, x,
            waits if self.cfg.secure_phase_sync else (),
            lambda name: obstrace.annotate(cs.obs.name, name, level) or _NO_CTX,
        ).add_done_callback(
            functools.partial(self._fetched, cs.obs, level, marks, waits, held)
        )
        return held

    @classmethod
    def _fetched(cls, reg, level: int, marks: list, waits: tuple, held, fut) -> None:
        """One ``_fetch_behind``'s thread call is over: ``held``, which
        a fetch stage awaits, is resolved ON EVERY PATH out of here: the
        array, the thread's error (that stage then fails the verb), an
        error where the call was cancelled before it ran (the server is
        closing); and a fault in the recording of the stamps is logged
        and counted (``secure_account_errors``) and the array handed
        over all the same: the account never fails a level."""
        if held.done():  # cancelled with its stage: nobody takes it
            if not fut.cancelled():
                fut.exception()  # seen, so asyncio does not log it
            return
        if fut.cancelled():
            held.set_exception(ConnectionError(
                f"secure level {level}: the chunk's fetch was cancelled "
                "before its thread ran (the server is closing)"
            ))
            return
        if fut.exception() is not None:
            held.set_exception(fut.exception())
            return
        out, *stamps = fut.result()
        try:
            cls._record_fetch(reg, level, marks, waits, *stamps)
        except Exception as err:  # fhh-lint: disable=broad-except (the account of a fetch that succeeded: a fault in it costs the level its timers, not its result)
            reg.count("secure_account_errors", level=level)
            obs.emit(
                "secure.account_error", severity="warning", comp=reg.name,
                level=int(level), error=f"{type(err).__name__}: {err}",
            )
        finally:
            held.set_result(out)

    @staticmethod
    def _record_fetch(reg, level: int, marks: list, waits: tuple,
                      ready: float, copy: float, stamps: list) -> None:
        """The stamps of one ``_fetch_behind`` as this registry's spans
        and timers ON THE LOOP, as ``_dp_send`` records a send.  One
        line a span and chunk, disjoint and in order: the first
        program's span from the first dispatch's begin to its output
        ready, each later one from the output before it to its own,
        ``d2h`` from the last output ready to this callback (without
        the sync: each program's dispatch, and ``d2h`` as ``_fetch``
        has it).  ``program_device`` is the thread's waits,
        ``program_hop`` the hand-over (last dispatch ended -> the
        thread's first line), ``d2h_ready`` / ``d2h_copy`` what
        ``_fetch`` finds after the waits, ``d2h_hop`` the finished
        thread's wait for the loop; ``secure_fetch_syncs`` counts the
        outputs waited for."""
        began, *ends, _ = stamps
        now = time.time()
        device = hop = 0.0
        if ends:  # a span ends where the device finished its program
            edges = [marks[0], *ends]
            device, hop = ends[-1] - began, began - marks[-1]
            reg.count("secure_fetch_syncs", len(ends), level=level)
        else:  # no sync: dispatch time
            edges = marks
        tracing = obstrace.enabled()
        spans = [(w[0], a, b) for w, a, b in zip(waits, edges, edges[1:])]
        for names, a, b in (*spans, (("d2h",), edges[-1], now)):
            up = None  # a second name lies inside the first (ot2s in b2a)
            for name in names:
                reg.timer_add(name, b - a, level)
                if tracing:
                    up = obstrace.span_at(
                        name, reg.name, a, b - a, level, parent=up
                    )
        reg.count("device_fetches", level=level)
        for name, seconds in (
            ("program_device", device), ("program_hop", hop),
            ("d2h_ready", ready), ("d2h_copy", copy),
            ("d2h_hop", now - edges[-1] - ready - copy),
        ):
            reg.timer_add(name, seconds, level)

    async def _fetch_taken(self, cs, level: int, stage: str, k: int, held):
        """Await a chunk's fetch (``_fetch_behind``'s future) for the
        fetch stage ``stage``, no longer than the plane lets a peer stay
        silent (``_plane_silence_s``: past it the peer's verb would have
        lost this server anyway).  A thread call that has not come back
        by then fails the verb here, with the stage and the chunk it
        stood in; the leader's quiesce fails the peer's, and the level
        run again is exact (tests/test_secure_chunks.py)."""
        bound = self._plane_silence_s()
        try:
            done, _ = await asyncio.wait({held}, timeout=bound)
        except asyncio.CancelledError:
            held.cancel()  # the level is over: nobody takes it
            raise
        if not done:
            held.cancel()
            cs.obs.count("device_wait_timeouts", level=level)
            raise TimeoutError(
                f"secure level {level}: {stage} waited {bound:g} s for "
                f"chunk {k}'s device programs and copy (a fetch thread "
                "that does not return)"
            )
        return held.result()

    @staticmethod
    def _dispatched(cs, level: int, fn, *args, marks=None):
        """``fn(*args)``, a jitted call's host side ON the loop thread,
        its seconds added to the timer ``program_dispatch``; ``marks``
        takes the wall clock when it returned (``_fetch_behind``)."""
        t0 = time.perf_counter()
        try:
            return fn(*args)
        finally:
            cs.obs.timer_add(
                "program_dispatch", time.perf_counter() - t0, level
            )
            if marks is not None:
                marks.append(time.time())

    @classmethod
    def _program(cls, cs, level: int, fn, *args, marks=None):
        """Hand the device ONE program of a secure chunk, inside its
        ``otext`` or ``b2a`` span, and count it: the counter
        ``secure_chunk_programs`` reads 2 x K a level on either server
        (tests/test_secure_chunks.py holds the calls to one jitted
        program each)."""
        cs.obs.count("secure_chunk_programs", level=level)
        return cls._dispatched(cs, level, fn, *args, marks=marks)

    def _zero_phases(self, cs, level: int, *names: str) -> None:
        """Materialize zero-valued phase timers so the secure-kernel
        split always carries all four keys on both servers (a garbler
        has no eval phase, the ot2s path has no garble phase — the run
        report must show those as 0, not absent)."""
        for n in names:
            cs.obs.timer_add(n, 0.0, level=level)

    @staticmethod
    @contextlib.contextmanager
    def _leaf_exchange(cs, level: int, tests: int):
        """The LAST level's exchange told from the inner levels': the
        span ``leaf_gc_ot`` inside its ``gc_ot`` and the counter
        ``leaf_tests`` of its ``B = F*C*N``.  The leaf level compares
        over F255 (a payload of eight words to FE62's two) at whatever
        bucket the crawl ends in, and in a short crawl run to its hitter
        set round after round it is the largest single share of the time
        (16 levels of 2 x 16 bits: 40-50%), which ``gc_ot`` summed over
        the levels cannot show."""
        cs.obs.count("leaf_tests", tests, level=level)
        with cs.obs.span("leaf_gc_ot", level=level):
            yield

    # -- the secure level as a stream of row chunks -------------------------
    #
    # ``secure.level_chunks`` cuts the level's test batch into K runs of
    # whole planar blocks, and each of the two messages crosses as K
    # frames.  Each server runs its stages as tasks with a short queue
    # between them — kernel, fetch and send of what it makes
    # (``_chunk_senders``), receive and kernel of what it is sent — so
    # chunk k+1's kernel and chunk k's fetch run while chunk k-1 is on
    # the socket and the peer works on chunk k-2.  A stage that hands
    # the device a chunk's programs does not wait for them: it starts
    # the ONE thread call of the chunk's fetch where it dispatches
    # (``_fetch_behind``), and that thread waits for each program,
    # stamps the spans' device ends and copies; the fetch stage awaits
    # it, no longer than the plane lets a peer stay silent
    # (``_fetch_taken``).  Only the opening, whose output nothing
    # fetches, is awaited by its stage (``_phase_sync``).  Every call
    # that parks in ``block_until_ready`` does so on a thread of the
    # server's own (``_DeviceWaits``, ``DEVICE_WAITS`` of them), not on
    # asyncio's default executor.  A send is a whole frame on the
    # plane's writer thread, and a send stage keeps ``FRAMES_OUT`` of
    # them there: it hands chunk k+1's frame over while chunk k's is on
    # the socket and waits for the older one only then, so the thread
    # finds its next frame queued when a write ends (the loop's turns
    # between two frames run beside the write, not between writes).  The
    # peer's reader thread always reads, and ``PlaneMux`` holds frames
    # ahead of their receiver in order.  One chunk (K = 1) is the whole
    # level: the same calls, one after the other.

    # how many chunks the evaluator's u may run ahead of the tables it
    # has opened (``_ev_chunks``): every level of eight chunks or fewer
    # runs as far ahead as it has chunks.  It bounds the chunks whose u
    # the kernel has whole (``on_sent``) and whose table is not opened:
    # CHUNKS_AHEAD in ``sent`` and the one that waits to get in, which
    # is what the gauge ``secure_t_rows_held_bytes`` counts.  Since the
    # send stage keeps FRAMES_OUT frames with the writer thread, ONE
    # chunk more may have its u on the socket behind those (handed over,
    # ``on_sent`` not yet called): its strings and T rows are on the
    # device too, as are those of every chunk ``extend`` runs ahead
    # (``made``, ``fetched``), none of which the gauge counts
    CHUNKS_AHEAD = 8

    # chunks a stage may run ahead of the stage after it: the bound of
    # the queues ``made`` / ``built`` / ``fetched``
    STAGE_QUEUE = 2

    # frames a send stage keeps with the plane's writer thread: the one
    # on the socket and one behind it (``_chunk_senders``).  The hop
    # back to the loop and the turn that takes and pickles the next
    # chunk are 1.3-3.2 ms against a write of 2.6-5.1 (PERF.md), so one
    # frame behind is what hides them; a stage pins the arrays of this
    # many fetched chunks beside the STAGE_QUEUE in ``fetched``
    FRAMES_OUT = 2

    # the threads of a server's waits for the device (``_DeviceWaits``),
    # from what a level can have parked at once: a fetch's thread call
    # for every chunk in ``made`` (or ``built``), for the one its
    # dispatching stage holds while it waits for room there and for the
    # one the fetch stage awaits; the opening's sync; and one more for a
    # fetch outside the chunk stages (the ``field`` phase's, another
    # verb's).  The gauge ``device_waits_high`` reads against it.
    DEVICE_WAITS = STAGE_QUEUE + 1 + 1 + 1 + 1

    @staticmethod
    def _chunk_frame(k: int, K: int, arr: np.ndarray):
        """What chunk ``k`` of ``K`` crosses as: the array alone where
        the level goes whole (the frame it always was), else tagged."""
        return arr if K == 1 else (k, K, arr)

    @staticmethod
    def _chunk_label(k: int, K: int):
        """Under fhh-trace, ``chunk=k`` on the spans inside; a level
        that goes whole labels nothing."""
        return obstrace.chunk(k if K > 1 else None)

    async def _chunk_recv(self, cs, k: int, K: int) -> np.ndarray:
        """The array of chunk ``k`` of ``K`` off this session's channel.
        A peer that cut the level differently sent something else: the
        streams have diverged for good, so this end of the plane is
        closed (the peer's blocked receives fail with it, nobody hangs)
        and the verb fails like one on a severed plane."""
        got = await self._dp_recv(cs)
        if K == 1 and isinstance(got, np.ndarray):
            return got
        if (
            K > 1 and isinstance(got, tuple) and len(got) == 3
            and got[:2] == (k, K)
        ):
            return got[2]
        tag = got[:2] if isinstance(got, tuple) else "an untagged frame"
        await self.plane_break(None)
        raise ConnectionError(
            f"secure level: expected chunk {k} of {K} from the peer, "
            f"got {tag}"
        )

    @staticmethod
    async def _chunk_tasks(*coros) -> list:
        """Run a level's chunk tasks to their end.  The first to fail
        cancels its siblings and its error fails the verb (a cancelled
        verb cancels them all); none outlives this call."""
        tasks = [asyncio.ensure_future(c) for c in coros]
        try:
            # fhh-lint: disable=unbounded-await (the tasks' own waits are the data plane's, bounded by TCP keepalive like every exchange; a verb deadline cancels this await and the tasks with it)
            await asyncio.wait(tasks, return_when=asyncio.FIRST_EXCEPTION)
        finally:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        for t in tasks:
            if not t.cancelled() and t.exception() is not None:
                raise t.exception()
        return [t.result() for t in tasks]

    def _chunk_senders(self, cs, level: int, K: int, made: asyncio.Queue,
                       stages: tuple, on_sent=None):
        """The two tasks that take a level's K chunk arrays from the
        device to the peer, in order: one awaits each fetch, one sends,
        two chunks at most between them, so chunk k+1's fetch runs
        while chunk k is on the socket.  ``made`` yields ``(fetch,
        token)``, the fetch a future that the stage before began where
        it dispatched the chunk's programs (``_fetch_behind``) and that
        is awaited under the plane's bound (``_fetch_taken``).  The send
        stage hands each frame to the plane's writer thread as soon as
        ``fetched`` yields it (``_dp_send_begin``) and waits for the
        OLDEST frame out (``_dp_send_finish``) only when ``FRAMES_OUT``
        are with the thread, and for those still out at the level's
        end: the thread is FIFO, so frames leave and end in order, and
        chunk k+1's is queued when chunk k's write ends.  ``on_sent``
        is awaited with the token once a chunk, in chunk order, after
        THAT chunk's frame is with the kernel.  The timer ``stream_gap``
        is, by the thread's own stamps, what it waited between the end
        of one frame of this stage and the start of the next.  A frame
        that fails raises where it is waited for and fails the verb; a
        stage that is cancelled, or failed, gives up the frames still
        out without waiting for them.  ``stages`` names the two in the
        level's wait account (:class:`_Stage`)."""
        fetched: asyncio.Queue = asyncio.Queue(self.STAGE_QUEUE)

        async def fetch():
            with _Stage(cs.obs, stages[0], level) as st:
                for k in range(K):
                    # fhh-lint: disable=unbounded-await (fed by a sibling task, which _chunk_tasks cancels with this one)
                    arr, token = await st.starved(made.get())
                    arr = await self._fetch_taken(
                        cs, level, stages[0], k, arr
                    )
                    # fhh-lint: disable=unbounded-await (drained by a sibling task, as above)
                    await st.blocked(fetched.put((arr, token)))

        async def send():
            reg = cs.obs
            # frames with the writer thread, oldest first, and where the
            # thread ended the last one this stage has seen the end of
            out: _collections.deque = _collections.deque(maxlen=self.FRAMES_OUT)
            last_end = None

            async def finish():
                nonlocal last_end
                k, token, frame = out.popleft()
                with self._chunk_label(k, K):
                    t_begin, t_end = await self._dp_send_finish(frame)
                if last_end is not None:
                    reg.timer_add(
                        "stream_gap", max(0.0, t_begin - last_end), level
                    )
                last_end = t_end
                if on_sent is not None:
                    await st.blocked(on_sent(token))

            with _Stage(reg, stages[1], level) as st:
                reg.timer_add("stream_gap", 0.0, level)
                try:
                    for k in range(K):
                        # fhh-lint: disable=unbounded-await (fed by a sibling task, as above)
                        arr, token = await st.starved(fetched.get())
                        with self._chunk_label(k, K):
                            frame = await self._dp_send_begin(
                                cs, self._chunk_frame(k, K, arr)
                            )
                        out.append((k, token, frame))
                        arr = None
                        if len(out) == self.FRAMES_OUT:
                            await finish()
                    while out:
                        await finish()
                finally:
                    # cancelled, or a frame failed: the frames still out
                    # are not awaited (the thread sends or fails them all
                    # the same, ``wire.PlaneStreams._sent``)
                    for *_, frame in out:
                        frame.done.cancel()

        return fetch(), send()

    async def _ev_chunks(
        self, cs, level: int, flat, chunks, B: int, W: int, path: str,
        count_field,
    ):
        """Evaluator and OT receiver of a level: one task hands the
        device the extension of chunk after chunk (stage ``extend``: it
        awaits room in ``made`` and nothing else; ``otext`` is stamped
        on the fetch's thread, ``_fetch_behind``), two carry each u to
        the peer (:meth:`_chunk_senders`: ``u_fetch``, ``u_send``), one
        opens each table as it arrives (``open``, which awaits its
        ``_phase_sync``: nothing fetches what it makes).
        Returns the level's field values [B(, limbs)] on the device."""
        S, K = flat.shape[1], len(chunks)
        rcv = cs._ot_rcv
        # the cursors move past the whole batch up front: a level that
        # fails midway leaves them where the whole-level flow would
        idx0, off = rcv.consumed, rcv.stream_offset
        rcv.advance(B * S)
        made: asyncio.Queue = asyncio.Queue(self.STAGE_QUEUE)
        # chunks whose u is on the wire, for the task that opens them;
        # their (y, T rows) wait on the device meanwhile, and the gauge
        # ``secure_t_rows_held_bytes`` says how many bytes at the fullest.
        # At most CHUNKS_AHEAD of them: the peer always has a u to work
        # on, and a level of 64 chunks holds neither all its T rows on
        # the device nor all its u frames in the peer's receive slabs
        sent: asyncio.Queue = asyncio.Queue(maxsize=min(K, self.CHUNKS_AHEAD))
        held = [0, 0]  # device bytes of the tokens in ``sent``: now, peak

        def nbytes(token) -> int:
            return sum(int(a.nbytes) for a in token)

        async def on_sent(token):
            held[0] += nbytes(token)
            held[1] = max(held)
            # fhh-lint: disable=unbounded-await (drained by a sibling task, which _chunk_tasks cancels with this one)
            await sent.put(token)

        async def extend():
            with _Stage(cs.obs, "extend", level) as st:
                for k, (t0, n) in enumerate(chunks):
                    with self._chunk_label(k, K):
                        marks = [time.time()]
                        u, t_rows, y = self._program(
                            cs, level, secure.ev_chunk_extend,
                            rcv, flat, off, t0, n, marks=marks,
                        )
                        # the fetch's thread waits for the extension
                        u = self._fetch_behind(
                            cs, level, u, marks, ((("otext",), u),)
                        )
                    # fhh-lint: disable=unbounded-await (drained by a sibling task, which _chunk_tasks cancels with this one)
                    await st.blocked(made.put((u, (y, t_rows))))

        async def consume():
            vals = []
            with _Stage(cs.obs, "open", level) as st:
                for k, (t0, _) in enumerate(chunks):
                    # fhh-lint: disable=unbounded-await (fed by the sibling task, which _chunk_tasks cancels with this one)
                    y, t_rows = await st.starved(sent.get())
                    held[0] -= nbytes((y, t_rows))
                    with self._chunk_label(k, K):
                        bmsg = self._h2d(
                            cs, level,
                            await st.starved(self._chunk_recv(cs, k, K)),
                        )
                        if path != "ot2s":
                            with cs.obs.span("eval", level=level):
                                pay = self._dispatched(
                                    cs, level, secure.ev_chunk_eval,
                                    t_rows, y, bmsg, W, idx0, t0,
                                )
                                await self._phase_sync(cs, level, pay)
                        with cs.obs.span("b2a", level=level):
                            if path == "ot2s":
                                # the opening of the 2^S table, inside b2a
                                with cs.obs.span("ot2s", level=level):
                                    v = self._program(
                                        cs, level, secure.ev_chunk_open,
                                        count_field, t_rows, y, bmsg, idx0,
                                        t0,
                                    )
                                    await self._phase_sync(cs, level, v)
                            else:
                                v = self._program(
                                    cs, level, secure.ev_chunk_field,
                                    count_field, pay,
                                )
                                await self._phase_sync(cs, level, v)
                        vals.append(v)
            return vals

        *_, vals = await self._chunk_tasks(
            extend(),
            *self._chunk_senders(
                cs, level, K, made, ("u_fetch", "u_send"), on_sent
            ),
            consume(),
        )
        cs.obs.gauge("secure_t_rows_held_bytes", held[1], level=level)
        self._zero_phases(
            cs, level, "garble", *(("eval",) if path == "ot2s" else ())
        )
        return vals[0] if K == 1 else jnp.concatenate(vals)

    async def _gb_chunks(
        self, cs, level: int, flat, chunks, B: int, W: int, path: str,
        count_field, garbler: int, gc_seed, b2a_seed,
    ):
        """Garbler and OT sender of a level: one task receives each u
        and hands the device that chunk's programs back to back (stage
        ``build``: no await between a chunk's dispatch and its ``put``;
        ``otext``, ``b2a`` / ``ot2s`` (``garble``) are stamped on the
        fetch's thread, ``_fetch_behind``), two carry the message to the
        peer (:meth:`_chunk_senders`: ``msg_fetch``, ``msg_send``), two
        chunks at most between each, so chunk k's fetch overlaps chunk
        k+1's kernel and chunk k-1's write and ``build`` runs ahead of
        the device by those queues and no further.  Returns the level's
        share values [B(, limbs)] on the device."""
        S, K = flat.shape[1], len(chunks)
        snd = cs._ot_snd
        idx0, off = snd.consumed, snd.stream_offset
        snd.advance(B * S)  # see _ev_chunks
        # the level's two 16-byte constants go to the device once, not
        # with every chunk's program (a sharded server's to its own first
        # chip, where its one-device kernel stage runs: see _h2d)
        put = jax.device_put if cs._mesh is None else cs._mesh.gather
        b2a_seed, s_block = put(b2a_seed), put(snd.s_block)
        built: asyncio.Queue = asyncio.Queue(self.STAGE_QUEUE)

        async def build():
            vals = []
            with _Stage(cs.obs, "build", level) as st:
                for k, (t0, n) in enumerate(chunks):
                    with self._chunk_label(k, K):
                        u = self._h2d(
                            cs, level,
                            await st.starved(self._chunk_recv(cs, k, K)),
                        )
                        marks = [time.time()]
                        q = self._program(
                            cs, level, secure.gb_chunk_extend,
                            snd, u, S, off, t0, n, marks=marks,
                        )
                        if path == "ot2s":
                            # the share pair and the 2^S table: ot2s is b2a
                            msg, v = self._program(
                                cs, level, secure.gb_chunk_table,
                                count_field, b2a_seed, q, flat,
                                s_block, idx0, t0, n, garbler, marks=marks,
                            )
                            waits = (("otext",), q), (("b2a", "ot2s"), msg)
                        else:
                            v, w0, w1 = self._program(
                                cs, level, secure.gb_chunk_pair,
                                b2a_seed, t0, count_field, garbler, n,
                                marks=marks,
                            )
                            msg = self._dispatched(
                                cs, level, secure.gb_chunk_garble,
                                s_block, q, gc_seed, flat, w0, w1, W,
                                idx0, t0, n, marks=marks,
                            )
                            waits = (
                                (("otext",), q), (("b2a",), w1),
                                (("garble",), msg),
                            )
                        vals.append(v)
                        # the fetch's thread waits for them, in order
                        msg = self._fetch_behind(cs, level, msg, marks, waits)
                    # fhh-lint: disable=unbounded-await (drained by a sibling task, which _chunk_tasks cancels with this one)
                    await st.blocked(built.put((msg, None)))
            return vals

        vals, *_ = await self._chunk_tasks(
            build(),
            *self._chunk_senders(
                cs, level, K, built, ("msg_fetch", "msg_send")
            ),
        )
        self._zero_phases(
            cs, level, "eval", *(("garble",) if path == "ot2s" else ())
        )
        return vals[0] if K == 1 else jnp.concatenate(vals)

    async def _crawl_counts_secure(
        self, cs, level: int, count_field, last: bool = False, garbler: int = 0,
        shard=None, ot_path=None,
    ) -> np.ndarray:
        """The real 2PC data plane (ref: collect.rs:419-501): equality +
        OT b2a over the peer socket; returns this server's additive field
        share of every per-(node, pattern) count.  No packed share-bit
        tensor ever crosses the server boundary in this mode.

        ``garbler`` names the server that garbles this level (the leader
        alternates it per level — the reference's ``gc_sender`` flag,
        rpc.rs:20-23 — so garbling cost splits across the servers); each
        direction runs its own OT-extension session (``_setup_secure``).
        A level is ONE protocol round trip, ev u -> sender's planar
        message — the 1-of-2^S payload table when ``secure.ot_path``
        picks "ot2s" (no garbled circuit at all), the packed garbled
        batch with the b2a payloads riding the OUTPUT wire labels
        otherwise (the reference runs per-core GC then a separate OT
        round here, collect.rs:419-482) — and both messages cross as a
        STREAM OF ROW CHUNKS: ``secure.level_chunks`` cuts the level's
        ``B = F*C*N`` tests into K runs of whole planar blocks, from
        ``(B, S, W, path)`` alone, so that the larger of a chunk's two
        frames weighs about ``secure.CHUNK_FRAME_BYTES``, and each
        server runs its stages as tasks (``_ev_chunks`` / ``_gb_chunks``)
        so that chunk k+1's kernel and chunk k's fetch run while chunk
        k-1 is on the socket and the peer works on what it has.  One
        device fetch and one frame a chunk and message; a frame is the
        array alone at K = 1 and ``(k, K, array)`` otherwise.  Chunk k takes rows of the level's
        ONE extension, b2a stream and label draw, so every share is
        what the level gone whole (K = 1: buckets under 16 in the
        benchmark's cell, every small test) gives, bit for bit, and the
        frames side by side are its two messages
        (tests/test_secure_chunks.py).  The counter ``secure_chunks``
        says K, level by level, and ``secure_chunk_programs`` the device
        programs handed over inside its ``otext`` and ``b2a`` spans: one
        a span, 2 x K a level on either server (``secure``'s chunk
        functions are one jitted program each, the chunk's first test
        traced).  The row-sharded kernel stage
        (``ks``, parallel/kernel_shard.py) keeps one frame a message: a
        sharded server and an unsharded peer agree only while the level
        is under two chunks.

        The ``gc_ot`` span splits into the secure-kernel phases
        ``otext`` (extension), ``garble``/``eval`` (circuit work — zero
        on the ot2s path), and ``b2a`` (payload table / open + field
        conversion); wire waits are the gc_ot remainder.  The last
        level's exchange is also the span ``leaf_gc_ot``, inside
        ``gc_ot``, with its ``B`` in the counter ``leaf_tests``
        (``_leaf_exchange``).  A chunk
        program whose output a fetch takes (the extensions, the table,
        the circuit) is waited for on that fetch's thread, which stamps
        where its span ends (``_fetch_behind``; counter
        ``secure_fetch_syncs``); the opening is awaited by its stage
        (``_phase_sync``; ``secure_phase_waits``).  What each stage task
        of the chunk pipeline WAITED for, and how the spans that wait
        on a thread split, is in the timers of :class:`_Stage`,
        ``_phase_sync``, ``_fetch_behind``, ``_fetch`` and
        ``_dp_send``.  Those threads are the server's own
        (:class:`_DeviceWaits`): the gauge ``device_waits_high`` is the
        most calls the level had parked there at once, beside
        ``device_wait_threads``."""
        self._waits.take_high()  # this level's high-water mark from here
        with cs.obs.span("fss", level=level) as sp_fss:
            # dispatch time only: the FSS expansion itself overlaps the
            # exchange below (no sync — a block_until_ready here would
            # serialize them); a pipelined leader already ran this
            # stage at frame arrival (``_maybe_pre_expand``).  The
            # device turn serializes dispatch FIFO across tenants and
            # counts the stall fills multi-tenancy exists to create —
            # except for a pre-expanded span, whose dispatch was already
            # counted at frame arrival (note_dispatch).
            pre = (bool(last), int(level), shard) in cs._expand_ready
            async with self._sched.device_turn(cs.key, count=not pre):
                ex = self._expand_stage(cs, level, last, shard)
            children, frontier, flat = (
                ex["children"], ex["frontier"], ex["flat"]
            )
            F_, C, N, S = ex["dims"]
            B = F_ * C * N
            cs.obs.count("gc_tests", B, level=level)
            cs.obs.gauge("ot_batch_size", B * S, level=level)
            # the level's shape: bits a test compares (2 a dimension and
            # radix step), child patterns a node, and the u32 words of
            # its message's payload (the width of the field it counts over)
            W = secure.payload_words(count_field)
            cs.obs.gauge("secure_string_bits", S, level=level)
            cs.obs.gauge("child_patterns", C, level=level)
            cs.obs.gauge("secure_payload_words", W, level=level)
        with cs.obs.span("gc_ot", level=level) as sp_gc, (
            self._leaf_exchange(cs, level, B) if last else _NO_CTX
        ):
            w = secure.alive_weight(frontier.alive, cs.alive_keys, C)
            # crawl counter makes every garbling's randomness unique even
            # if a leader re-crawls a level without reset (seed reuse with
            # a fixed R = s would leak cross-run equality deltas to the
            # evaluator)
            cs._crawl_ctr += 1
            gc_seed = secure.derive_seed(cs._sec_seed, 1, level, cs._crawl_ctr)
            b2a_seed = secure.derive_seed(cs._sec_seed, 2, level, cs._crawl_ctr)
            # the leader names the path per verb (like ``garbler``) so
            # both servers always agree on the wire format even when a
            # bench/parity leader overrides its own config; absent, the
            # server's config decides
            path = secure.ot_path(S, ot_path or self.cfg.ot_path)
            cs.obs.count(f"ot_path_{path}", level=level)
            ks = ex.get("kernel")
            # the row-sharded stage keeps its one frame a message
            chunks = (
                [(0, B)] if ks is not None
                else secure.level_chunks(B, S, W, path)
            )
            cs.obs.count("secure_chunks", len(chunks), level=level)
            # what this pass adds to these goes into its ``secure_level``
            # instant: programs handed over, and where they were waited for
            passed = ("chunk_programs", "fetch_syncs", "phase_waits")
            before = [
                cs.obs.counter_value(f"secure_{n}", level=level) for n in passed
            ]
            if cs._mesh is not None:
                # per-level kernel layout: the active row-shard count (1
                # = the degraded gather path) feeds the mesh report
                # section and the acceptance gate (kernel_gather ~ 0)
                cs.obs.gauge(
                    "kernel_shards", ks.k if ks is not None else 1,
                    level=level,
                )
            if self.server_id == garbler:  # garbler/sender + OT-ext sender
                if ks is not None:
                    u = await self._dp_recv(cs)
                    # ROW-SHARDED kernel stage: extension, payload pair,
                    # and the equality kernel all run per mesh shard
                    # (parallel/kernel_shard.py); the frame reads back
                    # per shard and reassembles positionally — nothing
                    # gathers onto one device
                    with cs.obs.span("h2d", level=level):
                        u = kernel_shard.put_u(ks, u)
                    with cs.obs.span("otext", level=level):
                        q, idx0 = self._dispatched(
                            cs, level, kernel_shard.snd_extend,
                            ks, cs._ot_snd, u,
                        )
                        await self._phase_sync(cs, level, q)
                    kphase = "b2a" if path == "ot2s" else "garble"
                    with cs.obs.span(kphase, level=level):
                        planes, vals = self._dispatched(
                            cs, level, kernel_shard.gb_kernel,
                            ks, cs._ot_snd.s_block, q, flat, gc_seed,
                            b2a_seed, count_field, garbler, path, idx0,
                        )
                        await self._phase_sync(cs, level, planes)
                    self._zero_phases(
                        cs,
                        level, "eval",
                        *(("garble",) if path == "ot2s" else ("b2a",)),
                    )
                    cs.obs.count("device_fetches", ks.k, level=level)
                    # msg_wire starts the per-shard D2H copies itself
                    with cs.obs.span("d2h", level=level):
                        msg_np = await asyncio.to_thread(
                            kernel_shard.msg_wire, ks, planes
                        )
                    await self._dp_send(cs, msg_np)
                else:
                    vals = await self._gb_chunks(
                        cs, level, flat, chunks, B, W, path, count_field,
                        garbler, gc_seed, b2a_seed,
                    )
            else:  # evaluator + OT receiver (inputs stay on device: each
                # np.asarray here would be a blocking device->host fetch)
                if ks is not None:
                    with cs.obs.span("otext", level=level):
                        u_arr, t_rows, idx0 = self._dispatched(
                            cs, level, kernel_shard.rcv_extend,
                            ks, cs._ot_rcv, flat,
                        )
                        await self._phase_sync(cs, level, u_arr)
                    cs.obs.count("device_fetches", ks.k, level=level)
                    # u_wire starts the per-shard D2H copies itself
                    with cs.obs.span("d2h", level=level):
                        u_np = await asyncio.to_thread(
                            kernel_shard.u_wire, ks, u_arr
                        )
                    await self._dp_send(cs, u_np)
                    bmsg = await self._dp_recv(cs)
                    with cs.obs.span("h2d", level=level):
                        bmsg = kernel_shard.put_msg(
                            ks, bmsg, count_field, path
                        )
                    kphase = "b2a" if path == "ot2s" else "eval"
                    with cs.obs.span(kphase, level=level):
                        vals = self._dispatched(
                            cs, level, kernel_shard.ev_open,
                            ks, t_rows, flat, bmsg, count_field, path, idx0,
                        )
                        await self._phase_sync(cs, level, vals)
                    self._zero_phases(
                        cs,
                        level, "garble",
                        *(("eval",) if path == "ot2s" else ("b2a",)),
                    )
                else:
                    vals = await self._ev_chunks(
                        cs, level, flat, chunks, B, W, path, count_field
                    )
            # the pad index of this direction's extension session never
            # resets and is 64 bits wide (otext.index_base): a session
            # that has passed 2^32 OTs says so
            evaluates = self.server_id != garbler
            ot = cs._ot_rcv if evaluates else cs._ot_snd
            index_high = ot.consumed >> 32
            cs.obs.gauge("ot_index_high", index_high, level=level)
            # the span log carries no gauges: under fhh-trace the level's
            # K, what its evaluator held (the gauge ``_ev_chunks`` has
            # just set), the index's high word and its shape are an instant
            # (scripts/trace_spans.py ``secure_levels``)
            obstrace.instant(
                "secure_level", comp=cs.obs.name, level=int(level),
                chunks=len(chunks), index_high=index_high,
                # device programs of this pass's ``otext`` + ``b2a`` spans
                # (the counter ``secure_chunk_programs``): 2 a chunk; of
                # them and the circuit's, how many a fetch's thread
                # waited for and how many a stage awaited
                **{
                    n.removeprefix("chunk_"):
                    cs.obs.counter_value(f"secure_{n}", level=level) - b
                    for n, b in zip(passed, before)
                },
                string_bits=S, patterns=C, payload_words=W,
                t_rows_held=cs.obs.gauge_value(
                    "secure_t_rows_held_bytes", level=level
                ) if evaluates and ks is None else 0,
            )
        with cs.obs.span("field", level=level) as sp_field:
            if ks is not None:
                # test-sharded b2a shares: scatter into the (F, C, N)
                # frame per shard, alive-gate, and psum back over ICI —
                # the kernel-stage twin of ServerMesh.node_share_sums
                cs.obs.gauge("data_shards", cs._mesh.shards, level=level)
                with cs.obs.span("ici_reduce", level=level):
                    out = kernel_shard.share_sums(
                        ks, count_field, vals, w, F_, C, N
                    )
                    shares = await _fetch(out, cs.obs, waits=self._waits)
            else:
                vals = vals.reshape((F_, C, N) + count_field.limb_shape)
                shares = await self._reduced_fetch(
                    cs, level, secure.node_share_sums,
                    count_field, vals, jnp.asarray(w),
                )
        # the most thread calls this server had parked in waits for the
        # device at once in the level, beside the threads it has for them
        cs.obs.gauge("device_waits_high", self._waits.take_high(), level=level)
        cs.obs.gauge("device_wait_threads", self._waits.workers, level=level)
        cs.obs.observe(
            "level_latency", sp_fss.seconds + sp_gc.seconds + sp_field.seconds
        )
        cs.stash_children(level, shard, children)
        return shares

    @staticmethod
    def _parse_shard(req):
        s = (req or {}).get("shard")
        return None if s is None else (int(s[0]), int(s[1]))

    async def _mesh_guard(self, cs, level, thunk):
        """Device-loss containment for the multi-chip server: fire any
        scheduled mesh chaos at the crawl boundary (the consumed-once
        :class:`resilience.chaos.MeshChaos` schedule), and on a mesh
        fault recover IN PLACE — a lost device
        is NOT a lost server.  ``state_lost`` (kill) re-shards the
        frontier from the newest on-disk checkpoint and rebuilds the
        keys from the host-side upload chunks; a suspect collective
        (drop) just re-runs.  Either way the crawl re-runs ONCE inside
        the same verb, so the leader sees a slow span, never a fault —
        ``shards_rerun`` counts the cost, ``levels_rerun`` stays zero.

        The chaos hook fires BEFORE any data-plane I/O of the level, so
        the re-run exchanges with the peer exactly once; a real device
        loss mid-exchange desynchronizes the plane and correctly
        escalates through the verb error to the leader's plane_reset +
        retry machinery instead.  The chaos schedule receives the
        SESSION (it clobbers ``frontier``/``_children`` on a kill) —
        whichever tenant's crawl reaches the scheduled level first eats
        the fault, which is exactly the tenant-isolation scenario the
        chaos suite asserts."""
        try:
            if self._mesh_chaos is not None:
                self._mesh_chaos.before_level(cs, int(level))
            return await thunk()
        except reschaos.MeshFaultError as err:
            if cs._mesh is None:
                raise
            await self._mesh_recover(cs, int(level), err)
            return await thunk()

    async def _mesh_recover(self, cs, level: int, err) -> None:
        """Re-shard after a device loss (see :meth:`_mesh_guard`)."""
        cs.obs.count("mesh_faults", level=level)
        cs._expand_ready.clear()  # pre-expanded dispatches are suspect
        state_lost = bool(getattr(err, "state_lost", False))
        if state_lost or cs.frontier is None:
            prev = level - 1
            if cs.ckpt_dir is None or prev not in cs.ckpt_levels():
                # nothing to re-shard from: surface the original fault —
                # the supervising leader owns recovery at that point
                raise RuntimeError(
                    f"mesh device lost at level {level} with no level-"
                    f"{prev} checkpoint to re-shard from"
                ) from err
            cs.keys = None  # device-resident: lost with the shard
            await self.tree_restore({"level": prev}, cs)
            if cs.frontier is None or cs.keys is None:
                # the level stamp existed but the blob was ingest-only
                # (windowed front door between windows): pools came
                # back, crawl state did not — escalate exactly like the
                # no-checkpoint case instead of re-running on None
                raise RuntimeError(
                    f"mesh device lost at level {level}: the level-"
                    f"{prev} checkpoint is ingest-only — no crawl state "
                    "to re-shard from"
                ) from err
            cs.obs.count("mesh_reshards", level=level)
        cs.obs.count("shards_rerun", level=level)
        obs.emit(
            "resilience.mesh_reshard",
            severity="warn",
            server=self.server_id,
            collection=cs.key,
            level=level,
            state_lost=state_lost,
            error=str(err),
        )

    async def tree_crawl(self, req, cs: CollectionSession | None = None) -> np.ndarray:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """-> FE62 shares of per-child counts [F, 2^d] (ref: rpc.rs:60).
        An optional ``shard: (lo, hi)`` restricts the crawl to that node
        span (mid-level retry granularity — the leader assembles)."""
        cs = cs if cs is not None else self._default()
        level = req["level"]
        shard = self._parse_shard(req)
        # a plane reset since the last exchange re-keys this session's
        # channel (fresh coin flip + base-OT) before any wire I/O
        await self._ensure_session_plane(cs)
        if self.cfg.secure_exchange:
            # chip-profiler hook: FHH_PROFILE + FHH_PROFILE_LEVELS=N,...
            # wraps exactly this level's device work in a jax.profiler
            # capture, recorded against the live trace id (obs.trace)
            with obstrace.profile_capture("level", level=int(level)):
                return await self._mesh_guard(
                    cs, level,
                    lambda: self._crawl_counts_secure(
                        cs, level, FE62, garbler=int(req.get("garbler", 0)),
                        shard=shard, ot_path=req.get("ot_path"),
                    ),
                )
        with obstrace.profile_capture("level", level=int(level)):
            counts = await self._mesh_guard(
                cs, level, lambda: self._crawl_counts(cs, level, shard=shard)
            )
        # NB: trusted mode — both servers hold these plaintext counts; the
        # shared-seed mask below is a WIRE-FORMAT shim so the leader's
        # uniform v0 - v1 reconstruction works, not a secrecy mechanism
        # (the reference's hardcoded bogus PRG seed plays the same role,
        # server.rs:331-332).  Secrecy comes from secure_exchange above.
        r = cs.mask_rows(level, shard, counts.shape[-1], f255=False)
        if self.server_id == 0:
            # counts are already host-side; the mask add stays host-side
            # too (FE62.np_add) — the old device add + _fetch cost a
            # device->host fetch per level for a ~KB elementwise op
            return FE62.np_add(counts.astype(np.uint64), r)
        return r

    async def tree_crawl_last(self, req, cs: CollectionSession | None = None) -> np.ndarray:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """-> F255 shares [F, 2^d, 8] for the final level (ref: rpc.rs:61,
        collect.rs:775-916 — BlockPair double-block OT payloads in secure
        mode).  Shares are retained for final_shares re-serving; sharded
        calls bank their span and ``tree_prune_last`` assembles."""
        cs = cs if cs is not None else self._default()
        level = req["level"]
        shard = self._parse_shard(req)
        await self._ensure_session_plane(cs)
        if self.cfg.secure_exchange:
            with obstrace.profile_capture("level", level=int(level)):
                shares = await self._mesh_guard(
                    cs, level,
                    lambda: self._crawl_counts_secure(
                        cs, level, F255, last=True,
                        garbler=int(req.get("garbler", 0)), shard=shard,
                        ot_path=req.get("ot_path"),
                    ),
                )
        else:
            with obstrace.profile_capture("level", level=int(level)):
                counts = await self._mesh_guard(
                    cs, level,
                    lambda: self._crawl_counts(
                        cs, level, last=True, shard=shard
                    ),
                )
            r = cs.mask_rows(level, shard, counts.shape[-1], f255=True)
            if self.server_id == 0:
                c = np.zeros(counts.shape + (8,), np.uint32)
                c[..., 0] = counts
                # host-side limb add (F255.np_add): no device round trip
                shares = F255.np_add(c, r)
            else:
                shares = r
        if shard is None:
            cs._last_shares = shares
        else:
            cs._last_shares = None
            cs._shard_last[int(shard[0])] = shares
        return shares

    async def tree_prune(self, req, cs: CollectionSession | None = None) -> bool:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Fused prune+advance: materialize surviving children
        (ref: rpc.rs:63 tree_prune + collect.rs:918-929).  The sketch DPF
        states advance with the same survivor table."""
        cs = cs if cs is not None else self._default()
        level = req["level"]
        # fhh-lint: disable=host-sync-in-hot-loop (wire input: host numpy)
        parent = np.asarray(req["parent_idx"], np.int32)
        # fhh-lint: disable=host-sync-in-hot-loop (wire input: host numpy)
        pat_bits = np.asarray(req["pattern_bits"], bool)
        n_alive = int(req["n_alive"])
        # radix wire shape: [F', d] is a radix-1 prune, [F', r, d] a
        # fused one (r bits per dim, step-major — the leader derives it
        # from the same crawl_radix_bits knob this session holds)
        r = 1 if pat_bits.ndim == 2 else pat_bits.shape[1]
        if r != cs.crawl_radix(level):
            raise RuntimeError(
                f"prune pattern carries {r} step bit(s) where this "
                f"session's level-{int(level)} round fuses "
                f"{cs.crawl_radix(level)} (crawl_radix_bits mismatch "
                f"between leader and server?)"
            )
        pb1 = pat_bits if pat_bits.ndim == 2 else pat_bits[:, 0, :]
        cs._expand_ready.clear()  # the frontier is about to mutate
        if cs._children is None and cs._shard_children:
            cs._children = cs.assemble_shard_children()
        if cs._children is not None:  # cache from this level's crawl
            if r == 1:
                cs.frontier = collect.advance_from_children(
                    cs._children, parent, pb1, n_alive
                )
            else:
                cs.frontier = collect.advance_from_children_radix(
                    cs._children, parent, pat_bits, n_alive, r
                )
            cs._children = None
        elif r == 1:  # prune without a preceding crawl: re-expand
            cs.frontier = collect.advance(
                cs.keys, cs.frontier, level, parent, pb1, n_alive,
                use_pallas=cs.planar(),
            )
        else:  # fused prune without a crawl cache: re-expand r bits
            _, children = cs.expand(cs.frontier, level, r, True)
            cs.frontier = collect.advance_from_children_radix(
                children, parent, pat_bits, n_alive, r
            )
        if cs._sketch is not None:
            cs.advance_sketch(int(level), parent, pat_bits, n_alive)
            cs._ratchet_digest = sketchmod.transcript_absorb(
                cs._ratchet_digest, int(level), parent, pat_bits, n_alive
            )
        cs.obs.gauge("survivors", n_alive, level=int(level))
        return True

    async def tree_prune_last(self, req, cs: CollectionSession | None = None) -> bool:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Last level keeps no child count states to advance — compact the
        stored leaf count shares down to the survivors
        (ref: collect.rs:931-942).  The sketch DPF does advance once more
        so its F255 leaf payloads can be verified post-prune."""
        cs = cs if cs is not None else self._default()
        cs._expand_ready.clear()  # leaf level: nothing expands past it
        if cs._last_shares is None and cs._shard_last:
            parts = sorted(cs._shard_last.items())
            whole = np.concatenate([p for _, p in parts], axis=0)
            if whole.shape[0] != cs.frontier.f_bucket:
                raise RuntimeError(
                    f"sharded last crawl incomplete: shares cover "
                    f"{whole.shape[0]} of {cs.frontier.f_bucket} slots"
                )
            cs._last_shares = whole
            cs._shard_last.clear()
        if cs._last_shares is None:  # protocol-boundary check: no assert
            raise RuntimeError("tree_prune_last called before tree_crawl_last")
        cs._children = None  # leaf level: nothing advances past it
        # fhh-lint: disable=host-sync-in-hot-loop (wire input: host numpy)
        parent = np.asarray(req["parent_idx"], np.int64)
        # fhh-lint: disable=host-sync-in-hot-loop (wire input: host numpy)
        pattern = np.asarray(req["pattern_bits"], bool)
        n_alive = int(req["n_alive"])
        L = cs.keys.cw_seed.shape[-2]
        if pattern.ndim == 2:  # radix-1 wire shape [F', d]
            d = pattern.shape[1]
            child = (pattern[:n_alive] << np.arange(d)).sum(axis=1)
            base = L - 1
        else:  # fused leaf prune [F', r, d]: step-major fused child id
            r_, d = pattern.shape[1], pattern.shape[2]
            base = L - r_
            if r_ != cs.crawl_radix(base):
                raise RuntimeError(
                    f"leaf prune pattern carries {r_} step bit(s) where "
                    f"this session's tail round fuses "
                    f"{cs.crawl_radix(base)}"
                )
            shift = np.arange(r_)[:, None] * d + np.arange(d)[None, :]
            child = (
                pattern[:n_alive].astype(np.int64) << shift
            ).sum(axis=(1, 2))
        cs._last_shares = cs._last_shares[parent[:n_alive], child]
        if cs._sketch is not None:
            cs.advance_sketch(
                # fhh-lint: disable=host-sync-in-hot-loop (wire input)
                base, np.asarray(req["parent_idx"], np.int32), pattern, n_alive
            )
            cs._ratchet_digest = sketchmod.transcript_absorb(
                cs._ratchet_digest, base, parent, pattern, n_alive
            )
        cs.obs.gauge(
            "survivors", n_alive, level=cs.keys.cw_seed.shape[-2] - 1
        )
        return True

    async def final_shares(self, req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Re-serve the surviving leaves' count shares for leader-side
        reconstruction (ref: rpc.rs:65, collect.rs:993-1004; tree paths
        live with the leader in this design, see protocol/collect.py)."""
        cs = cs if cs is not None else self._default()
        if cs._window_seal_ts is not None:
            # window seal -> hitters served: the server-visible half of
            # the seal-to-hitters SLO (the driver observes its own
            # leader-side copy in WindowedIngest.crawl_window)
            cs.obs.observe(
                "seal_to_hitters", max(0.0, time.time() - cs._window_seal_ts)
            )
            cs._window_seal_ts = None  # one observation per loaded window
        return {"server_id": self.server_id, "shares": cs._last_shares}

    # -- streaming ingest front door (ROADMAP "Streaming ingestion": the
    # online successor of the one-shot add_keys upload).  Pools and the
    # admission gate are PER SESSION (sessions.CollectionSession): each
    # collection has its own token bucket, quotas, and reservoir, so a
    # flooding tenant exhausts only its own gate. --------------------------

    async def submit_keys(self, req, cs: CollectionSession | None = None) -> dict:
        """Streaming key submission into the named window's pool —
        admission-controlled, append-only, idempotent per ``sub_id``.

        Dispatches WITHOUT the verb lock (like ``add_keys``) — ingest
        rides concurrently with a crawl holding the lock, which is what
        lets a window accrue while the previous window's frozen snapshot
        is crawled.  The admission arithmetic (token bucket, quotas,
        reservoir draws) runs in an EXECUTOR behind the session's
        ``_adm_gate``, so a flooding tenant's admission math cannot
        stall the shared event loop; the one suspension point
        re-validates the dup/seal state before any pool mutates (the
        append itself never suspends).

        Req: ``{window, sub_id, client_id, keys: chunk}`` plus an
        optional ``mirror`` dict carrying the GATE server's verdict —
        the leader-side driver gets the admission decision from server 0
        and replays it onto server 1, so the two pools stay positionally
        identical (admission must never diverge between the servers).

        Verdicts: ``{"admitted": True, slot}``, ``{"admitted": False,
        "shed": True}`` (reservoir mode — final), or ``{"admitted":
        False, "overloaded": True, scope, retry_after_s}`` (retryable:
        the client's RetryPolicy backs off and re-attempts)."""
        cs = cs if cs is not None else self._default()
        window = int(req["window"])
        sub_id = str(req["sub_id"])
        # fhh-lint: disable=chunked-device-readback (wire input: pickled host numpy, no device involved)
        chunk = tuple(np.asarray(a) for a in req["keys"])
        n_keys = int(chunk[0].shape[0])
        # malicious mode: the client's sketch material (MAC'd payload
        # DPFs + triples) rides the SAME entry tuple — the pool's slot
        # semantics (append, reservoir replace) and the checkpoint's
        # generic leaf flattening then cover it for free, and
        # window_load splits the leaves back apart
        if req.get("sketch") is not None:
            if not self.cfg.malicious:
                raise RuntimeError(
                    "sketch material submitted to a semi-honest "
                    "collector (cfg.malicious is off)"
                )
            # fhh-lint: disable=chunked-device-readback (wire input: pickled host numpy, no device involved)
            chunk = chunk + tuple(np.asarray(a) for a in req["sketch"])
        elif self.cfg.malicious:
            raise RuntimeError(
                "malicious mode: submit_keys requires the client's "
                "sketch chunk alongside its keys"
            )
        pool = cs.ingest_pool(window)
        prev = pool.verdicts.get(sub_id)
        if prev is not None:
            # at-least-once delivery made safe: a replayed submission
            # (reconnect replay under a new req_id, recovery journal
            # replay) answers its RECORDED verdict — the pool and the
            # reservoir RNG are untouched, so nothing double-admits
            cs.obs.count("pool_dup_submits")
            return dict(prev, dup=True)
        if pool.sealed:
            raise RuntimeError(
                f"ingest window {window} is sealed — submit into a later "
                "window"
            )
        mirror = req.get("mirror")
        if mirror is not None:
            # mirror replay never suspends: the gate server's verdict is
            # applied positionally, so the two pools stay identical
            resp = pool.apply_mirror(
                sub_id, chunk, mirror, str(req.get("client_id", ""))
            )
        else:
            v = await cs._admission.admit_offloaded(
                pool.wa, str(req.get("client_id", "")), n_keys,
                gate=cs._adm_gate,
            )
            # the executor await suspended this task: another frame may
            # have replayed this sub_id or sealed the window meanwhile —
            # re-validate before the (non-suspending) append mutates
            prev = pool.verdicts.get(sub_id)
            if prev is not None:
                cs.obs.count("pool_dup_submits")
                return dict(prev, dup=True)
            if pool.sealed:
                raise RuntimeError(
                    f"ingest window {window} sealed during admission — "
                    "submit into a later window"
                )
            resp = pool.apply(sub_id, chunk, v)
        if resp.get("admitted"):
            cs.obs.count("pool_admitted_keys", n_keys)
        return resp

    async def window_seal(self, req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Freeze the named window at its boundary: no further
        submissions land in it (later ``submit_keys`` name later
        windows); returns the pool stats.  Idempotent — re-sealing a
        sealed window (recovery replays) returns the same stats."""
        cs = cs if cs is not None else self._default()
        w = int(req["window"])
        pool = cs._ingest_pools.get(w)
        if pool is None:
            pool = cs.ingest_pool(w)  # sealing an idle window is legal
        want_root = req.get("sk_root")
        if want_root is not None:
            # recovery re-seal: the driver hands back the ORIGINAL
            # window root it banked at first seal, so a journal-rebuilt
            # pool (restarted server, no usable ingest checkpoint)
            # still commits the identical challenge root — never a
            # fresh one (same slabs under a new root = <r - r', x>)
            want_root = np.array(want_root, np.uint32)
            if pool.sk_root is None:
                pool.sk_root = want_root
            elif not np.array_equal(pool.sk_root, want_root):
                raise RuntimeError(
                    f"window_seal: window {w} already committed a "
                    "different sketch root (recovery replayed stats "
                    "from another life?)"
                )
        if not pool.sealed:
            if self.cfg.malicious and pool.sk_root is None:
                # the window's coin-flip commitment: derived from the
                # SESSION coin flip + window id at the seal boundary,
                # then carried by the seal stats and every ingest
                # checkpoint — the per-window twin of the batch path's
                # tree_init root commit
                await self._ensure_session_plane(cs)
                pool.sk_root = sketchmod.window_root(cs._sketch_seed, w)
            pool.sealed = True
            # the seal instant starts this window's seal-to-hitters SLO
            # clock (observed at final_shares of the crawl that loads it)
            pool.sealed_at = time.time()
            obs.emit(
                "ingest.window_sealed",
                server=self.server_id,
                collection=cs.key,
                window=w,
                keys=pool.keys,
                subs=len(pool.entries),
                shed=pool.shed_keys,
            )
        return pool.stats()

    async def window_load(self, req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Materialize a SEALED window's frozen pool as the crawl's key
        batch (the streaming twin of the ``add_keys`` upload): the crawl
        state resets to empty, ``keys_parts`` becomes the pool's
        admitted chunks in slot order, and the normal ``tree_init`` →
        level loop runs on it — while ``submit_keys`` keeps landing in
        later windows.  Ingest pools and checkpoint files are untouched;
        consumed EARLIER windows are dropped (bounded live windows)."""
        cs = cs if cs is not None else self._default()
        w = int(req["window"])
        pool = cs._ingest_pools.get(w)
        if pool is None:
            raise RuntimeError(f"window_load: no ingest pool for window {w}")
        if not pool.sealed:
            raise RuntimeError(f"window_load: window {w} is not sealed")
        if not pool.entries:
            raise RuntimeError(f"window_load: window {w} admitted no keys")
        nk = len(IbDcfKeyBatch._fields)
        has_sketch = len(pool.entries[0]) > nk
        # every refusal BEFORE any state mutates (the PR-4 contract): a
        # half-loaded window must never leave the session with this
        # window's sketch material but no committed root — a later
        # tree_init would commit the live coin flip instead, and a
        # retried window would re-open the same Beaver slabs under a
        # different challenge (<r - r', x>)
        if self.cfg.malicious and not has_sketch:
            raise RuntimeError(
                f"window_load: malicious mode but window {w} carries no "
                "sketch material (submitted before cfg.malicious?)"
            )
        if has_sketch and pool.sk_root is None:
            raise RuntimeError(
                f"window_load: window {w} carries sketch material "
                "but no committed challenge root (sealed by a "
                "pre-sketch server?)"
            )
        cs.clear_crawl_state()  # per-window sketch state clears with it
        cs.key_planes = None  # the window's pool is the key set now
        cs.keys_parts = [IbDcfKeyBatch(*e[:nk]) for e in pool.entries]
        if has_sketch:
            # the sketch leaves ride each entry tuple (submit_keys
            # appended them): split them back into upload-chunk form and
            # install the window's committed challenge root for the
            # coming tree_init — a recovered window re-loads the SAME
            # root from the restored pool, so its re-run replays the
            # identical challenge sequence
            treedef = jax.tree.structure(_SKETCH_TREEDEF)
            cs._sketch_parts = [
                jax.tree.unflatten(treedef, list(e[nk:]))
                for e in pool.entries
            ]
            cs._window_sketch_root = np.array(pool.sk_root, np.uint32)
        cs._window_seal_ts = pool.sealed_at  # seal-to-hitters SLO clock
        for old in [k for k in cs._ingest_pools if k < w]:
            del cs._ingest_pools[old]
        obs.emit(
            "ingest.window_loaded",
            server=self.server_id,
            collection=cs.key,
            window=w,
            keys=pool.keys,
            sketch=has_sketch,
        )
        return {
            "window": w, "keys": pool.keys, "subs": len(pool.entries),
            "sketch": has_sketch,
        }

    # -- fleet migration verbs (protocol/fleet.py: live session
    # placement across host pairs) ----------------------------------------

    async def session_export(self, req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Bank this session's migratable state as a stamped blob for a
        ``session_import`` on another host pair, or — ``retire`` mode —
        drop the source copy after a confirmed transfer.

        Export quiesces at a window/level boundary only: a session with
        mid-level crawl caches (children banked between crawl and prune,
        sharded spans in flight) refuses loudly — migrating a torn level
        would strand half an exchange on each pair.  The blob is the
        ingest form of the PR-4/7 checkpoint (pools, per-``sub_id``
        verdicts, quota ledgers, reservoir RNG state, and each sealed
        window's committed challenge root), stamped with this server's
        boot id and a per-session export epoch so the importer can
        refuse a replayed or double-applied transfer.  Crawl state is
        NOT exported: windowed crawls rebuild it from the pools
        (``window_load``), and the destination pair re-keys its own
        base-OT/coin-flip plane — per-window challenge roots ride the
        pools, so a migrated malicious window still replays the
        IDENTICAL challenge.

        Retire mode (``{"retire": True, "epoch": E}``): called after the
        destination confirmed its import — drops every retained window
        pool (sealed ones included: bounded retention only evicts on
        idle, and a migrated-away tenant would otherwise pin its pools
        on this host forever) and the crawl state, leaving the session
        idle-evictable."""
        cs = cs if cs is not None else self._default()
        req = req or {}
        if req.get("retire"):
            epoch = int(req.get("epoch", -1))
            if epoch <= 0 or epoch != cs._export_epoch:
                raise RuntimeError(
                    f"session_export: retire epoch {epoch} does not match "
                    f"the last export ({cs._export_epoch}) — refusing to "
                    "drop state that was never transferred"
                )
            dropped = len(cs._ingest_pools)
            cs._ingest_pools.clear()
            cs.clear_crawl_state()
            cs.drop_keys()
            cs.alive_keys = None
            if cs.ckpt_dir is not None and os.path.exists(self._export_path(cs)):
                os.remove(self._export_path(cs))
            # the migrated-away tenant must not hold this pair's
            # progress-age placement signal high forever
            self._sched.forget(cs.key)
            cs.obs.count("sessions_retired")
            obs.emit(
                "fleet.session_retired",
                server=self.server_id,
                collection=cs.key,
                pools_dropped=dropped,
            )
            return {"retired": True, "pools_dropped": dropped}
        # mid-level = in-flight expand caches (children banked between
        # crawl and prune, sharded spans mid-assembly).  _last_shares
        # alone is NOT mid-level: it lingers after a COMPLETED crawl
        # (final_shares re-serves it) and the destination rebuilds crawl
        # state from the pools via window_load anyway.
        if (cs._children is not None or cs._shard_children
                or cs._shard_last):
            raise RuntimeError(
                "session_export: session is mid-level — a migration "
                "quiesces at a window/level boundary only"
            )
        if cs.ckpt_dir is None:
            raise RuntimeError(
                "session_export: no checkpoint dir configured "
                "(start the server with FHH_CKPT_DIR set)"
            )
        blob = {
            "ing_only": np.bool_(True),
            "sess": np.str_(cs.key),
            "level": np.int64(-1),
            # crawl-radix stamp: the destination session must crawl the
            # same fused level grid (its window_load rebuilds crawl state
            # and its sketch ratchet absorbs one fused level per step)
            "radix": np.int64(cs._radix),
        }
        cs.ingest_ckpt_fields(blob)
        cs._export_epoch += 1
        blob["xp_boot"] = np.str_(self._boot_id)
        blob["xp_epoch"] = np.int64(cs._export_epoch)
        path = self._export_path(cs)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            np.savez(f, **blob)
        os.replace(tmp, path)
        cs.obs.count("session_exports")
        obs.emit(
            "fleet.session_exported",
            server=self.server_id,
            collection=cs.key,
            epoch=cs._export_epoch,
            windows=sorted(cs._ingest_pools),
            path=path,
        )
        return {
            "path": path,
            "boot": self._boot_id,
            "epoch": cs._export_epoch,
            "windows": sorted(cs._ingest_pools),
        }

    def _export_path(self, cs: CollectionSession) -> str:
        # inside the session's checkpoint namespace but OUTSIDE the
        # level-stamp grammar ("xport" never parses as an int), so
        # ckpt_levels/ckpt_prune ignore it
        return os.path.join(cs.ckpt_dir, f"{cs.ckpt_prefix()}xport.npz")

    async def session_import(self, req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Adopt a migrated (or orphaned) session's banked state.

        Two sources: ``{"path", "boot", "epoch"}`` names a
        ``session_export`` blob (live migration — the stamps must match
        the announced export, and a (boot, epoch) pair imports at most
        ONCE: double-applying a transfer would double-land its in-flight
        ``sub_id`` replays); ``{"level": N}`` names this session's own
        checkpoint-namespace blob (whole-host failover — the orphan's
        newest ingest checkpoint in the shared store, no stamps).

        Validate-before-mutate: a torn/corrupt blob, a wrong-collection
        stamp, a replayed stamp, or a torn ``ing_*`` tail refuses with
        the live state of BOTH hosts untouched.  Only ingest-form blobs
        import — crawl state rebuilds from the pools via ``window_load``,
        and the per-session secure plane is force re-keyed (fresh
        coin flip + base-OT against THIS pair's peer at the next
        data-plane verb), never carried across hosts."""
        cs = cs if cs is not None else self._default()
        req = req or {}
        if cs.ckpt_dir is None:
            raise RuntimeError("session_import: no checkpoint dir configured")
        stamp = None
        if req.get("path") is not None:
            path = str(req["path"])
            stamp = (str(req.get("boot", "")), int(req.get("epoch", 0)))
        else:
            path = cs.ckpt_path(int(req["level"]))
        if not os.path.exists(path):
            raise RuntimeError(f"session_import: no blob at {path}")
        try:
            with np.load(path) as npz:
                z = {k: npz[k] for k in npz.files}
        # np.load surfaces torn/partial writes as BadZipFile/ValueError/
        # EOFError depending on where the file was cut (same boundary as
        # tree_restore)
        except Exception as e:  # fhh-lint: disable=broad-except (corrupt-blob classification: every load failure maps to the same loud refusal; state is untouched)
            raise RuntimeError(
                f"session_import: corrupt or truncated blob at {path} "
                f"({type(e).__name__}: {e})"
            ) from e
        if "sess" in z and str(z["sess"]) != cs.key:
            raise RuntimeError(
                f"session_import: blob at {path} is stamped for "
                f"collection {str(z['sess'])!r}, not {cs.key!r}"
            )
        if stamp is not None:
            got = (str(z.get("xp_boot", "")), int(z.get("xp_epoch", 0)))
            if got != stamp:
                raise RuntimeError(
                    f"session_import: blob at {path} carries stamp {got}, "
                    f"not the announced export {stamp} (stale file?)"
                )
            if stamp in cs._import_seen:
                raise RuntimeError(
                    f"session_import: export {stamp} was already imported "
                    "— double-applying a transfer would double-land its "
                    "in-flight submissions"
                )
        if not ("ing_only" in z and bool(z["ing_only"])):
            raise RuntimeError(
                "session_import: only ingest-form blobs migrate (crawl "
                "state rebuilds from the pools via window_load); use "
                "add_keys + tree_restore for a crawl-level checkpoint"
            )
        # crawl-radix stamp (validate-before-mutate, both directions):
        # an exported k=2 session refuses to land in a k=1 session and
        # vice versa — the fused level grid and the sketch ratchet's
        # per-step absorption must match across the migration
        xp_radix = int(z["radix"]) if "radix" in z else 1
        if xp_radix != cs._radix:
            raise RuntimeError(
                f"session_import: blob at {path} was exported under "
                f"crawl_radix_bits={xp_radix}; this session runs "
                f"crawl_radix_bits={cs._radix}"
            )
        # validate the whole ing_* tail BEFORE any state mutates
        parsed = cs.ingest_validate(z, path)
        # -- all checks passed: mutate ------------------------------------
        if parsed is not None:
            cs.ingest_restore_apply(parsed)
            if taint_guard.enabled():
                # the reconstructed pool entries are the clients' key
                # SHARES (and in malicious mode their sketch material):
                # secret bytes this host never saw before the import —
                # register them so the obs sinks keep refusing them
                for pool in cs._ingest_pools.values():
                    for entry in pool.entries:
                        for leaf in entry:
                            taint_guard.register(
                                "CollectionSession._imported_pool_shares",
                                leaf,
                            )
        else:
            cs._ingest_pools.clear()  # an empty export imports as empty
        if stamp is not None:
            cs._import_seen.add(stamp)
        # force a fresh per-session plane handshake against THIS pair's
        # peer: OT endpoints and the coin flip never migrate across
        # hosts (epoch 0 = never keyed — _ensure_session_plane re-keys
        # lazily at the next data-plane verb)
        cs._ot = None
        cs._ot_snd = None
        cs._ot_rcv = None
        cs._sec_seed = None
        cs.plane_epoch = 0
        cs.obs.count("session_imports")
        obs.emit(
            "fleet.session_imported",
            server=self.server_id,
            collection=cs.key,
            windows=sorted(cs._ingest_pools),
            path=path,
        )
        return {"windows": sorted(cs._ingest_pools)}

    # -- resilience verbs (no reference analogue: the reference's only
    # recovery verb is reset, server.rs:64-69) ---------------------------

    async def status(self, _req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Cheap probe for the supervising leader: the boot id tells a
        reconnecting leader whether this is the same process (replay is
        safe) or a restart (state is gone — restore path), and the dedup
        counter lets recovery tests assert no verb double-applied.

        The crawl/ingest/mesh fields describe the CALLING session (so
        single-tenant probes read exactly as before); ``sessions`` is
        the multi-tenant rollup — every live collection's phase, level,
        queue depth, replay-dedup entries, and checkpoint levels, plus
        the tenant scheduler's stall-fill accounting."""
        cs = cs if cs is not None else self._default()
        # live device-memory sample (obs.devmem): the status probe IS
        # the operator's HBM tick — watermark/delta land on this
        # server's registry, scrape-visible and report-visible
        obsdevmem.sample(self.obs, phase="status")
        sess = self._sessions_status()
        # alert tick: session rules over the rows just built, registry
        # rules over everything live — fire-once per (rule, subject), so
        # repeated probes of a stalled tenant yield ONE alert event
        obsalerts.evaluate_sessions(
            sess["per_session"], source=f"server{self.server_id}"
        )
        obsalerts.evaluate_registries()
        return {
            "boot_id": self._boot_id,
            "collection": cs.key,
            # wall clock for the leader's trace clock-offset handshake
            # (obs.trace: NTP-style midpoint against the caller's
            # send/recv instants; piggybacked here and on __hello__)
            "clock": round(time.time(), 6),
            "has_keys": (
                cs.keys is not None
                or bool(cs.keys_parts)
                or cs.key_planes is not None
            ),
            "has_frontier": cs.frontier is not None,
            "dedup_hits": int(self.obs.counter_value("dedup_hits")),
            "plane_resets": int(self.obs.counter_value("plane_resets")),
            # numerically-ordered checkpoint levels on disk — the
            # supervisor's "latest checkpoint" source of truth (string
            # sorts would order l9 after l10 from level 10 on)
            "ckpt_levels": cs.ckpt_levels(),
            # streaming front-door health (pool occupancy per window,
            # unsealed queue depth, admit/shed/reject counters)
            "ingest": cs.ingest_status(),
            # multi-chip mesh health (None on a single-device server):
            # device/shard counts, per-shard client occupancy, and the
            # reduction/recovery instruments the run report rolls up
            "mesh": self._mesh_status(cs),
            # multi-tenant rollup (sessions.SessionTable + tenancy)
            "sessions": sess,
            # fleet identity + migration accounting (protocol/fleet.py):
            # which registered pair this server is half of, and how many
            # sessions moved through it
            "fleet": {
                "pair": os.environ.get("FHH_FLEET_PAIR", ""),
                "boot_id": self._boot_id,
                "session_exports": int(
                    cs.obs.counter_value("session_exports")
                ),
                "session_imports": int(
                    cs.obs.counter_value("session_imports")
                ),
                # placement signals in the shape FleetDirectory.note_load
                # consumes — the supervisor's probe loop forwards them
                "load": self._sched.fleet_load(),
            },
            # live SLO quantiles (obs.hist): per-level crawl latency,
            # per-verb RPC latency, seal-to-hitters — p50/p95/p99 from
            # the calling session's fixed-bucket histograms
            "slo": cs.obs.hists_summary(),
            # alert transitions (obs.alerts): every rule that fired in
            # this process, newest last — the supervisor's "is anything
            # wrong" answer without scraping /metrics
            "alerts": obsalerts.status_section(),
        }

    def _sessions_status(self) -> dict:  # fhh-race: atomic (read-only rollup over the session table in one event-loop slice; per-session reads are point-in-time probes for an operator, not protocol state)
        """The ``status.sessions`` section: one row per live collection
        plus the tenant scheduler's stall-fill accounting."""
        dedup_by: dict[str, int] = {}
        with guards.unguarded(
            "status rollup: point-in-time operator probe over the "
            "replay table (atomic contract on _sessions_status)"
        ):
            for sess in self._sessions.values():
                key = getattr(sess, "collection", DEFAULT_COLLECTION)
                dedup_by[key] = dedup_by.get(key, 0) + len(sess.cache)
            rows = {}
            for key, cs in self._table.items():
                sp = cs.obs.current_span()
                pools = list(cs._ingest_pools.values())
                rows[key] = {
                    "phase": sp.name if sp is not None else None,
                    "level": sp.level if sp is not None else None,
                    "queue_depth": sum(
                        p.keys for p in pools if not p.sealed
                    ),
                    "dedup_entries": dedup_by.get(key, 0),
                    "ckpt_levels": cs.ckpt_levels(),
                    "has_frontier": cs.frontier is not None,
                    "plane_epoch": cs.plane_epoch,
                    # seconds since this session last COMPLETED a verb:
                    # a wedged tenant shows a growing gap here while the
                    # process-wide heartbeat only names the active one
                    "last_progress_s": round(
                        max(0.0, time.monotonic() - cs.last_progress), 3
                    ),
                }
        return {
            "count": len(self._table),
            "scheduler": self._sched.stats(),
            "per_session": rows,
        }

    def _mesh_status(self, cs: CollectionSession) -> dict | None:
        if cs._mesh is None:
            return None
        return {
            "data_devices": cs._mesh.n_devices,
            "data_shards": cs._mesh.shards,
            "shard_clients": cs._mesh.occupancy(),
            "ici_reduce_seconds": round(
                cs.obs.timer_seconds("ici_reduce"), 6
            ),
            "reshards": int(cs.obs.counter_value("mesh_reshards")),
            "faults": int(cs.obs.counter_value("mesh_faults")),
            # row-sharded secure kernel stage (parallel/kernel_shard.py):
            # the last level's active shard count / the crawl's deepest
            # (None before any secure crawl).  The LAYOUT signal is the
            # gauge + the kernel_gathers counter (exactly the levels
            # that ran the degraded gather path — 0 of them on a fully
            # sharded crawl); kernel_gather_seconds is that gather's
            # DISPATCH time (the transfer itself completes lazily under
            # the level's later fetch), a supplement, not the detector
            "kernel_shards": cs.obs.gauge_value("kernel_shards"),
            "kernel_shards_max": cs.obs.gauge_max("kernel_shards"),
            "kernel_gathers": int(cs.obs.counter_value("kernel_gathers")),
            "kernel_gather_seconds": round(
                cs.obs.timer_seconds("kernel_gather"), 6
            ),
            # malicious-secure sketch verify layout (parallel/
            # sketch_shard.py): the last verify's active shard count
            # (None before any sketch level; 1 = the single fused
            # program / the meshless path)
            "sketch_shards": cs.obs.gauge_value("sketch_shards"),
        }

    async def tree_checkpoint(self, req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Persist the crawl state AFTER the given level completed:
        frontier eval states + node liveness + client liveness + the
        state layout flag (planar Pallas vs interleaved XLA — a restore
        under the other engine converts).  Keys are NOT in the blob (the
        leader re-uploads them on a restart — they are the bulk of the
        bytes and the leader already holds them).  Atomic tmp+rename so
        a crash mid-write never corrupts the previous checkpoint.

        Session-namespaced: each collection writes into its own filename
        namespace (sessions.CollectionSession.ckpt_prefix — the default
        session keeps the legacy names) and every blob is STAMPED with
        its collection key (``sess`` field), so a blob renamed across
        namespaces refuses to restore instead of resurrecting another
        tenant's tree.

        Malicious (sketch) mode checkpoints too: the blob carries the
        frontier-following sketch DPF states, the stored (yet-unopened)
        pair shares, the committed ratchet root, and the transcript
        digest — everything a re-run needs to replay each level's
        challenge bit-identically (see ``sketch.py``'s ratchet note).

        Streaming ingest pools ride EVERY checkpoint (``ing_*`` fields):
        a server killed mid-window restores its admitted pools — entry
        slots, recorded per-``sub_id`` verdicts, quota ledgers, and the
        reservoir sampler's RNG state — so recovery neither loses nor
        double-counts admitted keys and the shed stream resumes
        seed-identically.  A server with pools but no frontier (between
        windows) may checkpoint too: the blob is then ingest-only."""
        cs = cs if cs is not None else self._default()
        if cs.ckpt_dir is None:
            raise RuntimeError(
                "tree_checkpoint: no checkpoint dir configured "
                "(start the server with FHH_CKPT_DIR set)"
            )
        ing_only = bool((req or {}).get("ingest_only"))
        if cs.frontier is None and not cs._ingest_pools:
            raise RuntimeError("tree_checkpoint before tree_init")
        if ing_only and not cs._ingest_pools:
            raise RuntimeError("tree_checkpoint: no ingest pools to persist")
        level = int(req["level"])
        if cs.frontier is not None and not ing_only:
            st = cs.frontier.states
            # ONE stacked fetch for the whole blob (device_get of the
            # pytree), not one sync per plane — each fetch is a blocking
            # device->host round trip
            fetch = {
                "seed": st.seed,
                "bit": st.bit,
                "y_bit": st.y_bit,
                "alive": cs.frontier.alive,
            }
            if cs._sketch is not None:
                fetch["sk_state_seed"] = cs._sketch_states.seed
                fetch["sk_state_t"] = cs._sketch_states.t
                if cs._sketch_pairs is not None:
                    # one entry per fused bit level (radix-2^k crawls
                    # store several); indexed keys keep the npz flat
                    for i, (p, _, _) in enumerate(cs._sketch_pairs):
                        fetch[f"sk_pairs_{i}"] = p
            blob = jax.device_get(fetch)
            blob["alive_keys"] = np.asarray(cs.alive_keys)
            blob["planar"] = np.bool_(cs.planar())
            blob["keys_fp"] = cs.keys_fp()
        else:
            blob = {"ing_only": np.bool_(True)}
        blob["level"] = np.int64(level)
        # the session stamp: restore validates it against the restoring
        # session BEFORE any state mutates (satellite of the PR-4
        # validate-before-mutate contract)
        blob["sess"] = np.str_(cs.key)
        # crawl-radix stamp: a blob written under k=2 holds a frontier at
        # a depth grid (0, 2, 4, …) a k=1 session never visits — restore
        # refuses a mismatch before any state mutates
        blob["radix"] = np.int64(cs._radix)
        cs.ingest_ckpt_fields(blob)
        if cs._sketch is not None:
            blob["sk_pids"] = np.asarray(cs._sketch_pids)
            blob["sk_depth"] = np.int64(cs._sketch_depth)
            blob["sk_root"] = np.asarray(cs._sketch_root, np.uint32)
            blob["sk_digest"] = np.frombuffer(
                cs._ratchet_digest, np.uint8
            )
            if cs._sketch_pairs is not None:
                blob["sk_pairs_depth"] = np.asarray(
                    [dep for (_, dep, _) in cs._sketch_pairs], np.int64
                )
                blob["sk_pairs_last"] = np.asarray(
                    [f is F255 for (_, _, f) in cs._sketch_pairs], bool
                )
        path = cs.ckpt_path(level)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            np.savez(f, **blob)
        os.replace(tmp, path)
        cs.ckpt_prune()
        cs.obs.count("checkpoint_writes", level=level)
        obs.emit(
            "resilience.server_checkpoint",
            server=self.server_id,
            collection=cs.key,
            level=level,
            path=path,
        )
        return {"level": level}

    async def tree_restore(self, req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Reload the :meth:`tree_checkpoint` for the level the leader
        names; returns the completed level so the leader re-runs from
        ``level + 1``.  Requires keys: either still held (transient
        fault, same process) or re-uploaded via ``add_keys`` after a
        restart — and refuses a blob written under a different key
        batch.

        Every validation runs BEFORE any state mutates: a mismatched
        fingerprint, a truncated/corrupt npz, a blob from a deeper
        level than this key batch's tree, or a blob STAMPED for a
        different collection session must fail loudly and leave the
        server's live state exactly as it was.

        Streaming ingest pools restore alongside (``ing_*`` fields, same
        validate-before-mutate contract); an ingest-ONLY blob (written
        between windows, no frontier) restores just the pools and leaves
        the crawl state empty — ``window_load`` rebuilds it."""
        cs = cs if cs is not None else self._default()
        if cs.ckpt_dir is None:
            raise RuntimeError("tree_restore: no checkpoint dir configured")
        want_level = int(req["level"])
        path = cs.ckpt_path(want_level)
        if not os.path.exists(path):
            raise RuntimeError(f"tree_restore: no checkpoint at {path}")
        try:
            with np.load(path) as npz:
                z = {k: npz[k] for k in npz.files}
        # np.load surfaces torn/partial writes as BadZipFile/ValueError/
        # EOFError depending on where the file was cut; all of them mean
        # the same thing at this boundary
        except Exception as e:  # fhh-lint: disable=broad-except (corrupt-blob classification: every load failure maps to the same loud refusal; state is untouched)
            raise RuntimeError(
                f"tree_restore: corrupt or truncated checkpoint at {path} "
                f"({type(e).__name__}: {e})"
            ) from e
        if "sess" in z and str(z["sess"]) != cs.key:
            # session-namespace stamp: a blob renamed (or copied) across
            # collection namespaces must refuse — restoring another
            # tenant's tree into this session would silently serve its
            # heavy hitters under the wrong collection
            raise RuntimeError(
                f"tree_restore: checkpoint at {path} is stamped for "
                f"collection {str(z['sess'])!r}, not {cs.key!r} "
                "(renamed across session namespaces?)"
            )
        # crawl-radix stamp (validate-before-mutate, both directions): a
        # k=2 blob into a k=1 session — or vice versa — refuses with live
        # state untouched (blobs predating the stamp are radix-1 crawls)
        saved_radix = int(z["radix"]) if "radix" in z else 1
        if saved_radix != cs._radix:
            raise RuntimeError(
                f"tree_restore: checkpoint at {path} was written under "
                f"crawl_radix_bits={saved_radix}; this session runs "
                f"crawl_radix_bits={cs._radix} — its level grid never "
                "visits the blob's frontier depth"
            )
        if "ing_only" in z and bool(z["ing_only"]):
            # ingest-only blob: pools back, crawl state untouched-empty.
            # No key requirement — the keys ARE the pools.
            if int(z.get("level", want_level)) != want_level:
                raise RuntimeError(
                    f"tree_restore: checkpoint at {path} is stamped level "
                    f"{want_level} but records level {int(z['level'])} "
                    "(renamed or tampered file)"
                )
            parsed = cs.ingest_validate(z, path)
            if parsed is None:
                raise RuntimeError(
                    f"tree_restore: ingest-only checkpoint at {path} "
                    "carries no ingest pools (truncated write?)"
                )
            cs.ingest_restore_apply(parsed)
            cs.obs.count("checkpoint_restores", level=want_level)
            obs.emit(
                "resilience.server_restore",
                server=self.server_id,
                collection=cs.key,
                level=want_level,
                ingest_only=True,
            )
            return {"level": want_level}
        cs.ready_keys("tree_restore")
        required = {"seed", "bit", "y_bit", "alive", "alive_keys", "level",
                    "planar", "keys_fp"}
        missing = required - set(z)
        if missing:
            raise RuntimeError(
                f"tree_restore: checkpoint at {path} is missing fields "
                f"{sorted(missing)} (truncated write?)"
            )
        if not np.array_equal(z["keys_fp"], cs.keys_fp()):
            raise RuntimeError(
                "tree_restore: checkpoint was written under a different "
                "key batch — re-upload the original keys"
            )
        level = int(z["level"])
        L = cs.keys.cw_seed.shape[-2]
        if level != want_level:
            raise RuntimeError(
                f"tree_restore: checkpoint at {path} is stamped level "
                f"{want_level} but records level {level} (renamed or "
                "tampered file)"
            )
        if level >= L - 1:
            raise RuntimeError(
                f"tree_restore: checkpoint level {level} is deeper than "
                f"this key batch's tree (data_len={L}) — wrong collection"
            )
        n = cs.keys.cw_seed.shape[0]
        # fhh-lint: disable=host-sync-in-hot-loop (restore path: host npz entry, once per recovery)
        alive_keys = np.asarray(z["alive_keys"])
        if alive_keys.shape[0] != n:
            raise RuntimeError(
                "tree_restore: checkpoint client count != key batch"
            )
        has_sketch = bool(cs._sketch_parts) or cs._sketch is not None
        if has_sketch != ("sk_root" in z):
            raise RuntimeError(
                "tree_restore: sketch material mismatch — the checkpoint "
                + ("lacks" if has_sketch else "carries")
                + " sketch state relative to the uploaded keys"
            )
        if has_sketch:
            # the validate-before-mutate contract covers the sketch
            # fields too: a blob with sk_root but a torn/tampered tail
            # must refuse here, not KeyError after the frontier mutated
            sk_req = {"sk_state_seed", "sk_state_t", "sk_pids", "sk_depth",
                      "sk_digest"}
            if "sk_pairs" in z:
                sk_req |= {"sk_pairs_depth", "sk_pairs_last"}
            sk_missing = sk_req - set(z)
            if sk_missing:
                raise RuntimeError(
                    f"tree_restore: checkpoint at {path} is missing sketch "
                    f"fields {sorted(sk_missing)} (truncated write?)"
                )
        # ingest pools validate with everything else (a torn ing_* tail
        # refuses before ANY state mutates); None = pre-streaming blob
        parsed_ing = cs.ingest_validate(z, path)
        # -- all checks passed: mutate ------------------------------------
        with cs.home():
            states = EvalState(
                seed=jax.device_put(z["seed"]),
                bit=jax.device_put(z["bit"]),
                y_bit=jax.device_put(z["y_bit"]),
            )
            saved_planar, planar = bool(z["planar"]), cs.planar()
            if saved_planar != planar:
                states = (
                    collect.to_interleaved(states)
                    if saved_planar
                    else collect.to_planar(states)
                )
            cs.alive_keys = alive_keys
            cs.frontier = collect.Frontier(
                states=states, alive=jax.device_put(z["alive"])
            )
        if cs._mesh is not None:
            # re-shard from the host-side blob: the frontier lands
            # client-axis-sharded across whatever local devices are
            # live — this is the device-loss recovery primitive (a lost
            # device is re-covered by re-placement, not a server restart)
            cs.frontier = cs._mesh.shard_frontier(cs.frontier, planar)
        cs._children = None
        cs._last_shares = None
        cs._shard_children.clear()
        cs._shard_last.clear()
        cs._shard_level = None
        cs._expand_ready.clear()
        if has_sketch:
            if cs._sketch is None:
                cs.concat_sketch()
            cs._sketch_states = dpf.DpfEvalState(
                seed=jax.device_put(z["sk_state_seed"]),
                t=jax.device_put(z["sk_state_t"]),
            )
            # fhh-lint: disable=host-sync-in-hot-loop (restore path: host npz entries, once per recovery)
            cs._sketch_pids = np.asarray(z["sk_pids"])
            cs._sketch_depth = int(z["sk_depth"])
            # fhh-lint: disable=host-sync-in-hot-loop (as above)
            cs._sketch_root = np.asarray(z["sk_root"], np.uint32).copy()
            # fhh-lint: disable=host-sync-in-hot-loop (as above)
            cs._ratchet_digest = np.asarray(
                z["sk_digest"], np.uint8
            ).tobytes()
            if "sk_pairs_0" in z:
                # fhh-lint: disable=host-sync-in-hot-loop (as above)
                depths = np.atleast_1d(np.asarray(z["sk_pairs_depth"]))
                # fhh-lint: disable=host-sync-in-hot-loop (as above)
                lasts = np.atleast_1d(np.asarray(z["sk_pairs_last"]))
                cs._sketch_pairs = [
                    (
                        jax.device_put(z[f"sk_pairs_{i}"]),
                        int(depths[i]),
                        F255 if bool(lasts[i]) else FE62,
                    )
                    for i in range(len(depths))
                ]
            elif "sk_pairs" in z:
                # pre-radix blob form: a single stored pair
                cs._sketch_pairs = [(
                    jax.device_put(z["sk_pairs"]),
                    int(z["sk_pairs_depth"]),
                    F255 if bool(z["sk_pairs_last"]) else FE62,
                )]
            else:
                cs._sketch_pairs = None
        if parsed_ing is not None:
            cs.ingest_restore_apply(parsed_ing)
        cs.obs.count("checkpoint_restores", level=level)
        obs.emit(
            "resilience.server_restore",
            server=self.server_id,
            collection=cs.key,
            level=level,
        )
        return {"level": level}

    async def plane_reset(self, _req, cs: CollectionSession = None) -> bool:  # fhh-race: holds=_verb_lock (dispatched by _dispatch under the SERVER infra lock — the plane is shared infrastructure across sessions; sanitizer-validated)
        """Re-establish the server↔server data plane after a peer loss.

        Only the DIALER (server 0) acts: it drops the dead transport and
        redials under the shared backoff policy; the listener's side is
        re-accepted automatically (``_on_peer`` on its still-bound
        listener).  The plane is SHARED infrastructure: a reset bumps the
        PlaneMux epoch, so EVERY session re-keys its channel (fresh coin
        flip + base-OT) lazily at its next data-plane verb
        (``_ensure_session_plane``) — a tenant that was mid-level fails
        its wedged exchange loudly and its own supervisor re-runs the
        level, exactly the per-tenant recovery story."""
        if self.server_id != 0:
            return True  # listener: re-accept + re-key is automatic
        if self._peer is not None:
            self._peer.close()
        await self._dial_peer()
        self.obs.count("plane_resets")
        obs.emit("resilience.plane_reset", server=self.server_id)
        return True

    async def plane_break(self, _req, cs: CollectionSession = None) -> bool:
        """Forcibly close this server's end of the peer data plane WITHOUT
        re-establishing it — the pipelined leader's quiesce primitive.  A
        faulted pipeline can leave a verb on EITHER server blocked in a
        ``_swap`` recv while holding its session's verb lock (its span
        reached only one server, so the peer's matching frame never
        comes); this verb dispatches OUTSIDE the verb locks (see
        ``_dispatch``) precisely so it can break that wedge: the close
        ends both streams and fails the mux, every channel's blocked
        recv and every queued send raises, the wedged verbs error out
        and release their locks, and the leader's subsequent
        ``plane_reset`` re-keys the plane cleanly."""
        if self._peer is not None:
            self._peer.close()
        self.obs.count("plane_breaks")
        obs.emit("resilience.plane_break", server=self.server_id)
        return True

    async def warmup(self, req, cs: CollectionSession | None = None) -> dict:  # fhh-race: holds=_verb_lock (dispatched only by _dispatch, which holds the session's verb lock; sanitizer-validated)
        """Pre-compile the per-``f_bucket`` crawl programs so bucket
        recompiles stop billing into measured (or production) crawl time:
        for every requested bucket (and every shard-span size it implies
        under ``cfg.crawl_shard_nodes``), run the expand stage and — in
        secure mode — the whole 2PC kernel chain against a THROWAWAY
        in-process OT session with the real key batch's shapes.  Touches
        no protocol state: the live OT sessions, frontier, and data plane
        are never involved, so warmup can run any time after ``add_keys``
        (the leader calls it right after ``tree_init``).

        Multi-tenant: the compiled-program ladder is PROCESS-shared
        (tenancy.WarmLadder) — a shape any session already warmed is
        skipped (``ladder_hits`` in the response), so a new collection
        on a warmed shape pays zero fresh compiles AND zero redundant
        warm executions.  Returns the number of (bucket, span) shapes
        warmed plus the ladder hits."""
        cs = cs if cs is not None else self._default()
        cs.ready_keys("warmup")
        buckets = sorted(
            {int(b) for b in (req or {}).get("f_buckets", []) if int(b) > 0}
        )
        # the requesting leader may name the equality-test path (its own
        # config's, possibly overriding this server's — the same per-req
        # override the crawl verbs honor) and ask for span-sized shapes
        # (a leader that will crawl with secure_whole_level=False)
        ot_path = (req or {}).get("ot_path") or self.cfg.ot_path
        want_spans = bool((req or {}).get("secure_spans"))
        # the leader names its shard layout so a config skew (a leader
        # that believes this server runs k-way sharded when it does not,
        # or vice versa) surfaces at warmup time instead of as mystery
        # recompiles on the measured clock; the server's own mesh is
        # authoritative — warmup always compiles the programs the LIVE
        # crawl will dispatch
        want_devices = (req or {}).get("data_shards")
        have_shards = 1 if cs._mesh is None else cs._mesh.shards
        if want_devices is not None and int(want_devices) > 0:
            # the leader names a DEVICE budget; resolve it exactly like
            # this server resolved its own (visible-device cap, then
            # the largest divisor of the bound client batch) so
            # identically-configured pairs never warn — only real
            # config skew does
            want_shards = smesh._largest_divisor_leq(
                cs.keys.cw_seed.shape[0],
                smesh.resolve_data_devices(int(want_devices)),
            )
            if want_shards != have_shards:
                obs.emit(
                    "warmup.shard_mismatch",
                    severity="warn",
                    server=self.server_id,
                    leader_data_shards=want_shards,
                    server_data_shards=have_shards,
                )
        L = cs.keys.cw_seed.shape[-2]
        shapes = 0
        ladder_hits = 0
        with cs.obs.span("warmup"):
            for b in buckets:
                if (
                    self.cfg.secure_exchange
                    and self.cfg.secure_whole_level
                    and not want_spans
                ):
                    # whole-level secure crawls never shard the GC/OT
                    # batch — warming span-sized programs would compile
                    # shapes no crawl dispatches (and break the
                    # warmed-crawl-compiles-nothing contract's economy)
                    sizes = set()
                else:
                    sizes = {
                        hi - lo
                        for lo, hi in collect.shard_spans(
                            b, self.cfg.crawl_shard_nodes
                        )
                    }
                for fb in sorted(sizes | {b}):
                    with cs.home():
                        fresh = self._warm_bucket(cs, fb, L, ot_path)
                    if fresh:
                        shapes += 1
                    else:
                        ladder_hits += 1
                    # yield between compiles: each can take seconds, and
                    # the control socket must keep answering keepalives
                    await asyncio.sleep(0)
        # the warmup ladder is now the compile baseline: every fresh XLA
        # compile from here on is a NAMED, counted anomaly
        # (devmem.fresh_compiles_post_warmup -> recompile_after_warmup
        # alert), and the post-warmup HBM watermark is the crawl's floor
        obsdevmem.note_warmup_done()
        obsdevmem.sample(cs.obs, phase="warmup")
        return {"shapes": shapes, "ladder_hits": ladder_hits}

    def _warm_key(self, cs: CollectionSession, fb: int, L: int,
                  ot_path: str | None) -> tuple:
        """Everything that feeds the identity of the compiled programs a
        (bucket ``fb``, this session's key batch) crawl dispatches —
        the WarmLadder's skip key.  Two sessions with the same batch
        shape/config hit the same jit executables, so re-warming for
        the second is pure waste."""
        mesh_shards = 0 if cs._mesh is None else cs._mesh.shards
        return (
            "warm",
            cs.keys.cw_seed.shape,  # client batch + dims + depth
            fb,
            L,
            bool(self.cfg.secure_exchange),
            bool(self.cfg.secure_whole_level),
            str(ot_path or self.cfg.ot_path),
            mesh_shards,
            int(self.cfg.secure_kernel_shards),
            cs.planar(),
            # radix-2^k fusion: k changes every compiled shape downstream
            # of expand (packed bit layout, S' = 2·d·k equality width,
            # C = 2^(d·k) count columns, fused sketch advance)
            int(cs._radix),
            # malicious lane: the sketch verify ladder compiles its own
            # fused per-bucket programs, sharded by the sketch plan
            bool(cs._sketch_parts) or cs._sketch is not None,
            int(self.cfg.sketch_shards),
        )

    def _warm_bucket(self, cs: CollectionSession, fb: int, L: int,
                     ot_path: str | None = None) -> bool:
        """Compile (by running on throwaway inputs) every device program
        a crawl at frontier bucket ``fb`` will hit: expand with and
        without children, the trusted count reduction, and in secure
        mode the OT-extension + equality + b2a + share-sum chain for both
        FE62 (inner levels) and F255 (the leaf level).  Under the
        multi-chip mesh every stage warms with the SHARDED layout the
        live crawl dispatches.  Returns False when the process-level
        WarmLadder says some session already warmed this exact shape
        (the compiled programs are in the process jit cache — a new
        tenant on a warmed shape pays zero fresh compiles)."""
        ladder_key = self._warm_key(cs, fb, L, ot_path)
        if tenancy.warmed(ladder_key):
            return False
        mesh = cs._mesh
        fr = cs.init_frontier(fb)
        d = cs.keys.cw_seed.shape[1]
        # radix-2^k fusion: the live crawl dispatches at most two fused
        # shapes — (r = k, inner level, FE62, with children) and
        # (r = tail, leaf-bearing level, F255, no children), where the
        # tail radix is L - k·⌊(L-1)/k⌋ (== k when k divides L).  At
        # k = 1 this reduces exactly to the historical (False, True)
        # `lasts` ladder, via the radix==1 delegation in collect/secure.
        rdx = cs._radix
        base_last = rdx * ((L - 1) // rdx)
        steps = (
            [(min(rdx, L), True)]
            if base_last == 0
            else [(rdx, False), (L - base_last, True)]
        )
        for r, last in steps:
            level = base_last if last else 0
            packed, _ = cs.expand(fr, level, r, not last)
            if self.cfg.secure_exchange:
                N = cs.keys.cw_seed.shape[0]
                ks = (
                    mesh.kernel_bind(
                        fb * (1 << (d * r)) * N, 2 * d * r,
                        self.cfg.secure_kernel_shards,
                    )
                    if mesh is not None
                    else None
                )
                if ks is not None:
                    # the live crawl runs this shape ROW-SHARDED: warm
                    # the sharded flat/extension/kernel/open/psum chain
                    # (both roles, both garbling signs) — warming the
                    # gathered twins would leave every live program cold
                    secure.warm_level_kernels_sharded(
                        ks, packed, d, fb, N, F255 if last else FE62,
                        path=ot_path or self.cfg.ot_path, radix=r,
                    )
                    continue
                secure.warm_level_kernels(
                    # same pre-kernel gather as the live expand stage
                    # (_do_expand) — warm and live must dispatch the
                    # same single-device 2PC programs
                    packed if mesh is None else mesh.gather(packed),
                    d, F255 if last else FE62,
                    path=ot_path or self.cfg.ot_path,
                    share_sums=mesh.node_share_sums if mesh is not None
                    else None,
                    radix=r,
                    put=jax.device_put if mesh is None else mesh.gather,
                )
            else:
                masks = collect.pattern_masks_radix(d, r)
                alive = (
                    cs.alive_keys
                    if cs.alive_keys is not None
                    else np.ones(cs.keys.cw_seed.shape[0], bool)
                )
                if mesh is not None:
                    # peer rows arrive as host numpy on the live path
                    peer = np.asarray(packed)  # fhh-lint: disable=chunked-device-readback,host-sync-in-hot-loop (warmup only: deliberately mirrors the live wire round trip, off the measured clock)
                    jax.block_until_ready(
                        mesh.counts_by_pattern(
                            packed, peer, masks, alive, fr.alive
                        )
                    )
                else:
                    jax.block_until_ready(
                        collect.counts_by_pattern(
                            packed, packed, masks, alive, fr.alive
                        )
                    )
        if cs._sketch_parts or cs._sketch is not None:
            # malicious lane: compile the fused sketch-verify ladder at
            # this bucket rung (and, once per batch, the level-0
            # full-width check) so a warmed malicious crawl dispatches
            # zero fresh programs
            self._warm_sketch(cs, fb, L)
        tenancy.mark_warmed(ladder_key)
        return True

    def _warm_sketch(self, cs: CollectionSession, fb: int, L: int) -> None:
        """Compile every device program the malicious verify lane
        dispatches at frontier bucket ``fb``: the frontier-following
        advance (``advance_sketch``'s eval_bit + liveness gating at the
        bucket shape, both fields), the level-0 full-width check (root
        eval_bit at the batch shape + the m = 2 verify chain), and the
        fused sharded cor/out/verdict chain at m = ``fb`` for FE62
        (inner levels) and F255 (the leaf) — all on throwaway inputs,
        never the live sketch state, with the wire arrays
        round-tripping through host numpy exactly like the live path
        (sketch_shard.warm_verify)."""
        if cs._sketch is None:
            cs.concat_sketch()
        k = cs._sketch.key
        n, d = k.root_seed.shape[0], k.root_seed.shape[1]
        ss = self._sketch_bind(cs, n, d)
        idx = bool(self.server_id)
        # level-0 full-width check: root states + both children per dim
        root = dpf.eval_init(k)
        st0 = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (1,) + a.shape), root
        )
        last0 = L == 1
        fld0 = F255 if last0 else FE62
        cw0 = dpf.level_cw(k, 0)
        cwv0 = k.cw_val[..., 0, :] if not last0 else k.cw_val_last
        st_r = jax.tree.map(lambda a: a[0], st0)
        sides = [
            dpf.eval_bit(
                cw0, st_r, jnp.full((n, d), c), cwv0, k.key_idx, fld0,
                sketchmod.LANES,
            )[1]
            for c in (False, True)
        ]
        jax.block_until_ready(jnp.stack(sides))
        sketch_shard.warm_verify(ss, fld0, 2, n, d, idx)
        if L == 1:
            return  # data_len=1: the level-0 full check IS the leaf check
        # frontier advance + per-rung verify, both fields (FE62 inner
        # levels via tree_prune, F255 leaf via tree_prune_last)
        parent = jnp.zeros(fb, jnp.int32)
        direction = jnp.zeros((fb, 1, d), bool)
        for last in (False, True):
            fld = F255 if last else FE62
            lvl = L - 1 if last else 0
            cw = tuple(a[None] for a in dpf.level_cw(k, lvl))
            cwv = (k.cw_val_last if last else k.cw_val[..., lvl, :])[None]
            st = jax.tree.map(lambda a: a[parent], st0)
            _, pair = dpf.eval_bit(
                cw, st, direction, cwv, k.key_idx[None], fld,
                sketchmod.LANES,
            )
            gate = jnp.ones((fb, 1, d) + (1,) * (pair.ndim - 3), bool)
            jax.block_until_ready(jnp.where(gate, pair, 0))
            sketch_shard.warm_verify(ss, fld, fb, n, d, idx)

    # -- wiring ----------------------------------------------------------

    _VERBS = (
        "reset",
        "add_keys",
        "tree_init",
        "tree_crawl",
        "tree_crawl_last",
        "tree_prune",
        "tree_prune_last",
        "final_shares",
        "sketch_verify",  # the TreeSketchFrontier* verbs' live successor
        # streaming ingest front door (ROADMAP "Streaming ingestion")
        "submit_keys",
        "window_seal",
        "window_load",
        # resilience verbs (no reference analogue)
        "status",
        "tree_checkpoint",
        "tree_restore",
        "plane_reset",
        "plane_break",  # pipelined-crawl quiesce (unlocked dispatch)
        "warmup",  # per-f_bucket compile warmup (no protocol state)
        # fleet migration/failover (protocol/fleet.py)
        "session_export",
        "session_import",
    )

    # verbs that run under the SERVER infra lock instead of the calling
    # session's: the peer data plane is shared across sessions
    _SERVER_VERBS = ("plane_reset",)

    def _bind_session(self, req) -> _Session | None:  # fhh-race: atomic (serve-loop session table: create-or-attach + eviction never suspends; all connections share one event loop)
        """Create-or-attach the leader session named in a ``__hello__``.
        Sessions are bounded (oldest-idle evicted) so reconnecting leaders
        with fresh session ids cannot grow server memory without bound."""
        sid = (req or {}).get("session")
        if sid is None:
            return None
        sess = self._sessions.get(sid)
        if sess is None:
            while len(self._sessions) >= _SESSION_CAP:
                oldest = min(
                    self._sessions, key=lambda k: self._sessions[k].last_seen
                )
                del self._sessions[oldest]
            sess = self._sessions[sid] = _Session()
        epoch = int((req or {}).get("epoch", 0))
        if epoch > 1:  # epoch 1 is the first connect, not a recovery
            obs.emit(
                "resilience.session_reconnect",
                server=self.server_id,
                epoch=epoch,
            )
        sess.epoch = epoch
        sess.last_seen = time.monotonic()
        return sess

    async def _dispatch(self, sess: _Session | None,
                        cs: CollectionSession | None, req_id, verb, req):
        """Run one verb AT MOST ONCE per (session, req_id): replays of a
        finished verb answer from the bounded response cache; replays of a
        verb still executing await the same execution.  Errors are
        responses too — a deterministic rejection must replay as the same
        rejection, not as a second execution attempt.

        ``cs`` is the collection session the connection bound at hello
        (None = a legacy client that never said hello: the DEFAULT
        session).  Per-collection verbs serialize on the SESSION's verb
        lock — two collections' verbs interleave on the event loop,
        which is the whole multi-tenant point — while plane verbs take
        the server infra lock (the plane is shared)."""
        self.obs.count("verb_requests")  # denominator of the dedup rate
        if cs is None:
            with guards.unguarded(
                "serve-loop session bind: event-loop-atomic by the "
                "fhh-race atomic contract on SessionTable.get"
            ):
                cs = self._table.get()
        cs.last_used = time.monotonic()
        if sess is not None:
            sess.last_seen = time.monotonic()
            if req_id in sess.cache:
                self.obs.count("dedup_hits")
                obs.emit(
                    "resilience.replay",
                    severity="debug",
                    server=self.server_id,
                    verb=verb,
                    req_id=req_id,
                )
                sess.cache.move_to_end(req_id)
                return sess.cache[req_id]
            live = sess.inflight.get(req_id)
            if live is not None:
                self.obs.count("dedup_hits")
                return await asyncio.shield(live)
            done = sess.inflight[req_id] = (
                asyncio.get_event_loop().create_future()
            )
        # distributed trace context: the request dict carries the
        # leader's {"t": trace_id, "s": span_id} (stamped once per
        # CollectorClient.call and replayed VERBATIM with the req_id),
        # so every span the verb opens below records as a child of the
        # leader's call.  Replays never reach this point for an
        # already-executed verb (the dedup cache answered above), so a
        # (trace_id, span_id) records exactly once per execution.
        ttok = (
            obstrace.activate((req or {}).get("trace"))
            if obstrace.enabled() and isinstance(req, dict)
            else None
        )
        t_verb = time.monotonic()
        try:
            try:
                # verb span: arrival-to-response on the session's
                # registry — the trace parent of the phase spans inside,
                # and (via the rpc:{verb} histogram below) the per-verb
                # RPC-latency SLO.  An exception unwinding through it
                # (a severed data plane mid-exchange) marks the trace
                # span error=true instead of leaving it dangling.
                # status is exempt: its sessions rollup reads every
                # session's CURRENT span as "the phase", and a probe
                # must not report itself.
                span_ctx = (
                    contextlib.nullcontext() if verb == "status"
                    # fhh-lint: disable=span-discipline (bound to a name only so status can swap in a nullcontext; the `with` on the next line enters/exits it normally)
                    else cs.obs.span(f"verb:{verb}")
                )
                with span_ctx:
                    if verb in ("add_keys", "submit_keys", "plane_break"):
                        # add_keys: append-only, no awaits -> atomic;
                        # submit_keys MUST bypass the lock so ingest
                        # keeps flowing while a windowed crawl holds it
                        # (that concurrency is the whole point of the
                        # front door) — its one suspension (executor
                        # admission) re-validates before mutating.
                        # plane_break MUST bypass it too: it exists to
                        # break a verb wedged on the data plane while
                        # HOLDING the lock (pipelined quiesce) — behind
                        # the lock it could never run.
                        with guards.unguarded(
                            "unlocked fast-path verb: add_keys is "
                            "event-loop-atomic by its fhh-race contract; "
                            "submit_keys re-validates after its one "
                            "suspension (executor admission)"
                        ):
                            resp = await getattr(self, verb)(req, cs)
                    elif verb in self._SERVER_VERBS:
                        # shared-plane verbs serialize on the SERVER
                        # lock: two tenants' concurrent plane_resets
                        # must not interleave redials
                        async with self._verb_lock:
                            resp = await getattr(self, verb)(req, cs)
                    else:
                        # frame-arrival expand stage: overlap a sharded
                        # crawl's device work with the span currently
                        # holding the lock
                        with guards.unguarded(
                            "frame-arrival prefetch: event-loop-atomic "
                            "by the fhh-race atomic contract on "
                            "_maybe_pre_expand"
                        ):
                            self._maybe_pre_expand(cs, verb, req)
                        async with cs._verb_lock:
                            resp = await getattr(self, verb)(req, cs)
            # fhh-lint: disable=broad-except (RPC boundary: EVERY failure
            # mode must surface to the caller as an error response — a
            # narrowed list would hang the leader on the first unlisted one)
            except Exception as e:
                obs.emit(
                    "verb.error", severity="warn", server=self.server_id,
                    verb=verb, error=f"{type(e).__name__}: {e}",
                )
                resp = {"__error__": f"{type(e).__name__}: {e}"}
            except asyncio.CancelledError:
                # drain-path cancellation: release any replay waiting on
                # this execution, then propagate
                if sess is not None:
                    sess.inflight.pop(req_id, None)
                    if not done.done():
                        done.cancel()
                raise
        finally:
            obstrace.deactivate(ttok)
        # per-verb RPC latency histogram (SLO surface: status.slo +
        # the run report's slo.verbs) and the per-session heartbeat-gap
        # instrument: last_progress marks verb COMPLETION — a wedged
        # tenant keeps bumping last_used at frame arrival while
        # last_progress stalls, which is the visible signal.  status is
        # exempt from BOTH (like the verb span): a probe is not
        # progress — an operator polling a stalled tenant's collection
        # must not reset the very gap the probe exists to read — and
        # probe counts would flood the verbs latency table.
        if verb != "status":
            cs.obs.observe(f"rpc:{verb}", time.monotonic() - t_verb)
            cs.last_progress = time.monotonic()
            cs.obs.gauge("last_progress_ts", round(time.time(), 3))
        if sess is not None:
            sess.put(req_id, resp)
            sess.inflight.pop(req_id, None)
            if not done.done():
                done.set_result(resp)
        return resp

    async def _handle_leader(self, reader, writer):
        """Control-plane serve loop with request ids and concurrent
        handling (the reference's tarpc ids + buffer_unordered(100),
        server.rs:359-376): each frame is (req_id, verb, payload) and every
        request runs as its own task, so many in-flight add_keys batches
        deserialize and append while others are still on the wire.  Verbs
        that touch the data plane or mutate protocol state serialize on
        their session's verb lock; responses carry the id so completion
        order is free.

        A ``__hello__`` frame (sent by the reconnecting client on every
        connect) binds this connection to a leader session AND to a
        collection session (``collection`` field; absent = the default
        collection); all later verbs on the connection go through that
        session's replay dedup (:meth:`_dispatch`) and run against that
        collection's state.  A client that never says hello gets the
        legacy at-most-once-per-connection, default-collection
        behavior."""
        write_lock = asyncio.Lock()
        sess: _Session | None = None
        cs: CollectionSession | None = None
        self._ctl_writers.add(writer)

        async def respond(req_id, resp):
            try:
                async with write_lock:
                    await _send(
                        writer, (req_id, resp),
                        reg=self.obs, counter="control_bytes_sent",
                    )
            except (ConnectionResetError, BrokenPipeError):
                pass  # leader gone; the work itself must still have finished
            except RuntimeError:
                # asyncio raises RuntimeError for writes on a closing
                # transport — swallow only that case; anything else would
                # silently strand the leader awaiting this req_id
                if not writer.is_closing():
                    raise

        async def handle(req_id, verb, req):
            await respond(
                req_id, await self._dispatch(sess, cs, req_id, verb, req)
            )
            # the span log is block-buffered (obs/trace.py): every span
            # of an answered verb, its response's too, is on disk now
            obstrace.flush()

        tasks = set()
        try:
            while True:
                req_id, verb, req = await _recv(
                    reader, reg=self.obs, counter="control_bytes_recv"
                )
                if verb == "__hello__":
                    try:
                        with guards.unguarded(
                            "serve-loop session bind: event-loop-atomic by "
                            "the fhh-race atomic contracts on _bind_session "
                            "and SessionTable.get"
                        ):
                            sess = self._bind_session(req)
                            new_cs = self._table.get(
                                (req or {}).get("collection")
                            )
                            if new_cs is not cs:
                                # refcount the binding: a bound session
                                # is never idle-evicted (see
                                # CollectionSession.bound)
                                if cs is not None:
                                    cs.bound -= 1
                                new_cs.bound += 1
                            cs = new_cs
                            if sess is not None:
                                sess.collection = cs.key
                    except (ValueError, RuntimeError) as e:
                        # a refused collection (bad key, table at cap)
                        # answers the hello with an error instead of
                        # binding the connection to the wrong session
                        await respond(
                            req_id,
                            {"__error__": f"{type(e).__name__}: {e}"},
                        )
                        continue
                    await respond(
                        req_id,
                        {
                            "boot_id": self._boot_id,
                            "server_id": self.server_id,
                            "collection": cs.key,
                            # trace clock-offset handshake (see status)
                            "clock": round(time.time(), 6),
                        },
                    )
                    continue
                if verb not in self._VERBS:
                    raise ValueError(f"unknown verb {verb!r}")
                t = asyncio.create_task(handle(req_id, verb, req))
                tasks.add(t)
                t.add_done_callback(tasks.discard)
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            # Drain, don't cancel: a verb may be mid-_swap on the PERSISTENT
            # peer data plane — cancelling between its send and recv would
            # leave the peer's frame unread and desynchronize every later
            # exchange (the old sequential loop always finished the verb in
            # flight; concurrent handling must keep that guarantee), and a
            # verb may legitimately run for minutes (first-call device
            # compiles).  Cancel only on the one condition draining cannot
            # cover: the PEER connection itself is gone — then the data
            # plane is already lost and cancelling costs nothing.
            # A silently-dead peer (partition/power loss, no FIN/RST) is
            # surfaced by the data-plane socket's TCP keepalive (_keepalive,
            # ~2 min): the blocked recv then raises through the mux, the
            # verb task finishes on its own, and is_closing() turns true —
            # so this loop needs no wall-clock guess that could misfire on
            # a LIVE peer running legitimately long verbs.
            pending = set(tasks)
            deadline = time.monotonic() + 1800  # generous overall backstop
            while pending:
                done, pending = await asyncio.wait(pending, timeout=30)
                if done:  # progress: push the backstop out again
                    deadline = time.monotonic() + 1800
                if pending and (
                    self._peer is None
                    or self._peer.is_closing()
                    or time.monotonic() > deadline
                    # the backstop covers what keepalive cannot: a verb
                    # blocked while the peer data plane stays OPEN (e.g. a
                    # desynchronized _swap after a peer-side verb error) —
                    # 30 min without a single task completing is not a
                    # legitimate long verb, it is a wedged handler
                ):
                    for t in pending:
                        t.cancel()
                    break
            writer.close()
            self._ctl_writers.discard(writer)
            if cs is not None:
                cs.bound -= 1  # connection gone: release the binding

    async def aclose(self) -> None:
        """Tear the whole server down — listeners, leader connections,
        peer data plane.  In-memory protocol state is NOT cleared: this
        is process death as far as peers can observe (the chaos tests'
        kill primitive; a restart is a fresh :class:`CollectorServer`)."""
        srvs = [
            srv
            for srv in (getattr(self, "_rpc_srv", None), getattr(self, "_peer_srv", None))
            if srv is not None
        ]
        for srv in srvs:
            srv.close()  # stop accepting
        # close every accepted connection BEFORE waiting: wait_closed()
        # returns only once the listener's connections are gone, and a
        # half-closed peer plane (the other server hung up first) stays
        # open until its writer here closes
        for w in list(self._ctl_writers):
            if not w.is_closing():
                w.close()
        self._ctl_writers.clear()
        if self._peer is not None:
            self._peer.close()
        self._plane.close()
        self._waits.close()
        for srv in srvs:
            await srv.wait_closed()

    # TCP keepalive of a plane stream: idle seconds before the first
    # probe, seconds between probes, probes unanswered before the
    # stream is dead
    PLANE_KEEPALIVE = (60, 20, 3)

    @classmethod
    def _plane_silence_s(cls) -> float:
        """Seconds a silent peer keeps a plane stream (``_keepalive``):
        the bound of a wait on the data plane, and of a fetch stage's
        wait for its chunk (``_fetch_taken``)."""
        idle, interval, probes = cls.PLANE_KEEPALIVE
        return float(idle + interval * probes)

    @classmethod
    def _keepalive(cls, sock) -> None:
        """Aggressive-ish TCP keepalive on a stream of the persistent
        data plane so a SILENTLY dead peer (partition, power loss — no
        FIN/RST) surfaces as a connection error within ~2 minutes
        (``_plane_silence_s``) instead of hanging a blocked send or
        receive forever (kernels default to ~2 hours).  It is what
        bounds the streams' threads."""
        import socket

        sock.setsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE, 1)
        for opt, val in zip(
            ("TCP_KEEPIDLE", "TCP_KEEPINTVL", "TCP_KEEPCNT"),
            cls.PLANE_KEEPALIVE,
        ):
            if hasattr(socket, opt):
                sock.setsockopt(socket.IPPROTO_TCP, getattr(socket, opt), val)

    def _plane_annotation(self, name: str):
        """The streams' threads' profiler annotations under fhh-trace
        (``<server>:wire_write`` / ``wire_read`` / ``wire_unpickle``
        while they run, on the thread that runs them)."""
        return obstrace.annotate(self.obs.name, name) or _NO_CTX

    def _attach_plane(self, send_sock, recv_sock) -> None:
        """Bind a fresh pair of peer streams as THE plane: the old one
        closed (failing every session's blocked recv and queued send),
        keepalive on both sockets, a new mux epoch, the threads started
        (``wire.PlaneStreams``: received frames reach the mux on the
        loop, stamped; byte accounting happens in the mux's route hook,
        the channel being known only after unpickling).  Sessions re-key
        their channels lazily (``_ensure_session_plane`` compares
        ``cs.plane_epoch`` to the mux epoch)."""
        if self._peer is not None:
            self._peer.close()  # the replaced plane: nobody else will
        for sock in (send_sock, recv_sock):
            self._keepalive(sock)
        epoch = self._plane.attach()
        self._peer = wire.PlaneStreams(
            send_sock, recv_sock,
            on_frame=functools.partial(self._plane.route, epoch),
            on_lost=functools.partial(self._plane.lost, epoch),
            reg=self.obs, annotate=self._plane_annotation,
            name=self.obs.name,
        )

    async def _dial_peer(self) -> None:
        """Dial the peer data plane under the shared backoff policy (the
        reference's connect_with_retries_tcp, server.rs:235, upgraded from
        fixed sleeps to exponential backoff + full jitter): the peer's
        data port twice, a hello on each that says which direction it
        carries and which dial it belongs to, and the listener's answer
        once it holds both.  Per-session channel handshakes (coin flip +
        base-OT) run lazily over the fresh plane — see
        ``_ensure_session_plane``."""
        peer_host, peer_port = self._peer_addr

        async def dial():
            loop = asyncio.get_running_loop()
            token = _secrets.token_hex(8).encode()
            socks = []
            try:
                for direction in _PLANE_DIRECTIONS:
                    sock = await wire.open_plane_socket(
                        peer_host, peer_port, respolicy.DIAL_TIMEOUT_S
                    )
                    socks.append(sock)
                    hello = b" ".join((_PLANE_HELLO, direction, token))
                    await asyncio.wait_for(
                        loop.sock_sendall(sock, wire.hello_frame(hello)),
                        respolicy.DIAL_TIMEOUT_S,
                    )
                ok = await asyncio.wait_for(
                    wire.sock_recv_hello(socks[0]), respolicy.DIAL_TIMEOUT_S
                )
                if ok != b" ".join((_PLANE_HELLO, b"ok", token)):
                    raise ConnectionError(
                        f"peer answered the data-plane hello with {ok[:64]!r}"
                    )
            except BaseException:
                for sock in socks:
                    sock.close()
                raise
            return socks

        try:
            send_sock, recv_sock = await respolicy.retry_async(
                dial,
                respolicy.DIAL_POLICY,
                what=f"peer data plane {peer_host}:{peer_port}",
            )
        except respolicy.TRANSIENT_ERRORS as e:
            raise ConnectionError(
                f"peer data-plane unreachable at {peer_host}:{peer_port}: {e!r}"
            ) from e
        self._attach_plane(send_sock, recv_sock)

    async def start(self, host: str, port: int, peer_host: str, peer_port: int):
        """Bring up the data plane FIRST (like the reference: GC mesh before
        the RPC listener, server.rs:344-354), then serve the leader.  The
        per-session secure handshakes (base-OT etc.) run lazily when each
        collection first touches the plane."""
        self._peer_addr = (peer_host, peer_port)
        obs.emit("server.engines", server=self.server_id, **self.engine_tags())
        with self.obs.span("setup"):
            if self.server_id == 1:
                srv = await wire.start_socket_server(
                    self._on_peer, host, peer_port
                )
                self._peer_ready = asyncio.Event()
                self._peer_srv = srv
                # fhh-lint: disable=unbounded-await (startup barrier: a
                # listening server legitimately waits as long as it takes
                # its peer to come up; operators bound this externally)
                await self._peer_ready.wait()
            else:
                await self._dial_peer()
            self._rpc_srv = await wire.start_server(
                self._handle_leader, host, port
            )
        return self._rpc_srv

    async def _on_peer(self, conn) -> None:
        """One accepted connection of the data plane: its first frame
        must be the hello of a stream, ``hello direction token``.
        The second stream of a dial completes the plane, which is then
        attached and answered on the stream this server reads.  Anything
        else (a peer that opens the plane on one connection and starts
        with a data frame, a torn or late hello) is refused loudly and
        the connection closed: a plane is never met halfway."""
        try:
            hello = (await asyncio.wait_for(
                wire.sock_recv_hello(conn), respolicy.DIAL_TIMEOUT_S
            )).split(b" ")
            if not (
                len(hello) == 3 and hello[0] == _PLANE_HELLO
                and hello[1] in _PLANE_DIRECTIONS
            ):
                raise ConnectionError(
                    "the first frame of a data-plane connection is not a "
                    "stream's hello (a peer of the one-connection plane?)"
                )
        except BaseException as e:
            conn.close()
            if not isinstance(e, Exception):
                raise
            self.obs.count("plane_hellos_refused")
            obs.emit(
                "plane.hello_refused", severity="error",
                server=self.server_id, error=repr(e),
            )
            return
        _, direction, token = hello
        half = self._plane_half
        if half is None or half[0] != token:
            # a newer dial: what an abandoned one left half-open goes
            for stale in (half[1].values() if half is not None else ()):
                stale.close()
            half = self._plane_half = (token, {})
        half[1][direction] = conn
        if len(half[1]) < len(_PLANE_DIRECTIONS):
            return
        self._plane_half = None
        recv_sock, send_sock = (half[1][d] for d in _PLANE_DIRECTIONS)
        self._attach_plane(send_sock, recv_sock)
        # the answer: a few dozen bytes into an empty socket buffer
        # (the reader thread has this socket's other direction)
        recv_sock.sendall(
            wire.hello_frame(b" ".join((_PLANE_HELLO, b"ok", token)))
        )
        self._peer_ready.set()

    async def _ensure_session_plane(self, cs: CollectionSession) -> None:
        """Key this session's data-plane channel against the CURRENT
        transport: coin-flip the shared sketch-challenge seed and (in
        secure mode) run the base-OT handshakes — all over the session's
        OWN mux channel, so N sessions key up concurrently on one
        socket.  Runs once per (session, plane epoch): a plane reset
        bumps the mux epoch and every session lazily re-keys at its next
        data-plane verb.  Callers hold the session's verb lock, so the
        handshake can never interleave with the session's own exchanges;
        both servers reach this from the same leader verb
        (tree_init/tree_crawl*/sketch_verify via ``_both``), so the
        channel's FIFO carries matching handshake frames."""
        if cs.plane_epoch == self._plane.epoch and (
            cs._ot is not None or not self.cfg.secure_exchange
        ):
            return
        with cs.obs.span("plane_handshake"):
            # coin flip: each side contributes 16 random bytes; the XOR
            # is uniform if either is honest — and crucially NEVER a
            # public constant (a client that can predict the challenge r
            # can forge a passing sketch)
            mine = _secrets.token_bytes(16)
            theirs = await self._swap(cs, mine)
            cs._sketch_seed = np.frombuffer(
                bytes(a ^ b for a, b in zip(mine, theirs)), dtype="<u4"
            ).copy()
            taint_guard.register(
                "CollectionSession._sketch_seed", cs._sketch_seed
            )
            await self._setup_secure(cs)
        cs.plane_epoch = self._plane.epoch
        obs.emit(
            "plane.session_keyed",
            severity="debug",
            server=self.server_id,
            collection=cs.key,
            epoch=cs.plane_epoch,
        )

    async def _setup_secure(self, cs: CollectionSession) -> None:
        """One-time base-OT setup seeding this SESSION's IKNP extension
        (the ocelot session init of collect.rs:454-461 — ~128 host-side
        Chou-Orlandi OTs; all per-level OT volume then runs as device
        kernels).  TWO sessions, one per garbling direction, so the
        leader can alternate the garbler per level (the reference's
        ``gc_sender`` flip, rpc.rs:20-23, leader.rs:204-210) and
        garbling cost splits across the servers.  In session ``g``
        server ``g`` is the OT-extension sender and plays base-OT
        *receiver* with its secret ``s`` — the standard IKNP role flip
        (ops/otext.py).  Per collection: two tenants' OT streams are
        fully independent (independent secrets, independent cursors), so
        their 2PC transcripts are bit-identical to solo runs by
        construction."""
        if not self.cfg.secure_exchange:
            return
        for g in (0, 1):
            if self.server_id == g:  # extension sender <- base-OT receiver
                s_bits = otext.fresh_s_bits()
                a_msg = await self._dp_recv(cs)
                br = baseot.BaseOtReceiver(s_bits)
                await self._dp_send(cs, br.round1(a_msg))
                cs._ot_snd = otext.OtExtSender(s_bits, br.seeds())
            else:  # extension receiver <- base-OT sender
                bs = baseot.BaseOtSender()
                await self._dp_send(cs, bs.round1())
                r_msgs = await self._dp_recv(cs)
                s0, s1 = bs.seeds([baseot.decompress(m) for m in r_msgs])
                cs._ot_rcv = otext.OtExtReceiver(s0, s1)
        cs._ot = (cs._ot_snd, cs._ot_rcv)  # marker: secure plane live
        cs._sec_seed = np.frombuffer(
            _secrets.token_bytes(16), dtype="<u4"
        ).copy()
        taint_guard.register("CollectionSession._sec_seed", cs._sec_seed)


class ServerRestartedError(ConnectionError):
    """The reconnect handshake found a DIFFERENT server process (new boot
    id): in-memory protocol state is gone, so blind verb replay is not
    safe — the supervising leader must run the restore path (re-upload
    keys, ``tree_restore``) instead.  Subclasses ConnectionError so
    non-supervised callers still see it as a connection-shaped failure."""


class CollectorClient:
    """Leader-side RPC stub (the tarpc-generated client analogue), now
    RECONNECTING.

    The framing carries request ids, so any number of calls may be in
    flight on one connection; a reader task resolves futures by id
    (tarpc's pipelining model, leader.rs:340-364 drives 1000 in-flight
    addkey batches through it).

    Recovery semantics: the client owns a session id for its lifetime.
    Every (re)connect sends ``__hello__ {session, epoch}``; on transport
    loss mid-call, the call redials under ``dial_policy`` (one winner per
    epoch — concurrent failed calls piggyback on the same redial) and
    RESENDS its frame with the SAME req_id.  The server's per-session
    dedup cache answers replays idempotently, so a verb whose response
    was lost in flight is never double-applied.  Two ways out of the
    retry loop: the per-verb wall-clock budget (``VerbBudgets``)
    expires, or the hello discovers a new server boot id
    (:class:`ServerRestartedError` — replay would run against empty
    state)."""

    def __init__(
        self,
        host: str,
        port: int,
        reg: obsmetrics.Registry | None = None,
        *,
        dial_policy: respolicy.RetryPolicy | None = None,
        budgets: respolicy.VerbBudgets | None = None,
        collection: str | None = None,
    ):
        self._host, self._port = host, port
        # the collection session this client's connections bind to on
        # the server (multi-tenant wire keying; None/"" = the default
        # collection — every single-tenant flow unchanged)
        self.collection = collection or DEFAULT_COLLECTION
        self._r = self._w = None
        self._send_lock = asyncio.Lock()
        self._conn_lock = asyncio.Lock()
        self._flush_task: asyncio.Task | None = None
        self._pending: dict[int, asyncio.Future] = {}
        self._next_id = 0
        self._reader_task: asyncio.Task | None = None
        self._dead: ConnectionError | None = None
        self.session_id = _secrets.token_hex(8)
        self.epoch = 0  # successful connects; >1 means we have reconnected
        self.boot_id: str | None = None  # server identity from last hello
        # trace clock-handshake component tag ("server0"/"server1"),
        # learned from the hello's server_id
        self._clock_tag: str | None = None
        self.dial_policy = dial_policy or respolicy.DIAL_POLICY
        self.budgets = budgets or respolicy.VerbBudgets()
        # control-plane byte accounting lands on the leader process's
        # default registry unless the caller owns one
        self.obs = obsmetrics.default_registry() if reg is None else reg

    @classmethod
    async def connect(cls, host: str, port: int, **kw) -> "CollectorClient":
        c = cls(host, port, **kw)
        await c._ensure_connected(0)
        return c

    async def aclose(self) -> None:
        self._dead = ConnectionError("client closed")
        if self._reader_task is not None:
            self._reader_task.cancel()
        if self._w is not None and not self._w.is_closing():
            self._w.close()
        if self._flush_task is not None and not self._flush_task.done():
            self._flush_task.set_exception(self._dead)
        self._fail_pending(self._dead)
        obstrace.flush()  # this client's last ``call:*`` lines

    def _fail_pending(self, err: ConnectionError) -> None:
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(err)
        self._pending.clear()

    async def _ensure_connected(self, seen_epoch: int) -> None:
        """(Re)dial unless someone already did since ``seen_epoch`` (the
        epoch the caller last observed).  All concurrently-failed calls
        funnel here; the first through the lock redials, the rest find a
        fresh epoch and just resend."""
        if self._dead is not None:
            raise self._dead
        async with self._conn_lock:
            if (
                self.epoch > seen_epoch
                and self._w is not None
                and not self._w.is_closing()
            ):
                return  # a concurrent caller already reconnected

            async def dial():
                return await asyncio.wait_for(
                    wire.open_connection(self._host, self._port),
                    respolicy.DIAL_TIMEOUT_S,
                )

            try:
                r, w = await respolicy.retry_async(
                    dial,
                    self.dial_policy,
                    what=f"dial {self._host}:{self._port}",
                )
            except respolicy.TRANSIENT_ERRORS as e:
                # NOT permanent: a later call (e.g. the supervisor's next
                # recovery wave) may find the server back up and redial
                err = ConnectionError(
                    f"server {self._host}:{self._port} unreachable: {e!r}"
                )
                self._fail_pending(err)
                raise err from e
            if self._reader_task is not None:
                self._reader_task.cancel()
            if self._w is not None and not self._w.is_closing():
                # close the superseded transport: after a peer FIN (or a
                # reader death that left TCP up, e.g. a corrupt frame)
                # the old fd would otherwise sit in CLOSE_WAIT — and the
                # server would keep a zombie handler bound to it — for
                # the life of the process, one leak per reconnect
                self._w.close()
            # any future still pending belongs to the OLD transport: its
            # response can never arrive — fail it so its owner replays on
            # the fresh epoch instead of waiting out its whole budget
            self._fail_pending(
                ConnectionError("transport replaced by reconnect")
            )
            # a coalesced drain still waiting on the old transport can
            # never finish — fail it (ConnectionError = transient, so its
            # waiters replay on the fresh epoch) and start clean
            if self._flush_task is not None and not self._flush_task.done():
                self._flush_task.set_exception(
                    ConnectionError("transport replaced by reconnect")
                )
            self._flush_task = None
            self._r, self._w = r, w
            self.epoch += 1
            self._reader_task = asyncio.ensure_future(self._read_loop(r))
            # session handshake: bind this connection to our session (the
            # server arms replay dedup) and learn the server's boot id
            self._next_id += 1
            t_hello = time.time()
            hello = await self._roundtrip(
                self._next_id,
                "__hello__",
                {
                    "session": self.session_id,
                    "epoch": self.epoch,
                    "collection": self.collection,
                },
                respolicy.Deadline(self.budgets.budget("__hello__")),
            )
            self._note_clock(hello, t_hello)
            if isinstance(hello, dict) and "__error__" in hello:
                # the server refused the collection (bad key / session
                # table at cap): NOT transport-shaped — retrying the
                # dial cannot help, the caller must change its ask
                raise RuntimeError(
                    f"hello refused by {self._host}:{self._port}: "
                    f"{hello['__error__']}"
                )
            new_boot = hello.get("boot_id")
            old_boot, self.boot_id = self.boot_id, new_boot
            if self.epoch > 1:
                self.obs.count("reconnects")
                obs.emit(
                    "resilience.reconnect",
                    host=self._host,
                    port=self._port,
                    epoch=self.epoch,
                    restarted=bool(old_boot and old_boot != new_boot),
                )

    def _note_clock(self, resp, t_sent: float) -> None:
        """Trace clock-offset handshake: a hello/status response carries
        the server's wall clock — record the NTP-style midpoint offset
        (server_clock - leader_clock) so ``obs.trace merge`` can place
        both servers' spans on the leader's timeline.  No-op without
        tracing or when the response carries no clock."""
        if not obstrace.enabled() or not isinstance(resp, dict):
            return
        clock = resp.get("clock")
        if clock is None:
            return
        sid = resp.get("server_id")
        if sid is not None:
            self._clock_tag = f"server{sid}"
        if self._clock_tag is None:
            return
        t_recv = time.time()
        obstrace.note_clock(
            self._clock_tag,
            float(clock) - (t_sent + t_recv) / 2.0,
            t_recv - t_sent,
        )

    async def _roundtrip(self, req_id, verb, req, deadline: respolicy.Deadline):
        """One send + response wait on the CURRENT transport (no retry —
        :meth:`call` owns the retry loop, and owns the req_id: a REPLAY
        must reuse the original id or the server's dedup cache can never
        recognize it)."""
        if (
            self._w is None
            or self._w.is_closing()
            or self._reader_task is None
            or self._reader_task.done()
        ):
            # transport already known-dead: a write might still "succeed"
            # locally (FIN'd socket) and the dead reader would never
            # resolve the future — fail fast into the reconnect path
            # instead of waiting out the whole verb budget
            raise ConnectionError("transport down")
        fut = asyncio.get_event_loop().create_future()
        self._pending[req_id] = fut
        try:
            async with self._send_lock:
                await _send(
                    self._w, (req_id, verb, req or {}),
                    reg=self.obs, counter="control_bytes_sent", flush=False,
                )
            # coalesced drain: a burst of concurrent frames (the 256-deep
            # upload window, a pipelined level's span verbs) shares ONE
            # drain instead of one await per frame — backpressure is
            # still applied, once per burst
            await self._flush()
            return await deadline.wait_for(fut)
        finally:
            # send raised mid-write, the wait timed out, or the reader
            # failed the future: either way the response slot is dead —
            # drop it so _pending can't grow across failed calls
            self._pending.pop(req_id, None)

    async def _flush(self) -> None:
        """Shared, coalesced ``drain()``: every writer since the last
        flush awaits the SAME drain outcome (a future the drain helper
        resolves).  Shielded so one caller's cancellation (a verb
        deadline firing) cannot starve the other writers; a drain
        failure (dead transport) surfaces to every waiter as the same
        connection-shaped error the per-frame drain raised — and a
        reconnect fails the stale flush with ConnectionError (see
        ``_ensure_connected``), which the retry loop classifies as
        transient and replays through."""
        fut = self._flush_task
        if fut is None or fut.done():
            fut = self._flush_task = asyncio.get_event_loop().create_future()
            # no "never retrieved" GC noise if every waiter was cancelled
            fut.add_done_callback(
                lambda f: f.exception() if not f.cancelled() else None
            )

            async def do_drain(w=self._w, fut=fut):
                try:
                    await w.drain()
                except Exception as e:  # fhh-lint: disable=broad-except (outcome relay: every drain failure must reach the coalesced waiters, whatever its type)
                    if not fut.done():
                        fut.set_exception(e)
                else:
                    if not fut.done():
                        fut.set_result(None)

            asyncio.ensure_future(do_drain())
        await asyncio.shield(fut)

    async def _read_loop(self, reader):
        try:
            while True:
                req_id, resp = await _recv(
                    reader, reg=self.obs, counter="control_bytes_recv"
                )
                fut = self._pending.pop(req_id, None)
                if fut is not None and not fut.done():
                    fut.set_result(resp)
        except Exception as e:  # reader death fails every in-flight caller
            err = ConnectionError(f"connection lost: {e!r}")
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(err)
            self._pending.clear()
            if not isinstance(e, respolicy.TRANSIENT_ERRORS):
                # anything else is a BUG in this client, not a transport
                # death.  Emit it NOW — nothing awaits the reader task, so
                # a bare re-raise would sit unretrieved until GC — then
                # re-raise for any future consumer of the task result.
                obs.emit(
                    "client.reader_error", severity="error",
                    error=f"{type(e).__name__}: {e}",
                )
                raise

    async def call(self, verb: str, req=None):
        """At-most-once verb call with transparent replay: transient
        transport failures redial and resend the SAME req_id (the server
        dedups); the per-verb wall-clock budget bounds the whole affair —
        every redial, every replay, and the server's execution."""
        if self._dead is not None:
            raise self._dead
        deadline = self.budgets.deadline(verb)
        first_boot = self.boot_id
        payload = req or {}
        # distributed trace: stamp this call's span id into the request
        # ONCE — replays resend the identical {"t","s","p"} with the
        # req_id, so the server records the span exactly once per
        # execution (dedup by (trace_id, span_id), like req_ids).  The
        # payload is COPIED before stamping: callers share one req dict
        # across both servers' calls (RpcLeader._both), and each call
        # owns its own span.
        twire = obstrace.wire_ctx() if obstrace.enabled() else None
        if twire is not None:
            payload = dict(payload)
            payload["trace"] = twire[0]
        self._next_id += 1
        req_id = self._next_id  # ONE id for the call's lifetime: replays
        resp = None             # reuse it so the server can dedup them
        retried = False
        try:
            while True:
                seen_epoch = self.epoch
                try:
                    t_sent = time.time()
                    resp = await self._roundtrip(
                        req_id, verb, payload, deadline
                    )
                    if verb == "status" and not retried:
                        # periodic clock-offset refresh rides the probe.
                        # First-attempt responses only: a retried status
                        # may be answered from the replay-dedup cache,
                        # whose "clock" is the ORIGINAL execution's —
                        # pairing it with this attempt's send/recv
                        # instants would skew the offset by the whole
                        # reconnect backoff.
                        self._note_clock(resp, t_sent)
                    break
                except respolicy.TRANSIENT_ERRORS as e:
                    retried = True
                    if deadline.expired():
                        raise TimeoutError(
                            f"verb {verb!r} exceeded its "
                            f"{self.budgets.budget(verb):g}s budget "
                            f"(last error: {type(e).__name__}: {e})"
                        ) from e
                    obs.emit(
                        "resilience.call_retry",
                        severity="debug",
                        verb=verb,
                        epoch=seen_epoch,
                        error=f"{type(e).__name__}: {e}",
                    )
                    await self._ensure_connected(seen_epoch)
                    if first_boot is not None and self.boot_id != first_boot:
                        raise ServerRestartedError(
                            f"server {self._host}:{self._port} restarted "
                            f"while {verb!r} was in flight — state lost, "
                            "replay unsafe"
                        ) from e
        except BaseException:
            if twire is not None:
                # the call span closes error=true — a severed transport
                # or blown budget never leaves it dangling in the trace
                obstrace.call_event(verb, self.obs.name, twire[1], error=True)
            raise
        # a server-side failure travels as an __error__ RESPONSE: the
        # call span must close error=true too (filtering the merged
        # timeline by error has to surface server failures, not just
        # transport ones)
        server_err = isinstance(resp, dict) and "__error__" in resp
        if twire is not None:
            obstrace.call_event(
                verb, self.obs.name, twire[1], error=server_err
            )
        if server_err:
            raise RuntimeError(f"server error on {verb}: {resp['__error__']}")
        return resp

    def __getattr__(self, verb):
        if verb.startswith("_"):
            raise AttributeError(verb)

        async def _verb(req=None):
            return await self.call(verb, req)

        return _verb
