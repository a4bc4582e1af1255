"""Wire layer: the frame format and the socket endpoints under it.

One frame, on the control plane (leader↔server) and on the data plane
(server↔server) alike::

    <Q n>                                  length of the body, all that follows
    body:  <I k>                           count of out-of-band buffers
           <Q len(meta)> <Q len(buf_1)> .. <Q len(buf_k)>
           meta                            pickle protocol 5 stream
           buf_1 .. buf_k                  the buffers, raw

A contiguous buffer of at least :data:`OOB_MIN` bytes that pickle
protocol 5 offers out of band (a numpy array's memory) is never
serialised: :func:`encode` hands its ``memoryview`` to the transport,
which ``sendmsg``s it (one copy, into the kernel), and
:func:`read_body` reads it from the socket straight into the memory the
received array will own (one copy, out of the kernel).  Everything else
(envelopes, verbs, small or strided arrays) stays in ``meta``.  The
outer prefix is the only thing a forwarder (``resilience/chaos.py``)
needs; inner lengths that do not sum to it are a corrupt frame
(:class:`FrameError`).

The endpoints replace ``asyncio``'s streams because
``StreamReader.readexactly(n)`` cannot do the second half: it grows a
``bytearray`` 256 KiB a loop turn (pausing and resuming the transport
each time at the default 64 KiB limit), then copies the body out.
:class:`FrameReader` is an :class:`asyncio.BufferedProtocol`, chosen
over ``loop.sock_recv_into`` because it keeps the loop's transport, and
with it ``write`` / ``drain`` back-pressure, ``close`` and keepalive as
they were: ``get_buffer`` hands the transport the unfilled rest of the
buffer the consumer awaits, so each readiness event is one
``recv_into`` of all the kernel holds, with no pause and no second
copy.  :class:`FrameWriter` is the ``StreamWriter`` subset the package
uses, on the same transport.
"""

from __future__ import annotations

import asyncio
import collections
import pickle
import struct
import sys

import numpy as np

HDR = struct.Struct("<Q")
_NBUF = struct.Struct("<I")

# buffers under this size ride in the pickled metadata: below it a
# second iovec and a second allocation cost more than the copy saves
OOB_MIN = 64 << 10
# bytes that arrive while no read is pending land here (the next
# frame's header and the start of its body), at most this many before
# the transport is paused
_SPILL = 64 << 10


# New pages cost 1 ms a MB at their first touch on the chip's host (4 us
# a 4 KiB page: 33 and 67 ms for the secure level's two frames), and
# whether malloc hands out new pages or its own heap for a request
# between 128 KiB and 32 MiB depends on the process's history (its mmap
# and trim thresholds move: the trusted lane's "two speeds"; from 32 MiB
# up it always maps) (PERF.md section 6, PR 29).  Receive buffers from
# here up are therefore slabs that come back: see _Lease for why a slab
# is never handed out while anything can read it.  Below, a frame's
# buffer is new memory and too small for the difference to show.
_SLAB_MIN = 1 << 20
_SLAB_KEEP = 512 << 20  # bytes of released slabs kept, newest first
# PEP 688: a Python class can export a buffer, so that the consumers'
# own references say when it is released
_LEASES = sys.version_info >= (3, 12)
# released slabs (np.uint8 arrays), newest first; a release onto a full
# list drops the oldest
_free_slabs: collections.deque = collections.deque(maxlen=16)


class _Lease:
    """One frame's hold on a slab.  ``np.frombuffer(lease)`` and every
    array derived from it (``pickle.loads``' arrays, their views and
    slices, the read-only wrapper of a read-only source, the reference
    ``jax.device_put`` keeps until its copy is done, a transport queue
    that holds the array for a re-send) reach the memory THROUGH this
    object, so each holds a reference to it: ``__del__`` runs when the
    last of them is gone, and only then does the slab return to the
    free list.  Release is the interpreter's reference count reaching
    zero, never a guess about who is done."""

    __slots__ = ("_slab",)

    def __init__(self, slab: np.ndarray):
        self._slab = slab

    def __buffer__(self, flags) -> memoryview:
        return memoryview(self._slab)

    def __del__(self, _free=_free_slabs):
        # any thread may drop the last reference, at interpreter exit
        # too: one atomic append, the trimming is _recv_buffer's
        _free.appendleft(self._slab)


def _recv_buffer(size: int, reg=None) -> np.ndarray:
    """``size`` bytes for ONE frame's out-of-band buffer: memory that
    nothing else can read.  Either new (``np.empty``), or a released
    slab of exactly that size (see :class:`_Lease`).  ``reg`` (an
    ``obs.metrics.Registry``) counts a slab's bytes under
    ``wire_slab_new_bytes`` or ``wire_slab_reused_bytes``: new ones are
    what the receive then pays a first touch for."""
    if size < _SLAB_MIN or not _LEASES:
        return np.empty(size, dtype=np.uint8)
    slab, kept, others = None, 0, []
    while _free_slabs:  # newest first; popleft is atomic, so a slab has one taker
        cand = _free_slabs.popleft()
        if slab is None and cand.nbytes == size:
            slab = cand
        elif kept + cand.nbytes <= _SLAB_KEEP:
            kept += cand.nbytes
            others.append(cand)  # beyond the cap the oldest are dropped
    _free_slabs.extend(others)
    if reg is not None:
        reg.count(
            "wire_slab_new_bytes" if slab is None else "wire_slab_reused_bytes",
            size,
        )
    if slab is None:
        slab = np.empty(size, dtype=np.uint8)
    return np.frombuffer(_Lease(slab), dtype=np.uint8)


class FrameError(ConnectionError):
    """A frame whose inner lengths contradict its prefix: the stream
    cannot be re-synchronised, so it is transport loss (and, being a
    ConnectionError, classified transient like a torn frame)."""


def encode(obj) -> tuple[list, int, int]:
    """``obj`` as the pieces of one frame, never joined: ``(pieces,
    framed byte size, bytes out of band)``.  ``pieces[2:]`` are views
    of ``obj``'s own arrays, not copies: the transport's write queue
    holds them (and through them the arrays, alive) until the kernel
    has taken the bytes, which is when ``drain()`` returns
    (:meth:`FrameReader.connection_made`).  Whoever sent ``obj`` must
    not write to it before that.  No caller does: a sent array is a
    fresh device fetch or a slice of an immutable key batch."""
    bufs: list[memoryview] = []

    def in_band(pb: pickle.PickleBuffer) -> bool:
        raw = pb.raw()  # 1-D bytes; pickle offers contiguous buffers only
        if raw.nbytes < OOB_MIN:
            return True
        bufs.append(raw)
        return False

    meta = pickle.dumps(obj, protocol=5, buffer_callback=in_band)
    lens = [len(meta), *(b.nbytes for b in bufs)]
    oob = sum(lens) - len(meta)
    n = _NBUF.size + 8 * len(lens) + len(meta) + oob
    head = struct.pack(f"<QI{len(lens)}Q", n, len(bufs), *lens)
    return [head, meta, *bufs], HDR.size + n, oob


async def read_body(reader: "FrameReader", n: int,
                    reg=None) -> tuple[bytearray, list]:
    """The body of a frame whose prefix said ``n``: ``(meta, buffers)``
    for ``pickle.loads(meta, buffers=buffers)``; ``reg`` counts the
    slabs (:func:`_recv_buffer`).  Each buffer is
    obtained HERE, once, at its stated length, filled from the socket
    and handed to exactly one frame: the arrays ``pickle.loads`` builds
    are views of it, and nothing writes to it again while any of them,
    or anything made from them, is alive: a view or a pending
    ``jax.device_put`` of a queued frame can never see the next frame's
    bytes (a level's frames may sit several deep in a ``PlaneMux``
    channel while a device copy still reads the first).  Small buffers
    are new memory; the largest are slabs whose return is the last
    reference's death (:func:`_recv_buffer`)."""
    if n < _NBUF.size:
        raise FrameError(f"frame of {n} bytes holds no buffer count")
    # fhh-lint: disable=unbounded-await (part of a frame whose header has arrived; the callers' frame reads are unbounded by design, see rpc._recv)
    (k,) = _NBUF.unpack(await reader.readexactly(_NBUF.size))
    if _NBUF.size + 8 * (k + 1) > n:
        raise FrameError(f"frame of {n} bytes cannot hold {k} buffer lengths")
    # fhh-lint: disable=unbounded-await (as above)
    lens = struct.unpack(f"<{k + 1}Q", await reader.readexactly(8 * (k + 1)))
    if _NBUF.size + 8 * (k + 1) + sum(lens) != n:
        raise FrameError(
            f"frame lengths {lens} do not sum to its prefix {n}"
        )
    # fhh-lint: disable=unbounded-await (as above)
    meta = await reader.readexactly(lens[0])
    bufs = []
    for size in lens[1:]:
        buf = _recv_buffer(size, reg)
        # fhh-lint: disable=unbounded-await (as above)
        await reader.readinto(buf)
        bufs.append(buf)
    return meta, bufs


class FrameReader(asyncio.BufferedProtocol):
    """The receiving half of a connection, and its protocol.  One
    consumer at a time (a serve loop, a client's read loop, the mux
    pump) awaits :meth:`readinto` / :meth:`readexactly`; while it does,
    the transport ``recv_into``s the awaited buffer itself.  Also keeps
    the drain state a :class:`FrameWriter` on the same transport waits
    on."""

    def __init__(self, on_connect=None):
        self._loop = asyncio.get_running_loop()
        self._on_connect = on_connect
        self._task: asyncio.Task | None = None
        self._transport: asyncio.Transport | None = None
        self._spill = memoryview(bytearray(_SPILL))
        self._lo = self._hi = 0  # unread spill: [lo, hi)
        self._dst: memoryview | None = None  # the awaited buffer
        self._got = 0
        self._waiter: asyncio.Future | None = None
        self._eof = False
        self._exc: BaseException | None = None
        self._write_paused = False
        self._drain_waiters: list[asyncio.Future] = []
        self._closed = self._loop.create_future()
        # nobody may ever await wait_closed(): no "never retrieved" noise
        self._closed.add_done_callback(
            lambda f: f.exception() if not f.cancelled() else None
        )

    # -- protocol: connection ---------------------------------------------

    def connection_made(self, transport) -> None:
        self._transport = transport
        # the transport queues VIEWS of the sender's arrays: with both
        # water marks at 0 a drain() returns only once the queue is
        # empty, so "drained" means the kernel has every byte and no
        # view of the caller's memory is left behind
        transport.set_write_buffer_limits(high=0)
        if self._on_connect is None:
            return
        res = self._on_connect(self, FrameWriter(transport, self))
        if asyncio.iscoroutine(res):
            self._task = self._loop.create_task(res)
            self._task.add_done_callback(self._handler_done)

    def _handler_done(self, task: asyncio.Task) -> None:
        # as asyncio's own stream server does: a handler that died must
        # not leave its connection open, and its error must be seen
        if not task.cancelled() and task.exception() is None:
            return
        if not task.cancelled():
            self._loop.call_exception_handler({
                "message": "unhandled exception in connection handler",
                "exception": task.exception(),
                "transport": self._transport,
            })
        if self._transport is not None:
            self._transport.close()

    def connection_lost(self, exc) -> None:
        self._exc = exc or self._exc
        self._eof = True
        self._fail_read()
        for fut in (*self._drain_waiters, self._closed):
            if fut.done():
                continue
            if exc is None:
                fut.set_result(None)
            else:
                fut.set_exception(exc)
        self._transport = None
        self._task = None

    # -- protocol: receive --------------------------------------------------

    def get_buffer(self, sizehint: int) -> memoryview:
        if self._dst is not None:
            return self._dst[self._got:]
        return self._spill[self._hi:]  # never empty: full -> paused

    def buffer_updated(self, nbytes: int) -> None:
        if self._dst is not None:
            self._got += nbytes
            if self._got == len(self._dst):
                self._dst = None
                if not self._waiter.done():
                    self._waiter.set_result(None)
            return
        self._hi += nbytes
        if self._hi == _SPILL:
            self._transport.pause_reading()  # until a read empties the spill

    def eof_received(self) -> bool:
        self._eof = True
        self._fail_read()
        return True  # the write side stays open until the writer closes

    def _fail_read(self) -> None:
        if self._waiter is not None and not self._waiter.done():
            self._waiter.set_exception(self._read_error())

    def _read_error(self) -> BaseException:
        """What ends the read in flight (``_dst`` is set)."""
        if self._exc is not None:
            return self._exc
        return asyncio.IncompleteReadError(
            bytes(self._dst[: self._got]), len(self._dst)
        )

    async def readinto(self, buf) -> None:
        """Fill ``buf`` (writable, contiguous) exactly, from the spill
        first and then by the transport's own ``recv_into``.  Raises
        ``IncompleteReadError`` at EOF and the transport's error after a
        loss, as ``StreamReader.readexactly`` did.  A read cancelled
        after some of its bytes were taken has torn the stream: the
        connection is aborted rather than left one frame out of step."""
        view = memoryview(buf).cast("B")
        take = min(len(view), self._hi - self._lo)
        if take:
            view[:take] = self._spill[self._lo:self._lo + take]
            self._lo += take
        if self._lo == self._hi:
            self._lo = self._hi = 0
            if self._transport is not None:
                self._transport.resume_reading()  # idempotent
        if take == len(view):
            return
        if self._dst is not None:
            raise RuntimeError("FrameReader has one consumer at a time")
        self._dst, self._got = view, take
        if self._eof:
            err = self._read_error()
            self._dst = None
            raise err
        self._waiter = self._loop.create_future()
        try:
            await self._waiter
        except asyncio.CancelledError:
            if self._got and self._transport is not None:
                self._exc = FrameError("frame read cancelled midway")
                self._transport.abort()
            raise
        finally:
            self._dst = self._waiter = None

    async def readexactly(self, n: int) -> bytearray:
        """``n`` bytes of their own: headers and the pickled metadata."""
        out = bytearray(n)
        # fhh-lint: disable=unbounded-await (the primitive itself; every caller bounds or justifies its own frame read)
        await self.readinto(out)
        return out

    # -- protocol: flow control for the writer -----------------------------

    def pause_writing(self) -> None:
        self._write_paused = True

    def resume_writing(self) -> None:
        self._write_paused = False
        for w in self._drain_waiters:
            if not w.done():
                w.set_result(None)

    async def _drain(self) -> None:
        if self._exc is not None:
            raise self._exc
        if self._transport is None:
            raise ConnectionResetError("Connection lost")
        if not self._write_paused:
            return
        waiter = self._loop.create_future()
        self._drain_waiters.append(waiter)
        try:
            await waiter
        finally:
            self._drain_waiters.remove(waiter)


class FrameWriter:
    """The sending half: what the package used of ``StreamWriter``,
    over the transport :class:`FrameReader` reads from."""

    def __init__(self, transport: asyncio.Transport, proto: FrameReader):
        self.transport = transport
        self._proto = proto

    def __del__(self):
        # as StreamWriter does: a connection whose writer nobody holds
        # any more (a handler that returned without closing) is closed,
        # or its listener's wait_closed() would wait for it for ever
        if not self.transport.is_closing() and not self._proto._loop.is_closed():
            self.transport.close()

    def writelines(self, pieces) -> None:
        """Queue the pieces of a frame: the selector transport keeps
        them as ``memoryview``s and ``sendmsg``s them together.  On a
        closing connection it raises what ``drain()`` would (the
        transport's own ``writelines`` fails obscurely once the
        connection is lost, where its ``write`` drops the data)."""
        if self.transport.is_closing():
            raise ConnectionResetError("write to a closing connection")
        self.transport.writelines(pieces)

    async def drain(self) -> None:
        """Wait until the transport's queue is empty: every byte
        written so far is in the kernel (at once when it already is)."""
        if self.transport.is_closing():
            # let a pending connection_lost run, so that a write to a
            # dead peer raises here instead of buffering forever
            await asyncio.sleep(0)
        await self._proto._drain()

    def close(self) -> None:
        self.transport.close()

    def is_closing(self) -> bool:
        return self.transport.is_closing()

    async def wait_closed(self) -> None:
        # fhh-lint: disable=unbounded-await (resolved by connection_lost, which close() schedules)
        await asyncio.shield(self._proto._closed)

    def get_extra_info(self, name: str, default=None):
        return self.transport.get_extra_info(name, default)


async def open_connection(host: str, port: int) -> tuple[FrameReader, FrameWriter]:
    """``asyncio.open_connection`` with this module's endpoints."""
    loop = asyncio.get_running_loop()
    transport, proto = await loop.create_connection(FrameReader, host, port)
    return proto, FrameWriter(transport, proto)


async def start_server(on_connect, host: str, port: int) -> asyncio.Server:
    """``asyncio.start_server``: ``on_connect(reader, writer)`` runs as
    a task per accepted connection."""
    loop = asyncio.get_running_loop()
    return await loop.create_server(
        lambda: FrameReader(on_connect), host, port
    )
