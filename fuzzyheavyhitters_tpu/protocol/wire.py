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

The server↔server data plane does not use them.  Its bytes are most of a
secure level's, and a loop thread that copies them dispatches nothing
meanwhile: :class:`PlaneStreams` carries the plane on two one-way TCP
streams over blocking sockets, each written by a thread at its sender
and read by a thread at its receiver (:func:`open_plane_socket`,
:func:`start_socket_server`, :func:`hello_frame` and
:func:`sock_recv_hello` bring the sockets up and exchange the hellos
that say which direction each carries).  The frame is the same.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import pickle
import queue
import socket
import struct
import sys
import threading
import time
import weakref

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
_free_slabs: collections.deque = collections.deque(maxlen=16)  # fhh-guard: _free_slabs=_slab_lock
# One taker at a time: the data plane's reader threads (one a server,
# two in a process that holds both) and the loop's control-plane reads
# all take from the list, and a taker empties it while it scans.  A
# second taker that came meanwhile would see no slab and pay first
# touches for a new one.  Releases need no lock (_Lease.__del__).
_slab_lock = threading.Lock()


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

    # any thread may drop the last reference, at interpreter exit too,
    # and inside _recv_buffer's locked scan (a collection run by one of
    # its allocations): one atomic append and never the lock, the
    # trimming is _recv_buffer's
    # fhh-lint: disable=guarded-state-unlocked (a release is one atomic deque.appendleft; taking _slab_lock in a finalizer could deadlock its own thread inside _recv_buffer)
    def __del__(self, _free=_free_slabs):
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
    with _slab_lock:
        while _free_slabs:  # newest first
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


def _body_reads(n: int, reg=None):
    """The reads that take in the body of a frame whose prefix said
    ``n``, for whoever owns the socket: a generator that yields a
    count twice (that many bytes of their own, to be sent back in: the
    buffer count, then the lengths) and then ONE list of buffers to be
    filled exactly, in order (the pickled metadata and every
    out-of-band buffer: a blocking socket fills them with one scatter
    read), and returns ``(meta, buffers)`` for
    ``pickle.loads(meta, buffers=buffers)``; ``reg`` counts the slabs
    (:func:`_recv_buffer`).  Each out-of-band buffer is
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
    (k,) = _NBUF.unpack((yield _NBUF.size))
    if _NBUF.size + 8 * (k + 1) > n:
        raise FrameError(f"frame of {n} bytes cannot hold {k} buffer lengths")
    lens = struct.unpack(f"<{k + 1}Q", (yield 8 * (k + 1)))
    if _NBUF.size + 8 * (k + 1) + sum(lens) != n:
        raise FrameError(
            f"frame lengths {lens} do not sum to its prefix {n}"
        )
    meta = bytearray(lens[0])
    bufs = [_recv_buffer(size, reg) for size in lens[1:]]
    yield [meta, *bufs]
    return meta, bufs


async def read_body(reader: "FrameReader", n: int,
                    reg=None) -> tuple[bytearray, list]:
    """The body of a frame whose prefix said ``n``, off a
    :class:`FrameReader`: see :func:`_body_reads`."""
    reads, got = _body_reads(n, reg), None
    try:
        while True:
            want, got = reads.send(got), None
            if isinstance(want, int):
                # fhh-lint: disable=unbounded-await (part of a frame whose header has arrived; the callers' frame reads are unbounded by design, see rpc._recv)
                got = await reader.readexactly(want)
                continue
            # the metadata by readexactly, which a plain StreamReader
            # has too (it reads frames that carry no raw buffer)
            # fhh-lint: disable=unbounded-await (as above)
            want[0][:] = await reader.readexactly(len(want[0]))
            for buf in want[1:]:
                # fhh-lint: disable=unbounded-await (as above)
                await reader.readinto(buf)
    except StopIteration as done:
        return done.value


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


# -- the data plane's endpoints: blocking sockets, a thread a stream ----------

# a hello is a few words: a first frame that claims more is not one,
# and is refused before its body is read
_HELLO_MAX = 256
_IOV_MAX = 1024


async def open_plane_socket(host: str, port: int, timeout: float) -> socket.socket:
    """A connected TCP socket (non-blocking, no transport on it) for one
    stream of the data plane; each address ``host`` resolves to is given
    ``timeout`` seconds."""
    loop = asyncio.get_running_loop()
    infos = await loop.getaddrinfo(host, port, type=socket.SOCK_STREAM)
    err: BaseException = OSError(f"{host}:{port} resolves to no address")
    for family, kind, proto, _, addr in infos:
        sock = socket.socket(family, kind, proto)
        sock.setblocking(False)
        try:
            await asyncio.wait_for(loop.sock_connect(sock, addr), timeout)
            return sock
        except (OSError, asyncio.TimeoutError) as e:
            sock.close()
            err = e
        except BaseException:
            sock.close()
            raise
    raise err


def hello_frame(words: bytes) -> bytes:
    """The frame that opens a stream of the data plane, and its answer:
    the outer prefix (all a forwarder reads) over a few plain words,
    not a pickle: nothing of a connection is unpickled before it has
    said what it is."""
    return HDR.pack(len(words)) + words


async def sock_recv_hello(sock: socket.socket) -> bytes:
    """The words of the frame that opens a stream, off a non-blocking
    socket, from the loop (the streams' threads are not running yet),
    and not a byte past it: what follows is the reader thread's.  The
    caller bounds the wait."""
    loop = asyncio.get_running_loop()

    async def read(n: int) -> bytes:
        buf = memoryview(bytearray(n))
        got = 0
        while got < n:
            # fhh-lint: disable=unbounded-await (the caller's wait_for bounds the whole frame)
            k = await loop.sock_recv_into(sock, buf[got:])
            if not k:
                raise ConnectionResetError("stream closed inside its hello")
            got += k
        return bytes(buf)

    (n,) = HDR.unpack(await read(HDR.size))
    if n > _HELLO_MAX:
        raise FrameError(f"a first frame of {n} bytes is no data-plane hello")
    return await read(n)


class SocketServer:
    """A listening socket whose accepted connections go to
    ``on_connect(sock)`` as they are, non-blocking and with no
    transport: the data plane's listener.  ``close`` /
    ``wait_closed`` as ``asyncio.Server`` has them; handlers still
    running at ``close`` are cancelled."""

    def __init__(self, sock: socket.socket, on_connect):
        self._sock = sock
        self._on_connect = on_connect
        self._handlers: set[asyncio.Task] = set()
        self._task = asyncio.get_running_loop().create_task(self._accept())

    async def _accept(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            # fhh-lint: disable=unbounded-await (a listener waits for its next connection by design; close() cancels it)
            conn, _ = await loop.sock_accept(self._sock)
            t = loop.create_task(self._on_connect(conn))
            self._handlers.add(t)
            t.add_done_callback(self._handlers.discard)

    def close(self) -> None:
        for t in (self._task, *self._handlers):
            t.cancel()
        self._sock.close()

    async def wait_closed(self) -> None:
        await asyncio.gather(
            self._task, *self._handlers, return_exceptions=True
        )


async def start_socket_server(on_connect, host: str, port: int) -> SocketServer:
    sock = socket.create_server((host, port))
    sock.setblocking(False)
    return SocketServer(sock, on_connect)


def _advance(views: list, n: int) -> None:
    """Drop the first ``n`` bytes of ``views`` (what one ``sendmsg`` or
    ``recvmsg_into`` moved)."""
    while views and n >= len(views[0]):
        n -= len(views.pop(0))
    if n:
        views[0] = views[0][n:]


def _recv_exact(sock: socket.socket, *bufs) -> None:
    """Fill ``bufs``, in order, from a blocking socket: one scatter
    read for all of them where the kernel allows (``MSG_WAITALL``),
    the GIL released throughout.  A thread that has to take the GIL
    back from a busy loop thread after every call waits up to the
    interpreter's switch interval each time: few calls a frame."""
    views = [memoryview(b).cast("B") for b in bufs if len(b)]
    while views:
        got = sock.recvmsg_into(views[:_IOV_MAX], 0, socket.MSG_WAITALL)[0]
        if not got:
            raise ConnectionResetError("data-plane stream closed by the peer")
        _advance(views, got)


def _sock_read_body(sock: socket.socket, n: int, count, reg=None):
    """:func:`read_body` off a blocking socket; the body's first bytes,
    the buffer count, came with the prefix (``count``)."""
    reads = _body_reads(n, reg)
    next(reads)  # asks for the count
    got = count
    try:
        while True:
            want, got = reads.send(got), None
            if isinstance(want, int):
                want = [got := bytearray(want)]
            _recv_exact(sock, *want)
    except StopIteration as done:
        return done.value


def _send_all(sock: socket.socket, pieces) -> None:
    """Every byte of ``pieces`` into the kernel, from a blocking
    socket: ``sendmsg`` over the views, never joined, the GIL released
    for each call."""
    views = [memoryview(p).cast("B") for p in pieces if len(p)]
    while views:
        _advance(views, sock.sendmsg(views[:_IOV_MAX]))


class PlaneStreams:
    """One server's end of the server↔server data plane: two one-way
    TCP streams on blocking sockets, the one this server writes with a
    writer thread on it, the one it reads with a reader thread on it.
    The loop thread hands frames over (:meth:`send`, or
    :meth:`hand_over` for a sender that keeps a second frame behind the
    one on the socket) and takes frames
    back (``on_frame``) and never copies a byte of either; a socket is
    never shared by a writer and a reader of bulk (two directions on
    one socket fight for its lock, PERF.md section 6, PR 36).

    The pair is ONE plane: the loss of either stream, seen by its
    thread, closes both, and :meth:`close` (which ``shutdown``s the
    sockets: what wakes a thread blocked in ``sendmsg`` or
    ``recv_into``) fails every frame still queued.  Whoever closes or
    loses the plane, ``on_lost(err)`` is called once, on the loop.

    ``on_frame(nbytes, frame, stamps)`` is called on the loop for each
    frame received, in order; ``stamps`` are the reader thread's wall
    clock with the header read, the body held and the frame
    unpickled.  No span is opened on either thread: the loop turns
    stamps into spans, where the registries' span stacks live.
    ``annotate(name)`` gives the threads' profiler annotations
    (``wire_write`` / ``wire_read`` / ``wire_unpickle``)."""

    # frames with the writer thread at once, the one being written
    # included: a dead peer stalls the producers here instead of
    # growing the queue (the threads' own bound is TCP keepalive)
    SEND_DEPTH = 8

    def __init__(self, send_sock: socket.socket, recv_sock: socket.socket,
                 on_frame, on_lost, reg=None, annotate=None, name="plane"):
        self._loop = asyncio.get_running_loop()
        self._socks = (send_sock, recv_sock)
        for s in self._socks:
            s.setblocking(True)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._on_frame, self._on_lost = on_frame, on_lost
        self._reg = reg
        self._annotate = annotate or (lambda _name: contextlib.nullcontext())
        self._closed = False
        # bounded by _slots: a frame enters with a slot and gives it
        # back when the thread is done with it
        # fhh-lint: disable=unbounded-queue (SEND_DEPTH slots bound it; queue.Queue(maxsize) would block the loop thread instead of suspending the producer)
        self._sendq: queue.SimpleQueue = queue.SimpleQueue()
        self._slots = asyncio.Semaphore(self.SEND_DEPTH)
        self.waiting = 0  # frames handed over and not yet sent (loop thread's)
        # A thread blocked on its socket or its queue holds this object
        # weakly: a plane that was dropped unclosed (with its server,
        # its loop long gone) is collected, __del__ wakes the threads
        # and they end, instead of pinning server and sockets for ever.
        me = weakref.ref(self)
        self.threads = [
            threading.Thread(
                target=fn, args=(me, *args), name=f"{name}-plane-{what}",
                daemon=True,
            )
            for fn, args, what in (
                (self._write_loop, (send_sock, self._sendq), "write"),
                (self._read_loop, (recv_sock,), "read"),
            )
        ]
        for t in self.threads:
            t.start()

    # -- the loop thread's side ----------------------------------------------

    async def hand_over(self, pieces) -> tuple[asyncio.Future, float, int]:
        """The first half of :meth:`send`: take a slot, put one frame's
        pieces (:func:`encode`) on the writer thread's queue and return
        without waiting for the write.  Frames go out in the order of
        their hand-over, so a sender that hands over a second frame
        while the first is on the socket finds the stream busy again
        the moment the first ends.  Returns the future of ``(t_begin,
        t_end, t_seen)``, the thread's wall clock at the start and the
        end of this frame's send and the loop's when it heard of it (it
        raises ``ConnectionError`` where the plane is closed or lost
        first), the wall clock at the hand-over, and how many frames
        the thread then held, this one included (1: the stream was
        free).  The views of the caller's arrays live until that future
        is done.  Whoever gives the future up cancels it: the frame
        goes whole all the same."""
        # fhh-lint: disable=unbounded-await (a slot comes back when the thread is done with a frame, sent or failed: bounded by the socket's TCP keepalive and by close(), like the send itself)
        await self._slots.acquire()
        if self._closed:
            self._slots.release()
            raise ConnectionResetError("data plane closed")
        self.waiting += 1
        done = self._loop.create_future()
        t_put = time.time()
        self._sendq.put((pieces, done))
        return done, t_put, self.waiting

    async def send(self, pieces) -> tuple[float, float, float, int]:
        """:meth:`hand_over` and the wait in one: hand the frame to the
        writer thread and wait until the kernel has every byte, so that
        no view of the caller's arrays is left behind.  Returns the
        wall clock at the hand-over, at the start and at the end of the
        thread's send, and how many frames the thread then held, this
        one included.  Raises ``ConnectionError`` on a plane that is,
        or while it waits becomes, closed or lost."""
        done, t_put, held = await self.hand_over(pieces)
        pieces = None
        # fhh-lint: disable=unbounded-await (resolved by the writer thread for every frame it was handed, sent or failed; see hand_over)
        t_begin, t_end, _ = await done
        return t_put, t_begin, t_end, held

    def _sent(self, done: asyncio.Future, t_begin: float, t_end: float,
              err: BaseException | None) -> None:
        self.waiting -= 1
        self._slots.release()
        if done.done():  # its sender was cancelled: the frame went whole all the same
            return
        if err is None:
            done.set_result((t_begin, t_end, time.time()))
        else:
            done.set_exception(ConnectionResetError(f"data plane lost: {err!r}"))

    def _wake(self) -> None:
        """``shutdown`` is what ends a ``sendmsg`` or a ``recv_into``
        another thread is blocked in (``close`` alone does not), and
        the ``None`` what ends the writer's wait for a frame; the
        threads close the sockets as they end."""
        for s in self._socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass  # already closed by its thread
        self._sendq.put(None)

    def close(self, err: BaseException | None = None) -> None:
        """Close both streams (idempotent).  Every thread wakes, every
        queued frame fails with ``ConnectionError``, and ``on_lost``
        hears of it."""
        if self._closed:
            return
        self._closed = True
        self._wake()
        self._on_lost(err or ConnectionResetError("data plane closed"))

    def is_closing(self) -> bool:
        return self._closed

    def __del__(self):
        if not self._closed:
            self._wake()

    # -- the threads ------------------------------------------------------------

    def _post(self, fn, *args) -> bool:
        try:
            self._loop.call_soon_threadsafe(fn, *args)
        except RuntimeError:  # the loop is closed: nobody is left to tell
            return False
        return True

    @staticmethod
    def _write_loop(me, sock: socket.socket, sendq) -> None:
        err = None
        while True:
            # blocks without a bound of its own: _wake() ends the wait
            # with None (close(), or the plane collected unclosed)
            item = sendq.get()
            plane = me()
            if item is None or plane is None:
                break
            pieces, done = item
            t_begin = time.time()
            if err is None:
                try:
                    # blocks while the peer does not read: bounded by
                    # the socket's TCP keepalive (the server sets it,
                    # ~2 min for a silent peer) and ended by _wake()'s
                    # shutdown, which fails the call
                    with plane._annotate("wire_write"):
                        _send_all(sock, pieces)
                except OSError as e:
                    err = e
                    plane._post(plane.close, e)
            item = pieces = None  # the views of the sender's arrays end here
            if not plane._post(plane._sent, done, t_begin, time.time(), err):
                break
            plane = None
        sock.close()

    @staticmethod
    def _read_loop(me, sock: socket.socket) -> None:
        # the prefix and the buffer count, which every frame has, in
        # one read
        hdr = bytearray(HDR.size + _NBUF.size)
        plane = None
        try:
            while True:
                # blocks until the peer sends: a reader waits for its
                # next frame by design; bounded by TCP keepalive and
                # ended by _wake()'s shutdown, like the send
                _recv_exact(sock, hdr)
                t_hdr = time.time()
                plane = me()
                if plane is None:
                    return
                (n,) = HDR.unpack_from(hdr)
                with plane._annotate("wire_read"):
                    meta, bufs = _sock_read_body(
                        sock, n, hdr[HDR.size:], plane._reg
                    )
                t_body = time.time()
                with plane._annotate("wire_unpickle"):
                    frame = pickle.loads(meta, buffers=bufs)
                if not plane._post(
                    plane._on_frame, n + HDR.size, frame,
                    (t_hdr, t_body, time.time()),
                ):
                    return
                # the loop owns the frame now: a thread that kept it in
                # its locals until the NEXT frame arrives would pin the
                # receive buffers long after their consumer let go
                frame = meta = bufs = plane = None
        # fhh-lint: disable=broad-except (transport boundary: EOF, a reset, a corrupt frame or an unpicklable one all end the stream, and the plane with it)
        except Exception as e:
            plane = plane or me()
            if plane is not None:
                plane._post(plane.close, e)
        finally:
            sock.close()
